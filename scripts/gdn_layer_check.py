"""On-chip, element by element: ONE layer of each kind of models/qwen3_next.py
at the published widths and L = 4096 against the plain reference
(grid/references/qwen3_next.py): the chunked gated delta rule (on a TPU
ops/delta_rule.py's kernel pair, through the entry ``gated_delta_net`` calls)
against the recurrence walked token by token, the gated full layer (ops/attention.py's
kernel at heads of 256 lanes where ``attention_form`` takes them) against one
dense softmax.

The grid's ``correct`` holds losses and NORMS (grid/check.py).  This script
holds each layer's OUTPUT and the GRADIENTS of a seeded scalar of it
(``sum(out * w)``, w seeded) with respect to x and every leaf, each as the
largest difference over the reference's largest entry, beside its tolerance;
for the DeltaNet layer also the delta rule's core alone (``core``: o of the
entry ``delta_rule`` against the recurrence on the same q, k, v, g, beta).
Both at the precision the step runs (``default``: a float32 product multiplies
in one bfloat16 pass on the chip) and at ``highest`` (the program's own
equations in the reference's arithmetic: what is left is the order of float32
sums).  The expert layer's leaves are reported beside a loose tolerance only
(``grads_experts``): the router's top-k is discontinuous, and a position whose
experts differ by one moves a whole position in and out of an expert's sums.

``--fault`` plants a fault in the PROGRAM and has to FAIL: ``no-decay`` (g = 0:
a state never forgets), ``beta-one`` (beta = 1: every write replaces what the
key held), ``no-carry`` (every chunk entered with an empty state), ``no-gate``
(the full layer's output gate held open).

    python scripts/gdn_layer_check.py [--fault NAME[,NAME...]] [--seed N]

One JSON line per layer kind and precision (``gdn_layer_check {...}``); exit 0
iff every number is within its tolerance (with ``--fault``: iff under EVERY
fault named some number is NOT: each was seen), 2 without a TPU.  ``--tiny``
runs the same at a small size off the chip, which tests/test_qwen3_next.py
drives.  ``--readings ...`` hands the rest of the
command line to grid/readings.py with the fault planted: the cell's numbers
under a fault, beside its limits.
"""

import argparse
import contextlib
import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

#: Largest difference over the reference's largest entry, by precision, each
#: between the sound readings and the faults' (my chip runs, PR 49, two seeds,
#: PERF.md section 6; sound, DeltaNet layer | full layer, at the default: core
#: 6.4e-3 to 6.9e-3, out 1.1e-2 to 1.2e-2 | 1.5e-2 to 1.7e-2, grads_mixer 1.7e-2
#: to 1.8e-2 | 2.7e-2, grads_experts 0.31 to 0.35 | 0.27 to 0.33; at ``highest``:
#: core 1.0e-4 to 2.3e-4, out 9.6e-6 to 2.8e-5 | 1.7e-7 to 2.3e-7, grads_mixer
#: 1.9e-4 to 3.6e-4 | 3.4e-5 to 3.5e-5, grads_experts 1.5e-5 to 5.5e-5 | 8.3e-7 to
#: 9.9e-7; in the layer it breaks a fault reads, at either precision: ``no-decay``
#: core 2.35, out 0.45, grads_mixer 3.3; ``beta-one`` 1.14, 0.155, 1.0;
#: ``no-carry`` 0.98, 0.15, 0.85; ``no-gate`` out 0.42, grads_mixer 1.1).  ``core``
#: at ``highest`` is the order of float32 sums in a chunk's triangular system
#: against the recurrence's.  ``grads_experts`` is NOT held tight at the default:
#: the router's top-10 flips where two scores are near-tied and the program's
#: one-bfloat16-pass products decide the other way.
TOLERANCES = {
    "highest": {"core": 1e-3, "out": 1e-4, "grads_mixer": 2e-3, "grads_experts": 0.6},
    "default": {"core": 3e-2, "out": 5e-2, "grads_mixer": 0.1, "grads_experts": 0.6},
}
MIXER = {"delta": ("x", "attn_norm", "w_qkvz", "w_ba", "conv", "A_log", "dt_bias", "o_norm", "wo"),
         "full": ("x", "attn_norm", "wq", "wk", "wv", "q_norm", "k_norm", "wo")}
FAULTS = ("no-decay", "beta-one", "no-carry", "no-gate")


def load_reference():
    spec = importlib.util.spec_from_file_location(
        "gdn_layer_check_reference", os.path.join(ROOT, "grid", "references", "qwen3_next.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@contextlib.contextmanager
def planted(fault):
    """Break the PROGRAM for what is traced inside the block."""
    import jax.numpy as jnp

    from aggregathor_tpu.models import qwen3_next

    sound = {name: getattr(qwen3_next, name) for name in (
        "delta_heads", "delta_rule", "chunked_delta_rule", "attention_heads")}

    def no_decay(u, layer, cfg):
        q, k, v, z, g, beta = sound["delta_heads"](u, layer, cfg)
        return q, k, v, z, 0 * g, beta

    def beta_one(u, layer, cfg):
        q, k, v, z, g, beta = sound["delta_heads"](u, layer, cfg)
        return q, k, v, z, g, jnp.ones_like(beta)

    def no_carry(whole):
        """Every chunk a sequence of its own: nothing crosses a boundary,
        whichever form ``whole`` runs."""
        def broken(q, k, v, g, beta, chunk):
            b, length = q.shape[:2]
            alone = lambda a: a.reshape((b * length // chunk, chunk) + a.shape[2:])
            out, state = sound[whole](*(alone(a) for a in (q, k, v, g, beta)), chunk)
            return (out.reshape((b, length) + out.shape[2:]),
                    state[length // chunk - 1::length // chunk])
        return broken

    def no_gate(u, layer, cfg):
        q, k, v, gate = sound["attention_heads"](u, layer, cfg)
        return q, k, v, jnp.full_like(gate, 30.0)

    faults = {None: {}, "no-decay": {"delta_heads": no_decay}, "beta-one": {"delta_heads": beta_one},
              # the entry the layer calls, and the XLA form behind it off a TPU
              "no-carry": {name: no_carry(name) for name in ("delta_rule", "chunked_delta_rule")},
              "no-gate": {"attention_heads": no_gate}}
    if fault not in faults:
        raise SystemExit("no fault named %r: %s" % (fault, ", ".join(FAULTS)))
    for name, broken in faults[fault].items():
        setattr(qwen3_next, name, broken)
    try:
        yield
    finally:
        for name, whole in sound.items():
            setattr(qwen3_next, name, whole)


def run_check(seed=0, fault=None, tiny=False, emit=print):
    """The rows (one a layer kind a precision) and whether every number of
    every row is within its tolerance."""
    import jax
    import jax.numpy as jnp

    from aggregathor_tpu.models import qwen3_next
    from aggregathor_tpu.ops import attention

    reference = load_reference()
    with open(os.path.join(ROOT, "grid", "configs", "qwen3next-80b-a3b-ep64-n3.json")) as fd:
        shape = dict(json.load(fd)["image_size"], num_hidden_layers=2, full_attention_interval=2)
    cfg = qwen3_next.Qwen3NextConfig(layers=2, full_interval=2)
    if tiny:
        shape.update(sequence_length=64, hidden_size=64, num_attention_heads=4,
                     num_key_value_heads=2, head_dim=16, linear_num_key_heads=2,
                     linear_num_value_heads=4, linear_key_head_dim=16, linear_value_head_dim=16,
                     delta_chunk=16, num_experts=16, num_experts_per_tok=4,
                     moe_intermediate_size=24, shared_expert_intermediate_size=24,
                     experts_held=[0, 1, 2, 3])
        cfg = qwen3_next.Qwen3NextConfig(
            layers=2, full_interval=2, vocab=50, hidden=64, heads=4, kv_heads=2, head_dim=16,
            key_heads=2, value_heads=4, key_dim=16, value_dim=16, chunk=16, experts=16,
            experts_per_token=4, expert_width=24, shared_width=24, experts_held=(0, 1, 2, 3),
            seq=64, attn_chunk=16)
    length = cfg.seq
    key = jax.random.PRNGKey(seed)
    params = reference.init(jax.random.fold_in(key, 0), shape, 128)   # records the shape
    gain = 10.0 if tiny else 1.0   # at hidden 64 seeded scores are too small to tell keys apart
    x = jax.random.normal(jax.random.fold_in(key, 1), (1, length, cfg.hidden), jnp.float32)
    weight = jax.random.normal(jax.random.fold_in(key, 2), x.shape, jnp.float32)
    rows, sound = [], True
    gap = lambda a, b: float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
    for (kind, _), run in zip(cfg.runs(), params["layers"]):
        layer = {name: leaf[0] if name.endswith("norm") or name in ("A_log", "dt_bias")
                 else gain * leaf[0] for name, leaf in run.items()}

        def ours(x, layer, kind=kind):
            """(the seeded scalar of the layer's output, (the output, the delta rule's core))."""
            out = qwen3_next.decoder_layer(x, layer, cfg, kind)[0]
            core = None
            if kind == qwen3_next.DELTA:
                u = qwen3_next.rms_norm(x, 1 + layer["attn_norm"], cfg.norm_eps)
                q, k, v, _, g, beta = qwen3_next.delta_heads(u, layer, cfg)
                core = qwen3_next.delta_rule(q, k, v, g, beta, cfg.chunk)[0]
            return jnp.sum(out * weight), jax.lax.stop_gradient((out, core))

        def theirs(x, layer, kind=kind):
            out = reference._layer(x, layer, shape, kind)
            core = None
            if kind == "delta":  # the SOUND program's q, k, v, g, beta through the recurrence
                u = qwen3_next.rms_norm(x, 1 + layer["attn_norm"], cfg.norm_eps)
                q, k, v, _, g, beta = qwen3_next.delta_heads(u, layer, cfg)
                core = reference._recurrence(q, k, v, g, beta)
            return jnp.sum(out * weight), jax.lax.stop_gradient((out, core))

        both = lambda fn: jax.jit(jax.value_and_grad(fn, argnums=(0, 1), has_aux=True))
        with jax.default_matmul_precision("highest"):
            (_, (ref_out, ref_core)), ref_grads = both(theirs)(x, layer)
        for precision in ("default", "highest"):
            form = attention.forced_form("kernel") if tiny else contextlib.nullcontext()
            with jax.default_matmul_precision(precision), form, planted(fault):
                (_, (out, core)), grads = both(ours)(x, layer)
            by_leaf = {"x": gap(grads[0], ref_grads[0])}
            by_leaf.update({name: gap(grads[1][name], ref_grads[1][name]) for name in sorted(layer)})
            numbers = {
                "out": gap(out, ref_out),
                "grads_mixer": max(by_leaf[name] for name in MIXER[kind]),
                "grads_experts": max(value for name, value in by_leaf.items()
                                     if name not in MIXER[kind])}
            if kind == qwen3_next.DELTA:
                numbers["core"] = gap(core, ref_core)
            limits = TOLERANCES[precision]
            within = {name: bool(value <= limits[name]) for name, value in numbers.items()}
            rows.append({
                "metric": "gdn_layer_check", "kind": kind, "precision": precision, "fault": fault,
                "seed": seed, "length": length, **numbers, "tolerances": limits, "within": within,
                "grads_by_leaf": by_leaf, "device": jax.devices()[0].device_kind})
            emit("gdn_layer_check %s" % json.dumps(rows[-1]))
            sound = sound and all(within.values())
    return rows, sound


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--fault", default=None,
                        help="%s, or several by commas: each has to be seen" % " | ".join(FAULTS))
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--tiny", action="store_true", help="a small size, off the chip")
    parser.add_argument("--readings", nargs=argparse.REMAINDER, default=None,
                        help="grid/readings.py's arguments: the cell's numbers under the fault")
    args = parser.parse_args()
    if args.readings is not None:
        sys.path.insert(0, os.path.join(ROOT, "grid"))
        import readings

        sys.argv = [os.path.join(ROOT, "grid", "readings.py")] + args.readings
        with planted(args.fault):
            return readings.main()

    import jax

    from aggregathor_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    platform = jax.devices()[0].platform
    if platform != "tpu" and not args.tiny:
        print(json.dumps({"error": "gdn_layer_check requires a TPU backend, got %r" % platform}))
        sys.exit(2)
    if not args.fault:
        sys.exit(0 if run_check(args.seed, None, args.tiny)[1] else 1)
    seen = [not run_check(args.seed, fault, args.tiny)[1] for fault in args.fault.split(",")]
    sys.exit(0 if all(seen) else 1)


if __name__ == "__main__":
    main()
