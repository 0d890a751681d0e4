"""BASELINE config 5 (stretch): per-layer Krum on the fully-sharded
transformer engine — steps/s of the dp x pp x tp (+sp/ep) jitted step.

The reference has no LLM path at all (SURVEY.md §5: no attention anywhere);
this measures the new capability: a MoE transformer trained under per-layer
robust aggregation (ShardedRobustEngine, granularity="layer"), every
parallelism axis live in one compiled step.

Single real chip cannot host w >= 4 workers x pipeline stages, so the
default measurement runs the virtual 8-device CPU mesh (w=4, pp=2) — the
honest label is in the JSON.  On a pod slice, pass --mesh w,pp,tp sized to
the hardware.

Usage::

    python benchmarks/sharded_transformer.py [--mesh 4,2,1] [--steps 10]
                                             [--d-model 128] [--layers 4]
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="4,2,1", help="workers,pipeline,tensor axes")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--d-model", type=int, default=128)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=4, help="per-worker batch")
    ap.add_argument("--gar", default="krum")
    ap.add_argument("--platform", default=None)
    args = ap.parse_args()

    w, pp, tp = (int(x) for x in args.mesh.split(","))
    nb_devices = w * pp * tp
    if args.platform:
        os.environ["JAX_PLATFORMS"] = args.platform
    import jax

    resolved = args.platform or os.environ.get("JAX_PLATFORMS", "").strip().lower()
    if resolved:
        # config-level pin: the env var alone is overridden by ambient
        # accelerator plugins (cli/runner.py does the same dance)
        jax.config.update("jax_platforms", resolved)
    if resolved == "cpu":
        # before any backend init (jax.devices() would lock the count)
        import re

        m = re.search(r"xla_force_host_platform_device_count=(\d+)",
                      os.environ.get("XLA_FLAGS", ""))
        if m is None or int(m.group(1)) < nb_devices:
            jax.config.update("jax_num_cpu_devices", nb_devices)

    import optax

    from aggregathor_tpu import gars
    from aggregathor_tpu.models import transformer as tfm
    from aggregathor_tpu.parallel.mesh import make_mesh
    from aggregathor_tpu.parallel.engine import RobustEngine

    mesh = make_mesh(nb_workers=w, model_parallelism=tp, pipeline_parallelism=pp)
    cfg = tfm.TransformerConfig(
        vocab_size=256, d_model=args.d_model, n_heads=max(2, args.d_model // 64),
        n_layers=args.layers * pp, n_experts=2 * tp,
    )
    f = max(0, (w - 3) // 2) if args.gar.startswith("krum") else max(0, (w - 1) // 3)
    engine = RobustEngine(mesh, gars.instantiate(args.gar, w, f),
                          granularity="layer", sharding="sharded")
    tx = optax.sgd(1e-2)
    state = engine.init_state(lambda k: tfm.init_params(cfg, k, n_stages=pp), tfm.param_specs(cfg), tx)
    step = engine.build_step(tfm.make_pipeline_loss(cfg, n_stages=pp, microbatches=2), tx, state)
    nb_params = sum(leaf.size for leaf in jax.tree_util.tree_leaves(state.params))

    rng = np.random.default_rng(0)
    batch = engine.shard_batch({
        "tokens": rng.integers(0, 256, size=(w, args.batch, args.seq)).astype(np.int32),
        "targets": rng.integers(0, 256, size=(w, args.batch, args.seq)).astype(np.int32),
    })
    # Timing ends on a host fetch of a value the whole step feeds.
    sync = lambda m: float(np.asarray(m["total_loss"]))
    t0 = time.perf_counter()
    state, metrics = step(state, batch)
    sync(metrics)
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(args.steps):
        state, metrics = step(state, batch)
    sync(metrics)
    steps_per_s = args.steps / (time.perf_counter() - t0)
    print(json.dumps({
        "metric": "sharded_transformer_steps_per_s",
        "config": "per_layer_%s_w%d_pp%d_tp%d" % (args.gar, w, pp, tp),
        "note": "BASELINE config 5 stretch: MoE transformer, per-layer robust GAR, dp/pp/tp/sp/ep",
        "platform": jax.devices()[0].platform,
        "nb_params": nb_params,
        "d_model": args.d_model, "layers": cfg.n_layers, "seq": args.seq,
        "per_worker_batch": args.batch,
        "value": round(steps_per_s, 3),
        "unit": "steps/s",
        "first_step_s": round(first, 2),
        "final_loss": float(np.asarray(metrics["total_loss"])),
    }))


if __name__ == "__main__":
    main()
