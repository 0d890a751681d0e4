"""On-chip validation of the Pallas GAR kernel tier.

The Pallas kernels exist to replace the reference's C++ custom ops
(native/op_krum/cpu.cpp:53-122, native/op_bulyan/cpu.cpp:52-188), but the
CPU test suite exercises them only in interpreter mode
(ops/pallas_kernels.py interprets off-TPU).  This script is the missing
piece of evidence: it REQUIRES a TPU backend, runs every ``*-pallas`` rule
COMPILED (non-interpret), cross-checks each output against the jnp tier
on-device, and times both tiers with the one timing protocol of
``gars/scaling.time_aggregate`` (host clock, every repetition waited for).

Inputs include NaN-poisoned rows so the kernels' non-finite conventions
(+inf keying, lower-index ties, poison passthrough) are checked on the chip,
not just in the interpreter.

Usage::

    python scripts/pallas_tpu_check.py [--n 32] [--f 8] [--dims 65536,1048576]
                                       [--reps 10]

Prints one JSON line per (rule, d) with parity verdict + per-tier ms.
Exit code 0 iff every parity check passed; 2 when there is no TPU.
``run_check`` is the same thing as a callable (chip_smoke.py's leg C).

The attention column (``run_attention_check``; ``--columns gar,attention``):
ops/attention.py's fused kernel against the models' chunked XLA forms at the
two shapes of ``laguna_avgmedian_causal4k`` (24 query heads over 4, full; 32
over 4 under a window of 512; L = 4096, three workers vmapped as the engine
does; models/laguna.py) and at ``sdar30b_median_blockdiff``'s (32 over 4 under
the block-diffusion mask over [noisy ; clean] of 2,048 each, blocks of 4, four
workers; models/sdar.py) — output and the gradients of q, k and v at
``highest`` precision (what separates the two is then the order of float32
sums) and at the default (what the step runs), and each form alone, forward
and forward + backward, on a device-synchronised host clock, least of
``--attention-reps``; and at ``keye_avgmedian_sparse8k``'s (32 over 4 under a
mask that is DATA, each query's 2,048 keys of up to 8,192 from seeded scores,
L = 8192, three workers; models/keye_vl2.py: the row ``selected``).
``--attention-shapes block-diffusion`` keeps some of
the rows; ``--attention-tiles 128x256,256x512`` times the kernel alone at
other tiles, ``--attention-tiles sweep`` at the five of ``TILE_SWEEP``.

The select column (``run_select_check``; ``--columns select``): ops/select.py's
threshold by counting against models/keye_vl2.py's stable sort at
``keye_avgmedian_sparse8k``'s shape (three workers, a chunk of 512 queries over
8,192 scores, k = 2,048), the chunks numbered 0, 4 and 15 of the 16: seeded
scores with a run of 300 equal ones planted across the threshold, infinities
and a NaN, and for one worker a row set whose threshold falls among zeros of
both signs and denormals — ``array_equal`` of the two forms' int8 pairs (nothing
but time may differ) and each form's least ms of three.

The delta column (``run_delta_check``; ``--columns delta``): ops/delta_rule.py's
kernel pair against models/qwen3_next.py's ``chunked_delta_rule`` at
``qwen3next_avgmedian_causal4k``'s shape (three workers vmapped, 1 x 4,096 x 32
heads of 128 by 128, chunks of 64; q, k, v, g, beta seeded as ``delta_heads``
leaves them) — o, the last state and the gradients of q, k, v, g and beta, each
as the largest difference over the oracle's largest entry, at the default
precision and at ``highest``; beside each the XLA form's OWN difference from the
recurrence walked token by token (grid/references/qwen3_next.py, ``highest``,
the first worker), which is the bound: the kernel may stand no further from
the XLA form than the XLA form stands from the recurrence; and each form alone,
forward and forward + backward, least ms of ``--attention-reps``.

The operands column (``run_operands_check``; ``--columns operands``):
ops/gdn_operands.py's kernel pair against models/qwen3_next.py's ``split_heads``
at ``qwen3next_avgmedian_causal4k``'s shape (three workers vmapped, a projection
of 1 x 4,096 x 12,288 float32, 16 key heads and 32 value heads of 128 lanes, 4
taps shared by the workers) — q, k, v, z and the gradients of a seeded scalar of
all four to the projection and to the taps, each as the largest difference over
the oracle's largest entry, at ``highest`` and at the default precision (the
kernels hold no product: the two agree); and each form alone, least ms of
``--attention-reps``: the forward kernel and the backward kernel (the gradient
of a scalar of q, k, v needs no forward pass: the residuals are the arguments)
beside the XLA form's forward and forward + backward, the bytes the kernels
move (``fwd_bytes``, ``bwd_bytes``: blocks in with their halos, blocks out; dz
a zero the compiler makes) and the share of 819 GB/s that is
(``*_of_hbm_pct``; the backward's time holds the scalar's own passes too).

The leaf column (``run_leaf_check``; ``--columns leaf``): each plane kernel's
leaf entry (the step's in-place path, parallel/in_place.py) against its 2-D
entry at the grid's largest leaves — (4, 8, 768, 2048) x 4 workers, (2048,
18992) x 3, (4, 2048, 576) x 3 — NaN, both infinities and ties planted:
``differing`` 0 bit for bit, and each entry's least ms of five.
"""

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

RULES = ("average-nan", "median", "averaged-median", "krum", "bulyan", "trimmed-mean")


def _print_row(row):
    print(json.dumps(row), flush=True)


def run_check(n, f, dims, rules=RULES, reps=10, nan_workers=2,
              allow_interpret=False, emit=_print_row):
    """Parity (+ host-clock ms) of every ``rule``-pallas against the jnp tier
    at each ``d`` of ``dims``, then the four vmapped kernels at the two
    smallest; ``emit(row)`` per case.  Returns the rows that did not come out
    ``parity == "ok"`` — compile errors included, so a caller fails on any.

    Unless ``allow_interpret`` (the harness self-test off-TPU: timings
    meaningless, parity logic still exercised) the kernels must compile:
    raises when they would interpret."""
    import jax

    from aggregathor_tpu import gars
    from aggregathor_tpu.gars.common import forced_tier
    from aggregathor_tpu.gars.scaling import time_aggregate
    from aggregathor_tpu.ops import pallas_kernels as pk

    if not allow_interpret and pk._interpret():
        raise RuntimeError(
            "pallas_tpu_check needs a TPU backend (kernels would interpret on %r)"
            % jax.default_backend())

    rng = np.random.default_rng(7)
    failed = []

    def report(row):
        emit(row)
        if row["parity"] != "ok":
            failed.append(row)

    # Pin the plain rule names to the pure-jnp tier while this runs: the
    # base coordinate rules auto-dispatch to the Pallas kernels on TPU
    # (gars/common.py kernel_tier), which would turn the jnp column into a
    # second Pallas column.  The *-pallas registrations override
    # aggregate_block directly and ignore this.
    with forced_tier("jnp"):
        for d in dims:
            g_host = rng.normal(size=(n, d)).astype(np.float32)
            if nan_workers:
                # Scattered non-finite coordinates on the first k rows — the
                # UDP packet-loss shape the NaN conventions exist for
                # (reference mpi_rendezvous_mgr.patch:833-841).
                idx = rng.choice(d, size=max(8, d // 4096), replace=False)
                for w in range(nan_workers):
                    g_host[w, idx[w::nan_workers]] = np.nan
            g_dev = jax.device_put(g_host)

            for rule in rules:
                rule_f = min(f, (n - 3) // 4) if rule.startswith("bulyan") else f
                row = {"metric": "pallas_tpu_check", "rule": rule, "n": n,
                       "f": rule_f, "d": d}
                try:
                    jagg = jax.jit(gars.instantiate(rule, n, rule_f).aggregate)
                    pagg = jax.jit(gars.instantiate(rule + "-pallas", n, rule_f).aggregate)
                    out_p = np.asarray(pagg(g_dev))
                    out_j = np.asarray(jagg(g_dev))
                    # f32 pairwise distances over large d accumulate
                    # differently between the Gram-form kernel and the jnp
                    # diff form; parity is semantic (same selection, same
                    # coordinates) with a float-accumulation tolerance.
                    close = np.isclose(out_p, out_j, rtol=2e-3, atol=2e-4, equal_nan=True)
                    if not close.all():
                        row["mismatch_count"] = int((~close).sum())
                        diffs = np.abs(out_p[~close] - out_j[~close])
                        finite = diffs[np.isfinite(diffs)]
                        # All-NaN diffs (poison-passthrough divergence) must
                        # not leak a bare NaN token into the JSONL.
                        row["max_abs_diff"] = float(finite.max()) if finite.size else None
                        row["nonfinite_mismatches"] = int(diffs.size - finite.size)
                    row["parity"] = "ok" if close.all() else "FAIL"
                    row["pallas_ms"] = round(time_aggregate(lambda: pagg(g_dev), reps), 4)
                    row["jnp_tpu_ms"] = round(time_aggregate(lambda: jagg(g_dev), reps), 4)
                except Exception as exc:  # compile failure (VMEM/tiling) is a finding
                    row["parity"] = "ERROR"
                    row["error"] = "%s: %s" % (type(exc).__name__, str(exc)[:400])
                report(row)

        # Vmapped kernels: the per-leaf path calls the rules under
        # jax.vmap (engine._aggregate_per_leaf), which routes every
        # guarded kernel — coordinate median, averaged-median, trimmed-mean,
        # AND the streamed pairwise distances — through Pallas' batching
        # rule: interpret-mode in the CPU suite, compiled here.
        beta = max(1, n - f)
        keep = max(1, n - 2 * f)
        vmap_cases = [
            ("median-vmap4", pk.coordinate_median),
            ("averaged-median-vmap4", lambda x: pk.coordinate_averaged_median(x, beta)),
            ("trimmed-mean-vmap4",
             lambda x: pk.coordinate_trimmed_mean(x, (n - keep) // 2, keep)),
            ("pairwise-dist-vmap4", pk.pairwise_sq_distances),
        ]
        for d in sorted(dims)[:2]:  # smallest two: the proof, not a sweep
            stack_host = rng.normal(size=(4, n, d)).astype(np.float32)
            stack_host[0, 0, :: max(1, d // 64)] = np.nan
            stack = jax.device_put(stack_host)
            for name, kernel in vmap_cases:
                row = {"metric": "pallas_tpu_check", "rule": name, "n": n, "f": f, "d": d}
                try:
                    vm = jax.jit(jax.vmap(kernel))
                    out_v = np.asarray(vm(stack))
                    out_l = np.stack([np.asarray(kernel(stack[i]))
                                      for i in range(stack.shape[0])])
                    ok = bool(np.allclose(out_v, out_l, rtol=1e-6, atol=1e-6, equal_nan=True))
                    row["parity"] = "ok" if ok else "FAIL"
                    row["pallas_ms"] = round(time_aggregate(lambda: vm(stack), reps), 4)
                except Exception as exc:  # batching-rule lowering failure is a finding
                    row["parity"] = "ERROR"
                    row["error"] = "%s: %s" % (type(exc).__name__, str(exc)[:400])
                report(row)
    return failed


def _laguna_attention(window):
    """``(length, kv_heads, head_dim) -> (mask, the model's attention, (key
    heads, the scores' width, the values'), the pairs of a mask that is data or
    None)``."""
    def make(length, kv_heads, head_dim):
        from aggregathor_tpu.models import laguna
        from aggregathor_tpu.ops import attention

        cfg = laguna.LagunaConfig(seq=length, head_dim=head_dim, kv_heads=kv_heads,
                                  attn_chunk=min(length, laguna.LagunaConfig.attn_chunk))
        return (attention.Causal(window),
                lambda q, k, v: laguna.causal_attention(q, k, v, cfg, window),
                (kv_heads, head_dim, head_dim), None)
    return make


def _sdar_attention(block):
    def make(length, kv_heads, head_dim):
        from aggregathor_tpu.models import sdar

        half = length // 2
        cfg = sdar.SdarConfig(seq=half, block=block, head_dim=head_dim, kv_heads=kv_heads,
                              attn_chunk=min(half, sdar.SdarConfig.attn_chunk))
        return (sdar.BlockDiffusion(half, block),
                lambda q, k, v: sdar.masked_attention(q, k, v, cfg),
                (kv_heads, head_dim, head_dim), None)
    return make


def _latent_attention(length, kv_heads, head_dim):
    """models/deepseek_v3.py's: every head its own key head (four times the
    others' ``kv_heads``: 16), scores half again as wide as the values (192
    over 128), each of which the kernel carries at its own width."""
    from aggregathor_tpu.models import deepseek_v3, laguna
    from aggregathor_tpu.ops import attention

    cfg = deepseek_v3.DeepseekV3Config(seq=length, attn_chunk=min(length, 256))
    return (attention.Causal(),
            lambda q, k, v: attention.attend(
                q, k, v, attention.Causal(),
                lambda q, k, v: laguna.chunked_attention(q, k, v, cfg, None)),
            (4 * kv_heads, head_dim * 3 // 2, head_dim), None)


def _selected_attention(length, kv_heads, head_dim):
    """models/keye_vl2.py's: a mask that is DATA.  Each query reads the
    ``length // 4`` keys up to its own of largest seeded score (2,048 of up to
    8,192 at the cell's length), one selection for every head and, here, for
    every worker; the pairs are the kernel's operand and what the model's
    chunked XLA form masks by."""
    import jax
    import jax.numpy as jnp

    from aggregathor_tpu.models import keye_vl2
    from aggregathor_tpu.ops import attention

    cfg = keye_vl2.KeyeVL2Config(seq=length, index_topk=length // 4, head_dim=head_dim,
                                 kv_heads=kv_heads, attn_chunk=min(length, 256))
    chunk = min(length, 512)
    scores = jax.random.normal(jax.random.PRNGKey(13), (length // chunk, 1, chunk, length))
    pairs = jax.lax.map(lambda numbered: keye_vl2.top_keys(
        numbered[1], numbered[0] * chunk + jnp.arange(chunk), cfg.index_topk).astype(jnp.int8),
        (jnp.arange(length // chunk), scores)).swapaxes(0, 1).reshape(1, length, length)
    mask = attention.Selected(cfg.index_topk)
    return (mask, lambda q, k, v: attention.attend(
        q, k, v, mask, lambda q, k, v: keye_vl2.chunked_attention(q, k, v, pairs, cfg),
        pairs=pairs), (kv_heads, head_dim, head_dim), pairs)


#: (name, workers, query heads a kv head, the model's mask and attention, the
#: row's length in ``run_attention_check``'s) of the grid's Laguna cell — layers 0 and 4,
#: layers 1-3 (grid/configs/laguna-xs2-ep32-n3.json) — of its SDAR cell
#: (grid/configs/sdar-30b-a3b-ep16-n4.json), of its Kanana cell
#: (grid/configs/kanana2-30b-a3b-ep16-n3.json: G 16, R 1, 192 / 128) and of
#: its Keye cell (grid/configs/keye-vl2-30b-a3b-ep16-n3.json: the mask is
#: data, L = 8192, twice the others').
ATTENTION_SHAPES = (("full", 3, 6, _laguna_attention(None), 1),
                    ("window", 3, 8, _laguna_attention(512), 1),
                    ("block-diffusion", 4, 8, _sdar_attention(4), 1),
                    ("latent", 3, 1, _latent_attention, 1),
                    ("selected", 3, 8, _selected_attention, 2))

#: ``--attention-tiles sweep``: queries x keys a tile
TILE_SWEEP = ((128, 256), (256, 256), (512, 256), (256, 512), (512, 512))


def _least_ms(fn, reps):
    """Least wall ms of ``fn()`` over ``reps`` calls, each waited for."""
    import time

    import jax

    jax.block_until_ready(fn())
    times = []
    for _ in range(max(1, reps)):
        begin = time.perf_counter()
        jax.block_until_ready(fn())
        times.append(time.perf_counter() - begin)
    return min(times) * 1e3


def run_attention_check(reps=5, tiles=(), length=4096, kv_heads=4, head_dim=128,
                        shapes=ATTENTION_SHAPES, allow_interpret=False, emit=_print_row):
    """Parity and time of the fused attention kernel against the model's
    chunked XLA form at each of ``shapes``; ``emit(row)`` per shape, then per
    (shape, tile) of ``tiles``.  Returns the rows whose parity is not
    ``"ok"``."""
    import jax
    import jax.numpy as jnp

    from aggregathor_tpu.ops import attention

    if not allow_interpret and attention._interpret():
        raise RuntimeError("the attention column needs a TPU backend (the kernel would "
                           "interpret on %r)" % jax.default_backend())
    failed = []
    given = length
    for name, workers, rep, make, longer in shapes:
        length = given * longer
        mask, model_attention, (key_heads, qk_dim, v_dim), pairs = make(
            length, kv_heads, head_dim)
        key = jax.random.PRNGKey(11)
        normal = lambda place, *dims: jax.random.normal(
            jax.random.fold_in(key, place), (workers, 1, length) + dims, jnp.float32)
        q, k, v = normal(0, key_heads, rep, qk_dim), normal(1, key_heads, qk_dim), normal(
            2, key_heads, v_dim)
        weight = normal(3, key_heads * rep * v_dim)

        def forms(attend):
            forward = jax.vmap(attend)
            loss = lambda q, k, v: jnp.sum(forward(q, k, v) * weight)
            return jax.jit(forward), jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))

        def traced(form, precision):
            """(forward, loss-and-gradients) of ``form``, traced inside the seam."""
            def enter(fn):
                def call(*args):
                    with attention.forced_form(form), jax.default_matmul_precision(precision):
                        return fn(*args)
                return call
            return [enter(fn) for fn in forms(model_attention)]

        q_tile, k_tile = attention.tiles_for(length, rep)
        row = {"metric": "pallas_tpu_check", "rule": "attention-" + name, "workers": workers,
               "length": length, "heads": "%d/%d" % (key_heads * rep, key_heads),
               "widths": "%d/%d" % (qk_dim, v_dim), "mask": repr(mask),
               "tiles": "%dx%d" % (q_tile, k_tile),
               **attention.table_counts(attention.tile_table(mask, length, q_tile, k_tile))}
        try:
            worst, exact = {}, None
            for precision, bound in (("highest", 1e-4), ("default", 3e-2)):
                ours = traced("kernel", precision)
                theirs = traced("xla", precision)
                out_k, out_x = ours[0](q, k, v), theirs[0](q, k, v)
                (_, grads_k), (_, grads_x) = ours[1](q, k, v), theirs[1](q, k, v)
                gap = lambda a, b: float("%.3g" % (jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b))))
                gaps = [gap(a, b) for a, b in zip((out_k,) + tuple(grads_k),
                                                  (out_x,) + tuple(grads_x))]
                finite = all(bool(jnp.all(jnp.isfinite(a))) for a in (out_k,) + tuple(grads_k))
                row["gap_" + precision] = gaps
                worst[precision] = finite and max(gaps) <= bound
                suffix = "_ms" if precision == "default" else "_highest_ms"
                row["kernel_fwd" + suffix] = round(_least_ms(lambda: ours[0](q, k, v), reps), 4)
                row["kernel_fwd_bwd" + suffix] = round(_least_ms(lambda: ours[1](q, k, v), reps), 4)
                if precision == "highest":
                    exact = out_k
                else:
                    # how the chip multiplies the kernel's float32 operands at the default:
                    # ~1e-6 from ``highest`` in several passes, ~3e-3 in one bfloat16 pass
                    row["kernel_default_from_highest"] = gap(out_k, exact)
                    row["xla_fwd_ms"] = round(_least_ms(lambda: theirs[0](q, k, v), reps), 4)
                    row["xla_fwd_bwd_ms"] = round(_least_ms(lambda: theirs[1](q, k, v), reps), 4)
            row["parity"] = "ok" if all(worst.values()) else "FAIL"
        except Exception as exc:  # a kernel the compiler refuses is a finding
            row["parity"] = "ERROR"
            row["error"] = "%s: %s" % (type(exc).__name__, str(exc)[:400])
        emit(row)
        if row["parity"] != "ok":
            failed.append(row)
        for q_tile, k_tile in tiles:
            row = {"metric": "pallas_tpu_check", "rule": "attention-%s-tiles" % name,
                   "tiles": "%dx%d" % (q_tile, k_tile),
                   **attention.table_counts(attention.tile_table(mask, length, q_tile, k_tile))}
            try:
                forward, gradients = forms(lambda q, k, v: attention.fused_attention(
                    q, k, v, mask, q_tile, k_tile, pairs=pairs))
                row["kernel_fwd_ms"] = round(_least_ms(lambda: forward(q, k, v), reps), 4)
                row["kernel_fwd_bwd_ms"] = round(_least_ms(lambda: gradients(q, k, v), reps), 4)
            except Exception as exc:
                row["error"] = "%s: %s" % (type(exc).__name__, str(exc)[:400])
            emit(row)
    return failed


def run_select_check(reps=3, workers=3, chunk=512, length=8192, topk=2048, numbers=(0, 4, 15),
                     allow_interpret=False, emit=_print_row):
    """``array_equal`` and time of ops/select.py's kernel against
    models/keye_vl2.py's sort, one row a chunk of ``numbers``; ``emit(row)``
    each.  Returns the rows whose pairs differ."""
    import jax
    import jax.numpy as jnp

    from aggregathor_tpu.models import keye_vl2
    from aggregathor_tpu.ops import select

    if not allow_interpret and select._interpret():
        raise RuntimeError("the select column needs a TPU backend (the kernel would "
                           "interpret on %r)" % jax.default_backend())

    def traced(form):
        def pairs(scores, number):
            with select.forced_form(form):
                q_pos = number * chunk + jnp.arange(chunk)
                return jax.vmap(lambda scores: keye_vl2.top_keys(
                    scores, q_pos, topk).astype(jnp.int8))(scores)
        return jax.jit(pairs)

    ours, theirs, failed = traced("kernel"), traced("xla"), []
    for number in numbers:
        scores = np.array(jax.random.normal(
            jax.random.PRNGKey(17 + number), (workers, 1, chunk, length), jnp.float32))
        middle = number * chunk + chunk // 2
        if middle >= topk:      # the topk-th largest causal score of one query: ties planted there
            scores[..., 64:364] = np.sort(scores[0, 0, chunk // 2, :middle + 1])[-topk]
        scores[..., 7::1031] = np.inf
        scores[..., 11::1033] = -np.inf
        scores[0, 0, ::5, 13] = np.nan
        # a worker whose threshold falls among zeros of both signs and denormals
        edge = np.resize(np.float32([0.0, -0.0, 1e-40, -1e-40, 2e-40]), length)
        scores[-1, 0, :, :] = np.where(np.arange(length) % 3 == 0, edge, -1.0 - np.abs(scores[-1, 0]))
        scores, number = jnp.asarray(scores), jnp.int32(number)
        row = {"metric": "pallas_tpu_check", "rule": "select", "workers": workers, "chunk": chunk,
               "length": length, "topk": topk, "number": int(number),
               "tile_rows": select.tile_rows(chunk, length), "passes": list(select.passes(length))}
        try:
            pairs_k, pairs_x = ours(scores, number), theirs(scores, number)
            row["selected"] = int(jnp.sum(pairs_k.astype(jnp.int32)))
            row["differing"] = int(jnp.sum((pairs_k != pairs_x).astype(jnp.int32)))
            row["kernel_ms"] = round(_least_ms(lambda: ours(scores, number), reps), 4)
            row["sort_ms"] = round(_least_ms(lambda: theirs(scores, number), reps), 4)
            row["parity"] = "ok" if row["differing"] == 0 and pairs_k.dtype == jnp.int8 else "FAIL"
        except Exception as exc:  # a kernel the compiler refuses is a finding
            row["parity"] = "ERROR"
            row["error"] = "%s: %s" % (type(exc).__name__, str(exc)[:400])
        emit(row)
        if row["parity"] != "ok":
            failed.append(row)
    return failed


def run_delta_check(reps=5, workers=3, length=4096, heads=32, width=128, chunk=64,
                    allow_interpret=False, emit=_print_row):
    """Parity and time of the delta rule's kernel pair against the XLA form, one
    row; ``emit(row)``.  Returns the row if its parity is not ``"ok"``."""
    import importlib.util

    import jax
    import jax.numpy as jnp

    from aggregathor_tpu.models import qwen3_next
    from aggregathor_tpu.ops import delta_rule

    if not allow_interpret and delta_rule._interpret():
        raise RuntimeError("the delta column needs a TPU backend (the kernels would "
                           "interpret on %r)" % jax.default_backend())
    spec = importlib.util.spec_from_file_location("pallas_tpu_check_reference", os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "grid", "references",
        "qwen3_next.py"))
    reference = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reference)

    key = jax.random.PRNGKey(19)
    normal = lambda place, *dims: jax.random.normal(
        jax.random.fold_in(key, place), (workers, 1, length, heads) + dims, jnp.float32)
    inputs = (qwen3_next.l2_normalised(normal(0, width)) * width ** -0.5,
              qwen3_next.l2_normalised(normal(1, width)), normal(2, width),
              # a head forgets from a fifth of its state to next to nothing a token
              -jnp.exp(normal(3) - 3.0), jax.nn.sigmoid(normal(4)))
    weight = normal(5, width)

    def forms(rule, batched=True):
        forward = jax.vmap(rule) if batched else rule
        scalar = lambda *args: jnp.sum(forward(*args)[0] * (weight if batched else weight[0]))
        return jax.jit(forward), jax.jit(jax.value_and_grad(scalar, argnums=(0, 1, 2, 3, 4)))

    def traced(form, precision):
        """(forward, scalar-and-gradients) of the model's entry, traced inside the seam."""
        def enter(fn):
            def call(*args):
                with delta_rule.forced_form(form), jax.default_matmul_precision(precision):
                    return fn(*args)
            return call
        return [enter(fn) for fn in forms(lambda *args: qwen3_next.delta_rule(*args, chunk))]

    names = ("o", "state", "dq", "dk", "dv", "dg", "dbeta")
    gap = lambda a, b: float("%.3g" % (jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b))))
    row = {"metric": "pallas_tpu_check", "rule": "delta", "workers": workers, "length": length,
           "heads": heads, "widths": "%d/%d" % (width, width), "chunk": chunk,
           "tile_chunks": delta_rule.tile_chunks_for(length, chunk), "quantities": list(names)}
    try:
        with jax.default_matmul_precision("highest"):   # the oracle: the first worker, token by token
            walk, gradients = forms(lambda *args: (reference._recurrence(*args), None),
                                    batched=False)
            first = [a[0] for a in inputs]
            exact = (walk(*first)[0],) + tuple(gradients(*first)[1])
        within = {}
        for precision in ("highest", "default"):
            ours, theirs = traced("kernel", precision), traced("xla", precision)
            (out_k, state_k), (out_x, state_x) = ours[0](*inputs), theirs[0](*inputs)
            (_, grads_k), (_, grads_x) = ours[1](*inputs), theirs[1](*inputs)
            all_k, all_x = (out_k, state_k) + tuple(grads_k), (out_x, state_x) + tuple(grads_x)
            row["gap_" + precision] = [gap(a, b) for a, b in zip(all_k, all_x)]
            # the last state has no oracle here (the recurrence hands back o alone)
            oracle = lambda every: [gap(a[0], b) for a, b in zip(every[:1] + every[2:], exact)]
            row["xla_from_recurrence_" + precision] = oracle(all_x)
            row["kernel_from_recurrence_" + precision] = oracle(all_k)
            bound = max(row["xla_from_recurrence_" + precision])
            within[precision] = all(bool(jnp.all(jnp.isfinite(a))) for a in all_k) and max(
                row["gap_" + precision]) <= bound
            suffix = "_ms" if precision == "default" else "_highest_ms"
            row["kernel_fwd" + suffix] = round(_least_ms(lambda: ours[0](*inputs), reps), 4)
            row["kernel_fwd_bwd" + suffix] = round(_least_ms(lambda: ours[1](*inputs), reps), 4)
            if precision == "default":
                row["xla_fwd_ms"] = round(_least_ms(lambda: theirs[0](*inputs), reps), 4)
                row["xla_fwd_bwd_ms"] = round(_least_ms(lambda: theirs[1](*inputs), reps), 4)
        row["parity"] = "ok" if all(within.values()) else "FAIL"
    except Exception as exc:  # a kernel the compiler refuses is a finding
        row["parity"] = "ERROR"
        row["error"] = "%s: %s" % (type(exc).__name__, str(exc)[:400])
    emit(row)
    return [row] if row["parity"] != "ok" else []


def run_operands_check(reps=5, workers=3, length=4096, key_heads=16, value_heads=32, width=128,
                       taps=4, allow_interpret=False, emit=_print_row):
    """Parity and time of the operands' kernel pair against the XLA form, one
    row; ``emit(row)``.  Returns the row if its parity is not ``"ok"``."""
    import jax
    import jax.numpy as jnp

    from aggregathor_tpu.models import qwen3_next
    from aggregathor_tpu.ops import gdn_operands

    if not allow_interpret and gdn_operands._interpret():
        raise RuntimeError("the operands column needs a TPU backend (the kernels would "
                           "interpret on %r)" % jax.default_backend())
    cfg = qwen3_next.Qwen3NextConfig(key_heads=key_heads, value_heads=value_heads, key_dim=width,
                                     value_dim=width, conv=taps)
    keys, values = key_heads * width, value_heads * width
    key = jax.random.PRNGKey(23)
    normal = lambda place, *dims: jax.random.normal(jax.random.fold_in(key, place), dims,
                                                    jnp.float32)
    projected = normal(0, workers, 1, length, 2 * keys + 2 * values)
    conv = 0.5 * normal(1, 2 * keys + values, taps)
    weights = [normal(2 + i, workers, 1, length, value_heads, width) for i in range(4)]

    def traced(form, precision):
        """(forward, scalar-and-gradients) of the model's entry, traced inside the seam."""
        def operands(projected, taps):
            return gdn_operands.gdn_operands(
                projected, taps, key_heads, value_heads, width, width, qwen3_next.L2_EPS,
                lambda projected, taps: qwen3_next.split_heads(projected, taps, cfg))

        forward = jax.vmap(operands, in_axes=(0, None))
        scalar = lambda projected, taps: sum(
            jnp.sum(out * w) for out, w in zip(forward(projected, taps), weights))

        def enter(fn):
            def call(*args):
                with gdn_operands.forced_form(form), jax.default_matmul_precision(precision):
                    return fn(*args)
            return call
        return enter(jax.jit(forward)), enter(jax.jit(jax.grad(scalar, argnums=(0, 1))))

    names = ("q", "k", "v", "z", "dprojected", "dtaps")
    gap = lambda a, b: float("%.3g" % (jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b))))
    tile = gdn_operands.tile_for(length)
    rows, lanes, out = workers * length, 2 * keys + values, 3 * values
    halo = gdn_operands.ROWS / tile
    row = {"metric": "pallas_tpu_check", "rule": "operands", "workers": workers, "length": length,
           "heads": "%d/%d" % (key_heads, value_heads), "width": width, "taps": taps,
           "tile": tile, "quantities": list(names),
           "fwd_bytes": int(4 * rows * (lanes * (1 + halo) + out)),
           "bwd_bytes": int(4 * rows * (lanes * (1 + 2 * halo) + out * (1 + halo) + values
                                        + lanes + values))}
    try:
        for precision in ("highest", "default"):
            ours, theirs = traced("kernel", precision), traced("xla", precision)
            all_k = tuple(ours[0](projected, conv)) + tuple(ours[1](projected, conv))
            all_x = tuple(theirs[0](projected, conv)) + tuple(theirs[1](projected, conv))
            row["gap_" + precision] = [gap(a, b) for a, b in zip(all_k, all_x)]
            row["finite_" + precision] = all(bool(jnp.all(jnp.isfinite(a))) for a in all_k)
        # each form alone: the kernels through ``fused_operands`` (their results as the delta
        # rule's kernels read them: no z, no 4-D shape to lay out); the gradient of a scalar of
        # q, k, v needs no forward pass (the residuals are the arguments), so it times the
        # backward kernel alone where the XLA form runs forward and backward
        alone = {"kernel": lambda projected, taps: gdn_operands.fused_operands(
            projected, taps, key_heads, value_heads, width, width, qwen3_next.L2_EPS, tile)[:3],
                 "xla": lambda projected, taps: qwen3_next.split_heads(projected, taps, cfg)[:3]}
        for form, operands in alone.items():
            forward = jax.jit(jax.vmap(operands, in_axes=(0, None)))
            flat = [w.reshape(forward(projected, conv)[i].shape) for i, w in enumerate(weights[:3])]
            backward = jax.jit(jax.grad(lambda projected, taps: sum(
                jnp.sum(out * w) for out, w in zip(forward(projected, taps), flat)), argnums=(0, 1)))
            row[form + "_fwd_ms"] = round(_least_ms(lambda: forward(projected, conv), reps), 4)
            row[form + ("_bwd_ms" if form == "kernel" else "_fwd_bwd_ms")] = round(
                _least_ms(lambda: backward(projected, conv), reps), 4)
        if not allow_interpret:
            share = lambda count, ms: round(100 * count / (ms * 1e-3) / 819e9, 2)
            row["fwd_of_hbm_pct"] = share(row["fwd_bytes"], row["kernel_fwd_ms"])
            row["bwd_of_hbm_pct"] = share(row["bwd_bytes"], row["kernel_bwd_ms"])
        # float32 sums in another order on both sides, and a division that is two Newton steps
        row["parity"] = "ok" if all(
            row["finite_" + p] and max(row["gap_" + p]) < 2e-6 for p in ("highest", "default")) \
            else "FAIL"
    except Exception as exc:  # a kernel the compiler refuses is a finding
        row["parity"] = "ERROR"
        row["error"] = "%s: %s" % (type(exc).__name__, str(exc)[:400])
    emit(row)
    return [row] if row["parity"] != "ok" else []


#: ``--columns leaf``: (workers, a worker's leaf) — the grid's largest leaves:
#: cell 5's held experts, cells 5 and 8's head (18,992 is 148 whole lanes and
#: 48 columns), cell 7's ``wkv_a`` (576: 4 whole lanes and 64), cell 9's fused
#: ``w_qkvz`` (the widest leaf a layer) and its ``w_ba`` (64 lanes: its rows as lanes)
LEAF_SHAPES = ((4, (4, 8, 768, 2048)), (3, (2048, 18992)), (3, (4, 2048, 576)),
               (3, (3, 2048, 12288)), (3, (3, 2048, 64)))


def run_leaf_check(reps=5, shapes=LEAF_SHAPES, allow_interpret=False, emit=_print_row):
    """Each plane kernel's LEAF entry (``ops/pallas_kernels._plane_leaf_call``:
    the n workers' copies of one gradient leaf as they lie) against its 2-D
    entry on the same numbers laid out as (n, d) rows, at ``shapes``, NaN, both
    infinities and ties planted: ``differing`` elements of the two results
    (bit for bit; 0 wanted: one ``rule`` closure serves both) and each entry's
    least ms — the 2-D entry's WITHOUT the flatten in front of it and the
    reshape behind it, which the step's rows path pays besides.  ``emit(row)``
    each; returns the rows that differ."""
    import jax
    import jax.numpy as jnp

    from aggregathor_tpu.ops import pallas_kernels as pk

    if not allow_interpret and pk._interpret():
        raise RuntimeError("the leaf column needs a TPU backend (the kernels would "
                           "interpret on %r)" % jax.default_backend())

    def entries(n):
        f = 1
        return (
            ("median", pk.coordinate_median_leaf, pk.coordinate_median),
            ("averaged-median",
             lambda x: pk.coordinate_averaged_median_leaf(x, beta=n - f),
             lambda x: pk.coordinate_averaged_median(x, n - f)),
            ("trimmed-mean",
             lambda x: pk.coordinate_trimmed_mean_leaf(x, trim=f, keep=n - 2 * f),
             lambda x: pk.coordinate_trimmed_mean(x, f, n - 2 * f)),
        )

    @jax.jit
    def planted(key, like):
        x = jax.random.normal(key, like.shape, jnp.float32)
        x = x.at[0, ..., 5::131].set(jnp.nan).at[1, ..., 7::257].set(jnp.inf)
        x = x.at[-1, ..., 9::263].set(-jnp.inf).at[:, ..., 11::127].set(1.0)
        x = x.at[1, ..., 3, :].set(jnp.nan)  # a worker's whole row of every tile column
        # as the backward pass leaves a stacked leaf: the workers just above the tiles
        return jnp.moveaxis(x, 0, -3)

    def workers_first(lain):
        return jnp.moveaxis(lain, -3, 0)

    failed = []
    for n, shape in shapes:
        lain = planted(jax.random.PRNGKey(n + len(shape)), jnp.zeros((n,) + shape))
        rows = jax.jit(lambda lain: workers_first(lain).reshape(n, -1))(lain)
        for rule, leaf_entry, flat_entry in entries(n):
            leaf_entry = jax.jit(lambda lain, entry=leaf_entry: entry(workers_first(lain)))
            flat_entry = jax.jit(flat_entry)
            row = {"metric": "pallas_tpu_check", "rule": rule, "entry": "leaf", "n": n,
                   "shape": list(shape), "block": list(pk.leaf_blocks(workers_first(lain)))}
            try:
                ours, theirs = leaf_entry(lain), flat_entry(rows).reshape(shape)
                row["differing"] = int(jnp.sum(
                    jax.lax.bitcast_convert_type(ours, jnp.int32)
                    != jax.lax.bitcast_convert_type(theirs, jnp.int32)))
                row["nonfinite"] = int(jnp.sum(~jnp.isfinite(ours)))
                row["leaf_ms"] = round(_least_ms(lambda: leaf_entry(lain), reps), 4)
                row["rows_ms"] = round(_least_ms(lambda: flat_entry(rows), reps), 4)
                row["leaf_gb_per_s"] = round((n + 1) * 4 * ours.size / row["leaf_ms"] / 1e6, 1)
                row["parity"] = "ok" if row["differing"] == 0 and ours.shape == shape else "FAIL"
            except Exception as exc:  # a kernel the compiler refuses is a finding
                row["parity"] = "ERROR"
                row["error"] = "%s: %s" % (type(exc).__name__, str(exc)[:400])
            emit(row)
            if row["parity"] != "ok":
                failed.append(row)
    return failed


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=32)
    ap.add_argument("--f", type=int, default=8)
    ap.add_argument("--dims", default="65536,1048576,8388608")
    ap.add_argument("--rules", default=",".join(RULES))
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--nan-workers", type=int, default=2,
                    help="rows given scattered NaN coordinates (lossy-link parity)")
    ap.add_argument("--allow-interpret", action="store_true",
                    help="harness self-test: run off-TPU in interpreter mode "
                         "(timings meaningless; parity logic still exercised)")
    ap.add_argument("--columns", default="gar,attention",
                    help="which checks run: 'gar' (the rules), 'attention' (the fused kernel), "
                         "'select' (the threshold by counting), 'leaf' (the plane kernels' "
                         "leaf entry against their 2-D entry), 'delta' (the gated delta rule's "
                         "kernel pair against the XLA form), 'operands' (the DeltaNet "
                         "operands' kernel pair against the XLA form)")
    ap.add_argument("--attention-reps", type=int, default=5)
    ap.add_argument("--attention-shapes", default=",".join(name for name, *_ in ATTENTION_SHAPES),
                    help="which rows of the attention column run")
    ap.add_argument("--attention-tiles", default="",
                    help="QxK,... : also time the attention kernel alone at these tiles; "
                         "'sweep': at the five of TILE_SWEEP")
    args = ap.parse_args()

    import jax

    from aggregathor_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    platform = jax.devices()[0].platform
    if platform != "tpu" and not args.allow_interpret:
        print(json.dumps({"error": "pallas_tpu_check requires a TPU backend, got %r" % platform}))
        sys.exit(2)

    columns, failed = args.columns.split(","), []
    if "gar" in columns:
        failed += run_check(
            args.n, args.f, [int(d) for d in args.dims.split(",")],
            rules=args.rules.split(","), reps=args.reps,
            nan_workers=args.nan_workers, allow_interpret=args.allow_interpret,
        )
    if "attention" in columns:
        tiles = TILE_SWEEP if args.attention_tiles == "sweep" else [
            tuple(int(size) for size in pair.split("x"))
            for pair in args.attention_tiles.split(",") if pair]
        failed += run_attention_check(
            args.attention_reps, tiles, allow_interpret=args.allow_interpret,
            shapes=[shape for shape in ATTENTION_SHAPES
                    if shape[0] in args.attention_shapes.split(",")])
    if "select" in columns:
        failed += run_select_check(allow_interpret=args.allow_interpret)
    if "leaf" in columns:
        failed += run_leaf_check(allow_interpret=args.allow_interpret)
    if "delta" in columns:
        failed += run_delta_check(args.attention_reps, allow_interpret=args.allow_interpret)
    if "operands" in columns:
        failed += run_operands_check(args.attention_reps, allow_interpret=args.allow_interpret)
    sys.exit(1 if failed else 0)


if __name__ == "__main__":
    main()
