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

        # Vmapped kernels: the bucketed leaf path calls the rules under
        # jax.vmap (engine._aggregate_per_leaf_bucketed), which routes every
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
    args = ap.parse_args()

    import jax

    from aggregathor_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    platform = jax.devices()[0].platform
    if platform != "tpu" and not args.allow_interpret:
        print(json.dumps({"error": "pallas_tpu_check requires a TPU backend, got %r" % platform}))
        sys.exit(2)

    failed = run_check(
        args.n, args.f, [int(d) for d in args.dims.split(",")],
        rules=args.rules.split(","), reps=args.reps,
        nan_workers=args.nan_workers, allow_interpret=args.allow_interpret,
    )
    sys.exit(1 if failed else 0)


if __name__ == "__main__":
    main()
