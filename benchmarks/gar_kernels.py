"""GAR kernel latency benchmark: ms vs gradient dimension, per tier.

The measurement protocol BASELINE.md prescribes: per-rule kernel latency as a
function of the flattened gradient dimension ``d``, alongside the steps/s
bench (bench.py). Tiers:

- ``jnp``     — the default jit/XLA tier (runs on whatever backend is live)
- ``pallas``  — the hand-written TPU kernels (TPU only; silently skipped
                elsewhere)
- ``native``  — the C++ host library via ctypes (CPU threads)

Usage::

    python benchmarks/gar_kernels.py [--n 32] [--f 8] [--dims 65536,1048576]
                                     [--rules krum,bulyan,median] [--reps 20]

Prints one human table and one machine-readable JSON line per (rule, tier, d).
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Pin the plain rule names to the pure-jnp tier: round 4 made the base
# coordinate rules auto-dispatch to the Pallas kernels on TPU
# (gars/common.py use_pallas_coordinate_tier), which would silently turn
# this script's jnp column into a second Pallas column.  The *-pallas
# registrations override aggregate_block directly and ignore this.
os.environ["GRAFT_GAR_TIER"] = "jnp"


def time_fn(fn, reps):
    """Median per-call ms; EVERY timed repetition individually synced.

    Delegates to the ONE canonical timing protocol in
    ``aggregathor_tpu.gars.scaling.time_aggregate`` (warmup, then per rep:
    ``sync_fetch`` — ``block_until_ready`` + a scalar host fetch — of that
    rep's own output, median over reps).
    """
    from aggregathor_tpu.gars.scaling import time_aggregate

    return time_aggregate(fn, reps)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=32, help="worker count")
    ap.add_argument("--f", type=int, default=8, help="declared Byzantine count")
    ap.add_argument("--dims", default="65536,1048576,8388608", help="comma list of d")
    ap.add_argument(
        "--rules",
        default="average,average-nan,median,averaged-median,krum,bulyan,"
                "trimmed-mean,centered-clip,geometric-median,bucketing,dnc",
    )
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--scale-ns", default=None,
                    help="comma list of worker counts: sweep krum+bulyan at "
                         "--scale-d, reporting COMPILE seconds + kernel ms "
                         "(the reference's C++ selection loop had no n limit, "
                         "op_bulyan/cpu.cpp:134-161; Bulyan's lax.scan form "
                         "must keep compile time flat in t = n - 2f - 2)")
    ap.add_argument("--scale-d", type=int, default=65536)
    ap.add_argument("--sweep-ns", default=None,
                    help="comma list of worker counts (e.g. 8,32,128,512): "
                         "the n-sweep scaling mode — flat krum/bulyan vs the "
                         "composite tree rules (hier, bucketing-over-hier) "
                         "at fixed --sweep-d, emitting one "
                         "aggregathor.gar.scaling.v1 document with the "
                         "sublinear-in-n² verdict (gars/scaling.py, "
                         "docs/gar_scaling.md)")
    ap.add_argument("--sweep-d", type=int, default=65536,
                    help="fixed gradient dimension for --sweep-ns")
    ap.add_argument("--sweep-f", type=int, default=1,
                    help="declared Byzantine count for --sweep-ns (small, so "
                         "every generated composite stays feasible at the "
                         "smallest swept n)")
    ap.add_argument("--sweep-reps", type=int, default=5)
    ap.add_argument("--sweep-out", default=None,
                    help="write the aggregathor.gar.scaling.v1 JSON here")
    ap.add_argument("--platform", default=None, help="force a JAX platform")
    ap.add_argument("--resume-file", default=None,
                    help="JSON path recording completed (rule, tier, d) "
                         "cells: a re-run skips them (and reprints their "
                         "rows) so a scarce TPU up-window resumes the sweep "
                         "instead of restarting it.")
    args = ap.parse_args()

    if args.platform:
        os.environ["JAX_PLATFORMS"] = args.platform
    import jax

    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    import jax.numpy as jnp

    from aggregathor_tpu import gars
    from aggregathor_tpu.ops import native

    from aggregathor_tpu.utils.state import load_json, save_json_atomic

    platform = jax.devices()[0].platform
    on_tpu = platform == "tpu"
    native_ok = native.available()
    rules = args.rules.split(",") if args.rules else []
    dims = [int(d) for d in args.dims.split(",") if d]  # "" = scale-n only
    rows = []
    resume = load_json(args.resume_file) if args.resume_file else {}

    def measured(rule, tier, d, f, thunk):
        """The cell's ms: from the resume cache, or measured via thunk()."""
        key = "%s|%s|%d|%d|%d|%d" % (rule, tier, d, args.n, args.f, args.reps)
        ms = resume.get(key)
        if ms == 0.0:
            # A 0.0 cell is the old unsynced timer's failure signature (its
            # dispatch-loop slope clamped negative), not a measurement:
            # re-measure it with the per-rep-synced protocol.
            ms = None
        if ms is None:
            ms = thunk()
            if args.resume_file:
                resume[key] = ms
                save_json_atomic(args.resume_file, resume)
        rows.append((rule, tier, d, ms, f))

    for d in dims:
        # The d=8.4M fixture is ~1 GB of random floats; build it LAZILY so
        # a fully-cached d costs neither the generation nor the device
        # transfer.  Seeded per-d, so laziness never changes the values.
        fixture = {}

        def g_host(d=d, fixture=fixture):
            if "host" not in fixture:
                fixture["host"] = np.random.default_rng(d).normal(
                    size=(args.n, d)).astype(np.float32)
            return fixture["host"]

        def g_dev(fixture=fixture):
            if "dev" not in fixture:
                fixture["dev"] = jax.device_put(g_host())
            return fixture["dev"]

        for rule in rules:
            # Bulyan's bound is n >= 4f + 3; clamp f so every rule runs at
            # the requested n (the reference would reject such configs too).
            f = min(args.f, (args.n - 3) // 4) if rule.startswith("bulyan") else args.f
            # jit tier
            gar = gars.instantiate(rule, args.n, f)
            agg = jax.jit(gar.aggregate)
            measured(rule, "jnp:" + platform, d, f,
                     lambda: time_fn(lambda: agg(g_dev()), args.reps))

            # pallas tier (TPU only)
            if on_tpu and (rule + "-pallas") in gars.itemize():
                pgar = gars.instantiate(rule + "-pallas", args.n, f)
                pagg = jax.jit(pgar.aggregate)
                measured(rule, "pallas", d, f,
                         lambda: time_fn(lambda: pagg(g_dev()), args.reps))

            # native host tier
            if native_ok and hasattr(native, rule.replace("-", "_")):
                nfn = getattr(native, rule.replace("-", "_"))
                if rule in ("krum", "bulyan", "averaged-median"):
                    call = lambda nfn=nfn, f=f: nfn(g_host(), f)
                else:
                    call = lambda nfn=nfn: nfn(g_host())
                measured(rule, "native", d, f,
                         lambda: time_fn(call, max(3, args.reps // 4)))

    scale_rows = []
    if args.scale_ns:
        d = args.scale_d
        for n in (int(x) for x in args.scale_ns.split(",")):
            f = max(1, (n - 3) // 4)  # the largest f Bulyan admits at n
            g = None  # lazily built: a fully-cached n costs no fixture
            for rule in ("krum", "bulyan"):
                key = "scale|%s|%d|%d|%d" % (rule, n, d, args.reps)
                cached = resume.get(key)
                if cached is not None:
                    compile_s, ms = cached
                else:
                    if g is None:
                        g = jax.device_put(np.random.default_rng(n).normal(
                            size=(n, d)).astype(np.float32))
                    agg = jax.jit(gars.instantiate(rule, n, f).aggregate)
                    # PURE trace+compile time (the flatness claim): AOT
                    # lower+compile, no execution or host fetch mixed in.
                    t0 = time.perf_counter()
                    compiled = agg.lower(g).compile()
                    compile_s = time.perf_counter() - t0
                    ms = time_fn(lambda: compiled(g), max(3, args.reps // 2))
                    if args.resume_file:
                        resume[key] = [compile_s, ms]
                        save_json_atomic(args.resume_file, resume)
                scale_rows.append({
                    "metric": "gar_scale_n", "rule": rule,
                    "tier": "jnp:" + platform, "n": n, "f": f, "d": d,
                    "compile_s": round(compile_s, 2),
                    "value": round(ms, 4), "unit": "ms",
                })

    sweep_doc = None
    if args.sweep_ns:
        from aggregathor_tpu.gars import scaling

        sweep_doc = scaling.run_sweep(
            [int(x) for x in args.sweep_ns.split(",") if x],
            args.sweep_d, f=args.sweep_f, reps=args.sweep_reps,
            progress=lambda line: print("sweep  " + line, flush=True),
        )
        scaling.validate_scaling_doc(sweep_doc)
        print(scaling.render_table(sweep_doc))
        if args.sweep_out:
            scaling.save_doc(args.sweep_out, sweep_doc)
            print("wrote %s" % args.sweep_out)

    print("%-18s %-12s %12s %12s" % ("rule", "tier", "d", "ms"))
    for rule, tier, d, ms, f in rows:
        print("%-18s %-12s %12d %12.3f" % (rule, tier, d, ms))
    for rule, tier, d, ms, f in rows:
        print(
            json.dumps(
                {
                    "metric": "gar_kernel_ms",
                    "rule": rule,
                    "tier": tier,
                    "n": args.n,
                    "f": f,  # effective f (clamped for bulyan's n >= 4f+3)
                    "d": d,
                    "value": round(ms, 4),
                    "unit": "ms",
                }
            )
        )
    for row in scale_rows:
        print(json.dumps(row))
    if sweep_doc is not None:
        print("GRAFT_BENCH_RESULT " + json.dumps(sweep_doc, sort_keys=True))
        return 0 if sweep_doc["verdict"]["ok"] else 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
