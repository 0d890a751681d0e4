"""Host->device input pipeline: sync vs old-prefetch vs three-stage pipeline.

The bench trajectory (BENCH_r02..r05, through a transport that no longer
exists) showed the trainer INPUT-bound, not compute-bound, and the old chunk
``DevicePrefetcher`` measurably SLOWER than synchronous dispatch (2.62 vs
2.74 steps/s there; not measured on this machine): its one daemon thread
serially re-did the same gather + one monolithic ``device_put`` the sync
path pays anyway.  This benchmark times the REAL
unrolled trainer (``build_multi_step``, K distinct batches per dispatch)
under the three input strategies the CLI offers (docs/input_pipeline.md):

- ``sync``      gather + transfer ON the timed path, no helper thread —
                the ``--prefetch 0`` baseline;
- ``prefetch``  the retired whole-chunk background thread (kept for
                iterators without ``next_many``): one daemon does
                gather + one monolithic ``device_put`` per chunk;
- ``pipeline``  the three-stage ``ChunkPipeline``: parallel sharded gather
                into ping-pong buffers, S sliced async transfers, jitted
                device-side assemble — with its overlap metrics read back
                from a private ``MetricsRegistry``.

Per mode it reports steps/s and the INPUT-GAP fraction (wall time the
consumer spent acquiring the next device chunk / total wall time — the
slice of the run the device sat idle waiting on input).  For ``pipeline``
the registry's ``input_overlap_fraction`` / ``input_gather_seconds_total``
/ ``input_put_seconds_total`` land in the JSON too, so overlap is measured,
not presumed.

Usage::

    python benchmarks/input_pipeline.py [--experiment cnnet]
        [--nb-workers 8] [--gar multikrum] [--f 2] [--unroll 10]
        [--chunks 6] [--slices 4] [--depth 2] [--output pipeline.json]
        [--bar 1.5] [--strict]

Emits one human table plus machine-readable JSON (schema
``aggregathor.input.pipeline.v1``; registered in BENCHMARKS.md).  The
verdict line states whether the pipeline beat ``--bar`` x sync steps/s and
whether the old prefetcher's <=1.0x regression is gone; ``--strict`` turns
a missed bar into a nonzero exit (CI boxes with one loaded core cannot
always overlap, so the default is report-only).
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SCHEMA = "aggregathor.input.pipeline.v1"

MODES = ("sync", "prefetch", "pipeline")


def build_parser():
    parser = argparse.ArgumentParser(
        description="host->device input strategies: steps/s + input-gap fraction")
    parser.add_argument("--experiment", default="cnnet", help="experiment name (models registry)")
    parser.add_argument("--experiment-args", nargs="*", default=["batch-size:64", "augment:device"],
                        help="key:value experiment arguments")
    parser.add_argument("--nb-workers", type=int, default=8)
    parser.add_argument("--gar", default="krum", help="aggregation rule (gars registry)")
    parser.add_argument("--f", type=int, default=2, help="declared Byzantine workers")
    parser.add_argument("--unroll", type=int, default=10, help="steps per chunk (K)")
    parser.add_argument("--chunks", type=int, default=6, help="timed chunks per mode")
    parser.add_argument("--slices", type=int, default=4,
                        help="transfer slices per chunk (pipeline mode)")
    parser.add_argument("--depth", type=int, default=2, help="queue depth (threaded modes)")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--bar", type=float, default=1.5,
                        help="pipeline-vs-sync speedup bar")
    parser.add_argument("--strict", action="store_true",
                        help="exit nonzero when the bar is missed")
    parser.add_argument("--output", default=None, metavar="JSON")
    parser.add_argument("--platform", default=None, help="force a JAX platform (tpu/cpu)")
    return parser


def main(argv=None):
    args = build_parser().parse_args(argv)
    if args.platform:
        os.environ["JAX_PLATFORMS"] = args.platform

    import jax

    if args.platform:
        jax.config.update("jax_platforms", args.platform)

    from aggregathor_tpu import gars, models
    from aggregathor_tpu.core import build_optimizer, build_schedule
    from aggregathor_tpu.models.datasets import (
        ChunkPipeline, DevicePrefetcher, split_chunk)
    from aggregathor_tpu.obs.metrics import MetricsRegistry
    from aggregathor_tpu.parallel import RobustEngine, make_mesh

    n, unroll, chunks = args.nb_workers, args.unroll, args.chunks
    experiment = models.instantiate(args.experiment, args.experiment_args)
    gar = gars.instantiate(args.gar, n, args.f)
    tx = build_optimizer("sgd", build_schedule("fixed", ["initial-rate:0.05"]))
    engine = RobustEngine(
        make_mesh(nb_workers=1), gar, nb_workers=n, nb_real_byz=0,
        batch_transform=experiment.device_transform(),
    )
    multi_fn = engine.build_multi_step(experiment.loss, tx)
    # host copy: the K-step trainer DONATES its state, so a device-resident
    # canonical params tree would be deleted by the first mode's first call
    params = jax.tree_util.tree_map(
        np.asarray, experiment.init(jax.random.PRNGKey(args.seed)))

    def fresh_state():
        return engine.init_state(params, tx, seed=args.seed + 1)

    # Warm up once: compile the K-step trainer and the pipeline's
    # slice-assemble executable so no mode's timed loop pays a compile.
    it = experiment.make_train_iterator(n, seed=args.seed + 2)
    state = fresh_state()
    warm_chunk = engine.shard_batches(it.next_many(unroll))
    state, metrics = multi_fn(state, warm_chunk)
    jax.block_until_ready(metrics["total_loss"])
    parts = [engine.shard_batches(s)
             for s in split_chunk(it.next_many(unroll), args.slices)]
    jax.block_until_ready(engine.assemble_batches(parts))

    results = {}

    def timed_mode(mode):
        """Run ``chunks`` dispatches under ``mode``; per-chunk input wait and
        total wall time give the mode's input-gap fraction.  Every mode
        consumes the SAME sample stream (fresh iterator, same seed), so the
        losses are comparable and pipeline bit-identity shows up as an
        identical final loss."""
        mode_it = experiment.make_train_iterator(n, seed=args.seed + 2)
        mode_state = fresh_state()
        source = None
        registry = None
        if mode == "prefetch":
            def chunk_source():
                for _ in range(chunks):
                    yield mode_it.next_many(unroll)

            source = DevicePrefetcher(chunk_source(), engine.shard_batches,
                                      depth=args.depth)
        elif mode == "pipeline":
            registry = MetricsRegistry()
            source = ChunkPipeline(
                mode_it, unroll, chunks, put=engine.shard_batches,
                assemble=engine.assemble_batches, depth=args.depth,
                slices=args.slices, registry=registry,
            )
        input_s = 0.0
        loss = None
        t_start = time.perf_counter()
        try:
            for _ in range(chunks):
                t0 = time.perf_counter()
                if source is not None:
                    device_chunk = next(source)
                else:
                    device_chunk = engine.shard_batches(mode_it.next_many(unroll))
                input_s += time.perf_counter() - t0
                mode_state, metrics = multi_fn(mode_state, device_chunk)
                loss = metrics["total_loss"]
            loss = float(np.asarray(jax.block_until_ready(loss))[-1])
        finally:
            if source is not None:
                source.close()
        total_s = time.perf_counter() - t_start
        row = {
            "steps_per_s": round(chunks * unroll / total_s, 3),
            "input_gap_fraction": round(input_s / total_s, 4),
            "input_s": round(input_s, 4),
            "total_s": round(total_s, 4),
            "final_loss": round(loss, 6),
            "timed_steps": chunks * unroll,
        }
        if registry is not None:
            snap = registry.snapshot()
            for name, key in (
                ("input_overlap_fraction", "overlap_fraction"),
                ("input_gather_seconds_total", "gather_s"),
                ("input_put_seconds_total", "put_s"),
                ("input_wait_seconds_total", "wait_s"),
                ("input_chunks_total", "chunks_produced"),
            ):
                row[key] = round(float(snap[name]), 4)
        return row

    for mode in MODES:
        results[mode] = timed_mode(mode)

    sync_rate = results["sync"]["steps_per_s"]
    speedup = {
        mode: round(results[mode]["steps_per_s"] / sync_rate, 3)
        for mode in ("prefetch", "pipeline")
    }
    doc = {
        "schema": SCHEMA,
        "experiment": args.experiment,
        "platform": jax.devices()[0].platform,
        "nb_workers": n,
        "gar": args.gar,
        "f": args.f,
        "unroll": unroll,
        "chunks": chunks,
        "slices": args.slices,
        "depth": args.depth,
        "batch_size": experiment.batch_size,
        "modes": results,
        "speedup_vs_sync": speedup,
        "bar": args.bar,
    }
    print("%-10s %12s %12s %12s %12s" % (
        "mode", "steps/s", "input-gap", "final loss", "vs sync"))
    for mode in MODES:
        row = results[mode]
        print("%-10s %12.3f %12.4f %12.6f %12s" % (
            mode, row["steps_per_s"], row["input_gap_fraction"],
            row["final_loss"],
            "%.2fx" % speedup[mode] if mode in speedup else "1.00x"))
    ok = speedup["pipeline"] >= args.bar
    print("verdict: pipeline %.2fx sync (bar %.2fx) %s; old prefetch %.2fx "
          "(regression %s); pipeline overlap fraction %.3f" % (
              speedup["pipeline"], args.bar, "OK" if ok else "MISSED",
              speedup["prefetch"],
              "gone" if speedup["pipeline"] > speedup["prefetch"] else "NOT gone",
              results["pipeline"].get("overlap_fraction", 0.0)))
    if args.output:
        with open(args.output, "w") as fd:
            json.dump(doc, fd, indent=2, sort_keys=True)
            fd.write("\n")
        print("wrote %s" % args.output)
    print("GRAFT_BENCH_RESULT " + json.dumps(doc, sort_keys=True))
    return 0 if (ok or not args.strict) else 1


if __name__ == "__main__":
    sys.exit(main())
