"""chip_smoke.py — does the system still start, train and aggregate on the chip?

One process, no children, nothing that could fall back: it needs a TPU and
exits non-zero at once without one.  It drives the main path through the
entry point a user calls (``aggregathor_tpu.cli.runner.main``) at the full
width of BASELINE config 2 — ``cnnet`` (1.76 M parameters) on CIFAR-10
shapes, batch 128 per worker, n = 8, f = 2, Multi-Krum, f32 — on every chip
``jax.devices()`` reports that divides n (the runner's default).  The data is
the deterministic synthetic stand-in (the machine has no network; the run's
log says so); the weights are random, from the run seed.

Legs, chosen by device count and named in the summary; none is skipped on
error and none is wrapped in ``try``:

  A  scanned, device-sampled: ``--input-source device --unroll 20
     --max-step 60``; every loss finite (the runner aborts on a non-finite
     step), the last chunk's loss below the first's, a finite evaluation.
  B  per-step, host-fed, under attack: ``--unroll 1 --max-step 10
     --nb-real-byz-workers 2 --attack signflip``; every loss finite.
  C  kernels compile: the six ``*-pallas`` rules and the four vmapped cases
     of scripts/pallas_tpu_check.py, compiled (not interpreted), parity
     against the jnp tier at (n=8, f=2, d=config 2's d) and (n=32, f=7,
     d=1,048,576); and the lowered text of config 2's own step contains a
     Mosaic custom call.
  D  (>= 4 chips) sharded: ``--experiment transformer --aggregator krum
     --nb-workers 4 --nb-decl-byz-workers 1 --mesh 2,2,1 --granularity
     layer --max-step 6``.

Then: per-device peak memory (a mesh device left at zero fails), cold wall
time per leg, persistent-cache hits and misses, and that no shared library
of the checkout was loaded that this run did not build.  The summary also
lands in ``chiprun_out/chip_smoke.json``.  The last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""

import glob
import importlib.metadata
import json
import math
import os
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG2 = [
    "--experiment", "cnnet", "--experiment-args", "batch-size:128",
    "--aggregator", "krum", "--nb-workers", "8", "--nb-decl-byz-workers", "2",
]
#: the Mosaic (Pallas TPU) custom call target in lowered StableHLO
MOSAIC_CALL = "tpu_custom_call"


def mesh_width(devices, nb_workers=8):
    """The runner's default: every device that divides the worker count."""
    return max(d for d in range(1, len(devices) + 1) if nb_workers % d == 0)


def read_losses(summary_dir):
    """[(step, total_loss)] of the one summary stream under ``summary_dir``."""
    (path,) = glob.glob(os.path.join(summary_dir, "*.jsonl"))
    with open(path) as fd:
        events = [json.loads(line) for line in fd]
    return [(e["step"], e["total_loss"]) for e in events if "total_loss" in e]


def require_finite(losses, what):
    bad = [(step, loss) for step, loss in losses if loss is None or not math.isfinite(loss)]
    if bad:
        raise SystemExit("%s: non-finite loss at %r" % (what, bad))


def run_runner(argv, workdir, name, summary_delta):
    """``cli.runner.main`` with its summaries under ``workdir/name``; returns
    the per-fire losses."""
    from aggregathor_tpu.cli import runner

    summary_dir = os.path.join(workdir, name)
    os.makedirs(summary_dir)
    code = runner.main(argv + [
        "--platform", "tpu", "--summary-dir", summary_dir,
        "--summary-delta", str(summary_delta), "--summary-period", "-1",
    ])
    if code != 0:
        raise SystemExit("%s: runner returned %r" % (name, code))
    return read_losses(summary_dir)


def leg_a(workdir):
    eval_file = os.path.join(workdir, "leg_a_eval.tsv")
    losses = run_runner(CONFIG2 + [
        "--input-source", "device", "--unroll", "20", "--max-step", "60",
        "--evaluation-delta", "60", "--evaluation-period", "-1",
        "--evaluation-file", eval_file,
    ], workdir, "leg_a", summary_delta=20)
    require_finite(losses, "leg A")
    if [step for step, _ in losses] != [20, 40, 60]:
        raise SystemExit("leg A: expected one loss per 20-step chunk, got %r" % losses)
    if not losses[-1][1] < losses[0][1]:
        raise SystemExit("leg A: loss did not fall across chunks: %r" % losses)
    with open(eval_file) as fd:
        accuracy = float(fd.read().split()[-1].split(":")[1])
    if not 0.0 <= accuracy <= 1.0:
        raise SystemExit("leg A: evaluation accuracy %r" % accuracy)
    return {"chunk_end_losses": [loss for _, loss in losses], "eval_accuracy": accuracy}


def leg_b(workdir):
    losses = run_runner(CONFIG2 + [
        "--unroll", "1", "--max-step", "10",
        "--nb-real-byz-workers", "2", "--attack", "signflip",
        "--evaluation-delta", "-1", "--evaluation-period", "-1",
    ], workdir, "leg_b", summary_delta=1)
    require_finite(losses, "leg B")
    if len(losses) != 10:
        raise SystemExit("leg B: expected 10 per-step losses, got %r" % losses)
    return {"losses": [loss for _, loss in losses]}


def leg_c(_workdir):
    import jax
    import optax

    from aggregathor_tpu import gars, models
    from aggregathor_tpu.parallel import RobustEngine, make_mesh

    sys.path.insert(0, os.path.join(HERE, "scripts"))
    import pallas_tpu_check

    # config 2's own step through the library surface, same arguments as the
    # runner builds it with: is the distance kernel inside the
    # shard_map/scan step the compiled one?
    experiment = models.instantiate("cnnet", ["batch-size:128"])
    nb_devices = mesh_width(jax.devices())
    engine = RobustEngine(
        make_mesh(nb_workers=nb_devices, devices=jax.devices()[:nb_devices]),
        gars.instantiate("krum", 8, 2), 8,
        batch_transform=experiment.device_transform(),
    )
    tx = optax.sgd(1e-3)
    state = engine.init_state(experiment.init(jax.random.PRNGKey(0)), tx)
    batch = engine.shard_batch(next(experiment.make_train_iterator(8, seed=0)))
    lowered = engine.build_step(experiment.loss, tx).lower(state, batch).as_text()
    mosaic_calls = lowered.count(MOSAIC_CALL)
    if not mosaic_calls:
        raise SystemExit("leg C: config 2's lowered step holds no %s" % MOSAIC_CALL)
    dim = sum(leaf.size for leaf in jax.tree_util.tree_leaves(state.params))

    rows = []

    def emit(row):
        rows.append(row)
        print(json.dumps(row), flush=True)

    failed = []
    for n, f, d in ((8, 2, dim), (32, 7, 1 << 20)):
        failed += pallas_tpu_check.run_check(n, f, [d], reps=3, emit=emit)
    if failed:
        raise SystemExit("leg C: %d kernel case(s) failed: %r" % (len(failed), failed))
    return {"config2_dim": dim, "mosaic_calls_in_step": mosaic_calls,
            "kernel_cases_ok": len(rows)}


def leg_d(workdir):
    losses = run_runner([
        "--experiment", "transformer", "--aggregator", "krum",
        "--nb-workers", "4", "--nb-decl-byz-workers", "1",
        "--mesh", "2,2,1", "--granularity", "layer", "--max-step", "6",
        "--evaluation-delta", "-1", "--evaluation-period", "-1",
    ], workdir, "leg_d", summary_delta=1)
    require_finite(losses, "leg D")
    if len(losses) != 6:
        raise SystemExit("leg D: expected 6 per-step losses, got %r" % losses)
    return {"losses": [loss for _, loss in losses]}


def foreign_libraries(started):
    """Shared libraries of this checkout mapped into the process that were
    not built during this run."""
    with open("/proc/self/maps") as fd:
        mapped = {line.split()[-1] for line in fd if line.rstrip().endswith(".so")}
    return sorted(path for path in mapped
                  if path.startswith(HERE + os.sep) and os.path.getmtime(path) < started)


def main():
    started = time.time()
    import jax
    import jaxlib

    cache = {"hits": 0, "misses": 0}

    def count_cache_events(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache["misses"] += 1

    jax.monitoring.register_event_listener(count_cache_events)
    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    print("chip_smoke: platform=%s device_kind=%s count=%d jax=%s jaxlib=%s libtpu=%s"
          % (device["platform"], device["kind"], device["count"], jax.__version__,
             jaxlib.__version__, importlib.metadata.version("libtpu")), flush=True)
    if device["platform"] != "tpu":
        raise SystemExit("chip_smoke needs a TPU; JAX found platform %r"
                         % device["platform"])

    from aggregathor_tpu.utils.compile_cache import place_compile_cache

    cache_dir = place_compile_cache()

    legs = {"A": leg_a, "B": leg_b, "C": leg_c}
    if len(devices) >= 4:
        legs["D"] = leg_d
    summary = {"device": device, "legs": {}, "cache_dir": cache_dir}
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as workdir:
        for name, leg in legs.items():
            begin = time.perf_counter()
            outcome = leg(workdir)
            outcome["wall_s"] = round(time.perf_counter() - begin, 1)
            summary["legs"][name] = outcome
            print("chip_smoke: leg %s ok in %.1fs" % (name, outcome["wall_s"]), flush=True)

    # every device of the widest mesh a leg built must have held something
    nb_mesh = mesh_width(devices)
    summary["peak_bytes_in_use"] = {
        str(d.id): d.memory_stats()["peak_bytes_in_use"] for d in devices}
    idle = [d.id for d in devices[:nb_mesh] if not summary["peak_bytes_in_use"][str(d.id)]]
    if idle:
        raise SystemExit("mesh device(s) %r never held a byte: everything ran "
                         "on the first chip" % idle)
    foreign = foreign_libraries(started)
    if foreign:
        raise SystemExit("loaded shared libraries this run did not build: %r" % foreign)
    summary["cache"] = cache
    summary["wall_s"] = round(time.time() - started, 1)

    os.makedirs(os.path.join(HERE, "chiprun_out"), exist_ok=True)
    with open(os.path.join(HERE, "chiprun_out", "chip_smoke.json"), "w") as fd:
        json.dump(summary, fd, indent=1)
    print("chip_smoke summary: " + json.dumps(summary), flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
