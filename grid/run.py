"""The benchmark's command: one cell, one seed, one run.

    python3 grid/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (timed by parts, from a clock taken before ``import jax``): imports,
backend, data, seeded state, and the warm-up of the cell's one program — its
first dispatch from the seeded state, whose record the comparison uses later,
and a second one that is timed to size the window.  The window is a number of
whole K-step dispatches fixed beforehand, ended by a wait on the last one's
loss.  With ``--trace 1`` a profiler session is then bracketed round two or
three further dispatches (once more round one fewer where the device's buffer
dropped too many events), and another round the rule alone.  Last, with the
program's state freed, the plain reference decides ``correct`` (check.py).
The last line of standard output is the result.  No TPU, no number; and where
the traced run's numbers contradict each other, no number either: the run says
``grid contradiction: ...`` and exits with ``CONTRADICTION_EXIT``.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

#: The exit code of a run whose trace reduction contradicted itself (not 1, a
#: crash's; not 2, argparse's and the chip tool's; not 3, the chip tool's).
CONTRADICTION_EXIT = 4
COUNTERS = {"cache_hits": 0, "cache_misses": 0, "programs_loaded": 0}
_LISTENING = []


def listen():
    """Count persistent-cache hits and misses, and every program compiled or
    loaded (``backend_compile_duration`` fires for both), for the process."""
    if _LISTENING:
        return
    import jax

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            COUNTERS["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            COUNTERS["cache_misses"] += 1

    def on_duration(event, _duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            COUNTERS["programs_loaded"] += 1

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    _LISTENING.append(True)


def require_chips(devices, chips):
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise SystemExit("the cell asks for %d TPU chip(s); JAX found %d %s device(s)"
                         % (chips, len(devices), devices[0].platform))


def memory_peak_bytes(device):
    """The allocator's peak of live buffers plus its peak reservation for the
    loaded programs' temporaries: while the step program runs, both are held
    (on this runtime ``peak_bytes_in_use`` alone leaves the temporaries out)."""
    stats = device.memory_stats()
    return stats["peak_bytes_in_use"] + stats["peak_bytes_reserved"]


def wait_loss(metrics):
    """The fence: the dispatch's last loss, which its every step feeds."""
    import numpy as np

    return float(np.asarray(metrics["total_loss"]).reshape(-1)[-1])


def profiled(body):
    """Run ``body()`` inside a profiler session of its own; returns the
    session's trace in the reduction's neutral form."""
    import jax

    from trace_reduce import load_xplane

    trace_dir = tempfile.mkdtemp(prefix="grid_trace_")
    try:
        jax.profiler.start_trace(trace_dir)
        try:
            body()
        finally:
            jax.profiler.stop_trace()
        return load_xplane(trace_dir)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


def traced_dispatches(cell, state, nb_dispatches):
    """``nb_dispatches`` whole dispatches, each ended by a wait, inside a
    profiler session of their own; returns (state, neutral trace)."""
    import jax

    def body():
        nonlocal state
        for _ in range(nb_dispatches):
            with jax.profiler.TraceAnnotation("input"):
                data = cell.feed.next()
            with jax.profiler.TraceAnnotation("dispatch"):
                state, metrics = cell.multi(state, data)
            with jax.profiler.TraceAnnotation("wait_loss"):
                wait_loss(metrics)

    return state, profiled(body)


def capture(cell, state, nb_dispatches, steps_per_s):
    """The traced dispatches and their reduction: (state, neutral trace,
    reduced).  A capture whose leaves cover too little of the spans
    (``trace_reduce.DroppedEvents``) is taken once more with one dispatch
    fewer, as its message says; a second one stands or falls as it is."""
    from trace_reduce import DroppedEvents, reduce

    for last in (False, True):
        state, raw = traced_dispatches(cell, state, nb_dispatches)
        try:
            return state, raw, reduce(raw, nb_dispatches * cell.unroll, steps_per_s)
        except DroppedEvents as dropped:
            if last:
                raise
            nb_dispatches = max(1, nb_dispatches - 1)
            print("grid capture: %s; tracing %d dispatch(es) instead" % (dropped, nb_dispatches),
                  flush=True)


def gar_probe(cell):
    """Device time of the cell's rule alone at the shape one chip aggregates,
    from a trace of the harness's own jitted wrapper."""
    import jax
    import jax.numpy as jnp

    from trace_reduce import module_mean_ms

    n, f = cell.nb_workers, cell.nb_byz
    d = -(-cell.spec["config_data"]["parameters"] // len(cell.devices))
    rows = jax.jit(lambda key: jax.random.normal(key, (n, d), jnp.float32))(jax.random.PRNGKey(0))

    def grid_gar_probe(block):
        return cell.gar.aggregate(block)

    probe = jax.jit(grid_gar_probe)
    probe(rows).block_until_ready()
    device_ms = module_mean_ms(
        profiled(lambda: [probe(rows).block_until_ready() for _ in range(3)]), "grid_gar_probe")
    if device_ms is None:
        raise RuntimeError("the trace holds no grid_gar_probe module")
    return {"device_ms": device_ms, "n": n, "f": f, "d": d}


def run_cell(spec, seed, seconds, trace, devices, *, device_metrics=True,
             make_cell=None, parts=None):
    """One run of the cell ``spec`` (cell.cell_spec); returns the result line
    as a dict.  ``device_metrics=False`` is for rehearsals off the chip: it
    reads no memory and no trace, and its result carries no metric."""
    import jax
    import numpy as np

    import check
    from cell import Cell, flops_per_step, load_module, peaks

    listen()
    parts = dict(parts or {})
    mark = time.perf_counter()

    def lap(name):
        nonlocal mark
        now = time.perf_counter()
        parts[name] = now - mark
        mark = now

    cell = (make_cell or Cell)(spec, devices)
    jax.block_until_ready(cell.feed.start())
    lap("data_s")
    state = cell.seeded_state(seed)
    jax.block_until_ready(state.params)
    lap("init_s")
    state, metrics = cell.multi(state, cell.feed.next())
    record = check.program_record(metrics, state.params)
    lap("first_dispatch_s")
    begin = time.perf_counter()
    state, metrics = cell.multi(state, cell.feed.next())
    wait_loss(metrics)
    dispatch_s = time.perf_counter() - begin
    nb_dispatches = max(2, round(seconds / dispatch_s))
    lap("second_dispatch_s")
    setup_s = time.perf_counter() - T0

    loaded_before = COUNTERS["programs_loaded"]
    losses, input_s = [], 0.0
    begin = time.perf_counter()
    for _ in range(nb_dispatches):
        fed = time.perf_counter()
        data = cell.feed.next()
        input_s += time.perf_counter() - fed
        state, metrics = cell.multi(state, data)
        losses.append(metrics["total_loss"])
    wait_loss(metrics)
    window_s = time.perf_counter() - begin
    loaded_in_window = COUNTERS["programs_loaded"] - loaded_before
    steps = nb_dispatches * cell.unroll
    steps_per_s = steps / window_s
    failed = int(sum(np.sum(~np.isfinite(np.asarray(loss))) for loss in losses))
    window = {"steps": steps, "dispatches": nb_dispatches, "seconds": window_s,
              "steps_per_s": steps_per_s, "programs_loaded": loaded_in_window}

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    result = {"correct": False, "attempted": steps, "failed": failed, "metrics": {},
              "device": device}
    if device_metrics:
        peak = peaks(devices[0].device_kind)
        device["memory_peak_bytes"] = max(memory_peak_bytes(d) for d in cell.devices)
        end_to_end = {
            "steps_per_s": steps_per_s,
            "mfu_pct": 100.0 * flops_per_step(spec) * steps_per_s
            / (len(cell.devices) * peak["bf16_flops_per_s"]),
            "peak_hbm_gb": device["memory_peak_bytes"] / 1e9,
            "setup_s": setup_s,
        }
        if trace:
            nb_traced = 2 if dispatch_s > 1.5 else 3  # a few seconds of device time
            state, raw, reduced = capture(cell, state, nb_traced, steps_per_s)
            # raw_trace: every device operation, module span and host annotation of the
            # traced dispatches, for a reader that the reduction does not serve
            ctx = {"trace": reduced, "raw_trace": raw, "counters": dict(COUNTERS),
                   "spans": {"input_s": input_s},
                   "window": window, "cell": spec, "peaks": peak,
                   "gar_probe": gar_probe(cell)}
            device["busy_s"], device["window_s"] = reduced["busy_s"], reduced["window_s"]
            result["breakdown"] = reduced["breakdown"]
            print("grid trace %s" % json.dumps(
                {k: v for k, v in reduced.items() if k != "breakdown"}), flush=True)
            wanted = [m for m in spec["manifest"]["per_layer"]
                      if spec["name"] in m.get("workloads", [spec["name"]])]
            for metric in wanted:
                value = load_module("layer_metrics", metric["name"]).read(ctx)
                if value is not None:
                    result["metrics"][metric["name"]] = {"value": value, "unit": metric["unit"]}
        else:
            units = {m["name"]: m["unit"] for m in spec["manifest"]["end_to_end"]}
            result["metrics"] = {name: {"value": value, "unit": units[name]}
                                 for name, value in end_to_end.items()}

    began = time.perf_counter()
    numbers = {"narrow_products": check.narrow_products(cell, state, data)}
    del state, data, metrics, losses
    cell.feed.close()
    numbers.update(check.compare(record, check.PlainReference(cell).follow(seed),
                                 spec["limits"]["limits"]))
    parts["reference_s"] = time.perf_counter() - began
    print("grid setup_parts %s" % json.dumps(dict(parts, setup_s=setup_s, **COUNTERS)),
          flush=True)
    print("grid window %s" % json.dumps(window), flush=True)
    within = check.verdict(numbers, spec["limits"]["limits"])
    result["correct"] = bool(within and failed == 0 and loaded_in_window == 0)
    if loaded_in_window:
        print("grid compare: %d program(s) compiled or loaded inside the window"
              % loaded_in_window, flush=True)
    # each number compared beside its limit: the result's last key, standard error's last lines
    result["compared"] = dict(
        {name: [float(numbers.get(name, math.nan)), limit]
         for name, limit in spec["limits"]["limits"].items()},
        failed=[failed, 0], programs_loaded=[loaded_in_window, 0])
    for name, (value, limit) in result["compared"].items():
        print("grid compared %s %r limit %r" % (name, value, limit), file=sys.stderr, flush=True)
    return result


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    if not math.isfinite(args.seconds) or args.seconds <= 0:
        raise SystemExit("--seconds must be positive")

    from cell import cell_spec

    spec = cell_spec(args.workload)
    import jax
    import aggregathor_tpu.core  # noqa: F401  (the program's imports, timed apart from its work)
    import aggregathor_tpu.gars  # noqa: F401
    import aggregathor_tpu.models  # noqa: F401
    import aggregathor_tpu.parallel  # noqa: F401
    from aggregathor_tpu.utils.compile_cache import place_compile_cache

    parts = {"imports_s": time.perf_counter() - T0}
    devices = jax.devices()
    require_chips(devices, spec["chips"])
    print("grid compile cache at %s" % place_compile_cache(), flush=True)
    parts["backend_s"] = time.perf_counter() - T0 - parts["imports_s"]
    from trace_reduce import TraceContradiction

    try:
        result = run_cell(spec, args.seed, args.seconds, bool(args.trace), devices, parts=parts)
    except TraceContradiction as contradiction:
        # none of the reduction's numbers may be printed beside it: no result line
        print("grid contradiction: %s" % contradiction, flush=True)
        raise SystemExit(CONTRADICTION_EXIT)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
