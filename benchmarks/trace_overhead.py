"""Span-tracing overhead: instrumented vs disabled vs uninstrumented.

The acceptance bar of the telemetry layer (docs/observability.md): tracing
must cost ~0% when disabled and <=5% median step latency when enabled.
This benchmark measures the REAL training dispatch three ways, same engine,
same jitted executable (``TracedCallable.inner`` is the untouched jit, so
"uninstrumented" is literally the wrapper bypassed — no rebuild, no
recompile, identical cache):

- ``uninstrumented``  call the raw jit (``step.inner``) — the pre-telemetry
  baseline;
- ``disabled``        call through the span wrapper with NO tracer
  installed — the fast path every untraced run pays (one ``None`` check
  and an inactive ``jax.profiler.TraceAnnotation``);
- ``enabled``         call through the wrapper with a tracer installed and
  the runner's companion spans (``input``/``host_gap``) simulated per step
  — the fully traced run.

Usage::

    python benchmarks/trace_overhead.py [--experiment mnist]
        [--nb-workers 8] [--gar median] [--steps 60] [--repeats 3]
        [--output overhead.json]

Emits one human table plus machine-readable JSON (schema
``aggregathor.obs.trace-overhead.v1``); ``--output`` writes the document.
The verdict line asserts the bar: enabled median overhead <= ``--bar``
percent (default 5), disabled <= ``--bar-disabled`` (default 2 — clock
jitter on a loaded 1-core CI box, not real cost).
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SCHEMA = "aggregathor.obs.trace-overhead.v1"

MODES = ("uninstrumented", "disabled", "enabled")


def build_parser():
    parser = argparse.ArgumentParser(description="span-tracing step-latency overhead")
    parser.add_argument("--experiment", default="mnist", help="experiment name (models registry)")
    parser.add_argument("--experiment-args", nargs="*", default=["batch-size:16"],
                        help="key:value experiment arguments")
    parser.add_argument("--nb-workers", type=int, default=8)
    parser.add_argument("--gar", default="median", help="aggregation rule (gars registry)")
    parser.add_argument("--steps", type=int, default=60, help="timed steps per mode per repeat")
    parser.add_argument("--repeats", type=int, default=3,
                        help="interleaved repeats (median-of-medians tames drift)")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--bar", type=float, default=5.0,
                        help="enabled-mode median overhead bar, percent")
    parser.add_argument("--bar-disabled", type=float, default=2.0,
                        help="disabled-mode median overhead bar, percent")
    parser.add_argument("--flight-capacity", type=int, default=64,
                        help="flight-recorder ring rows for the paired "
                             "recorder-on/off cell (0 skips the cell)")
    parser.add_argument("--bar-flight", type=float, default=2.0,
                        help="recorder-on median overhead bar, percent "
                             "(the ISSUE 9 acceptance bar)")
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
    from aggregathor_tpu.obs import trace
    from aggregathor_tpu.parallel import RobustEngine, make_mesh

    n = args.nb_workers
    experiment = models.instantiate(args.experiment, args.experiment_args)
    gar = gars.instantiate(args.gar, n, 0)
    tx = build_optimizer("sgd", build_schedule("fixed", ["initial-rate:0.05"]))
    engine = RobustEngine(make_mesh(nb_workers=1), gar, nb_workers=n)
    step = engine.build_step(experiment.loss, tx)
    state = engine.init_state(experiment.init(jax.random.PRNGKey(args.seed)), tx,
                              seed=args.seed + 1)
    it = experiment.make_train_iterator(n, seed=args.seed + 2)
    # one fixed device-resident batch: the benchmark times the DISPATCH path,
    # not input variation (the trace wrapper has no data dependence anyway)
    batch = engine.shard_batch(next(it))

    assert trace.installed() is None, "a leaked tracer would bias every mode"
    # warm up: compile once, fault in the data path (shared by every mode —
    # TracedCallable.inner is the same executable)
    state, metrics = step(state, batch)
    jax.block_until_ready(metrics["total_loss"])
    baseline_cache = step._cache_size()

    def run(mode, nb_steps):
        nonlocal state
        fn = step.inner if mode == "uninstrumented" else step
        samples = []
        for index in range(nb_steps):
            t0 = time.perf_counter()
            if mode == "enabled":
                # the runner's per-step companion spans, so "enabled" prices
                # the full instrumentation, not just the dispatch wrapper
                with trace.span("input", cat="train"):
                    pass
                with trace.span("host_gap", cat="train"):
                    pass
            state, metrics = fn(state, batch)
            jax.block_until_ready(metrics["total_loss"])
            samples.append(time.perf_counter() - t0)
        return samples

    # Interleave modes across repeats so thermal/CI-load drift hits them
    # all; overhead is then estimated PER REPEAT (modes adjacent in time)
    # and the median across repeats is reported — paired comparison, so a
    # load spike during one repeat cannot masquerade as tracer cost.
    samples = {mode: [] for mode in MODES}
    repeat_medians = {mode: [] for mode in MODES}
    for repeat in range(args.repeats):
        for mode in MODES:
            if mode == "enabled":
                trace.install(None, run_id="overhead-bench")  # in-memory
            try:
                chunk = run(mode, args.steps)
            finally:
                if mode == "enabled":
                    trace.uninstall(save=False)
            samples[mode] += chunk
            repeat_medians[mode].append(float(np.median(chunk)))
    assert step._cache_size() == baseline_cache, (
        "tracing recompiled the step: %d -> %d"
        % (baseline_cache, step._cache_size())
    )

    def stats(values):
        arr = np.asarray(values, np.float64)
        return {
            "median_ms": round(float(np.median(arr)) * 1e3, 4),
            "p95_ms": round(float(np.percentile(arr, 95)) * 1e3, 4),
            "mean_ms": round(float(arr.mean()) * 1e3, 4),
            "steps": int(arr.size),
        }

    # Intrinsic per-span cost (µs), resolvable where the step-level numbers
    # drown in scheduler noise: the disabled path is one global None check,
    # the enabled path one lock + append.
    def span_cost_us(nb=20000):
        t0 = time.perf_counter()
        for _ in range(nb):
            with trace.span("micro", cat="bench"):
                pass
        return (time.perf_counter() - t0) / nb * 1e6

    disabled_span_us = span_cost_us()
    trace.install(None, run_id="overhead-bench")
    try:
        enabled_span_us = span_cost_us()
    finally:
        trace.uninstall(save=False)

    modes = {mode: stats(values) for mode, values in samples.items()}
    for mode in ("disabled", "enabled"):
        per_repeat = [
            (m - base) / base * 100.0
            for m, base in zip(repeat_medians[mode], repeat_medians["uninstrumented"])
        ]
        modes[mode]["overhead_pct"] = round(float(np.median(per_repeat)), 3)
        modes[mode]["overhead_pct_per_repeat"] = [round(v, 3) for v in per_repeat]
    doc = {
        "schema": SCHEMA,
        "experiment": args.experiment,
        "platform": jax.devices()[0].platform,
        "nb_workers": n,
        "gar": args.gar,
        "steps_per_mode": args.steps * args.repeats,
        "compile_count": int(step._cache_size()),
        "modes": modes,
        "span_cost_us": {
            "disabled": round(disabled_span_us, 3),
            "enabled": round(enabled_span_us, 3),
        },
        "bar_pct": {"enabled": args.bar, "disabled": args.bar_disabled},
    }
    print("%-16s %12s %10s %10s %10s" % ("mode", "median_ms", "p95_ms", "mean_ms", "overhead"))
    for mode in MODES:
        row = modes[mode]
        print("%-16s %12.3f %10.3f %10.3f %10s" % (
            mode, row["median_ms"], row["p95_ms"], row["mean_ms"],
            "%+.2f%%" % row["overhead_pct"] if "overhead_pct" in row else "—",
        ))
    # Verdict.  The PRIMARY check is the span budget: the intrinsic enabled
    # span cost times the runner's ~4 spans/step, as a fraction of the real
    # step — deterministic, resolvable, and what the <=5% bar actually
    # bounds.  The step-level paired medians are checked too, but only fail
    # when they exceed BOTH the bar and the box's own measured noise floor
    # (the spread of the uninstrumented per-repeat medians): on a loaded CI
    # core the jitter dwarfs a microsecond-scale wrapper, and a noise spike
    # must not read as tracer cost.
    spans_per_step = 4
    base_us = modes["uninstrumented"]["median_ms"] * 1e3
    span_budget_pct = enabled_span_us * spans_per_step / base_us * 100.0
    uninstr = np.asarray(repeat_medians["uninstrumented"])
    noise_pct = float(
        (uninstr.max() - uninstr.min()) / 2.0 / np.median(uninstr) * 100.0
    )
    print("per-span cost: disabled %.2f us, enabled %.2f us "
          "(budget %.3f%% of a step at %d spans/step; box noise ±%.1f%%)"
          % (disabled_span_us, enabled_span_us, span_budget_pct,
             spans_per_step, noise_pct))

    doc["span_budget_pct"] = round(span_budget_pct, 4)
    doc["noise_pct"] = round(noise_pct, 3)

    def step_level_ok(mode, bar):
        overhead = modes[mode]["overhead_pct"]
        return overhead <= bar or overhead <= noise_pct

    ok = (
        span_budget_pct <= args.bar
        and step_level_ok("enabled", args.bar)
        and step_level_ok("disabled", args.bar_disabled)
    )

    # ---- paired flight-recorder cell (ISSUE 9): recorder-on vs -off ---- #
    # The in-scan ring is IN-GRAPH cost (unlike the host-side span
    # wrapper), so the on/off cells are two different executables over the
    # same experiment/batch — interleaved per repeat so drift hits both,
    # overhead estimated per repeat like the tracer modes.  The bar is
    # measured, not presumed: <= --bar-flight percent of step time (or the
    # box's own noise floor on a loaded CI core).
    if args.flight_capacity > 0:
        from aggregathor_tpu.obs.flight import FlightRecorder

        recorder = FlightRecorder(args.flight_capacity, n)
        engine_on = RobustEngine(make_mesh(nb_workers=1), gar, nb_workers=n,
                                 flight=recorder)
        step_on = engine_on.build_step(experiment.loss, tx)
        cells = {
            "flight_off": (step.inner, state),
            "flight_on": (
                step_on.inner,
                engine_on.init_state(
                    experiment.init(jax.random.PRNGKey(args.seed)), tx,
                    seed=args.seed + 1,
                ),
            ),
        }
        cell_states = {name: st for name, (_, st) in cells.items()}
        for name, (fn, _) in cells.items():  # warm: compile excluded
            cell_states[name], m = fn(cell_states[name], batch)
            jax.block_until_ready(m["total_loss"])
        flight_samples = {name: [] for name in cells}
        flight_repeat_medians = {name: [] for name in cells}
        for repeat in range(args.repeats):
            for name, (fn, _) in cells.items():
                chunk = []
                for _ in range(args.steps):
                    t0 = time.perf_counter()
                    cell_states[name], m = fn(cell_states[name], batch)
                    jax.block_until_ready(m["total_loss"])
                    chunk.append(time.perf_counter() - t0)
                flight_samples[name] += chunk
                flight_repeat_medians[name].append(float(np.median(chunk)))
        compile_counts = {
            "flight_off": int(step._cache_size()),
            "flight_on": int(step_on._cache_size()),
        }
        assert compile_counts["flight_on"] == compile_counts["flight_off"] == 1, (
            "the recorder changed the compile count: %r" % compile_counts
        )
        per_repeat = [
            (on - off) / off * 100.0
            for on, off in zip(flight_repeat_medians["flight_on"],
                               flight_repeat_medians["flight_off"])
        ]
        flight_overhead = float(np.median(per_repeat))
        flight_noise = np.asarray(flight_repeat_medians["flight_off"])
        flight_noise_pct = float(
            (flight_noise.max() - flight_noise.min()) / 2.0
            / np.median(flight_noise) * 100.0
        )
        flight_ok = (
            flight_overhead <= args.bar_flight
            or flight_overhead <= flight_noise_pct
        )
        doc["flight"] = {
            "capacity": args.flight_capacity,
            "modes": {name: stats(values)
                      for name, values in flight_samples.items()},
            "overhead_pct": round(flight_overhead, 3),
            "overhead_pct_per_repeat": [round(v, 3) for v in per_repeat],
            "noise_pct": round(flight_noise_pct, 3),
            "bar_pct": args.bar_flight,
            "compile_count": compile_counts,
            "within_bar": bool(flight_ok),
        }
        print("flight recorder (capacity %d): on %+.2f%% vs off "
              "(bar %.1f%%, box noise ±%.1f%%, compile %d==%d): %s"
              % (args.flight_capacity, flight_overhead, args.bar_flight,
                 flight_noise_pct, compile_counts["flight_on"],
                 compile_counts["flight_off"],
                 "OK" if flight_ok else "EXCEEDED"))
        ok = ok and flight_ok

    doc["within_bar"] = bool(ok)
    print(json.dumps(doc))
    if args.output:
        with open(args.output, "w") as fd:
            json.dump(doc, fd, indent=1)
    if not ok:
        print("OVERHEAD BAR EXCEEDED (enabled %+.2f%% bar %.1f%%; disabled "
              "%+.2f%% bar %.1f%%)" % (
                  modes["enabled"]["overhead_pct"], args.bar,
                  modes["disabled"]["overhead_pct"], args.bar_disabled),
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
