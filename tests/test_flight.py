"""Tests for the device-side observability layer (ISSUE 9): the in-scan
flight-recorder rings (obs/flight.py + both engines), the profiler
instruments (obs/profiler.py), the live trainer exporter (obs/live.py),
the regression sentinel (obs/slo.py), and the runner's shutdown-drain
satellites (--metrics-file final flush, forensics lagged-feed drain,
post-mortem dumps)."""

import json
import os
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from aggregathor_tpu import gars, models
from aggregathor_tpu.cli import runner
from aggregathor_tpu.core import build_optimizer, build_schedule
from aggregathor_tpu.obs import flight, live, profiler, slo
from aggregathor_tpu.obs.flight import FlightRecorder
from aggregathor_tpu.obs.metrics import MetricsRegistry, parse_prometheus
from aggregathor_tpu.parallel import RobustEngine, make_mesh
from aggregathor_tpu.utils import UserException


# --------------------------------------------------------------------- #
# ring mechanics (unit)


def _synthetic_metrics(i):
    return {
        "total_loss": jnp.float32(10.0 + i),
        "grad_norm": jnp.float32(i),
        "chaos_regime": jnp.int32(i % 3),
    }


def test_ring_wraparound_and_capacity():
    """Writing more steps than the capacity keeps exactly the newest C
    rows, each slot self-identified by its step lane."""
    rec = FlightRecorder(4, 2, probe=False, chaos=True)
    buffers = rec.init_buffers()
    assert rec.fetch(buffers)["step"].size == 0  # empty ring: no valid rows

    @jax.jit
    def run(buffers):
        def body(i, buf):
            return rec.record(buf, i, _synthetic_metrics(i))
        return jax.lax.fori_loop(0, 10, body, buffers)

    window = rec.fetch(run(buffers))
    np.testing.assert_array_equal(window["step"], [6, 7, 8, 9])
    np.testing.assert_array_equal(window["loss"], [16.0, 17.0, 18.0, 19.0])
    np.testing.assert_array_equal(window["chaos_regime"], [0, 1, 2, 0])


def test_ring_partial_fill_orders_by_step():
    rec = FlightRecorder(8, 2, probe=False)
    buffers = rec.init_buffers()
    for i in range(3):
        buffers = rec.record(buffers, jnp.int32(i), _synthetic_metrics(i))
    window = rec.fetch(buffers)
    np.testing.assert_array_equal(window["step"], [0, 1, 2])
    np.testing.assert_array_equal(window["update_norm"], [0.0, 1.0, 2.0])


def test_recorder_rejects_bad_config():
    with pytest.raises(UserException):
        FlightRecorder(0, 2)
    with pytest.raises(UserException):
        FlightRecorder(4, 0)


def test_recorder_engine_lane_validation():
    """A recorder configured for a lane the engine will not compute must be
    rejected at engine construction, not fail inside the trace."""
    gar = gars.instantiate("median", 4, 1)
    rec = FlightRecorder(4, 4, worker_metrics=True)
    with pytest.raises(UserException):
        RobustEngine(make_mesh(nb_workers=1), gar, nb_workers=4, flight=rec)
    with pytest.raises(UserException):  # n mismatch
        RobustEngine(make_mesh(nb_workers=1), gar, nb_workers=4,
                     flight=FlightRecorder(4, 8))


def test_dump_and_load_window_nonfinite_encoding(tmp_path):
    """Post-mortem docs are strict JSON: NaN/±inf lanes serialize as tagged
    strings (the divergence evidence must keep its kind), and load_window
    re-validates the schema."""
    rec = FlightRecorder(4, 2, probe=False)
    buffers = rec.init_buffers()
    for i, value in enumerate((1.5, float("nan"), float("inf"), float("-inf"))):
        buffers = rec.record(buffers, jnp.int32(i), {
            "total_loss": jnp.float32(value), "grad_norm": jnp.float32(i),
        })
    path = str(tmp_path / "post.json")
    doc = flight.dump_window(path, rec.fetch(buffers), run_id="r", reason="crash",
                             capacity=4, extra={"at_step": 4})
    assert doc["lanes"]["loss"] == [1.5, "nan", "inf", "-inf"]
    loaded = flight.load_window(path)
    assert loaded["schema"] == flight.SCHEMA
    assert loaded["reason"] == "crash" and loaded["extra"]["at_step"] == 4
    assert loaded["step_range"] == [0, 3]
    # a tampered document (ragged lanes) is rejected
    doc["lanes"]["loss"] = doc["lanes"]["loss"][:-1]
    with open(path, "w") as fd:
        json.dump(doc, fd)
    with pytest.raises(ValueError):
        flight.load_window(path)


def test_summarize_window_tail():
    rec = FlightRecorder(8, 2, probe=False)
    buffers = rec.init_buffers()
    for i in range(7):
        buffers = rec.record(buffers, jnp.int32(i), _synthetic_metrics(i))
    summary = flight.summarize_window(rec.fetch(buffers), tail=3)
    assert summary["rows"] == 7
    assert summary["first_step"] == 0 and summary["last_step"] == 6
    assert summary["loss"] == [14.0, 15.0, 16.0]
    assert flight.summarize_window({"step": np.zeros((0,), np.int32)}) == {"rows": 0}


# --------------------------------------------------------------------- #
# engine integration: bit identity + compile counts


def _flat_setup(nb_workers=4, flight=None, mode="flat", nb_devices=1):
    """Delegates to the suite-wide cached engine-fixture factory
    (tests/conftest.py, ISSUE 10 satellite).  ``flight`` is a (capacity,
    worker_metrics) tuple; the recorder is ``engine.flight``.  Identical
    configurations across tests share one compiled step."""
    from conftest import build_engine_stack

    exp, engine, tx, step, make_state = build_engine_stack(
        mode=mode, gar="median", n=nb_workers, f=1, nb_devices=nb_devices,
        flight=flight)
    return exp, engine, tx, step, make_state


def test_ring_bit_identical_to_metrics_unroll1():
    """Per-step dispatches: the fetched ring rows equal the per-dispatch
    metrics BIT-EXACTLY — every lane stores the same traced value."""
    exp, engine, tx, step, make_state = _flat_setup(flight=(8, True))
    rec, state = engine.flight, make_state()
    it = exp.make_train_iterator(4, seed=2)
    seen = {"loss": [], "norm": [], "spike": [], "nan": [], "dist": []}
    for _ in range(5):
        state, m = step(state, engine.shard_batch(next(it)))
        m = jax.device_get(m)
        seen["loss"].append(np.asarray(m["total_loss"]))
        seen["norm"].append(np.asarray(m["grad_norm"]))
        seen["spike"].append(np.asarray(m["probe"]["spike"]))
        seen["nan"].append(np.asarray(m["probe"]["worker_nan_rows"]))
        seen["dist"].append(np.asarray(m["worker_sq_dist"]))
    window = rec.fetch(state.flight)
    np.testing.assert_array_equal(window["step"], np.arange(5))
    np.testing.assert_array_equal(window["loss"], np.stack(seen["loss"]))
    np.testing.assert_array_equal(window["update_norm"], np.stack(seen["norm"]))
    np.testing.assert_array_equal(window["spike"], np.stack(seen["spike"]))
    np.testing.assert_array_equal(window["worker_nan"], np.stack(seen["nan"]))
    np.testing.assert_array_equal(
        window["worker_sq_dist"], np.stack(seen["dist"]))


def test_ring_bit_identical_to_metrics_unroll8():
    """One 8-step scanned dispatch: the ring's rows equal the scan's
    per-step metrics stack bit-exactly (the in-scan write IS the metric)."""
    exp, engine, tx, _, make_state = _flat_setup(flight=(8, True))
    rec, state = engine.flight, make_state()
    multi = engine.build_multi_step(exp.loss, tx)
    it = exp.make_train_iterator(4, seed=2)
    chunk = jax.tree_util.tree_map(
        lambda *xs: np.stack(xs), *[next(it) for _ in range(8)])
    state, many = multi(state, engine.shard_batches(chunk))
    many = jax.device_get(many)
    window = rec.fetch(state.flight)
    np.testing.assert_array_equal(window["step"], np.arange(8))
    np.testing.assert_array_equal(window["loss"], np.asarray(many["total_loss"]))
    np.testing.assert_array_equal(
        window["update_norm"], np.asarray(many["grad_norm"]))
    np.testing.assert_array_equal(
        window["spike"], np.asarray(many["probe"]["spike"]))
    np.testing.assert_array_equal(
        window["worker_nan"], np.asarray(many["probe"]["worker_nan_rows"]))
    np.testing.assert_array_equal(
        window["worker_sq_dist"], np.asarray(many["worker_sq_dist"]))


def test_zero_recompile_recorder_on_vs_off():
    """ACCEPTANCE: the recorder-on compile count equals the recorder-off
    run — 1 steady-state executable each for the per-step and the scanned
    trainer (the ring rides the one compiled program)."""
    counts = {}
    for label, flight in (("off", None), ("on", (8, False))):
        exp, engine, tx, step, make_state = _flat_setup(flight=flight)
        state = make_state()
        multi = engine.build_multi_step(exp.loss, tx)
        it = exp.make_train_iterator(4, seed=2)
        for _ in range(3):
            state, _ = step(state, engine.shard_batch(next(it)))
        chunk = jax.tree_util.tree_map(
            lambda *xs: np.stack(xs), *[next(it) for _ in range(4)])
        for _ in range(2):
            state, _ = multi(state, engine.shard_batches(chunk))
        from conftest import assert_zero_recompiles

        assert_zero_recompiles(step, multi)  # recorder on == off == 1
        counts[label] = (step._cache_size(), multi._cache_size())
    assert counts["on"] == counts["off"], counts


def test_sharded_engine_ring_matches_metrics():
    """The sharded dataflow writes the same ring (replicated, in-scan):
    rows bit-identical to its per-step metrics, one compile, per-worker
    lanes sized (n,).  Runs on the cheap sharded-mode stack of the unified
    engine (conftest factory) — ring parity no longer pays a transformer
    compile (ISSUE 10 satellite dedup), so it rides tier-1."""
    from conftest import assert_zero_recompiles

    exp, engine, tx, step, make_state = _flat_setup(
        mode="sharded", nb_devices=2, flight=(6, False))
    rec, state = engine.flight, make_state()
    it = exp.make_train_iterator(4, seed=2)
    losses, norms = [], []
    for _ in range(3):
        state, m = step(state, engine.shard_batch(next(it)))
        losses.append(np.asarray(jax.device_get(m["total_loss"])))
        norms.append(np.asarray(jax.device_get(m["grad_norm"])))
    assert_zero_recompiles(step)
    window = rec.fetch(state.flight)
    np.testing.assert_array_equal(window["step"], np.arange(3))
    np.testing.assert_array_equal(window["loss"], np.stack(losses))
    np.testing.assert_array_equal(window["update_norm"], np.stack(norms))
    assert window["worker_nan"].shape == (3, 4)


# --------------------------------------------------------------------- #
# profiler instruments


def test_profiler_window_parses_and_rejects():
    window = profiler.ProfilerWindow("4:8", "/tmp/nowhere")
    assert (window.begin, window.end) == (4, 8)
    assert not window.maybe_start(3)  # outside the window
    for bad in ("8:4", "4", "a:b", "-1:3", "4:4"):
        with pytest.raises(UserException):
            profiler.ProfilerWindow(bad, "/tmp/nowhere")


@pytest.mark.slow  # a real jax.profiler session costs ~13 s on this box
def test_profiler_window_captures_steps(tmp_path):
    """Open at A, annotate inside, closed at B; the capture directory is
    produced by the real jax.profiler."""
    window = profiler.ProfilerWindow("1:2", str(tmp_path / "prof"))
    assert not window.maybe_start(0)
    assert window.maybe_start(1)
    with window.annotate(1):
        jax.block_until_ready(jnp.ones((4,)) * 2)
    assert not window.maybe_stop(1)
    assert window.maybe_stop(2)
    assert window.done and not window.active
    assert not window.maybe_start(1)  # never reopens
    assert os.path.isdir(str(tmp_path / "prof"))


def test_compile_watch_names_misses_with_shapes():
    """A wrapped executable's cache growth is reported with the executable
    name and the triggering abstract shapes; steady-state calls report
    nothing."""
    reg = MetricsRegistry()
    events = []

    class FakeSummaries:
        def event(self, step, tag, payload):
            events.append((step, tag, payload))

    watch = profiler.CompileWatch(reg, summaries=FakeSummaries(),
                                  step_provider=lambda: 7)
    fn = watch.wrap("double", jax.jit(lambda x: x * 2))
    assert watch.wrap("double", fn) is fn  # idempotent
    fn(jnp.ones((3,), jnp.float32))
    fn(jnp.ones((3,), jnp.float32))  # cache hit: no new miss
    fn(jnp.ones((4, 4), jnp.float32))  # retrace
    names = [name for name, _, _ in watch.misses]
    assert names == ["double", "double"]
    # the counter sees both misses; the summary EVENT fires only for the
    # true retrace — the first compile of an executable is expected
    assert len(events) == 1
    assert events[-1][0] == 7 and events[-1][1] == "compile_cache_miss"
    assert "float32[4,4]" in events[-1][2]["arg_shapes"]
    counter = reg.counter("compile_cache_misses_total",
                          labelnames=("executable",))
    assert counter.labels(executable="double").value == 2.0
    assert fn._cache_size() == 2  # attribute fallthrough to the jit


def test_compile_listener_counts_backend_compiles():
    reg = MetricsRegistry()
    profiler.install_compile_listener(reg)
    families = {f.name: f for f in reg.families()}
    before = families["compile_backend_total"].value
    jax.jit(lambda x: x + jnp.float32(12345))(jnp.float32(1.0))  # fresh shape
    assert families["compile_backend_total"].value >= before + 1


def test_memory_gauges_with_fake_devices():
    """memory_stats-reporting devices get live/peak gauges; stat-less
    devices (XLA:CPU) register nothing."""
    reg = MetricsRegistry()

    class FakeDevice:
        def __init__(self, stats):
            self._stats = stats

        def memory_stats(self):
            return self._stats

    devices = [FakeDevice({"bytes_in_use": 123, "peak_bytes_in_use": 456}),
               FakeDevice(None)]
    assert profiler.install_memory_gauges(reg, devices=devices) == 1
    live_gauge = reg.gauge("device_memory_live_bytes", labelnames=("device",))
    peak_gauge = reg.gauge("device_memory_peak_bytes", labelnames=("device",))
    assert live_gauge.labels(device="0").value == 123.0
    assert peak_gauge.labels(device="0").value == 456.0
    devices[0]._stats["bytes_in_use"] = 999  # scrape-time: reads live
    assert live_gauge.labels(device="0").value == 999.0
    assert profiler.install_memory_gauges(
        reg, devices=jax.devices()) == 0  # XLA:CPU reports no stats


# --------------------------------------------------------------------- #
# live exporter


def test_live_exporter_scrape_roundtrip():
    """/metrics round-trips the strict Prometheus parser, /status carries
    the provider payload, /healthz answers, unknown paths 404."""
    reg = MetricsRegistry()
    reg.counter("fl_test_total", "x").inc(3)
    server = live.LiveExporter(
        registry=reg, run_id="live-test",
        status_provider=lambda: {"step": 12, "flight": {"rows": 4}})
    host, port = server.serve_background()
    base = "http://%s:%d" % (host, port)
    try:
        text = urllib.request.urlopen(base + "/metrics", timeout=10).read().decode()
        parsed = parse_prometheus(text)
        samples = dict(
            (n, v) for n, _, v in parsed["fl_test_total"]["samples"])
        assert samples["fl_test_total"] == 3.0
        snap = json.loads(urllib.request.urlopen(
            base + "/metrics?format=json", timeout=10).read())
        assert snap["fl_test_total"] == 3.0
        status = json.loads(urllib.request.urlopen(
            base + "/status", timeout=10).read())
        assert status["run_id"] == "live-test" and status["step"] == 12
        assert status["flight"] == {"rows": 4}
        health = json.loads(urllib.request.urlopen(
            base + "/healthz", timeout=10).read())
        assert health["status"] == "ok"
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(base + "/nope", timeout=10)
        # the scrape counter itself is on the registry
        scrapes = reg.counter("live_scrapes_total", labelnames=("endpoint",))
        assert scrapes.labels(endpoint="metrics").value == 2.0
    finally:
        server.shutdown_all()


def test_live_exporter_status_provider_error_degrades():
    reg = MetricsRegistry()

    def broken():
        raise RuntimeError("loop state gone")

    server = live.LiveExporter(registry=reg, status_provider=broken)
    host, port = server.serve_background()
    try:
        status = json.loads(urllib.request.urlopen(
            "http://%s:%d/status" % (host, port), timeout=10).read())
        assert "loop state gone" in status["error"]
    finally:
        server.shutdown_all()


# --------------------------------------------------------------------- #
# regression sentinel


def test_sentinel_pass_regress_and_skip(tmp_path):
    path = str(tmp_path / "base.json")
    slo.capture(path, {"steps_per_s": 100.0, "gar_seconds_total": 2.0},
                run_id="seed", tolerances={"steps_per_s": 0.2})
    sentinel = slo.Sentinel(path)
    verdict = sentinel.verdict(
        {"steps_per_s": 85.0, "gar_seconds_total": 2.3}, run_id="now")
    assert verdict["verdict"] == "PASS" and verdict["regressed"] == 0
    by_name = {c["metric"]: c for c in verdict["checks"]}
    assert by_name["steps_per_s"]["status"] == "ok"
    assert by_name["gar_seconds_total"]["status"] == "ok"  # lower-is-better
    # throughput collapse -> REGRESS
    verdict = sentinel.verdict({"steps_per_s": 50.0, "gar_seconds_total": 2.0})
    assert verdict["verdict"] == "REGRESS" and verdict["regressed"] == 1
    # cost blow-up on the lower-is-better metric -> REGRESS
    verdict = sentinel.verdict({"steps_per_s": 100.0, "gar_seconds_total": 9.0})
    assert verdict["verdict"] == "REGRESS"
    # an unmeasured metric is SKIPPED, never a fabricated regression
    verdict = sentinel.verdict({"steps_per_s": 100.0})
    assert verdict["verdict"] == "PASS"
    assert {c["metric"]: c["status"] for c in verdict["checks"]}[
        "gar_seconds_total"] == "skipped"
    out = str(tmp_path / "verdict.json")
    slo.save_verdict(out, verdict)
    assert json.load(open(out))["schema"] == slo.SCHEMA + ".verdict"
    assert "SLO PASS" in slo.describe_verdict(verdict)


def test_sentinel_rejects_bad_baselines(tmp_path):
    with pytest.raises(UserException):
        slo.Sentinel(str(tmp_path / "missing.json"))
    bad = str(tmp_path / "bad.json")
    with open(bad, "w") as fd:
        json.dump({"schema": "other.v1"}, fd)
    with pytest.raises(UserException):
        slo.Sentinel(bad)
    with open(bad, "w") as fd:
        json.dump({"schema": slo.SCHEMA, "metrics": {}}, fd)
    with pytest.raises(UserException):
        slo.Sentinel(bad)


def test_collect_current_skips_unmeasured():
    """Zero/absent instruments stay OUT of the current dict (a zero would
    read as an infinite throughput regression)."""
    reg = MetricsRegistry()

    class FakePerf:
        nb_steps = 10

        def steps_per_s_excl_first(self):
            return 42.0

    current = slo.collect_current(reg, FakePerf())
    assert current == {"steps_per_s": 42.0}
    reg.counter("gar_seconds_total", "x").inc(1.5)
    reg.gauge("input_overlap_fraction", "x").set(0.8)
    current = slo.collect_current(reg, FakePerf())
    assert current["gar_seconds_total"] == 1.5
    assert current["input_overlap_fraction"] == 0.8


# --------------------------------------------------------------------- #
# forensics attachment


def test_ledger_attach_flight_survives_truncation():
    from aggregathor_tpu.obs.forensics import ForensicsLedger

    ledger = ForensicsLedger(4, run_id="r")
    for step in range(6):
        ledger.observe(step + 1, worker_sq_dist=np.ones(4))
    ledger.attach_flight(6, "guardian_rollback", path="/tmp/x.json",
                         window_summary={"rows": 6, "last_step": 5})
    ledger.truncate_after(2)
    report = ledger.report()
    assert report["flight_postmortems"] == [{
        "at_step": 6, "reason": "guardian_rollback", "path": "/tmp/x.json",
        "window": {"rows": 6, "last_step": 5},
    }]


# --------------------------------------------------------------------- #
# runner end-to-end: satellites + acceptance


BASE_ARGS = [
    "--experiment", "mnist", "--experiment-args", "batch-size:16",
    "--aggregator", "median", "--nb-workers", "4",
    "--nb-decl-byz-workers", "1", "--learning-rate-args", "initial-rate:0.05",
    "--evaluation-delta", "-1", "--evaluation-period", "-1", "--prefetch", "0",
]


def test_runner_metrics_file_flushed_without_summary_fire(tmp_path):
    """SATELLITE: a run whose summary cadence never fires still exits with
    a parseable --metrics-file (the final flush is independent of cadence
    fires and of the other telemetry writers)."""
    prom = str(tmp_path / "train.prom")
    assert 0 == runner.main(BASE_ARGS + [
        "--max-step", "3",
        "--summary-delta", "-1", "--summary-period", "-1",
        "--metrics-file", prom,
    ])
    parsed = parse_prometheus(open(prom).read())
    samples = dict(
        (n, v) for n, _, v in parsed["train_steps_total"]["samples"])
    assert samples["train_steps_total"] >= 3.0


def test_runner_forensics_drains_final_dispatch(tmp_path):
    """SATELLITE: the forensics feed runs one dispatch behind — the report
    must still cover the FINAL dispatch's steps (drained at shutdown, not
    dropped)."""
    report_path = str(tmp_path / "forensics.json")
    assert 0 == runner.main(BASE_ARGS + [
        "--max-step", "8", "--unroll", "4",
        "--summary-delta", "4", "--forensics", report_path,
    ])
    report = json.load(open(report_path))
    assert report["steps_observed"] == 8
    assert report["step_range"] == [1, 8]


def test_runner_flight_fetch_and_gauges(tmp_path):
    """--flight: summary fires fetch the ring (counter + gauges on the one
    registry), and the run completes with zero behavior change."""
    prom = str(tmp_path / "train.prom")
    assert 0 == runner.main(BASE_ARGS + [
        "--max-step", "8", "--unroll", "4", "--flight", "8",
        "--summary-delta", "4", "--metrics-file", prom,
    ])
    parsed = parse_prometheus(open(prom).read())
    fetches = dict(
        (n, v) for n, _, v in parsed["flight_fetches_total"]["samples"])
    assert fetches["flight_fetches_total"] >= 1.0
    last = dict((n, v) for n, _, v in parsed["flight_last_step"]["samples"])
    assert last["flight_last_step"] == 8.0


def test_runner_flight_postmortem_on_divergence(tmp_path):
    """SATELLITE/ACCEPTANCE: an injected divergence dumps the ring with the
    exact per-step evidence (NaN loss lane, per-worker NaN flags, the chaos
    regime that did it)."""
    dump = str(tmp_path / "crash.json")
    with pytest.raises(UserException):
        runner.main([
            "--experiment", "mnist", "--experiment-args", "batch-size:16",
            "--aggregator", "average", "--nb-workers", "4",
            "--nb-decl-byz-workers", "1", "--nb-real-byz-workers", "1",
            "--chaos", "0:calm 4:attack=inf",
            "--learning-rate-args", "initial-rate:0.05",
            "--evaluation-delta", "-1", "--evaluation-period", "-1",
            "--prefetch", "0",
            "--max-step", "12", "--unroll", "4", "--flight", "8",
            "--flight-dump", dump, "--summary-delta", "50",
        ])
    doc = flight.load_window(dump)
    assert doc["reason"] == "divergence"
    steps = doc["lanes"]["step"]
    # the attack regime begins at in-graph step 4: the ring must hold NaN
    # loss rows and name every worker's NaN submission flags
    attacked = [i for i, s in enumerate(steps) if s >= 4]
    assert attacked and all(
        doc["lanes"]["loss"][i] == "nan" for i in attacked[1:])
    assert any(sum(doc["lanes"]["worker_nan"][i]) > 0 for i in attacked)
    assert all(doc["lanes"]["chaos_regime"][i] == 1 for i in attacked)


def test_runner_flight_rejects_bad_flags():
    with pytest.raises(UserException):
        runner.main(BASE_ARGS + ["--max-step", "2", "--flight", "-1"])
    with pytest.raises(UserException):
        runner.main(BASE_ARGS + [
            "--max-step", "2", "--flight-dump", "/tmp/x.json"])
    with pytest.raises(UserException):
        runner.main(BASE_ARGS + [
            "--max-step", "2", "--live-ready-file", "/tmp/r"])


@pytest.mark.slow  # two full runner mains; the regress test keeps tier-1 coverage
def test_runner_slo_capture_then_verdict(tmp_path):
    """End-to-end sentinel loop: a capture run seeds the baseline, the next
    run judges itself PASS against it and writes the verdict document +
    summary event."""
    baseline = str(tmp_path / "slo.json")
    assert 0 == runner.main(BASE_ARGS + [
        "--max-step", "6", "--summary-delta", "3",
        "--slo-capture", baseline,
    ])
    doc = json.load(open(baseline))
    assert doc["schema"] == slo.SCHEMA and "steps_per_s" in doc["metrics"]
    verdict_path = str(tmp_path / "verdict.json")
    sum_dir = str(tmp_path / "sum")
    assert 0 == runner.main(BASE_ARGS + [
        "--max-step", "6", "--summary-delta", "3", "--summary-dir", sum_dir,
        "--slo-baseline", baseline, "--slo-verdict", verdict_path,
    ])
    verdict = json.load(open(verdict_path))
    assert verdict["verdict"] in ("PASS", "REGRESS")
    # the process-wide registry may carry metrics from earlier tests in
    # this pytest process (overlap/gar gauges are get-or-create), so only
    # the always-measured metric is pinned
    assert "steps_per_s" in {c["metric"] for c in verdict["checks"]}
    events = [json.loads(line)
              for name in os.listdir(sum_dir)
              for line in open(os.path.join(sum_dir, name))]
    slo_events = [e for e in events if e.get("event") == "slo_verdict"]
    assert len(slo_events) == 1
    assert slo_events[0]["verdict"] == verdict["verdict"]


def test_runner_slo_regress_verdict(tmp_path):
    """A baseline demanding impossible throughput must produce REGRESS."""
    baseline = str(tmp_path / "slo.json")
    slo.capture(baseline, {"steps_per_s": 1e9}, run_id="impossible")
    verdict_path = str(tmp_path / "verdict.json")
    assert 0 == runner.main(BASE_ARGS + [
        "--max-step", "4", "--summary-delta", "2",
        "--slo-baseline", baseline, "--slo-verdict", verdict_path,
    ])
    assert json.load(open(verdict_path))["verdict"] == "REGRESS"


@pytest.mark.slow  # 60-step threaded run; the unit scrape + smoke cover tier-1
def test_runner_live_exporter_scrapes_training_process(tmp_path):
    """The live exporter serves /metrics + /status for a real training run
    (in-process here; the smoke script covers the separate-process scrape),
    and the ready-file handshake publishes the bound address."""
    import threading

    ready = str(tmp_path / "ready")
    done = {"rc": None}

    def train():
        done["rc"] = runner.main(BASE_ARGS + [
            "--max-step", "60", "--unroll", "4", "--flight", "8",
            "--summary-delta", "4",
            "--live-port", "0", "--live-ready-file", ready,
        ])

    thread = threading.Thread(target=train, daemon=True)
    thread.start()
    import time

    addr = None
    for _ in range(600):
        if os.path.exists(ready):
            addr = open(ready).read().split()
            break
        time.sleep(0.05)
    assert addr, "live exporter never published its address"
    base = "http://%s:%s" % (addr[0], addr[1])
    status = None
    for _ in range(600):
        if not thread.is_alive():
            break
        try:
            status = json.loads(urllib.request.urlopen(
                base + "/status", timeout=5).read())
            if status.get("flight") and status["flight"].get("rows"):
                break
        except (OSError, urllib.error.URLError):
            pass
        time.sleep(0.02)
    thread.join(120)
    assert done["rc"] == 0
    assert status is not None and status["flight"]["rows"] >= 1, status


@pytest.mark.slow  # guardian breakdown run; the divergence dump keeps tier-1 coverage
def test_runner_guardian_rollback_dumps_flight(tmp_path):
    """A guardian rollback dumps the diverged window (suffixed per
    rollback) and attaches it to the forensics report."""
    dump = str(tmp_path / "flight.json")
    report_path = str(tmp_path / "forensics.json")
    assert 0 == runner.main([
        "--experiment", "mnist", "--experiment-args", "batch-size:16",
        "--nb-workers", "8", "--nb-decl-byz-workers", "2",
        "--nb-real-byz-workers", "2",
        "--chaos", "0:calm 8:attack=inf",
        "--max-step", "30", "--learning-rate-args", "initial-rate:0.05",
        "--evaluation-delta", "-1", "--evaluation-period", "-1",
        "--prefetch", "0",
        "--aggregator", "average",
        "--guardian", "--guardian-args", "ladder:gar=median", "recover:5",
        "--checkpoint-dir", str(tmp_path / "ckpt"),
        "--checkpoint-delta", "4", "--checkpoint-period", "-1",
        "--summary-delta", "5",
        "--flight", "8", "--flight-dump", dump,
        "--forensics", report_path,
    ])
    dumps = [name for name in os.listdir(str(tmp_path))
             if name.startswith("flight.rollback-")]
    assert dumps, "rollback left no flight dump"
    doc = flight.load_window(str(tmp_path / sorted(dumps)[0]))
    assert doc["reason"] == "guardian_rollback"
    assert "nan" in doc["lanes"]["loss"] or "inf" in doc["lanes"]["loss"]
    report = json.load(open(report_path))
    assert report["flight_postmortems"]
    assert report["flight_postmortems"][0]["reason"] == "guardian_rollback"
