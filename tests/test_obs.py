"""Tests for the observability layer: cadences, checkpoints, eval TSV, and
the telemetry pillars — span tracing (Chrome trace JSON), the process-wide
metrics registry (Prometheus exposition round-trip), and the Byzantine
forensics ledger (attribution on synthetic and real suspicion streams)."""

import json
import os
import threading
import time

import jax
import numpy as np
import optax
import pytest

from aggregathor_tpu.core import TrainState
from aggregathor_tpu.obs import CadenceTrigger, Checkpoints, EvalFile
from aggregathor_tpu.obs import trace
from aggregathor_tpu.obs.forensics import ForensicsLedger, binom_sf, render_markdown
from aggregathor_tpu.obs.metrics import (
    MetricsRegistry,
    parse_prometheus,
)
from aggregathor_tpu.utils import UserException


@pytest.fixture
def tracer(tmp_path):
    """A process-installed tracer torn down afterwards (the module global
    must never leak into other tests)."""
    t = trace.install(str(tmp_path / "out.trace.json"), run_id="test-run")
    yield t
    trace.uninstall(save=False)


def test_cadence_delta():
    trig = CadenceTrigger(delta=10, period=-1.0)
    assert trig.should_fire(0)  # fires once at start
    trig.fired(0)
    assert not trig.should_fire(9)
    assert trig.should_fire(10)
    trig.fired(10)
    assert not trig.should_fire(19)
    assert trig.should_fire(25)


def test_cadence_disabled():
    trig = CadenceTrigger(delta=-1, period=-1.0)
    assert not trig.enabled
    assert not trig.should_fire(0)


def test_cadence_period():
    trig = CadenceTrigger(delta=-1, period=0.0)
    trig.fired(0)
    assert trig.should_fire(1)  # period 0: every opportunity


def _tiny_state(value=0.0):
    params = {"w": np.full((3,), value, np.float32), "b": np.zeros((2,), np.float32)}
    tx = optax.sgd(0.1)
    return TrainState.create(params, tx), tx


def test_checkpoints_roundtrip(tmp_path):
    state, _ = _tiny_state(1.5)
    ckpts = Checkpoints(str(tmp_path), "model", max_to_keep=2)
    assert not ckpts.can_restore()
    with pytest.raises(UserException):
        ckpts.restore(state)
    ckpts.save(state, 5)
    state2, _ = _tiny_state(9.9)
    restored, step = ckpts.restore(state2)
    assert step == 5
    np.testing.assert_allclose(np.asarray(restored.params["w"]), 1.5)


def test_checkpoints_exclude_clever_carry(tmp_path):
    """The CLEVER carry is a transport buffer, not model state: snapshots must
    not contain it (size) and must restore into templates with or without one
    (compatibility both ways), re-zeroing the buffer like the reference's
    restarted PS reallocates its reassembly one."""
    state, _ = _tiny_state(2.5)
    big = np.ones((4, 1 << 16), np.float32)  # 1 MB: would be visible in the file
    ckpts = Checkpoints(str(tmp_path), "model")
    path = ckpts.save(state.replace(carry=big), 3)
    assert os.path.getsize(path) < big.nbytes // 2, "carry leaked into the snapshot"
    # restore into a clever template: params come back, carry stays the template's
    template, _ = _tiny_state(0.0)
    zeros = np.zeros_like(big)
    restored, step = ckpts.restore(template.replace(carry=zeros))
    assert step == 3
    np.testing.assert_allclose(np.asarray(restored.params["w"]), 2.5)
    np.testing.assert_allclose(np.asarray(restored.carry), 0.0)
    # restore into a carry-less template (old snapshot shape) also works
    restored2, _ = ckpts.restore(template)
    assert restored2.carry is None
    np.testing.assert_allclose(np.asarray(restored2.params["w"]), 2.5)


def test_checkpoints_latest_and_prune(tmp_path):
    state, _ = _tiny_state()
    ckpts = Checkpoints(str(tmp_path), "model", max_to_keep=2)
    for step in (3, 7, 11):
        ckpts.save(state.replace(step=jax.numpy.int32(step)), step)
    assert ckpts.steps() == [7, 11]  # pruned to 2, oldest dropped
    _, step = ckpts.restore(state)
    assert step == 11


def test_eval_file_format(tmp_path):
    path = str(tmp_path / "eval")
    ef = EvalFile(path)
    ef.append(42, {"accuracy": 0.5, "xent": 1.25})
    ef.close()
    with open(path) as fd:
        fields = fd.read().strip().split("\t")
    assert fields[1] == "42"
    assert "accuracy:0.5" in fields
    float(fields[0])  # walltime parses


def test_eval_file_disabled():
    ef = EvalFile(None)
    ef.append(0, {"a": 1.0})  # no-op, no crash
    ef.close()


def test_background_checkpoints_equivalent(tmp_path):
    """background=True writes the same bytes as the synchronous path; wait()
    flushes, and a failing write surfaces at wait() — not silently."""
    import flax.serialization
    import jax
    import numpy as np
    import optax
    import pytest

    from aggregathor_tpu.core.train_state import TrainState
    from aggregathor_tpu.obs.checkpoint import Checkpoints

    params = {"w": np.arange(6, dtype=np.float32).reshape(2, 3)}
    state = TrainState.create(params, optax.sgd(0.1), rng=jax.random.PRNGKey(0))
    sync_dir, bg_dir = str(tmp_path / "sync"), str(tmp_path / "bg")
    Checkpoints(sync_dir).save(state, 7)
    bg = Checkpoints(bg_dir, background=True)
    bg.save(state, 7)
    bg.wait()
    a = open(os.path.join(sync_dir, "model-7.ckpt"), "rb").read()
    b = open(os.path.join(bg_dir, "model-7.ckpt"), "rb").read()
    assert a == b
    restored, step = bg.restore(state)
    assert step == 7
    np.testing.assert_array_equal(np.asarray(restored.params["w"]), params["w"])
    # failure path: a write error surfaces at wait() — not silently.
    # (chmod tricks don't fail under root: replace the directory by a file.)
    bad_dir = str(tmp_path / "bad")
    bad = Checkpoints(bad_dir, background=True)
    os.rmdir(bad_dir)
    open(bad_dir, "w").close()
    bad.save(state, 9)
    with pytest.raises(OSError):
        bad.wait()


def test_summary_nonfinite_serializes_null(tmp_path):
    """Non-finite scalars/vector entries become JSON null, never a bare NaN
    token (strict-JSON readers reject those) — ADVICE r2 finding 1."""
    import json

    import numpy as np

    from aggregathor_tpu.obs.summaries import SummaryWriter

    sw = SummaryWriter(str(tmp_path), run_name="t")
    sw.scalars(3, {
        "loss": float("nan"),
        "worker_sq_dist": np.array([1.0, np.nan, np.inf, 4.0]),
        "suspect_worker": 3,
    })
    sw.close()
    line = open(sw.path).read().strip()
    event = json.loads(line, parse_constant=lambda s: pytest.fail("bare %s token" % s))
    assert event["loss"] is None
    assert event["worker_sq_dist"] == [1.0, None, None, 4.0]
    assert event["suspect_worker"] == 3


def test_checkpoints_wait_shutdown_retires_thread(tmp_path):
    """wait(shutdown=True) joins the worker thread (ADVICE r2 finding 3)."""
    import jax
    import numpy as np
    import optax

    from aggregathor_tpu.core.train_state import TrainState
    from aggregathor_tpu.obs.checkpoint import Checkpoints

    state = TrainState.create(
        {"w": np.zeros(3, np.float32)}, optax.sgd(0.1), rng=jax.random.PRNGKey(0)
    )
    ckpt = Checkpoints(str(tmp_path / "c"), background=True)
    ckpt.save(state, 1)
    ckpt.wait()  # plain wait keeps the pool usable
    assert ckpt._pool is not None
    pool = ckpt._pool
    ckpt.save(state, 2)
    ckpt.wait(shutdown=True)
    assert ckpt._pool is None
    # THIS instance's worker thread is retired (other tests' Checkpoints may
    # have live "ckpt" threads, so a global threading.enumerate scan is racy)
    assert all(not t.is_alive() for t in pool._threads)
    assert ckpt.steps() == [1, 2]


# --------------------------------------------------------------------- #
# pillar 1: span tracing (obs/trace.py)


def test_span_nesting_and_chrome_schema(tracer):
    """Nested spans record parent/depth, an instant event lands, and the
    written file is structurally valid Chrome trace JSON carrying the
    run_id in its metadata."""
    with trace.span("outer", cat="test", step=3):
        with trace.span("inner", cat="test"):
            pass
        trace.instant("tick", cat="test", k=1)
    path = trace.save()
    payload = json.load(open(path))
    events = trace.validate_chrome_trace(payload)
    assert payload["otherData"]["run_id"] == "test-run"
    by_name = {e["name"]: e for e in events if e["ph"] in ("X", "i")}
    assert by_name["inner"]["args"] == {"parent": "outer", "depth": 1}
    assert by_name["outer"]["args"] == {"step": 3}
    assert by_name["tick"]["ph"] == "i" and by_name["tick"]["args"] == {"k": 1}
    # "inner" nests inside "outer" by time containment (how Perfetto nests)
    outer, inner = by_name["outer"], by_name["inner"]
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6


def test_span_decorator_and_error_annotation(tracer):
    @trace.span("work", cat="test")
    def work(x):
        return x + 1

    assert work(1) == 2

    with pytest.raises(ValueError):
        with trace.span("broken", cat="test"):
            raise ValueError("boom")
    events = {e["name"]: e for e in json.load(open(trace.save()))["traceEvents"]}
    assert events["work"]["ph"] == "X"
    assert events["broken"]["args"]["error"] == "ValueError"


def test_span_disabled_is_noop(tmp_path):
    """With no tracer installed every entry point is a cheap no-op."""
    assert trace.installed() is None
    with trace.span("nothing"):
        pass
    trace.instant("nothing")
    assert trace.save() is None
    assert trace.uninstall() is None


def test_span_thread_safety(tracer):
    """Concurrent spans from many threads all land; per-thread nesting
    stacks do not cross-talk (each thread sees its own parent chain)."""
    errors = []

    def worker(tid):
        try:
            for i in range(50):
                with trace.span("outer-%d" % tid, cat="t"):
                    with trace.span("inner-%d" % tid, cat="t"):
                        pass
        except Exception as exc:  # pragma: no cover
            errors.append(exc)

    threads = [threading.Thread(target=worker, args=(t,)) for t in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    events = trace.validate_chrome_trace(json.load(open(trace.save())))
    spans = [e for e in events if e["ph"] == "X" and e["cat"] == "t"]  # not the start-up's
    assert len(spans) == 8 * 50 * 2
    for event in spans:
        name = event["name"]
        if name.startswith("inner-"):
            tid = name.split("-")[1]
            assert event["args"]["parent"] == "outer-%s" % tid


def test_trace_event_cap_counts_drops(tmp_path, monkeypatch):
    monkeypatch.setattr(trace, "MAX_EVENTS", 10)
    tracer = trace.Tracer(str(tmp_path / "cap.json"))
    for i in range(50):
        tracer.instant("e%d" % i)
    assert tracer.nb_events <= 10
    payload = json.load(open(tracer.save()))
    assert payload["otherData"]["dropped_events"] > 0
    trace.validate_chrome_trace(payload)


def test_validate_chrome_trace_rejects_malformed():
    with pytest.raises(ValueError):
        trace.validate_chrome_trace({"notTraceEvents": []})
    with pytest.raises(ValueError):
        trace.validate_chrome_trace({"traceEvents": [{"ph": "X", "name": "x"}]})
    with pytest.raises(ValueError):
        trace.validate_chrome_trace({"traceEvents": [
            {"ph": "X", "name": "x", "pid": 1, "tid": 1, "ts": 0.0, "dur": -5.0},
        ]})


def test_traced_callable_falls_through_and_adds_zero_compiles(tracer):
    """The TracedCallable wrapper never touches the jit: attribute access
    (``_cache_size``) falls through, and calling through the wrapper with
    tracing enabled does not retrace."""
    jitted = jax.jit(lambda x: x * 2.0)
    wrapped = trace.traced("double.dispatch", jitted)
    assert float(wrapped(np.float32(1.0))) == 2.0
    baseline = wrapped._cache_size()
    for _ in range(3):
        wrapped(np.float32(3.0))
    assert wrapped._cache_size() == baseline
    assert wrapped.inner is jitted
    events = [e for e in json.load(open(trace.save()))["traceEvents"]
              if e["name"] == "double.dispatch"]
    assert len(events) == 4


def test_engine_instrumentation_zero_extra_compiles():
    """Acceptance: the instrumented engine's dispatch is a traced wrapper
    over ONE jitted executable — running with tracing off, then ENABLING
    tracing mid-run, leaves the compile count at exactly 1 (the span layer
    is host-side only) while dispatch spans appear in the trace."""
    from aggregathor_tpu import gars, models
    from aggregathor_tpu.core import build_optimizer, build_schedule
    from aggregathor_tpu.parallel import RobustEngine, make_mesh

    exp = models.instantiate("mnist", ["batch-size:16"])
    gar = gars.instantiate("median", 4, 1)
    tx = build_optimizer("sgd", build_schedule("fixed", ["initial-rate:0.05"]))
    engine = RobustEngine(make_mesh(nb_workers=1), gar, nb_workers=4)
    step = engine.build_step(exp.loss, tx)
    state = engine.init_state(exp.init(jax.random.PRNGKey(0)), tx, seed=1)
    it = exp.make_train_iterator(4, seed=2)
    assert trace.installed() is None
    for _ in range(2):
        state, _ = step(state, engine.shard_batch(next(it)))
    assert step._cache_size() == 1
    tracer = trace.install(None)  # in-memory tracer: no file path needed
    try:
        for _ in range(2):
            state, _ = step(state, engine.shard_batch(next(it)))
        assert step._cache_size() == 1, "enabling tracing retraced the step"
        names = [e["name"] for e in tracer._events]
        assert names.count("train_step.dispatch") == 2
    finally:
        trace.uninstall(save=False)


# --------------------------------------------------------------------- #
# the start-up record (obs/trace.py ``startup``, ``startup_record``)


@pytest.fixture
def startup_fresh(monkeypatch):
    """An empty start-up record for the test; the process's own comes back."""
    monkeypatch.setattr(trace, "_startup_events", [])
    monkeypatch.setattr(trace, "_startup_dropped", 0)


def _self_seconds(events):
    own = {event["id"]: event["dur_s"] for event in events}
    for event in events:
        if event["parent"] is not None:
            own[event["parent"]] -= event["dur_s"]
    return own


def test_startup_record_is_bounded_and_counts_drops(startup_fresh, monkeypatch):
    monkeypatch.setattr(trace, "STARTUP_MAX_EVENTS", 5)
    for i in range(4):
        with trace.startup("startup.part", index=i):
            trace.startup_event("compile.load", 0.0, 0.0, program="p%d" % i)
    record = trace.startup_record()
    assert len(record["events"]) == 5 and record["dropped"] == 3 and record["limit"] == 5
    assert [event["id"] for event in record["events"]] == list(range(5))
    # a span the full record dropped is no parent, and leaves the stack as it found it
    with trace.startup("startup.late"):
        pass
    assert trace._startup_stack() == [] and trace.startup_record()["dropped"] == 4


def test_startup_parent_and_self_time_under_nesting_and_two_threads(startup_fresh):
    def work(label):
        with trace.startup("startup.outer", label=label):
            with trace.startup("startup.inner", label=label) as inner:
                time.sleep(0.01)
                inner.note(bytes=7)
            with trace.startup("startup.inner", label=label):
                time.sleep(0.01)

    other = threading.Thread(target=work, args=("other",))
    other.start()
    work("main")
    other.join()
    events = trace.startup_record()["events"]
    assert len(events) == 6 and len({event["thread"] for event in events}) == 2
    by_id = {event["id"]: event for event in events}
    own = _self_seconds(events)
    for outer in (event for event in events if event["name"] == "startup.outer"):
        assert outer["parent"] is None
        inners = [event for event in events if event["parent"] == outer["id"]]
        assert [event["name"] for event in inners] == ["startup.inner"] * 2
        # a thread's spans nest in that thread's spans only
        assert {event["thread"] for event in inners} == {outer["thread"]}
        assert {event["args"]["label"] for event in inners} == {outer["args"]["label"]}
        assert inners[0]["args"]["bytes"] == 7 and "bytes" not in inners[1]["args"]
        assert sum(event["dur_s"] for event in inners) <= outer["dur_s"]
        assert 0 <= own[outer["id"]] < outer["dur_s"] - 0.015
    assert all(by_id[event["parent"]]["name"] == "startup.outer"
               for event in events if event["name"] == "startup.inner")


def test_startup_events_nest_by_time_not_by_order_of_report(startup_fresh):
    """JAX reports an inner stage when it ends, before the one round it: the
    record sorts them out by time, within one thread and one start-up span."""
    with trace.startup("startup.first_call"):
        trace.startup_event("compile.trace", 1.2, 0.1, program="_where")
        trace.startup_event("compile.trace", 1.1, 0.3, program="inner")
        trace.startup_event("compile.trace", 1.5, 0.1, program="add")
        trace.startup_event("compile.trace", 1.0, 1.0, program="outer")
        trace.startup_event("compile.lower", 2.0, 0.5, program="jit(outer)")
    trace.startup_event("compile.trace", 1.05, 0.5, program="elsewhere")  # under no span
    events = trace.startup_record()["events"]
    named = {event["args"].get("program"): event for event in events}
    first_call = events[0]["id"]
    assert named["outer"]["parent"] == first_call == named["jit(outer)"]["parent"]
    assert named["inner"]["parent"] == named["outer"]["id"] == named["add"]["parent"]
    assert named["_where"]["parent"] == named["inner"]["id"]
    assert named["elsewhere"]["parent"] is None
    own = _self_seconds(events)
    assert own[named["outer"]["id"]] == pytest.approx(1.0 - 0.3 - 0.1)
    assert own[named["inner"]["id"]] == pytest.approx(0.3 - 0.1)


def test_startup_records_with_no_tracer_installed(startup_fresh):
    assert trace.installed() is None

    @trace.startup("startup.mesh", axes=3)
    def make():
        return 5

    assert make() == 5 and make() == 5
    events = trace.startup_record()["events"]
    assert [(event["name"], event["args"]) for event in events] == [
        ("startup.mesh", {"axes": 3})] * 2
    assert all(event["dur_s"] >= 0 and event["parent"] is None for event in events)
    # the copy is the caller's
    events[0]["args"]["axes"] = 0
    assert trace.startup_record()["events"][0]["args"] == {"axes": 3}


def test_startup_with_a_tracer_records_once_in_each(startup_fresh, tracer):
    with trace.startup("startup.engine", workers=4):
        trace.startup_event("compile.load", time.perf_counter(), 0.25, program="jit(f)")
    in_record = [event["name"] for event in trace.startup_record()["events"]]
    assert in_record == ["startup.engine", "compile.load"]
    events = trace.validate_chrome_trace(json.load(open(trace.save())))
    in_tracer = [(e["name"], e["cat"]) for e in events if e["ph"] == "X"]
    assert sorted(in_tracer) == [("compile.load", "startup"), ("startup.engine", "startup")]
    load = next(e for e in events if e["name"] == "compile.load")
    assert load["dur"] == pytest.approx(0.25e6) and load["args"] == {"program": "jit(f)"}


def test_install_replays_the_startup_record(startup_fresh, tmp_path):
    with trace.startup("startup.experiment", experiment="mnist"):
        with trace.startup("startup.data_host"):
            pass
    trace.startup_event("compile.load", time.perf_counter() - 1.0, 0.5, program="jit(init)")
    trace.install(str(tmp_path / "late.trace.json"), run_id="late")
    try:
        with trace.span("dispatch", cat="train"):
            pass
        payload = json.load(open(trace.save()))
    finally:
        trace.uninstall(save=False)
    spans = [e for e in trace.validate_chrome_trace(payload) if e["ph"] == "X"]
    assert [e["name"] for e in spans] == [
        "startup.experiment", "startup.data_host", "compile.load", "dispatch"]
    replayed = [e for e in spans if e["args"].get("replayed")]
    assert len(replayed) == 3 and all(e["cat"] == "startup" for e in replayed)
    # one clock: what came before the install comes before it in the file, from 0 on
    assert min(e["ts"] for e in spans) == 0.0
    assert all(e["ts"] + e["dur"] <= spans[-1]["ts"] for e in replayed)
    outer, inner = spans[0], spans[1]
    assert outer["ts"] <= inner["ts"] and inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
    # the record is as it was: a replay moves nothing
    assert len(trace.startup_record()["events"]) == 3


def test_startup_summary_is_one_line_for_an_operator(startup_fresh):
    from aggregathor_tpu.obs import profiler

    with trace.startup("startup.experiment"):
        with trace.startup("startup.data_host"):
            pass
    trace.startup_event("compile.load", 10.0, 0.5, program="jit(init)", cache="hit")
    with trace.startup("startup.first_call", dispatcher="train_step.dispatch", program="body_p1"):
        trace.startup_event("compile.trace", 20.5, 0.25, program="add")
        trace.startup_event("compile.trace", 20.0, 1.5, program="body_p1")
        trace.startup_event("compile.lower", 21.5, 0.5, program="jit(body_p1)")
        trace.startup_event("compile.load", 22.0, 3.0, program="jit(body_p1)", cache="miss")
    line = profiler.startup_summary()
    assert line.startswith("Start-up: experiment 0.00 s; first_call 0.00 s; ")
    assert "data_host" not in line  # inside startup.experiment: not a top-level part
    assert "step program body_p1: trace 1.50 s, lower 0.50 s, load 3.00 s (cache miss); " in line
    assert line.endswith("2 program(s) loaded in 3.50 s") and "\n" not in line


def test_traced_callable_first_call_is_one_startup_event(startup_fresh):
    jitted = jax.jit(lambda x: x * 3.0)
    wrapped = trace.traced("triple.dispatch", jitted)
    # a call under a trace dispatches nothing, and starts nothing
    spans = lambda: [e for e in trace.startup_record()["events"]
                     if e["name"].startswith("startup.")]
    jax.make_jaxpr(wrapped)(jax.ShapeDtypeStruct((2,), np.float32))
    assert spans() == []
    assert float(wrapped(np.ones(2, np.float32))[0]) == 3.0
    first = spans()
    assert [(e["name"], e["args"]) for e in first] == [
        ("startup.first_call", {"dispatcher": "triple.dispatch", "program": "<lambda>"})]
    held, size = len(trace.startup_record()["events"]), wrapped._cache_size()
    for _ in range(100):
        wrapped(np.ones(2, np.float32))
    assert len(trace.startup_record()["events"]) == held
    assert wrapped._cache_size() == size == 1


def test_the_paths_that_were_free_touch_no_startup_record(monkeypatch):
    """``span`` and ``instant`` with no tracer, and a dispatcher past its first
    call, run what they ran before the record existed."""
    wrapped = trace.traced("free.dispatch", jax.jit(lambda x: x + 1.0))
    wrapped(np.float32(1.0))

    def refuse(*_args, **_kwargs):
        raise AssertionError("the start-up record was touched")

    for name in ("_startup_append", "_startup_stack", "startup_event", "startup"):
        monkeypatch.setattr(trace, name, refuse)
    assert trace.installed() is None
    with trace.span("plain", cat="test"):
        trace.instant("tick")
    assert float(wrapped(np.float32(2.0))) == 3.0


# --------------------------------------------------------------------- #
# pillar 2: metrics registry (obs/metrics.py)


def test_registry_counter_gauge_histogram_snapshot():
    reg = MetricsRegistry()
    c = reg.counter("requests_total", "Requests")
    c.inc()
    c.inc(2.5)
    g = reg.gauge("depth", "Queue depth")
    g.set(7)
    g.dec(2)
    h = reg.histogram("lat_seconds", "Latency", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    snap = reg.snapshot()
    assert snap["requests_total"] == 3.5
    assert snap["depth"] == 5.0
    assert snap["lat_seconds"]["count"] == 3
    assert snap["lat_seconds"]["sum"] == pytest.approx(5.55)
    assert "p50" in snap["lat_seconds"]["percentiles"]
    with pytest.raises(UserException):
        c.inc(-1.0)


def test_registry_get_or_create_and_kind_mismatch():
    reg = MetricsRegistry()
    a = reg.counter("shared_total")
    b = reg.counter("shared_total")
    assert a is b  # independent subsystems reach the same instrument
    with pytest.raises(UserException):
        reg.gauge("shared_total")
    with pytest.raises(UserException):
        reg.counter("shared_total", labelnames=("worker",))
    with pytest.raises(UserException):
        reg.counter("bad name!")
    # histogram bucket mismatch fails loudly too (same spelling-insensitive
    # bounds are fine)
    hist = reg.histogram("h_seconds", buckets=(1.0, 0.1))
    assert reg.histogram("h_seconds", buckets=(0.1, 1)) is hist
    with pytest.raises(UserException):
        reg.histogram("h_seconds", buckets=(5.0, 50.0))


def test_registry_labels_and_escaping_roundtrip():
    """Exposition escapes label values; the text-format parser recovers
    them exactly (the acceptance round-trip)."""
    reg = MetricsRegistry()
    fam = reg.gauge("worker_dist", "Distance", labelnames=("worker", "note"))
    nasty = 'a"b\\c\nd'
    fam.labels(worker="3", note=nasty).set(1.5)
    fam.labels("4", "plain").set(float("inf"))
    with pytest.raises(UserException):
        fam.set(1.0)  # labelled family has no solo child
    with pytest.raises(UserException):
        fam.labels("3")  # wrong arity
    text = reg.render_prometheus()
    parsed = parse_prometheus(text)
    assert parsed["worker_dist"]["type"] == "gauge"
    samples = {
        (labels["worker"], labels["note"]): value
        for _, labels, value in parsed["worker_dist"]["samples"]
    }
    assert samples[("3", nasty)] == 1.5
    assert samples[("4", "plain")] == float("inf")


def test_histogram_buckets_exposition_roundtrip():
    reg = MetricsRegistry()
    h = reg.histogram("step_seconds", "Step latency", buckets=(0.1, 1.0))
    for v in (0.05, 0.05, 0.5, 5.0):
        h.observe(v)
    assert h.cumulative_buckets() == [(0.1, 2), (1.0, 3), (float("inf"), 4)]
    parsed = parse_prometheus(reg.render_prometheus())
    samples = parsed["step_seconds"]["samples"]
    buckets = {
        labels["le"]: value for name, labels, value in samples
        if name == "step_seconds_bucket"
    }
    assert buckets["0.1"] == 2 and buckets["1.0"] == 3 and buckets["+Inf"] == 4
    totals = {name: value for name, labels, value in samples if not labels}
    assert totals["step_seconds_count"] == 4
    assert totals["step_seconds_sum"] == pytest.approx(5.6)
    # a boundary value belongs to its own le bucket (cumulative semantics)
    h.observe(0.1)
    assert h.cumulative_buckets()[0] == (0.1, 3)


def test_gauge_set_function_reads_live():
    reg = MetricsRegistry()
    box = {"v": 1}
    reg.gauge("live").set_function(lambda: box["v"])
    assert reg.snapshot()["live"] == 1.0
    box["v"] = 9
    assert reg.snapshot()["live"] == 9.0


def test_registry_concurrency_exact_totals():
    reg = MetricsRegistry()
    counter = reg.counter("hits_total")
    hist = reg.histogram("obs_seconds", buckets=(0.5,))
    fam = reg.counter("labelled_total", labelnames=("t",))

    def pound(tid):
        for i in range(500):
            counter.inc()
            hist.observe(0.25 if i % 2 else 0.75)
            fam.labels(t=str(tid % 2)).inc()

    threads = [threading.Thread(target=pound, args=(t,)) for t in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert counter.value == 8 * 500
    assert hist.count == 8 * 500
    children = fam.children()
    assert sum(c.value for c in children.values()) == 8 * 500
    parse_prometheus(reg.render_prometheus())  # still renders cleanly


def test_parse_prometheus_rejects_garbage():
    with pytest.raises(ValueError):
        parse_prometheus("this is not { exposition\n")
    with pytest.raises(ValueError):  # garbage BETWEEN label pairs
        parse_prometheus('m{a="1";;;b="2"} 3\n')
    # the text format allows a trailing comma before "}"
    parsed = parse_prometheus('m{a="1",} 3\n')
    assert parsed["m"]["samples"] == [("m", {"a": "1"}, 3.0)]


def test_perf_report_percentiles_are_per_run():
    """Two registry-backed PerfReports in one process (sequential
    runner.main calls in tests) must each print THEIR OWN latency spread;
    the shared registry histogram stays cumulative (Prometheus contract)."""
    from aggregathor_tpu.obs import PerfReport

    reg = MetricsRegistry()
    first = PerfReport(registry=reg)
    for _ in range(3):
        first.step_begin()
        first.step_end()
    second = PerfReport(registry=reg)
    assert second.latency.count == 0  # fresh per-run reservoir
    assert reg.histogram("train_step_latency_seconds").count == 2  # excl. 1st
    assert reg.counter("train_steps_total").value == 3.0


# --------------------------------------------------------------------- #
# pillar 3: Byzantine forensics (obs/forensics.py)


def test_binom_sf_exact_and_monotone():
    assert binom_sf(4, 0, 0.5) == 1.0
    assert binom_sf(4, 5, 0.5) == 0.0
    assert binom_sf(4, 4, 0.5) == pytest.approx(1.0 / 16.0)
    values = [binom_sf(10, k, 1.0 / 6.0) for k in range(11)]
    assert values == sorted(values, reverse=True)


def test_forensics_strong_attribution_and_intervals():
    """A distance outlier every step is attributed with a single merged
    interval carrying the regime; honest workers stay honest."""
    led = ForensicsLedger(4, run_id="r1")
    for step in range(20):
        dist = [1.0, 1.1, 0.9, 50.0]
        led.observe(step, worker_sq_dist=dist, regime=1, regime_desc="1:attack=empire")
    report = led.report()
    assert report["schema"] == "aggregathor.obs.forensics.v1"
    assert report["run_id"] == "r1"
    assert report["suspects"] == [3]
    w3 = report["workers"][3]
    assert w3["evidence"]["distance"] == 20
    assert w3["intervals"] == [{
        "start": 0, "end": 19, "steps": 20, "regimes": [1],
        "regime_specs": ["1:attack=empire"], "evidence": ["distance", "rank"],
    }]
    assert all(not w["byzantine"] for w in report["workers"][:3])
    md = render_markdown(report)
    assert "worker(s) 3" in md and "**BYZANTINE**" in md


def test_forensics_windowed_attack_not_diluted():
    """An attacker active for only 10 of 100 steps must still be named:
    the windowed strong rate catches what the global rate dilutes away."""
    rng = np.random.default_rng(3)
    led = ForensicsLedger(4)
    for step in range(100):
        dist = rng.uniform(0.9, 1.1, 4)
        if 40 <= step < 50:
            dist[2] = 80.0
        led.observe(step, worker_sq_dist=dist, regime=int(40 <= step < 50))
    report = led.report()
    assert report["suspects"] == [2]
    w2 = report["workers"][2]
    assert w2["strong_rate"] < 0.5  # global rate alone would miss it
    assert w2["strong_window_rate"] >= 0.5
    # one merged interval covers the whole attack burst under its regime
    # (scattered honest rank-tops may add unrelated single-step intervals)
    attack = [iv for iv in w2["intervals"]
              if iv["start"] <= 40 <= iv["end"] and "distance" in iv["evidence"]]
    assert attack and attack[0]["end"] >= 49
    assert 1 in attack[0]["regimes"]


def test_forensics_rank_persistence_catches_marginal_attacker():
    """An attacker below the distance factor but persistently FARTHEST is
    attributed through the Binomial rank test; an honest worker topping at
    the ~1/n base rate is not."""
    rng = np.random.default_rng(7)
    led = ForensicsLedger(5)
    for step in range(60):
        dist = rng.uniform(1.0, 1.5, 5)
        dist[1] = 2.5 + rng.uniform(0.0, 0.1)  # ~2x the median: no 'distance'
        led.observe(step, worker_sq_dist=dist)
    report = led.report()
    assert report["suspects"] == [1]
    w1 = report["workers"][1]
    assert w1["evidence"].get("distance", 0) == 0
    assert w1["rank_p_value"] <= led.rank_alpha
    assert all(
        w["rank_p_value"] > led.rank_alpha
        for w in report["workers"] if w["worker"] != 1
    )


def test_forensics_nan_reputation_channels_and_vector_checks():
    led = ForensicsLedger(3)
    for step in range(10):
        led.observe(step, worker_nan=[0, 1, 0], reputation=[1.0, 0.9, 0.2])
    report = led.report()
    assert report["suspects"] == [1, 2]
    assert report["workers"][1]["evidence"] == {"nan_row": 10}
    assert report["workers"][2]["evidence"] == {"reputation": 10}
    with pytest.raises(ValueError):
        led.observe(99, worker_sq_dist=[1.0, 2.0])  # wrong length


def test_forensics_nonfinite_distances_masked_not_flagged():
    """A NaN/inf distance row is the nan_row channel's job; it must not
    poison the median anchor or mark 'distance' evidence by itself."""
    led = ForensicsLedger(4)
    led.observe(0, worker_sq_dist=[1.0, float("inf"), float("nan"), 1.2])
    report = led.report()
    assert all(
        "distance" not in w["evidence"] for w in report["workers"]
    )


def test_forensics_truncate_after_and_guardian_events():
    led = ForensicsLedger(2)
    for step in range(10):
        led.observe(step, worker_nan=[0, 1])
    led.note_guardian(4, "rollback", {"reason": "spike"})
    led.note_guardian(9, "escalation", {"rung": "f+1"})
    dropped = led.truncate_after(4)
    assert dropped == 5
    report = led.report()
    assert report["steps_observed"] == 5
    assert report["step_range"] == [0, 4]
    assert [e["kind"] for e in report["guardian_events"]] == ["rollback"]
    md = render_markdown(report)
    assert "Guardian events" in md and "rollback" in md


def test_forensics_save_writes_schema_and_markdown(tmp_path):
    led = ForensicsLedger(2, run_id="rx")
    led.observe(0, worker_nan=[1, 0])
    json_path = str(tmp_path / "forensics.json")
    md_path = str(tmp_path / "forensics.md")
    report = led.save(json_path, markdown_path=md_path)
    on_disk = json.load(open(json_path))
    assert on_disk["schema"] == report["schema"] == "aggregathor.obs.forensics.v1"
    assert on_disk["suspects"] == [0]
    assert "Byzantine forensics" in open(md_path).read()


def test_campaign_attribution_two_gars_time_varying_schedule():
    """Acceptance: the forensics report names the injected attacker (right
    worker id, step range overlapping the attack window) under TWO robust
    GARs driven by a TIME-VARYING chaos schedule (calm, then attack)."""
    from aggregathor_tpu.chaos.campaign import run_cell

    for gar_name in ("median", "krum"):
        cell = run_cell(
            "mnist", ["batch-size:16"], gar_name, [], 6, 1, 1,
            "0:calm 8:attack=empire,epsilon=4.0", [], 16, 0.05, 0,
            forensics=True,
        )
        fx = cell["forensics"]
        assert fx["expected"] == [0]
        assert fx["suspects"] == [0], (gar_name, fx)
        assert fx["attribution_correct"], (gar_name, fx)
        # the named intervals overlap the attack window (steps 9..16)
        intervals = fx["suspect_intervals"]["0"]
        assert any(iv["end"] >= 9 for iv in intervals), (gar_name, intervals)


# --------------------------------------------------------------------- #
# run_id stamping (obs/summaries.py)


def test_summary_lines_stamped_with_run_id(tmp_path):
    from aggregathor_tpu.obs.summaries import SummaryWriter, make_run_id

    rid = make_run_id()
    assert rid and rid != make_run_id()
    sw = SummaryWriter(str(tmp_path), run_name="t", run_id=rid)
    sw.scalars(1, {"loss": 2.0})
    sw.event(2, "chaos_transition", {"run_id": "spoofed", "spec": "calm"})
    sw.close()
    lines = [json.loads(line) for line in open(sw.path)]
    assert [line["run_id"] for line in lines] == [rid, rid]  # reserved key wins
    auto = SummaryWriter(str(tmp_path), run_name="auto")
    assert auto.run_id  # generated when not given
    auto.close()
