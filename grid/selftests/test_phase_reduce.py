"""The cut by phase on the small recorded trace, and its checks of itself firing
on doctored copies.  The trace is PR 23's (one K=20 dispatch of
cnnet_krum_sampled on a v5e, operations under 5 us left out); the table beside
it is ``obs.profiler.phase_table`` of PR 24's scoped program compiled for a
described v5e (a compile gives names, never times), kept for the 56 of the
trace's 57 instructions it names.  The scopes moved no instruction's name but
the Pallas call's: PR 23's ``closed_call.59`` is ``pairwise_sq_distances.12``
now, so its 0.25 ms a step stay unattributed here, beside the scan's own
``while``."""

import copy
import gzip
import json
import os
import types

import pytest

import phase_reduce
import trace_reduce
from aggregathor_tpu.obs import profiler

RECORDED = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "recorded")
STEPS = 20


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(os.path.join(RECORDED, "cnnet_krum_sampled.trace.json.gz"), "rt") as fd:
        trace = json.load(fd)
    with open(os.path.join(RECORDED, "cnnet_krum_sampled.phase_table.json")) as fd:
        table = json.load(fd)
    return trace, table, trace_reduce.reduce(trace, STEPS)


def cut(trace, table, reduced, entries=None, steps=STEPS):
    return phase_reduce.cut(trace, reduced["step_module"], steps, reduced["device_step_ms"],
                            table["table"] if entries is None else entries, table["notes"])


def with_dropped_events(trace, share, start=0.3):
    """``trace`` less a run of ``share`` of its operation events, as a device's
    event buffer that overflowed leaves it: the module spans and the loops that
    hold the lost leaves are still there."""
    doctored = copy.deepcopy(trace)
    for lines in doctored["devices"].values():
        first = int(len(lines["ops"]) * start)
        del lines["ops"][first:first + int(len(lines["ops"]) * share)]
    return doctored


def twice_dispatched(trace, between=None):
    """``trace``'s one dispatch and a second one after it; ``between`` is an
    operation [name, duration] of another program, run 1 ms after the first
    dispatch's span, the second dispatch 1 ms after that."""
    doctored = copy.deepcopy(trace)
    for lines in doctored["devices"].values():
        (_name, start, duration), = lines["modules"]
        shift = duration + 2_000_000 + (between[1] if between else 0)
        again = lambda events: [[e[0], e[1] + shift] + e[2:] for e in events]
        lines["modules"] = lines["modules"] + again(lines["modules"])
        lines["ops"] = lines["ops"] + again(lines["ops"])
        if between:
            lines["ops"].append([between[0], start + duration + 1_000_000, between[1]])
    return doctored


def test_phases_sum_to_the_module_span(recorded):
    trace, table, reduced = recorded
    found = cut(trace, table, reduced)
    total = sum(found["phases"].values()) + found["unattributed_ms"]
    assert total == pytest.approx(found["total_ms"])
    # self times telescope to the top-level operations: the span less their hand-overs
    assert -1e-5 < total / reduced["device_step_ms"] - 1 < 0 < phase_reduce.SPAN_AGREE
    assert found["cover"] > 0.99
    # closed_call.59 (0.25), the scan's own ``while``, whose op_name names no phase (0.155),
    # and an async copy next to nothing (0.03)
    assert found["unattributed_ms"] == pytest.approx(0.436, abs=0.01)
    # what PR 23's records let one expect: the convolutions 75-82 ms, the crop over 15.5
    assert 75 < found["phases"]["grad"] + found["phases"]["flatten"] < 82
    assert found["phases"]["sample"] + found["phases"]["augment"] > 15.5
    assert found["phases"]["gar"] < 1.0
    assert 0 < found["inherited_ms"] < 2 and 0 < found["soft_fusion_ms"] < found["total_ms"]


def test_the_cut_reads_what_it_read_before_the_span_was_its_yardstick(recorded):
    """PR 42 changed what the sum is held to, not the sum: the parent's code
    (83e836c) reads these from the same file, to the last digit."""
    trace, table, reduced = recorded
    found = cut(trace, table, reduced)
    assert found["total_ms"] == 97.5119283 and found["unattributed_ms"] == 0.43585515
    assert found["cover"] == 0.9955302376068386
    assert found["phases"] == {"augment": 19.6637578, "epilogue": 0.018614099999999998,
                               "flatten": 0.0244584, "gar": 0.2366499, "grad": 76.08003325,
                               "sample": 1.0525597}
    assert reduced["device_step_ms"] == 97.51236425 and reduced["busy_step_ms"] == 96.439906


@pytest.mark.parametrize("share, leaf_cover, phase_cover, parent_refused", [
    (0.01, 0.978, 0.985, False), (0.03, 0.964, 0.971, True), (0.05, 0.939, 0.946, True)])
def test_dropped_leaves_move_the_covers_and_not_the_sum(recorded, share, leaf_cover, phase_cover,
                                                        parent_refused):
    """A capture that lost events above ``reduce``'s floor stands: the lost
    leaves' time falls to the ``while`` that held them, which names no phase,
    so the sum is what it was and both covers say what was lost.  The parent
    held the sum to the busy time within 3 %, which is leaf cover >= 0.971
    under another name."""
    trace, table, sound = recorded
    doctored = with_dropped_events(trace, share)
    reduced = trace_reduce.reduce(doctored, STEPS)
    found = cut(doctored, table, reduced)
    assert found["total_ms"] == cut(trace, table, sound)["total_ms"]
    assert reduced["device_step_ms"] == sound["device_step_ms"]
    assert reduced["cover"] == pytest.approx(leaf_cover, abs=1e-3) and leaf_cover < sound["cover"]
    assert found["cover"] == pytest.approx(phase_cover, abs=1e-3)
    assert (found["total_ms"] / reduced["busy_step_ms"] - 1 > 0.03) is parent_refused


def test_leaves_dropped_under_the_floor_are_refused_by_the_reduction(recorded):
    trace, _table, _reduced = recorded
    with pytest.raises(trace_reduce.DroppedEvents, match="trace fewer dispatches"):
        trace_reduce.reduce(with_dropped_events(trace, 0.10), STEPS)


def test_half_a_table_is_refused_by_the_cover_check(recorded):
    trace, table, reduced = recorded
    names = sorted(table["table"])
    half = {name: table["table"][name] for name in names[: len(names) // 2]}
    with pytest.raises(trace_reduce.TraceContradiction, match="cover"):
        cut(trace, table, reduced, entries=half)


def test_a_sum_that_leaves_the_module_span_is_refused(recorded):
    """What the comparison is for: a ``device_step_ms`` that is not this
    trace's, and operations inside the first-to-last window that are not the
    step program's — another program's, run between two dispatches."""
    trace, table, reduced = recorded
    with pytest.raises(trace_reduce.TraceContradiction, match="device_step_ms"):
        cut(trace, table, dict(reduced, device_step_ms=1.01 * reduced["device_step_ms"]))
    twice = twice_dispatched(trace)
    assert cut(twice, table, trace_reduce.reduce(twice, 2 * STEPS), steps=2 * STEPS)[
        "total_ms"] == pytest.approx(cut(trace, table, reduced)["total_ms"])
    # 2 % of a dispatch between the two: + 1 % a step, which the reduction's 10 % lets by
    foreign = twice_dispatched(trace, between=["fusion.1 f32[8]", 40_000_000])
    passed = trace_reduce.reduce(foreign, 2 * STEPS)
    with pytest.raises(trace_reduce.TraceContradiction, match="between the dispatches"):
        cut(foreign, table, passed, steps=2 * STEPS)
    # the step's whole top-level loop a second time between them is the reduction's to refuse
    (loop,) = [op for op in trace["devices"]["0"]["ops"] if op[0].startswith("while.14 ")]
    with pytest.raises(trace_reduce.TraceContradiction, match="busy time per step"):
        trace_reduce.reduce(twice_dispatched(trace, between=[loop[0], loop[2]]), 2 * STEPS)


def test_an_event_recorded_twice_telescopes_away(recorded):
    """Twins nest (the second inside the first, whose self time is then 0):
    the sum and every phase read what they read."""
    trace, table, reduced = recorded
    doctored = copy.deepcopy(trace)
    ops = doctored["devices"]["0"]["ops"]
    ops += copy.deepcopy(ops[:200])
    again = trace_reduce.reduce(doctored, STEPS)
    assert again["busy_step_ms"] == reduced["busy_step_ms"]
    found, sound = cut(doctored, table, again), cut(trace, table, reduced)
    assert found["total_ms"] == pytest.approx(sound["total_ms"], rel=1e-12)
    assert found["phases"] == pytest.approx(sound["phases"], rel=1e-12)


def test_the_manifest_lists_the_phases_for_every_cell_and_a_reader_for_every_metric():
    from cell import GRID, ROOT, load_json

    manifest = load_json(os.path.join(ROOT, "BENCHMARK.json"), "manifest")
    cells = [cell["name"] for cell in manifest["workloads"]]
    listed = {metric["name"]: metric for metric in manifest["per_layer"]}
    for name in ("input_device_ms_per_step", "grad_ms_per_step", "reshard_ms_per_step",
                 "gar_in_step_ms", "apply_ms_per_step", "phase_cover_pct"):
        assert listed[name]["workloads"] == cells and listed[name]["moves"] == "steps_per_s"
    readers = {name[:-3] for name in os.listdir(os.path.join(GRID, "layer_metrics"))
               if name.endswith(".py") and not name.startswith("_")}
    assert readers == set(listed)  # no metric without its file, no file without its metric


def fake_captures(monkeypatch, traces):
    """``run.capture`` over ``traces`` in turn, in place of dispatches on a chip."""
    import run

    taken = []

    def traced_dispatches(cell, state, nb_dispatches):
        taken.append(nb_dispatches)
        return state + 1, traces[len(taken) - 1]

    monkeypatch.setattr(run, "traced_dispatches", traced_dispatches)
    return run.capture, taken


def test_a_capture_that_lost_too_much_is_taken_once_more(recorded, monkeypatch, capsys):
    trace, _table, sound = recorded
    capture, taken = fake_captures(monkeypatch, [with_dropped_events(trace, 0.10), trace])
    state, raw, reduced = capture(types.SimpleNamespace(unroll=STEPS), 0, 2, None)
    assert taken == [2, 1] and state == 2 and raw is trace
    assert reduced["steps_traced"] == STEPS and reduced["cover"] == sound["cover"]
    said = [line for line in capsys.readouterr().out.splitlines() if line.startswith("grid capture")]
    assert len(said) == 1 and "cover 0.889" in said[0] and "tracing 1 dispatch" in said[0]


def test_a_second_capture_that_lost_too_much_is_refused_in_words(recorded, monkeypatch):
    trace, _table, _sound = recorded
    lost = with_dropped_events(trace, 0.10)
    capture, taken = fake_captures(monkeypatch, [lost, lost])
    with pytest.raises(trace_reduce.TraceContradiction, match="dropped events"):
        capture(types.SimpleNamespace(unroll=STEPS), 0, 1, None)
    assert taken == [1, 1]  # never under one dispatch


def test_a_capture_above_the_floor_stands(recorded, monkeypatch, capsys):
    trace, _table, _sound = recorded
    lossy = with_dropped_events(trace, 0.05)
    capture, taken = fake_captures(monkeypatch, [lossy])
    _state, raw, reduced = capture(types.SimpleNamespace(unroll=STEPS), 0, 1, None)
    assert taken == [1] and raw is lossy and reduced["cover"] == pytest.approx(0.939, abs=1e-3)
    assert "grid capture" not in capsys.readouterr().out


SCOPED = """HloModule jit_many, is_scheduled=true

ENTRY %main.2 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %negate.1 = f32[8]{0} negate(%p), metadata={op_name="jit(many)/step.apply/neg"}
}
"""


class Dispatcher:
    def __init__(self, name, text=None):
        self.__name__, self.text, self.asked = name, text, 0

    def compiled_text(self):
        self.asked += 1
        if self.text is None:
            raise RuntimeError("never called")
        return self.text


def test_the_table_is_the_one_dispatcher_that_runs_the_step_module():
    step, other = Dispatcher("many", SCOPED), Dispatcher("sharded", SCOPED)
    table, notes, seconds = phase_reduce.program_table(
        "jit_many(9439790079306549169)", [other, step, Dispatcher("many")], profiler.phase_table)
    assert table["negate.1"] == "apply" and notes == {"soft": [], "inherited": []}
    assert seconds >= 0 and other.asked == 0  # a program of another name is never compiled


@pytest.mark.parametrize("dispatchers", [
    [Dispatcher("sharded", SCOPED)],
    [Dispatcher("many", SCOPED), Dispatcher("many", SCOPED)],
    [],
])
def test_an_unknown_or_doubled_step_module_is_refused(dispatchers):
    with pytest.raises(trace_reduce.TraceContradiction, match="exactly one"):
        phase_reduce.program_table("jit_many(1)", dispatchers, profiler.phase_table)


def test_a_program_without_phases_has_nothing_to_read(monkeypatch):
    # the parent of PR 24: obs.trace has no dispatchers() to import
    from aggregathor_tpu.obs import trace as program_trace

    monkeypatch.delattr(program_trace, "dispatchers")
    ctx = {"trace": {}, "raw_trace": {}}
    assert phase_reduce.phases(ctx) is None and phase_reduce.per_step_ms(ctx, "gar") is None
    assert ctx["phases"] is None
