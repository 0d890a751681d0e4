"""The cut by phase on the small recorded trace, and its checks of itself firing
on doctored copies.  The trace is PR 23's (one K=20 dispatch of
cnnet_krum_sampled on a v5e, operations under 5 us left out); the table beside
it is ``obs.profiler.phase_table`` of PR 24's scoped program compiled for a
described v5e (a compile gives names, never times), kept for the 56 of the
trace's 57 instructions it names.  The scopes moved no instruction's name but
the Pallas call's: PR 23's ``closed_call.59`` is ``pairwise_sq_distances.12``
now, so its 0.25 ms a step stay unattributed here, beside the scan's own
``while``."""

import gzip
import json
import os

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


def cut(trace, table, reduced, entries=None):
    return phase_reduce.cut(trace, reduced["step_module"], STEPS, reduced["busy_step_ms"],
                            table["table"] if entries is None else entries, table["notes"])


def test_phases_sum_to_the_busy_time(recorded):
    trace, table, reduced = recorded
    found = cut(trace, table, reduced)
    total = sum(found["phases"].values()) + found["unattributed_ms"]
    assert total == pytest.approx(found["total_ms"])
    assert abs(total / reduced["busy_step_ms"] - 1) < phase_reduce.AGREE
    assert found["cover"] > 0.99
    # closed_call.59 (0.25), the scan's own ``while``, whose op_name names no phase (0.155),
    # and an async copy next to nothing (0.03)
    assert found["unattributed_ms"] == pytest.approx(0.436, abs=0.01)
    # what PR 23's records let one expect: the convolutions 75-82 ms, the crop over 15.5
    assert 75 < found["phases"]["grad"] + found["phases"]["flatten"] < 82
    assert found["phases"]["sample"] + found["phases"]["augment"] > 15.5
    assert found["phases"]["gar"] < 1.0
    assert 0 < found["inherited_ms"] < 2 and 0 < found["soft_fusion_ms"] < found["total_ms"]


def test_half_a_table_is_refused_by_the_cover_check(recorded):
    trace, table, reduced = recorded
    names = sorted(table["table"])
    half = {name: table["table"][name] for name in names[: len(names) // 2]}
    with pytest.raises(trace_reduce.TraceContradiction, match="cover"):
        cut(trace, table, reduced, entries=half)


def test_a_sum_that_leaves_the_busy_time_is_refused(recorded):
    trace, table, reduced = recorded
    with pytest.raises(trace_reduce.TraceContradiction, match="busy_step_ms"):
        phase_reduce.cut(trace, reduced["step_module"], STEPS, 2 * reduced["busy_step_ms"],
                         table["table"], table["notes"])


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
