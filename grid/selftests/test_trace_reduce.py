"""The reduction on the small recorded trace, and its two checks of itself
firing on doctored copies.  The trace is one K=20 dispatch of
cnnet_krum_sampled on a v5e (PR 23), cut from a traced run by
``python3 grid/trace_reduce.py <kept> <recorded> 1 5000``: operations shorter
than 5 us are left out (the per-image slices of the crop, 33 thousand a step,
held inside loops that then count as leaves), which moves the cover from
0.985 to 0.989."""

import copy
import gzip
import json
import os

import pytest

import trace_reduce

RECORDED = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "recorded", "cnnet_krum_sampled.trace.json.gz")
STEPS = 20


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(RECORDED, "rt") as fd:
        return json.load(fd)


def test_recorded_trace_reduces_and_agrees_with_itself(recorded):
    reduced = trace_reduce.reduce(recorded, STEPS)
    assert abs(reduced["device_step_ms"] - 97.5) < 0.5
    assert reduced["cover"] >= trace_reduce.COVER_MIN
    assert 0.0 <= reduced["idle_share"] < 0.05
    assert abs(reduced["busy_step_ms"] / reduced["device_step_ms"] - 1) < trace_reduce.AGREE
    assert reduced["collective_ms_per_step"] == 0.0
    assert len(reduced["breakdown"]["device_ops"]) == 10
    # the rate the step time implies passes the second check, and a rate that
    # says the device is busy a quarter of the time does not
    trace_reduce.reduce(recorded, STEPS, steps_per_s=1e3 / reduced["device_step_ms"])
    with pytest.raises(trace_reduce.TraceContradiction, match="busy"):
        trace_reduce.reduce(recorded, STEPS, steps_per_s=0.25e3 / reduced["device_step_ms"])


def test_dropped_events_are_refused(recorded):
    doctored = copy.deepcopy(recorded)
    for lines in doctored["devices"].values():
        lines["ops"] = lines["ops"][: len(lines["ops"]) // 2]
    with pytest.raises(trace_reduce.TraceContradiction, match="cover"):
        trace_reduce.reduce(doctored, STEPS)


def test_wrong_step_count_is_refused(recorded):
    # busy time per step against the module span per step: the first check of
    # agreement cannot be fooled by the step count (both scale), so doctor the
    # module span instead, as a trace whose session outlived the steps would
    doctored = copy.deepcopy(recorded)
    for lines in doctored["devices"].values():
        lines["modules"] = [[name, start, 4 * duration] for name, start, duration in lines["modules"]]
    with pytest.raises(trace_reduce.TraceContradiction):
        trace_reduce.reduce(doctored, STEPS)


def test_leaves_and_self_time_of_nested_operations():
    ops = [["while", 0, 100], ["a", 0, 40], ["b", 50, 30], ["tail", 120, 10]]
    leaves, self_time = trace_reduce._leaves_and_self_times(ops)
    assert sorted(leaves) == [[0, 40], [50, 80], [120, 130]]
    assert self_time == {"while": 30, "a": 40, "b": 30, "tail": 10}


def test_collectives_are_found_by_opcode_not_by_instruction_name():
    line = ("%all_to_all.14 = f32[4,8,6389258]{2,1,0:T(8,128)} all-to-all(f32[4,8,6389258]{2,1,0} "
            "%copy.1), channel_id=3, replica_groups={{0,1,2,3}}")
    assert trace_reduce.opcode(line) == "all-to-all"
    assert trace_reduce.opcode("%fusion.4 = (f32[8]{0}, f32[8]{0}) fusion(f32[8]{0} %p), kind=kLoop") == "fusion"
    module = [["jit_many", 0, 1000]]
    ops = [["all_to_all.14 f32[4,8]", 0, 300, "all-to-all"], ["fusion.1 f32[8]", 300, 690]]
    reduced = trace_reduce.reduce({"devices": {"0": {"modules": module, "ops": ops}}, "host": []}, 1)
    assert reduced["collective_ms_per_step"] == pytest.approx(300 / 1e6)


def test_short_name():
    assert trace_reduce.short_name(
        "%fusion.3 = f32[8,512]{1,0:T(8,128)} fusion(f32[8]{0} %p), kind=kLoop") == "fusion.3 f32[8,512]"
    assert trace_reduce.short_name("jit_many") == "jit_many"
