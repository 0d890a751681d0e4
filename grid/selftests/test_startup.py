"""Set-up by the program's own parts (layer_metrics/_startup.py), on recorded
start-up records in the form ``obs.trace.startup_record()`` returns
(grid/recorded/*.startup.json): nesting and self time, a ``jit`` traced inside
the step's trace, a warm load and a cold one, a record that dropped events, and
a program that keeps no record."""

import copy
import json
import os

import pytest

GRID = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STEP_MODULE = "jit_many_p1(9439790079306549169)"
PROGRAMS = {"many_p1", "evaluate"}


def recorded(name):
    with open(os.path.join(GRID, "recorded", name + ".startup.json")) as fd:
        return json.load(fd)


def reduced(record):
    from layer_metrics._startup import reduce_record

    found, why = reduce_record(record, STEP_MODULE, PROGRAMS)
    assert why is None, why
    return found


def test_the_step_s_stages_and_the_cut():
    found = reduced(recorded("nested_hit"))
    assert found["step_program"] == "many_p1"
    assert found["dispatcher"] == "train_sampled_multi_step.dispatch"
    step = found["step"]
    assert (step["trace_s"], step["lower_s"], step["load_s"]) == (2.0, 1.0, 2.8)
    assert step["cache"] == "hit" and step["retrieval_s"] == 1.5
    assert step["first_call_s"] == 6.0
    assert step["trace_s"] + step["lower_s"] + step["load_s"] <= step["first_call_s"]
    # what the harness lowers and loads after the step's first call is not set-up
    assert found["cut_s"] == pytest.approx(14.0) and found["after_cut"] == 4
    assert found["events"] == 28 and found["dropped"] == 0


def test_self_time_is_a_span_less_its_children():
    found = reduced(recorded("nested_hit"))
    top = {part["name"]: part for part in found["top"]}
    assert list(top) == ["startup.backend", "startup.experiment", "startup.mesh", "startup.engine",
                         "startup.build_step", "compile.outside_spans", "startup.put",
                         "startup.state_init", "startup.first_call"]
    assert top["startup.experiment"]["total_s"] == 3.0
    assert top["startup.experiment"]["self_s"] == pytest.approx(1.0)  # less its data_host
    assert top["startup.state_init"]["self_s"] == pytest.approx(1.0 - 0.05 - 0.05 - 0.2 - 0.3)
    assert top["startup.first_call"]["self_s"] == pytest.approx(6.0 - 2.0 - 1.0 - 2.8)
    # the seeded initialiser's three stages outside any span: its nested trace is inside the first
    assert top["compile.outside_spans"]["count"] == 3
    assert top["compile.outside_spans"]["total_s"] == pytest.approx(1.0)
    assert top["startup.put"]["count"] == 1  # the state's put is inside startup.state_init
    assert found["named_s"] == pytest.approx(11.5)
    assert found["data_host_s"] == 2.0 and found["state_init_s"] == 1.0


def test_a_nested_pjit_is_inside_the_step_s_trace_and_named():
    found = reduced(recorded("nested_hit"))
    step = found["step"]
    assert step["trace_self_s"] == pytest.approx(2.0 - 0.6 - 0.15)
    nested = {entry["program"]: entry for entry in step["nested"]}
    assert list(nested) == ["pair_kernel", "_where", "add"]  # by self seconds
    assert nested["pair_kernel"]["self_s"] == pytest.approx(0.6 - 0.1 - 0.1)
    assert nested["_where"]["count"] == 2 and nested["_where"]["self_s"] == pytest.approx(0.2)
    assert all(entry["in"] == "compile.trace" for entry in step["nested"])
    # every other program's stages up to the cut, each less its children: never the step's
    other = found["other_programs"]
    assert other["loaded"] == 2 and other["events"] == 7
    assert other["self_s"] == pytest.approx(0.1 + 0.3 + 0.1 + 0.5 + 0.05 + 0.05 + 0.2)


def test_a_cold_load_is_a_miss_and_holds_the_compile():
    found = reduced(recorded("miss"))
    step = found["step"]
    assert step["load_s"] == 40.0 and step["cache"] == "miss" and step["retrieval_s"] is None
    assert (step["trace_s"], step["lower_s"]) == (2.0, 1.0)
    assert found["cut_s"] == pytest.approx(51.2) and found["after_cut"] == 4
    assert found["other_programs"]["self_s"] == pytest.approx(1.3)


def test_a_record_with_drops_says_so():
    found = reduced(recorded("drops"))
    assert found["dropped"] == 7 and found["limit"] == 24 and found["events"] == 24
    assert found["step"]["load_s"] == 2.8 and found["after_cut"] == 0


def test_a_step_traced_before_its_first_call_reads_zero_for_that_stage():
    record = recorded("nested_hit")
    record["events"] = [event for event in record["events"] if event["id"] not in range(17, 23)]
    step = reduced(record)["step"]
    assert (step["trace_s"], step["lower_s"], step["load_s"]) == (0.0, 0.0, 2.8)
    assert step["nested"] == []


def test_an_open_span_and_another_thread_are_left_where_they_are():
    record = recorded("nested_hit")
    record["events"].append({"name": "startup.experiment", "start_s": 2.0, "dur_s": None,
                             "parent": None, "thread": 7, "args": {}, "id": 28})
    record["events"].append({"name": "compile.load", "start_s": 9.0, "dur_s": 1.0, "parent": None,
                             "thread": 7, "args": {"program": "jit(evaluate)", "cache": "hit",
                                                   "retrieval_s": 0.5}, "id": 29})
    found = reduced(record)
    assert found["step"]["load_s"] == 2.8  # the other thread's load is another program's
    assert found["other_programs"]["loaded"] == 3
    assert found["other_programs"]["self_s"] == pytest.approx(2.3)


@pytest.mark.parametrize("step_module, programs, why", [
    ("jit_other_p1(1)", PROGRAMS, "no live dispatcher"),
    (STEP_MODULE, PROGRAMS | {"never_called_p1"}, None),
    ("jit_never_called_p1(2)", PROGRAMS | {"never_called_p1"}, "no startup.first_call"),
])
def test_a_step_the_record_does_not_hold_is_refused_by_name(step_module, programs, why):
    from layer_metrics._startup import reduce_record

    found, said = reduce_record(recorded("nested_hit"), step_module, programs)
    assert (found is None and why in said) if why else (said is None and found is not None)


def test_the_six_readers_read_the_one_line(capsys, monkeypatch):
    from aggregathor_tpu.obs import trace
    from cell import load_module

    record = recorded("nested_hit")

    class Dispatcher:
        __name__ = "many_p1"

    monkeypatch.setattr(trace, "startup_record", lambda: copy.deepcopy(record))
    monkeypatch.setattr(trace, "dispatchers", lambda: [Dispatcher()])
    ctx = {"trace": {"step_module": STEP_MODULE}}
    values = {name: load_module("layer_metrics", name).read(ctx) for name in (
        "step_trace_s", "step_lower_s", "step_load_s", "other_programs_s", "data_host_s",
        "state_init_s")}
    assert values == {"step_trace_s": 2.0, "step_lower_s": 1.0, "step_load_s": 2.8,
                      "other_programs_s": pytest.approx(1.3), "data_host_s": 2.0,
                      "state_init_s": 1.0}
    lines = [line for line in capsys.readouterr().out.splitlines()
             if line.startswith("grid startup")]
    assert len(lines) == 1 and json.loads(lines[0].split(" ", 2)[2])["step_program"] == "many_p1"


def test_a_program_without_the_record_gives_nothing_to_read(capsys, monkeypatch):
    """The parent of PR 37: ``obs.trace`` has no ``startup_record``."""
    from aggregathor_tpu.obs import trace
    from cell import load_module

    monkeypatch.delattr(trace, "startup_record")
    ctx = {"trace": {"step_module": STEP_MODULE}}
    for name in ("step_trace_s", "step_lower_s", "step_load_s", "other_programs_s",
                 "data_host_s", "state_init_s"):
        assert load_module("layer_metrics", name).read(ctx) is None
    said = [line for line in capsys.readouterr().out.splitlines()
            if line.startswith("grid startup")]
    assert len(said) == 1 and "nothing to read" in said[0]


def test_the_manifest_lists_the_six_for_every_cell():
    from cell import load_json, ROOT

    manifest = load_json(os.path.join(ROOT, "BENCHMARK.json"), "manifest")
    # their order among themselves, wherever later PRs' metrics come to stand
    six = [metric for metric in manifest["per_layer"] if metric["moves"] == "setup_s"]
    assert [metric["name"] for metric in six] == [
        "step_trace_s", "step_lower_s", "step_load_s", "other_programs_s", "data_host_s",
        "state_init_s"]
    assert all("workloads" not in metric and metric["unit"] == "s"
               and metric["source"] == "program_counter" for metric in six)
    assert all(os.path.exists(os.path.join(GRID, "layer_metrics", metric["name"] + ".py"))
               for metric in six)
