"""The command off the chip: it refuses; and the rest of a run, driven past the
look for a chip at a tiny size, decides ``correct`` — true for the sound path,
false for a timed path broken underneath (a state returned unchanged, an update
of twice the size or of the wrong sign) and false for the control, the
program's own ``dtype:bfloat16`` path; and a trace reduction that contradicts
itself ends the run in words."""

import copy
import json
import os
import subprocess
import sys

import pytest

GRID = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_command_refuses_without_a_tpu():
    done = subprocess.run(
        [sys.executable, os.path.join(GRID, "run.py"), "--workload", "cnnet_krum_sampled",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=os.path.dirname(GRID), env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert done.returncode != 0
    assert "TPU" in done.stderr
    for line in done.stdout.splitlines():
        assert not line.startswith("{"), "a result line was printed without a chip"


def tiny_spec():
    from cell import cell_spec

    spec = cell_spec("cnnet_krum_sampled")
    config = spec["config_data"]
    config["batch_per_worker"] = 16
    config["experiment_args"] = ["batch-size:16", "augment:device"]
    spec["traffic_data"]["unroll"] = 3
    return spec


def run_tiny(make_cell=None):
    import jax

    import run

    return run.run_cell(tiny_spec(), 2 ** 31 + 11, 0.5, False, jax.devices(),
                        device_metrics=False, make_cell=make_cell)


def compared(capsys):
    return {c["number"]: c for c in (
        json.loads(line.split(" ", 2)[2]) for line in capsys.readouterr().out.splitlines()
        if line.startswith("grid compare {"))}


def unchanged_state(spec, devices):
    import jax
    import jax.numpy as jnp

    from cell import Cell

    cell = Cell(spec, devices)
    real = cell.multi

    def multi(state, dataset):
        before = jax.tree.map(jnp.copy, state.params)
        after, metrics = real(state, dataset)
        return after.replace(params=before), metrics

    cell.multi = multi
    return cell


def rate_times(factor):
    """The program steps at ``factor`` times the configuration's rate; the
    reference keeps the configuration's."""
    def make_cell(spec, devices):
        from cell import Cell

        wrong = copy.deepcopy(spec)
        wrong["config_data"]["learning_rate_args"] = ["initial-rate:%r" % (0.001 * factor)]
        cell = Cell(wrong, devices)
        cell.spec = spec
        return cell
    return make_cell


def bf16_path(spec, devices):
    from cell import Cell
    from readings import BF16_PATH_ARGS

    return Cell(spec, devices, extra_experiment_args=BF16_PATH_ARGS)


def test_sound_path_is_correct_and_prints_no_metric(capsys):
    result = run_tiny()
    assert result["correct"] is True and result["failed"] == 0
    assert result["metrics"] == {} and "memory_peak_bytes" not in result["device"]
    said = capsys.readouterr()
    numbers = {"narrow_products", "loss_gap", "grad_norm_gap", "dparam_gap"}
    assert {json.loads(line.split(" ", 2)[2])["number"] for line in said.out.splitlines()
            if line.startswith("grid compare {")} == numbers
    # each number beside its limit: the result's last key and standard error's last lines
    assert list(result)[-1] == "compared"
    assert set(result["compared"]) == numbers | {"failed", "programs_loaded"}
    assert all(value <= limit for value, limit in result["compared"].values())
    assert [line.split()[2] for line in said.err.splitlines()[-6:]] == list(result["compared"])


@pytest.mark.parametrize("broken, number", [
    (unchanged_state, "dparam_gap"), (rate_times(2.0), "dparam_gap"),
    (rate_times(-1.0), "loss_gap")], ids=["unchanged", "twice_the_rate", "wrong_sign"])
def test_broken_timed_path_is_not_correct(broken, number, capsys):
    result = run_tiny(broken)
    assert result["correct"] is False
    assert compared(capsys)[number]["within"] is False
    assert result["compared"][number][0] > result["compared"][number][1]


def test_control_is_not_correct(capsys):
    """The lower precision fails the exact number, whatever the seed."""
    assert run_tiny(bf16_path)["correct"] is False
    assert compared(capsys)["narrow_products"]["value"] > 0


def test_a_contradiction_that_stands_leaves_in_words(monkeypatch, capsys):
    """``main`` past the look for a chip, the run's reduction contradicting
    itself: the words on standard output, a code of their own, no result line."""
    import run
    import trace_reduce

    def contradicted(*_args, **_kwargs):
        raise trace_reduce.TraceContradiction("phases cover 0.450 of the step's operations")

    monkeypatch.setattr(run, "require_chips", lambda devices, chips: None)
    monkeypatch.setattr(run, "run_cell", contradicted)
    with pytest.raises(SystemExit) as left:
        run.main(["--workload", "cnnet_krum_sampled", "--seed", "1", "--seconds", "1",
                  "--trace", "1"])
    assert left.value.code == run.CONTRADICTION_EXIT and run.CONTRADICTION_EXIT not in (0, 1, 2)
    said = capsys.readouterr().out.splitlines()
    assert said[-1] == "grid contradiction: phases cover 0.450 of the step's operations"
    assert not any(line.startswith("{") for line in said)


@pytest.mark.parametrize("workload", ["resnet50_bulyan_4chip"])
def test_narrow_products_on_the_four_chip_program(workload):
    """Traced only, at the cell's own size and mesh: none in the configuration's
    float32, some in the control."""
    import jax

    import check
    from cell import Cell, cell_spec
    from readings import BF16_PATH_ARGS

    spec = cell_spec(workload)
    for extra, expected in (((), False), (BF16_PATH_ARGS, True)):
        cell = Cell(spec, jax.devices(), extra_experiment_args=extra)
        state = jax.eval_shape(cell.seeded_state, 1)
        assert (check.narrow_products(cell, state, cell.arrays) > 0) is expected


@pytest.mark.parametrize("key, name, kind", [
    ("input_source", "stream", "feeds"), ("attack", "little", "attacks"),
    ("aggregator", "brute", "rules")])
def test_a_missing_file_fails_by_name(key, name, kind):
    import jax

    import check
    from cell import Cell

    spec = tiny_spec()
    cell = Cell(spec, jax.devices())
    spec["traffic_data"][key] = name
    with pytest.raises(SystemExit, match="%s.*%s" % (kind, name)):
        Cell(spec, jax.devices()) if kind == "feeds" else check.PlainReference(cell)
