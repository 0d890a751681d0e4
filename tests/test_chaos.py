"""Chaos subsystem tests: schedule DSL, regime boundaries, stragglers,
engine integration (flat + sharded), CLI plumbing and the campaign harness."""

import json
import os

import jax
import numpy as np
import pytest

from aggregathor_tpu import gars, models
from aggregathor_tpu.chaos import ChaosSchedule
from aggregathor_tpu.chaos.campaign import CELL_KEYS, SCHEMA
from aggregathor_tpu.chaos.campaign import main as campaign_main
from aggregathor_tpu.core import build_optimizer, build_schedule
from aggregathor_tpu.parallel import RobustEngine, attacks, lossy, make_mesh
from aggregathor_tpu.utils import UserException


def flat_params(state):
    return np.concatenate([np.ravel(np.asarray(x)) for x in jax.tree_util.tree_leaves(state.params)])


def make_setup(gar_name="average", n=8, f=0, nb_devices=8, chaos=None, nb_real_byz=0,
               lossy_link=None, lr=0.05):
    exp = models.instantiate("mnist", ["batch-size:16"])
    gar = gars.instantiate(gar_name, n, f)
    tx = build_optimizer("sgd", build_schedule("fixed", ["initial-rate:%s" % lr]))
    engine = RobustEngine(make_mesh(nb_workers=nb_devices), gar, nb_workers=n,
                          nb_real_byz=nb_real_byz, chaos=chaos, lossy_link=lossy_link)
    step = engine.build_step(exp.loss, tx)
    state = engine.init_state(exp.init(jax.random.PRNGKey(42)), tx, seed=1)
    return exp, engine, step, state


def run_steps(exp, engine, step, state, count, seed=3, with_metrics=False):
    it = exp.make_train_iterator(engine.nb_workers, seed=seed)
    losses, regimes = [], []
    for _ in range(count):
        state, metrics = step(state, engine.shard_batch(next(it)))
        losses.append(float(metrics["total_loss"]))
        if with_metrics and "chaos_regime" in metrics:
            regimes.append(int(metrics["chaos_regime"]))
    if with_metrics:
        return state, losses, regimes
    return state, losses


# --------------------------------------------------------------------- #
# schedule DSL


def test_schedule_parses_full_grammar():
    sched = ChaosSchedule(
        "0:calm 500:drop=0.3 1000:attack=empire,epsilon=4.0 "
        "1500:straggle=0.25,straggle-mode=stale", 8, nb_real_byz=2,
    )
    assert len(sched) == 4
    assert [r.start for r in sched.regimes] == [0, 500, 1000, 1500]
    assert sched.regimes[0].spec == "calm"
    assert sched.regimes[1].drop_rate == pytest.approx(0.3)
    assert sched.regimes[2].attack is not None and sched.regimes[2].attack.omniscient
    assert sched.regimes[2].attack.epsilon == pytest.approx(4.0)
    assert sched.regimes[3].straggler_rate == pytest.approx(0.25)
    assert sched.regimes[3].straggler_stale
    assert sched.has_drop and sched.has_stragglers and sched.has_omniscient_attacks
    assert sched.needs_carry  # the stale regime rides the CLEVER carry
    assert not sched.has_local_attacks
    # out-of-order segments sort; a local attack flips the family flags
    sched2 = ChaosSchedule("40:attack=signflip,scale=2.0 0:calm", 4, nb_real_byz=1)
    assert [r.start for r in sched2.regimes] == [0, 40]
    assert sched2.has_local_attacks and not sched2.has_omniscient_attacks


def test_schedule_jitter_heavy_tail_parse():
    """``jitter=SIGMA`` rides a straggler regime: per-regime lognormal
    sigma for the HOST straggler model (bounded-wait); the in-graph
    lateness simulation stays binary (parallel/bounded.py)."""
    sched = ChaosSchedule(
        "0:calm 10:straggle=0.5,jitter=1.5 20:straggle=1.0", 8)
    assert [r.straggler_jitter for r in sched.regimes] == [0.0, 1.5, 0.0]
    assert list(sched._straggler_jitter) == [0.0, 1.5, 0.0]
    assert sched.has_stragglers and not sched.needs_carry


def test_schedule_implicit_calm_at_zero():
    sched = ChaosSchedule("100:drop=0.5", 4)
    assert len(sched) == 2
    assert sched.regimes[0].start == 0 and sched.regimes[0].spec == "calm"
    assert sched.regime_at(99) == 0 and sched.regime_at(100) == 1


@pytest.mark.parametrize("spec,nb_byz", [
    ("", 0),                               # empty schedule
    ("   ", 0),                            # whitespace only
    ("calm", 0),                           # missing STEP:
    ("x:calm", 0),                         # non-integer step
    ("-5:calm", 0),                        # negative step
    ("0:calm 0:drop=0.1", 0),              # duplicate start
    ("0:bogus", 0),                        # not calm, not KEY=VALUE
    ("0:drop=1.5", 0),                     # rate out of [0, 1]
    ("0:drop=abc", 0),                     # non-numeric rate
    ("0:straggle=2", 0),                   # straggle out of range
    ("0:straggle-mode=stale", 0),          # mode without a rate
    ("0:straggle=0.5,straggle-mode=late", 0),  # unknown mode
    ("0:jitter=1.0", 0),                   # jitter without a straggle rate
    ("0:straggle=0.5,jitter=-0.5", 0),     # negative lognormal sigma
    ("0:straggle=0.5,jitter=abc", 0),      # non-numeric sigma
    ("0:attack=nosuchattack", 2),          # unregistered attack
    ("0:epsilon=1.0", 0),                  # attack args without attack=
    ("0:attack=empire", 0),                # attack with no real byz workers
    ("0:drop=0.1,drop=0.2", 0),            # duplicate key in one regime
    ("0:attack=empire,dorp=0.3", 2),       # typo'd DSL key must not vanish
    ("0:attack=empire,epsilom=9.0", 2),    # typo'd attack option either
    ("0:attack=zero,scale=2.0", 2),        # option the attack does not take
])
def test_schedule_rejects(spec, nb_byz):
    with pytest.raises(UserException):
        ChaosSchedule(spec, 8, nb_real_byz=nb_byz)


def test_schedule_rejects_bad_args():
    with pytest.raises(UserException):  # unknown schedule-wide option
        ChaosSchedule("0:calm", 8, args=["bogus:1"])
    with pytest.raises(UserException):  # straggle-workers beyond n
        ChaosSchedule("0:straggle=0.5", 8, args=["straggle-workers:9"])


def test_schedule_process_fault_keys_parse():
    """kill=/hang= are PROCESS-plane keys (benchmarks/soak.py): parsed
    host-side into regime target lists, never shipped to devices."""
    sched = ChaosSchedule(
        "0:calm 10:kill=train 20:hang=backend-a+backend-b,kill=router",
        4, allow_process_faults=True)
    assert sched.has_process_faults
    assert sched.regimes[0].kills == () and sched.regimes[0].hangs == ()
    assert sched.regimes[1].kills == ("train",)
    assert sched.regimes[2].kills == ("router",)
    assert sched.regimes[2].hangs == ("backend-a", "backend-b")
    assert sched.process_faults() == [
        (10, ("train",), ()),
        (20, ("router",), ("backend-a", "backend-b")),
    ]
    # composes with the existing device-plane grammar in one regime
    mixed = ChaosSchedule("0:drop=0.5,kill=train", 4,
                          allow_process_faults=True)
    assert mixed.regimes[0].kills == ("train",)
    # and a schedule WITHOUT process keys reports none
    calm = ChaosSchedule("0:calm", 4, allow_process_faults=True)
    assert not calm.has_process_faults and calm.process_faults() == []


def test_schedule_process_fault_keys_gated():
    """Outside the fleet plane (train CLI: allow_process_faults False)
    kill=/hang= must be rejected loudly, naming the offending regime."""
    with pytest.raises(UserException, match="kill"):
        ChaosSchedule("0:calm 10:kill=train", 4)
    with pytest.raises(UserException, match="fleet plane"):
        ChaosSchedule("0:hang=backend-a", 4)


@pytest.mark.parametrize("spec", [
    "0:kill=",                       # empty target list
    "0:kill=a+",                     # trailing separator
    "0:kill=+a",                     # leading separator
    "0:kill=a++b",                   # empty name between separators
    "0:kill=a+a",                    # duplicate target
    "0:kill=a b",                    # space inside a name
    "0:hang=a,hang=b",               # duplicate key in one regime
])
def test_schedule_process_fault_rejects(spec):
    with pytest.raises(UserException):
        ChaosSchedule(spec, 4, allow_process_faults=True)


def test_parse_process_targets_grammar():
    from aggregathor_tpu.chaos.replica_faults import parse_process_targets

    assert parse_process_targets("kill", "train") == ("train",)
    assert parse_process_targets("hang", "a+b-2+c.3") == ("a", "b-2", "c.3")
    with pytest.raises(UserException):
        parse_process_targets("stop", "train")      # unknown key
    with pytest.raises(UserException):
        parse_process_targets("kill", " train")     # padded name
    with pytest.raises(UserException):
        parse_process_targets("kill", "a:b")        # DSL metachar in name


def test_schedule_regime_boundaries():
    """Off-by-one discipline: the regime starting at s governs steps
    [s, next_start) — host and traced lookups agree at every boundary."""
    sched = ChaosSchedule("0:calm 5:drop=0.5 10:drop=1.0", 4)
    expected = {0: 0, 4: 0, 5: 1, 9: 1, 10: 2, 11: 2, 1000: 2}
    for step, want in expected.items():
        assert sched.regime_at(step) == want, step
    traced = jax.jit(sched.regime_index)
    for step, want in expected.items():
        assert int(traced(np.int32(step))) == want, step
    assert sched.describe(1) == "5:drop=0.5"
    assert sched.transitions() == [(0, "calm"), (5, "drop=0.5"), (10, "drop=1.0")]


# --------------------------------------------------------------------- #
# engine integration (flat)


def test_regime_switch_exact_step_without_retracing():
    """Acceptance: a mid-run calm -> straggler switch changes per-step
    behavior at EXACTLY the scheduled step, inside one compiled program.
    Full-rate NaN-drop stragglers under plain average poison the params on
    the switch step and not one step earlier; the jit cache stays at one
    entry across the transition."""
    chaos = ChaosSchedule("0:calm 3:straggle=1.0,straggle-mode=drop", 8)
    exp, engine, step, state = make_setup("average", n=8, chaos=chaos)
    it = exp.make_train_iterator(8, seed=3)
    regimes = []
    for i in range(3):  # steps 0-2: calm
        state, metrics = step(state, engine.shard_batch(next(it)))
        regimes.append(int(metrics["chaos_regime"]))
    assert np.all(np.isfinite(flat_params(state)))  # calm segment untouched
    state, metrics = step(state, engine.shard_batch(next(it)))  # step 3: late
    regimes.append(int(metrics["chaos_regime"]))
    assert not np.all(np.isfinite(flat_params(state))), "switch step did not apply"
    assert regimes == [0, 0, 0, 1]
    from conftest import assert_zero_recompiles

    assert_zero_recompiles(step)  # regime switches must not retrace


def test_chaotic_run_deterministic():
    """Same seeds -> bit-identical parameters under a schedule exercising
    drop + stragglers + an omniscient attack coalition.  average-nan
    absorbs any drop pattern, so the whole trajectory stays finite and the
    equality is meaningful coordinate by coordinate."""
    spec = "0:drop=0.2 4:attack=empire,epsilon=4.0 8:straggle=0.4,straggle-mode=stale"
    results = []
    for _ in range(2):
        chaos = ChaosSchedule(spec, 8, nb_real_byz=2, args=["packet-coords:1024"])
        exp, engine, step, state = make_setup("average-nan", n=8, f=2, chaos=chaos, nb_real_byz=2)
        state, losses = run_steps(exp, engine, step, state, 10)
        assert np.all(np.isfinite(losses))
        results.append(flat_params(state))
    np.testing.assert_array_equal(results[0], results[1])


def test_chaotic_run_device_count_invariance():
    """A chaotic run is a function of (seed, step, global worker index)
    only: 8 devices and 1 device produce the same loss trajectory and the
    same parameters."""
    spec = "0:calm 2:drop=0.3 5:attack=empire,epsilon=4.0 8:straggle=0.5,straggle-mode=stale"
    outs = []
    for nb_devices in (8, 1):
        chaos = ChaosSchedule(spec, 8, nb_real_byz=2, args=["packet-coords:1024"])
        exp, engine, step, state = make_setup(
            "average-nan", n=8, f=2, nb_devices=nb_devices, chaos=chaos, nb_real_byz=2,
        )
        state, losses = run_steps(exp, engine, step, state, 10)
        assert np.all(np.isfinite(losses)), losses
        outs.append((np.asarray(losses), flat_params(state)))
    np.testing.assert_allclose(outs[0][0], outs[1][0], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(outs[0][1], outs[1][1], rtol=1e-5, atol=1e-6)


def test_stale_straggler_rate_one_matches_clever_full_loss():
    """stale-mode semantics ARE the CLEVER carry semantics: every-step-late
    stragglers reproduce a clever lossy link at drop-rate 1.0 bit-for-bit
    (both re-send the previous received value, both start from the zeroed
    reassembly buffer)."""
    chaos = ChaosSchedule("0:straggle=1.0,straggle-mode=stale", 8)
    exp, eng_chaos, step_chaos, s_chaos = make_setup("average", n=8, chaos=chaos)
    assert eng_chaos.carries_gradients and s_chaos.carry is not None

    link = lossy.LossyLink(8, ["drop-rate:1.0", "packet-coords:1024",
                               "min-coords:0", "clever:true"])
    _, eng_clever, step_clever, s_clever = make_setup("average", n=8, lossy_link=link)

    it1 = exp.make_train_iterator(8, seed=3)
    it2 = exp.make_train_iterator(8, seed=3)
    for _ in range(4):
        s_chaos, _ = step_chaos(s_chaos, eng_chaos.shard_batch(next(it1)))
        s_clever, _ = step_clever(s_clever, eng_clever.shard_batch(next(it2)))
    np.testing.assert_array_equal(flat_params(s_chaos), flat_params(s_clever))
    np.testing.assert_array_equal(np.asarray(s_chaos.carry), np.asarray(s_clever.carry))


def test_straggler_nan_drop_absorbed_by_robust_rules():
    """f always-late NaN-drop stragglers: median and Multi-Krum stay finite
    and converge (the NaN row is excluded), plain average is poisoned —
    the lossy-link matrix (test_engine.py) replayed through the chaos
    scheduler's straggler model."""
    losses_by_rule = {}
    for rule, f in (("median", 2), ("krum", 2)):
        chaos = ChaosSchedule("0:straggle=1.0,straggle-mode=drop", 8,
                              args=["straggle-workers:2"])
        exp, engine, step, state = make_setup(rule, n=8, f=f, chaos=chaos)
        state, losses = run_steps(exp, engine, step, state, 25)
        assert np.all(np.isfinite(flat_params(state))), rule
        assert losses[-1] < losses[0], (rule, losses)
        losses_by_rule[rule] = losses

    chaos = ChaosSchedule("0:straggle=1.0,straggle-mode=drop", 8,
                          args=["straggle-workers:2"])
    exp, engine, step, state = make_setup("average", n=8, chaos=chaos)
    state, _ = run_steps(exp, engine, step, state, 3)
    assert not np.all(np.isfinite(flat_params(state)))


def test_partial_rate_stale_stragglers_keep_training():
    """A 30% stale-straggler regime composes with plain averaging: stale
    re-sends are finite by construction, training converges, and the carry
    threads across steps."""
    chaos = ChaosSchedule("0:straggle=0.3,straggle-mode=stale", 8)
    exp, engine, step, state = make_setup("average", n=8, chaos=chaos)
    state, losses = run_steps(exp, engine, step, state, 25)
    assert np.all(np.isfinite(flat_params(state)))
    assert losses[-1] < losses[0]
    assert np.all(np.isfinite(np.asarray(state.carry)))


def test_chaos_engine_validation():
    mesh = make_mesh(nb_workers=4)
    gar = gars.instantiate("average", 4, 0)
    chaos = ChaosSchedule("0:drop=0.1", 4)
    with pytest.raises(UserException):  # chaos + static attack
        RobustEngine(mesh, gar, 4, nb_real_byz=1, chaos=chaos,
                     attack=attacks.instantiate("zero", 4, 1))
    with pytest.raises(UserException):  # chaos + static lossy link
        RobustEngine(mesh, gar, 4, chaos=chaos,
                     lossy_link=lossy.LossyLink(2, ["drop-rate:0.1"]))
    with pytest.raises(UserException):  # worker-count mismatch
        RobustEngine(mesh, gar, 4, chaos=ChaosSchedule("0:calm", 8))
    with pytest.raises(UserException):  # attack regimes need a coalition
        RobustEngine(mesh, gar, 4,
                     chaos=ChaosSchedule("0:attack=zero", 4, nb_real_byz=1))
    with pytest.raises(UserException):  # coalition-size mismatch
        RobustEngine(mesh, gar, 4, nb_real_byz=2,
                     chaos=ChaosSchedule("0:attack=zero", 4, nb_real_byz=1))


def test_chaos_attack_regime_switch_flat():
    """An empire coalition that wakes at step 5: the pre-switch segment is
    clean training (identical to a calm run), the post-switch segment is
    where the trajectories diverge — and median still converges."""
    spec = "0:calm 5:attack=empire,epsilon=4.0"
    chaos = ChaosSchedule(spec, 8, nb_real_byz=2)
    exp, engine, step, state = make_setup("median", n=8, f=2, chaos=chaos, nb_real_byz=2)
    state, losses, regimes = run_steps(exp, engine, step, state, 12, with_metrics=True)
    assert regimes == [0] * 5 + [1] * 7
    assert np.all(np.isfinite(losses)), losses

    calm_exp, calm_engine, calm_step, calm_state = make_setup("median", n=8, f=2)
    calm_state, calm_losses = run_steps(calm_exp, calm_engine, calm_step, calm_state, 12)
    # losses are reported pre-update, so the first divergence caused by the
    # step-5 regime's forged gradients shows in the step-6 loss
    np.testing.assert_allclose(losses[:6], calm_losses[:6], rtol=1e-5)
    assert not np.allclose(losses[6:], calm_losses[6:], rtol=1e-5)


def test_sharded_engine_adam_state_sharded():
    """The explicit opt-state out-shardings in init_state: adam's mu/nu
    (params-treedef subtrees) must take the params' NamedSharding layouts —
    not replicate, not commit to one device — and the update must run."""
    import optax

    from aggregathor_tpu.models import transformer as tfm
    from aggregathor_tpu.parallel import RobustEngine

    cfg = tfm.TransformerConfig(vocab_size=32, d_model=16, n_heads=2, n_layers=2)
    mesh = make_mesh(nb_workers=2, model_parallelism=2, pipeline_parallelism=2)
    tx = optax.adam(1e-3)
    engine = RobustEngine(mesh, gars.instantiate("median", 2, 0), sharding="sharded")
    state = engine.init_state(lambda k: tfm.init_params(cfg, k, n_stages=2),
                              tfm.param_specs(cfg), tx)
    param_shardings = jax.tree_util.tree_leaves(
        jax.tree.map(lambda p: p.sharding, state.params))
    mu = state.opt_state[0].mu  # ScaleByAdamState
    mu_shardings = jax.tree_util.tree_leaves(jax.tree.map(lambda m: m.sharding, mu))
    assert len(mu_shardings) == len(param_shardings)
    for ms, ps in zip(mu_shardings, param_shardings):
        assert ms == ps, (ms, ps)
    loss_fn = tfm.make_pipeline_loss(cfg, n_stages=2, microbatches=2)
    step = engine.build_step(loss_fn, tx, state)
    rng = np.random.default_rng(1)
    batch = engine.shard_batch({
        "tokens": rng.integers(0, 32, (2, 4, 16)),
        "targets": rng.integers(0, 32, (2, 4, 16)),
    })
    state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["total_loss"]))


def test_sharded_engine_chaos_regimes():
    """The fully-sharded engine accepts the same schedule: a signflip
    coalition wakes at step 2 and a stale straggler regime at step 4; the
    run stays finite (stale re-sends are finite), the regime metric tracks
    the schedule, and the carry buffer threads worker-sharded."""
    import optax

    from aggregathor_tpu.models import transformer as tfm
    from aggregathor_tpu.parallel import RobustEngine

    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2, n_layers=2)
    mesh = make_mesh(nb_workers=2, model_parallelism=2, pipeline_parallelism=2)
    tx = optax.sgd(0.05)
    chaos = ChaosSchedule(
        "0:calm 2:attack=signflip,scale=5.0 4:straggle=1.0,straggle-mode=stale",
        2, nb_real_byz=1,
    )
    engine = RobustEngine(mesh, gars.instantiate("median", 2, 0), nb_real_byz=1, chaos=chaos,
                          sharding="sharded")
    assert engine.carries_gradients
    state = engine.init_state(lambda k: tfm.init_params(cfg, k, n_stages=2),
                              tfm.param_specs(cfg), tx)
    loss_fn = tfm.make_pipeline_loss(cfg, n_stages=2, microbatches=2)
    step = engine.build_step(loss_fn, tx, state)
    rng = np.random.default_rng(7)
    batch = engine.shard_batch({
        "tokens": rng.integers(0, 64, (2, 4, 16)),
        "targets": rng.integers(0, 64, (2, 4, 16)),
    })
    losses, regimes = [], []
    for _ in range(6):
        state, metrics = step(state, batch)
        losses.append(float(metrics["total_loss"]))
        regimes.append(int(metrics["chaos_regime"]))
    assert regimes == [0, 0, 1, 1, 2, 2]
    assert np.all(np.isfinite(losses)), losses


# --------------------------------------------------------------------- #
# CLI runner plumbing


def test_runner_chaos_end_to_end(tmp_path):
    """--chaos through the real CLI: chaos_regime lands in the eval TSV as
    an int column, the summary stream carries both the scalar and the
    regime-switch events, and the run completes."""
    from aggregathor_tpu.cli import runner

    eval_file = str(tmp_path / "eval.tsv")
    sum_dir = str(tmp_path / "sum")
    assert 0 == runner.main([
        "--experiment", "mnist", "--experiment-args", "batch-size:16",
        "--aggregator", "krum",
        "--nb-workers", "8", "--nb-decl-byz-workers", "2",
        "--nb-real-byz-workers", "2",
        "--chaos", "0:calm 6:attack=signflip,scale=10.0",
        "--max-step", "12",
        "--learning-rate-args", "initial-rate:0.05",
        "--evaluation-delta", "5", "--evaluation-period", "-1",
        "--evaluation-file", eval_file,
        "--summary-dir", sum_dir, "--summary-delta", "4",
    ])
    lines = [l.split("\t") for l in open(eval_file).read().strip().splitlines()]
    regimes = {}
    for fields in lines:
        metrics = dict(field.split(":", 1) for field in fields[2:])
        regimes[int(fields[1])] = metrics["chaos_regime"]
    assert regimes[1] == "0" and regimes[12] == "1", regimes  # int spelling, right value
    events = [json.loads(l) for l in open(os.path.join(sum_dir, os.listdir(sum_dir)[0]))]
    switches = [ev for ev in events if ev.get("event") == "chaos_regime_switch"]
    assert len(switches) == 1 and switches[0]["step"] == 6 and switches[0]["regime"] == 1
    scalar_regimes = [ev["chaos_regime"] for ev in events if "chaos_regime" in ev]
    assert 0 in scalar_regimes and 1 in scalar_regimes


def test_runner_rejects_chaos_plus_static_attack():
    from aggregathor_tpu.cli import runner

    with pytest.raises(UserException):
        runner.main([
            "--experiment", "mnist", "--aggregator", "average", "--nb-workers", "4",
            "--nb-real-byz-workers", "1", "--attack", "zero",
            "--chaos", "0:drop=0.1", "--max-step", "2",
        ])


# --------------------------------------------------------------------- #
# campaign harness


def test_campaign_micro_matrix(tmp_path):
    """Acceptance (a): a CPU-only micro campaign through campaign.main —
    plain average fails under the empire regime, median converges — and the
    resilience-matrix JSON honors its schema contract."""
    out = str(tmp_path / "matrix.json")
    report = str(tmp_path / "report.md")
    assert 0 == campaign_main([
        "--experiment", "mnist", "--experiment-args", "batch-size:16",
        "--nb-workers", "8", "--nb-decl-byz-workers", "2",
        "--nb-real-byz-workers", "2",
        "--gars", "average", "median", "--attacks", "empire,epsilon=4.0",
        "--nb-steps", "25", "--output", out, "--report", report,
    ])
    matrix = json.load(open(out))
    assert matrix["schema"] == SCHEMA
    assert len(matrix["cells"]) == 4  # 2 gars x (calm + empire)
    for cell in matrix["cells"]:
        for key in CELL_KEYS:
            assert key in cell, key
        assert len(cell["losses"]) >= 1
    by = {(c["gar"], c["scenario"]): c for c in matrix["cells"]}
    assert by[("average", "calm")]["converged"]
    assert by[("median", "calm")]["converged"]
    assert by[("median", "empire")]["converged"]
    assert not by[("average", "empire")]["converged"]
    # calm cells carry no coalition; attack cells carry the requested one
    assert by[("average", "calm")]["nb_real_byz"] == 0
    assert by[("median", "empire")]["nb_real_byz"] == 2
    text = open(report).read()
    assert "| GAR |" in text and "median" in text and "empire" in text


def test_campaign_rejects_ambiguous_grids(tmp_path):
    """Scenario names key the matrix and report: duplicates are refused, and
    --breakdown without any attack scenario (nothing to size a coalition
    for) is refused rather than comparing two attacker-free runs."""
    with pytest.raises(UserException):  # two scenarios both named 'empire'
        campaign_main([
            "--gars", "median", "--nb-steps", "1",
            "--attacks", "empire,epsilon=1.0", "empire,epsilon=8.0",
        ])
    with pytest.raises(UserException):  # breakdown on a storm-only schedule
        campaign_main([
            "--gars", "median", "--nb-steps", "1", "--breakdown",
            "--schedules", "storm=0:drop=0.5",
        ])


@pytest.mark.slow
def test_campaign_breakdown_boundary(tmp_path):
    """Acceptance: the empirical f-breakdown probe — the declared budget
    (r = f) converges, a Byzantine majority (r = n//2 + 1) does not, for
    both selection and coordinate rules."""
    out = str(tmp_path / "matrix.json")
    assert 0 == campaign_main([
        "--experiment", "mnist", "--experiment-args", "batch-size:16",
        "--nb-workers", "8", "--nb-decl-byz-workers", "2",
        "--nb-real-byz-workers", "2",
        "--gars", "median", "krum", "--attacks", "empire,epsilon=4.0",
        "--nb-steps", "25", "--breakdown", "--output", out,
    ])
    matrix = json.load(open(out))
    assert matrix["breakdown"], "breakdown probe produced no entries"
    for entry in matrix["breakdown"]:
        assert entry["r_within"] == 2 and entry["r_beyond"] == 5
        assert entry["bound_holds"] is True, entry
