"""End-to-end engine tests: convergence, device-count invariance, attacks, lossy links."""

import json
import os

import jax
import numpy as np
import pytest

from aggregathor_tpu import gars, models
from aggregathor_tpu.core import build_optimizer, build_schedule
from aggregathor_tpu.parallel import RobustEngine, attacks, lossy, make_mesh


def flat_params(state):
    return np.concatenate([np.ravel(np.asarray(x)) for x in jax.tree_util.tree_leaves(state.params)])


def make_setup(gar_name="average", n=8, f=0, nb_devices=1, attack=None,
               attack_args=(), nb_real_byz=0, lossy_spec=None, lr=0.05,
               mode="flat", experiment="mnist"):
    """Delegates to the suite-wide cached engine-fixture factory
    (tests/conftest.py, ISSUE 10 satellite): identical configurations share
    one compiled step across tests; multi-device coverage lives in the
    explicit device-count invariance sweeps, so the default is the cheap
    1-device mesh."""
    from conftest import build_engine_stack

    exp, engine, tx, step, make_state = build_engine_stack(
        mode=mode, experiment=experiment, gar=gar_name, n=n, f=f, nb_devices=nb_devices,
        lr=lr, attack=attack, attack_args=attack_args, nb_real_byz=nb_real_byz,
        lossy=lossy_spec)
    return exp, engine, step, make_state()


def run_steps(exp, engine, step, state, count, seed=3):
    it = exp.make_train_iterator(engine.nb_workers, seed=seed)
    losses = []
    for _ in range(count):
        state, metrics = step(state, engine.shard_batch(next(it)))
        losses.append(float(metrics["total_loss"]))
    return state, losses


@pytest.mark.parametrize(
    "gar_name,f",
    [("average", 0), ("median", 1), ("krum", 1),
     # order-statistic-heavy rules compile slowly on the 1-core CPU host;
     # their convergence is also covered by the oracle property tests
     pytest.param("bulyan", 1, marks=pytest.mark.slow),
     pytest.param("trimmed-mean", 1, marks=pytest.mark.slow),
     pytest.param("centered-clip", 1, marks=pytest.mark.slow)],
)
def test_training_decreases_loss(gar_name, f):
    exp, engine, step, state = make_setup(gar_name, n=8, f=f)
    state, losses = run_steps(exp, engine, step, state, 25)
    assert losses[-1] < losses[0], "%s: loss %r -> %r" % (gar_name, losses[0], losses[-1])


# Neither model's d is a multiple of W x 128, so on W > 1 devices every run
# below pads its rows to the aligned block width (engine._block_width): mnist
# (d = 79,510) takes the 1,024-column tile at every W, digits (d = 7,510) the
# 128-column one on 8 devices (939 columns a block) and 1,024 on 4 and 2.
@pytest.mark.parametrize("experiment", ["mnist", "digits"])
def test_device_count_invariance(experiment):
    """n=8 workers on 8 devices must produce the same updates as on 1 device
    (the sharded all_to_all/psum path vs the degenerate local path)."""
    results = []
    for nb_devices in (8, 1):
        exp, engine, step, state = make_setup("krum", n=8, f=1, nb_devices=nb_devices,
                                              experiment=experiment)
        state, _ = run_steps(exp, engine, step, state, 3)
        results.append(flat_params(state))
    np.testing.assert_allclose(results[0], results[1], rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("experiment", ["mnist", "digits"])
def test_intermediate_device_count_invariance(experiment):
    """n=8 over 4 devices (2 workers/device) matches the fully sharded run."""
    results = []
    for nb_devices in (8, 4, 2):
        exp, engine, step, state = make_setup("bulyan", n=8, f=1, nb_devices=nb_devices,
                                              experiment=experiment)
        state, _ = run_steps(exp, engine, step, state, 2)
        results.append(flat_params(state))
    np.testing.assert_allclose(results[0], results[1], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(results[0], results[2], rtol=1e-5, atol=1e-6)


def test_krum_resists_signflip_attack():
    """f=2 sign-flipping Byzantine workers: krum must still converge while
    plain averaging visibly degrades (the AggregaThor thesis in one test)."""
    exp, engine, step, state = make_setup("krum", n=8, f=2, attack="signflip",
                                          attack_args=("scale:10.0",), nb_real_byz=2)
    state, losses = run_steps(exp, engine, step, state, 25)
    assert losses[-1] < losses[0]

    exp2, engine2, step2, state2 = make_setup(
        "average", n=8, f=0, attack="signflip", attack_args=("scale:10.0",),
        nb_real_byz=2)
    state2, losses2 = run_steps(exp2, engine2, step2, state2, 25)
    assert losses2[-1] > losses[-1], "averaging under attack should do worse than krum"


def test_omniscient_attack_applies():
    """Empire (epsilon=2: byz sum overwhelms the honest sum and flips the
    averaged gradient) — coordinate-wise median resists it, plain averaging
    diverges.  (Note: Krum is *expected* to fall to Empire — identical
    colluding vectors have zero mutual distance and win the score; that
    weakness is the reason Bulyan exists.)"""
    exp, engine, step, state = make_setup("median", n=8, f=2, attack="empire",
                                          attack_args=("epsilon:4.0",), nb_real_byz=2)
    state, losses = run_steps(exp, engine, step, state, 25)
    assert losses[-1] < losses[0]

    exp2, engine2, step2, state2 = make_setup(
        "average", n=8, f=0, attack="empire", attack_args=("epsilon:4.0",),
        nb_real_byz=2)
    state2, losses2 = run_steps(exp2, engine2, step2, state2, 25)
    assert losses2[-1] > losses[-1], "average under empire should do worse than median"


def test_lossy_link_with_average_nan():
    """Lossy workers NaN-mask packet runs; average-nan absorbs them."""
    exp, engine, step, state = make_setup(
        "average-nan", n=8, f=0,
        lossy_spec=(4, "drop-rate:0.3", "packet-coords:1024", "min-coords:0"))
    state, losses = run_steps(exp, engine, step, state, 25)
    assert losses[-1] < losses[0]
    assert np.all(np.isfinite(flat_params(state)))


def test_lossy_link_breaks_plain_average():
    """Same lossy link with plain average: NaNs reach the params (the reason
    average-nan exists; mpi_rendezvous_mgr.patch:833-841 semantics)."""
    exp, engine, step, state = make_setup(
        "average", n=8, f=0,
        lossy_spec=(4, "drop-rate:0.3", "packet-coords:1024", "min-coords:0"))
    state, _ = run_steps(exp, engine, step, state, 3)
    assert not np.all(np.isfinite(flat_params(state)))


@pytest.mark.slow
def test_bf16_exchange_converges_and_stays_invariant():
    """bfloat16 wire exchange: training still converges, and the result is
    device-count invariant (the quantization happens identically before the
    collective on every layout)."""
    import optax

    results = []
    for nb_devices in (8, 1):
        exp = models.instantiate("mnist", ["batch-size:16"])
        gar = gars.instantiate("krum", 8, 1)
        tx = optax.sgd(0.05)
        engine = RobustEngine(make_mesh(nb_workers=nb_devices), gar, nb_workers=8,
                              exchange="bf16")
        step = engine.build_step(exp.loss, tx)
        state = engine.init_state(exp.init(jax.random.PRNGKey(42)), tx, seed=1)
        state, losses = run_steps(exp, engine, step, state, 20)
        assert losses[-1] < losses[0]
        results.append(flat_params(state))
    np.testing.assert_allclose(results[0], results[1], rtol=1e-4, atol=1e-5)


def test_worker_momentum_converges_under_attack():
    """History-aware robustness: workers send bias-corrected momenta; krum on
    momenta still converges under a signflip coalition, and the momentum
    buffer is threaded worker-sharded through the step."""
    import optax

    atk = attacks.instantiate("signflip", 8, 2, ["scale:10.0"])
    exp = models.instantiate("mnist", ["batch-size:16"])
    gar = gars.instantiate("krum", 8, 2)
    tx = optax.sgd(0.05)
    engine = RobustEngine(make_mesh(nb_workers=8), gar, nb_workers=8, nb_real_byz=2,
                          attack=atk, worker_momentum=0.9)
    step = engine.build_step(exp.loss, tx)
    state = engine.init_state(exp.init(jax.random.PRNGKey(42)), tx, seed=1)
    assert state.momentum is not None and state.momentum.shape[0] == 8
    state, losses = run_steps(exp, engine, step, state, 25)
    assert losses[-1] < losses[0]
    assert np.all(np.isfinite(np.asarray(state.momentum)))


def test_worker_momentum_matches_closed_form():
    """n=1, average GAR, one fixed batch: the sent value is the bias-corrected
    EMA of a constant-ish gradient stream; step 1 must equal plain SGD's."""
    import optax

    exp = models.instantiate("mnist", ["batch-size:8"])
    tx = optax.sgd(0.1)
    batch = next(exp.make_train_iterator(1, seed=5))

    def one_step_params(worker_momentum):
        gar = gars.instantiate("average", 1, 0)
        engine = RobustEngine(make_mesh(nb_workers=1), gar, nb_workers=1,
                              worker_momentum=worker_momentum)
        step = engine.build_step(exp.loss, tx)
        state = engine.init_state(exp.init(jax.random.PRNGKey(0)), tx)
        state, _ = step(state, engine.shard_batch(batch))
        return flat_params(state)

    # bias correction makes the first momentum step IDENTICAL to plain SGD
    np.testing.assert_allclose(one_step_params(0.9), one_step_params(None),
                               rtol=1e-5, atol=1e-6)


def test_worker_momentum_multi_step_matches_single():
    import optax

    exp = models.instantiate("mnist", ["batch-size:16"])
    tx = optax.sgd(0.05)
    gar = gars.instantiate("average", 4, 0)
    engine = RobustEngine(make_mesh(nb_workers=4), gar, nb_workers=4, worker_momentum=0.8)
    single = engine.build_step(exp.loss, tx)
    multi = engine.build_multi_step(exp.loss, tx)
    it = exp.make_train_iterator(4, seed=9)
    batches = [next(it) for _ in range(4)]
    stacked = jax.tree_util.tree_map(lambda *xs: np.stack(xs), *batches)
    s1 = engine.init_state(exp.init(jax.random.PRNGKey(0)), tx)
    for b in batches:
        s1, _ = single(s1, engine.shard_batch(b))
    s2 = engine.init_state(exp.init(jax.random.PRNGKey(0)), tx)
    s2, _ = multi(s2, engine.shard_batches(stacked))
    for a, b in zip(jax.tree_util.tree_leaves(jax.device_get(s1.params)),
                    jax.tree_util.tree_leaves(jax.device_get(s2.params))):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(s1.momentum), np.asarray(s2.momentum),
                               rtol=1e-6, atol=1e-7)


def test_worker_momentum_bias_correction_restarts_on_restore(tmp_path):
    """After restore the momentum buffer re-zeroes, so its bias correction
    must restart with it: the first post-restore step equals a plain-SGD
    step on the restored params, not a (1-beta)-attenuated one."""
    import optax

    from aggregathor_tpu.obs import Checkpoints

    exp = models.instantiate("mnist", ["batch-size:8"])
    tx = optax.sgd(0.1)
    gar = gars.instantiate("average", 4, 0)
    engine = RobustEngine(make_mesh(nb_workers=4), gar, nb_workers=4, worker_momentum=0.9)
    step = engine.build_step(exp.loss, tx)
    state = engine.init_state(exp.init(jax.random.PRNGKey(0)), tx)
    it = exp.make_train_iterator(4, seed=1)
    for _ in range(3):
        state, _ = step(state, engine.shard_batch(next(it)))
    ckpts = Checkpoints(str(tmp_path))
    ckpts.save(state)

    template = engine.init_state(exp.init(jax.random.PRNGKey(0)), tx)
    fresh_buffers = (template.carry, template.momentum)
    host_template = jax.device_get(template.replace(carry=None, momentum=None))
    restored, _ = ckpts.restore(host_template)
    restored = engine.put_state(
        restored.replace(carry=fresh_buffers[0], momentum=fresh_buffers[1])
    )
    assert int(jax.device_get(restored.momentum_steps)) == 0
    params_before = jax.device_get(restored.params)
    batch = next(it)
    restored, _ = step(restored, engine.shard_batch(batch))
    momentum_delta = flat_params(restored) - np.concatenate(
        [np.ravel(np.asarray(x)) for x in jax.tree_util.tree_leaves(params_before)])

    plain = RobustEngine(make_mesh(nb_workers=4), gar, nb_workers=4)
    pstep = plain.build_step(exp.loss, tx)
    pstate = plain.init_state(exp.init(jax.random.PRNGKey(0)), tx)
    pstate = pstate.replace(params=plain.replicate(params_before))
    pstate, _ = pstep(pstate, plain.shard_batch(batch))
    plain_delta = flat_params(pstate) - np.concatenate(
        [np.ravel(np.asarray(x)) for x in jax.tree_util.tree_leaves(params_before)])
    np.testing.assert_allclose(momentum_delta, plain_delta, rtol=1e-4, atol=1e-6)


def test_lossy_clever_stale_infill():
    """CLEVER=1 parity (mpi_rendezvous_mgr.patch:833-835): a lost packet keeps
    the previous step's received value, so even plain average stays finite and
    converges where NaN infill destroys it (test_lossy_link_breaks_plain_average)."""
    exp, engine, step, state = make_setup(
        "average", n=8, f=0, lossy_spec=(4, "drop-rate:0.3",
        "packet-coords:1024", "min-coords:0", "clever:true"))
    assert engine.carries_gradients
    assert state.carry is not None and state.carry.shape[0] == 8
    state, losses = run_steps(exp, engine, step, state, 25)
    assert np.all(np.isfinite(flat_params(state)))
    assert losses[-1] < losses[0]


def test_lossy_clever_multi_step_carry():
    """The scanned trainer threads the carry across steps like single steps."""
    exp, engine, _, _ = make_setup(
        "average", n=4, f=0, nb_devices=4, lossy_spec=(2, "drop-rate:0.5",
        "packet-coords:64", "min-coords:0", "clever:true"))
    tx = build_optimizer("sgd", build_schedule("fixed", ["initial-rate:0.05"]))
    multi = engine.build_multi_step(exp.loss, tx)
    it = exp.make_train_iterator(4, seed=7)
    batches = [next(it) for _ in range(4)]
    stacked = jax.tree_util.tree_map(lambda *xs: np.stack(xs), *batches)

    s1 = engine.init_state(exp.init(jax.random.PRNGKey(0)), tx, seed=1)
    single = engine.build_step(exp.loss, tx)
    for b in batches:
        s1, _ = single(s1, engine.shard_batch(b))
    s2 = engine.init_state(exp.init(jax.random.PRNGKey(0)), tx, seed=1)
    s2, _ = multi(s2, engine.shard_batches(stacked))
    for a, b in zip(jax.tree_util.tree_leaves(jax.device_get(s1.params)),
                    jax.tree_util.tree_leaves(jax.device_get(s2.params))):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(s1.carry), np.asarray(s2.carry), rtol=1e-6, atol=1e-7)


def test_eval_step():
    exp, engine, step, state = make_setup("average", n=8)
    eval_step = engine.build_eval(exp.metrics)
    for batch in exp.make_eval_iterator(8):
        out = eval_step(state, engine.shard_batch(batch))
        assert 0.0 <= float(out["accuracy"]) <= 1.0
        break


def test_total_loss_is_sum_of_worker_losses():
    """train metric = total loss across workers (graph.py:304-305 parity)."""
    exp, engine, step, state = make_setup("average", n=8)
    it = exp.make_train_iterator(8, seed=3)
    batch = next(it)
    # copy params to host first: step() donates the state buffers
    params = jax.tree_util.tree_map(np.asarray, state.params)
    _, metrics = step(state, engine.shard_batch(batch))
    expect = 0.0
    for w in range(8):
        wb = {k: v[w] for k, v in batch.items()}
        expect += float(exp.loss(params, wb))
    np.testing.assert_allclose(float(metrics["total_loss"]), expect, rtol=1e-5)


def test_multi_step_matches_single_step_chain():
    """The scanned K-step trainer reproduces K single steps bit-for-bit-ish."""
    import optax

    exp = models.instantiate("mnist", ["batch-size:16"])
    n = 4
    gar = gars.instantiate("krum", n, 1)
    tx = build_optimizer("sgd", build_schedule("fixed", ["initial-rate:0.05"]))
    mesh = make_mesh(nb_workers=4)
    engine = RobustEngine(mesh, gar, nb_workers=n)
    single = engine.build_step(exp.loss, tx)
    multi = engine.build_multi_step(exp.loss, tx)
    repeat = engine.build_multi_step(exp.loss, tx, repeat_steps=5)

    it = exp.make_train_iterator(n, seed=0)
    batches = [next(it) for _ in range(5)]
    stacked = jax.tree_util.tree_map(lambda *xs: np.stack(xs), *batches)

    s1 = engine.init_state(exp.init(jax.random.PRNGKey(0)), tx)
    for b in batches:
        s1, m1 = single(s1, engine.shard_batch(b))
    s2 = engine.init_state(exp.init(jax.random.PRNGKey(0)), tx)
    s2, m2 = multi(s2, engine.shard_batches(stacked))
    assert np.asarray(m2["total_loss"]).shape == (5,)
    for a, b in zip(jax.tree_util.tree_leaves(jax.device_get(s1.params)),
                    jax.tree_util.tree_leaves(jax.device_get(s2.params))):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)

    # repeat form: 5 steps on one batch == 5 single steps on that batch
    s3 = engine.init_state(exp.init(jax.random.PRNGKey(0)), tx)
    s3, m3 = repeat(s3, engine.shard_batch(batches[0]))
    s4 = engine.init_state(exp.init(jax.random.PRNGKey(0)), tx)
    for _ in range(5):
        s4, _ = single(s4, engine.shard_batch(batches[0]))
    for a, b in zip(jax.tree_util.tree_leaves(jax.device_get(s3.params)),
                    jax.tree_util.tree_leaves(jax.device_get(s4.params))):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


def test_worker_metrics_expose_attackers():
    """Opt-in suspicion diagnostics: under a large-deviation Gaussian attack
    with Multi-Krum, the attackers' participation weight is exactly 0 (never
    selected) and their squared distance to the aggregate dominates the
    honest workers'.  (A deviation-100 forgery is an unambiguous outlier at
    every step; signflip can legitimately win Krum selection early on, when
    honest gradients are still noise-dominated.)"""
    import jax
    import numpy as np
    import optax

    from aggregathor_tpu import gars, models
    from aggregathor_tpu.parallel.attacks import instantiate as make_attack
    from aggregathor_tpu.parallel.engine import RobustEngine
    from aggregathor_tpu.parallel.mesh import make_mesh

    n, f = 8, 2
    ex = models.instantiate("mnist", ["batch-size:16"])
    engine = RobustEngine(
        make_mesh(nb_workers=4), gars.instantiate("krum", n, f), n,
        nb_real_byz=f, attack=make_attack("gaussian", n, f, ["deviation:100"]),
        worker_metrics=True,
    )
    tx = optax.sgd(1e-2)
    state = engine.init_state(ex.init(jax.random.PRNGKey(0)), tx)
    step = engine.build_step(ex.loss, tx)
    it = ex.make_train_iterator(n, seed=0)
    for _ in range(3):
        state, metrics = step(state, engine.shard_batch(next(it)))
    wdist = np.asarray(jax.device_get(metrics["worker_sq_dist"]))
    part = np.asarray(jax.device_get(metrics["worker_participation"]))
    assert wdist.shape == (n,) and part.shape == (n,)
    np.testing.assert_allclose(part.sum(), 1.0, rtol=1e-5)
    # attackers (workers 0, 1) are excluded and far from the aggregate
    np.testing.assert_allclose(part[:f], 0.0, atol=1e-7)
    assert wdist[:f].min() > wdist[f:].max()
    # diagnostics off by default: no extra metrics, no extra cost path
    plain = RobustEngine(make_mesh(nb_workers=4), gars.instantiate("krum", n, f), n)
    pstate = plain.init_state(ex.init(jax.random.PRNGKey(0)), tx)
    _, pmetrics = plain.build_step(ex.loss, tx)(pstate, plain.shard_batch(next(it)))
    assert "worker_sq_dist" not in pmetrics


def test_reputation_quarantine_excludes_attacker():
    """Reputation EMA + quarantine: a persistent deviation-100 attacker's
    reputation decays below threshold within a few steps, it gets quarantined
    (row masked NaN, never selected), honest workers stay trusted, and
    training converges."""
    import jax
    import numpy as np
    import optax

    from aggregathor_tpu import gars, models
    from aggregathor_tpu.parallel.attacks import instantiate as make_attack
    from aggregathor_tpu.parallel.engine import RobustEngine
    from aggregathor_tpu.parallel.mesh import make_mesh

    n, f = 8, 2
    ex = models.instantiate("mnist", ["batch-size:16"])
    engine = RobustEngine(
        make_mesh(nb_workers=4), gars.instantiate("krum", n, f), n,
        nb_real_byz=f, attack=make_attack("gaussian", n, f, ["deviation:100"]),
        worker_metrics=True, reputation_decay=0.5, quarantine_threshold=0.4,
    )
    tx = optax.sgd(1e-2)
    state = engine.init_state(ex.init(jax.random.PRNGKey(0)), tx)
    step = engine.build_step(ex.loss, tx)
    it = ex.make_train_iterator(n, seed=0)
    losses = []
    for _ in range(8):
        state, metrics = step(state, engine.shard_batch(next(it)))
        losses.append(float(metrics["total_loss"]))
    rep = np.asarray(jax.device_get(metrics["worker_reputation"]))
    assert rep.shape == (n,)
    # both attackers: the rank signal drops exactly the f farthest, which the
    # deviation-100 forgeries always are -> signal 0 every step
    assert rep[:f].max() < 0.1, rep
    assert rep[f:].min() > 0.9, rep    # honest workers stay trusted
    assert int(jax.device_get(metrics["nb_quarantined"])) == f
    assert np.all(np.isfinite(losses)) and losses[-1] < losses[0]


def test_quarantine_requires_nan_tolerant_rule():
    import pytest

    from aggregathor_tpu import gars
    from aggregathor_tpu.parallel.engine import RobustEngine
    from aggregathor_tpu.parallel.mesh import make_mesh
    from aggregathor_tpu.utils import UserException

    mesh = make_mesh(nb_workers=4)
    with pytest.raises(UserException):  # plain average propagates NaN
        RobustEngine(mesh, gars.instantiate("average", 4, 0), 4,
                     reputation_decay=0.5, quarantine_threshold=0.5)
    with pytest.raises(UserException):  # median SHIFTS under NaN rows, not excludes
        RobustEngine(mesh, gars.instantiate("median", 4, 1), 4,
                     reputation_decay=0.5, quarantine_threshold=0.5)
    with pytest.raises(UserException):  # threshold without decay
        RobustEngine(mesh, gars.instantiate("krum", 4, 1), 4, quarantine_threshold=0.5)
    with pytest.raises(UserException):  # decay out of bounds
        RobustEngine(mesh, gars.instantiate("krum", 4, 1), 4, reputation_decay=1.5)
    # bucketing's tolerance is the inner rule's
    assert gars.instantiate("bucketing", 8, 1, ["s:2", "inner:krum"]).nan_row_tolerant
    assert not gars.instantiate("bucketing", 8, 1, ["s:2", "inner:average"]).nan_row_tolerant


def test_quarantined_worker_really_excluded():
    """With average-nan and worker 3 quarantined, the step EXACTLY equals
    SGD on the mean of workers 0-2's gradients — the masked row is gone,
    and it is the RIGHT row."""
    import jax
    import numpy as np
    import optax

    from aggregathor_tpu import gars, models
    from aggregathor_tpu.parallel.engine import RobustEngine
    from aggregathor_tpu.parallel.mesh import make_mesh

    n, lr = 4, 0.1
    ex = models.instantiate("mnist", ["batch-size:8"])
    params0 = ex.init(jax.random.PRNGKey(0))
    # host copy: build_step donates the state, deleting the device params
    params0 = jax.tree_util.tree_map(np.asarray, params0)
    batch = next(ex.make_train_iterator(n, seed=5))

    eng = RobustEngine(
        make_mesh(nb_workers=4), gars.instantiate("average-nan", n, 1), n,
        reputation_decay=0.9, quarantine_threshold=0.5,
    )
    tx = optax.sgd(lr)
    state = eng.init_state(params0, tx)
    state = eng.put_state(
        state.replace(reputation=np.asarray([1.0, 1.0, 1.0, 0.1], np.float32))
    )
    state, _ = eng.build_step(ex.loss, tx)(state, eng.shard_batch(batch))
    got = jax.device_get(state.params)

    # oracle: mean gradient of workers 0-2 only, one SGD step
    grads = [
        jax.grad(ex.loss)(params0, jax.tree_util.tree_map(lambda x: x[i], batch))
        for i in range(3)
    ]
    mean = jax.tree_util.tree_map(lambda *g: sum(np.asarray(x) for x in g) / 3.0, *grads)
    want = jax.tree_util.tree_map(lambda p, g: np.asarray(p) - lr * g, params0, mean)
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)


def test_reputation_sees_omniscient_forgeries():
    """Omniscient attacks forge rows in block space AFTER the worker-space
    reshard; the reputation signal measures the post-attack raw block, so an
    empire coalition's forged submissions (not their honest gradients) drive
    their reputation down."""
    import optax

    atk = attacks.instantiate("empire", 8, 2, ["epsilon:4.0"])
    exp = models.instantiate("mnist", ["batch-size:16"])
    engine = RobustEngine(
        make_mesh(nb_workers=4), gars.instantiate("median", 8, 2), 8,
        nb_real_byz=2, attack=atk, worker_metrics=True, reputation_decay=0.5,
    )
    tx = optax.sgd(0.05)
    state = engine.init_state(exp.init(jax.random.PRNGKey(0)), tx)
    step = engine.build_step(exp.loss, tx)
    it = exp.make_train_iterator(8, seed=0)
    for _ in range(6):
        state, metrics = step(state, engine.shard_batch(next(it)))
    rep = np.asarray(jax.device_get(metrics["worker_reputation"]))
    assert rep[:2].max() < 0.1, rep   # the forgers, as submitted
    assert rep[2:].min() > 0.9, rep


def test_quarantine_capped_at_declared_budget():
    """No matter how many reputations sit below threshold, at most f rows
    are masked per step (the rule's NaN budget) — krum stays finite even
    when 4 of 8 workers are below threshold, and nb_quarantined reports the
    CAPPED count."""
    import optax

    n, f = 8, 2
    ex = models.instantiate("mnist", ["batch-size:8"])
    eng = RobustEngine(
        make_mesh(nb_workers=4), gars.instantiate("krum", n, f), n,
        worker_metrics=True, reputation_decay=0.9, quarantine_threshold=0.5,
    )
    tx = optax.sgd(0.05)
    state = eng.init_state(ex.init(jax.random.PRNGKey(0)), tx)
    # 4 workers below threshold: an unbounded mask would leave krum with
    # only 4 finite rows < n-f-2+1 distances and NaN the aggregate
    state = eng.put_state(
        state.replace(reputation=np.asarray([0.1, 0.2, 0.3, 0.4, 1, 1, 1, 1], np.float32))
    )
    step = eng.build_step(ex.loss, tx)
    state, metrics = step(state, eng.shard_batch(next(ex.make_train_iterator(n, seed=1))))
    assert int(jax.device_get(metrics["nb_quarantined"])) == f
    assert np.isfinite(float(metrics["total_loss"]))
    assert np.all(np.isfinite(flat_params(state)))


def test_quarantine_requires_declared_byzantine():
    import pytest

    from aggregathor_tpu.utils import UserException

    with pytest.raises(UserException):  # f=0: the mask budget is empty
        RobustEngine(make_mesh(nb_workers=4), gars.instantiate("average-nan", 4, 0), 4,
                     reputation_decay=0.5, quarantine_threshold=0.5)


def test_leaf_granularity_average_matches_vector():
    """Averaging is layer-separable: granularity:leaf and :vector produce
    identical parameters (the per-leaf path is exercised end to end with no
    semantic change for a separable rule)."""
    import optax

    exp = models.instantiate("mnist", ["batch-size:16"])
    batchs = [next(exp.make_train_iterator(8, seed=2)) for _ in range(3)]
    outs = {}
    for gran in ("vector", "leaf"):
        eng = RobustEngine(make_mesh(nb_workers=4), gars.instantiate("average", 8, 0), 8,
                           granularity=gran)
        tx = optax.sgd(0.05)
        state = eng.init_state(exp.init(jax.random.PRNGKey(0)), tx)
        step = eng.build_step(exp.loss, tx)
        for b in batchs:
            state, _ = step(state, eng.shard_batch(b))
        outs[gran] = flat_params(state)
    np.testing.assert_allclose(outs["leaf"], outs["vector"], rtol=1e-5, atol=1e-6)


def test_leaf_granularity_krum_device_invariance_and_attack():
    """Per-leaf krum: device-count invariant (per-leaf all_gathers see the
    same rows on any layout) and converges under a signflip coalition; the
    suspicion metrics come back with the right shapes."""
    import optax

    atk = attacks.instantiate("signflip", 8, 2, ["scale:10.0"])
    outs = {}
    for nb_devices in (8, 1):
        exp = models.instantiate("mnist", ["batch-size:16"])
        eng = RobustEngine(make_mesh(nb_workers=nb_devices), gars.instantiate("krum", 8, 2), 8,
                           nb_real_byz=2, attack=atk, granularity="leaf", worker_metrics=True)
        tx = optax.sgd(0.05)
        state = eng.init_state(exp.init(jax.random.PRNGKey(42)), tx, seed=1)
        step = eng.build_step(exp.loss, tx)
        it = exp.make_train_iterator(8, seed=3)
        losses = []
        for _ in range(10):
            state, metrics = step(state, eng.shard_batch(next(it)))
            losses.append(float(metrics["total_loss"]))
        assert losses[-1] < losses[0]
        assert np.asarray(metrics["worker_sq_dist"]).shape == (8,)
        assert np.asarray(metrics["worker_participation"]).shape == (8,)
        outs[nb_devices] = flat_params(state)
    np.testing.assert_allclose(outs[8], outs[1], rtol=1e-5, atol=1e-6)


def test_leaf_granularity_quarantine():
    """Quarantine composes with per-leaf selection: the deviation-100
    attacker quarantines and training stays finite."""
    import optax

    exp = models.instantiate("mnist", ["batch-size:16"])
    eng = RobustEngine(
        make_mesh(nb_workers=4), gars.instantiate("krum", 8, 2), 8,
        nb_real_byz=2, attack=attacks.instantiate("gaussian", 8, 2, ["deviation:100"]),
        granularity="leaf", worker_metrics=True,
        reputation_decay=0.5, quarantine_threshold=0.4,
    )
    tx = optax.sgd(0.05)
    state = eng.init_state(exp.init(jax.random.PRNGKey(0)), tx)
    step = eng.build_step(exp.loss, tx)
    it = exp.make_train_iterator(8, seed=0)
    for _ in range(6):
        state, metrics = step(state, eng.shard_batch(next(it)))
    rep = np.asarray(jax.device_get(metrics["worker_reputation"]))
    assert rep[:2].max() < 0.1 and rep[2:].min() > 0.9, rep
    assert int(jax.device_get(metrics["nb_quarantined"])) == 2
    assert np.all(np.isfinite(flat_params(state)))


def oracle_leaf(rule, rows, f):
    """(aggregate (d_leaf,), participation (n,)) of ``rule`` on one leaf's (n,
    d_leaf) rows, by the numpy oracle: each worker's averaging weight, for
    Bulyan the mean over its rounds (``GAR.worker_participation``)."""
    from aggregathor_tpu.gars import oracle

    n = rows.shape[0]
    if rule == "krum":
        rounds = [np.argsort(oracle.krum_scores(rows, f), kind="stable")[:n - f - 2]]
    else:
        rounds = oracle.bulyan_rounds(rows, f)
    weights = np.zeros((len(rounds), n))
    for weight, workers in zip(weights, rounds):
        weight[workers] = 1.0 / len(workers)
    return getattr(oracle, rule)(rows, f), weights.mean(axis=0)


@pytest.mark.parametrize("rule,f", [("krum", 2), ("bulyan", 1)])
def test_leaf_granularity_is_the_rule_leaf_by_leaf(rule, f):
    """The definition of granularity:leaf: one step of the per-leaf path (the
    bucketed, vmapped program a TPU runs) is the rule applied to each leaf's
    (n, d_leaf) rows alone — here by the numpy oracle, on gradients taken with
    ``jax.grad`` outside the engine.  Plain SGD, so the update is the
    aggregate; one worker's batch carries a scale of its loss (its images'
    would leave the bounded bias gradients where they were) that makes the
    rule leave it out of every leaf, and any leaf whose selection differed
    would move that leaf's aggregate and the participation."""
    import optax

    n, outlier, rate = 8, 5, 0.5
    exp = models.instantiate("mnist", ["batch-size:16"])
    batch = dict(next(exp.make_train_iterator(n, seed=4)), scale=np.ones((n, 1), np.float32))
    batch["scale"][outlier] = 40.0

    def loss(params, batch):
        return exp.loss(params, batch) * batch["scale"][0]

    params = exp.init(jax.random.PRNGKey(11))
    grads = jax.vmap(jax.grad(loss), in_axes=(None, 0))(params, batch)

    engine = RobustEngine(make_mesh(nb_workers=4), gars.instantiate(rule, n, f), n,
                          granularity="leaf", worker_metrics=True)
    tx = optax.sgd(rate)
    state = engine.init_state(jax.tree.map(np.asarray, params), tx)
    state, metrics = engine.build_step(loss, tx)(state, engine.shard_batch(batch))

    participations = []
    for before, after, leaf in zip(*map(jax.tree.leaves, (params, state.params, grads))):
        rows = np.asarray(leaf, np.float64).reshape(n, -1)
        aggregate, participation = oracle_leaf(rule, rows, f)
        assert participation[outlier] == 0.0
        participations.append(participation)
        np.testing.assert_allclose(
            (np.asarray(before) - np.asarray(after)).ravel() / rate, aggregate,
            rtol=1e-4, atol=1e-6 * np.abs(rows).max())
    # the leaves do not all keep the same workers: a whole-vector selection would not pass
    assert len({tuple(p > 0) for p in participations}) > 1
    np.testing.assert_allclose(np.asarray(metrics["worker_participation"]),
                               np.mean(participations, axis=0), rtol=1e-5, atol=1e-7)


def test_sampled_multi_step_trains_and_is_mesh_invariant():
    """The device-resident sampled trainer (build_sampled_multi_step) draws
    fresh in-graph batches: loss decreases, the draw stream is a function of
    (rng, step, global worker) only — so 8-device and 1-device meshes
    produce identical parameters — and re-running with the same seed is
    bit-reproducible.

    The CONVERGENCE bar is capability-gated (the tests/test_cli.py triage
    pattern): some jaxlib builds miss the loss-decrease bar on this trainer
    (known-environmental since the seed) — on those, every backend-
    independent property (finiteness, fresh draws, mesh invariance,
    reproducibility) is still asserted FIRST and the test then reports a
    triaged SKIP for the bar instead of a red."""
    import optax

    converges = True
    results = []
    for nb_devices in (8, 1):
        exp = models.instantiate("mnist", ["batch-size:16"])
        gar = gars.instantiate("krum", 8, 1)
        tx = build_optimizer("sgd", build_schedule("fixed", ["initial-rate:0.05"]))
        engine = RobustEngine(make_mesh(nb_workers=nb_devices), gar, nb_workers=8)
        multi = engine.build_sampled_multi_step(exp.loss, tx, repeat_steps=12, batch_size=16)
        data = engine.replicate({
            "image": exp.dataset.x_train, "label": exp.dataset.y_train,
        })
        state = engine.init_state(exp.init(jax.random.PRNGKey(42)), tx, seed=1)
        state, metrics = multi(state, data)
        losses = np.asarray(jax.device_get(metrics["total_loss"]))
        assert losses.shape == (12,)
        assert np.all(np.isfinite(losses))
        converges = converges and bool(losses[-1] < losses[0])
        # fresh draws each step: a same-batch scan would still vary through
        # the params, but per-step losses must not be an exact repeat chain
        assert len({round(float(x), 6) for x in losses}) > 1
        results.append(flat_params(state))
    np.testing.assert_allclose(results[0], results[1], rtol=1e-5, atol=1e-6)

    # reproducibility: identical seed, identical final parameters
    exp = models.instantiate("mnist", ["batch-size:16"])
    gar = gars.instantiate("krum", 8, 1)
    tx = build_optimizer("sgd", build_schedule("fixed", ["initial-rate:0.05"]))
    engine = RobustEngine(make_mesh(nb_workers=8), gar, nb_workers=8)
    multi = engine.build_sampled_multi_step(exp.loss, tx, repeat_steps=12, batch_size=16)
    data = engine.replicate({"image": exp.dataset.x_train, "label": exp.dataset.y_train})
    state = engine.init_state(exp.init(jax.random.PRNGKey(42)), tx, seed=1)
    state, _ = multi(state, data)
    np.testing.assert_array_equal(results[0], flat_params(state))

    if not converges:
        pytest.skip(
            "sampled-trainer loss-decrease bar unmet on this backend/jaxlib "
            "build (known-environmental); finiteness, fresh draws, mesh "
            "invariance and bit-reproducibility above all PASSED"
        )


def test_sampled_multi_step_differs_from_repeat_batch():
    """Sampling must actually change the data each step: the sampled trainer
    and the one-resident-batch repeat trainer diverge after a few steps."""
    exp = models.instantiate("mnist", ["batch-size:16"])
    gar = gars.instantiate("average", 4, 0)
    tx = build_optimizer("sgd", build_schedule("fixed", ["initial-rate:0.05"]))
    engine = RobustEngine(make_mesh(nb_workers=4), gar, nb_workers=4)
    data = engine.replicate({"image": exp.dataset.x_train, "label": exp.dataset.y_train})

    sampled = engine.build_sampled_multi_step(exp.loss, tx, repeat_steps=5, batch_size=16)
    s1 = engine.init_state(exp.init(jax.random.PRNGKey(0)), tx, seed=2)
    s1, _ = sampled(s1, data)

    repeat = engine.build_multi_step(exp.loss, tx, repeat_steps=5)
    it = exp.make_train_iterator(4, seed=2)
    s2 = engine.init_state(exp.init(jax.random.PRNGKey(0)), tx, seed=2)
    s2, _ = repeat(s2, engine.shard_batch(next(it)))

    assert not np.allclose(flat_params(s1), flat_params(s2), rtol=1e-4)


@pytest.mark.slow
def test_sampled_multi_step_composes_with_momentum_and_clever():
    """The sampled trainer threads the worker-sharded side buffers exactly
    like the streamed scan: momentum + CLEVER lossy carry + attack compose
    under in-graph batch draws, and the run stays finite and mesh-invariant."""
    import optax

    results = []
    for nb_devices in (4, 1):
        exp = models.instantiate("mnist", ["batch-size:8"])
        gar = gars.instantiate("krum", 8, 2)
        atk = attacks.instantiate("signflip", 8, 2)
        ll = lossy.LossyLink(1, ["drop-rate:0.2", "packet-coords:16",
                                 "min-coords:0", "clever:true"])
        engine = RobustEngine(make_mesh(nb_workers=nb_devices), gar, 8,
                              nb_real_byz=2, attack=atk, lossy_link=ll,
                              worker_momentum=0.9)
        tx = optax.sgd(0.05)
        multi = engine.build_sampled_multi_step(exp.loss, tx, repeat_steps=6, batch_size=8)
        data = engine.replicate({"image": exp.dataset.x_train,
                                 "label": exp.dataset.y_train})
        state = engine.init_state(exp.init(jax.random.PRNGKey(3)), tx, seed=4)
        state, metrics = multi(state, data)
        losses = np.asarray(jax.device_get(metrics["total_loss"]))
        assert losses.shape == (6,) and np.all(np.isfinite(losses))
        assert int(jax.device_get(state.momentum_steps)) == 6
        results.append(flat_params(state))
    np.testing.assert_allclose(results[0], results[1], rtol=1e-5, atol=1e-6)


# --------------------------------------------------------------------- #
# the ONE engine-fixture sweep (ISSUE 10 satellite): the same feature
# assertions against BOTH dataflows of the unified engine, through the
# shared cached factory — sharded-mode feature parity without a
# transformer compile


@pytest.mark.parametrize("mode", ["flat", "sharded"])
def test_engine_mode_sweep_trains_and_probes(mode):
    from conftest import assert_zero_recompiles, build_engine_stack

    exp, engine, tx, step, make_state = build_engine_stack(
        mode=mode, experiment="digits", experiment_args=("batch-size:8",),
        gar="median", n=4, f=1, nb_devices=(1 if mode == "flat" else 2))
    assert engine.sharded == (mode == "sharded")
    state = make_state()
    it = exp.make_train_iterator(4, seed=3)
    losses = []
    for _ in range(6):
        state, m = step(state, engine.shard_batch(next(it)))
        assert "probe" in m  # the shared epilogue rides both dataflows
        losses.append(float(jax.device_get(m["total_loss"])))
    assert losses[-1] < losses[0], losses
    assert_zero_recompiles(step)


# --------------------------------------------------------------------- #
# fixed-seed regression pins, captured on the installed JAX
# (scripts/capture_engine_goldens.py stamps jax_version into the file)


def _golden_module():
    import importlib.util

    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "scripts", "capture_engine_goldens.py")
    spec = importlib.util.spec_from_file_location("capture_engine_goldens", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _goldens():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "data", "golden_engine.json")
    with open(path) as fd:
        doc = json.load(fd)
    if doc["jax_version"] != jax.__version__:
        pytest.skip("goldens were captured on JAX %s, this is %s: re-run "
                    "scripts/capture_engine_goldens.py"
                    % (doc["jax_version"], jax.__version__))
    return doc


@pytest.mark.parametrize("name", [
    "flat_vector_rich",
    # the leaf-path golden costs a second full stack; tier-1 keeps the
    # feature-dense vector config, the leaf path rides the full suite
    pytest.param("flat_leaf", marks=pytest.mark.slow),
])
def test_unified_engine_bit_identical_to_flat_predecessor(name):
    """A regression pin on this installation: the flat dataflow reproduces
    its captured fixed-seed run bit-exactly — losses as float hex, final
    params by SHA-256 over the raw bytes (re-captured at PR 21 on the
    installed JAX; the PR-10 goldens came from another one)."""
    mod = _golden_module()
    if name == "flat_vector_rich":
        doc = mod.run_flat("vector", secure=True, momentum=0.9,
                           attack_name="signflip", worker_metrics=True,
                           reputation_decay=0.9)
    else:
        doc = mod.run_flat("leaf")
    assert doc == _goldens()[name]


@pytest.mark.slow  # transformer compiles dominate; the flat configs above
@pytest.mark.parametrize("name", ["sharded_layer", "sharded_global"])
def test_unified_engine_bit_identical_to_sharded_predecessor(name):
    """Sharded twin of the regression pin: layer granularity with l1/l2 +
    momentum, and global granularity."""
    mod = _golden_module()
    if name == "sharded_layer":
        doc = mod.run_sharded("layer", l1=1e-4, l2=1e-4, momentum=0.9)
    else:
        doc = mod.run_sharded("global")
    assert doc == _goldens()[name]


def test_mesh_axes_are_auto():
    """parallel/mesh: the ONE mesh constructor states Auto axes.  The engine
    hand-places every collective under shard_map(check_vma=False);
    jax.make_mesh's default (Explicit) puts sharding in the avals, which
    costs the bounded-wait aggregate a second steady-state compile and
    breaks jnp.nanmedian on a worker-sharded block."""
    for kw in ({"nb_workers": 8}, {"nb_workers": 2, "model_parallelism": 2,
                                    "pipeline_parallelism": 2}):
        mesh = make_mesh(**kw)
        assert mesh.axis_names == ("worker", "pipe", "model")
        assert all(t == jax.sharding.AxisType.Auto for t in mesh.axis_types), mesh.axis_types

