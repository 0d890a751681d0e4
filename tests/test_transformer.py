"""Transformer family + sharded engine tests.

Strategy (SURVEY.md §4): redundant implementations as cross-checks — the
collective-free dense path is the oracle for the pipelined/ring/TP path, and
a manual numpy SGD step is the oracle for the sharded engine.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from aggregathor_tpu import config, gars
from aggregathor_tpu.models import transformer as tfm
from aggregathor_tpu.parallel import RobustEngine
from aggregathor_tpu.parallel.mesh import factor_devices, make_mesh

CFG = tfm.TransformerConfig(vocab_size=17, d_model=16, n_heads=2, n_layers=4)


def _merge_stages(params):
    """(S, Lp, ...) stage-stacked leaves -> (1, S*Lp, ...) single-stage layout."""
    out = {}
    for k, v in params.items():
        if k in ("embed", "unembed", "final_norm"):
            out[k] = v
        else:
            out[k] = np.asarray(v).reshape((1, v.shape[0] * v.shape[1]) + v.shape[2:])
    return out


def _batch(rng, nb_workers, bsz=4, seq=16, vocab=17):
    return {
        "tokens": rng.integers(0, vocab, size=(nb_workers, bsz, seq)).astype(np.int32),
        "targets": rng.integers(0, vocab, size=(nb_workers, bsz, seq)).astype(np.int32),
    }


def test_factor_devices():
    assert factor_devices(8) == (2, 2, 2)
    assert factor_devices(4) == (2, 2, 1)
    assert factor_devices(2) == (2, 1, 1)
    assert factor_devices(1) == (1, 1, 1)
    w, p, m = factor_devices(12)
    assert w * p * m == 12


def test_ring_attention_matches_dense(rng):
    b, s, h, dh = 2, 32, 2, 8
    q, k, v = (jnp.asarray(rng.normal(size=(b, s, h, dh)), jnp.float32) for _ in range(3))
    dense = tfm.ring_attention(q, k, v, jnp.arange(s), axis=None)

    mesh = jax.make_mesh((4,), (config.model_axis,))

    def body(q, k, v):
        sb = q.shape[1]
        pos = jax.lax.axis_index(config.model_axis) * sb + jnp.arange(sb)
        return tfm.ring_attention(q, k, v, pos, axis=config.model_axis)

    spec = P(None, config.model_axis, None, None)
    ringed = jax.jit(
        jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False)
    )(q, k, v)
    np.testing.assert_allclose(np.asarray(ringed), np.asarray(dense), rtol=2e-5, atol=2e-5)


def test_pipeline_loss_matches_dense(rng):
    params = tfm.init_params(CFG, jax.random.PRNGKey(3), n_stages=2)
    batch = jax.tree.map(lambda x: jnp.asarray(x[0]), _batch(rng, 1))
    dense = tfm.loss_dense(_merge_stages(params), batch, CFG)

    mesh = make_mesh(nb_workers=2, model_parallelism=2, pipeline_parallelism=2)
    loss_fn = tfm.make_pipeline_loss(CFG, n_stages=2, microbatches=2)

    def body(p, b):  # local partials sum to the batch loss
        return jax.lax.psum(loss_fn(p, b), (config.pipe_axis, config.model_axis))

    sharded = jax.jit(
        jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(tfm.param_specs(CFG), P()),
            out_specs=P(),
            check_vma=False,
        )
    )
    piped = sharded(params, batch)
    np.testing.assert_allclose(float(piped), float(dense), rtol=1e-5)


def test_sharded_engine_average_matches_manual_sgd(rng):
    w, pp, tp = 2, 2, 2
    mesh = make_mesh(nb_workers=w, model_parallelism=tp, pipeline_parallelism=pp)
    gar = gars.instantiate("average", w, 0)
    eng = RobustEngine(mesh, gar, granularity="global", sharding="sharded")
    lr = 0.1
    tx = optax.sgd(lr)
    state = eng.init_state(lambda k: tfm.init_params(CFG, k, n_stages=pp), tfm.param_specs(CFG), tx)
    params0 = jax.device_get(state.params)
    batch = _batch(rng, w)
    loss_fn = tfm.make_pipeline_loss(CFG, n_stages=pp, microbatches=2)
    step = eng.build_step(loss_fn, tx, state)
    state, metrics = step(state, eng.shard_batch(batch))
    got = jax.device_get(state.params)

    # Oracle: dense per-worker grads, averaged, one SGD step
    dense0 = _merge_stages(params0)
    grads = [
        jax.grad(lambda p, b: tfm.loss_dense(p, b, CFG))(dense0, jax.tree.map(lambda x: jnp.asarray(x[i]), batch))
        for i in range(w)
    ]
    mean = jax.tree.map(lambda *g: sum(np.asarray(x) for x in g) / w, *grads)
    want = jax.tree.map(lambda p, g: np.asarray(p) - lr * g, dense0, mean)
    for k in ("wq", "w_down", "embed", "unembed", "final_norm"):
        np.testing.assert_allclose(
            np.asarray(_merge_stages(got)[k]), np.asarray(want[k]), rtol=5e-4, atol=1e-5, err_msg=k
        )


@pytest.mark.slow
def test_sharded_engine_l1_l2_regularization_exact(rng):
    """l1/l2 on the sharded engine is applied analytically to the completed
    gradients (no per-shard double counting): the result matches the dense
    oracle with the reg gradient added, and the reported loss carries the
    norm term exactly once per worker (VERDICT r3 next-step 6)."""
    w, pp, tp = 2, 2, 2
    l1, l2 = 1e-3, 1e-2
    mesh = make_mesh(nb_workers=w, model_parallelism=tp, pipeline_parallelism=pp)
    gar = gars.instantiate("average", w, 0)
    lr = 0.1
    tx = optax.sgd(lr)
    loss_fn = tfm.make_pipeline_loss(CFG, n_stages=pp, microbatches=2)
    batch = _batch(rng, w)

    def run_engine(**reg):
        eng = RobustEngine(mesh, gar, granularity="global", sharding="sharded", **reg)
        state = eng.init_state(
            lambda k: tfm.init_params(CFG, k, n_stages=pp), tfm.param_specs(CFG), tx
        )
        params0 = jax.device_get(state.params)
        step = eng.build_step(loss_fn, tx, state)
        state, metrics = step(state, eng.shard_batch(batch))
        return params0, jax.device_get(state.params), jax.device_get(metrics)

    params0, got, metrics = run_engine(l1_regularize=l1, l2_regularize=l2)
    _, _, metrics_plain = run_engine()

    # The loss metric includes the norm term once per worker: the reg'd and
    # plain runs share params/batch at step one, so the difference is exactly
    # w * (l1*sum|p| + l2*sum p^2).  Replication double counting would
    # inflate it by the pp*tp in-group factor.
    leaves = jax.tree_util.tree_leaves(params0)
    norm1 = sum(float(np.sum(np.abs(p))) for p in leaves)
    norm2 = sum(float(np.sum(np.asarray(p, np.float64) ** 2)) for p in leaves)
    want_reg = w * (l1 * norm1 + l2 * norm2)
    got_reg = float(metrics["total_loss"]) - float(metrics_plain["total_loss"])
    np.testing.assert_allclose(got_reg, want_reg, rtol=1e-3)

    # Oracle update: dense per-worker grads + analytic reg gradient
    dense0 = _merge_stages(params0)
    grads = [
        jax.grad(lambda p, b: tfm.loss_dense(p, b, CFG))(
            dense0, jax.tree.map(lambda x: jnp.asarray(x[i]), batch)
        )
        for i in range(w)
    ]
    mean = jax.tree.map(lambda *g: sum(np.asarray(x) for x in g) / w, *grads)
    want = jax.tree.map(
        lambda p, g: np.asarray(p) - lr * (g + l1 * np.sign(p) + 2.0 * l2 * np.asarray(p)),
        dense0, mean,
    )
    merged = _merge_stages(got)
    for k in ("wq", "w_down", "embed", "unembed", "final_norm"):
        np.testing.assert_allclose(
            np.asarray(merged[k]), np.asarray(want[k]), rtol=5e-4, atol=1e-5, err_msg=k
        )


@pytest.mark.slow
def test_sharded_engine_multi_step_matches_per_step(rng):
    """build_multi_step (K batches, one scanned dispatch) reproduces K
    sequential build_step calls and returns per-step metrics (leading K) —
    the flat engine's --unroll contract on the sharded engine."""
    w, pp, tp = 2, 2, 2
    mesh = make_mesh(nb_workers=w, model_parallelism=tp, pipeline_parallelism=pp)
    gar = gars.instantiate("median", w, 0)
    tx = optax.sgd(0.05)
    loss_fn = tfm.make_pipeline_loss(CFG, n_stages=pp, microbatches=2)
    batches = [_batch(rng, w) for _ in range(2)]

    def fresh_state(eng):
        return eng.init_state(
            lambda k: tfm.init_params(CFG, k, n_stages=pp), tfm.param_specs(CFG), tx
        )

    eng = RobustEngine(mesh, gar, granularity="layer", sharding="sharded")
    state = fresh_state(eng)
    step = eng.build_step(loss_fn, tx, state)
    losses = []
    for b in batches:
        state, metrics = step(state, eng.shard_batch(b))
        losses.append(float(metrics["total_loss"]))
    want = jax.device_get(state.params)

    state2 = fresh_state(eng)
    multi = eng.build_multi_step(loss_fn, tx, state2)
    chunk = jax.tree_util.tree_map(lambda *xs: np.stack(xs), *batches)
    state2, many = multi(state2, eng.shard_batches(chunk))
    got = jax.device_get(state2.params)

    assert np.asarray(many["total_loss"]).shape == (2,)
    np.testing.assert_allclose(np.asarray(many["total_loss"]), losses, rtol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7), want, got
    )

    # repeat_steps form: one resident batch scanned K times, loss evolves
    state3 = fresh_state(eng)
    multi_rep = eng.build_multi_step(loss_fn, tx, state3, repeat_steps=3)
    state3, many_rep = multi_rep(state3, eng.shard_batch(batches[0]))
    assert np.asarray(many_rep["total_loss"]).shape == (3,)
    assert int(jax.device_get(state3.step)) == 3


@pytest.mark.parametrize("granularity", ["layer", "global"])
def test_per_layer_krum_under_attack_converges(rng, granularity):
    from aggregathor_tpu.parallel.attacks import instantiate as make_attack

    w, pp, tp = 4, 2, 1
    mesh = make_mesh(nb_workers=w, model_parallelism=tp, pipeline_parallelism=pp)
    gar = gars.instantiate("krum", w, 1)
    eng = RobustEngine(
        mesh, gar, nb_real_byz=1, attack=make_attack("signflip", w, 1), granularity=granularity,
        sharding="sharded",
    )
    tx = optax.sgd(0.05)
    state = eng.init_state(lambda k: tfm.init_params(CFG, k, n_stages=pp), tfm.param_specs(CFG), tx)
    loss_fn = tfm.make_pipeline_loss(CFG, n_stages=pp, microbatches=2)
    step = eng.build_step(loss_fn, tx, state)
    losses = []
    for _ in range(8):
        state, metrics = step(state, eng.shard_batch(_batch(rng, w)))
        losses.append(float(metrics["total_loss"]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], losses


def test_moe_dense_forward(rng):
    cfg = tfm.TransformerConfig(vocab_size=17, d_model=16, n_heads=2, n_layers=2, n_experts=4)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0), n_stages=1)
    tokens = jnp.asarray(rng.integers(0, 17, size=(2, 16)), jnp.int32)
    logits, aux = tfm.forward_dense(params, tokens, cfg)
    assert logits.shape == (2, 16, 17)
    assert bool(jnp.all(jnp.isfinite(logits)))
    assert float(aux) > 0.0


def test_transformer_experiment_registered():
    from aggregathor_tpu import models

    assert "transformer" in models.itemize()


def test_sharded_engine_bf16_exchange_converges():
    """bfloat16 per-bucket gathers on the sharded dataflow: per-layer median
    still trains (GAR math stays f32 on the upcast rows).  Runs on the cheap
    sharded-mode stack (conftest factory, ISSUE 10 satellite dedup) — the
    wire-precision path is dataflow plumbing, not transformer-specific; the
    pipeline/tensor-parallel collectives keep their own tests below."""
    from conftest import build_engine_stack

    exp, eng, tx, step, make_state = build_engine_stack(
        mode="sharded", experiment="digits", experiment_args=("batch-size:8",),
        gar="median", n=4, f=1, nb_devices=2, exchange="bf16")
    state = make_state()
    it = exp.make_train_iterator(4, seed=5)
    losses = []
    for _ in range(25):
        state, metrics = step(state, eng.shard_batch(next(it)))
        losses.append(float(metrics["total_loss"]))
    assert np.isfinite(losses).all()
    # windowed comparison: single digits steps are noisy at this batch size
    assert np.mean(losses[-5:]) < np.mean(losses[:5]), losses


def test_sharded_engine_momentum_first_step_matches_plain():
    """Bias correction makes the first momentum step identical to the plain
    step on the same batch (flat-engine parity of the policy) — on the cheap
    sharded-mode stack (conftest factory, ISSUE 10 satellite dedup)."""
    from conftest import build_engine_stack

    results = {}
    for momentum in (0.9, None):
        kw = {} if momentum is None else {"worker_momentum": momentum}
        exp, eng, tx, step, make_state = build_engine_stack(
            mode="sharded", experiment="digits",
            experiment_args=("batch-size:8",), gar="average", n=4, f=0,
            nb_devices=2, **kw)
        state = make_state()
        it = exp.make_train_iterator(4, seed=5)
        state, _ = step(state, eng.shard_batch(next(it)))
        results[momentum] = jax.device_get(state.params)
    for a, b in zip(jax.tree_util.tree_leaves(results[0.9]),
                    jax.tree_util.tree_leaves(results[None])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6)


def test_sharded_engine_momentum_under_attack_converges():
    """History-aware robustness on the sharded dataflow (cheap sharded-mode
    stack; ISSUE 10 satellite dedup): per-worker momentum buffers carried
    worker-sharded, krum resists a sign-flipping coalition."""
    from conftest import build_engine_stack

    exp, eng, tx, step, make_state = build_engine_stack(
        mode="sharded", experiment="digits", experiment_args=("batch-size:8",),
        gar="krum", n=4, f=1, nb_devices=2, attack="signflip",
        nb_real_byz=1, worker_momentum=0.8)
    state = make_state()
    assert state.momentum is not None
    it = exp.make_train_iterator(4, seed=5)
    losses = []
    for _ in range(25):
        state, metrics = step(state, eng.shard_batch(next(it)))
        losses.append(float(metrics["total_loss"]))
    assert np.isfinite(losses).all()
    assert np.mean(losses[-5:]) < np.mean(losses[:5]), losses


def test_sharded_engine_clever_lossy():
    """CLEVER stale infill on the sharded dataflow (cheap sharded-mode
    stack; ISSUE 10 satellite dedup): plain average stays finite and trains
    under a lossy worker, where NaN infill would poison params."""
    from conftest import build_engine_stack

    exp, eng, tx, step, make_state = build_engine_stack(
        mode="sharded", experiment="digits", experiment_args=("batch-size:8",),
        gar="average", n=2, f=0, nb_devices=2,
        lossy=(1, "drop-rate:0.3", "packet-coords:64", "min-coords:0",
               "clever:true"))
    state = make_state()
    assert state.carry is not None
    it = exp.make_train_iterator(2, seed=5)
    losses = []
    for _ in range(25):
        state, metrics = step(state, eng.shard_batch(next(it)))
        losses.append(float(metrics["total_loss"]))
    assert np.isfinite(losses).all(), losses
    assert np.mean(losses[-5:]) < np.mean(losses[:5]), losses
    finite = [bool(np.isfinite(np.asarray(l)).all())
              for l in jax.tree_util.tree_leaves(state.params)]
    assert all(finite)


@pytest.mark.slow
def test_sharded_engine_uses_axis_rules_exact_across_tp(rng):
    """uses_axis rules (geometric-median, centered-clip) psum their row norms
    over the model axis: a tp=2 run must produce the tp=1 params (no
    shard-local-norm approximation)."""
    batch = _batch(rng, 2)
    loss1 = tfm.make_pipeline_loss(CFG, n_stages=1, microbatches=2)
    for rule in ("geometric-median", "centered-clip"):
        outs = {}
        for tp in (1, 2):
            mesh = make_mesh(nb_workers=2, model_parallelism=tp, pipeline_parallelism=1)
            gar = gars.instantiate(rule, 2, 0)
            eng = RobustEngine(mesh, gar, granularity="layer", sharding="sharded")
            tx = optax.sgd(0.05)
            state = eng.init_state(
                lambda k: tfm.init_params(CFG, k, n_stages=1), tfm.param_specs(CFG), tx
            )
            step = eng.build_step(loss1, tx, state)
            state, _ = step(state, eng.shard_batch(batch))
            outs[tp] = jax.device_get(state.params)
        for a, b in zip(
            jax.tree_util.tree_leaves(outs[1]), jax.tree_util.tree_leaves(outs[2])
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=5e-4, atol=1e-5, err_msg=rule
            )


@pytest.mark.slow
def test_sharded_engine_worker_metrics(rng):
    """Suspicion diagnostics on the sharded engine: under a deviation-100
    Gaussian attack with per-layer Krum, the attacker's mean participation is
    exactly 0, participation sums to 1, and its whole-model distance to the
    aggregate dominates — across both tp=1 and tp=2 meshes."""
    from aggregathor_tpu.parallel.attacks import instantiate as make_attack

    for pp, tp in ((2, 1), (1, 2)):
        w = 4
        mesh = make_mesh(nb_workers=w, model_parallelism=tp, pipeline_parallelism=pp)
        gar = gars.instantiate("krum", w, 1)
        eng = RobustEngine(
            mesh, gar, nb_real_byz=1,
            attack=make_attack("gaussian", w, 1, ["deviation:100"]),
            granularity="layer", worker_metrics=True,
            sharding="sharded",
        )
        tx = optax.sgd(0.05)
        state = eng.init_state(lambda k: tfm.init_params(CFG, k, n_stages=pp), tfm.param_specs(CFG), tx)
        step = eng.build_step(tfm.make_pipeline_loss(CFG, n_stages=pp, microbatches=2), tx, state)
        state, metrics = step(state, eng.shard_batch(_batch(rng, w)))
        wdist = np.asarray(jax.device_get(metrics["worker_sq_dist"]))
        part = np.asarray(jax.device_get(metrics["worker_participation"]))
        assert wdist.shape == part.shape == (w,)
        np.testing.assert_allclose(part.sum(), 1.0, rtol=1e-4)
        np.testing.assert_allclose(part[0], 0.0, atol=1e-7)  # the attacker
        assert wdist[0] > wdist[1:].max()


@pytest.mark.slow
def test_sharded_engine_reputation_quarantine(rng):
    """Reputation + quarantine on the sharded engine: a deviation-100
    Gaussian attacker's reputation decays to ~0 and it quarantines, honest
    workers stay trusted, and training stays finite — on a dp×pp mesh with
    per-layer krum."""
    from aggregathor_tpu.parallel.attacks import instantiate as make_attack

    w, pp, tp = 4, 2, 1
    mesh = make_mesh(nb_workers=w, model_parallelism=tp, pipeline_parallelism=pp)
    eng = RobustEngine(
        mesh, gars.instantiate("krum", w, 1), nb_real_byz=1,
        attack=make_attack("gaussian", w, 1, ["deviation:100"]),
        granularity="layer", worker_metrics=True,
        reputation_decay=0.5, quarantine_threshold=0.4,
        sharding="sharded",
    )
    tx = optax.sgd(0.05)
    state = eng.init_state(lambda k: tfm.init_params(CFG, k, n_stages=pp), tfm.param_specs(CFG), tx)
    step = eng.build_step(tfm.make_pipeline_loss(CFG, n_stages=pp, microbatches=2), tx, state)
    for _ in range(6):
        state, metrics = step(state, eng.shard_batch(_batch(rng, w)))
        assert np.isfinite(float(metrics["total_loss"]))
    rep = np.asarray(jax.device_get(metrics["worker_reputation"]))
    assert rep[0] < 0.1, rep
    assert rep[1:].min() > 0.9, rep
    assert int(jax.device_get(metrics["nb_quarantined"])) == 1


@pytest.mark.slow
def test_code_corpus_real_text_lm():
    """REAL-text LM anchor (the transformer-family analogue of the real
    digits accuracy test): corpus-source:code trains on the Python stdlib's
    own bytes with a held-out final-10% split, and 150 robust steps push
    held-out nll decisively below the corpus's unigram entropy — context is
    being used, which no uniform/Markov synthetic stream can demonstrate."""
    from aggregathor_tpu import models
    from aggregathor_tpu.parallel.engine import RobustEngine

    exp = models.instantiate(
        "transformer",
        ["corpus-source:code", "corpus:500000", "d-model:32", "layers:1",
         "seq:64", "batch-size:8", "heads:2"])
    assert not exp.synthetic
    assert exp.cfg.vocab_size == 256
    assert len(exp.corpus) == 450000 and len(exp.eval_corpus) == 50000
    # Deterministic assembly: a second instantiation sees identical bytes.
    again = models.instantiate("transformer", ["corpus-source:code", "corpus:500000"])
    np.testing.assert_array_equal(
        np.concatenate([again.corpus, again.eval_corpus])[:450000], exp.corpus)

    counts = np.bincount(exp.corpus, minlength=256).astype(np.float64)
    p = counts / counts.sum()
    p = p[p > 0]
    unigram_nats = float(-(p * np.log(p)).sum())
    assert unigram_nats > 2.5, "stdlib bytes should be far from uniform"

    eng = RobustEngine(make_mesh(nb_workers=4), gars.instantiate("krum", 4, 1), 4)
    tx = optax.adam(3e-3)
    step = eng.build_step(exp.loss, tx)
    state = eng.init_state(exp.init(jax.random.PRNGKey(0)), tx, seed=1)
    it = exp.make_train_iterator(4, seed=2)
    for i in range(150):
        state, m = step(state, eng.shard_batch(next(it)))
        if i % 25 == 24:
            jax.device_get(m["total_loss"])  # bound the async dispatch queue
    ev = eng.build_eval_sums(exp.metrics)
    sums = None
    for b in exp.make_eval_iterator(4):
        f = jax.device_get(ev(state, eng.shard_batch(b)))
        sums = f if sums is None else jax.tree_util.tree_map(lambda a, b: a + b, sums, f)
    nll = float(sums["nll"][0]) / float(sums["nll"][1])
    # Calibrated: ~2.24 nats at these settings vs ~3.14 unigram; 0.95x the
    # unigram bar leaves slack for backend jitter while still requiring
    # genuinely sub-unigram (context-using) prediction.
    assert nll < 0.95 * unigram_nats, (
        "held-out nll %.3f not below unigram %.3f" % (nll, unigram_nats))
