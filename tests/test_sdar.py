"""models/sdar.py against its plain reference (grid/references/sdar_moe.py), at
a tiny size on the CPU: hidden 64, 2 layers, 16 experts of which 4 are held,
L = 32, block 4.  Products run at ``highest`` precision, so what separates the
two is the order of float32 sums (chunked softmax, tiled expert sums)."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from aggregathor_tpu import gars, models
from aggregathor_tpu.models import sdar
from aggregathor_tpu.parallel import RobustEngine, make_mesh
from aggregathor_tpu.parallel.engine import PHASES

GRID = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "grid")


def grid_module(kind, name):
    spec = importlib.util.spec_from_file_location(
        "sdar_test_%s_%s" % (kind, name), os.path.join(GRID, kind, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


reference = grid_module("references", "sdar_moe")
feed = grid_module("references", "feed_device_tokens")

HELD = (1, 4, 7, 12)
VOCAB, LENGTH, BLOCK = 50, 32, 4
ARGS = ["vocab:%d" % VOCAB, "hidden:64", "heads:8", "kv-heads:2", "head-dim:16", "layers:2",
        "experts:16", "experts-per-token:4", "expert-width:24",
        "experts-held:" + ",".join(map(str, HELD)), "seq:%d" % LENGTH, "block:%d" % BLOCK,
        "attn-chunk:16", "batch-size:2", "corpus:16"]
SHAPE = {"sequence_length": LENGTH, "block_length": BLOCK, "mask_token_id": VOCAB - 1,
         "num_hidden_layers": 2, "experts_held": list(HELD), "hidden_size": 64,
         "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16, "num_experts": 16,
         "num_experts_per_tok": 4, "moe_intermediate_size": 24, "rope_theta": 1e6,
         "rms_norm_eps": 1e-6}
AUGMENT = "mask_token_id=%d" % (VOCAB - 1)


@pytest.fixture(autouse=True)
def highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture
def experiment():
    return models.instantiate("sdar", ARGS)


def seeded_params(seed=3, gain=10.0):
    """The reference's own weights, the matrices of the layers scaled up so
    that routing and attention are far from uniform."""
    params = reference.init(jax.random.PRNGKey(seed), SHAPE, VOCAB)
    return {name: leaf * gain if name in sdar.STACKED and not name.endswith("norm") else leaf
            for name, leaf in params.items()}


def noised(experiment, key=5):
    batch = {"tokens": jnp.asarray(experiment.corpus[:2])}
    return experiment.device_transform()(batch, jax.random.PRNGKey(key))


def test_experiment_and_reference_build_the_same_tree(experiment):
    ours = experiment.init(jax.random.PRNGKey(3))
    theirs = reference.init(jax.random.PRNGKey(3), SHAPE, VOCAB)
    assert jax.tree.map(lambda a: a.shape, ours) == jax.tree.map(lambda a: a.shape, theirs)
    assert all(bool(jnp.all(ours[name] == theirs[name])) for name in ours)
    full = sdar.SdarConfig()
    assert sum(int(np.prod(shape)) for shape in sdar.leaf_shapes(full).values()) == 305351680


def test_loss_and_gradients_match_the_reference(experiment):
    """(a) Tolerance 2e-3 of each leaf's largest gradient entry: both sides are
    float32 at ``highest``, and differ by the order of their sums (the
    program's running softmax by chunk and per-tile expert sums against one
    softmax and one dense product); read 4e-4 at worst (the embedding)."""
    params, batch = seeded_params(), noised(experiment)
    (loss, counters), grads = jax.jit(jax.value_and_grad(experiment.loss, has_aux=True))(
        params, batch)
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(reference.loss))(
        params, {"noisy": batch["noisy"], "t": batch["t"]}, batch["tokens"])
    assert abs(float(loss) - float(ref_loss)) <= 1e-5 * abs(float(ref_loss))
    for name in grads:
        scale = float(jnp.max(jnp.abs(ref_grads[name])))
        assert scale > 0, name
        assert float(jnp.max(jnp.abs(grads[name] - ref_grads[name]))) <= 2e-3 * scale, name
    assert 0 < float(counters["masked_share"]) < 1
    assert float(counters["routed_positions"]) > 0


def test_the_shares_of_an_expert_layer_add_up_to_the_whole(experiment):
    """(b) 16 experts over 4 shares of 4: each share is told which experts it
    holds and routes over all 16; the four partial outputs add up to the uncut
    reference's layer.  There is no shared expert to count once."""
    cfg = experiment.cfg
    key = jax.random.PRNGKey(11)
    tokens = jax.random.normal(jax.random.fold_in(key, 0), (1, 2 * LENGTH, cfg.hidden))
    whole = {"router": jax.random.normal(jax.random.fold_in(key, 1), (cfg.hidden, 16))}
    for place, (name, shape) in enumerate((("we_gate", (16, cfg.hidden, 24)),
                                           ("we_up", (16, cfg.hidden, 24)),
                                           ("we_down", (16, 24, cfg.hidden)))):
        whole[name] = 0.2 * jax.random.normal(jax.random.fold_in(key, 2 + place), shape)
    uncut = reference._experts(tokens, whole, dict(SHAPE, experts_held=list(range(16))))
    total, routed = jnp.zeros_like(uncut), 0.0
    for share in range(4):
        held = tuple(range(4 * share, 4 * share + 4))
        layer = dict(whole, **{name: whole[name][jnp.asarray(held)]
                               for name in ("we_gate", "we_up", "we_down")})
        part, count, _idle = jax.jit(sdar.moe, static_argnums=2)(
            tokens, layer, sdar.dataclasses.replace(cfg, experts_held=held))
        total, routed = total + part, routed + float(count)
    assert routed == 2 * LENGTH * cfg.experts_per_token  # every choice landed on one share
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut), rtol=1e-4, atol=1e-5)


def written_rule(i, j, length, block):
    """Query i may read key j, over [noisy ; clean], as ISSUE 31 writes it."""
    i_noisy, j_noisy = i < length, j < length
    bi, bj = (i % length) // block, (j % length) // block
    if i_noisy and j_noisy:
        return bi == bj
    if i_noisy and not j_noisy:
        return bj < bi
    if not i_noisy and not j_noisy:
        return bj <= bi
    return False


def test_the_mask_is_the_four_written_rules():
    """(c) every (i, j) of a small case, the program's rule and the reference's
    matrix alike."""
    length, block = 12, 3
    positions = jnp.tile(jnp.arange(length), 2)
    is_noisy = jnp.arange(2 * length) < length
    ours = np.asarray(sdar.allowed(positions, is_noisy, positions, is_noisy, block))
    theirs = np.asarray(reference.block_mask(length, block))
    for i in range(2 * length):
        for j in range(2 * length):
            assert ours[i, j] == theirs[i, j] == written_rule(i, j, length, block), (i, j)
    assert grid_module("flops", "sdar_moe").allowed_pairs(length, block) == int(ours.sum())


@pytest.mark.parametrize("half,block", [(2048, 4), (16, 4), (12, 3), (30, 6), (2048, 8)])
def test_the_predicate_is_allowed_on_every_pair(half, block):
    """``BlockDiffusion`` — what ops/attention.py is handed — against
    ``allowed`` over all (2 * half)^2 pairs: at the cell's shape, at a small
    one, at blocks that are no power of two (a division, not a shift) and at
    the planted fault's block; on numpy indices, as ``tile_table`` calls it,
    and on int32 device arrays, as the kernel does."""
    positions, is_noisy = jnp.tile(jnp.arange(half), 2), jnp.arange(2 * half) < half
    theirs = np.asarray(sdar.allowed(positions, is_noisy, positions, is_noisy, block))
    mask, index = sdar.BlockDiffusion(half, block), np.arange(2 * half)
    assert np.array_equal(np.asarray(mask(index[:, None], index[None, :])), theirs)
    on_device = jax.jit(mask)(jnp.arange(2 * half, dtype=jnp.int32)[:, None],
                              jnp.arange(2 * half, dtype=jnp.int32)[None, :])
    assert on_device.dtype == jnp.bool_ and np.array_equal(np.asarray(on_device), theirs)
    assert hash(mask) == hash(sdar.BlockDiffusion(half, block))  # a static argument of the kernels
    assert repr(mask) == "BlockDiffusion(half=%d, block=%d)" % (half, block)


def test_on_the_cpu_the_loss_runs_the_chunked_form_bit_for_bit(experiment, monkeypatch):
    """Off a TPU ``attention_form`` answers ``xla`` at the cell's shape and at
    the test's, the loss traces no kernel, and its value and gradients are
    those of ``chunked_attention`` called with nothing in between — the
    parent's ``masked_attention`` under its new name."""
    from aggregathor_tpu.ops import attention

    assert attention.attention_form(4096, 128) == "xla"
    assert attention.attention_form(2 * LENGTH, 16) == "xla"
    params, batch = seeded_params(), noised(experiment)
    value_and_grad = lambda: jax.jit(jax.value_and_grad(experiment.loss, has_aux=True))(params, batch)
    assert "pallas_call" not in str(jax.make_jaxpr(experiment.loss)(params, batch))
    (loss, _), grads = value_and_grad()
    monkeypatch.setattr(sdar, "masked_attention", sdar.chunked_attention)
    (plain_loss, _), plain_grads = value_and_grad()
    assert float(loss) == float(plain_loss)
    assert all(np.array_equal(np.asarray(grads[name]), np.asarray(plain_grads[name]))
               for name in grads)


def test_chunked_attention_is_a_dense_masked_softmax(experiment):
    """(c) the running softmax over a chunk's two key ranges against one
    softmax over all 2L keys under the boolean matrix."""
    cfg = experiment.cfg
    g, r, dh = cfg.kv_heads, cfg.heads // cfg.kv_heads, cfg.head_dim
    key = jax.random.PRNGKey(2)
    q = jax.random.normal(jax.random.fold_in(key, 0), (2, 2 * LENGTH, g, r, dh))
    k = jax.random.normal(jax.random.fold_in(key, 1), (2, 2 * LENGTH, g, dh))
    v = jax.random.normal(jax.random.fold_in(key, 2), (2, 2 * LENGTH, g, dh))
    ours = jax.jit(sdar.masked_attention, static_argnums=3)(
        q, k, v, sdar.dataclasses.replace(cfg, attn_chunk=8))
    mask = reference.block_mask(LENGTH, BLOCK)
    scores = jnp.einsum("bqgrd,bkgd->bgrqk", q, k) / np.sqrt(dh)
    weights = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    dense = jnp.einsum("bgrqk,bkgd->bqgrd", weights, v).reshape(2, 2 * LENGTH, g * r * dh)
    np.testing.assert_allclose(np.asarray(ours), np.asarray(dense), rtol=1e-5, atol=1e-5)


def forced_layer(cfg, chosen):
    """A layer whose router sends every position with feature 0 at one to
    ``chosen``: the column of each chosen expert is large on that feature."""
    key = jax.random.PRNGKey(7)
    router = np.zeros((cfg.hidden, cfg.experts), np.float32)
    router[0, list(chosen)] = 20.0 + np.arange(len(chosen))
    held = len(cfg.experts_held)
    return {"router": jnp.asarray(router),
            "we_gate": 0.2 * jax.random.normal(jax.random.fold_in(key, 0), (held, cfg.hidden, 24)),
            "we_up": 0.2 * jax.random.normal(jax.random.fold_in(key, 1), (held, cfg.hidden, 24)),
            "we_down": 0.2 * jax.random.normal(jax.random.fold_in(key, 2), (held, 24, cfg.hidden))}


def forced_tokens(cfg):
    tokens = jax.random.normal(jax.random.PRNGKey(9), (1, 2 * LENGTH, cfg.hidden))
    return tokens.at[..., 0].set(1.0)


def test_a_batch_routed_to_one_held_expert_loses_no_token(experiment):
    """(d) every position chooses held expert 7 (and three absent ones): all
    2L positions are computed, none dropped."""
    cfg = experiment.cfg
    layer, tokens = forced_layer(cfg, (7, 8, 9, 10)), forced_tokens(cfg)
    out, routed, idle = sdar.moe(tokens, layer, cfg)
    assert float(routed) == 2 * LENGTH and float(idle) == len(HELD) - 1
    theirs = reference._experts(tokens, layer, SHAPE)
    assert float(jnp.min(jnp.max(jnp.abs(theirs), axis=-1))) > 0  # every position has an output
    np.testing.assert_allclose(np.asarray(out), np.asarray(theirs), rtol=1e-4, atol=1e-6)


def test_a_batch_that_reaches_no_held_expert_gives_exactly_zero_expert_gradients(experiment):
    """(d) what only this system sees: such a worker's row holds an exactly-zero
    stretch for the expert leaves, and the median is taken over it."""
    cfg = experiment.cfg
    layer, tokens = forced_layer(cfg, (8, 9, 10, 11)), forced_tokens(cfg)

    def summed(layer, tokens):
        out, routed, idle = sdar.moe(tokens, layer, cfg)
        return jnp.sum(out * out) + jnp.sum(tokens), (routed, idle)

    (_, (routed, idle)), (dlayer, _dtokens) = jax.value_and_grad(
        summed, argnums=(0, 1), has_aux=True)(layer, tokens)
    assert float(routed) == 0 and float(idle) == len(HELD)
    for name in ("we_gate", "we_up", "we_down"):
        assert not np.any(np.asarray(dlayer[name])), name


def engine_keys(seed, step, worker):
    """The engine's documented per-step, per-worker keys: sampling (fold tag
    4) and augmentation (fold tag 3)."""
    worker_key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(seed), step), worker)
    return jax.random.fold_in(worker_key, 4), jax.random.fold_in(worker_key, 3)


@pytest.mark.parametrize("step,worker", [(0, 0), (3, 2)])
def test_device_transform_and_the_restated_feed_draw_alike(experiment, step, worker):
    """(e) the same rows and the same mask from the same keys."""
    dataset = {"tokens": jnp.asarray(experiment.corpus)}
    sample_key, augment_key = engine_keys(17, step, worker)
    rows = jax.random.randint(sample_key, (2,), 0, len(experiment.corpus))
    ours = experiment.device_transform()({"tokens": dataset["tokens"][rows]}, augment_key)
    inputs, targets = feed.worker_batch(dataset, jax.random.PRNGKey(17), step, worker,
                                        batch_size=2, augment=AUGMENT)
    assert np.array_equal(ours["tokens"], targets)
    assert np.array_equal(ours["noisy"], inputs["noisy"])
    assert np.array_equal(ours["t"], inputs["t"])
    assert np.any(np.asarray(ours["noisy"]) == VOCAB - 1)


def test_an_engine_step_under_median_matches_the_plain_loop(experiment):
    """(f) two scanned, device-sampled steps of ``RobustEngine`` under the
    coordinate-wise median at n = 4, f = 1 against the plain loop: restated
    stream, reference loss, plain median, plain SGD.  The counters ride with
    the loss."""
    from jax.flatten_util import ravel_pytree

    median = grid_module("rules", "median")
    n, seed, rate, steps = 4, 23, 0.05, 2
    engine = RobustEngine(make_mesh(nb_workers=1, devices=jax.devices()[:1]),
                          gars.instantiate("median", n, 1), n,
                          batch_transform=experiment.device_transform())
    tx = optax.sgd(rate)
    multi = engine.build_sampled_multi_step(experiment.loss, tx, repeat_steps=steps,
                                            batch_size=experiment.batch_size)
    params = seeded_params()
    theta, unravel = ravel_pytree(params)
    state = engine.init_state(jax.tree.map(jnp.copy, params), tx, seed=seed)
    state, metrics = multi(state, engine.replicate(experiment.train_arrays()))

    dataset = {"tokens": jnp.asarray(experiment.corpus)}
    plain = jax.jit(jax.value_and_grad(
        lambda v, inputs, targets: reference.loss(unravel(v), inputs, targets)))
    losses = []
    for step in range(steps):
        rows, total = [], 0.0
        for worker in range(n):
            inputs, targets = feed.worker_batch(dataset, jax.random.PRNGKey(seed), step, worker,
                                                batch_size=2, augment=AUGMENT)
            value, gradient = plain(theta, inputs, targets)
            rows.append(gradient)
            total += float(value)
        losses.append(total)
        theta = theta - rate * median.aggregate(jnp.stack(rows), 1)
    np.testing.assert_allclose(np.asarray(metrics["total_loss"]), losses, rtol=1e-5)
    ours = ravel_pytree(state.params)[0]
    moved = float(jnp.linalg.norm(theta - ravel_pytree(params)[0]))
    assert float(jnp.linalg.norm(ours - theta)) <= 2e-3 * moved
    counters = metrics["model_counters"]
    assert counters["routed_positions"].shape == (steps, n)
    assert np.all(np.asarray(counters["masked_share"]) > 0)


def test_a_scalar_loss_leaves_the_metrics_as_they_were():
    """The seam is taken only by a loss marked ``has_aux``."""
    engine = RobustEngine(make_mesh(nb_workers=1, devices=jax.devices()[:1]),
                          gars.instantiate("median", 4, 1), 4)
    tx = optax.sgd(0.1)
    step = engine.build_step(lambda p, b: jnp.sum((b["x"] @ p["w"]) ** 2), tx)
    state = engine.init_state({"w": jnp.ones((3, 2))}, tx, seed=0)
    _state, metrics = step(state, engine.shard_batch({"x": jnp.ones((4, 5, 3))}))
    assert "model_counters" not in metrics


def test_model_scopes_make_a_second_table(experiment):
    """``phase_table`` with ``MODEL_PREFIX`` cuts the same program by the
    model's parts, and the step's own table takes no notice of them."""
    from aggregathor_tpu.obs import profiler

    engine = RobustEngine(make_mesh(nb_workers=1, devices=jax.devices()[:1]),
                          gars.instantiate("median", 4, 1), 4,
                          batch_transform=experiment.device_transform())
    tx = optax.sgd(0.1)
    multi = engine.build_sampled_multi_step(experiment.loss, tx, repeat_steps=1,
                                            batch_size=experiment.batch_size)
    state = engine.init_state(seeded_params(), tx, seed=1)
    multi(state, engine.replicate(experiment.train_arrays()))
    text = multi.compiled_text()
    by_part, _ = profiler.phase_table(text, profiler.MODEL_PREFIX)
    assert {"embed", "attention", "router", "experts", "head", "noise"} <= set(by_part.values())
    by_phase, _ = profiler.phase_table(text)
    assert set(filter(None, by_phase.values())) <= set(PHASES)
    assert profiler.phase_of("jit(f)/step.grad/vmap(jvp(model.attention))/dot") == "grad"
    assert profiler.phase_of("jit(f)/step.grad/vmap(jvp(model.attention))/dot",
                             profiler.MODEL_PREFIX) == "attention"
