"""models/laguna.py against its plain reference (grid/references/laguna.py), at
a tiny size on the CPU: hidden 64, the published pattern of five layers (full,
three sliding, full; dense, then sparse) with 6 and 8 heads over 2 key/value
heads, a window of 12 at L = 32, 16 experts of which 4 are held.  Products run
at ``highest`` precision, so what separates the two is the order of float32
sums (the running softmax by chunk against one softmax over all the keys)."""

import dataclasses
import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from aggregathor_tpu import gars, models
from aggregathor_tpu.models import laguna
from aggregathor_tpu.models.transformer import rope, rope_frequencies
from aggregathor_tpu.ops.attention import forced_form
from aggregathor_tpu.parallel import RobustEngine, make_mesh

GRID = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "grid")


def grid_module(kind, name):
    spec = importlib.util.spec_from_file_location(
        "laguna_test_%s_%s" % (kind, name.replace("-", "_")), os.path.join(GRID, kind, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


reference = grid_module("references", "laguna")
feed = grid_module("references", "feed_device_tokens_causal")

HELD = (1, 4, 7, 12)
VOCAB, LENGTH, WINDOW = 50, 32, 12
PUBLISHED = (("full", "sliding", "sliding", "sliding", "full"),
             ("dense", "sparse", "sparse", "sparse", "sparse"), (6, 8, 8, 8, 6))
#: another pattern than the published one: runs of 2, 1, 1, 1 and no leading dense layer
OTHER = (("sliding", "sliding", "full", "full", "sliding"),
         ("sparse", "sparse", "dense", "sparse", "sparse"), (4, 4, 8, 8, 6))
ROPE = {"full_attention": {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
                           "original_max_position_embeddings": 4096, "beta_slow": 1, "beta_fast": 64,
                           "attention_factor": 1.4158883083359672, "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}}


def arguments(pattern=PUBLISHED, held=HELD, batch=2):
    listed = lambda values: ",".join(map(str, values))
    return ["vocab:%d" % VOCAB, "hidden:64", "kv-heads:2", "head-dim:16",
            "layer-types:" + listed(pattern[0]), "mlp-types:" + listed(pattern[1]),
            "heads:" + listed(pattern[2]), "window:%d" % WINDOW, "dense-width:96", "experts:16",
            "experts-per-token:4", "expert-width:24", "shared-width:24",
            "experts-held:" + listed(held), "seq:%d" % LENGTH, "attn-chunk:8",
            "batch-size:%d" % batch, "corpus:16"]


def shape(pattern=PUBLISHED, held=HELD):
    return {"sequence_length": LENGTH, "hidden_size": 64, "num_key_value_heads": 2, "head_dim": 16,
            "layer_types": [kind + "_attention" for kind in pattern[0]],
            "mlp_layer_types": list(pattern[1]),
            "num_attention_heads_per_layer": list(pattern[2]), "sliding_window": WINDOW,
            "intermediate_size": 96, "num_experts": 16, "num_experts_per_tok": 4,
            "moe_intermediate_size": 24, "shared_expert_intermediate_size": 24,
            "moe_routed_scaling_factor": 2.5, "experts_held": list(held),
            "rope_parameters": ROPE, "rms_norm_eps": 1e-6}


@pytest.fixture(autouse=True)
def highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture
def experiment():
    return models.instantiate("laguna", arguments())


def seeded_params(pattern=PUBLISHED, seed=3, gain=10.0):
    """The reference's own weights, the matrices of the layers scaled up so
    that routing and attention are far from uniform."""
    params = reference.init(jax.random.PRNGKey(seed), shape(pattern), VOCAB)
    params["layers"] = [{name: leaf if name.endswith("norm") else leaf * gain
                         for name, leaf in run.items()} for run in params["layers"]]
    return params


def test_experiment_and_reference_build_the_same_tree(experiment):
    ours = experiment.init(jax.random.PRNGKey(3))
    theirs = reference.init(jax.random.PRNGKey(3), shape(), VOCAB)
    assert jax.tree.map(lambda a: a.shape, ours) == jax.tree.map(lambda a: a.shape, theirs)
    assert all(bool(jnp.all(a == b)) for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)))
    assert [count for _kind, count in experiment.cfg.runs()] == [1, 3, 1]
    full = jax.tree.leaves(laguna.leaf_shapes(laguna.LagunaConfig()),
                           is_leaf=lambda leaf: isinstance(leaf, tuple))
    assert sum(int(np.prod(dims)) for dims in full) == 389044224


@pytest.mark.parametrize("pattern", [PUBLISHED, OTHER], ids=["published", "other"])
def test_loss_and_gradients_match_the_reference(pattern):
    """The model driven by the three lists (runs stacked and scanned) against
    the reference's plain loop over the layers, at the published pattern and at
    another.  Tolerance 2e-3 of each leaf's largest gradient entry: both sides
    are float32 at ``highest`` and differ by the order of their sums; read
    4e-6 at worst."""
    experiment = models.instantiate("laguna", arguments(pattern))
    params = seeded_params(pattern)
    batch = {"tokens": jnp.asarray(experiment.corpus[:2])}
    (loss, counters), grads = jax.jit(jax.value_and_grad(experiment.loss, has_aux=True))(
        params, batch)
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(reference.loss))(
        params, batch["tokens"][:, :-1], batch["tokens"][:, 1:])
    assert abs(float(loss) - float(ref_loss)) <= 1e-5 * abs(float(ref_loss))
    for (path, ours), theirs in zip(jax.tree_util.tree_leaves_with_path(grads),
                                    jax.tree.leaves(ref_grads)):
        scale = float(jnp.max(jnp.abs(theirs)))
        assert scale > 0, path
        assert float(jnp.max(jnp.abs(ours - theirs))) <= 2e-3 * scale, path
    assert float(counters["routed_positions"]) > 0


def attention_layer(kind, seed=5):
    """One layer's attention leaves for ``kind`` and a batch of inputs."""
    cfg = laguna.LagunaConfig(hidden=64, kv_heads=2, head_dim=16, window=WINDOW, seq=LENGTH,
                              attn_chunk=8)
    key = jax.random.PRNGKey(seed)
    heads = kind[2]
    layer = {"wq": 0.05 * jax.random.normal(jax.random.fold_in(key, 0), (64, heads * 16)),
             "wk": 0.05 * jax.random.normal(jax.random.fold_in(key, 1), (64, 32)),
             "wv": jax.random.normal(jax.random.fold_in(key, 2), (64, 32)),
             "wo": jax.random.normal(jax.random.fold_in(key, 3), (heads * 16, 64))}
    return cfg, layer, jax.random.normal(jax.random.fold_in(key, 4), (1, LENGTH, 64))


@pytest.mark.parametrize("kind,reads_far", [(("sliding", "sparse", 8), False),
                                            (("full", "sparse", 6), True)])
def test_the_window_is_a_window(kind, reads_far):
    """A token ``window`` or more back moves no sliding layer's output at that
    query, and does move a full layer's; one inside the window moves both."""
    cfg, layer, u = attention_layer(kind)
    attend = jax.jit(lambda u: laguna.attention(u, layer, cfg, kind))
    query = LENGTH - 3
    base = attend(u)
    far = attend(u.at[0, query - WINDOW].add(1.0))    # exactly ``window`` back: outside
    near = attend(u.at[0, query - WINDOW + 1].add(1.0))  # the window's first key
    moved = lambda other: float(jnp.max(jnp.abs(other[0, query] - base[0, query])))
    assert (moved(far) > 1e-4) is reads_far
    assert moved(far) == 0.0 or reads_far
    assert moved(near) > 1e-4
    # and no layer reads ahead
    ahead = attend(u.at[0, query + 1].add(1.0))
    assert moved(ahead) == 0.0


@pytest.mark.parametrize("form", ["xla", "kernel"])
@pytest.mark.parametrize("window", [None, WINDOW, 5])
def test_chunked_attention_is_a_dense_masked_softmax(window, form):
    """The running softmax over a chunk's key ranges against one softmax over
    all L keys under the boolean matrix, and the ranges against the mask; the
    fused kernel (ops/attention.py, interpreted here) through the same call."""
    cfg = laguna.LagunaConfig(attn_chunk=8, seq=LENGTH)
    key = jax.random.PRNGKey(2)
    q = jax.random.normal(jax.random.fold_in(key, 0), (2, LENGTH, 2, 3, 16))
    k = jax.random.normal(jax.random.fold_in(key, 1), (2, LENGTH, 2, 16))
    v = jax.random.normal(jax.random.fold_in(key, 2), (2, LENGTH, 2, 16))
    with forced_form(form):
        ours = jax.jit(lambda q, k, v: laguna.causal_attention(q, k, v, cfg, window))(q, k, v)
    mask = reference.causal_mask(LENGTH, window)
    positions = jnp.arange(LENGTH)
    assert np.array_equal(np.asarray(laguna.allowed(positions, positions, window)), np.asarray(mask))
    scores = jnp.einsum("bqgrd,bkgd->bgrqk", q, k) / np.sqrt(16)
    weights = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    dense = jnp.einsum("bgrqk,bkgd->bqgrd", weights, v).reshape(2, LENGTH, 2 * 3 * 16)
    np.testing.assert_allclose(np.asarray(ours), np.asarray(dense), rtol=1e-5, atol=1e-5)
    mask = np.asarray(mask)
    clear, edged = laguna.key_offsets(8, LENGTH // 8, window)
    for i in range(LENGTH // 8):  # the folded chunks hold every allowed key, the clear ones allowed keys only
        folded = np.zeros(LENGTH, bool)
        folded[8 * i:8 * i + 8] = True
        for back in clear + edged:
            if i - back >= 0:
                folded[8 * (i - back):8 * (i - back) + 8] = True
                assert back in edged or mask[8 * i:8 * i + 8, 8 * (i - back):8 * (i - back) + 8].all()
        assert not mask[8 * i:8 * i + 8, ~folded].any()
    assert len(clear + edged) == (LENGTH // 8 - 1 if window is None else (window + 6) // 8)
    assert grid_module("flops", "laguna").allowed_pairs(LENGTH, window) == int(mask.sum())


@pytest.mark.parametrize("form", ["xla", "kernel"])
@pytest.mark.parametrize("window", [None, WINDOW])
def test_a_narrower_dtype_keeps_its_scores_wide(window, form):
    """Under ``dtype:bfloat16`` every score product of the chunked attention
    leaves as float32 (rounded to bfloat16 first, q's and k's gradients read
    NaN on the chip), and the gradients are finite.  The kernel widens what it
    loads: its products take float32 operands and none is narrow."""
    cfg = laguna.LagunaConfig(attn_chunk=8, seq=LENGTH, dtype=jnp.bfloat16)
    key = jax.random.PRNGKey(4)
    q = jax.random.normal(jax.random.fold_in(key, 0), (1, LENGTH, 2, 3, 16)).astype(jnp.bfloat16)
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, LENGTH, 2, 16)).astype(jnp.bfloat16)
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, LENGTH, 2, 16)).astype(jnp.bfloat16)
    value = lambda q, k, v: jnp.sum(
        laguna.causal_attention(q, k, v, cfg, window).astype(jnp.float32) ** 2)
    products = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general":
                products.append((tuple(a.aval.dtype for a in eqn.invars), eqn.outvars[0].aval.dtype))
            for inner in jax.core.jaxprs_in_params(eqn.params):
                walk(inner)

    with forced_form(form):
        walk(jax.make_jaxpr(value)(q, k, v).jaxpr)
        grads = jax.jit(jax.grad(value, argnums=(0, 1, 2)))(q, k, v)
    narrow = [out for operands, out in products if operands == (jnp.bfloat16, jnp.bfloat16)]
    assert products and all(out == jnp.float32 for _operands, out in products)
    assert bool(narrow) is (form == "xla")
    assert all(bool(jnp.all(jnp.isfinite(g.astype(jnp.float32)))) for g in grads)


def test_the_shares_of_an_expert_layer_add_up_to_the_whole():
    """64 experts over 8 shares of 8: each share is told which experts it
    holds and routes over all 64; the routed parts of the eight shares and the
    shared expert COUNTED ONCE add up to the uncut reference's layer."""
    cfg = laguna.LagunaConfig(hidden=64, experts=64, experts_per_token=8, expert_width=24,
                              shared_width=24)
    key = jax.random.PRNGKey(11)
    tokens = jax.random.normal(jax.random.fold_in(key, 0), (1, LENGTH, 64))
    whole = {"router": jax.random.normal(jax.random.fold_in(key, 1), (64, 64))}
    for place, (name, dims) in enumerate((
            ("we_gate", (64, 64, 24)), ("we_up", (64, 64, 24)), ("we_down", (64, 24, 64)),
            ("ws_gate", (64, 24)), ("ws_up", (64, 24)), ("ws_down", (24, 64)))):
        whole[name] = 0.2 * jax.random.normal(jax.random.fold_in(key, 2 + place), dims)
    uncut = reference._sparse(tokens, whole, dict(shape(), num_experts=64, num_experts_per_tok=8,
                                                  experts_held=list(range(64))))
    shared = reference._unit(tokens, whole["ws_gate"], whole["ws_up"], whole["ws_down"])
    total, routed = shared, 0.0
    for share in range(8):
        held = tuple(range(8 * share, 8 * share + 8))
        layer = dict(whole, **{name: whole[name][jnp.asarray(held)]
                               for name in ("we_gate", "we_up", "we_down")})
        part, count, _idle = jax.jit(laguna.sparse_ffn, static_argnums=2)(
            tokens, layer, dataclasses.replace(cfg, experts_held=held))
        total, routed = total + (part - shared), routed + float(count)
    assert routed == LENGTH * cfg.experts_per_token  # every choice landed on one share
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut), rtol=1e-4, atol=1e-5)


def test_the_head_shares_of_an_attention_layer_add_up_to_the_whole():
    """The configuration holds half of each layer's heads: the two halves of a
    layer (query heads 0-3 with key/value head 0, 4-7 with 1: the columns of
    ``wq``, ``wk`` and ``wv`` and the rows of ``wo``) give partial sums through
    ``wo`` that add up to the uncut layer, sliding and full alike."""
    for kind in (("sliding", "sparse", 8), ("full", "sparse", 8)):
        cfg, layer, u = attention_layer(kind)
        whole = laguna.attention(u, layer, cfg, kind)
        total = jnp.zeros_like(whole)
        for share in range(2):
            q, kv = slice(64 * share, 64 * share + 64), slice(16 * share, 16 * share + 16)
            part = {"wq": layer["wq"][:, q], "wk": layer["wk"][:, kv], "wv": layer["wv"][:, kv],
                    "wo": layer["wo"][q]}
            total = total + laguna.attention(
                u, part, dataclasses.replace(cfg, kv_heads=1), (kind[0], kind[1], 4))
        np.testing.assert_allclose(np.asarray(total), np.asarray(whole), rtol=1e-4, atol=1e-4)


def test_yarn_frequencies_against_the_closed_form():
    """At the published sizes: 32 turned pairs of a 128-wide head, theta 5e5,
    factor 64 over 4096 positions, beta 64 and 1.  The correction dimensions
    are floor(5.66) = 5 and ceil(15.80) = 16: pairs up to 5 keep the default
    frequency, pairs from 16 on are slowed 64 times, pair 10 lies 5/11 of the
    way; the factor on cos and sin is 0.1 ln 64 + 1."""
    table = laguna.LagunaConfig().full_rope
    inv_freq = np.asarray(table.inverse_frequencies(128), np.float64)
    default = 5e5 ** (-np.arange(32) / 32.0)
    assert inv_freq.shape == (32,)
    np.testing.assert_allclose(inv_freq[:6], default[:6], rtol=1e-6)
    np.testing.assert_allclose(inv_freq[16:], default[16:] / 64, rtol=1e-6)
    np.testing.assert_allclose(inv_freq[10], default[10] * (6 / 11 + 5 / 11 / 64), rtol=1e-6)
    assert math.isclose(table.attention_factor, 0.1 * math.log(64) + 1, rel_tol=1e-12)
    theirs, factor = reference.rope_table(ROPE["full_attention"], 128)
    np.testing.assert_allclose(inv_freq, theirs, rtol=1e-6)
    assert factor == table.attention_factor
    sliding = np.asarray(laguna.LagunaConfig().sliding_rope.inverse_frequencies(128))
    np.testing.assert_allclose(sliding, 1e4 ** (-np.arange(64) / 64.0), rtol=1e-6)
    np.testing.assert_allclose(sliding, np.asarray(rope_frequencies(128, 1e4)), rtol=1e-5)


def test_partial_rotation_leaves_the_other_half_untouched():
    table = laguna.LagunaConfig().full_rope
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 7, 3, 128))
    turned = rope(x, jnp.arange(7), table.inverse_frequencies(128), table.attention_factor)
    assert np.array_equal(np.asarray(turned[..., 64:]), np.asarray(x[..., 64:]))
    assert np.all(np.asarray(turned[:, 1:, :, :64]) != np.asarray(x[:, 1:, :, :64]))
    # position 0 turns nothing, but cos is scaled
    np.testing.assert_allclose(np.asarray(turned[:, 0, :, :64]),
                               table.attention_factor * np.asarray(x[:, 0, :, :64]), rtol=1e-6)
    theirs = reference._rope(x, ROPE["full_attention"])
    np.testing.assert_allclose(np.asarray(turned), np.asarray(theirs), rtol=1e-5, atol=1e-6)


def test_an_unreached_held_expert_gets_exactly_zero_gradient():
    """What only this system sees: such a worker's row holds an exactly-zero
    stretch for that expert's leaves, and the rule ranks it beside the others'."""
    cfg = laguna.LagunaConfig(hidden=64, experts=16, experts_per_token=4, expert_width=24,
                              shared_width=24, experts_held=HELD)
    key = jax.random.PRNGKey(7)
    router = np.zeros((64, 16), np.float32)
    router[0, [4, 8, 9, 10]] = 20.0 + np.arange(4)  # every position chooses held expert 4 alone
    layer = {"router": jnp.asarray(router)}
    for place, (name, dims) in enumerate((
            ("we_gate", (4, 64, 24)), ("we_up", (4, 64, 24)), ("we_down", (4, 24, 64)),
            ("ws_gate", (64, 24)), ("ws_up", (64, 24)), ("ws_down", (24, 64)))):
        layer[name] = 0.2 * jax.random.normal(jax.random.fold_in(key, place), dims)
    tokens = jax.random.normal(jax.random.PRNGKey(9), (1, LENGTH, 64)).at[..., 0].set(1.0)

    def summed(layer):
        out, routed, idle = laguna.sparse_ffn(tokens, layer, cfg)
        return jnp.sum(out * out), (routed, idle)

    (_, (routed, idle)), dlayer = jax.value_and_grad(summed, has_aux=True)(layer)
    assert float(routed) == LENGTH and float(idle) == len(HELD) - 1
    for name in ("we_gate", "we_up", "we_down"):
        reached = np.asarray(dlayer[name])
        assert np.any(reached[1]) and not np.any(reached[[0, 2, 3]]), name
    assert np.any(np.asarray(dlayer["ws_gate"]))


@pytest.mark.parametrize("step,worker", [(0, 0), (3, 2)])
def test_the_restated_feed_draws_the_engines_rows(experiment, step, worker):
    dataset = {"tokens": jnp.asarray(experiment.corpus)}
    worker_key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(17), step), worker)
    rows = jax.random.randint(jax.random.fold_in(worker_key, 4), (2,), 0, len(experiment.corpus))
    inputs, targets = feed.worker_batch(dataset, jax.random.PRNGKey(17), step, worker,
                                        batch_size=2, augment="none")
    assert np.array_equal(inputs, experiment.corpus[np.asarray(rows), :-1])
    assert np.array_equal(targets, experiment.corpus[np.asarray(rows), 1:])
    assert experiment.corpus.shape == (16, LENGTH + 1) and experiment.device_transform() is None


def test_an_engine_step_under_the_averaged_median_matches_the_plain_loop():
    """Two scanned, device-sampled steps of ``RobustEngine`` under the averaged
    median at n = 3, f = 1 against the plain loop: restated stream, reference
    loss, plain rule, plain SGD.  The counters ride with the loss, and the
    model's parts make the second table of the compiled step.  One sequence a
    worker, as in the cell: with two, a worker that draws one row twice hands in
    exactly twice another's embedding gradient, (2x, 0, x) ties the two outer
    values exactly, and the rule's answer there (1.5x or 0.5x) turns on the
    last bit."""
    from jax.flatten_util import ravel_pytree

    from aggregathor_tpu.obs import profiler

    experiment = models.instantiate("laguna", arguments(batch=1))
    rule = grid_module("rules", "averaged-median")
    n, seed, rate, steps = 3, 23, 0.05, 2
    engine = RobustEngine(make_mesh(nb_workers=1, devices=jax.devices()[:1]),
                          gars.instantiate("averaged-median", n, 1), n)
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
                                                batch_size=1, augment="none")
            value, gradient = plain(theta, inputs, targets)
            rows.append(gradient)
            total += float(value)
        losses.append(total)
        theta = theta - rate * rule.aggregate(jnp.stack(rows), 1)
    np.testing.assert_allclose(np.asarray(metrics["total_loss"]), losses, rtol=1e-5)
    ours = ravel_pytree(state.params)[0]
    moved = float(jnp.linalg.norm(theta - ravel_pytree(params)[0]))
    assert float(jnp.linalg.norm(ours - theta)) <= 5e-3 * moved
    assert metrics["model_counters"]["routed_positions"].shape == (steps, n)
    by_part, _ = profiler.phase_table(multi.compiled_text(), profiler.MODEL_PREFIX)
    assert {"embed", "attention_full", "attention_window", "dense_mlp", "shared_expert", "router",
            "experts", "head"} <= set(by_part.values())


def test_plain_averaged_median_is_the_programs():
    """The plain restatement against gars/averaged_median.py at n = 3, f = 1,
    with exact zeros (a worker no token of which reached an expert), a NaN, an
    infinity and an exact tie of the two outer values planted."""
    rows = np.random.default_rng(0).normal(size=(3, 1000)).astype(np.float32)
    rows[1, 100:300] = 0.0
    rows[2, 200:400] = 0.0
    rows[2, 5], rows[0, 6] = np.nan, np.inf
    rows[:, 7] = (1.0, 2.0, 3.0)   # both outer values one away: the lower row wins
    rows[:, 8] = (3.0, 2.0, 1.0)
    rule = grid_module("rules", "averaged-median")
    ours = np.asarray(gars.instantiate("averaged-median", 3, 1).aggregate(jnp.asarray(rows)))
    plain = np.asarray(rule.aggregate(jnp.asarray(rows), 1))
    assert np.array_equal(ours, plain)
    assert plain[7] == 1.5 and plain[8] == 2.5
    ordered = np.sort(rows[:, 10:100], axis=0)
    nearer = np.where(ordered[1] - ordered[0] <= ordered[2] - ordered[1], ordered[0], ordered[2])
    np.testing.assert_allclose(plain[10:100], (ordered[1] + nearer) / 2, rtol=1e-6)
    assert rule.least_bytes(3, 1, 10) == 4 * 10 * 4 and rule.flops(3, 1, 10) == 60


def test_the_experiment_refuses_lists_that_disagree():
    from aggregathor_tpu.utils import UserException

    with pytest.raises(UserException):
        models.instantiate("laguna", ["heads:48,64", "batch-size:1", "corpus:1", "seq:256"])
    with pytest.raises(UserException):
        models.instantiate("laguna", ["layer-types:full,window,full,full,full", "corpus:1",
                                      "seq:256"])


def test_the_runner_trains_it_on_the_sampled_scanned_path(tmp_path):
    """``cli.runner`` builds the experiment, the engine and the device-sampled
    K-step trainer, and its regularisation wrapper keeps a loss's counters
    (a wrapper that dropped ``has_aux`` stopped the first step)."""
    from aggregathor_tpu.cli import runner

    with jax.default_matmul_precision("default"):
        assert 0 == runner.main([
            "--experiment", "laguna", "--experiment-args", *arguments(batch=1),
            "--aggregator", "averaged-median", "--nb-workers", "3", "--nb-decl-byz-workers", "1",
            "--max-step", "4", "--input-source", "device", "--unroll", "2",
            "--l2-regularize", "1e-4"])
