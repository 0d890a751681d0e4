"""models/keye_vl2.py against its plain reference (grid/references/keye_vl2.py),
at a tiny size on the CPU: hidden 64, 8 query heads over 2 key heads of 16, an
indexer of 4 heads of 8 choosing 8 of up to 32 keys a query, three layers, 16
experts of which 4 are held, 4 a token.  Products run at ``highest`` precision,
so what separates the two is the order of float32 sums (a threshold from one
stable sort against a rank from two argsorts; a chunk's softmax, or the
kernel's tiles, against one softmax over all the keys)."""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from aggregathor_tpu import gars, models
from aggregathor_tpu.models import keye_vl2
from aggregathor_tpu.models.transformer import rope, rope_frequencies
from aggregathor_tpu.ops import select
from aggregathor_tpu.ops.attention import forced_form
from aggregathor_tpu.parallel import RobustEngine, make_mesh
from aggregathor_tpu.utils import UserException

GRID = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "grid")


def grid_module(kind, name):
    spec = importlib.util.spec_from_file_location(
        "keye_test_%s_%s" % (kind, name.replace("-", "_")), os.path.join(GRID, kind, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


reference = grid_module("references", "keye_vl2")
feed = grid_module("references", "feed_device_tokens_causal")

HELD = (1, 4, 7, 12)
VOCAB, LENGTH, TOPK = 50, 32, 8


def arguments(layers=3, held=HELD, topk=TOPK, batch=2):
    return ["vocab:%d" % VOCAB, "hidden:64", "heads:8", "kv-heads:2", "head-dim:16",
            "layers:%d" % layers, "experts:16", "experts-per-token:4", "expert-width:24",
            "experts-held:" + ",".join(map(str, held)), "index-heads:4", "index-head-dim:8",
            "index-topk:%d" % topk, "mrope-section:2,3,3", "seq:%d" % LENGTH, "attn-chunk:8",
            "select-chunk:16", "batch-size:%d" % batch, "corpus:16"]


def shape(layers=3, held=HELD, topk=TOPK, experts=16):
    return {"sequence_length": LENGTH, "hidden_size": 64, "num_attention_heads": 8,
            "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": layers,
            "num_experts": experts, "num_experts_per_tok": 4, "moe_intermediate_size": 24,
            "experts_held": list(held), "rope_theta": 10000000, "rms_norm_eps": 1e-6,
            "rope_scaling": {"mrope_section": [2, 3, 3], "rope_type": "default", "type": "default"},
            "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 4, "indexer_num_kv_heads": 1,
                          "kv_chunk_size": 512, "q_chunk_size": 512, "topk": topk}}


def config(**changed):
    return dataclasses.replace(models.instantiate("keye_vl2", arguments()).cfg, **changed)


@pytest.fixture(autouse=True)
def highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


def seeded_params(layers=3, seed=3, gain=10.0, topk=TOPK):
    """The reference's own weights, the layers' matrices scaled up so that
    routing, attention and the index scores are far from uniform."""
    params = reference.init(jax.random.PRNGKey(seed), shape(layers, topk=topk), VOCAB)
    params["layers"] = [{name: leaf if name.endswith("norm") else leaf * gain
                         for name, leaf in run.items()} for run in params["layers"]]
    return params


def one_layer(params, place=0):
    return {name: leaf[place] for name, leaf in params["layers"][0].items()}


INDEXER = ("index_wq", "index_wk", "index_ww", "index_k_norm", "index_k_bias")


def test_experiment_and_reference_build_the_same_tree():
    experiment = models.instantiate("keye_vl2", arguments())
    ours = experiment.init(jax.random.PRNGKey(3))
    theirs = reference.init(jax.random.PRNGKey(3), shape(), VOCAB)
    assert jax.tree.map(lambda a: a.shape, ours) == jax.tree.map(lambda a: a.shape, theirs)
    assert all(bool(jnp.all(a == b)) for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)))
    assert experiment.cfg.runs() == [(None, 3)]
    count = lambda tree: sum(int(np.prod(dims)) for dims in jax.tree.leaves(
        tree, is_leaf=lambda leaf: isinstance(leaf, tuple)))
    published = keye_vl2.leaf_shapes(keye_vl2.KeyeVL2Config())   # the grid's configuration
    assert count(published) == 4 * 59150720 + 77793280 == 314396160
    assert count({name: dims for name, dims in published["layers"][0].items()
                  if name in INDEXER}) == 4 * 2261120            # leaves no gradient reaches
    assert float(jnp.max(jnp.abs(ours["layers"][0]["index_k_bias"]))) > 0  # seeded, not zero


@pytest.mark.parametrize("form", ["xla", "kernel"])
@pytest.mark.parametrize("layers,topk", [(3, TOPK), (2, 5)], ids=["three-layers", "top-5"])
def test_loss_and_gradients_match_the_reference(layers, topk, form):
    """The model (layers stacked and scanned, a quarter of the experts held, the
    selection by a threshold, the chunked softmax or the interpreted kernel
    under a mask that is data) against the reference's plain loop.  Tolerance
    2e-3 of each leaf's largest gradient entry: both sides are float32 at
    ``highest`` and differ by the order of their sums.  Every indexer leaf's
    gradient is exactly zero on both sides."""
    experiment = models.instantiate("keye_vl2", arguments(layers, topk=topk))
    params = seeded_params(layers, topk=topk)
    reference.init(jax.random.PRNGKey(0), shape(layers, topk=topk), VOCAB)  # records the shape
    batch = {"tokens": jnp.asarray(experiment.corpus[:2])}
    with forced_form(form):
        (loss, counters), grads = jax.jit(jax.value_and_grad(experiment.loss, has_aux=True))(
            params, batch)
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(reference.loss))(
        params, batch["tokens"][:, :-1], batch["tokens"][:, 1:])
    assert abs(float(loss) - float(ref_loss)) <= 1e-5 * abs(float(ref_loss))
    for (path, ours), theirs in zip(jax.tree_util.tree_leaves_with_path(grads),
                                    jax.tree.leaves(ref_grads)):
        scale = float(jnp.max(jnp.abs(theirs)))
        if any(name in jax.tree_util.keystr(path) for name in INDEXER):
            assert scale == 0.0 and not np.any(np.asarray(ours)), path
            continue
        assert scale > 0, path
        assert float(jnp.max(jnp.abs(ours - theirs))) <= 2e-3 * scale, path
    keys = sum(min(t + 1, topk) for t in range(LENGTH)) / LENGTH
    assert float(counters["selected_keys"]) == pytest.approx(keys, rel=1e-6)
    assert 0 < float(counters["selected_far_share"]) < 1
    assert 0 < float(counters["live_tile_share"]) <= 1
    assert float(counters["routed_positions"]) > 0


SELECT_FORMS = pytest.mark.parametrize("select_form", ["xla", "kernel"], ids=["sort", "count"])


@SELECT_FORMS
def test_every_query_selects_its_count_of_causal_keys(select_form):
    """``select``'s pairs: query t reads min(t + 1, k) keys, none after itself,
    the same ones as the reference's two argsorts choose from the same scores;
    the three counts are those of the pairs.  By the sort and by the counting
    kernel (ops/select.py, interpreted) alike."""
    cfg = config()
    layer = one_layer(seeded_params())
    u = jax.random.normal(jax.random.PRNGKey(6), (2, LENGTH, 64))
    positions = keye_vl2.text_positions(LENGTH)
    with select.forced_form(select_form):
        pairs, (selected, far, live) = jax.jit(
            lambda u: keye_vl2.select(u, layer, cfg, positions))(u)
    pairs = np.asarray(pairs)
    assert pairs.shape == (2, LENGTH, LENGTH) and pairs.dtype == np.int8
    assert set(np.unique(pairs)) == {0, 1} and not np.triu(pairs, 1).any()
    assert np.array_equal(pairs.sum(-1), np.broadcast_to(
        np.minimum(np.arange(LENGTH) + 1, TOPK), (2, LENGTH)))
    assert not np.array_equal(pairs[0], pairs[1])            # chosen by the data
    q, k, weights = keye_vl2.indexer_heads(u, layer, cfg, positions)
    scores = keye_vl2.index_scores(q, k, weights)
    theirs = reference._selected(scores, jnp.arange(LENGTH), TOPK)
    assert np.array_equal(pairs != 0, np.asarray(theirs))
    index = np.arange(LENGTH)
    assert float(selected) == pairs.sum()
    assert float(far) == (pairs * ((index[:, None] - index[None, :]) > TOPK)).sum() > 0
    tiles = pairs.reshape(2, LENGTH // 8, 8, LENGTH // 8, 8).max(axis=(2, 4))
    assert float(live) == tiles.sum() and 2 * 4 <= tiles.sum() <= 2 * 10  # of 10 causal tiles each


@SELECT_FORMS
def test_equal_scores_go_to_the_lower_key(select_form):
    """Five keys tie for the last three places: the three lowest are in, in the
    program's threshold and in the reference's rank alike; a zero of either
    sign ties with the other; a key after the query is never in, whatever its
    score."""
    scores = jnp.zeros((1, 2, 12)).at[0, :, 1].set(3.0).at[0, :, 4].set(2.0)
    scores = scores.at[0, :, jnp.asarray([2, 5, 7, 8, 9])].set(1.0).at[0, :, 11].set(9.0)
    scores = scores.at[0, 1, 3].set(-0.0)
    q_pos = jnp.asarray([10, 3])
    with select.forced_form(select_form):
        ours = np.asarray(keye_vl2.top_keys(scores, q_pos, 5))
        assert np.flatnonzero(ours[0, 0]).tolist() == [1, 2, 4, 5, 7]       # 8 and 9 tie and lose
        assert np.flatnonzero(ours[0, 1]).tolist() == [0, 1, 2, 3]          # all four causal keys
        assert np.flatnonzero(np.asarray(keye_vl2.top_keys(scores, q_pos, 3))[0, 1]).tolist() == [
            0, 1, 2]                                                     # -0.0 at 3 ties with 0.0 at 0
        for topk in (1, 3, 5, 12, 20):
            assert np.array_equal(np.asarray(keye_vl2.top_keys(scores, q_pos, topk)),
                                  np.asarray(reference._selected(scores, q_pos, topk))), topk


@pytest.mark.parametrize("form", ["xla", "kernel"])
def test_with_k_past_the_length_the_layer_is_dense_causal_attention(form):
    """``index-topk`` >= L: every query selects every key up to its own, the
    pairs ARE the causal triangle, and the layer's output is, to the last bit,
    what the same form gives under the triangle; it is also the reference's
    dense layer."""
    cfg = config(index_topk=LENGTH)
    params = seeded_params(topk=LENGTH)
    layer = one_layer(params)
    u = jax.random.normal(jax.random.PRNGKey(6), (2, LENGTH, 64))
    positions = keye_vl2.text_positions(LENGTH)
    pairs, _ = keye_vl2.select(u, layer, cfg, positions)
    triangle = np.tril(np.ones((LENGTH, LENGTH), np.int8))
    assert np.array_equal(np.asarray(pairs), np.broadcast_to(triangle, (2, LENGTH, LENGTH)))
    with forced_form(form):
        ours, _ = keye_vl2.sparse_attention(u, layer, cfg, positions)   # both op by op: no fusion differs
        q, k, v = keye_vl2.main_heads(u, layer, cfg, positions)
        dense = keye_vl2.attend(
            q, k, v, keye_vl2.Selected(LENGTH), lambda q, k, v: keye_vl2.chunked_attention(
                q, k, v, jnp.asarray(pairs), cfg), pairs=jnp.asarray(pairs)) @ layer["wo"]
    assert np.array_equal(np.asarray(ours), np.asarray(dense))
    theirs = reference._attention(u, layer, shape(topk=LENGTH), positions)
    np.testing.assert_allclose(np.asarray(ours), np.asarray(theirs), rtol=1e-4, atol=1e-5)
    sparse, _ = keye_vl2.sparse_attention(u, layer, config(), positions)
    assert float(jnp.max(jnp.abs(sparse - ours))) > 1e-3    # eight keys are not all of them


def test_no_gradient_reaches_the_indexer_and_the_indexer_reaches_the_loss():
    """Every indexer leaf's gradient is exactly zero (the selection is piecewise
    constant), yet each of the three projections, changed, changes which keys
    are read and so the loss."""
    experiment = models.instantiate("keye_vl2", arguments())
    params = seeded_params()
    batch = {"tokens": jnp.asarray(experiment.corpus[:2])}
    loss = jax.jit(lambda params: experiment.loss(params, batch)[0])
    grads = jax.jit(jax.grad(loss))(params)
    for name, leaf in grads["layers"][0].items():
        assert np.any(np.asarray(leaf)) != (name in INDEXER), name
    base = float(loss(params))
    for place, name in enumerate(("index_wq", "index_wk", "index_ww")):
        leaf = params["layers"][0][name]
        other = jax.random.normal(jax.random.PRNGKey(40 + place), leaf.shape) * jnp.std(leaf)
        changed = dict(params, layers=[dict(params["layers"][0], **{name: other})])
        assert abs(float(loss(changed)) - base) > 1e-6 * abs(base), name


def test_three_position_ids_turn_their_own_sections():
    """``rope`` with ``sections``: three equal ids are plain ``rope``'s result
    to the last bit; three unequal ids differ from it in the sections whose id
    differs and nowhere else, and are the reference's turn; through the main
    heads the model agrees with the reference's attention under an image
    grid's ids."""
    x = jax.random.normal(jax.random.PRNGKey(1), (2, LENGTH, 3, 16))
    inv_freq, sections = rope_frequencies(16, 1e7), (2, 3, 3)
    index = jnp.arange(LENGTH)
    plain = rope(x, index, inv_freq)
    assert np.array_equal(np.asarray(rope(x, jnp.stack([index] * 3), inv_freq, sections=sections)),
                          np.asarray(plain))
    grid = jnp.stack([index, index // 4, index % 4])     # temporal, height, width
    turned = np.asarray(rope(x, grid, inv_freq, sections=sections))
    assert np.array_equal(turned[..., :4], np.asarray(plain)[..., :4])   # the temporal section
    assert not np.allclose(turned[:, 9:, :, 4:10], np.asarray(plain)[:, 9:, :, 4:10])
    assert not np.allclose(turned[:, 9:, :, 10:], np.asarray(plain)[:, 9:, :, 10:])
    np.testing.assert_allclose(turned, np.asarray(reference._rope(
        x, reference._by_section(grid, sections), 1e7)), rtol=1e-5, atol=1e-6)
    cfg, layer = config(index_topk=LENGTH), one_layer(seeded_params())
    u = jax.random.normal(jax.random.PRNGKey(6), (1, LENGTH, 64))
    ours, _ = keye_vl2.sparse_attention(u, layer, cfg, grid)
    theirs = reference._attention(u, layer, shape(topk=LENGTH), grid)
    np.testing.assert_allclose(np.asarray(ours), np.asarray(theirs), rtol=1e-4, atol=1e-5)
    text, _ = keye_vl2.sparse_attention(u, layer, cfg, keye_vl2.text_positions(LENGTH))
    assert float(jnp.max(jnp.abs(text - ours))) > 1e-4


def test_the_shares_of_a_layer_add_up_to_the_whole():
    """The deployment's cut, on one layer: 32 experts over 16 shares of 2.
    Every share routes over all 32 and computes its own two; attention and the
    indexer (whole on every chip, every head reading the one selection) and
    the norms are COUNTED ONCE.  The sixteen shares add up to the uncut
    reference's layer.  Tolerance 1e-4 relative: float32 sums in another
    order."""
    cfg = config(experts=32)
    key = jax.random.PRNGKey(11)
    whole = one_layer(seeded_params(gain=15.0))
    dims = {"router": (64, 32), "we_gate": (32, 64, 24), "we_up": (32, 64, 24),
            "we_down": (32, 24, 64)}
    whole.update({name: 0.3 * jax.random.normal(jax.random.fold_in(key, place), dim)
                  for place, (name, dim) in enumerate(sorted(dims.items()))})
    x = jax.random.normal(jax.random.fold_in(key, 999), (1, LENGTH, 64))
    positions = keye_vl2.text_positions(LENGTH)
    uncut = reference._layer(x, whole, shape(held=tuple(range(32)), experts=32), positions)
    norm = lambda x, name: keye_vl2.rms_norm(x, whole[name], cfg.norm_eps)

    attended, _counts = keye_vl2.sparse_attention(norm(x, "attn_norm"), whole, cfg, positions)
    h = x + attended
    tokens, total, routed = norm(h, "mlp_norm"), h, 0.0
    for share in range(16):
        held = (2 * share, 2 * share + 1)
        part = dict(whole, **{name: whole[name][jnp.asarray(held)]
                              for name in ("we_gate", "we_up", "we_down")})
        out, count, _idle = jax.jit(keye_vl2.moe, static_argnums=2)(
            tokens, part, dataclasses.replace(cfg, experts_held=held))
        total, routed = total + out, routed + float(count)
    assert routed == LENGTH * cfg.experts_per_token  # every choice landed on one share
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut), rtol=1e-4, atol=1e-4)


def test_the_experiment_round_trips_its_arguments():
    """Every size handed in as ``key:value`` is the configuration's field, the
    defaults are the grid's configuration, and a key the model does not know,
    sections that do not fill a head, or a chunk that does not divide, fail."""
    experiment = models.instantiate("keye_vl2", arguments() + [
        "rope-theta:10000", "norm-eps:1e-5", "dtype:bfloat16"])
    cfg = experiment.cfg
    assert (cfg.vocab, cfg.hidden, cfg.heads, cfg.kv_heads, cfg.head_dim) == (VOCAB, 64, 8, 2, 16)
    assert (cfg.layers, cfg.experts, cfg.experts_per_token, cfg.expert_width) == (3, 16, 4, 24)
    assert (cfg.index_heads, cfg.index_head_dim, cfg.index_topk) == (4, 8, TOPK)
    assert (cfg.mrope_section, cfg.rope_theta, cfg.norm_eps) == ((2, 3, 3), 10000.0, 1e-5)
    assert cfg.experts_held == HELD and cfg.dtype == jnp.bfloat16
    assert (cfg.seq, cfg.attn_chunk, cfg.select_chunk, experiment.batch_size) == (LENGTH, 8, 16, 2)
    assert experiment.corpus.shape == (16, LENGTH + 1) and experiment.device_transform() is None
    grid = models.instantiate("keye_vl2", ["corpus:1"]).cfg
    assert grid == keye_vl2.KeyeVL2Config()
    assert (grid.heads, grid.kv_heads, grid.head_dim, grid.index_heads, grid.index_head_dim,
            grid.index_topk, grid.mrope_section, grid.rope_theta, grid.seq) == (
        32, 4, 128, 16, 64, 2048, (16, 24, 24), 1e7, 8192)
    for bad in ("window:512", "mrope-section:2,3,4", "kv-heads:3", "experts-held:16",
                "select-chunk:24", "index-topk:0"):
        others = [given for given in arguments() if given.split(":")[0] != bad.split(":")[0]]
        with pytest.raises(UserException):
            models.instantiate("keye_vl2", others + [bad])


def test_an_engine_step_under_the_averaged_median_matches_the_plain_loop():
    """Two scanned, device-sampled steps of ``RobustEngine`` under the averaged
    median at n = 3, f = 1 against the plain loop: restated stream, reference
    loss, plain rule, plain SGD (5e-3 of the parameters' move: float32 sums in
    another order through two steps; the indexer's leaves do not move at all).
    The five counters ride with the loss, and the model's parts make the
    second table of the compiled step."""
    from jax.flatten_util import ravel_pytree

    from aggregathor_tpu.obs import profiler

    experiment = models.instantiate("keye_vl2", arguments(batch=1))
    reference.init(jax.random.PRNGKey(0), shape(), VOCAB)
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
    for name in INDEXER:
        assert np.array_equal(np.asarray(state.params["layers"][0][name]),
                              np.asarray(params["layers"][0][name])), name
    for counter in ("routed_positions", "idle_held_experts", "selected_keys",
                    "selected_far_share", "live_tile_share"):
        assert metrics["model_counters"][counter].shape == (steps, n)
    by_part, _ = profiler.phase_table(multi.compiled_text(), profiler.MODEL_PREFIX)
    assert {"embed", "attention", "indexer", "select", "sparse_attend", "router", "experts",
            "head"} <= set(by_part.values())


def test_the_runner_trains_it_on_the_sampled_scanned_path():
    """``cli.runner`` builds the experiment, the engine and the device-sampled
    K-step trainer as it does for ``laguna``."""
    from aggregathor_tpu.cli import runner

    with jax.default_matmul_precision("default"):
        assert 0 == runner.main([
            "--experiment", "keye_vl2", "--experiment-args", *arguments(batch=1),
            "--aggregator", "averaged-median", "--nb-workers", "3", "--nb-decl-byz-workers", "1",
            "--max-step", "4", "--input-source", "device", "--unroll", "2"])


@pytest.mark.parametrize("fault", [None, "reversed", "no-relu"], ids=["sound", "reversed", "no-relu"])
def test_the_layer_check_script_sees_a_wrong_selection(fault):
    """scripts/keye_layer_check.py — one layer against the reference element by
    element, the chip's check of what norms cannot see — at its small size off
    the chip: the sound layer within every tolerance at both precisions, and
    each planted fault (the smallest index scores in place of the largest; the
    indexer's ReLU dropped) outside in the selection, the attention's output
    and its gradients, while the queries that choose nothing stay inside."""
    import sys

    scripts = os.path.join(os.path.dirname(GRID), "scripts")
    sys.path.insert(0, scripts)
    try:
        import keye_layer_check
    finally:
        sys.path.remove(scripts)
    sound = keye_vl2.index_scores
    with jax.default_matmul_precision("default"):
        rows, within = keye_layer_check.run_check(seed=1, fault=fault, tiny=True, emit=lambda _: None)
    sys.modules.pop("keye_layer_check", None)
    assert keye_vl2.index_scores is sound                # the fault went with the block
    assert [row["precision"] for row in rows] == ["default", "highest"] and within is (fault is None)
    for row in rows:
        assert row["selected_pairs"] == sum(min(t + 1, 16) for t in range(64))
        assert row["within"]["attn_unchosen"] and row["attn_unchosen"] <= 1e-5
        for name in ("flipped_share", "attn", "grads_attention"):
            assert row["within"][name] is (fault is None), (name, row[name])
        assert all(row["grads_by_leaf"][name] == 0.0 for name in keye_layer_check.INDEXER)


@SELECT_FORMS
def test_the_selection_is_made_once_a_layer_and_kept(select_form):
    """Under ``jax.checkpoint`` a layer's forward is computed again for its
    backward pass; the selection is not: its pairs are the one residual a layer
    keeps by name (``KEPT``), so the program of loss and gradient holds ONE sort
    (the scanned layers' forward body), not two — under the forced kernel no
    sort and ONE ``pallas_call`` named ``select_threshold``, in the forward scan
    and not in the backward one — and one stacked int8 (layers, B, L, L) residual
    between the forward scan and the backward one."""
    def primitives(jaxpr, found, scans=()):
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name == "pallas_call":
                name = str(eqn.params["name"])
            found.append((name, [v.aval for v in eqn.outvars], scans))
            inside = scans + (len(found),) if name == "scan" else scans
            for inner in jax.core.jaxprs_in_params(eqn.params):
                primitives(inner, found, inside)
        return found

    experiment = models.instantiate("keye_vl2", arguments())
    params = seeded_params()
    batch = {"tokens": jnp.asarray(experiment.corpus[:2])}
    with select.forced_form(select_form):
        found = primitives(jax.make_jaxpr(jax.grad(lambda p: experiment.loss(p, batch)[0]))(
            params).jaxpr, [])
    choosing = "sort" if select_form == "xla" else "select_threshold"
    assert sum(name == "sort" for name, _, _ in found) == (select_form == "xla")
    assert sum("select_threshold" in name for name, _, _ in found) == (select_form == "kernel")
    (chosen_in,) = [scans[0] for name, _, scans in found if choosing in name]
    (keeping,) = [place + 1 for place, (name, avals, _) in enumerate(found) if name == "scan"
                  and any(aval.shape == (3, 2, LENGTH, LENGTH) for aval in avals)]
    assert chosen_in == keeping                  # the forward scan, which keeps the pairs
    found = [(name, avals) for name, avals, _ in found]
    kept = [aval for name, avals in found if name == "scan" for aval in avals
            if aval.dtype == jnp.int8 and aval.shape[-2:] == (LENGTH, LENGTH)]   # not a chunk's
    assert [aval.shape for aval in kept] == [(3, 2, LENGTH, LENGTH)]
    assert keye_vl2.KEPT == "selected_pairs"
