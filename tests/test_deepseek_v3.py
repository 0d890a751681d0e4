"""models/deepseek_v3.py against its plain reference
(grid/references/deepseek_v3.py), at a tiny size on the CPU: hidden 64, 4 of 8
heads held, scores over 16 + 8 and values of 16 through a latent of 24, one
dense layer and two sparse ones, 16 experts of which 4 are held, 4 a token, two
shared.  Products run at ``highest`` precision, so what separates the two is the
order of float32 sums (the running softmax by chunk, or the kernel's tiles,
against one softmax over all the keys)."""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from aggregathor_tpu import gars, models
from aggregathor_tpu.models import deepseek_v3
from aggregathor_tpu.ops.attention import forced_form
from aggregathor_tpu.parallel import RobustEngine, make_mesh
from aggregathor_tpu.utils import UserException

GRID = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "grid")


def grid_module(kind, name):
    spec = importlib.util.spec_from_file_location(
        "deepseek_test_%s_%s" % (kind, name.replace("-", "_")), os.path.join(GRID, kind, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


reference = grid_module("references", "deepseek_v3")
feed = grid_module("references", "feed_device_tokens_causal")

HELD = (1, 4, 7, 12)
VOCAB, LENGTH = 50, 32


def arguments(layers=3, dense=1, held=HELD, heads_held=4, batch=2):
    return ["vocab:%d" % VOCAB, "hidden:64", "heads:8", "heads-held:%d" % heads_held,
            "qk-nope-head-dim:16", "qk-rope-head-dim:8", "v-head-dim:16", "kv-lora-rank:24",
            "layers:%d" % layers, "first-k-dense-replace:%d" % dense, "dense-width:96",
            "experts:16", "experts-per-token:4", "expert-width:24", "n-shared-experts:2",
            "experts-held:" + ",".join(map(str, held)), "seq:%d" % LENGTH, "attn-chunk:8",
            "batch-size:%d" % batch, "corpus:16"]


def shape(layers=3, dense=1, held=HELD, heads_held=4, experts=16, per_token=4):
    return {"sequence_length": LENGTH, "hidden_size": 64, "num_attention_heads": heads_held,
            "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16, "kv_lora_rank": 24,
            "q_lora_rank": None, "num_hidden_layers": layers, "first_k_dense_replace": dense,
            "intermediate_size": 96, "n_routed_experts": experts, "num_experts_per_tok": per_token,
            "moe_intermediate_size": 24, "n_shared_experts": 2, "routed_scaling_factor": 2.448,
            "experts_held": list(held), "rope_theta": 1000000, "rms_norm_eps": 1e-6}


@pytest.fixture(autouse=True)
def highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


def seeded_params(layers=3, dense=1, seed=3, gain=10.0):
    """The reference's own weights, the layers' matrices (and the router's
    bias) scaled up so that routing and attention are far from uniform."""
    params = reference.init(jax.random.PRNGKey(seed), shape(layers, dense), VOCAB)
    params["layers"] = [{name: leaf if name.endswith("norm") else leaf * gain
                         for name, leaf in run.items()} for run in params["layers"]]
    return params


def test_experiment_and_reference_build_the_same_tree():
    experiment = models.instantiate("deepseek_v3", arguments())
    ours = experiment.init(jax.random.PRNGKey(3))
    theirs = reference.init(jax.random.PRNGKey(3), shape(), VOCAB)
    assert jax.tree.map(lambda a: a.shape, ours) == jax.tree.map(lambda a: a.shape, theirs)
    assert all(bool(jnp.all(a == b)) for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)))
    assert experiment.cfg.runs() == [("dense", 1), ("sparse", 2)]
    count = lambda cfg: sum(int(np.prod(dims)) for dims in jax.tree.leaves(
        deepseek_v3.leaf_shapes(cfg), is_leaf=lambda leaf: isinstance(leaf, tuple)))
    published = deepseek_v3.DeepseekV3Config()        # the grid's configuration
    assert count(published) == 362045952 + 4 * 128    # the issue's count, and four layers' biases
    assert count(dataclasses.replace(published, heads_held=32)) == 424960512 + 4 * 128
    assert float(jnp.max(jnp.abs(ours["layers"][1]["router_bias"]))) > 0  # seeded, not zero


@pytest.mark.parametrize("form", ["xla", "kernel"])
@pytest.mark.parametrize("layers,dense", [(3, 1), (2, 0), (3, 2)],
                         ids=["published", "no-dense", "two-dense"])
def test_loss_and_gradients_match_the_reference(layers, dense, form):
    """The model (runs stacked and scanned, half the heads and a quarter of the
    experts held, the chunked softmax or the interpreted kernel by padding)
    against the reference's plain loop, at the published pattern and at two
    others.  Tolerance 2e-3 of each leaf's largest gradient entry: both sides
    are float32 at ``highest`` and differ by the order of their sums (read
    6e-6 at worst).  The bias's gradient is exactly zero on both sides."""
    experiment = models.instantiate("deepseek_v3", arguments(layers, dense))
    params = seeded_params(layers, dense)
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
        if "router_bias" in jax.tree_util.keystr(path):
            assert scale == 0.0 and not np.any(np.asarray(ours)), path
            continue
        assert scale > 0, path
        assert float(jnp.max(jnp.abs(ours - theirs))) <= 2e-3 * scale, path
    assert float(counters["routed_positions"]) > 0
    assert 0 < float(counters["bias_changed_positions"]) <= 2 * LENGTH * (layers - dense)


def one_layer(key, heads=8, experts=32, scale=0.3):
    """A whole sparse layer's leaves (every head, every expert) for hidden 64."""
    dims = {"wq": (64, heads * 24), "wkv_a": (64, 24 + 8), "wkv_b": (24, heads * 32),
            "wo": (heads * 16, 64), "router": (64, experts), "router_bias": (experts,),
            "we_gate": (experts, 64, 24), "we_up": (experts, 64, 24), "we_down": (experts, 24, 64),
            "ws_gate": (64, 48), "ws_up": (64, 48), "ws_down": (48, 64)}
    layer = {name: scale * jax.random.normal(jax.random.fold_in(key, place), dim)
             for place, (name, dim) in enumerate(sorted(dims.items()))}
    layer["router"] = layer["router"] * 5  # scores far from one half
    for place, name in enumerate(("attn_norm", "mlp_norm", "kv_norm")):
        layer[name] = 1.0 + 0.1 * jax.random.normal(jax.random.fold_in(key, 100 + place),
                                                     (24 if name == "kv_norm" else 64,))
    return layer


def test_the_shares_of_a_layer_add_up_to_the_whole():
    """The deployment's cut, on one layer: 32 experts over 16 shares of 2, 8
    heads over 2 shares of 4.  Each attention share holds its heads' columns of
    ``W_q`` and ``W_kvb`` and rows of ``W_o`` and gives a partial sum through
    ``W_o``; each expert share routes over all 32 under the same bias and
    computes its own two.  The 2 head shares, then the 16 expert shares with
    the shared unit, ``W_kva`` and the norms COUNTED ONCE, add up to the uncut
    reference's layer.  Tolerance 1e-4 relative: float32 sums in another order."""
    cfg = deepseek_v3.DeepseekV3Config(
        hidden=64, heads=8, heads_held=4, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        kv_lora_rank=24, experts=32, experts_per_token=4, expert_width=24, seq=LENGTH,
        attn_chunk=8)
    key = jax.random.PRNGKey(11)
    whole = one_layer(key)
    x = jax.random.normal(jax.random.fold_in(key, 999), (1, LENGTH, 64))
    uncut = reference._layer(x, whole, False, shape(heads_held=8, experts=32,
                                                     held=tuple(range(32))))
    norm = lambda x, name: deepseek_v3.rms_norm(x, whole[name], cfg.norm_eps)

    u, attended = norm(x, "attn_norm"), 0.0
    for share in range(2):
        heads = np.arange(4 * share, 4 * share + 4)
        columns = lambda width: (heads[:, None] * width + np.arange(width)).reshape(-1)
        part = dict(whole, wq=whole["wq"][:, columns(24)], wkv_b=whole["wkv_b"][:, columns(32)],
                    wo=whole["wo"][columns(16)])
        attended = attended + deepseek_v3.latent_attention(u, part, cfg)
    h = x + attended

    tokens = norm(h, "mlp_norm")
    shared = deepseek_v3.gated_unit(tokens, whole, "ws", jnp.float32)
    total, routed = h + shared, 0.0
    for share in range(16):
        held = (2 * share, 2 * share + 1)
        part = dict(whole, **{name: whole[name][jnp.asarray(held)]
                              for name in ("we_gate", "we_up", "we_down")})
        out, count, _idle, _changed = jax.jit(deepseek_v3.sparse_ffn, static_argnums=2)(
            tokens, part, dataclasses.replace(cfg, experts_held=held))
        total, routed = total + (out - shared), routed + float(count)
    assert routed == LENGTH * cfg.experts_per_token  # every choice landed on one share
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut), rtol=1e-4, atol=1e-4)


def test_the_bias_moves_the_choice_and_not_the_weights():
    """With the same scores, a bias changes WHICH experts a position takes; the
    weights are the scores at the chosen experts over their sum, whatever the
    bias; the counter is the number of positions whose set changed; and the
    bias's gradient through the layer is exactly zero."""
    cfg = deepseek_v3.DeepseekV3Config(hidden=64, experts=16, experts_per_token=4, expert_width=24,
                                       experts_held=HELD)
    key = jax.random.PRNGKey(7)
    tokens = jax.random.normal(jax.random.fold_in(key, 0), (LENGTH, 64))
    router = 0.3 * jax.random.normal(jax.random.fold_in(key, 1), (64, 16))
    bias = jnp.zeros((16,)).at[5].set(10.0).at[9].set(-10.0)  # 5 always chosen, 9 never
    scores = np.asarray(jax.nn.sigmoid(tokens @ router))
    weights, chosen, changed = deepseek_v3.route(tokens, router, bias, cfg)
    plain_w, plain_e, unchanged = deepseek_v3.route(tokens, router, jnp.zeros((16,)), cfg)
    chosen, plain_e = np.asarray(chosen), np.asarray(plain_e)
    assert float(unchanged) == 0.0
    assert (chosen == 5).any(axis=-1).all() and not (chosen == 9).any()
    differs = [set(a) != set(b) for a, b in zip(chosen, plain_e)]
    assert float(changed) == sum(differs) > 0
    at_chosen = np.take_along_axis(scores, chosen, axis=-1)
    np.testing.assert_allclose(np.asarray(weights), at_chosen / at_chosen.sum(-1, keepdims=True),
                               rtol=1e-6)
    assert np.all(np.asarray(weights) < 1.0)  # scores lie under one: no bias's 10 in any weight
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 1.0, rtol=1e-6)
    # the reference's router makes the same choice and the same weights
    ref_w, ref_e = reference._route(tokens, {"router": router, "router_bias": bias},
                                    {"num_experts_per_tok": 4})
    assert np.array_equal(np.sort(np.asarray(ref_e), -1), np.sort(chosen, -1))
    np.testing.assert_allclose(np.sort(np.asarray(ref_w), -1), np.sort(np.asarray(weights), -1),
                               rtol=1e-6)

    layer = {name: leaf[0] for name, leaf in seeded_params()["layers"][1].items()}
    layer = dict(layer, router=router, router_bias=bias)

    def summed(layer):
        out, *_counts = deepseek_v3.sparse_ffn(tokens[None], layer, cfg)
        return jnp.sum(out * out)

    grads = jax.grad(summed)(layer)
    assert not np.any(np.asarray(grads["router_bias"]))
    assert np.any(np.asarray(grads["router"])) and np.any(np.asarray(grads["ws_gate"]))


def test_the_rotary_key_is_one_head_shared_by_all():
    """Every head's key ends in the SAME 8 rotary dims (one ``k_pe`` from
    ``W_kva``, turned once), its first 16 are the head's own; a query's rotary
    part is its head's own; position 0 is not turned, later ones are."""
    cfg = deepseek_v3.DeepseekV3Config(
        hidden=64, heads=8, heads_held=4, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        kv_lora_rank=24, seq=LENGTH, attn_chunk=8)
    layer = one_layer(jax.random.PRNGKey(5), heads=4)
    u = jax.random.normal(jax.random.PRNGKey(6), (2, LENGTH, 64))
    q, k, v = deepseek_v3.latent_heads(u, layer, cfg)
    assert q.shape == (2, LENGTH, 4, 1, 24) and k.shape == (2, LENGTH, 4, 24)
    assert v.shape == (2, LENGTH, 4, 16)
    k = np.asarray(k)
    for head in range(1, 4):
        assert np.array_equal(k[:, :, head, 16:], k[:, :, 0, 16:])
        assert not np.array_equal(k[:, :, head, :16], k[:, :, 0, :16])
    raw = np.asarray(u @ layer["wkv_a"])[..., 24:]
    np.testing.assert_allclose(k[:, 0, 0, 16:], raw[:, 0], rtol=1e-6)  # position 0: no turn
    assert not np.allclose(k[:, 5, 0, 16:], raw[:, 5])
    np.testing.assert_allclose(np.linalg.norm(k[:, 5, 0, 16:], axis=-1),
                               np.linalg.norm(raw[:, 5], axis=-1), rtol=1e-5)  # a rotation
    q = np.asarray(q)[:, :, :, 0]
    assert not np.array_equal(q[:, :, 1, 16:], q[:, :, 0, 16:])
    # the reference turns the same pairs by the same angles
    np.testing.assert_allclose(k[:, :, :1, 16:], np.asarray(reference._rope(
        jnp.asarray(raw)[:, :, None, :], 1000000)), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("form", ["xla", "kernel"])
def test_latent_attention_reads_no_later_position_and_turns_by_theta(form):
    """A later token moves no earlier output; an earlier one does; and another
    rotary base changes the output (the rotary part reaches the scores)."""
    cfg = deepseek_v3.DeepseekV3Config(
        hidden=64, heads=8, heads_held=4, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        kv_lora_rank=24, seq=LENGTH, attn_chunk=8)
    layer = one_layer(jax.random.PRNGKey(5), heads=4)
    u = jax.random.normal(jax.random.PRNGKey(6), (1, LENGTH, 64))
    with forced_form(form):
        attend = jax.jit(lambda u, cfg=cfg: deepseek_v3.latent_attention(u, layer, cfg))
        other = jax.jit(lambda u: deepseek_v3.latent_attention(
            u, layer, dataclasses.replace(cfg, rope_theta=1e4)))
        query = LENGTH - 5
        base = attend(u)
        moved = lambda out: float(jnp.max(jnp.abs(out[0, query] - base[0, query])))
        assert moved(attend(u.at[0, query + 1].add(1.0))) == 0.0
        assert moved(attend(u.at[0, query - 3].add(1.0))) > 1e-4
        assert moved(other(u)) > 1e-4
    assert base.shape == (1, LENGTH, 64)


def test_a_query_lora_rank_fails_by_name():
    with pytest.raises(UserException, match="q_lora_rank"):
        models.instantiate("deepseek_v3", arguments() + ["q-lora-rank:1536"])
    with pytest.raises(SystemExit, match="q_lora_rank"):
        reference.init(jax.random.PRNGKey(0), dict(shape(), q_lora_rank=1536), VOCAB)
    models.instantiate("deepseek_v3", arguments() + ["q-lora-rank:null"])


def test_the_experiment_round_trips_its_arguments():
    """Every size handed in as ``key:value`` is the configuration's field, the
    defaults are the grid's configuration, and a key the model does not know, a
    head count or a layer count that cannot be, fail."""
    experiment = models.instantiate("deepseek_v3", arguments() + [
        "routed-scaling-factor:1.5", "rope-theta:10000", "norm-eps:1e-5", "dtype:bfloat16"])
    cfg = experiment.cfg
    assert (cfg.vocab, cfg.hidden, cfg.heads, cfg.heads_held) == (VOCAB, 64, 8, 4)
    assert (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank) == (
        16, 8, 16, 24)
    assert (cfg.layers, cfg.first_k_dense_replace, cfg.dense_width) == (3, 1, 96)
    assert (cfg.experts, cfg.experts_per_token, cfg.expert_width, cfg.n_shared_experts) == (
        16, 4, 24, 2)
    assert (cfg.routed_scaling_factor, cfg.rope_theta, cfg.norm_eps) == (1.5, 10000.0, 1e-5)
    assert cfg.experts_held == HELD and cfg.dtype == jnp.bfloat16
    assert (cfg.seq, cfg.attn_chunk, experiment.batch_size) == (LENGTH, 8, 2)
    assert experiment.corpus.shape == (16, LENGTH + 1) and experiment.device_transform() is None
    grid = models.instantiate("deepseek_v3", ["corpus:1"]).cfg
    assert grid == deepseek_v3.DeepseekV3Config()
    assert (grid.heads_held, grid.qk_nope_head_dim + grid.qk_rope_head_dim, grid.v_head_dim,
            grid.kv_lora_rank, grid.experts_per_token, grid.routed_scaling_factor) == (
        16, 192, 128, 512, 6, 2.448)
    for bad in ("window:512", "heads-held:9", "first-k-dense-replace:4", "experts-held:16",
                "qk-rope-head-dim:7"):
        others = [given for given in arguments() if given.split(":")[0] != bad.split(":")[0]]
        with pytest.raises(UserException):
            models.instantiate("deepseek_v3", others + [bad])


def test_an_engine_step_under_the_averaged_median_matches_the_plain_loop():
    """Two scanned, device-sampled steps of ``RobustEngine`` under the averaged
    median at n = 3, f = 1 against the plain loop: restated stream, reference
    loss, plain rule, plain SGD (5e-3 of the parameters' move: float32 sums in
    another order through two steps; the bias's leaf does not move at all).
    The three counters ride with the loss, and the model's parts make the
    second table of the compiled step."""
    from jax.flatten_util import ravel_pytree

    from aggregathor_tpu.obs import profiler

    experiment = models.instantiate("deepseek_v3", arguments(batch=1))
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
    assert np.array_equal(np.asarray(state.params["layers"][1]["router_bias"]),
                          np.asarray(params["layers"][1]["router_bias"]))
    for counter in ("routed_positions", "idle_held_experts", "bias_changed_positions"):
        assert metrics["model_counters"][counter].shape == (steps, n)
    by_part, _ = profiler.phase_table(multi.compiled_text(), profiler.MODEL_PREFIX)
    assert {"embed", "mla_project", "mla_attend", "dense_mlp", "router", "experts",
            "shared_expert", "head"} <= set(by_part.values())


def test_the_runner_trains_it_on_the_sampled_scanned_path():
    """``cli.runner`` builds the experiment, the engine and the device-sampled
    K-step trainer as it does for ``laguna``."""
    from aggregathor_tpu.cli import runner

    with jax.default_matmul_precision("default"):
        assert 0 == runner.main([
            "--experiment", "deepseek_v3", "--experiment-args", *arguments(batch=1),
            "--aggregator", "averaged-median", "--nb-workers", "3", "--nb-decl-byz-workers", "1",
            "--max-step", "4", "--input-source", "device", "--unroll", "2"])
