"""models/qwen3_next.py against its plain reference (grid/references/qwen3_next.py),
at a tiny size on the CPU: hidden 64, 4 query heads over 2 key heads of 16 (a
quarter of a head turned by RoPE), a Gated DeltaNet of 2 key and 4 value heads of
16 in chunks of 8 or 16 positions, one period of three DeltaNet layers and a
full one, 16 experts of which 4 are held, 4 a token, and a shared expert.
Products run at ``highest`` precision, so what separates the two is the order
of float32 sums: a chunk's triangular system and one carried state against the
recurrence walked token by token; a chunk's softmax, or the kernel's tiles,
against one softmax over all the keys."""

import dataclasses
import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from aggregathor_tpu import gars, models
from aggregathor_tpu.gars.common import forced_tier, leaf_tier
from aggregathor_tpu.models import qwen3_next
from aggregathor_tpu.ops.attention import forced_form
from aggregathor_tpu.parallel import RobustEngine, make_mesh
from aggregathor_tpu.utils import UserException

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GRID = os.path.join(ROOT, "grid")


def grid_module(kind, name):
    spec = importlib.util.spec_from_file_location(
        "qwen3_next_test_%s_%s" % (kind, name.replace("-", "_")),
        os.path.join(GRID, kind, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


reference = grid_module("references", "qwen3_next")
feed = grid_module("references", "feed_device_tokens_causal")

HELD = (1, 4, 7, 12)
VOCAB, LENGTH, CHUNK = 50, 64, 16


def arguments(layers=4, held=HELD, batch=2, length=LENGTH, chunk=CHUNK):
    return ["vocab:%d" % VOCAB, "hidden:64", "layers:%d" % layers, "full-interval:4", "heads:4",
            "kv-heads:2", "head-dim:16", "key-heads:2", "value-heads:4", "key-dim:16",
            "value-dim:16", "chunk:%d" % chunk, "experts:16", "experts-per-token:4",
            "expert-width:24", "shared-width:24", "experts-held:" + ",".join(map(str, held)),
            "seq:%d" % length, "attn-chunk:16", "batch-size:%d" % batch, "corpus:16"]


def shape(layers=4, held=HELD, length=LENGTH):
    return {"sequence_length": length, "hidden_size": 64, "num_hidden_layers": layers,
            "full_attention_interval": 4, "num_attention_heads": 4, "num_key_value_heads": 2,
            "head_dim": 16, "partial_rotary_factor": 0.25, "rope_theta": 10000000,
            "linear_num_key_heads": 2, "linear_num_value_heads": 4, "linear_key_head_dim": 16,
            "linear_value_head_dim": 16, "linear_conv_kernel_dim": 4, "delta_chunk": CHUNK,
            "num_experts": 16, "num_experts_per_tok": 4, "moe_intermediate_size": 24,
            "shared_expert_intermediate_size": 24, "experts_held": list(held),
            "rms_norm_eps": 1e-6}


@pytest.fixture(autouse=True)
def highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


KEPT_AS_SEEDED = ("A_log", "dt_bias")


def seeded_params(layers=4, seed=3, gain=10.0, held=HELD):
    """The reference's own weights, the layers' matrices scaled up so that
    routing, attention, the gates and the decay's inputs are far from uniform,
    and every norm's leaf moved off its seeded zero or one."""
    params = reference.init(jax.random.PRNGKey(seed), shape(layers, held), VOCAB)
    key = jax.random.PRNGKey(seed + 1)
    moved = lambda name, leaf: leaf + 0.1 * jax.random.normal(
        jax.random.fold_in(key, sum(map(ord, name))), leaf.shape)
    params["final_norm"] = moved("final_norm", params["final_norm"])
    params["layers"] = [{name: moved(name, leaf) if name.endswith("norm") else
                         leaf if name in KEPT_AS_SEEDED else leaf * gain
                         for name, leaf in run.items()} for run in params["layers"]]
    return params


def rule_inputs(length, seed=0, batch=2, heads=4, dk=16, dv=16):
    """q and k L2-normalised, q scaled, g in (-1, 0), beta in (0, 1)."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda key, width: qwen3_next.l2_normalised(
        jax.random.normal(key, (batch, length, heads, width)))
    return (unit(keys[0], dk) * dk ** -0.5, unit(keys[1], dk),
            jax.random.normal(keys[2], (batch, length, heads, dv)),
            -jax.random.uniform(keys[3], (batch, length, heads), minval=0.01, maxval=1.0),
            jax.random.uniform(keys[4], (batch, length, heads), minval=0.05, maxval=0.95))


def test_experiment_and_reference_build_the_same_tree():
    experiment = models.instantiate("qwen3_next", arguments(layers=5))
    ours = experiment.init(jax.random.PRNGKey(3))
    theirs = reference.init(jax.random.PRNGKey(3), shape(layers=5), VOCAB)
    assert jax.tree.map(lambda a: a.shape, ours) == jax.tree.map(lambda a: a.shape, theirs)
    assert all(bool(jnp.all(a == b)) for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)))
    assert experiment.cfg.runs() == [("delta", 3), ("full", 1), ("delta", 1)]
    assert set(ours["layers"][0]) - set(ours["layers"][1]) == {
        "w_qkvz", "w_ba", "conv", "A_log", "dt_bias", "o_norm"}     # the kinds' leaves differ
    assert set(ours["layers"][1]) - set(ours["layers"][0]) == {"wq", "wk", "wv", "q_norm", "k_norm"}
    count = lambda tree: sum(int(np.prod(dims)) for dims in jax.tree.leaves(
        tree, is_leaf=lambda leaf: isinstance(leaf, tuple)))
    published = qwen3_next.leaf_shapes(qwen3_next.Qwen3NextConfig())   # the grid's configuration
    assert (count(published) == 3 * 33718464 + 27263488 + 4 * (29362176 + 4096) + 77791232 + 2048
            == 323677248)
    delta = ours["layers"][0]
    assert not np.any(np.asarray(delta["attn_norm"])) and np.all(np.asarray(delta["o_norm"]) == 1)
    decay = np.exp(-np.exp(np.asarray(delta["A_log"])) * np.log1p(np.exp(np.asarray(delta["dt_bias"]))))
    assert np.all((decay > 0.19) & (decay < 1)) and decay.shape == (3, 4)   # exp(-A dt)


@pytest.mark.parametrize("size", [1, 2, 8, 64])
def test_unit_lower_inverse(size):
    a = jnp.tril(jax.random.normal(jax.random.PRNGKey(size), (3, size, size)), -1) * size ** -0.5
    inverse = qwen3_next.unit_lower_inverse(a)
    np.testing.assert_allclose(inverse @ (jnp.eye(size) + a), jnp.broadcast_to(jnp.eye(size), a.shape),
                               atol=1e-4)
    assert not np.any(np.triu(np.asarray(inverse), 1))


@pytest.mark.parametrize("length,chunk", [(64, 16), (56, 16), (128, 8), (5, 8)],
                         ids=["whole-chunks", "ragged", "sixteen-chunks", "under-a-chunk"])
def test_the_chunked_form_is_the_recurrence(length, chunk):
    """``chunked_delta_rule`` against the reference's token-by-token scan on the
    same q, k, v, g, beta: the outputs, the last state, and the gradients of a
    seeded scalar of the outputs with respect to all five; a length that is not
    whole chunks is padded with positions that neither decay nor write."""
    inputs = rule_inputs(length, seed=length)
    weight = jax.random.normal(jax.random.PRNGKey(9), inputs[2].shape)
    ours = lambda *a: jnp.sum(qwen3_next.chunked_delta_rule(*a, chunk)[0] * weight)
    theirs = lambda *a: jnp.sum(reference._recurrence(*a) * weight)
    out, state = jax.jit(lambda *a: qwen3_next.chunked_delta_rule(*a, chunk))(*inputs)
    np.testing.assert_allclose(out, reference._recurrence(*inputs), atol=2e-5)
    grads = jax.jit(jax.grad(ours, argnums=(0, 1, 2, 3, 4)))(*inputs)
    ref_grads = jax.jit(jax.grad(theirs, argnums=(0, 1, 2, 3, 4)))(*inputs)
    for name, a, b in zip("q k v g beta".split(), grads, ref_grads):
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-4 * float(jnp.max(jnp.abs(b))), name
    # the last state by the recurrence's own equations, one more token that reads it
    q, k, v, g, beta = (jnp.pad(a, ((0, 0), (0, 1)) + ((0, 0),) * (a.ndim - 2)) for a in inputs)
    probe = jnp.ones_like(q[:, :1])
    read = reference._recurrence(q.at[:, -1:].set(probe), k, v, g, beta)[:, -1]
    np.testing.assert_allclose(jnp.einsum("bhkv->bhv", state), read, atol=2e-5)


@pytest.fixture
def layer_check():
    """scripts/gdn_layer_check.py as a module, for the test's duration."""
    scripts = os.path.join(ROOT, "scripts")
    sys.path.insert(0, scripts)
    try:
        import gdn_layer_check
        yield gdn_layer_check
    finally:
        sys.path.remove(scripts)
        sys.modules.pop("gdn_layer_check", None)


def test_a_state_carried_across_chunks_is_needed(layer_check):
    """With every chunk entered on an empty state (the planted ``no-carry``)
    the first chunk's outputs stand and every later chunk's are wrong."""
    inputs = rule_inputs(LENGTH, seed=5)
    sound = reference._recurrence(*inputs)
    with layer_check.planted("no-carry"):
        reset, _ = qwen3_next.chunked_delta_rule(*inputs, CHUNK)
    np.testing.assert_allclose(reset[:, :CHUNK], sound[:, :CHUNK], atol=2e-5)
    off = jnp.max(jnp.abs(reset - sound), axis=(0, 2, 3)) / jnp.max(jnp.abs(sound))
    by_chunk = off.reshape(LENGTH // CHUNK, CHUNK)[1:]
    assert float(jnp.min(by_chunk[:, 0])) > 0.1 and float(jnp.min(by_chunk)) > 1e-5


def test_the_convolution_is_causal():
    """Position t's output reads positions t - 3 .. t and nothing later, tap by
    tap as the reference's grouped convolution has it."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 6))
    taps = jax.random.normal(jax.random.PRNGKey(1), (6, 4))
    out = qwen3_next.causal_conv(x, taps)
    np.testing.assert_allclose(out, reference._causal_conv(x, taps), atol=1e-5)
    grouped = jax.lax.conv_general_dilated(      # each channel a group of its own
        x.swapaxes(1, 2), taps[:, None, :], window_strides=(1,), padding=[(3, 0)],
        feature_group_count=6, dimension_numbers=("NCH", "OIH", "NCH")).swapaxes(1, 2)
    np.testing.assert_allclose(out, grouped, atol=1e-5)
    np.testing.assert_allclose(out[:, 0], x[:, 0] * taps[:, 3], atol=1e-6)   # zeros before the sequence
    later = qwen3_next.causal_conv(x.at[:, 7].add(1.0), taps)
    changed = np.any(np.asarray(later != out), axis=(0, 2))
    assert list(np.nonzero(changed)[0]) == [7, 8, 9, 10]


@pytest.mark.parametrize("form", ["xla", "kernel"])
@pytest.mark.parametrize("layers,length", [(4, LENGTH), (5, 48)], ids=["a-period", "five-ragged"])
def test_loss_and_gradients_match_the_reference(layers, length, form):
    """The model (runs of stacked layers scanned, a quarter of the experts
    held, the chunked delta rule, the chunked softmax or the interpreted kernel)
    against the reference's plain loop over tokens.  Tolerance 2e-3 of each
    leaf's largest gradient entry: both sides are float32 at ``highest`` and
    differ by the order of their sums.  Every leaf gets a gradient."""
    experiment = models.instantiate("qwen3_next", arguments(layers, length=length))
    params = seeded_params(layers)
    reference.init(jax.random.PRNGKey(0), shape(layers, length=length), VOCAB)  # records the shape
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
        assert scale > 0, path
        assert float(jnp.max(jnp.abs(ours - theirs))) <= 2e-3 * scale, path
    assert float(counters["routed_positions"]) > 0
    assert 0.19 < float(counters["mean_decay"]) < 1 and float(counters["state_rms"]) > 0


def test_the_counters_read_the_decay_and_the_state():
    """``mean_decay`` is the mean of exp(g) over the DeltaNet layers, positions
    and heads; ``state_rms`` the sum over those layers of the RMS of the state
    the last position leaves — here by the recurrence's own equations."""
    cfg = models.instantiate("qwen3_next", arguments(layers=1, batch=1)).cfg
    params = seeded_params(layers=1)
    layer = {name: leaf[0] for name, leaf in params["layers"][0].items()}
    x = jax.random.normal(jax.random.PRNGKey(2), (1, LENGTH, 64))
    _, _, _, decay, state_rms = qwen3_next.decoder_layer(x, layer, cfg, qwen3_next.DELTA)
    u = qwen3_next.rms_norm(x, 1 + layer["attn_norm"], cfg.norm_eps)
    q, k, v, _, g, beta = qwen3_next.delta_heads(u, layer, cfg)
    assert float(decay) == pytest.approx(float(jnp.sum(jnp.exp(g))), rel=1e-5)
    state = jnp.zeros((1, 4, 16, 16))
    for t in range(LENGTH):
        state = state * jnp.exp(g[:, t])[..., None, None]
        write = beta[:, t][..., None] * (v[:, t] - jnp.einsum("bhkv,bhk->bhv", state, k[:, t]))
        state = state + k[:, t][..., :, None] * write[..., None, :]
    assert float(state_rms) == pytest.approx(float(jnp.sqrt(jnp.mean(state ** 2))), rel=1e-4)


def test_the_shares_of_an_expert_layer_add_up_to_the_whole():
    """The share test (the model-configs guide, section 4): four chips that
    hold four of the sixteen experts each compute, of ONE expert layer on the
    same tokens, parts that add up — the shared expert, which every chip
    computes alike, counted once — to what the uncut reference gives for the
    whole layer.  No token is lost: every position's four choices are counted
    by exactly one share each.  An expert no position reached gets a gradient
    of exact zeros."""
    cfg = models.instantiate("qwen3_next", arguments(layers=1)).cfg
    params = seeded_params(layers=1, held=tuple(range(16)))
    whole = {name: leaf[0] for name, leaf in params["layers"][0].items()}
    u = jax.random.normal(jax.random.PRNGKey(5), (2, LENGTH, 64))
    uncut = reference._moe(u, whole, dict(shape(), experts_held=list(range(16))))
    shared = jax.nn.sigmoid(u @ whole["shared_gate"]) * reference._unit(
        u, whole["ws_gate"], whole["ws_up"], whole["ws_down"])
    total, routed_in_all = jnp.zeros_like(u), 0.0
    for first in range(0, 16, 4):
        held = tuple(range(first, first + 4))
        mine = dict(whole, **{name: whole[name][first:first + 4]
                              for name in ("we_gate", "we_up", "we_down")})
        out, routed, _ = qwen3_next.sparse_ffn(u, mine, dataclasses.replace(cfg, experts_held=held))
        total, routed_in_all = total + (out - shared), routed_in_all + float(routed)
    np.testing.assert_allclose(total + shared, uncut, atol=2e-5)
    assert routed_in_all == 2 * LENGTH * 4                      # every choice, once
    _, chosen = qwen3_next.route(u.reshape(-1, 64), whole["router"], cfg)
    reached = set(np.unique(np.asarray(chosen[:8]))) | {0}       # eight positions' choices
    idle = [e for e in range(16) if e not in reached][:3] + [0]
    held_cfg = dataclasses.replace(cfg, experts_held=tuple(idle))
    mine = dict(whole, **{name: whole[name][jnp.asarray(idle)]
                          for name in ("we_gate", "we_up", "we_down")})
    few = u.reshape(-1, 64)[:8][None]
    grads = jax.grad(lambda layer: jnp.sum(qwen3_next.sparse_ffn(few, layer, held_cfg)[0]))(mine)
    _, _, idle_count = qwen3_next.sparse_ffn(few, mine, held_cfg)
    unreached = [slot for slot, e in enumerate(idle) if e not in set(np.asarray(chosen[:8]).ravel())]
    assert unreached and float(idle_count) == len(unreached)
    for slot in unreached:
        for name in ("we_gate", "we_up", "we_down"):
            assert not np.any(np.asarray(grads[name][slot])), (name, slot)


def test_the_experiment_round_trips_its_arguments():
    """Every size handed in as ``key:value`` is the configuration's field, the
    defaults are the grid's configuration, and a key the model does not know, a
    chunk that is no power of two, heads that do not divide, a rotary share
    that leaves no whole pairs, or an expert out of range, fail by name."""
    experiment = models.instantiate("qwen3_next", arguments() + [
        "rope-theta:10000", "norm-eps:1e-5", "rotary:0.5", "conv:3", "dtype:bfloat16"])
    cfg = experiment.cfg
    assert (cfg.vocab, cfg.hidden, cfg.layers, cfg.full_interval) == (VOCAB, 64, 4, 4)
    assert (cfg.heads, cfg.kv_heads, cfg.head_dim, cfg.rotary) == (4, 2, 16, 0.5)
    assert (cfg.key_heads, cfg.value_heads, cfg.key_dim, cfg.value_dim) == (2, 4, 16, 16)
    assert (cfg.conv, cfg.chunk, cfg.rope_theta, cfg.norm_eps) == (3, CHUNK, 10000.0, 1e-5)
    assert (cfg.experts, cfg.experts_per_token, cfg.expert_width, cfg.shared_width) == (16, 4, 24, 24)
    assert cfg.experts_held == HELD and cfg.dtype == jnp.bfloat16
    assert (cfg.seq, cfg.attn_chunk, experiment.batch_size) == (LENGTH, 16, 2)
    assert experiment.corpus.shape == (16, LENGTH + 1) and experiment.device_transform() is None
    grid = models.instantiate("qwen3_next", ["corpus:1"]).cfg
    assert grid == qwen3_next.Qwen3NextConfig()
    assert (grid.heads, grid.kv_heads, grid.head_dim, grid.key_heads, grid.value_heads, grid.key_dim,
            grid.value_dim, grid.conv, grid.chunk, grid.experts, grid.experts_per_token,
            grid.rope_theta, grid.seq) == (16, 2, 256, 16, 32, 128, 128, 4, 64, 512, 10, 1e7, 4096)
    assert grid.kinds() == ["delta", "delta", "delta", "full"]
    for bad in ("window:512", "chunk:24", "kv-heads:3", "value-heads:3", "rotary:0.3",
                "experts-held:16", "attn-chunk:24", "full-interval:0"):
        others = [given for given in arguments() if given.split(":")[0] != bad.split(":")[0]]
        with pytest.raises(UserException):
            models.instantiate("qwen3_next", others + [bad])


def test_an_engine_step_under_the_averaged_median_matches_the_plain_loop(capsys):
    """Two scanned, device-sampled steps of ``RobustEngine`` under the averaged
    median at n = 3, f = 1 against the plain loop: restated stream, reference
    loss (the recurrence token by token), plain rule, plain SGD (5e-3 of the
    parameters' move: float32 sums in another order through two steps).  The
    step reduces its gradients in place, the four counters ride with the loss,
    and the model's parts make the second table of the compiled step."""
    from jax.flatten_util import ravel_pytree

    from aggregathor_tpu.obs import profiler

    experiment = models.instantiate("qwen3_next", arguments(batch=1))
    reference.init(jax.random.PRNGKey(0), shape(), VOCAB)
    rule = grid_module("rules", "averaged-median")
    n, seed, rate, steps = 3, 23, 0.05, 2
    engine = RobustEngine(make_mesh(nb_workers=1, devices=jax.devices()[:1]),
                          gars.instantiate("averaged-median", n, 1), n)
    assert engine.gradient_path == "in place"
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
    for counter in ("routed_positions", "idle_held_experts", "mean_decay", "state_rms"):
        assert metrics["model_counters"][counter].shape == (steps, n)
    by_part, _ = profiler.phase_table(multi.compiled_text(), profiler.MODEL_PREFIX)
    assert {"embed", "gdn_project", "delta_rule", "attention_full", "router", "experts",
            "shared_expert", "head"} <= set(by_part.values())
    assert "step reduces gradients in place: 36 leaves" in capsys.readouterr().out


def test_the_runner_trains_it_on_the_sampled_scanned_path():
    """``cli.runner`` builds the experiment, the engine and the device-sampled
    K-step trainer as it does for ``laguna``."""
    from aggregathor_tpu.cli import runner

    with jax.default_matmul_precision("default"):
        assert 0 == runner.main([
            "--experiment", "qwen3_next", "--experiment-args", *arguments(batch=1),
            "--aggregator", "averaged-median", "--nb-workers", "3", "--nb-decl-byz-workers", "1",
            "--max-step", "4", "--input-source", "device", "--unroll", "2"])


@pytest.mark.parametrize("fault", [None, "no-decay", "beta-one", "no-carry", "no-gate"],
                         ids=["sound", "no-decay", "beta-one", "no-carry", "no-gate"])
def test_the_layer_check_script_sees_a_broken_layer(fault, layer_check):
    """scripts/gdn_layer_check.py — one layer of each kind against the reference
    element by element — at its small size off the chip: the sound layers within
    every tolerance at both precisions, and each planted fault outside in the
    layer it breaks (the delta rule's core, the layer's output and the mixer's
    gradients) while the other kind of layer stays inside."""
    sound = {name: getattr(qwen3_next, name)
             for name in ("delta_heads", "chunked_delta_rule", "attention_heads")}
    with jax.default_matmul_precision("default"):
        rows, within = layer_check.run_check(seed=1, fault=fault, tiny=True, emit=lambda _: None)
    assert all(getattr(qwen3_next, name) is whole for name, whole in sound.items())
    assert [(row["kind"], row["precision"]) for row in rows] == [
        ("delta", "default"), ("delta", "highest"), ("full", "default"), ("full", "highest")]
    assert within is (fault is None)
    broken = None if fault is None else "full" if fault == "no-gate" else "delta"
    for row in rows:
        for name in ("core", "out", "grads_mixer"):
            if name in row:
                assert row["within"][name] is (row["kind"] != broken), (row["kind"], name, row[name])


#: a gradient leaf of the grid's configuration, by name -> the tier that reduces it
#: where the kernels are forced (off a TPU nothing goes to a kernel)
KERNEL_LEAVES = ("embed", "head", "w_qkvz", "w_ba", "wo", "wq", "wk", "wv", "router", "ws_gate",
                 "ws_up", "ws_down", "we_gate", "we_up", "we_down")
JNP_LEAVES = ("final_norm", "attn_norm", "mlp_norm", "o_norm", "q_norm", "k_norm", "A_log",
              "dt_bias", "conv", "shared_gate")


def test_the_new_leaves_go_through_leaf_tier():
    """The gradient leaves of the grid's configuration, as the backward pass
    leaves them ((3 workers, layers of the run, ...)), by the tier
    ``gars.common.leaf_tier`` gives each (PERF.md section 3): the plane kernels'
    leaf entry for every matrix — (3, 3, 2048, 12288) the widest; ``w_ba``'s 64
    lanes with its rows handed over as lanes —, the rule's ``aggregate_block``
    on the flattened leaf for the norms, the 32-element gates and the
    convolution's (…, 8192, 4), whose four lanes hold no tile either way."""
    gar = gars.instantiate("averaged-median", 3, 1)
    shapes = qwen3_next.leaf_shapes(qwen3_next.Qwen3NextConfig())
    leaves = {name: dims for group in [shapes] + shapes["layers"] for name, dims in group.items()
              if name != "layers"}
    assert set(leaves) == set(KERNEL_LEAVES + JNP_LEAVES)
    stack = lambda dims: jax.ShapeDtypeStruct((3,) + dims, jnp.float32)
    assert {leaf_tier(gar, stack(dims)) for dims in leaves.values()} == {"jnp"}   # off a TPU
    with forced_tier("pallas"):
        tiers = {name: leaf_tier(gar, stack(dims)) for name, dims in leaves.items()}
    assert tiers == dict({name: "kernel" for name in KERNEL_LEAVES},
                         **{name: "jnp" for name in JNP_LEAVES})
    assert leaves["w_qkvz"] == (3, 2048, 12288) and leaves["conv"] == (3, 8192, 4)
    assert leaves["A_log"] == leaves["dt_bias"] == (3, 32)
