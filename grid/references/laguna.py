"""Plain reference of Laguna (``model_type: laguna``, source
https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json) trained on
the next token, as one chip's share of an expert-parallel layer: ``jax.numpy``,
float32, a Python loop over the layers, each mask a boolean (L, L) matrix, the
RoPE tables from their formulas, a dense one-hot dispatch over the held
experts.  No running softmax, no stacked scan of layers, nothing of the
program's.

The config drives the model by three lists, one entry a layer: ``layer_types``
(``full_attention`` or ``sliding_attention``), ``mlp_layer_types`` (``dense`` or
``sparse``), ``num_attention_heads_per_layer``.  No bias anywhere, eps 1e-6:

    h = x + Attn_l(RMSNorm(x)),  y = h + FFN_l(RMSNorm(h))

    Attn_l: H_l query heads, kv key/value heads of head_dim; q = u W_q, k = u W_k,
      v = u W_v; key/value head j serves the query heads j * H_l/kv .. (j + 1) *
      H_l/kv - 1.  RoPE on q and k by the layer's kind (``rope_parameters``):
        full layer: the first head_dim * partial_rotary_factor dims of each head
          turn, the others pass.  Frequencies by YaRN: with w the turned width,
          e_i = theta^(-2i/w) and c(t) = w ln(original / (2 pi t)) / (2 ln theta),
          low = floor(c(beta_fast)), high = ceil(c(beta_slow)), ramp_i =
          clip((i - low) / (high - low), 0, 1): inv_freq_i = e_i / factor *
          ramp_i + e_i * (1 - ramp_i); cos and sin times attention_factor.
        sliding layer: the whole head, inv_freq_i = theta^(-2i/head_dim).
      softmax(q.k / sqrt(head_dim)) where query i reads key j iff j <= i and, on
      a sliding layer, i - j < sliding_window; W_o on the concatenated heads.
    FFN_l dense: W_down(silu(W_gate u) * W_up u), width intermediate_size.
    FFN_l sparse: shared(u), the same unit of shared_expert_intermediate_size,
      + moe_routed_scaling_factor * sum over e in S(u) THAT ARE HELD HERE of
      w_e expert_e(u); s = sigmoid(W_r u) over all the experts, S the
      num_experts_per_tok largest, w_e = s_e / sum_{e in S} s_e.  What the absent
      experts would add is left out, here as in the program (the deployment's
      exchange would add it; one chip has none).
    head: logits = RMSNorm(x) W_head; loss = mean over the positions of
      -log softmax(logits_i)[target_i].

``init(key, shape, vocabulary)`` is handed the configuration's family shape
(its ``image_size`` mapping) and keeps it for ``loss``, whose signature has no
room for it; ``inputs`` are a row's first L ids and ``targets`` its last L, as
grid/references/feed_device_tokens_causal.py makes them.  The parameters:
``embed``, ``head``, ``final_norm`` and ``layers``, a list with one entry a run
of consecutive layers alike in all three lists, each leaf of a run stacked on a
leading axis (the program's layout; this file indexes it layer by layer).

Departures from a literal transcription, both for memory (check.py puts this
under ``jax.value_and_grad`` beside 4.7 GB of rows): each layer is under
``jax.checkpoint``, and attention takes the queries 512 at a time, one block
after another (``lax.map``: a Python loop lets the compiler hold every block's
(heads, 512, L) scores at once, 10 GB a layer at 64 heads), each block
checkpointed too (its softmax is still over all L keys at once).  RoPE turns
the pairs (2i, 2i + 1) of a head's turned dims, the program's convention,
which is the half-split form of the published code under a fixed permutation
of those dims — the same model on seeded random weights.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

INIT_STD = 0.02
QUERY_BLOCK = 512

_SHAPE = {}


def _runs(shape):
    """[[layer, ...]]: consecutive layers alike in the three lists."""
    kinds = list(zip(shape["layer_types"], shape["mlp_layer_types"],
                     shape["num_attention_heads_per_layer"]))
    runs = []
    for index, kind in enumerate(kinds):
        if runs and kinds[runs[-1][-1]] == kind:
            runs[-1].append(index)
        else:
            runs.append([index])
    return runs


def _run_shapes(shape, first, count):
    d, dh, kv = shape["hidden_size"], shape["head_dim"], shape["num_key_value_heads"]
    q = shape["num_attention_heads_per_layer"][first] * dh
    dims = {"attn_norm": (d,), "mlp_norm": (d,), "wq": (d, q), "wk": (d, kv * dh),
            "wv": (d, kv * dh), "wo": (q, d)}
    if shape["mlp_layer_types"][first] == "dense":
        width = shape["intermediate_size"]
        dims.update({"w_gate": (d, width), "w_up": (d, width), "w_down": (width, d)})
    else:
        held, width = len(shape["experts_held"]), shape["moe_intermediate_size"]
        shared = shape["shared_expert_intermediate_size"]
        dims.update({"router": (d, shape["num_experts"]),
                     "we_gate": (held, d, width), "we_up": (held, d, width),
                     "we_down": (held, width, d),
                     "ws_gate": (d, shared), "ws_up": (d, shared), "ws_down": (shared, d)})
    return {name: (count,) + dim for name, dim in dims.items()}


def init(key, shape, vocabulary):
    """Norm scales at one, matrices N(0, 0.02^2), each from ``fold_in(key, its
    place)``: the top-level leaves by sorted name, then run after run, each
    run's by sorted name.  Records ``shape`` for ``loss``."""
    _SHAPE.clear()
    _SHAPE.update(shape)
    d = shape["hidden_size"]
    groups = [{"embed": (vocabulary, d), "head": (d, vocabulary), "final_norm": (d,)}]
    groups += [_run_shapes(shape, run[0], len(run)) for run in _runs(shape)]
    place, made = 0, []
    for group in groups:
        made.append({})
        for name, dims in sorted(group.items()):
            made[-1][name] = (jnp.ones(dims, jnp.float32) if name.endswith("norm") else INIT_STD
                              * jax.random.normal(jax.random.fold_in(key, place), dims, jnp.float32))
            place += 1
    return dict(made[0], layers=made[1:])


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope_table(parameters, head_dim):
    """(inverse frequencies of the turned pairs, the factor on cos and sin)."""
    width = int(head_dim * parameters.get("partial_rotary_factor", 1))
    theta = parameters["rope_theta"]
    plain = theta ** (-np.arange(0, width, 2) / width)
    if parameters["rope_type"] == "default":
        return plain, 1.0
    if parameters["rope_type"] != "yarn":
        raise SystemExit("references.laguna: rope_type %r is neither default nor yarn"
                         % parameters["rope_type"])
    original = parameters["original_max_position_embeddings"]
    turns_at = lambda turns: (width * math.log(original / (turns * 2 * math.pi))
                              / (2 * math.log(theta)))
    low = max(math.floor(turns_at(parameters["beta_fast"])), 0)
    high = min(math.ceil(turns_at(parameters["beta_slow"])), width - 1)
    ramp = np.clip((np.arange(width // 2) - low) / (high - low if high > low else 0.001), 0, 1)
    return (plain / parameters["factor"] * ramp + plain * (1 - ramp),
            parameters["attention_factor"])


def _rope(x, parameters):
    """x (B, L, H, Dh): pair (2i, 2i + 1) of the turned dims by position *
    inv_freq_i, cos and sin scaled; the other dims pass."""
    inv_freq, factor = rope_table(parameters, x.shape[-1])
    width = 2 * len(inv_freq)
    angles = (jnp.arange(x.shape[1], dtype=jnp.float32)[:, None]
              * jnp.asarray(inv_freq, jnp.float32)[None, :])
    cos, sin = factor * jnp.cos(angles)[None, :, None, :], factor * jnp.sin(angles)[None, :, None, :]
    even, odd = x[..., 0:width:2], x[..., 1:width:2]
    turned = jnp.stack([even * cos - odd * sin, even * sin + odd * cos], axis=-1)
    return jnp.concatenate([turned.reshape(x.shape[:-1] + (width,)), x[..., width:]], axis=-1)


def causal_mask(length, window=None):
    """(L, L) booleans: j <= i, and inside a window also i - j < window."""
    back = jnp.arange(length)[:, None] - jnp.arange(length)[None, :]
    return back >= 0 if window is None else (back >= 0) & (back < window)


def _attention(u, layer, index, s):
    b, length, _ = u.shape
    heads, kv, dh = s["num_attention_heads_per_layer"][index], s["num_key_value_heads"], s["head_dim"]
    sliding = s["layer_types"][index] == "sliding_attention"
    parameters = s["rope_parameters"]["sliding_attention" if sliding else "full_attention"]
    mask = causal_mask(length, s["sliding_window"] if sliding else None)
    q = _rope((u @ layer["wq"]).reshape(b, length, heads, dh), parameters)
    k = _rope((u @ layer["wk"]).reshape(b, length, kv, dh), parameters)
    v = (u @ layer["wv"]).reshape(b, length, kv, dh)
    k, v = jnp.repeat(k, heads // kv, axis=2), jnp.repeat(v, heads // kv, axis=2)

    @jax.checkpoint
    def some_queries(q_block, mask_block):
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_block, k) / math.sqrt(dh)
        weights = jax.nn.softmax(jnp.where(mask_block[None, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", weights, v)

    block = min(QUERY_BLOCK, length)
    out = jax.lax.map(lambda one: some_queries(*one), (
        q.reshape(b, length // block, block, heads, dh).swapaxes(0, 1),
        mask.reshape(length // block, block, length)))
    out = out.swapaxes(0, 1)
    return out.reshape(b, length, heads * dh) @ layer["wo"]


def _unit(u, gate, up, down):
    return (jax.nn.silu(u @ gate) * (u @ up)) @ down


def _sparse(u, layer, s):
    scores = jax.nn.sigmoid(u @ layer["router"])
    top_s, top_e = jax.lax.top_k(scores, s["num_experts_per_tok"])
    weights = top_s / jnp.sum(top_s, axis=-1, keepdims=True)
    routed = jnp.zeros_like(u)
    for slot, expert in enumerate(s["experts_held"]):
        mine = jnp.sum(jnp.where(top_e == expert, weights, 0.0), axis=-1)
        routed = routed + mine[..., None] * _unit(
            u, layer["we_gate"][slot], layer["we_up"][slot], layer["we_down"][slot])
    shared = _unit(u, layer["ws_gate"], layer["ws_up"], layer["ws_down"])
    return shared + s["moe_routed_scaling_factor"] * routed


def _layer(x, layer, index, s):
    h = x + _attention(_rms_norm(x, layer["attn_norm"], s["rms_norm_eps"]), layer, index, s)
    u = _rms_norm(h, layer["mlp_norm"], s["rms_norm_eps"])
    if s["mlp_layer_types"][index] == "dense":
        return h + _unit(u, layer["w_gate"], layer["w_up"], layer["w_down"])
    return h + _sparse(u, layer, s)


def loss(params, inputs, targets):
    s = _SHAPE
    if not s:
        raise RuntimeError("references.laguna: init(key, shape, vocabulary) records the "
                           "family's shape and has to be called before loss")
    x = params["embed"][inputs]
    for run, group in zip(_runs(s), params["layers"]):
        for place, index in enumerate(run):
            x = jax.checkpoint(lambda x, group, place=place, index=index: _layer(
                x, {name: leaf[place] for name, leaf in group.items()}, index, s))(x, group)
    logp = jax.nn.log_softmax(_rms_norm(x, params["final_norm"], s["rms_norm_eps"])
                              @ params["head"], axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))
