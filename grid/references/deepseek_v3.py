"""Plain reference of DeepSeek-V3's layer (``model_type: deepseek_v3``, source
https://huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601/blob/main/config.json,
Kanana-2-30B-A3B) trained on the next token, as one chip's share of an
expert-parallel layer: ``jax.numpy``, float32, a Python loop over the layers,
the mask a boolean (L, L) matrix, one softmax over all L keys, a dense one-hot
dispatch over the held experts.  No running softmax, no kernel, no stacked scan
of layers, nothing of the program's.  No bias on any projection, eps 1e-6:

    h = x + Attn(RMSNorm(x)),  y = h + FFN_l(RMSNorm(h))

    Attn (latent; H heads HELD HERE of the published num_attention_heads, the
      shape's ``num_attention_heads`` being the count held), u the normed input:
        q = u W_q -> (H, qk_nope_head_dim + qk_rope_head_dim): a head is
          [q_nope ; q_pe]            (q_lora_rank null: no low-rank query path)
        u W_kva -> [c_kv (kv_lora_rank) ; k_pe (qk_rope_head_dim)]: the latent and
          ONE rotary key for every head
        RMSNorm(c_kv) W_kvb -> (H, qk_nope_head_dim + v_head_dim): a head is
          [k_nope ; v]
        RoPE on q_pe and on k_pe: pair (2i, 2i + 1) by position * theta^(-2i /
          qk_rope_head_dim) (rope_interleave; rope_scaling null, so no factor)
        k = [k_nope ; k_pe]; softmax(q . k / sqrt(qk_nope_head_dim +
          qk_rope_head_dim)) over the keys j <= i, times v; W_o on the heads
          side by side: with H of them held, a partial sum over those heads
    FFN_l, l < first_k_dense_replace: W_down(silu(W_gate u) * W_up u) of
      intermediate_size.
    FFN_l, later: shared(u), the same unit of n_shared_experts x
      moe_intermediate_size, + routed_scaling_factor * sum over e in S(u) THAT
      ARE HELD HERE of w_e expert_e(u); s = sigmoid(W_g u) over all
      n_routed_experts, S the num_experts_per_tok largest of s + b (b the
      router's e_score_correction_bias; n_group = topk_group = 1: no group
      limit), w_e = s_e / sum_{e in S} s_e (norm_topk_prob): the bias moves the
      choice and not the weights.  What the absent experts would add is left
      out, here as in the program.
    head: logits = RMSNorm(x) W_head; loss = mean over the positions of
      -log softmax(logits_i)[target_i].

``init(key, shape, vocabulary)`` is handed the configuration's family shape
(its ``image_size`` mapping) and keeps it for ``loss``, whose signature has no
room for it; ``inputs`` are a row's first L ids and ``targets`` its last L, as
grid/references/feed_device_tokens_causal.py makes them.  The parameters:
``embed``, ``head``, ``final_norm`` and ``layers``, a list of two runs (the
leading dense layers, the sparse ones), each leaf of a run stacked on a leading
axis (the program's layout; this file indexes it layer by layer).

Departures from a literal transcription: (1) for memory (check.py puts this
under ``jax.value_and_grad`` beside three rows of gradients, and at 16 heads
held the chip has ~7 GB left for all of it): each layer is under
``jax.checkpoint``; attention takes the queries 512 at a time and the
feed-forward units and the head their positions 512 at a time
(``_by_blocks``: ``lax.map``, each block checkpointed too), which changes no
number's meaning: a query's softmax is still over all L keys at once, and a
position's feed-forward output and logits depend on that position alone.
(2) RoPE turns the pairs (2i, 2i + 1) as they lie; the published code first
moves each pair's members to the two halves of the rotary part and turns those
(``rope_interleave``): the same rotation under one fixed permutation of q_pe's
and k_pe's dims alike, so every score is the same.
(3) ``b`` is a leaf of the parameters, seeded like a matrix (N(0, 0.02^2)), so
that it moves choices; nothing updates it by the published balancing rule, and
its gradient is zero because only the indices of ``top_k(s + b)`` are used.
"""

import math

import jax
import jax.numpy as jnp

INIT_STD = 0.02
BLOCK = 512   # queries, or positions, computed at a time

_SHAPE = {}


def _run_shapes(shape, dense, count):
    d, heads = shape["hidden_size"], shape["num_attention_heads"]
    nope, turned, dv = (shape["qk_nope_head_dim"], shape["qk_rope_head_dim"], shape["v_head_dim"])
    rank = shape["kv_lora_rank"]
    dims = {"attn_norm": (d,), "mlp_norm": (d,), "kv_norm": (rank,),
            "wq": (d, heads * (nope + turned)), "wkv_a": (d, rank + turned),
            "wkv_b": (rank, heads * (nope + dv)), "wo": (heads * dv, d)}
    if dense:
        width = shape["intermediate_size"]
        dims.update({"w_gate": (d, width), "w_up": (d, width), "w_down": (width, d)})
    else:
        held, width = len(shape["experts_held"]), shape["moe_intermediate_size"]
        shared = shape["n_shared_experts"] * width
        dims.update({"router": (d, shape["n_routed_experts"]),
                     "router_bias": (shape["n_routed_experts"],),
                     "we_gate": (held, d, width), "we_up": (held, d, width),
                     "we_down": (held, width, d),
                     "ws_gate": (d, shared), "ws_up": (d, shared), "ws_down": (shared, d)})
    return {name: (count,) + dim for name, dim in dims.items()}


def _runs(shape):
    """[(dense?, how many layers)]: the leading dense layers, then the sparse."""
    dense = shape["first_k_dense_replace"]
    return [(is_dense, count) for is_dense, count in
            ((True, dense), (False, shape["num_hidden_layers"] - dense)) if count]


def init(key, shape, vocabulary):
    """Norm scales at one, every other leaf N(0, 0.02^2), each from
    ``fold_in(key, its place)``: the top-level leaves by sorted name, then run
    after run, each run's by sorted name.  Records ``shape`` for ``loss``."""
    if shape.get("q_lora_rank") is not None:
        raise SystemExit("references.deepseek_v3: q_lora_rank %r: only null is written down here"
                         % (shape["q_lora_rank"],))
    _SHAPE.clear()
    _SHAPE.update(shape)
    d = shape["hidden_size"]
    groups = [{"embed": (vocabulary, d), "head": (d, vocabulary), "final_norm": (d,)}]
    groups += [_run_shapes(shape, dense, count) for dense, count in _runs(shape)]
    place, made = 0, []
    for group in groups:
        made.append({})
        for name, dims in sorted(group.items()):
            made[-1][name] = (jnp.ones(dims, jnp.float32) if name.endswith("norm") else INIT_STD
                              * jax.random.normal(jax.random.fold_in(key, place), dims, jnp.float32))
            place += 1
    return dict(made[0], layers=made[1:])


def _by_blocks(fn, *arrays):
    """``fn`` over blocks of ``BLOCK`` positions of (B, L, ...) arrays, one
    block after another, each under ``jax.checkpoint``; the results stacked on
    a leading axis of blocks."""
    b, length = arrays[0].shape[:2]
    block = min(BLOCK, length)
    cut = lambda a: a.reshape((b, length // block, block) + a.shape[2:]).swapaxes(0, 1)
    return jax.lax.map(lambda blocks: jax.checkpoint(fn)(*blocks), tuple(cut(a) for a in arrays))


def _joined(blocks):
    """(blocks, B, block, ...) back to (B, L, ...)."""
    blocks = blocks.swapaxes(0, 1)
    return blocks.reshape((blocks.shape[0], -1) + blocks.shape[3:])


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x (B, L, H, W): pair (2i, 2i + 1) by position * theta^(-2i / W)."""
    width = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, width, 2, dtype=jnp.float32) / width)
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos], axis=-1).reshape(x.shape)


def _attention(u, layer, s):
    b, length, _ = u.shape
    heads, nope, turned, dv = (s["num_attention_heads"], s["qk_nope_head_dim"],
                               s["qk_rope_head_dim"], s["v_head_dim"])
    rank = s["kv_lora_rank"]
    q = (u @ layer["wq"]).reshape(b, length, heads, nope + turned)
    latent = u @ layer["wkv_a"]
    c_kv, k_pe = latent[..., :rank], latent[..., rank:]
    kv = (_rms_norm(c_kv, layer["kv_norm"], s["rms_norm_eps"]) @ layer["wkv_b"]).reshape(
        b, length, heads, nope + dv)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q_pe = _rope(q[..., nope:], s["rope_theta"])
    k_pe = _rope(k_pe[:, :, None, :], s["rope_theta"])
    q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
    k = jnp.concatenate([k_nope, jnp.repeat(k_pe, heads, axis=2)], axis=-1)
    mask = jnp.arange(length)[:, None] >= jnp.arange(length)[None, :]

    def some_queries(q_block, mask_block):
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_block, k) / math.sqrt(nope + turned)
        weights = jax.nn.softmax(jnp.where(mask_block[:, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", weights, v)

    out = _joined(_by_blocks(some_queries, q, jnp.broadcast_to(mask, (b, length, length))))
    return out.reshape(b, length, heads * dv) @ layer["wo"]


def _unit(u, gate, up, down):
    return (jax.nn.silu(u @ gate) * (u @ up)) @ down


def _route(u, layer, s):
    """(weights (..., k), chosen experts (..., k)): chosen by score + bias,
    weighted by the scores alone."""
    scores = jax.nn.sigmoid(u @ layer["router"])
    _, chosen = jax.lax.top_k(scores + layer["router_bias"], s["num_experts_per_tok"])
    top_s = jnp.take_along_axis(scores, chosen, axis=-1)
    return top_s / jnp.sum(top_s, axis=-1, keepdims=True), chosen


def _sparse(u, layer, s):
    weights, chosen = _route(u, layer, s)
    routed = jnp.zeros_like(u)
    for slot, expert in enumerate(s["experts_held"]):
        mine = jnp.sum(jnp.where(chosen == expert, weights, 0.0), axis=-1)
        routed = routed + mine[..., None] * _unit(
            u, layer["we_gate"][slot], layer["we_up"][slot], layer["we_down"][slot])
    shared = _unit(u, layer["ws_gate"], layer["ws_up"], layer["ws_down"])
    return shared + s["routed_scaling_factor"] * routed


def _layer(x, layer, dense, s):
    h = x + _attention(_rms_norm(x, layer["attn_norm"], s["rms_norm_eps"]), layer, s)
    u = _rms_norm(h, layer["mlp_norm"], s["rms_norm_eps"])
    if dense:
        return h + _joined(_by_blocks(
            lambda u: _unit(u, layer["w_gate"], layer["w_up"], layer["w_down"]), u))
    return h + _joined(_by_blocks(lambda u: _sparse(u, layer, s), u))


def loss(params, inputs, targets):
    s = _SHAPE
    if not s:
        raise RuntimeError("references.deepseek_v3: init(key, shape, vocabulary) records the "
                           "family's shape and has to be called before loss")
    x = params["embed"][inputs]
    for (dense, count), group in zip(_runs(s), params["layers"]):
        for place in range(count):
            x = jax.checkpoint(lambda x, group, place=place, dense=dense: _layer(
                x, {name: leaf[place] for name, leaf in group.items()}, dense, s))(x, group)

    def some_positions(x, targets):
        logp = jax.nn.log_softmax(_rms_norm(x, params["final_norm"], s["rms_norm_eps"])
                                  @ params["head"], axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, targets[..., None], axis=-1))

    return jnp.sum(_by_blocks(some_positions, x, targets)) / targets.size
