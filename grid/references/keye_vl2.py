"""Plain reference of Keye-VL-2.0's language layer (``model_type: KeyeVL2``,
source https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json)
trained on the next token, as one chip's share of an expert-parallel layer:
``jax.numpy``, float32, a Python loop over the layers, the selection by a
stable descending ``argsort`` of each query's index scores, one dense softmax
over all L keys under the boolean matrix of the selected pairs, a dense
one-hot dispatch over the held experts.  No running softmax, no kernel, no
threshold, no stacked scan of layers, nothing of the program's.  No bias on any
projection, eps 1e-6; ``u = RMSNorm(x)``; a token has three position ids
(temporal, height, width), equal for text:

    h = x + W_o SparseAttn(u),  y = h + MoE(RMSNorm(h))

    main heads: q = RoPE_m(RMSNorm_head(W_q u)) (num_attention_heads of
      head_dim), k = RoPE_m(RMSNorm_head(W_k u)), v = W_v u
      (num_key_value_heads; head h reads kv head h // (heads / kv heads));
      RoPE_m: pair (2i, 2i + 1) of a head turns by p * theta^(-2i / head_dim),
      p the temporal id for i < 16, the height's for 16 <= i < 40, the width's
      for i >= 40 (mrope_section [16, 24, 24])
    indexer, on stop_gradient(u): q_I = RoPE(W_Iq u) (indexer_num_heads of
      indexer_head_dim, by the temporal id, over the whole head),
      k_I = RoPE(LayerNorm(W_Ik u)) (ONE key head), w = (W_Iw u) /
      sqrt(indexer_num_heads * indexer_head_dim);
      I[t, s] = sum_j w[t, j] * relu(q_I[t, j] . k_I[s])
    selection: S_t = the topk keys s <= t of largest I[t, s], every s <= t
      while t + 1 <= topk; equal scores go to the lower s; one S_t for all heads
    SparseAttn: softmax over s in S_t of q . k / sqrt(head_dim), times v
    MoE: p = softmax(W_r u) over all num_experts; S the num_experts_per_tok
      largest; w_e = p_e / sum_{e in S} p_e (norm_topk_prob); sum over e in S
      THAT ARE HELD HERE of w_e expert_e(u), expert = W_down(silu(W_gate u) *
      W_up u).  What the absent experts would add is left out, here as in the
      program.
    head: logits = RMSNorm(x) W_head; loss = mean over the positions of
      -log softmax(logits_i)[target_i].

``init(key, shape, vocabulary)`` is handed the configuration's family shape
(its ``image_size`` mapping) and keeps it for ``loss``, whose signature has no
room for it; ``inputs`` are a row's first L ids and ``targets`` its last L, as
grid/references/feed_device_tokens_causal.py makes them.  The parameters:
``embed``, ``head``, ``final_norm`` and ``layers``, a list of ONE run whose
leaves are stacked on a leading axis (the program's layout; this file indexes
it layer by layer).

Departures from a literal transcription: (1) for memory (check.py puts this
under ``jax.value_and_grad`` beside three rows of gradients, and has 8.7 GiB
of the chip for it): each layer is under ``jax.checkpoint`` and is handed ITS
OWN leaves, cut out of the stacked run behind an ``optimization_barrier`` (a
layer handed the whole run gives back a cotangent as large as the run, 0.95 GB
a layer: 9.8 GiB of temporaries in all, which did not load; cut out without
the barrier the compiler reads the 16-wide ``index_ww`` straight out of the
flat parameter vector viewed as (19,649,760, 16), 8 x padded: 9.4 GB; with it
5.9 GB — the compiler's counts for the described chip, PERF.md section 6,
PR 45); the index scores, the selection and the softmax take
the queries ``BLOCK`` at a time and the experts and the head their positions
``BLOCK`` at a time (``_by_blocks``: ``lax.map``, each block checkpointed too),
which changes no number's meaning: a query's scores, its selection and its
softmax are over all L keys at once, and a position's feed-forward output and
logits depend on that position alone.  (2) RoPE turns the pairs (2i, 2i + 1)
as they lie; the published code turns the two halves of a head: the same
rotation under one fixed permutation of q's and k's dims alike, so every score
is the same.  (3) The indexer's gradient is zero through a selection whatever
one writes; ``stop_gradient`` says so.  The published family trains the indexer
by a KL loss towards the main heads' attention: not run, here or in the
program.
"""

import math

import jax
import jax.numpy as jnp

INIT_STD = 0.02
BLOCK = 512   # queries, or positions, computed at a time

_SHAPE = {}


def _run_shapes(shape):
    d, dh = shape["hidden_size"], shape["head_dim"]
    heads, kv = shape["num_attention_heads"], shape["num_key_value_heads"]
    j, di = shape["sa_config"]["indexer_num_heads"], shape["sa_config"]["indexer_head_dim"]
    held, width = len(shape["experts_held"]), shape["moe_intermediate_size"]
    dims = {"attn_norm": (d,), "mlp_norm": (d,), "q_norm": (dh,), "k_norm": (dh,),
            "wq": (d, heads * dh), "wk": (d, kv * dh), "wv": (d, kv * dh), "wo": (heads * dh, d),
            "index_wq": (d, j * di), "index_wk": (d, di), "index_ww": (d, j),
            "index_k_norm": (di,), "index_k_bias": (di,),
            "router": (d, shape["num_experts"]),
            "we_gate": (held, d, width), "we_up": (held, d, width), "we_down": (held, width, d)}
    return {name: (shape["num_hidden_layers"],) + dim for name, dim in dims.items()}


def init(key, shape, vocabulary):
    """Norm scales at one, every other leaf N(0, 0.02^2) (the indexer's
    LayerNorm bias too), each from ``fold_in(key, its place)``: the top-level
    leaves by sorted name, then the run's by sorted name.  Records ``shape``
    for ``loss``."""
    if shape["sa_config"]["indexer_num_kv_heads"] != 1:
        raise SystemExit("references.keye_vl2: indexer_num_kv_heads %r: only one key head is "
                         "written down here" % (shape["sa_config"]["indexer_num_kv_heads"],))
    _SHAPE.clear()
    _SHAPE.update(shape)
    d = shape["hidden_size"]
    groups = [{"embed": (vocabulary, d), "head": (d, vocabulary), "final_norm": (d,)},
              _run_shapes(shape)]
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


def _layer_norm(x, scale, bias, eps):
    centred = x - jnp.mean(x, axis=-1, keepdims=True)
    return centred * jax.lax.rsqrt(jnp.mean(centred * centred, axis=-1, keepdims=True)
                                   + eps) * scale + bias


def _rope(x, positions, theta):
    """x (B, L, H, W); ``positions`` (L, W / 2): the position id that turns
    each pair (2i, 2i + 1), by id * theta^(-2i / W)."""
    width = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, width, 2, dtype=jnp.float32) / width)
    angles = positions.astype(jnp.float32) * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos], axis=-1).reshape(x.shape)


def _by_section(ids, sections):
    """ids (3, L) -> (L, sum(sections)): pair i's id is that of its section."""
    return jnp.concatenate([jnp.repeat(ids[axis][:, None], pairs, axis=1)
                            for axis, pairs in enumerate(sections)], axis=1)


def _selected(scores, q_index, topk):
    """(B, Q, L) booleans: each query's ``topk`` causal keys of largest score,
    ties to the lower key, all of them where there are no more.  A stable
    descending argsort puts a query's keys in order of preference (a key past
    the query last); a key is in iff its rank in that order is under
    ``topk``."""
    length = scores.shape[-1]
    causal = jnp.arange(length)[None, :] <= q_index[:, None]
    order = jnp.argsort(jnp.where(causal, scores, -jnp.inf), axis=-1, stable=True,
                        descending=True)
    rank = jnp.argsort(order, axis=-1)
    return (rank < topk) & causal


def _indexer(u, layer, s, ids):
    """The indexer's queries (B, L, J, Di), its one key head (B, L, Di) and a
    query's weights (B, L, J), from ``stop_gradient(u)``."""
    b, length, _ = u.shape
    sa, theta = s["sa_config"], s["rope_theta"]
    j, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    still = jax.lax.stop_gradient(u)
    temporal = jnp.repeat(ids[0][:, None], di // 2, axis=1)
    q_i = _rope((still @ layer["index_wq"]).reshape(b, length, j, di), temporal, theta)
    k_i = _rope(_layer_norm(still @ layer["index_wk"], layer["index_k_norm"],
                            layer["index_k_bias"], s["rms_norm_eps"])[:, :, None, :],
                temporal, theta)[:, :, 0]
    return q_i, k_i, (still @ layer["index_ww"]) / math.sqrt(j * di)


def _index_scores(q_i, k_i, w_i):
    """I[t, s] of some queries against every key: (B, Q, L)."""
    return jnp.einsum("bqj,bqjk->bqk", w_i,
                      jax.nn.relu(jnp.einsum("bqjd,bkd->bqjk", q_i, k_i)))


def _attention(u, layer, s, ids):
    """``ids`` (3, L): the temporal, height and width id of each position."""
    b, length, _ = u.shape
    heads, kv, dh = s["num_attention_heads"], s["num_key_value_heads"], s["head_dim"]
    theta, eps = s["rope_theta"], s["rms_norm_eps"]
    by_section = _by_section(ids, s["rope_scaling"]["mrope_section"])
    q = _rope(_rms_norm((u @ layer["wq"]).reshape(b, length, heads, dh), layer["q_norm"], eps),
              by_section, theta)
    k = _rope(_rms_norm((u @ layer["wk"]).reshape(b, length, kv, dh), layer["k_norm"], eps),
              by_section, theta)
    v = (u @ layer["wv"]).reshape(b, length, kv, dh)
    k, v = jnp.repeat(k, heads // kv, axis=2), jnp.repeat(v, heads // kv, axis=2)
    q_i, k_i, w_i = _indexer(u, layer, s, ids)

    def some_queries(q_block, q_i_block, w_i_block, q_index):
        chosen = _selected(_index_scores(q_i_block, k_i, w_i_block), q_index[0],
                           s["sa_config"]["topk"])
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_block, k) / math.sqrt(dh)
        weights = jax.nn.softmax(jnp.where(chosen[:, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", weights, v)

    index = jnp.broadcast_to(jnp.arange(length), (b, length))
    out = _joined(_by_blocks(some_queries, q, q_i, w_i, index))
    return out.reshape(b, length, heads * dh) @ layer["wo"]


def _moe(u, layer, s):
    p = jax.nn.softmax(u @ layer["router"], axis=-1)
    top_p, chosen = jax.lax.top_k(p, s["num_experts_per_tok"])
    weights = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    out = jnp.zeros_like(u)
    for slot, expert in enumerate(s["experts_held"]):
        mine = jnp.sum(jnp.where(chosen == expert, weights, 0.0), axis=-1)
        hidden = jax.nn.silu(u @ layer["we_gate"][slot]) * (u @ layer["we_up"][slot])
        out = out + mine[..., None] * (hidden @ layer["we_down"][slot])
    return out


def _layer(x, layer, s, ids):
    h = x + _attention(_rms_norm(x, layer["attn_norm"], s["rms_norm_eps"]), layer, s, ids)
    u = _rms_norm(h, layer["mlp_norm"], s["rms_norm_eps"])
    return h + _joined(_by_blocks(lambda u: _moe(u, layer, s), u))


def loss(params, inputs, targets):
    """Every product in full float32, whatever the caller's precision is."""
    with jax.default_matmul_precision("highest"):
        return _loss(params, inputs, targets)


def _loss(params, inputs, targets):
    s = _SHAPE
    if not s:
        raise RuntimeError("references.keye_vl2: init(key, shape, vocabulary) records the "
                           "family's shape and has to be called before loss")
    x = params["embed"][inputs]
    ids = jnp.broadcast_to(jnp.arange(inputs.shape[1]), (3, inputs.shape[1]))  # text
    group, = jax.lax.optimization_barrier(params["layers"])
    for place in range(s["num_hidden_layers"]):
        x = jax.checkpoint(lambda x, layer: _layer(x, layer, s, ids))(
            x, {name: leaf[place] for name, leaf in group.items()})

    def some_positions(x, targets):
        logp = jax.nn.log_softmax(_rms_norm(x, params["final_norm"], s["rms_norm_eps"])
                                  @ params["head"], axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, targets[..., None], axis=-1))

    return jnp.sum(_by_blocks(some_positions, x, targets)) / targets.size
