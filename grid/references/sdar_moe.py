"""Plain reference of SDAR (``model_type: sdar_moe``, source
https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json) trained
by diffusion over blocks, as one chip's share of an expert-parallel layer:
``jax.numpy``, float32, a Python loop over the layers, the mask as a boolean
matrix, a dense one-hot dispatch over the held experts.  No sort, no grouped
product, no chunked softmax, nothing of the program's.

    h = x + Attn(RMSNorm(x)),  y = h + MoE(RMSNorm(h))

    Attn: q = W_q u (heads x head_dim), k, v = W_k u, W_v u (kv heads x
      head_dim); RMSNorm over each head's head_dim of q and of k, a learned
      scale each; RoPE on q and k; key/value head j serves the query heads
      j * heads/kv .. (j + 1) * heads/kv - 1; softmax(q.k / sqrt(head_dim))
      under the mask; W_o on the concatenated heads.
    MoE: p = softmax(W_r u) over all the experts; S = the experts_per_token
      largest; weights p_e / sum_{e in S} p_e; the output is the sum over the
      experts of S THAT ARE HELD HERE of w_e W_down^e(silu(W_gate^e u) *
      W_up^e u).  What the absent experts would add is left out, here as in
      the program (the deployment's exchange would add it; one chip has none).
    Block diffusion: the model reads [x_t ; x_0], 2L positions, both halves at
      positions 0..L-1.  With beta(i) = i // block, query i may read key j:
      noisy->noisy iff beta(i) = beta(j); noisy->clean iff beta(j) < beta(i);
      clean->clean iff beta(j) <= beta(i); clean->noisy never.  Logits at the
      noisy half; loss = (1/L) sum_{i masked} (1/t) -log softmax(logits_i)[x_0^i],
      averaged over the sequences of the batch.

``init(key, shape, vocabulary)`` is handed the configuration's family shape
(its ``image_size`` mapping: sequence and block length, layers, experts held,
the published widths) and keeps it for ``loss``, whose signature has no room
for it; ``inputs`` is ``{"noisy": x_t, "t": t}`` and ``targets`` is x_0, as
grid/references/feed_device_tokens.py makes them.

Departures from a literal transcription, both for memory (check.py puts this
under ``jax.value_and_grad`` beside 4.9 GB of rows): each layer is under
``jax.checkpoint``, and attention takes the queries 512 at a time, each block
checkpointed too (its softmax is still over all 2L keys at once).  RoPE
rotates the pairs (2i, 2i + 1) of a head, the program's convention, which is
the half-split form of the published code under a fixed permutation of each
head's dimensions — the same model on seeded random weights.
"""

import math

import jax
import jax.numpy as jnp

INIT_STD = 0.02
QUERY_BLOCK = 512

_SHAPE = {}


def _shapes(shape, vocabulary):
    d, dh, n = shape["hidden_size"], shape["head_dim"], shape["num_hidden_layers"]
    held, width = len(shape["experts_held"]), shape["moe_intermediate_size"]
    q, kv = shape["num_attention_heads"] * dh, shape["num_key_value_heads"] * dh
    return {
        "embed": (vocabulary, d), "head": (d, vocabulary), "final_norm": (d,),
        "attn_norm": (n, d), "mlp_norm": (n, d), "q_norm": (n, dh), "k_norm": (n, dh),
        "wq": (n, d, q), "wk": (n, d, kv), "wv": (n, d, kv), "wo": (n, q, d),
        "router": (n, d, shape["num_experts"]),
        "we_gate": (n, held, d, width), "we_up": (n, held, d, width),
        "we_down": (n, held, width, d),
    }


def init(key, shape, vocabulary):
    """Norm scales at one, matrices N(0, 0.02^2); a layer's leaves stacked on
    a leading axis.  Records ``shape`` for ``loss``."""
    _SHAPE.clear()
    _SHAPE.update(shape)
    params = {}
    for place, (name, dims) in enumerate(sorted(_shapes(shape, vocabulary).items())):
        if name.endswith("norm"):
            params[name] = jnp.ones(dims, jnp.float32)
        else:
            params[name] = INIT_STD * jax.random.normal(
                jax.random.fold_in(key, place), dims, jnp.float32)
    return params


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, positions, theta):
    """x (B, S, H, Dh): pair (2i, 2i + 1) turned by position * theta^(-2i/Dh)."""
    dh = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    angles = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    turned = jnp.stack([even * cos - odd * sin, even * sin + odd * cos], axis=-1)
    return turned.reshape(x.shape)


def block_mask(length, block):
    """(2L, 2L) booleans over [noisy ; clean] x [noisy ; clean]."""
    beta = jnp.arange(length) // block
    same, earlier = beta[:, None] == beta[None, :], beta[None, :] < beta[:, None]
    never = jnp.zeros((length, length), bool)
    return jnp.block([[same, earlier], [never, same | earlier]])


def _attention(u, layer, mask, positions, s):
    b, two_l, _ = u.shape
    heads, kv, dh = s["num_attention_heads"], s["num_key_value_heads"], s["head_dim"]
    q = (u @ layer["wq"]).reshape(b, two_l, heads, dh)
    k = (u @ layer["wk"]).reshape(b, two_l, kv, dh)
    v = (u @ layer["wv"]).reshape(b, two_l, kv, dh)
    q = _rope(_rms_norm(q, layer["q_norm"], s["rms_norm_eps"]), positions, s["rope_theta"])
    k = _rope(_rms_norm(k, layer["k_norm"], s["rms_norm_eps"]), positions, s["rope_theta"])
    k, v = jnp.repeat(k, heads // kv, axis=2), jnp.repeat(v, heads // kv, axis=2)

    @jax.checkpoint
    def some_queries(q_block, mask_block):
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_block, k) / math.sqrt(dh)
        weights = jax.nn.softmax(jnp.where(mask_block[None, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", weights, v)

    out = jnp.concatenate([
        some_queries(q[:, lo:lo + QUERY_BLOCK], mask[lo:lo + QUERY_BLOCK])
        for lo in range(0, two_l, QUERY_BLOCK)], axis=1)
    return out.reshape(b, two_l, heads * dh) @ layer["wo"]


def _experts(u, layer, s):
    p = jax.nn.softmax(u @ layer["router"], axis=-1)
    top_p, top_e = jax.lax.top_k(p, s["num_experts_per_tok"])
    weights = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    out = jnp.zeros_like(u)
    for slot, expert in enumerate(s["experts_held"]):
        mine = jnp.sum(jnp.where(top_e == expert, weights, 0.0), axis=-1)
        hidden = jax.nn.silu(u @ layer["we_gate"][slot]) * (u @ layer["we_up"][slot])
        out = out + mine[..., None] * (hidden @ layer["we_down"][slot])
    return out


def _layer(x, layer, mask, positions, s):
    h = x + _attention(_rms_norm(x, layer["attn_norm"], s["rms_norm_eps"]), layer, mask,
                       positions, s)
    return h + _experts(_rms_norm(h, layer["mlp_norm"], s["rms_norm_eps"]), layer, s)


def loss(params, inputs, targets):
    s = _SHAPE
    if not s:
        raise RuntimeError("references.sdar_moe: init(key, shape, vocabulary) records the "
                           "family's shape and has to be called before loss")
    noisy, t, clean = inputs["noisy"], inputs["t"], targets
    length = clean.shape[1]
    mask = block_mask(length, s["block_length"])
    positions = jnp.tile(jnp.arange(length), 2)
    x = params["embed"][jnp.concatenate([noisy, clean], axis=1)]
    stacked = [name for name in params if name not in ("embed", "head", "final_norm")]
    for index in range(s["num_hidden_layers"]):
        layer = {name: params[name][index] for name in stacked}
        x = jax.checkpoint(lambda x, layer: _layer(x, layer, mask, positions, s))(x, layer)
    hidden = _rms_norm(x[:, :length], params["final_norm"], s["rms_norm_eps"])
    logp = jax.nn.log_softmax(hidden @ params["head"], axis=-1)
    nll = -jnp.take_along_axis(logp, clean[..., None], axis=-1)[..., 0]
    masked = noisy == s["mask_token_id"]
    return jnp.mean(jnp.sum(jnp.where(masked, nll, 0.0) / t[:, None], axis=1) / length)
