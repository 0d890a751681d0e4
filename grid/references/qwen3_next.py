"""Plain reference of Qwen3-Next's two layers (``model_type: qwen3_next``,
source https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json)
trained on the next token, as one chip's share of an expert-parallel layer:
``jax.numpy``, float32, a Python loop over the layers, the gated delta rule as
its RECURRENCE, one token after another, one dense causal softmax over all L
keys, a dense one-hot dispatch over the held experts.  No chunked form, no
triangular system, no running softmax, no kernel, nothing of the program's.
No bias on any projection, eps 1e-6.  Layer i is full attention iff
``(i + 1) % full_attention_interval == 0``, else Gated DeltaNet; every norm but
the DeltaNet's gated one is zero-centred, ``zrms(x, w) = rms(x) * (1 + w)``:

    h = x + Mixer_i(zrms(x)),  y = h + MoE(zrms(h))

    Gated DeltaNet: [q | k | v | z] = u W_qkvz (linear_num_key_heads of
      linear_key_head_dim for q and for k, linear_num_value_heads of
      linear_value_head_dim for v and for z), [b | a] = u W_ba; [q | k | v]
      through a depthwise causal convolution of linear_conv_kernel_dim taps
      (no bias: channel c's own taps over its last inputs), then SiLU; value head h
      reads key head h // (value heads / key heads); q and k L2-normalised over
      a head (eps 1e-6), q times linear_key_head_dim^-1/2;
      beta_t = sigmoid(b_t), g_t = -exp(A_log) * softplus(a_t + dt_bias);
      S_0 = 0, and for t = 0, 1, ...:
        S' = exp(g_t) S_{t-1}
        S_t = S' + k_t (beta_t (v_t - S'^T k_t))^T
        o_t = S_t^T q_t
      out = (rms(o) * w_o_norm * silu(z)) W_o, the norm over a head's values
    gated attention: [q | gate] = u W_q (num_attention_heads of head_dim each
      half), k = W_k u, v = W_v u (num_key_value_heads); q = RoPE(zrms_head(q)),
      k = RoPE(zrms_head(k)), RoPE on the first partial_rotary_factor of a
      head's lanes, pair (2i, 2i + 1) by p * theta^(-2i / rotary lanes); head h
      reads kv head h // (heads / kv heads); causal softmax of q . k /
      sqrt(head_dim), times v; out = (that * sigmoid(gate)) W_o
    MoE: p = softmax(W_r u) over all num_experts; S the num_experts_per_tok
      largest; w_e = p_e / sum_{e in S} p_e (norm_topk_prob); sum over e in S
      THAT ARE HELD HERE of w_e expert_e(u), expert = W_down(silu(W_gate u) *
      W_up u); plus sigmoid(u w_sg) * shared(u), the same unit of
      shared_expert_intermediate_size.  What the absent experts would add is
      left out, here as in the program.
    head: logits = zrms(x) W_head; loss = mean over the positions of
      -log softmax(logits_i)[target_i].

``init(key, shape, vocabulary)`` is handed the configuration's family shape
(its ``image_size`` mapping) and keeps it for ``loss``, whose signature has no
room for it; ``inputs`` are a row's first L ids and ``targets`` its last L, as
grid/references/feed_device_tokens_causal.py makes them.  The parameters:
``embed``, ``head``, ``final_norm`` and ``layers``, a list of runs of
consecutive layers of one kind whose leaves are stacked on a leading axis (the
program's layout; this file indexes it layer by layer).

Departures from a literal transcription, for memory (check.py puts this under
``jax.value_and_grad`` beside three rows of gradients): each layer is under
``jax.checkpoint`` and is handed ITS OWN leaves, cut out of the stacked runs
behind an ``optimization_barrier`` (grid/references/keye_vl2.py says what the
two save); the recurrence is scanned in blocks of ``BLOCK_TOKENS`` tokens, each
block checkpointed, so that the backward pass holds one block's states and the
states between blocks, not L of them; the softmax takes its queries, and the
experts and the head their positions, ``BLOCK`` at a time (``_by_blocks``),
which changes no number's meaning.  RoPE turns the pairs (2i, 2i + 1) as they
lie, and the fused projections' columns lie [q | k | v | z], [b | a] and
[q | gate]; the published code turns the two halves of the rotary lanes and
interleaves the fused columns by key head: the same layer under one fixed
permutation of each matrix's columns.
"""

import itertools
import math

import jax
import jax.numpy as jnp

INIT_STD = 0.02
BLOCK = 512          # queries, or positions, computed at a time
BLOCK_TOKENS = 64    # tokens of the recurrence a checkpointed block
A_FLOOR, A_MAX, DT_RANGE = 1e-4, 16.0, (1e-3, 1e-1)   # the decay's initialiser
ZERO_CENTRED = ("attn_norm", "mlp_norm", "q_norm", "k_norm", "final_norm")

_SHAPE = {}


def _kinds(shape):
    interval = shape["full_attention_interval"]
    return ["full" if (i + 1) % interval == 0 else "delta"
            for i in range(shape["num_hidden_layers"])]


def _runs(shape):
    """[(kind, how many)] of the consecutive layers alike."""
    return [(kind, len(list(alike))) for kind, alike in itertools.groupby(_kinds(shape))]


def _run_shapes(shape, kind, count):
    d, held = shape["hidden_size"], len(shape["experts_held"])
    width, shared = shape["moe_intermediate_size"], shape["shared_expert_intermediate_size"]
    if kind == "delta":
        keys = shape["linear_num_key_heads"] * shape["linear_key_head_dim"]
        value_heads, dv = shape["linear_num_value_heads"], shape["linear_value_head_dim"]
        dims = {"w_qkvz": (d, 2 * keys + 2 * value_heads * dv), "w_ba": (d, 2 * value_heads),
                "conv": (2 * keys + value_heads * dv, shape["linear_conv_kernel_dim"]),
                "A_log": (value_heads,), "dt_bias": (value_heads,), "o_norm": (dv,),
                "wo": (value_heads * dv, d)}
    else:
        heads, kv, dh = shape["num_attention_heads"], shape["num_key_value_heads"], shape["head_dim"]
        dims = {"wq": (d, 2 * heads * dh), "wk": (d, kv * dh), "wv": (d, kv * dh),
                "q_norm": (dh,), "k_norm": (dh,), "wo": (heads * dh, d)}
    dims.update({"attn_norm": (d,), "mlp_norm": (d,), "router": (d, shape["num_experts"]),
                 "shared_gate": (d, 1), "ws_gate": (d, shared), "ws_up": (d, shared),
                 "ws_down": (shared, d), "we_gate": (held, d, width), "we_up": (held, d, width),
                 "we_down": (held, width, d)})
    return {name: (count,) + dim for name, dim in dims.items()}


def init(key, shape, vocabulary):
    """Every matrix, the convolution's taps and the shared expert's gate N(0,
    0.02^2), each from ``fold_in(key, its place)``: the top-level leaves by
    sorted name, then each run's by sorted name, run after run.  A zero-centred
    norm's offset is zero and ``o_norm`` one (a scale of one either way).  The
    decay of run r from ``fold_in(key, 1000 + r)`` split in two: A ~ U(1e-4, 16)
    and ``A_log = log A``; dt log-uniform over [1e-3, 1e-1] and ``dt_bias`` its
    inverse softplus.  Records ``shape`` for ``loss``."""
    _SHAPE.clear()
    _SHAPE.update(shape)
    d = shape["hidden_size"]
    groups = [{"embed": (vocabulary, d), "head": (d, vocabulary), "final_norm": (d,)}]
    groups += [_run_shapes(shape, kind, count) for kind, count in _runs(shape)]
    place, made = 0, []
    for group in groups:
        made.append({})
        for name, dims in sorted(group.items()):
            if name in ZERO_CENTRED:
                made[-1][name] = jnp.zeros(dims, jnp.float32)
            elif name.endswith("norm"):
                made[-1][name] = jnp.ones(dims, jnp.float32)
            else:
                made[-1][name] = INIT_STD * jax.random.normal(
                    jax.random.fold_in(key, place), dims, jnp.float32)
            place += 1
    for r, run in enumerate(made[1:]):
        if "A_log" in run:
            a_key, dt_key = jax.random.split(jax.random.fold_in(key, 1000 + r))
            dims = run["A_log"].shape
            a = jax.random.uniform(a_key, dims, jnp.float32, A_FLOOR, A_MAX)
            dt = jnp.exp(jax.random.uniform(dt_key, dims, jnp.float32,
                                            math.log(DT_RANGE[0]), math.log(DT_RANGE[1])))
            run["A_log"], run["dt_bias"] = jnp.log(a), dt + jnp.log(-jnp.expm1(-dt))
    return dict(made[0], layers=made[1:])


def _by_blocks(fn, *arrays, block=BLOCK):
    """``fn`` over blocks of ``block`` positions of (B, L, ...) arrays, one
    block after another, each under ``jax.checkpoint``; the results stacked on
    a leading axis of blocks."""
    b, length = arrays[0].shape[:2]
    block = block if length % block == 0 else length
    cut = lambda a: a.reshape((b, length // block, block) + a.shape[2:]).swapaxes(0, 1)
    return jax.lax.map(lambda blocks: jax.checkpoint(fn)(*blocks), tuple(cut(a) for a in arrays))


def _joined(blocks):
    """(blocks, B, block, ...) back to (B, L, ...)."""
    blocks = blocks.swapaxes(0, 1)
    return blocks.reshape((blocks.shape[0], -1) + blocks.shape[3:])


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _zrms(x, offset, eps):
    return _rms(x, eps) * (1.0 + offset)


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _rope(x, theta, lanes):
    """x (B, L, H, W): the first ``lanes`` of a head turn, pair (2i, 2i + 1) by
    position * theta^(-2i / lanes); the rest pass."""
    length = x.shape[1]
    inv_freq = theta ** (-jnp.arange(0, lanes, 2, dtype=jnp.float32) / lanes)
    angles = jnp.arange(length, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    even, odd = x[..., 0:lanes:2], x[..., 1:lanes:2]
    turned = jnp.stack([even * cos - odd * sin, even * sin + odd * cos], axis=-1)
    return jnp.concatenate([turned.reshape(x.shape[:-1] + (lanes,)), x[..., lanes:]], axis=-1)


def _causal_conv(x, taps):
    """x (B, L, C), taps (C, K): ``y_t = sum_s taps[:, K - 1 - s] * x_{t - s}``
    over the K last inputs of channel c, s positions back, zeros before the
    sequence.  (Written out tap by tap: as one grouped ``conv_general_dilated``
    of 8,192 groups its gradient did not pass the TPU compiler's own verifier,
    PERF.md section 6, PR 49.)"""
    length, width = x.shape[1], taps.shape[1]
    out = jnp.zeros_like(x)
    for back in range(width):
        earlier = jnp.concatenate([jnp.zeros_like(x[:, :back]), x[:, :length - back]], axis=1)
        out = out + taps[:, width - 1 - back] * earlier
    return out


def _recurrence(q, k, v, g, beta):
    """The gated delta rule, token by token: q and k (B, L, H, Dk), v (B, L, H,
    Dv), g and beta (B, L, H) -> o (B, L, H, Dv).  Blocks of ``BLOCK_TOKENS``
    tokens, each checkpointed."""
    b, length, heads, dk = q.shape

    def one_token(state, token):
        q_t, k_t, v_t, g_t, beta_t = token
        state = state * jnp.exp(g_t)[..., None, None]
        predicted = jnp.einsum("bhkv,bhk->bhv", state, k_t)
        state = state + k_t[..., :, None] * (beta_t[..., None] * (v_t - predicted))[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    @jax.checkpoint
    def one_block(state, tokens):
        return jax.lax.scan(one_token, state, tokens)

    block = BLOCK_TOKENS if length % BLOCK_TOKENS == 0 else length
    by_block = lambda a: a.swapaxes(0, 1).reshape((length // block, block) + a.shape[:1]
                                                  + a.shape[2:])
    state = jnp.zeros((b, heads, dk, v.shape[-1]), jnp.float32)
    _, out = jax.lax.scan(one_block, state, tuple(by_block(a) for a in (q, k, v, g, beta)))
    return out.reshape((length,) + out.shape[2:]).swapaxes(0, 1)


def _delta_net(u, layer, s):
    b, length, _ = u.shape
    key_heads, dk = s["linear_num_key_heads"], s["linear_key_head_dim"]
    value_heads, dv = s["linear_num_value_heads"], s["linear_value_head_dim"]
    keys, values = key_heads * dk, value_heads * dv
    projected = u @ layer["w_qkvz"]
    mixed = jax.nn.silu(_causal_conv(projected[..., :2 * keys + values], layer["conv"]))
    z = projected[..., 2 * keys + values:].reshape(b, length, value_heads, dv)
    q = mixed[..., :keys].reshape(b, length, key_heads, dk)
    k = mixed[..., keys:2 * keys].reshape(b, length, key_heads, dk)
    v = mixed[..., 2 * keys:].reshape(b, length, value_heads, dv)
    q = jnp.repeat(_l2(q) / math.sqrt(dk), value_heads // key_heads, axis=2)
    k = jnp.repeat(_l2(k), value_heads // key_heads, axis=2)
    gates = u @ layer["w_ba"]
    beta = jax.nn.sigmoid(gates[..., :value_heads])
    g = -jnp.exp(layer["A_log"]) * jax.nn.softplus(gates[..., value_heads:] + layer["dt_bias"])
    out = _recurrence(q, k, v, g, beta)
    out = _rms(out, s["rms_norm_eps"]) * layer["o_norm"] * jax.nn.silu(z)
    return out.reshape(b, length, values) @ layer["wo"]


def _attention(u, layer, s):
    b, length, _ = u.shape
    heads, kv, dh = s["num_attention_heads"], s["num_key_value_heads"], s["head_dim"]
    theta, eps = s["rope_theta"], s["rms_norm_eps"]
    lanes = int(dh * s["partial_rotary_factor"])
    projected = u @ layer["wq"]
    gate = projected[..., heads * dh:]
    q = projected[..., :heads * dh].reshape(b, length, heads, dh)
    q = _rope(_zrms(q, layer["q_norm"], eps), theta, lanes)
    k = _rope(_zrms((u @ layer["wk"]).reshape(b, length, kv, dh), layer["k_norm"], eps),
              theta, lanes)
    v = (u @ layer["wv"]).reshape(b, length, kv, dh)
    k, v = jnp.repeat(k, heads // kv, axis=2), jnp.repeat(v, heads // kv, axis=2)

    def some_queries(q_block, q_index):
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_block, k) / math.sqrt(dh)
        causal = jnp.arange(length)[None, :] <= q_index[0][:, None]
        weights = jax.nn.softmax(jnp.where(causal[None, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", weights, v)

    index = jnp.broadcast_to(jnp.arange(length), (b, length))
    out = _joined(_by_blocks(some_queries, q, index)).reshape(b, length, heads * dh)
    return (out * jax.nn.sigmoid(gate)) @ layer["wo"]


def _unit(u, gate, up, down):
    return (jax.nn.silu(u @ gate) * (u @ up)) @ down


def _moe(u, layer, s):
    p = jax.nn.softmax(u @ layer["router"], axis=-1)
    top_p, chosen = jax.lax.top_k(p, s["num_experts_per_tok"])
    weights = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    out = jax.nn.sigmoid(u @ layer["shared_gate"]) * _unit(
        u, layer["ws_gate"], layer["ws_up"], layer["ws_down"])
    for slot, expert in enumerate(s["experts_held"]):
        mine = jnp.sum(jnp.where(chosen == expert, weights, 0.0), axis=-1)
        out = out + mine[..., None] * _unit(u, layer["we_gate"][slot], layer["we_up"][slot],
                                            layer["we_down"][slot])
    return out


def _layer(x, layer, s, kind):
    u = _zrms(x, layer["attn_norm"], s["rms_norm_eps"])
    h = x + (_delta_net(u, layer, s) if kind == "delta" else _attention(u, layer, s))
    u = _zrms(h, layer["mlp_norm"], s["rms_norm_eps"])
    return h + _joined(_by_blocks(lambda u: _moe(u, layer, s), u))


def loss(params, inputs, targets):
    """Every product in full float32, whatever the caller's precision is."""
    with jax.default_matmul_precision("highest"):
        return _loss(params, inputs, targets)


def _loss(params, inputs, targets):
    s = _SHAPE
    if not s:
        raise RuntimeError("references.qwen3_next: init(key, shape, vocabulary) records the "
                           "family's shape and has to be called before loss")
    x = params["embed"][inputs]
    groups = jax.lax.optimization_barrier(params["layers"])
    for (kind, count), group in zip(_runs(s), groups):
        for place in range(count):
            x = jax.checkpoint(lambda x, layer, kind=kind: _layer(x, layer, s, kind))(
                x, {name: leaf[place] for name, leaf in group.items()})

    def some_positions(x, targets):
        logp = jax.nn.log_softmax(_zrms(x, params["final_norm"], s["rms_norm_eps"])
                                  @ params["head"], axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, targets[..., None], axis=-1))

    return jnp.sum(_by_blocks(some_positions, x, targets)) / targets.size
