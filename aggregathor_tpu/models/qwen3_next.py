"""Qwen3-Next (``model_type: qwen3_next``): a mixture-of-experts decoder three
of whose four layers mix tokens by a RECURRENCE (Gated DeltaNet) and the fourth
by gated softmax attention, trained on the next token, as ONE CHIP'S SHARE of
an expert-parallel deployment.

Source: https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json.
Layer ``i`` is full attention iff ``(i + 1) % full_interval == 0``, else Gated
DeltaNet.  Every layer, every width as published, no bias on any projection;
every norm but the DeltaNet's gated one is zero-centred, ``zrms(x, w) = rms(x) *
(1 + w)``:

    h = x + Mixer_i(zrms(x)),  y = h + MoE(zrms(h))

- **Gated DeltaNet** — ``[q | k | v | z] = u W_qkvz`` (``key_heads`` of
  ``key_dim`` for q and for k, ``value_heads`` of ``value_dim`` for v and for
  z), ``[b | a] = u W_ba`` (a scalar a value head each); ``[q | k | v]`` through
  a depthwise causal convolution of ``conv`` taps, no bias, then SiLU.  q and k
  serve ``value_heads // key_heads`` consecutive value heads, each
  L2-normalised over its ``key_dim``, q scaled by ``key_dim^-1/2``.
  ``beta_t = sigmoid(b_t)``, ``g_t = -exp(A_log) * softplus(a_t + dt_bias)``.
  A state S (``key_dim`` x ``value_dim``) a head, S_0 = 0:

      S' = exp(g_t) S_{t-1};  S_t = S' + k_t (beta_t (v_t - S'^T k_t))^T;  o_t = S_t^T q_t

  ``out = (rms(o) * w_o_norm * silu(z)) W_o``, the norm over each head's
  ``value_dim``.
- **Gated full attention** — ``[q | gate] = u W_q`` (``heads`` of ``head_dim``
  each half), k and v of ``kv_heads``; zero-centred per-head norm of q and of k;
  rotary on the first ``rotary`` share of each head's lanes; causal softmax
  attention, ``heads // kv_heads`` query heads a key head;
  ``out = (attention * sigmoid(gate)) W_o``.
- **MoE** — models/sdar.py's router (softmax over all ``experts``, the
  ``experts_per_token`` largest renormalised) and held-experts loop: the layer
  is TOLD WHICH EXPERTS IT HOLDS (``experts_held``) and computes their part;
  plus ``sigmoid(u w_sg) * shared(u)``, the shared expert whole on every chip;
  every unit SiLU-gated with three matrices.
- **Loss** — models/laguna.py's next-token cross-entropy over the ids held here.

How it is computed here.  The recurrence runs in its CHUNKED form
(``chunked_delta_rule``): the sequence is cut into chunks of ``chunk``
positions; inside a chunk the decay is a cumulative sum of g, the chunk's
updates are the solution of one unit lower-triangular system
``(I + tril(diag(beta) K K^T * decay, -1))`` (``unit_lower_inverse``: inverted
block by block in log2(chunk) rounds of matrix products), every product of a
chunk is made for all chunks at once, and ONE state a head is carried from
chunk to chunk by a ``lax.scan`` (three products a chunk: what the state
already predicts, what it answers the queries, its update).  That form is
plain XLA: the CPU's path, every shape's fallback and the oracle the kernel is
held to.  ON A TPU, where ``ops.delta_rule.delta_rule_form`` takes the shape
(chunks of 64, heads of whole lanes, a length of whole tiles of chunks: the
grid's), the same equations run as ops/delta_rule.py's Pallas kernel pair — the
state a head in VMEM across a sequence's chunks, a chunk's matrices never in
HBM, a backward kernel of its own — through the one entry ``delta_rule``
(``model.delta_rule``).  What stands between ``u W_qkvz`` and that entry — the
convolution, SiLU, the heads' L2 norm, the key heads handed to the value heads
they serve — is ``split_heads`` in plain XLA and, on a TPU where
``ops.gdn_operands.operands_form`` takes the shape (a float32 projection, heads
of whole lanes, a length of whole tiles), ops/gdn_operands.py's kernel pair:
one read of the projection as it lies, q, k and v written once in the blocks
the delta rule's kernels read.  Consecutive layers
of one kind are a run of stacked leaves (models/laguna.py ``layer_runs``:
scanned where several, each layer under ``jax.checkpoint``), so a period is a
run of ``full_interval - 1`` DeltaNet layers and a run of one attention layer,
whose leaves differ.  Attention is ops/attention.py's kernel under ``Causal()``
where ``attention_form`` takes the shape (heads of 256 lanes, 8 query heads a
key head), models/laguna.py's chunked softmax everywhere else.
"""

import dataclasses
import itertools
import math

import jax
import jax.numpy as jnp

from . import Experiment, register
from ..utils import UserException, parse_keyval
from ..ops.delta_rule import gated_delta_rule
from ..ops.gdn_operands import gdn_operands
from .common import check_dtype
from .laguna import (LagunaExperiment, causal_attention, gated_unit, layer_runs, next_token_loss,
                     seeded_corpus, seeded_leaves)
from .sdar import _parse_held, held_experts, route
from .transformer import _NEG, rms_norm, rope, rope_frequencies

DELTA, FULL = "delta", "full"

#: the initialiser of the decay's two leaves, a value head each (the source
#: family's, models after Mamba2's): A ~ U(A_FLOOR, A_MAX), ``A_log = log A``;
#: ``softplus(dt_bias)`` log-uniform over DT_RANGE
A_FLOOR, A_MAX, DT_RANGE = 1e-4, 16.0, (1e-3, 1e-1)


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    """The published widths, and this chip's share of depth, experts and
    vocabulary (grid/configs/qwen3next-80b-a3b-ep64-n3.json states the
    deployment)."""

    vocab: int = 18992
    hidden: int = 2048
    layers: int = 4
    full_interval: int = 4         # layer i is full attention iff (i + 1) % this == 0
    heads: int = 16
    kv_heads: int = 2
    head_dim: int = 256
    rotary: float = 0.25           # the share of each head that turns
    rope_theta: float = 1e7
    key_heads: int = 16
    value_heads: int = 32
    key_dim: int = 128
    value_dim: int = 128
    conv: int = 4                  # taps of the causal convolution
    chunk: int = 64                # positions a chunk of the delta rule
    experts: int = 512             # what the router scores
    experts_per_token: int = 10
    expert_width: int = 512
    shared_width: int = 512
    experts_held: tuple = tuple(range(8))
    norm_eps: float = 1e-6
    seq: int = 4096
    attn_chunk: int = 256          # queries a chunk of the XLA form of attention
    dtype: object = jnp.float32

    def check(self):
        if self.heads % self.kv_heads or self.value_heads % self.key_heads:
            raise UserException("heads (%d) must be a multiple of kv-heads (%d), and value-heads "
                                "(%d) of key-heads (%d)" % (self.heads, self.kv_heads,
                                                            self.value_heads, self.key_heads))
        if self.chunk < 1 or self.chunk & (self.chunk - 1) or self.conv < 1:
            raise UserException("chunk (%d) must be a power of two, and conv (%d) positive"
                                % (self.chunk, self.conv))
        if self.layers < 1 or self.full_interval < 1 or not 0 < self.rotary <= 1 \
                or self.head_dim * self.rotary % 2:
            raise UserException("layers (%d) and full-interval (%d) must be positive, and rotary "
                                "(%r) leave head-dim (%d) whole pairs" % (
                                    self.layers, self.full_interval, self.rotary, self.head_dim))
        if self.seq % self.attn_chunk:
            raise UserException("seq (%d) must divide into attn-chunk (%d)"
                                % (self.seq, self.attn_chunk))
        if not self.experts_held or not all(0 <= e < self.experts for e in self.experts_held):
            raise UserException("experts-held %r must name some of the %d experts"
                                % (self.experts_held, self.experts))
        return self

    def kinds(self):
        """One kind a layer: the mixer it has."""
        return [FULL if (i + 1) % self.full_interval == 0 else DELTA for i in range(self.layers)]

    def runs(self):
        """[(kind, how many consecutive layers)] (models/laguna.py ``layer_runs``)."""
        return [(kind, len(list(alike))) for kind, alike in itertools.groupby(self.kinds())]


def run_shapes(cfg, kind, count):
    """{leaf: shape} of one run: its layers' leaves on a leading axis.  The two
    kinds share the feed-forward's leaves and differ in the mixer's."""
    d, held = cfg.hidden, len(cfg.experts_held)
    if kind == DELTA:
        keys, values = cfg.key_heads * cfg.key_dim, cfg.value_heads * cfg.value_dim
        mixer = {"w_qkvz": (d, 2 * keys + 2 * values), "w_ba": (d, 2 * cfg.value_heads),
                 "conv": (2 * keys + values, cfg.conv), "A_log": (cfg.value_heads,),
                 "dt_bias": (cfg.value_heads,), "o_norm": (cfg.value_dim,), "wo": (values, d)}
    else:
        wide, narrow = cfg.heads * cfg.head_dim, cfg.kv_heads * cfg.head_dim
        mixer = {"wq": (d, 2 * wide), "wk": (d, narrow), "wv": (d, narrow),
                 "q_norm": (cfg.head_dim,), "k_norm": (cfg.head_dim,), "wo": (wide, d)}
    shapes = dict(mixer, **{
        "attn_norm": (d,), "mlp_norm": (d,), "router": (d, cfg.experts), "shared_gate": (d, 1),
        "ws_gate": (d, cfg.shared_width), "ws_up": (d, cfg.shared_width),
        "ws_down": (cfg.shared_width, d),
        "we_gate": (held, d, cfg.expert_width), "we_up": (held, d, cfg.expert_width),
        "we_down": (held, cfg.expert_width, d)})
    return {name: (count,) + shape for name, shape in shapes.items()}


def leaf_shapes(cfg):
    """The parameters' tree of shapes: the runs are a list under ``layers``."""
    return {"embed": (cfg.vocab, cfg.hidden), "head": (cfg.hidden, cfg.vocab),
            "final_norm": (cfg.hidden,),
            "layers": [run_shapes(cfg, kind, count) for kind, count in cfg.runs()]}


def init_params(cfg, key):
    """models/laguna.py's seeded leaves (every matrix, the convolution and the
    shared expert's gate N(0, INIT_STD^2)), and this family's own: a zero-centred
    norm's offset at ZERO (a scale of one; ``o_norm``, a plain scale, at one),
    and the decay's two leaves by ``decay_leaves``."""
    params = seeded_leaves(leaf_shapes(cfg), key)
    params["final_norm"] = jnp.zeros_like(params["final_norm"])
    for place, ((kind, _), group) in enumerate(zip(cfg.runs(), params["layers"])):
        for name in ("attn_norm", "mlp_norm", "q_norm", "k_norm"):
            if name in group:
                group[name] = jnp.zeros_like(group[name])
        if kind == DELTA:
            group["A_log"], group["dt_bias"] = decay_leaves(
                jax.random.fold_in(key, 1000 + place), group["A_log"].shape)
    return params


def decay_leaves(key, shape):
    """(A_log, dt_bias) of a run: ``A`` ~ U(A_FLOOR, A_MAX) and ``dt =
    softplus(dt_bias)`` log-uniform over DT_RANGE, a value head a layer: a head
    forgets ``exp(-A dt)`` a token at a gate's input of zero, from 0.2 of its
    state to a thousandth of a percent."""
    a_key, dt_key = jax.random.split(key)
    a = jax.random.uniform(a_key, shape, jnp.float32, A_FLOOR, A_MAX)
    low, high = (math.log(bound) for bound in DT_RANGE)
    dt = jnp.exp(jax.random.uniform(dt_key, shape, jnp.float32, low, high))
    return jnp.log(a), dt + jnp.log(-jnp.expm1(-dt))


# --------------------------------------------------------------------------- #
#  The gated delta rule, chunk by chunk                                       #
# --------------------------------------------------------------------------- #


def unit_lower_inverse(a):
    """``(I + a)^-1`` of strictly lower-triangular ``a`` (..., C, C), C a power of
    two: the inverse of a unit lower-triangular matrix is that of its two
    diagonal halves and ``-T22 a21 T11`` between them, so from the inverses of
    the 1 x 1 blocks (ones) each round doubles the blocks that are inverted,
    ``T <- T - T (a between the halves of a block) T``: log2 C rounds of two
    products each, every block of a round at once, and no longer chain of
    products than forward substitution has."""
    size = a.shape[-1]
    index = jnp.arange(size)
    together = lambda block: (index[:, None] // block) == (index[None, :] // block)
    inverse = jnp.broadcast_to(jnp.eye(size, dtype=a.dtype), a.shape)
    block = 1
    while block < size:
        between = jnp.where(together(2 * block) & ~together(block), a, 0)
        inverse = inverse - inverse @ between @ inverse
        block *= 2
    return inverse


def chunked_delta_rule(q, k, v, g, beta, chunk):
    """The gated delta rule of the module docstring over whole sequences, S_0 =
    0: q and k (B, L, H, Dk), L2-normalised and q scaled by its caller, v (B, L,
    H, Dv), g and beta (B, L, H), all float32 -> (o (B, L, H, Dv), the last
    state (B, H, Dk, Dv)).  L is padded to whole chunks with positions that
    neither decay nor write (g = 0, beta = 0) and whose outputs are dropped.

    With ``G`` the cumulative sum of g inside a chunk, ``D[i, j] = exp(G_i -
    G_j)`` for j <= i, ``T = (I + tril(diag(beta) K K^T * D, -1))^-1``, ``U = T
    diag(beta) V`` and ``W = T diag(beta exp(G)) K``, a chunk entered with state
    S writes ``V' = U - W S`` (each position's update, with what the chunk's
    earlier positions and the state already predict taken out), reads ``o =
    (q exp(G)) S + tril(Q K^T * D) V'`` and leaves ``exp(G_last) S + (K exp(G_last
    - G))^T V'``."""
    b, length, heads, dk = q.shape
    pad = -length % chunk
    if pad:
        q, k, v, g, beta = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                            for a in (q, k, v, g, beta))
    nb = (length + pad) // chunk
    # (B, L, H, ...) -> (B, H, chunks, chunk, ...)
    cut = lambda a: jnp.moveaxis(a.reshape((b, nb, chunk) + a.shape[2:]), 3, 1)
    q, k, v, g, beta = (cut(a) for a in (q, k, v, g, beta))
    total = jnp.cumsum(g, axis=-1)
    index = jnp.arange(chunk)
    upto = index[:, None] >= index[None, :]
    # masked BEFORE the exponential: above the diagonal the difference is positive and large
    decay = jnp.exp(jnp.where(upto, total[..., :, None] - total[..., None, :], _NEG))
    k_beta = k * beta[..., None]
    system = jnp.where(index[:, None] > index[None, :],
                       jnp.einsum("...id,...jd->...ij", k_beta, k) * decay, 0)
    solved = unit_lower_inverse(system)
    writes = solved @ (v * beta[..., None])                          # U
    predicts = solved @ (k_beta * jnp.exp(total)[..., None])         # W
    within = jnp.einsum("...id,...jd->...ij", q, k) * decay
    q_decayed = q * jnp.exp(total)[..., None]
    last = total[..., -1:]
    k_left = k * jnp.exp(last - total)[..., None]
    leaves = jnp.exp(last)[..., None]

    def one_chunk(state, blocks):
        writes, predicts, within, q_decayed, k_left, leaves = blocks
        new = writes - predicts @ state
        out = q_decayed @ state + within @ new
        after = leaves * state + jnp.swapaxes(k_left, -1, -2) @ new
        return after, out

    by_chunk = lambda a: jnp.moveaxis(a, 2, 0)
    state = jnp.zeros((b, heads, dk, v.shape[-1]), jnp.float32)
    state, out = jax.lax.scan(one_chunk, state, tuple(
        by_chunk(a) for a in (writes, predicts, within, q_decayed, k_left, leaves)))
    # (chunks, B, H, chunk, Dv) -> (B, L, H, Dv)
    out = out.transpose(1, 0, 3, 2, 4).reshape(b, nb * chunk, heads, -1)
    return out[:, :length], state


def delta_rule(q, k, v, g, beta, chunk):
    """``chunked_delta_rule``'s contract through ops/delta_rule.py's chooser: its
    kernel pair on a TPU for the shapes it takes, ``chunked_delta_rule``
    everywhere else."""
    return gated_delta_rule(q, k, v, g, beta, chunk, chunked_delta_rule)


def causal_conv(x, taps):
    """Depthwise causal convolution: x (B, L, C), taps (C, K) -> (B, L, C),
    ``y_t = sum_j taps[:, j] * x_{t - (K - 1) + j}`` with zeros before the
    sequence; the last tap is the position's own."""
    width = taps.shape[-1]
    padded = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    return sum(padded[:, j:j + x.shape[1]] * taps[:, j] for j in range(width))


#: what ``l2_normalised`` adds to a head's sum of squares
L2_EPS = 1e-6


def l2_normalised(x, eps=L2_EPS):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + eps)


def split_heads(projected, taps, cfg):
    """``u @ w_qkvz`` (B, L, 2 keys + 2 values) and the convolution's taps -> q
    and k (B, L, H, Dk), v and z (B, L, H, Dv), H the value heads: q, k and v
    through the causal convolution and SiLU, q and k L2-normalised a key head,
    q scaled, and handed to the value heads they serve; q, k, v float32."""
    b, length, _ = projected.shape
    keys, values = cfg.key_heads * cfg.key_dim, cfg.value_heads * cfg.value_dim
    rep = cfg.value_heads // cfg.key_heads
    mixed, z = jnp.split(projected, [2 * keys + values], axis=-1)
    mixed = jax.nn.silu(causal_conv(mixed, taps)).astype(jnp.float32)
    q, k, v = jnp.split(mixed, [keys, 2 * keys], axis=-1)
    by_key_head = lambda a: jnp.repeat(
        l2_normalised(a.reshape(b, length, cfg.key_heads, cfg.key_dim)), rep, axis=2)
    heads = (b, length, cfg.value_heads, cfg.value_dim)
    return by_key_head(q) * cfg.key_dim ** -0.5, by_key_head(k), v.reshape(heads), z.reshape(heads)


def delta_heads(u, layer, cfg):
    """(B, L, D) normed inputs -> q and k (B, L, H, Dk), v and z (B, L, H, Dv),
    g and beta (B, L, H), H the value heads; all but z float32.  q, k, v and z
    come out of the projection through ops/gdn_operands.py's chooser: its kernel
    pair on a TPU for the shapes it takes, ``split_heads`` everywhere else."""
    w = lambda name: layer[name].astype(cfg.dtype)
    q, k, v, z = gdn_operands(
        u @ w("w_qkvz"), w("conv"), cfg.key_heads, cfg.value_heads, cfg.key_dim, cfg.value_dim,
        L2_EPS, lambda projected, taps: split_heads(projected, taps, cfg))
    gates = (u @ w("w_ba")).astype(jnp.float32)
    beta = jax.nn.sigmoid(gates[..., :cfg.value_heads])
    g = -jnp.exp(layer["A_log"].astype(jnp.float32)) * jax.nn.softplus(
        gates[..., cfg.value_heads:] + layer["dt_bias"].astype(jnp.float32))
    return q, k, v, z, g, beta


def gated_delta_net(u, layer, cfg):
    """(B, L, D) normed inputs -> (the mixer's output (B, L, D), the sum of exp(g)
    over positions and heads, the RMS of the last state)."""
    b, length, _ = u.shape
    with jax.named_scope("model.gdn_project"):
        q, k, v, z, g, beta = delta_heads(u, layer, cfg)
    with jax.named_scope("model.delta_rule"):
        out, state = delta_rule(q, k, v, g, beta, cfg.chunk)
    with jax.named_scope("model.gdn_project"):
        out = rms_norm(out.astype(cfg.dtype), layer["o_norm"].astype(cfg.dtype), cfg.norm_eps)
        out = (out * jax.nn.silu(z)).reshape(b, length, -1) @ layer["wo"].astype(cfg.dtype)
        return out, jnp.sum(jnp.exp(g)), jnp.sqrt(jnp.mean(jnp.square(state)))


# --------------------------------------------------------------------------- #
#  Gated full attention, the feed-forward, the model                          #
# --------------------------------------------------------------------------- #


def zero_centred(layer, name, dtype):
    """The scale ``1 + w`` of a zero-centred norm, for ``rms_norm``."""
    return 1 + layer[name].astype(dtype)


def attention_heads(u, layer, cfg):
    """(B, L, D) normed inputs -> q (B, L, G, R, Dh), k and v (B, L, G, Dh) and
    the output's gate (B, L, G * R * Dh), the query projection's second half:
    zero-centred per-head norm of q and k, then rotary on a head's first lanes."""
    b, length, _ = u.shape
    g, r, dh = cfg.kv_heads, cfg.heads // cfg.kv_heads, cfg.head_dim
    w = lambda name: layer[name].astype(cfg.dtype)
    turn = lambda x: rope(x, jnp.arange(length),
                          rope_frequencies(int(dh * cfg.rotary), cfg.rope_theta))
    normed = lambda x, name: rms_norm(x, zero_centred(layer, name, cfg.dtype), cfg.norm_eps)
    q, gate = jnp.split(u @ w("wq"), 2, axis=-1)
    q = turn(normed(q.reshape(b, length, g * r, dh), "q_norm"))
    k = turn(normed((u @ w("wk")).reshape(b, length, g, dh), "k_norm"))
    return q.reshape(b, length, g, r, dh), k, (u @ w("wv")).reshape(b, length, g, dh), gate


def gated_attention(u, layer, cfg):
    """(B, L, D) normed inputs -> (B, L, D): causal attention whose output a
    sigmoid of its gate scales, lane by lane, in front of ``W_o``."""
    q, k, v, gate = attention_heads(u, layer, cfg)
    attended = causal_attention(q, k, v, cfg, None)
    return (attended * jax.nn.sigmoid(gate)) @ layer["wo"].astype(cfg.dtype)


def sparse_ffn(u, layer, cfg):
    """(B, S, D) -> (the held experts' part plus the gated shared expert,
    positions routed to held experts, held experts idle)."""
    b, s, d = u.shape
    tokens = u.reshape(b * s, d)
    with jax.named_scope("model.router"):
        weights, chosen = route(tokens, layer["router"].astype(cfg.dtype), cfg)
    with jax.named_scope("model.experts"):
        out, routed, idle = held_experts(tokens, weights, chosen, layer, cfg.experts_held,
                                         cfg.dtype)
    with jax.named_scope("model.shared_expert"):
        gate = jax.nn.sigmoid(tokens @ layer["shared_gate"].astype(cfg.dtype))
        out = out + gate * gated_unit(tokens, layer, "ws", cfg.dtype)
    return out.reshape(b, s, d), routed, idle


def decoder_layer(x, layer, cfg, kind):
    norm = lambda x, name: rms_norm(x, zero_centred(layer, name, cfg.dtype), cfg.norm_eps)
    if kind == DELTA:
        with jax.named_scope("model.gdn_project"):
            u = norm(x, "attn_norm")
        mixed, decay, state_rms = gated_delta_net(u, layer, cfg)
    else:
        with jax.named_scope("model.attention_full"):
            mixed = gated_attention(norm(x, "attn_norm"), layer, cfg)
        decay, state_rms = jnp.float32(0), jnp.float32(0)
    x = x + mixed
    y, routed, idle = sparse_ffn(norm(x, "mlp_norm"), layer, cfg)
    return x + y, routed, idle, decay, state_rms


def loss_and_counters(params, batch, cfg):
    """``batch``: ``tokens`` (B, L + 1).  Returns the next-token loss (mean
    over the B x L positions) and the step's counters: the two of the expert
    layers, ``mean_decay`` (the mean over the DeltaNet layers, positions and
    heads of exp(g): what a state keeps of itself a token) and ``state_rms``
    (the RMS of a layer's last state, summed over the DeltaNet layers)."""
    inputs, targets = batch["tokens"][:, :-1], batch["tokens"][:, 1:]
    with jax.named_scope("model.embed"):
        x = params["embed"][inputs].astype(cfg.dtype)
    x, routed, idle, decay, state_rms = layer_runs(
        x, (jnp.float32(0),) * 4, cfg.runs(), params["layers"],
        lambda x, leaves, kind: decoder_layer(x, leaves, cfg, kind))
    gates = cfg.kinds().count(DELTA) * inputs.size * cfg.value_heads
    zero_centred_head = dict(params, final_norm=1 + params["final_norm"])
    return next_token_loss(x, zero_centred_head, targets, cfg), {
        "routed_positions": routed, "idle_held_experts": idle,
        "mean_decay": decay / max(gates, 1), "state_rms": state_rms}


class Qwen3NextExperiment(LagunaExperiment):
    """Next-token training of one chip's share of Qwen3-Next-80B-A3B.

    Args (key:value), defaults = grid/configs/qwen3next-80b-a3b-ep64-n3.json:
    vocab:18992 hidden:2048 layers:4 full-interval:4 heads:16 kv-heads:2
    head-dim:256 rotary:0.25 rope-theta:10000000 key-heads:16 value-heads:32
    key-dim:128 value-dim:128 conv:4 chunk:64 experts:512 experts-per-token:10
    expert-width:512 shared-width:512 experts-held:0-7 norm-eps:1e-06 seq:4096
    attn-chunk:256 batch-size:1 corpus:256 dtype:float32.  The batch a worker is
    handed is ``{"tokens": (B, seq + 1)}``; feeds and metrics are
    models/laguna.py's.
    """

    #: the configuration's sizes that are arguments under their own names
    SIZES = tuple(field.name for field in dataclasses.fields(Qwen3NextConfig)
                  if field.name not in ("experts_held", "dtype"))
    init_params = staticmethod(init_params)
    loss_and_counters = staticmethod(loss_and_counters)

    def __init__(self, args):
        Experiment.__init__(self, args)  # the arguments are this family's, not Laguna's
        base, dashed = Qwen3NextConfig(), lambda name: name.replace("_", "-")
        kv = parse_keyval(args, strict=True, defaults=dict(
            {dashed(name): getattr(base, name) for name in self.SIZES},
            **{"experts-held": "0-7", "batch-size": 1, "corpus": 256, "dtype": "float32"}))
        self.cfg = Qwen3NextConfig(
            experts_held=_parse_held(kv["experts-held"]), dtype=check_dtype(kv["dtype"]),
            **{name: kv[dashed(name)] for name in self.SIZES}).check()
        self.batch_size = kv["batch-size"]
        self.corpus = seeded_corpus(kv["corpus"], self.cfg.seq, self.cfg.vocab)


register("qwen3_next", Qwen3NextExperiment)
