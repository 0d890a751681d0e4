"""Llama-style transformer with TPU-native 4D parallelism.

The reference has no attention models at all (SURVEY.md §5) — this family
exists for the driver's stretch config 5 ("Llama-class fine-tune with
per-layer Krum", BASELINE.md) and makes long-context + multi-axis sharding
first-class citizens of the framework:

- **TP** — SwiGLU MLP weights are column/row-sharded over the ``model`` mesh
  axis, Megatron-SP style: activations stay *sequence*-sharded between
  blocks, one ``all_gather`` enters the MLP, one ``psum_scatter`` leaves it.
- **SP (long context)** — ring attention over the ``model`` axis: K/V blocks
  rotate around the ring with ``ppermute`` while a numerically-stable online
  softmax accumulates, so no device ever materializes the (S, S) score
  matrix or the full sequence. Peak activation memory is O(S/T) per device.
- **EP** — optional switch-routed MoE MLPs; experts are sharded over the
  ``model`` axis and tokens travel through one ``all_to_all`` each way.
- **PP** — GPipe microbatch pipelining over the ``pipe`` axis: stages pass
  activations with ``ppermute`` inside a ``lax.scan`` over M + P - 1 ticks;
  autodiff flows backwards through the same ring (transpose of ppermute).

Everything is written to run *inside* ``jax.shard_map`` (see
parallel/engine.py, sharded mode) and degrades to plain single-device math when the
mesh axes have size 1 — the same code path serves the 8-device CPU test mesh
and a multi-host TPU pod.

Parameters are a plain pytree of arrays whose leading dimension is the
pipeline stage; ``param_specs`` gives the matching ``PartitionSpec`` tree.
"""

import dataclasses
import math
import os

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .. import config as global_config

_NEG = -1e30  # finite mask value: keeps the online softmax NaN-free


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    """Static architecture hyper-parameters (Llama-style defaults)."""

    vocab_size: int = 256
    d_model: int = 64
    n_heads: int = 4
    n_layers: int = 4
    d_ff: int = 0            # 0 -> 4 * d_model
    n_experts: int = 0       # 0 -> dense SwiGLU MLP; > 0 -> switch MoE
    capacity_factor: float = 1.5
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    dtype: object = jnp.float32
    remat: bool = True

    @property
    def head_dim(self):
        return self.d_model // self.n_heads

    @property
    def ff_dim(self):
        return self.d_ff if self.d_ff else 4 * self.d_model


# --------------------------------------------------------------------------- #
#  Parameter construction                                                     #
# --------------------------------------------------------------------------- #


#: Leaves with NO leading (n_stages, layers/stage) stage dims — every other
#: leaf is stage-stacked.  Shared by the dense forward, the pipeline loss,
#: and the stage-collapse in ``sharded_to_dense_params`` so a new
#: non-stacked leaf only needs declaring once.
NON_STACKED_LEAVES = ("embed", "unembed", "final_norm")


def init_params(cfg, key, n_stages=1):
    """Build the global parameter pytree; leaves lead with the stage dim."""
    if cfg.n_layers % n_stages != 0:
        raise ValueError("n_layers (%d) must divide into %d stages" % (cfg.n_layers, n_stages))
    lp = cfg.n_layers // n_stages
    d, h, dh, f, v, e = cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.ff_dim, cfg.vocab_size, cfg.n_experts
    ks = iter(jax.random.split(key, 16))

    def dense(k, *shape):
        fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
        return (jax.random.normal(k, shape) / math.sqrt(fan_in)).astype(cfg.dtype)

    params = {
        "embed": dense(next(ks), v, d),
        "unembed": dense(next(ks), d, v),
        "final_norm": jnp.ones((d,), cfg.dtype),
        "attn_norm": jnp.ones((n_stages, lp, d), cfg.dtype),
        "mlp_norm": jnp.ones((n_stages, lp, d), cfg.dtype),
        "wq": dense(next(ks), n_stages, lp, d, h * dh),
        "wk": dense(next(ks), n_stages, lp, d, h * dh),
        "wv": dense(next(ks), n_stages, lp, d, h * dh),
        "wo": dense(next(ks), n_stages, lp, h * dh, d),
    }
    if e:
        params.update(
            {
                "router": dense(next(ks), n_stages, lp, d, e),
                "we_gate": dense(next(ks), n_stages, lp, e, d, f),
                "we_up": dense(next(ks), n_stages, lp, e, d, f),
                "we_down": dense(next(ks), n_stages, lp, e, f, d),
            }
        )
    else:
        params.update(
            {
                "w_gate": dense(next(ks), n_stages, lp, d, f),
                "w_up": dense(next(ks), n_stages, lp, d, f),
                "w_down": dense(next(ks), n_stages, lp, f, d),
            }
        )
    return params


def param_specs(cfg):
    """PartitionSpec per leaf over the (worker, pipe, model) mesh.

    Workers replicate every parameter (the Byzantine-DP axis never shards
    weights); ``pipe`` shards the stage dim; MLP weights (or experts) shard
    over ``model``; everything else is replicated over ``model`` because
    activations are sequence-sharded there.
    """
    pa, ma = global_config.pipe_axis, global_config.model_axis
    specs = {
        "embed": P(),
        "unembed": P(),
        "final_norm": P(),
        "attn_norm": P(pa, None, None),
        "mlp_norm": P(pa, None, None),
        "wq": P(pa, None, None, None),
        "wk": P(pa, None, None, None),
        "wv": P(pa, None, None, None),
        "wo": P(pa, None, None, None),
    }
    if cfg.n_experts:
        specs.update(
            {
                "router": P(pa, None, None, None),
                "we_gate": P(pa, None, ma, None, None),
                "we_up": P(pa, None, ma, None, None),
                "we_down": P(pa, None, ma, None, None),
            }
        )
    else:
        specs.update(
            {
                "w_gate": P(pa, None, None, ma),
                "w_up": P(pa, None, None, ma),
                "w_down": P(pa, None, ma, None),
            }
        )
    return specs


# --------------------------------------------------------------------------- #
#  Building blocks                                                            #
# --------------------------------------------------------------------------- #


def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale


def rope_frequencies(width, theta):
    """The default table: ``theta^(-2i / width)`` for the ``width / 2`` pairs."""
    return jnp.exp(-jnp.arange(0, width, 2, dtype=jnp.float32) * (math.log(theta) / width))


def rope(x, positions, inv_freq, factor=None, sections=None):
    """Rotary embedding; ``positions`` are *global* so SP blocks stay aligned.

    ``inv_freq`` is one inverse frequency a rotated pair (``rope_frequencies``,
    or a table of the caller's own such as YaRN's blend): the first
    ``2 * len(inv_freq)`` dims of each head turn, pair (2i, 2i + 1) by
    ``position * inv_freq[i]``, and the rest pass as they are; ``factor``,
    where given, scales cos and sin.  With ``sections`` (``mrope_section`` of a
    config: how many pairs turn by each kind of position id, adding up to
    ``len(inv_freq)``) a token has one id a section and ``positions`` is
    (len(sections), s): pair i turns by the id of the section it lies in
    (models/keye_vl2.py: temporal, height, width; three equal ids are one)."""
    b, s, h, dh = x.shape
    width = 2 * inv_freq.shape[0]
    if sections is not None:  # (s, width/2): each pair's own kind of position
        by_pair = jnp.concatenate([jnp.broadcast_to(ids[:, None], (s, pairs))
                                   for ids, pairs in zip(positions, sections)], axis=1)
        angles = by_pair.astype(jnp.float32) * inv_freq[None, :]
    else:
        angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]  # (s, width/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if factor is not None:
        cos, sin = factor * cos, factor * sin
    turned = x if width == dh else x[..., :width]
    x1, x2 = turned[..., 0::2], turned[..., 1::2]
    rx1 = x1 * cos[None, :, None, :] - x2 * sin[None, :, None, :]
    rx2 = x1 * sin[None, :, None, :] + x2 * cos[None, :, None, :]
    turned = jnp.concatenate([rx1[..., None], rx2[..., None]], axis=-1).reshape(b, s, h, width).astype(x.dtype)
    return turned if width == dh else jnp.concatenate([turned, x[..., width:]], axis=-1)


def online_softmax_step(scores, weigh, num, den, mx):
    """Fold one block of keys into a running softmax: ``scores`` (..., q, k)
    are float32 and already masked with ``_NEG``, ``weigh(p)`` gives the
    block's (..., q, d) sum of values under the weights ``p``.  Shared with
    models/sdar.py, whose heads are grouped and whose mask is not causal."""
    new_mx = jnp.maximum(mx, scores.max(axis=-1))
    corr = jnp.exp(mx - new_mx)
    p = jnp.exp(scores - new_mx[..., None])
    num = num * corr[..., None] + weigh(p)
    den = den * corr + p.sum(axis=-1)
    return num, den, new_mx


def _attend_block(q, k, v, q_pos, k_pos, num, den, mx):
    """One online-softmax accumulation step of blockwise causal attention."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    mask = q_pos[:, None] >= k_pos[None, :]
    scores = jnp.where(mask[None, None], scores, _NEG)
    return online_softmax_step(
        scores, lambda p: jnp.einsum("bhqk,bkhd->bhqd", p, v.astype(jnp.float32)), num, den, mx)


def ring_attention(q, k, v, positions, axis):
    """Blockwise causal attention; K/V ride a ``ppermute`` ring over ``axis``.

    q/k/v: (B, S_blk, H, Dh) sequence-sharded over ``axis`` (or the full
    sequence when ``axis`` is None). ``positions``: (S_blk,) global positions
    of the local block. Returns (B, S_blk, H, Dh).
    """
    b, sb, h, dh = q.shape
    num = jnp.zeros((b, h, sb, dh), jnp.float32)
    den = jnp.zeros((b, h, sb), jnp.float32)
    mx = jnp.full((b, h, sb), _NEG, jnp.float32)
    if axis is None:
        num, den, mx = _attend_block(q, k, v, positions, positions, num, den, mx)
    else:
        t_size = jax.lax.psum(1, axis)
        my = jax.lax.axis_index(axis)
        perm = [(i, (i + 1) % t_size) for i in range(t_size)]

        def body(carry, i):
            kc, vc, num, den, mx = carry
            src = (my - i) % t_size  # who produced the K/V block we now hold
            k_pos = src * sb + jnp.arange(sb)
            num, den, mx = _attend_block(q, kc, vc, positions, k_pos, num, den, mx)
            kc = jax.lax.ppermute(kc, axis, perm)
            vc = jax.lax.ppermute(vc, axis, perm)
            return (kc, vc, num, den, mx), None

        body = jax.checkpoint(body)
        (_, _, num, den, mx), _ = jax.lax.scan(body, (k, v, num, den, mx), jnp.arange(t_size))
    out = num / jnp.maximum(den[..., None], 1e-30)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)  # (B, S_blk, H, Dh)


def attention_block(x, positions, wq, wk, wv, wo, cfg, axis):
    b, sb, d = x.shape
    h, dh = cfg.n_heads, cfg.head_dim
    turn = lambda heads: rope(heads, positions, rope_frequencies(dh, cfg.rope_theta))
    q = turn((x @ wq).reshape(b, sb, h, dh))
    k = turn((x @ wk).reshape(b, sb, h, dh))
    v = (x @ wv).reshape(b, sb, h, dh)
    out = ring_attention(q, k, v, positions, axis)
    return out.reshape(b, sb, h * dh) @ wo


def mlp_block(x, w_gate, w_up, w_down, axis):
    """Megatron-SP SwiGLU: gather seq -> TP matmuls -> psum_scatter seq."""
    if axis is not None and jax.lax.psum(1, axis) > 1:
        xg = jax.lax.all_gather(x, axis, axis=1, tiled=True)  # (B, S, D)
        y = (jax.nn.silu(xg @ w_gate) * (xg @ w_up)) @ w_down  # partial over F
        return jax.lax.psum_scatter(y, axis, scatter_dimension=1, tiled=True)
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def moe_block(x, router, we_gate, we_up, we_down, cfg, axis):
    """Switch (top-1) MoE with experts sharded over ``axis``.

    Tokens are dispatched into per-expert capacity slots (static shapes for
    XLA), travel to the expert owners through one ``all_to_all``, and return
    the same way. Returns (output, load-balancing aux loss).
    """
    b, sb, d = x.shape
    tokens = x.reshape(b * sb, d)
    n = tokens.shape[0]
    e = cfg.n_experts
    t_size = 1 if axis is None else jax.lax.psum(1, axis)
    el = e // t_size  # local experts per device

    logits = tokens @ router  # (N, E)
    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    expert = jnp.argmax(gates, axis=-1)
    gate = jnp.max(gates, axis=-1)
    onehot = jax.nn.one_hot(expert, e, dtype=jnp.float32)  # (N, E)

    # Load-balancing aux (Switch Transformer): E * <fraction routed> . <mean gate>
    aux = e * jnp.mean(jnp.mean(onehot, axis=0) * jnp.mean(gates, axis=0))

    cap = max(1, int(math.ceil(n * cfg.capacity_factor / e)))
    pos = jnp.einsum("ne,ne->n", jnp.cumsum(onehot, axis=0) - 1.0, onehot).astype(jnp.int32)
    keep = (pos < cap).astype(jnp.float32)
    dispatch = onehot * keep[:, None]  # (N, E) tokens that fit capacity
    disp_tensor = dispatch[..., None] * jax.nn.one_hot(pos, cap, dtype=jnp.float32)[:, None, :]  # (N, E, C)

    expert_in = jnp.einsum("nec,nd->ecd", disp_tensor, tokens.astype(jnp.float32))  # (E, C, D)
    if t_size > 1:
        ei = expert_in.reshape(t_size, el, cap, d)
        ei = jax.lax.all_to_all(ei, axis, split_axis=0, concat_axis=0, tiled=True)
        expert_in = ei.reshape(t_size, el, cap, d).transpose(1, 0, 2, 3).reshape(el, t_size * cap, d)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", expert_in, we_gate)) * jnp.einsum(
        "ecd,edf->ecf", expert_in, we_up
    )
    expert_out = jnp.einsum("ecf,efd->ecd", h, we_down)  # (El, T*C, D)
    if t_size > 1:
        eo = expert_out.reshape(el, t_size, cap, d).transpose(1, 0, 2, 3)  # (T, El, C, D)
        eo = jax.lax.all_to_all(eo, axis, split_axis=0, concat_axis=0, tiled=True)
        expert_out = eo.reshape(e, cap, d)
    combine = disp_tensor * gate[:, None, None]
    out = jnp.einsum("nec,ecd->nd", combine, expert_out)
    return out.reshape(b, sb, d).astype(x.dtype), aux.astype(jnp.float32)


def _layer(x, positions, lp_params, cfg, axis):
    """One pre-norm transformer block on a (B, S_blk, D) activation."""
    x = x + attention_block(
        rms_norm(x, lp_params["attn_norm"], cfg.norm_eps),
        positions,
        lp_params["wq"],
        lp_params["wk"],
        lp_params["wv"],
        lp_params["wo"],
        cfg,
        axis,
    )
    h = rms_norm(x, lp_params["mlp_norm"], cfg.norm_eps)
    if cfg.n_experts:
        y, aux = moe_block(
            h, lp_params["router"], lp_params["we_gate"], lp_params["we_up"], lp_params["we_down"], cfg, axis
        )
    else:
        y, aux = mlp_block(h, lp_params["w_gate"], lp_params["w_up"], lp_params["w_down"], axis), 0.0
    return x + y, aux


def stage_forward(x, positions, stage_params, cfg, axis):
    """Apply this stage's layers (scanned over the layer dim) to one microbatch."""

    def body(carry, lp_params):
        x, aux = carry
        x, a = _layer(x, positions, lp_params, cfg, axis)
        return (x, aux + a), None

    if cfg.remat:
        body = jax.checkpoint(body)
    (x, aux), _ = jax.lax.scan(body, (x, jnp.float32(0.0)), stage_params)
    return x, aux


# --------------------------------------------------------------------------- #
#  Dense (collective-free) path — DP engine / tests / bench                   #
# --------------------------------------------------------------------------- #


def forward_dense(params, tokens, cfg):
    """Plain single-device forward: (B, S) int tokens -> (B, S, V) logits.

    Vmappable and collective-free; this is what the registered experiment
    uses under the data-parallel RobustEngine.
    """
    stage_params = {
        k: v[0] for k, v in params.items() if k not in NON_STACKED_LEAVES
    }
    x = params["embed"][tokens]
    positions = jnp.arange(tokens.shape[1])
    x, aux = stage_forward(x, positions, stage_params, cfg, axis=None)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x @ params["unembed"], aux


def loss_dense(params, batch, cfg, aux_weight=1e-2):
    logits, aux = forward_dense(params, batch["tokens"], cfg)
    targets = batch["targets"]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(nll) + aux_weight * aux


# --------------------------------------------------------------------------- #
#  Pipelined, fully-sharded path — runs inside shard_map                      #
# --------------------------------------------------------------------------- #


def make_pipeline_loss(cfg, n_stages, microbatches, aux_weight=1e-2):
    """Build loss(params_local, batch_local) for use INSIDE shard_map.

    The returned function sees *local* parameter shards (leading stage dim of
    size 1) and a per-worker batch dict with ``tokens``/``targets`` of shape
    (B, S); B must divide into ``microbatches``. It uses collectives over the
    ``pipe`` axis (GPipe activation ring) and the ``model`` axis (ring
    attention, Megatron-SP gathers, MoE all_to_all).

    It returns the **local partial loss**: the sum over the (pipe, model)
    worker group equals the batch loss. Differentiate it as-is — the
    transposes of the in-group collectives assemble the exact gradient of
    that sum on each device (a final in-loss psum would instead *overcount*
    cotangents by the group size under shard_map without replication
    tracking). Callers psum the value over (pipe, model) for reporting.
    """
    pa, ma = global_config.pipe_axis, global_config.model_axis

    def loss_fn(params, batch):
        tokens, targets = batch["tokens"], batch["targets"]
        bsz, seq = tokens.shape
        t_size = jax.lax.psum(1, ma)
        p_size = jax.lax.psum(1, pa)
        stage = jax.lax.axis_index(pa)
        midx = jax.lax.axis_index(ma)
        if bsz % microbatches != 0:
            raise ValueError("batch %d not divisible into %d microbatches" % (bsz, microbatches))
        if seq % t_size != 0:
            raise ValueError("sequence %d not divisible over model axis %d" % (seq, t_size))
        mb = bsz // microbatches
        sb = seq // t_size

        # Local sequence block of every microbatch (SP sharding of activations)
        positions = midx * sb + jnp.arange(sb)
        tok_mb = tokens.reshape(microbatches, mb, seq)
        tgt_mb = targets.reshape(microbatches, mb, seq)
        tok_mb = jax.lax.dynamic_slice_in_dim(tok_mb, midx * sb, sb, axis=2)
        tgt_mb = jax.lax.dynamic_slice_in_dim(tgt_mb, midx * sb, sb, axis=2)

        stage_params = {
            k: v[0] for k, v in params.items() if k not in NON_STACKED_LEAVES
        }
        perm = [(i, (i + 1) % p_size) for i in range(p_size)]
        n_ticks = microbatches + p_size - 1

        def tick(carry, t):
            buf, loss_sum, aux_sum = carry
            feed_idx = jnp.clip(t, 0, microbatches - 1)
            # First stage embeds; the vocab gather is skipped elsewhere (the
            # predicate is uniform per stage, so each device runs one branch).
            x = jax.lax.cond(
                stage == 0,
                lambda: params["embed"][
                    jax.lax.dynamic_index_in_dim(tok_mb, feed_idx, keepdims=False)
                ].astype(cfg.dtype),
                lambda: buf,
            )
            x, aux = stage_forward(x, positions, stage_params, cfg, ma)

            # Last stage consumes finished microbatches t - (P-1) .. while
            # valid; the unembed projection (the largest matmul at real vocab
            # sizes) only runs on the last stage thanks to the cond.
            out_idx = jnp.clip(t - (p_size - 1), 0, microbatches - 1)

            def loss_tail():
                xf = rms_norm(x, params["final_norm"], cfg.norm_eps)
                logits = (xf @ params["unembed"]).astype(jnp.float32)
                tgt = jax.lax.dynamic_index_in_dim(tgt_mb, out_idx, keepdims=False)
                logp = jax.nn.log_softmax(logits, axis=-1)
                return jnp.sum(-jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0])

            tick_valid = (t >= p_size - 1).astype(jnp.float32)
            contrib = jax.lax.cond(stage == p_size - 1, loss_tail, lambda: jnp.float32(0.0))
            loss_sum = loss_sum + tick_valid * contrib
            # A stage holds a *real* microbatch (not pipeline-bubble padding)
            # only for ticks stage <= t < stage + M.
            real_mb = jnp.logical_and(t >= stage, t - stage < microbatches)
            aux_sum = aux_sum + jnp.where(real_mb, aux, 0.0)
            buf = jax.lax.ppermute(x, pa, perm) if p_size > 1 else x
            return (buf, loss_sum, aux_sum), None

        buf0 = jnp.zeros((mb, sb, cfg.d_model), cfg.dtype)
        (_, loss_sum, aux_sum), _ = jax.lax.scan(
            tick, (buf0, jnp.float32(0.0), jnp.float32(0.0)), jnp.arange(n_ticks)
        )
        # Local partial: non-final stages contributed 0 to loss_sum; summing
        # over (pipe, model) yields the token-mean CE plus the layer-summed,
        # microbatch/shard-mean aux.
        return loss_sum / (bsz * seq) + aux_weight * aux_sum / (microbatches * t_size)

    return loss_fn


# --------------------------------------------------------------------------- #
#  Registered experiment (dense path, synthetic corpus)                       #
# --------------------------------------------------------------------------- #


def synthetic_corpus(vocab_size, length, seed=0):
    """Deterministic order-2 Markov byte stream — learnable structure with no
    external dataset (the reference's datasets are all downloads/symlinks,
    experiments/mnist.py:51-81; an LM corpus has no such source here)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    trans = rng.dirichlet(np.full(vocab_size, 0.1), size=(vocab_size, vocab_size))
    cum = trans.cumsum(axis=-1)
    uniforms = rng.random(length)
    out = np.empty(length, np.int32)
    a = b = 0
    for i in range(length):
        c = min(int(np.searchsorted(cum[a, b], uniforms[i])), vocab_size - 1)
        out[i] = c
        a, b = b, c
    return out


def code_corpus(max_bytes=4_000_000):
    """REAL byte-level text with zero egress: the Python standard library's
    own source files (PSF-licensed, read locally), concatenated in sorted
    order for determinism.  Code-plus-docstrings has the skewed byte
    statistics and long-range structure a language model actually exploits —
    unlike the uniform/Markov synthetic streams — so bits-per-byte numbers
    against the unigram-entropy baseline mean something (the role real
    MNIST plays for the vision experiments; see also datasets.load_digits8x8).
    """
    import glob as _glob
    import sysconfig

    stdlib = sysconfig.get_paths()["stdlib"]
    chunks, total = [], 0
    for path in sorted(_glob.glob(os.path.join(stdlib, "*.py"))):
        try:
            data = open(path, "rb").read()
        except OSError:
            continue
        chunks.append(data)
        total += len(data)
        if total >= max_bytes:
            break
    blob = b"".join(chunks)[:max_bytes]
    # Fall back only when the STDLIB ran dry (we could not gather what was
    # asked for and what we got is tiny) — an explicitly small max_bytes
    # that was fully satisfied is honored, not silently replaced.
    if len(blob) < max_bytes and len(blob) < 65536:
        return None
    import numpy as np

    return np.frombuffer(blob, np.uint8).astype(np.int32)


from . import Experiment, register  # noqa: E402  (after module-level helpers)
from ..utils import parse_keyval  # noqa: E402


class TransformerExperiment(Experiment):
    """Next-token LM, dense path.

    Args (key:value): vocab:64 d-model:64 heads:4 layers:4 d-ff:0 experts:0
    seq:128 batch-size:16 corpus:65536 corpus-source:markov.

    ``corpus-source:code`` trains on REAL bytes (the Python stdlib's own
    sources, ``code_corpus``) with a held-out final-10% eval split and
    byte vocab 256; the default ``markov`` keeps the deterministic
    synthetic stream (eval windows drawn from the same stream — its
    generator IS the test distribution).  ``.synthetic`` says which.
    """

    def __init__(self, args):
        super().__init__(args)
        kv = parse_keyval(
            args,
            defaults={
                "vocab": 64,
                "d-model": 64,
                "heads": 4,
                "layers": 4,
                "d-ff": 0,
                "experts": 0,
                "seq": 128,
                "batch-size": 16,
                "corpus": 65536,
                "corpus-source": "markov",
            },
        )
        source = str(kv["corpus-source"])
        if source == "code":
            # Real bytes need the full byte vocab regardless of the default.
            kv["vocab"] = max(int(kv["vocab"]), 256)
        self.cfg = TransformerConfig(
            vocab_size=int(kv["vocab"]),
            d_model=int(kv["d-model"]),
            n_heads=int(kv["heads"]),
            n_layers=int(kv["layers"]),
            d_ff=int(kv["d-ff"]),
            n_experts=int(kv["experts"]),
        )
        self.seq = int(kv["seq"])
        self.batch_size = int(kv["batch-size"])
        corpus = code_corpus(int(kv["corpus"])) if source == "code" else None
        if corpus is not None:
            # Held-out eval: the last 10% of REAL text is never trained on.
            split = int(len(corpus) * 0.9)
            self.corpus, self.eval_corpus = corpus[:split], corpus[split:]
            self.synthetic = False
            if self.seq + 1 > len(self.eval_corpus):
                from ..utils import UserException

                # Fail at construction, not after all training at eval time.
                raise UserException(
                    "seq:%d needs at least %d eval bytes but the held-out "
                    "split of corpus:%s has %d — raise corpus or lower seq"
                    % (self.seq, self.seq + 1, kv["corpus"], len(self.eval_corpus)))
        else:
            if source == "code":
                from ..utils import warning

                warning("corpus-source:code unavailable (stdlib too small); "
                        "using the synthetic Markov stream")
            self.corpus = synthetic_corpus(self.cfg.vocab_size, int(kv["corpus"]))
            self.eval_corpus = self.corpus
            self.synthetic = True

    supports_sharded = True

    def init(self, rng):
        return init_params(self.cfg, rng, n_stages=1)

    # --- sharded-engine hooks (cli/runner.py --mesh W,PP,TP) ---
    def sharded_init(self, n_stages):
        return lambda key: init_params(self.cfg, key, n_stages=n_stages)

    def sharded_specs(self):
        return param_specs(self.cfg)

    def sharded_loss(self, n_stages, microbatches):
        return make_pipeline_loss(self.cfg, n_stages=n_stages, microbatches=microbatches)

    def sharded_to_dense_params(self, params):
        """Collapse the stage dim of a (host-resident) stage-stacked pytree:
        (S, L/S, ...) -> (1, L, ...), the ``n_stages=1`` layout every dense
        entry point (forward_dense, metrics) consumes.  Lets the sharded CLI
        path report real eval metrics (accuracy/nll) on a dense replica
        instead of loss only."""
        out = {}
        for name, leaf in params.items():
            if name in NON_STACKED_LEAVES:
                out[name] = leaf
            else:
                out[name] = leaf.reshape((1, leaf.shape[0] * leaf.shape[1]) + leaf.shape[2:])
        return out

    def loss(self, params, batch):
        return loss_dense(params, batch, self.cfg)

    def metrics(self, params, batch):
        logits, _ = forward_dense(params, batch["tokens"], self.cfg)
        pred = jnp.argmax(logits, axis=-1)
        hits = jnp.sum(pred == batch["targets"]).astype(jnp.float32)
        count = jnp.float32(batch["targets"].size)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, batch["targets"][..., None], axis=-1)
        return {"accuracy": (hits, count), "nll": (jnp.sum(nll), count)}

    def _sample(self, rng, nb_workers, batch_size, corpus=None):
        import numpy as np

        corpus = self.corpus if corpus is None else corpus
        starts = rng.integers(0, len(corpus) - self.seq - 1, size=(nb_workers, batch_size))
        idx = starts[..., None] + np.arange(self.seq + 1)
        window = corpus[idx]
        return {"tokens": window[..., :-1], "targets": window[..., 1:]}

    def make_train_iterator(self, nb_workers, seed=0):
        import numpy as np

        rng = np.random.default_rng(seed)
        while True:
            yield self._sample(rng, nb_workers, self.batch_size)

    def make_eval_iterator(self, nb_workers):
        import numpy as np

        rng = np.random.default_rng(10**9)
        for _ in range(4):
            yield self._sample(rng, nb_workers, self.batch_size, corpus=self.eval_corpus)


register("transformer", TransformerExperiment)
