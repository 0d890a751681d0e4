"""SDAR (``model_type: sdar_moe``): a Qwen3-style mixture-of-experts decoder
trained by diffusion over blocks, as ONE CHIP'S SHARE of an expert-parallel
deployment.

Source: https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json.
The layer, every width as published:

    h = x + Attn(RMSNorm(x)),  y = h + MoE(RMSNorm(h))

- **Attn** — ``heads`` query heads and ``kv_heads`` key/value heads of
  ``head_dim``, no biases; RMSNorm over each head's ``head_dim`` of q and of k
  with a learned scale each (the Qwen3 form); RoPE on q and k; each key/value
  head serves ``heads // kv_heads`` consecutive query heads; scores
  ``q.k / sqrt(head_dim)`` under the block-diffusion mask (``allowed``).
- **MoE** — ``p = softmax(W_r u)`` over all ``experts``; the
  ``experts_per_token`` largest, their weights renormalised to sum to one;
  SiLU-gated experts of ``expert_width``.  No token is dropped, there is no
  capacity and no auxiliary loss.  The layer is TOLD WHICH EXPERTS IT HOLDS
  (``experts_held``): the router scores and chooses over all of them, and the
  layer computes the part of the result that its own experts give.  What the
  absent experts would add is left out — that partial sum is what goes on to
  the next layer, as on a chip of the deployment before the exchange that
  this one-chip share runs without (the model-configs guide, section 4).
- **Block diffusion** (the BD3-LM objective SDAR states): a sequence ``x_0``
  of L tokens is cut into blocks of ``block``; draw ``t``, replace each token
  by the mask id with probability ``t`` to get ``x_t`` (``noise``, the
  experiment's in-step ``device_transform``).  The model reads ``[x_t ; x_0]``,
  2L positions, both halves at positions 0..L-1; logits are taken at the noisy
  half; loss = (1/L) sum over masked i of (1/t) * -log softmax(logits_i)[x_0^i].

How it is computed here: the layers run under ``lax.scan`` with
``jax.checkpoint``; attention hands q, k, v and the mask as a predicate
(``BlockDiffusion``) to ops/attention.py, whose fused kernel runs on a TPU at
the shapes it takes; everywhere else (the CPU, and the kernel's oracle in the
tests) it is ``chunked_attention``, plain XLA: the queries a chunk at a time,
each folding the two key ranges a chunk may read (the clean prefix, its own
noisy chunk) into the running softmax it shares with models/transformer.py.
Either way no 2L x 2L score tensor exists and nothing is computed for the
clean->noisy quarter or above the block diagonal's chunk (the kernel's tile
table skips those tiles); each held expert runs over every position under the
weight the router gave it there (``moe`` says why).
"""

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import Experiment, register
from ..utils import UserException, parse_keyval
from ..ops.attention import attend
from .common import check_dtype
from .transformer import _NEG, online_softmax_step, rms_norm, rope, rope_frequencies

#: standard deviation of every matrix's initial entries (norm scales start at 1)
INIT_STD = 0.02
#: ``t`` ~ U(T_MIN, 1): the weight 1/t stays bounded
T_MIN = 1e-3


@dataclasses.dataclass(frozen=True)
class SdarConfig:
    """The published widths, and this chip's share of depth, experts and
    vocabulary (grid/configs/sdar-30b-a3b-ep16-n4.json states the deployment)."""

    vocab: int = 18992          # ids held here; the last one is the mask id
    hidden: int = 2048
    heads: int = 32
    kv_heads: int = 4
    head_dim: int = 128
    layers: int = 4
    experts: int = 128          # what the router scores
    experts_per_token: int = 8
    expert_width: int = 768
    experts_held: tuple = tuple(range(8))
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    seq: int = 2048
    block: int = 4
    attn_chunk: int = 256       # queries a chunk, of each half
    dtype: object = jnp.float32

    @property
    def mask_id(self):
        return self.vocab - 1

    def check(self):
        if self.heads % self.kv_heads:
            raise UserException("heads (%d) must be a multiple of kv-heads (%d)"
                                % (self.heads, self.kv_heads))
        if self.seq % self.attn_chunk or self.attn_chunk % self.block:
            raise UserException("seq (%d) must divide into attn-chunk (%d), and that into "
                                "block (%d)" % (self.seq, self.attn_chunk, self.block))
        if not self.experts_held or not all(0 <= e < self.experts for e in self.experts_held):
            raise UserException("experts-held %r must name some of the %d experts"
                                % (self.experts_held, self.experts))
        return self


def leaf_shapes(cfg):
    """{leaf: shape}; a layer's leaves are stacked on a leading layer axis."""
    d, dh, n, held = cfg.hidden, cfg.head_dim, cfg.layers, len(cfg.experts_held)
    return {
        "embed": (cfg.vocab, d),
        "head": (d, cfg.vocab),
        "final_norm": (d,),
        "attn_norm": (n, d),
        "mlp_norm": (n, d),
        "q_norm": (n, dh),
        "k_norm": (n, dh),
        "wq": (n, d, cfg.heads * dh),
        "wk": (n, d, cfg.kv_heads * dh),
        "wv": (n, d, cfg.kv_heads * dh),
        "wo": (n, cfg.heads * dh, d),
        "router": (n, d, cfg.experts),
        "we_gate": (n, held, d, cfg.expert_width),
        "we_up": (n, held, d, cfg.expert_width),
        "we_down": (n, held, cfg.expert_width, d),
    }


def init_params(cfg, key):
    """Norm scales at one, every matrix N(0, INIT_STD^2), leaf by leaf from
    ``fold_in(key, its place in the sorted names)``."""
    params = {}
    for place, (name, shape) in enumerate(sorted(leaf_shapes(cfg).items())):
        if name.endswith("norm"):
            params[name] = jnp.ones(shape, jnp.float32)
        else:
            params[name] = INIT_STD * jax.random.normal(
                jax.random.fold_in(key, place), shape, jnp.float32)
    return params


# --------------------------------------------------------------------------- #
#  Attention under the block-diffusion mask                                   #
# --------------------------------------------------------------------------- #


def allowed(q_pos, q_noisy, k_pos, k_noisy, block):
    """(q, k) booleans: may the query read the key?  With beta = pos // block:
    noisy->noisy iff the same block; noisy->clean iff the key's block is
    earlier; clean->clean iff it is not later; clean->noisy never."""
    qb, kb = (q_pos // block)[:, None], (k_pos // block)[None, :]
    qn, kn = q_noisy[:, None], k_noisy[None, :]
    return jnp.where(qn, jnp.where(kn, kb == qb, kb < qb), ~kn & (kb <= qb))


@dataclasses.dataclass(frozen=True)
class BlockDiffusion:
    """``allowed`` as a predicate of ops/attention.py, over the indices of a
    sequence [noisy ; clean] of ``half`` positions each cut into blocks of
    ``block``: a noisy query reads its own noisy block and the clean blocks
    before it, a clean query the clean blocks up to its own.

    It runs on numpy indices at trace time (``tile_table``) and on int32 iotas
    inside the Mosaic kernel (``_scores``), so it is written in what lowers
    there: ``index % half`` as a compare and a subtract (an index lies under
    ``2 * half``), ``// block`` as a shift where ``block`` is a power of two,
    and the four rules as comparisons joined by ``&`` and ``|`` — no select
    over booleans."""

    half: int
    block: int

    def _block_of(self, index, clean):
        position = jnp.where(clean, index - self.half, index)
        if self.block & (self.block - 1) == 0:
            return position >> (self.block.bit_length() - 1)
        return position // self.block

    def __call__(self, q_index, k_index):
        q_noisy, q_clean = q_index < self.half, q_index >= self.half
        k_noisy, k_clean = k_index < self.half, k_index >= self.half
        q_block, k_block = self._block_of(q_index, q_clean), self._block_of(k_index, k_clean)
        same, earlier = k_block == q_block, k_block < q_block
        return (q_noisy & k_noisy & same) | (k_clean & (earlier | (q_clean & same)))


def masked_attention(q, k, v, cfg):
    """q (B, 2L, G, R, Dh), k and v (B, 2L, G, Dh), halves [noisy ; clean] ->
    (B, 2L, G * R * Dh): ops/attention.py's fused kernel under the
    ``BlockDiffusion`` predicate where ``attention_form`` says so (a TPU, at
    a shape the kernel takes), ``chunked_attention`` everywhere else."""
    return attend(q, k, v, BlockDiffusion(q.shape[1] // 2, cfg.block),
                  lambda q, k, v: chunked_attention(q, k, v, cfg))


def chunked_attention(q, k, v, cfg):
    """The XLA form, and the kernel's oracle.  Chunk i of the queries (its
    noisy and its clean positions together) reads the clean keys up to its own
    end and its own noisy keys: two folds of the running softmax."""
    b, two_l, g, r, dh = q.shape
    length, chunk = two_l // 2, cfg.attn_chunk
    scale = 1.0 / math.sqrt(dh)
    noisy_out, clean_out = [], []
    q_noisy = jnp.arange(2 * chunk) < chunk
    for lo in range(0, length, chunk):
        hi = lo + chunk
        qi = jnp.concatenate([q[:, lo:hi], q[:, length + lo:length + hi]], axis=1)
        q_pos = jnp.tile(jnp.arange(lo, hi), 2)
        num = jnp.zeros((b, g, r, 2 * chunk, dh), jnp.float32)
        den = jnp.zeros((b, g, r, 2 * chunk), jnp.float32)
        mx = jnp.full((b, g, r, 2 * chunk), _NEG, jnp.float32)
        for start, stop, k_noisy in ((length, length + hi, False), (lo, hi, True)):
            kk, vv = k[:, start:stop], v[:, start:stop].astype(jnp.float32)
            k_pos = jnp.arange(start, stop) % length
            mask = allowed(q_pos, q_noisy, k_pos, jnp.full(k_pos.shape, k_noisy), cfg.block)
            scores = jnp.einsum("bqgrd,bkgd->bgrqk", qi, kk).astype(jnp.float32) * scale
            num, den, mx = online_softmax_step(
                jnp.where(mask, scores, _NEG),
                lambda p, vv=vv: jnp.einsum("bgrqk,bkgd->bgrqd", p, vv), num, den, mx)
        out = (num / jnp.maximum(den[..., None], 1e-30)).astype(q.dtype)
        out = out.transpose(0, 3, 1, 2, 4).reshape(b, 2 * chunk, g * r * dh)
        noisy_out.append(out[:, :chunk])
        clean_out.append(out[:, chunk:])
    return jnp.concatenate(noisy_out + clean_out, axis=1)


def attention(u, layer, cfg):
    b, two_l, _ = u.shape
    g, r, dh = cfg.kv_heads, cfg.heads // cfg.kv_heads, cfg.head_dim
    w = lambda name: layer[name].astype(cfg.dtype)
    positions = jnp.tile(jnp.arange(two_l // 2), 2)
    q = (u @ w("wq")).reshape(b, two_l, g * r, dh)
    k = (u @ w("wk")).reshape(b, two_l, g, dh)
    v = (u @ w("wv")).reshape(b, two_l, g, dh)
    turn = lambda heads: rope(heads, positions, rope_frequencies(dh, cfg.rope_theta))
    q = turn(rms_norm(q, w("q_norm"), cfg.norm_eps))
    k = turn(rms_norm(k, w("k_norm"), cfg.norm_eps))
    return masked_attention(q.reshape(b, two_l, g, r, dh), k, v, cfg) @ w("wo")


# --------------------------------------------------------------------------- #
#  The expert layer: told which experts it holds, dropless                    #
# --------------------------------------------------------------------------- #


def route(tokens, router, cfg):
    """(weights, experts), both (N, experts_per_token): softmax over ALL the
    experts, the largest few, renormalised."""
    p = jax.nn.softmax((tokens @ router).astype(jnp.float32), axis=-1)
    top_p, top_e = jax.lax.top_k(p, cfg.experts_per_token)
    return top_p / jnp.sum(top_p, axis=-1, keepdims=True), top_e


def held_experts(tokens, weights, chosen, layer, held, dtype):
    """(N, D) tokens under a router's ``weights`` and ``chosen`` experts, both
    (N, experts_per_token) -> ((N, D) the part of the output that the experts
    ``held`` give, positions routed to them, held experts no position reached).
    The loop every model with such a layer calls (models/laguna.py too).

    Every held expert runs over every position and its output is weighted by
    what the router gave it there, zero where the position did not choose it:
    static shapes with no capacity cannot drop a token, and a step's time does
    not depend on where the tokens went (PERF.md section 6, PR 31: a loop whose
    trip count followed the tokens moved ``steps_per_s`` by 2.4 % between two
    seeds, five times what the grid admits in a new cell).  An expert no
    position chose gets a gradient of exact zeros."""
    out = jnp.zeros_like(tokens)
    routed, idle = jnp.float32(0), jnp.float32(0)
    for slot, expert in enumerate(held):
        hit = chosen == expert
        mine = jnp.sum(jnp.where(hit, weights, 0.0), axis=-1).astype(tokens.dtype)
        hidden = (jax.nn.silu(tokens @ layer["we_gate"][slot].astype(dtype))
                  * (tokens @ layer["we_up"][slot].astype(dtype)))
        out = out + mine[:, None] * (hidden @ layer["we_down"][slot].astype(dtype))
        count = jnp.sum(jnp.any(hit, axis=-1).astype(jnp.float32))
        routed, idle = routed + count, idle + (count == 0).astype(jnp.float32)
    return out, routed, idle


def moe(u, layer, cfg):
    """(B, S, D) -> ((B, S, D) the held experts' part of the output,
    positions routed to held experts, held experts no position reached)."""
    b, s, d = u.shape
    tokens = u.reshape(b * s, d)
    with jax.named_scope("model.router"):
        weights, chosen = route(tokens, layer["router"].astype(cfg.dtype), cfg)
    with jax.named_scope("model.experts"):
        out, routed, idle = held_experts(tokens, weights, chosen, layer, cfg.experts_held,
                                         cfg.dtype)
    return out.reshape(b, s, d), routed, idle


# --------------------------------------------------------------------------- #
#  The model, its loss, the noising                                           #
# --------------------------------------------------------------------------- #

STACKED = ("attn_norm", "mlp_norm", "q_norm", "k_norm", "wq", "wk", "wv", "wo", "router",
           "we_gate", "we_up", "we_down")


def decoder_layer(x, layer, cfg):
    with jax.named_scope("model.attention"):
        x = x + attention(rms_norm(x, layer["attn_norm"].astype(cfg.dtype), cfg.norm_eps),
                          layer, cfg)
    y, routed, idle = moe(rms_norm(x, layer["mlp_norm"].astype(cfg.dtype), cfg.norm_eps),
                          layer, cfg)
    return x + y, routed, idle


def loss_and_counters(params, batch, cfg):
    """``batch``: ``tokens`` x_0 (B, L), ``noisy`` x_t (B, L), ``t`` (B,) — what
    ``noise`` makes of a sampled batch.  Returns the block-diffusion loss (mean
    over the B sequences) and the step's counters."""
    clean, noisy, t = batch["tokens"], batch["noisy"], batch["t"]
    length = clean.shape[1]
    with jax.named_scope("model.embed"):
        x = params["embed"][jnp.concatenate([noisy, clean], axis=1)].astype(cfg.dtype)

    def body(carry, layer):
        x, routed, idle = carry
        x, r, i = decoder_layer(x, layer, cfg)
        return (x, routed + r, idle + i), None

    (x, routed, idle), _ = jax.lax.scan(
        jax.checkpoint(body), (x, jnp.float32(0), jnp.float32(0)), {name: params[name] for name in STACKED})
    with jax.named_scope("model.head"):
        hidden = rms_norm(x[:, :length], params["final_norm"].astype(cfg.dtype), cfg.norm_eps)
        logp = jax.nn.log_softmax((hidden @ params["head"].astype(cfg.dtype)).astype(jnp.float32))
        nll = -jnp.take_along_axis(logp, clean[..., None], axis=-1)[..., 0]
        masked = noisy == cfg.mask_id
        per_sequence = jnp.sum(jnp.where(masked, nll, 0.0), axis=1) / (t * length)
        loss = jnp.mean(per_sequence)
    return loss, {"routed_positions": routed, "idle_held_experts": idle,
                  "masked_share": jnp.mean(masked.astype(jnp.float32))}


def noise(worker_batch, key, mask_id):
    """The forward process, once a sequence: ``t`` ~ U(T_MIN, 1), each token
    masked independently with probability ``t``."""
    with jax.named_scope("model.noise"):
        tokens = worker_batch["tokens"]
        t_key, mask_key = jax.random.split(key)
        t = jax.random.uniform(t_key, tokens.shape[:1], jnp.float32, T_MIN, 1.0)
        masked = jax.random.uniform(mask_key, tokens.shape, jnp.float32) < t[:, None]
        return {"tokens": tokens, "noisy": jnp.where(masked, mask_id, tokens), "t": t}


def seeded_corpus(rows, length, vocab, seed=0):
    """(rows, length) int32 token rows from a seed, over every id but the
    last, which is the mask: the machine has no network, and speed and
    agreement with the reference need no text."""
    return np.random.default_rng(seed).integers(
        0, vocab - 1, size=(rows, length), dtype=np.int32)


def _parse_held(text):
    """``0-7`` or ``0,3,5``."""
    ids = []
    for part in str(text).split(","):
        lo, _, hi = part.partition("-")
        ids.extend(range(int(lo), int(hi or lo) + 1))
    return tuple(ids)


class SdarExperiment(Experiment):
    """Block-diffusion training of one chip's share of SDAR-30B-A3B-Chat.

    Args (key:value), defaults = grid/configs/sdar-30b-a3b-ep16-n4.json:
    vocab:18992 hidden:2048 heads:32 kv-heads:4 head-dim:128 layers:4
    experts:128 experts-per-token:8 expert-width:768 experts-held:0-7 seq:2048
    block:4 attn-chunk:256 batch-size:1 corpus:512 dtype:float32.  The batch a
    worker is handed is ``{"tokens": (B, seq)}``; the noising runs inside the
    step (``device_transform``).
    """

    #: the configuration's sizes that are arguments under their own names
    SIZES = ("vocab", "hidden", "heads", "kv_heads", "head_dim", "layers", "experts",
             "experts_per_token", "expert_width", "seq", "block", "attn_chunk")

    def __init__(self, args):
        super().__init__(args)
        base, dashed = SdarConfig(), lambda name: name.replace("_", "-")
        kv = parse_keyval(args, strict=True, defaults=dict(
            {dashed(name): getattr(base, name) for name in self.SIZES},
            **{"experts-held": "0-7", "batch-size": 1, "corpus": 512, "dtype": "float32"}))
        self.cfg = SdarConfig(
            experts_held=_parse_held(kv["experts-held"]), dtype=check_dtype(kv["dtype"]),
            **{name: kv[dashed(name)] for name in self.SIZES}).check()
        self.batch_size = kv["batch-size"]
        self.corpus = seeded_corpus(kv["corpus"], self.cfg.seq, self.cfg.vocab)

    def init(self, rng):
        return init_params(self.cfg, rng)

    def loss(self, params, batch):
        """(loss, counters): the engine carries the counters with the loss
        (``has_aux``, parallel/engine.py ``_worker_gradients``)."""
        return loss_and_counters(params, batch, self.cfg)

    loss.has_aux = True

    def metrics(self, params, batch):
        """Held-out rows are noised like training rows, from a fixed key."""
        noised = noise(batch, jax.random.PRNGKey(0), self.cfg.mask_id)
        loss, counters = loss_and_counters(params, noised, self.cfg)
        one = jnp.float32(1)
        return {"loss": (loss, one), "masked_share": (counters["masked_share"], one)}

    def device_transform(self):
        return functools.partial(noise, mask_id=self.cfg.mask_id)

    def train_arrays(self):
        return {"tokens": self.corpus}

    def make_train_iterator(self, nb_workers, seed=0):
        rng = np.random.default_rng(seed)

        def batches():
            while True:
                rows = rng.integers(0, len(self.corpus), size=(nb_workers, self.batch_size))
                yield {"tokens": self.corpus[rows]}

        return batches()

    def make_eval_iterator(self, nb_workers):
        rows = np.arange(nb_workers * self.batch_size) % len(self.corpus)
        yield {"tokens": self.corpus[rows].reshape(nb_workers, self.batch_size, -1)}


register("sdar", SdarExperiment)


# The token rows' start-up span (``startup.data_host``, obs/trace.py) goes on down here, import
# and all: a line added further up would move the frames of this file that a kernel's
# serialized body may carry (file and line of each caller), and with them the key of the
# step program in the persistent compilation cache (PERF.md §6, PR 37).
from .datasets import data_host  # noqa: E402

seeded_corpus = data_host(lambda rows: (len(rows), rows.nbytes))(seeded_corpus)
