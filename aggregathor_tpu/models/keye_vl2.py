"""Keye-VL-2.0's language model (``model_type: KeyeVL2``): a mixture-of-experts
decoder whose every query reads the keys A LEARNED INDEXER CHOSE for it,
trained on the next token, as ONE CHIP'S SHARE of an expert-parallel
deployment.

Source: https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json
(the language model's keys; the vision tower is no part of this).  The layer,
every width as published, no bias on any projection; ``u = RMSNorm(x)``, a
token has three position ids (temporal, height, width: equal for text):

    h = x + W_o SparseAttn(u),  y = h + MoE(RMSNorm(h))

- **Main heads** — ``q = RoPE_m(RMSNorm_head(W_q u))`` (``heads`` of
  ``head_dim``), ``k = RoPE_m(RMSNorm_head(W_k u))``, ``v = W_v u``
  (``kv_heads``; each serves ``heads // kv_heads`` consecutive query heads);
  ``RoPE_m`` turns the ``head_dim / 2`` frequency pairs by sections
  (``mrope_section`` [16, 24, 24]: the first 16 by the temporal id, 24 by the
  height's, 24 by the width's; models/transformer.py ``rope``).
- **Indexer**, on ``stop_gradient(u)`` — ``q_I = RoPE(W_Iq u)``
  (``index_heads`` of ``index_head_dim``), ``k_I = RoPE(LayerNorm(W_Ik u))``
  (ONE key head), ``w = (W_Iw u) / sqrt(index_heads * index_head_dim)`` (a
  weight a query an indexer head);
  ``I[t, s] = sum_j w[t, j] * relu(q_I[t, j] . k_I[s])``.
- **Selection** — ``S_t`` = the ``index_topk`` keys ``s <= t`` of largest
  ``I[t, s]`` (every ``s <= t`` while ``t + 1 <= index_topk``); equal scores go
  to the lower ``s``.  One ``S_t`` for all the heads.
- **SparseAttn** — the softmax over ``s in S_t`` of ``q . k / sqrt(head_dim)``,
  times v.
- **MoE** — models/sdar.py's: softmax router over all ``experts``, the
  ``experts_per_token`` largest renormalised, SiLU-gated experts, no shared
  one; the layer is told which experts it holds (``experts_held``).
- **Loss** — models/laguna.py's next-token cross-entropy over the ids held
  here.  Its gradient with respect to every indexer leaf is exactly zero: the
  selection is piecewise constant in them.  (The source family trains the
  indexer by a second signal, a KL loss towards the main heads' summed
  attention; it needs the per-head probabilities, which the kernel never
  writes, and is not run here: the indexer stays as seeded.)

How it is computed here.  A layer's index scores and its selection are made
``select_chunk`` queries at a time (``lax.map``): the (index heads, chunk, L)
products, their weighted sum, then the threshold (score and key) that a key
has to pass (``top_keys``): on a TPU found by counting, ops/select.py's one
kernel from the scores to the int8 pairs (32 + log2 L passes of compare and
sum over rows held in VMEM; ``select_form`` chooses), everywhere else the
``index_topk``-th entry of a stable sort of each query's causal scores, which
is also the kernel's oracle — never an (L, L) array of floats.  What leaves
is ``pairs``, (B, L, L) int8, nonzero where the query reads the key: the operand
of ops/attention.py's kernel under ``Selected(index_topk)`` (a mask that is
data) on a TPU, and of ``chunked_attention`` (plain XLA: a dense masked softmax
a checkpointed query chunk) everywhere else.  The layers run under
``lax.scan`` and ``jax.checkpoint`` (models/laguna.py ``layer_runs``), and
``pairs`` is the ONE thing a layer keeps for its backward pass beside its input
(``KEPT``): a byte a pair, 67 MB a layer a worker at L = 8192, against sorting
every query's scores a second time in the recomputed forward, which was 31 % of
the step (PERF.md section 6, PR 45).
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from . import Experiment, register
from ..utils import UserException, parse_keyval
from ..ops.attention import Selected, attend
from ..ops.select import chosen_form, select_threshold
from .common import check_dtype
from .laguna import LagunaExperiment, layer_runs, next_token_loss, seeded_corpus, seeded_leaves
from .sdar import _parse_held, moe
from .transformer import _NEG, rms_norm, rope, rope_frequencies

#: the name under which a layer's selection is kept through ``jax.checkpoint``
KEPT = "selected_pairs"


@dataclasses.dataclass(frozen=True)
class KeyeVL2Config:
    """The published widths, and this chip's share of depth, experts and
    vocabulary (grid/configs/keye-vl2-30b-a3b-ep16-n3.json states the
    deployment)."""

    vocab: int = 18992
    hidden: int = 2048
    heads: int = 32
    kv_heads: int = 4
    head_dim: int = 128
    layers: int = 4
    experts: int = 128             # what the router scores
    experts_per_token: int = 8
    expert_width: int = 768
    experts_held: tuple = tuple(range(8))
    index_heads: int = 16
    index_head_dim: int = 64
    index_topk: int = 2048
    mrope_section: tuple = (16, 24, 24)
    rope_theta: float = 1e7
    norm_eps: float = 1e-6
    seq: int = 8192
    attn_chunk: int = 256          # queries a chunk of the XLA form; the counters' tile
    select_chunk: int = 512        # queries whose index scores and selection are made at a time
    dtype: object = jnp.float32

    def check(self):
        if self.heads % self.kv_heads:
            raise UserException("heads (%d) must be a multiple of kv-heads (%d)"
                                % (self.heads, self.kv_heads))
        if 2 * sum(self.mrope_section) != self.head_dim or self.index_head_dim % 2:
            raise UserException("mrope-section %r must add up to the %d pairs of head-dim, and "
                                "index-head-dim (%d) be pairs" % (
                                    self.mrope_section, self.head_dim // 2, self.index_head_dim))
        if self.seq % self.attn_chunk or self.seq % self.select_chunk or self.index_topk < 1:
            raise UserException("seq (%d) must divide into attn-chunk (%d) and select-chunk (%d), "
                                "and index-topk (%d) be positive" % (
                                    self.seq, self.attn_chunk, self.select_chunk, self.index_topk))
        if not self.experts_held or not all(0 <= e < self.experts for e in self.experts_held):
            raise UserException("experts-held %r must name some of the %d experts"
                                % (self.experts_held, self.experts))
        return self

    def runs(self):
        """[(kind, how many consecutive layers)]: every layer is alike."""
        return [(None, self.layers)]


def leaf_shapes(cfg):
    """The parameters' tree of shapes, models/laguna.py's layout: one run of
    ``layers`` stacked layers under ``layers``."""
    d, dh, held = cfg.hidden, cfg.head_dim, len(cfg.experts_held)
    run = {
        "attn_norm": (d,), "mlp_norm": (d,), "q_norm": (dh,), "k_norm": (dh,),
        "wq": (d, cfg.heads * dh), "wk": (d, cfg.kv_heads * dh), "wv": (d, cfg.kv_heads * dh),
        "wo": (cfg.heads * dh, d),
        "index_wq": (d, cfg.index_heads * cfg.index_head_dim), "index_wk": (d, cfg.index_head_dim),
        "index_ww": (d, cfg.index_heads),
        "index_k_norm": (cfg.index_head_dim,), "index_k_bias": (cfg.index_head_dim,),
        "router": (d, cfg.experts),
        "we_gate": (held, d, cfg.expert_width), "we_up": (held, d, cfg.expert_width),
        "we_down": (held, cfg.expert_width, d),
    }
    return {"embed": (cfg.vocab, d), "head": (d, cfg.vocab), "final_norm": (d,),
            "layers": [{name: (cfg.layers,) + shape for name, shape in run.items()}]}


def init_params(cfg, key):
    """models/laguna.py's seeded leaves: norm scales at one, every other leaf
    N(0, INIT_STD^2) — the indexer's LayerNorm bias too."""
    return seeded_leaves(leaf_shapes(cfg), key)


# --------------------------------------------------------------------------- #
#  The indexer and the selection                                              #
# --------------------------------------------------------------------------- #


def by_chunks(a, chunk):
    """(B, L, ...) -> (L / chunk, B, chunk, ...): what a scan over chunks of
    positions is handed."""
    return a.reshape((a.shape[0], a.shape[1] // chunk, chunk) + a.shape[2:]).swapaxes(0, 1)


def layer_norm(x, scale, bias, eps):
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def indexer_heads(u, layer, cfg, positions):
    """(B, L, D) normed inputs -> the indexer's queries (B, L, J, Di), its ONE
    key head (B, L, Di) and a query's weights (B, L, J), float32; nothing of
    them reaches ``u``'s or any leaf's gradient through the choice they make."""
    b, length, _ = u.shape
    heads, dh = cfg.index_heads, cfg.index_head_dim
    u = jax.lax.stop_gradient(u)
    w = lambda name: layer[name].astype(cfg.dtype)
    turn = lambda x: rope(x, positions[0], rope_frequencies(dh, cfg.rope_theta))
    q = turn((u @ w("index_wq")).reshape(b, length, heads, dh))
    k = turn(layer_norm(u @ w("index_wk"), w("index_k_norm"), w("index_k_bias"),
                        cfg.norm_eps).astype(cfg.dtype)[:, :, None, :])[:, :, 0]
    weights = (u @ w("index_ww")).astype(jnp.float32) * (heads ** -0.5 * dh ** -0.5)
    return q, k, weights


def index_scores(q, k, weights):
    """``I`` of some queries: q (B, C, J, Di), k (B, L, Di), weights (B, C, J)
    -> (B, C, L) float32."""
    products = jnp.einsum("bqjd,bkd->bqjk", q, k, preferred_element_type=jnp.float32)
    return jnp.sum(jax.nn.relu(products) * weights[..., None], axis=2)


def top_keys(scores, q_pos, topk):
    """(B, C, L) booleans: the ``topk`` keys ``s <= q_pos`` of largest score a
    query, every one of them where there are no more; equal scores go to the
    lower ``s``.  Where ``ops.select.select_form`` says so, ops/select.py's
    kernel, which finds the same pairs by counting; else (the CPU's path and
    the kernel's oracle) one stable ascending sort of the negated scores (a key
    past the query at +inf, a zero of either sign at +0) carrying each key's
    position: the ``topk``-th entry is the last one in, and a key is in iff its
    (negated score, position) is not after that entry's."""
    k_pos = jnp.arange(scores.shape[-1])
    causal = k_pos[None, :] <= q_pos[:, None]
    if topk >= scores.shape[-1]:
        return jnp.broadcast_to(causal, scores.shape)
    if chosen_form(scores.shape, topk) == "kernel":
        return select_threshold(scores, q_pos, topk) != 0
    negated = jnp.where(causal, jnp.where(scores == 0, 0.0, -scores), jnp.inf)
    by_score, keys = jax.lax.sort(
        (negated, jnp.broadcast_to(k_pos.astype(jnp.int32), scores.shape)),
        dimension=-1, num_keys=1, is_stable=True)
    last, last_key = by_score[..., topk - 1:topk], keys[..., topk - 1:topk]
    return ((negated < last) | ((negated == last) & (k_pos <= last_key))) & causal


def select(u, layer, cfg, positions):
    """(B, L, D) normed inputs -> (``pairs`` (B, L, L) int8, nonzero where query
    (row) reads key; the layer's three counts: pairs selected, those more than
    ``index_topk`` behind their query, ``attn_chunk``-square tiles holding
    one)."""
    b, length, _ = u.shape
    chunk, topk = cfg.select_chunk, cfg.index_topk
    with jax.named_scope("model.indexer"):
        q, k, weights = indexer_heads(u, layer, cfg, positions)

    def some_queries(numbered):
        i, q, weights = numbered
        with jax.named_scope("model.indexer"):
            scores = index_scores(q, k, weights)
        with jax.named_scope("model.select"):
            return top_keys(scores, i * chunk + jnp.arange(chunk), topk).astype(jnp.int8)

    pairs = jax.lax.map(some_queries, (jnp.arange(length // chunk), by_chunks(q, chunk),
                                       by_chunks(weights, chunk)))
    with jax.named_scope("model.select"):
        pairs = pairs.swapaxes(0, 1).reshape(b, length, length)
        index = jnp.arange(length)
        far = (index[:, None] - index[None, :]) > topk
        tile, count = cfg.attn_chunk, lambda which: jnp.sum(which.astype(jnp.float32))
        tiles = pairs.reshape(b, length // tile, tile, length // tile, tile)
        return pairs, (count(pairs), count((pairs != 0) & far),
                       count(jnp.max(tiles, axis=(2, 4))))


# --------------------------------------------------------------------------- #
#  Attention over the selected keys                                           #
# --------------------------------------------------------------------------- #


def chunked_attention(q, k, v, pairs, cfg):
    """The XLA form, and the kernel's oracle: q (B, L, G, R, Dh), k and v (B,
    L, G, Dh), ``pairs`` (B, L, L) -> (B, L, G * R * Dh).  A scan over chunks
    of ``attn_chunk`` queries, each one dense softmax over all L keys under its
    rows of ``pairs``, each checkpointed: a layer's backward pass holds one
    chunk's scores."""
    b, length, g, r, dh = q.shape
    chunk, scale = cfg.attn_chunk, 1.0 / math.sqrt(dh)
    values = v.astype(jnp.float32)

    @jax.checkpoint
    def one_chunk(_, blocks):
        qi, allowed = blocks
        scores = jnp.einsum("bqgrd,bkgd->bgrqk", qi, k, preferred_element_type=jnp.float32)
        weights = jax.nn.softmax(
            jnp.where(allowed[:, None, None] != 0, scores * scale, _NEG), axis=-1)
        out = jnp.einsum("bgrqk,bkgd->bqgrd", weights, values).astype(q.dtype)
        return None, out.reshape(b, chunk, g * r * dh)

    _, outs = jax.lax.scan(one_chunk, None, (by_chunks(q, chunk), by_chunks(pairs, chunk)))
    return outs.swapaxes(0, 1).reshape(b, length, g * r * dh)


def main_heads(u, layer, cfg, positions):
    """(B, L, D) normed inputs -> q (B, L, G, R, Dh), k and v (B, L, G, Dh):
    per-head RMS norm of q and k, then RoPE by sections."""
    b, length, _ = u.shape
    g, r, dh = cfg.kv_heads, cfg.heads // cfg.kv_heads, cfg.head_dim
    w = lambda name: layer[name].astype(cfg.dtype)
    turn = lambda x: rope(x, positions, rope_frequencies(dh, cfg.rope_theta),
                          sections=cfg.mrope_section)
    q = turn(rms_norm((u @ w("wq")).reshape(b, length, g * r, dh), w("q_norm"), cfg.norm_eps))
    k = turn(rms_norm((u @ w("wk")).reshape(b, length, g, dh), w("k_norm"), cfg.norm_eps))
    return q.reshape(b, length, g, r, dh), k, (u @ w("wv")).reshape(b, length, g, dh)


def sparse_attention(u, layer, cfg, positions):
    """(B, L, D) normed inputs, positions (3, L) -> (what ``W_o`` gives of the
    attention over each query's selected keys, the selection's three counts)."""
    with jax.named_scope("model.attention"):
        q, k, v = main_heads(u, layer, cfg, positions)
    pairs, counts = select(u, layer, cfg, positions)
    pairs = checkpoint_name(pairs, KEPT)
    with jax.named_scope("model.sparse_attend"):
        out = attend(q, k, v, Selected(cfg.index_topk),
                     lambda q, k, v: chunked_attention(q, k, v, pairs, cfg), pairs=pairs)
    with jax.named_scope("model.attention"):
        return out @ layer["wo"].astype(cfg.dtype), counts


# --------------------------------------------------------------------------- #
#  The model and its loss                                                     #
# --------------------------------------------------------------------------- #


def text_positions(length):
    """(3, L): a text token's temporal, height and width ids are its index."""
    return jnp.broadcast_to(jnp.arange(length), (3, length))


def decoder_layer(x, layer, cfg, positions):
    norm = lambda x, name: rms_norm(x, layer[name].astype(cfg.dtype), cfg.norm_eps)
    with jax.named_scope("model.attention"):
        u = norm(x, "attn_norm")
    attended, counts = sparse_attention(u, layer, cfg, positions)
    x = x + attended
    y, routed, idle = moe(norm(x, "mlp_norm"), layer, cfg)
    return (x + y, routed, idle, *counts)


def loss_and_counters(params, batch, cfg):
    """``batch``: ``tokens`` (B, L + 1).  Returns the next-token loss (mean
    over the B x L positions) and the step's counters: the two of the expert
    layers, and of the selection ``selected_keys`` (mean keys a query reads),
    ``selected_far_share`` (share of the selected pairs more than
    ``index_topk`` behind their query) and ``live_tile_share`` (share of the
    causal ``attn_chunk``-square tiles that hold a selected pair), each the
    mean over the layers."""
    inputs, targets = batch["tokens"][:, :-1], batch["tokens"][:, 1:]
    b, length = inputs.shape
    positions = text_positions(length)
    with jax.named_scope("model.embed"):
        x = params["embed"][inputs].astype(cfg.dtype)
    x, routed, idle, selected, far, live = layer_runs(
        x, (jnp.float32(0),) * 5, cfg.runs(), params["layers"],
        lambda x, leaves, kind: decoder_layer(x, leaves, cfg, positions),
        policy=jax.checkpoint_policies.save_only_these_names(KEPT))
    tiles = length // cfg.attn_chunk
    return next_token_loss(x, params, targets, cfg), {
        "routed_positions": routed, "idle_held_experts": idle,
        "selected_keys": selected / (cfg.layers * b * length),
        "selected_far_share": far / selected,
        "live_tile_share": live / (cfg.layers * b * (tiles * (tiles + 1) // 2))}


class KeyeVL2Experiment(LagunaExperiment):
    """Next-token training of one chip's share of Keye-VL-2.0-30B-A3B's
    language model.

    Args (key:value), defaults = grid/configs/keye-vl2-30b-a3b-ep16-n3.json:
    vocab:18992 hidden:2048 heads:32 kv-heads:4 head-dim:128 layers:4
    experts:128 experts-per-token:8 expert-width:768 experts-held:0-7
    index-heads:16 index-head-dim:64 index-topk:2048 mrope-section:16,24,24
    rope-theta:10000000 norm-eps:1e-06 seq:8192 attn-chunk:256
    select-chunk:512 batch-size:1 corpus:256 dtype:float32.  The batch a worker
    is handed is ``{"tokens": (B, seq + 1)}``; feeds and metrics are
    models/laguna.py's.
    """

    #: the configuration's sizes that are arguments under their own names
    SIZES = tuple(field.name for field in dataclasses.fields(KeyeVL2Config)
                  if field.name not in ("experts_held", "mrope_section", "dtype"))
    init_params = staticmethod(init_params)
    loss_and_counters = staticmethod(loss_and_counters)

    def __init__(self, args):
        Experiment.__init__(self, args)  # the arguments are this family's, not Laguna's
        base, dashed = KeyeVL2Config(), lambda name: name.replace("_", "-")
        kv = parse_keyval(args, strict=True, defaults=dict(
            {dashed(name): getattr(base, name) for name in self.SIZES},
            **{"experts-held": "0-7", "mrope-section": ",".join(map(str, base.mrope_section)),
               "batch-size": 1, "corpus": 256, "dtype": "float32"}))
        self.cfg = KeyeVL2Config(
            experts_held=_parse_held(kv["experts-held"]), dtype=check_dtype(kv["dtype"]),
            mrope_section=tuple(int(pairs) for pairs in str(kv["mrope-section"]).split(",")),
            **{name: kv[dashed(name)] for name in self.SIZES}).check()
        self.batch_size = kv["batch-size"]
        self.corpus = seeded_corpus(kv["corpus"], self.cfg.seq, self.cfg.vocab)


register("keye_vl2", KeyeVL2Experiment)
