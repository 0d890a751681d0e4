"""DeepSeek-V3's layer (``model_type: deepseek_v3``): latent attention and a
router whose choice a bias moves, trained on the next token, as ONE CHIP'S
SHARE of an expert-parallel deployment.

Source: https://huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601/blob/main/config.json
(Kanana-2-30B-A3B), whose keys are the arguments' names.  The layer, every
width as published, no bias on any projection:

    h = x + Attn(RMSNorm(x)),  y = h + FFN_l(RMSNorm(h))

- **Attn, latent** — keys and values come through a low-rank latent, and a head
  scores over more dims than it reads values of.  With ``u`` the normed input:
  ``q = u W_q``, a head ``[q_nope (qk_nope_head_dim) ; q_pe (qk_rope_head_dim)]``
  (``q_lora_rank`` null: no low-rank query path; any other value fails by
  name); ``u W_kva = [c_kv (kv_lora_rank) ; k_pe (qk_rope_head_dim)]``: the
  latent and ONE rotary key that every head shares; ``RMSNorm(c_kv) W_kvb`` is a
  head's ``[k_nope ; v (v_head_dim)]``; RoPE on ``q_pe`` and ``k_pe`` (pairs
  (2i, 2i + 1), ``rope_interleave``; no scaling: ``rope_scaling`` null); a
  head's key is ``[k_nope ; k_pe]``; scores ``q . k / sqrt(qk_nope_head_dim +
  qk_rope_head_dim)`` under the causal mask, softmax, times v; the heads side
  by side through ``W_o``.  The layer is TOLD HOW MANY HEADS IT HOLDS
  (``heads_held`` of ``heads``): the columns of ``W_q`` and ``W_kvb`` and the
  rows of ``W_o`` of those heads, so that what leaves ``W_o`` is a partial sum
  over them; ``W_kva`` and the latent's norm are whole on every chip.
- **FFN_l** — the first ``first_k_dense_replace`` layers a dense gated unit of
  ``dense_width``; every later one sparse: ``s = sigmoid(W_g u)`` over all
  ``experts``; the ``experts_per_token`` largest of ``s + b`` are chosen, ``b``
  the router's ``e_score_correction_bias`` (``topk_method: noaux_tc``; one
  group, so no group limit); their weights are ``s`` (NOT ``s + b``) at the
  chosen, divided by their sum (``norm_topk_prob``), times
  ``routed_scaling_factor``; plus the ``n_shared_experts`` shared experts, ONE
  gated unit of ``n_shared_experts x expert_width`` that every position
  passes.  ``b`` enters the choice only and gets no gradient (the published
  training moves it by a balancing rule outside the loss, which this model
  does not run: ``b`` stays as seeded).  The layer is told which experts it
  holds (``experts_held``), as models/sdar.py's and models/laguna.py's.
- **Loss** — models/laguna.py's: the mean over the L positions of a row of
  L + 1 ids of ``-log softmax(logits_i)[token_{i+1}]`` over the ids held here.

What is computed here and what is another model's: the held-experts loop
(models/sdar.py), the gated unit, the runs of stacked layers under ``scan`` and
``checkpoint``, the chunked XLA attention, the head and its loss, the seeded
leaves and rows and the experiment's feeds (models/laguna.py), the norm and
RoPE (models/transformer.py).  Attention goes through ops/attention.py
``attend``: on a TPU the fused kernel, scores at 192 and values at 128 as they
are, two heads a grid step; elsewhere ``chunked_attention``, whose accumulator
is as wide as v.
"""

import dataclasses

import jax
import jax.numpy as jnp

from . import Experiment, register
from ..utils import UserException, parse_keyval
from ..ops.attention import Causal, attend
from .common import check_dtype
from .laguna import (DENSE, SPARSE, LagunaExperiment, chunked_attention, gated_unit, layer_runs,
                     next_token_loss, seeded_corpus, seeded_leaves)
from .sdar import _parse_held, held_experts
from .transformer import rms_norm, rope, rope_frequencies


@dataclasses.dataclass(frozen=True)
class DeepseekV3Config:
    """The published widths, and this chip's share of depth, heads, experts
    and vocabulary (grid/configs/kanana2-30b-a3b-ep16-n3.json states the
    deployment)."""

    vocab: int = 16032
    hidden: int = 2048
    heads: int = 32                # published; the scores' scale and widths do not depend on it
    heads_held: int = 16
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    kv_lora_rank: int = 512
    layers: int = 5
    first_k_dense_replace: int = 1
    dense_width: int = 6144
    experts: int = 128             # what the router scores
    experts_per_token: int = 6
    expert_width: int = 768
    n_shared_experts: int = 2
    routed_scaling_factor: float = 2.448
    experts_held: tuple = tuple(range(8))
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    seq: int = 4096
    attn_chunk: int = 256          # queries a chunk of the XLA form
    dtype: object = jnp.float32

    def check(self):
        if not 0 < self.heads_held <= self.heads:
            raise UserException("heads-held (%d) must be some of the %d heads"
                                % (self.heads_held, self.heads))
        if not 0 <= self.first_k_dense_replace <= self.layers:
            raise UserException("first-k-dense-replace (%d) must lie within the %d layers"
                                % (self.first_k_dense_replace, self.layers))
        if self.seq % self.attn_chunk or self.qk_rope_head_dim % 2:
            raise UserException("seq (%d) must divide into attn-chunk (%d), and qk-rope-head-dim "
                                "(%d) into pairs" % (self.seq, self.attn_chunk, self.qk_rope_head_dim))
        if not self.experts_held or not all(0 <= e < self.experts for e in self.experts_held):
            raise UserException("experts-held %r must name some of the %d experts"
                                % (self.experts_held, self.experts))
        return self

    def runs(self):
        """[(feed-forward kind, how many consecutive layers)]."""
        dense = self.first_k_dense_replace
        return [(kind, count) for kind, count in ((DENSE, dense), (SPARSE, self.layers - dense))
                if count]


def run_shapes(cfg, kind, count):
    """{leaf: shape} of one run: its layers' leaves on a leading axis."""
    d, held = cfg.hidden, cfg.heads_held
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    shapes = {
        "attn_norm": (d,), "mlp_norm": (d,), "kv_norm": (cfg.kv_lora_rank,),
        "wq": (d, held * qk), "wkv_a": (d, cfg.kv_lora_rank + cfg.qk_rope_head_dim),
        "wkv_b": (cfg.kv_lora_rank, held * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
        "wo": (held * cfg.v_head_dim, d),
    }
    prefix, width = (("w", cfg.dense_width) if kind == DENSE
                     else ("ws", cfg.n_shared_experts * cfg.expert_width))
    shapes.update({prefix + "_gate": (d, width), prefix + "_up": (d, width),
                   prefix + "_down": (width, d)})
    if kind == SPARSE:
        experts = len(cfg.experts_held)
        shapes.update({
            "router": (d, cfg.experts), "router_bias": (cfg.experts,),
            "we_gate": (experts, d, cfg.expert_width), "we_up": (experts, d, cfg.expert_width),
            "we_down": (experts, cfg.expert_width, d),
        })
    return {name: (count,) + shape for name, shape in shapes.items()}


def leaf_shapes(cfg):
    """The parameters' tree of shapes: the runs are a list under ``layers``."""
    return {"embed": (cfg.vocab, cfg.hidden), "head": (cfg.hidden, cfg.vocab),
            "final_norm": (cfg.hidden,),
            "layers": [run_shapes(cfg, kind, count) for kind, count in cfg.runs()]}


def init_params(cfg, key):
    """models/laguna.py's seeded leaves: norm scales at one, every other leaf
    N(0, INIT_STD^2) — the router's bias too, so that it is not zero and moves
    choices (``bias_changed_positions`` counts them)."""
    return seeded_leaves(leaf_shapes(cfg), key)


# --------------------------------------------------------------------------- #
#  Latent attention                                                           #
# --------------------------------------------------------------------------- #


def latent_heads(u, layer, cfg):
    """(B, L, D) normed inputs -> q (B, L, H, 1, Dqk), k (B, L, H, Dqk), v
    (B, L, H, Dv) of the H heads held: the low-rank path, the one rotary key
    turned and handed to every head."""
    b, length, _ = u.shape
    held, nope, turned = cfg.heads_held, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    w = lambda name: layer[name].astype(cfg.dtype)
    turn = lambda x: rope(x, jnp.arange(length), rope_frequencies(turned, cfg.rope_theta))
    q = (u @ w("wq")).reshape(b, length, held, nope + turned)
    latent = u @ w("wkv_a")
    k_pe = turn(latent[..., None, cfg.kv_lora_rank:])   # one head, shared
    kv = (rms_norm(latent[..., :cfg.kv_lora_rank], w("kv_norm"), cfg.norm_eps)
          @ w("wkv_b")).reshape(b, length, held, nope + cfg.v_head_dim)
    q = jnp.concatenate([q[..., :nope], turn(q[..., nope:])], axis=-1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_pe, (b, length, held, turned))],
                        axis=-1)
    return q[:, :, :, None, :], k, kv[..., nope:]


def latent_attention(u, layer, cfg):
    """Every head its own keys (no grouped queries): G = heads held, R = 1."""
    with jax.named_scope("model.mla_project"):
        q, k, v = latent_heads(u, layer, cfg)
    with jax.named_scope("model.mla_attend"):
        out = attend(q, k, v, Causal(), lambda q, k, v: chunked_attention(q, k, v, cfg, None))
    with jax.named_scope("model.mla_project"):
        return out @ layer["wo"].astype(cfg.dtype)


# --------------------------------------------------------------------------- #
#  Feed-forward: a dense unit, or the shared experts beside the held experts  #
# --------------------------------------------------------------------------- #


def route(tokens, router, bias, cfg):
    """(weights, experts, positions whose choice the bias changed): a sigmoid
    score for each of ALL the experts; the largest few of score + bias are
    chosen; the weights are the SCORES at the chosen, normalised to sum to one."""
    scores = jax.nn.sigmoid((tokens @ router).astype(jnp.float32))
    _, chosen = jax.lax.top_k(scores + jax.lax.stop_gradient(bias.astype(jnp.float32)),
                              cfg.experts_per_token)
    top_s = jnp.take_along_axis(scores, chosen, axis=-1)
    # the chosen are the largest few by score alone iff no other score passes their least
    passed = jnp.sum(scores > jnp.min(top_s, axis=-1, keepdims=True), axis=-1)
    changed = jnp.sum((passed >= cfg.experts_per_token).astype(jnp.float32))
    return top_s / jnp.sum(top_s, axis=-1, keepdims=True), chosen, changed


def sparse_ffn(u, layer, cfg):
    """(B, S, D) -> (the shared experts' unit plus ``routed_scaling_factor``
    times the held experts' part, positions routed to held experts, held
    experts idle, positions whose choice the bias changed)."""
    b, s, d = u.shape
    tokens = u.reshape(b * s, d)
    with jax.named_scope("model.router"):
        weights, chosen, changed = route(tokens, layer["router"].astype(cfg.dtype),
                                         layer["router_bias"], cfg)
    with jax.named_scope("model.experts"):
        out, routed, idle = held_experts(tokens, weights, chosen, layer, cfg.experts_held,
                                         cfg.dtype)
    with jax.named_scope("model.shared_expert"):
        out = gated_unit(tokens, layer, "ws", cfg.dtype) + cfg.routed_scaling_factor * out
    return out.reshape(b, s, d), routed, idle, changed


def dense_ffn(u, layer, cfg):
    with jax.named_scope("model.dense_mlp"):
        return gated_unit(u, layer, "w", cfg.dtype), 0.0, 0.0, 0.0


FFN = {DENSE: dense_ffn, SPARSE: sparse_ffn}


# --------------------------------------------------------------------------- #
#  The model and its loss                                                     #
# --------------------------------------------------------------------------- #


def decoder_layer(x, layer, cfg, kind):
    norm = lambda x, name: rms_norm(x, layer[name].astype(cfg.dtype), cfg.norm_eps)
    with jax.named_scope("model.mla_project"):
        u = norm(x, "attn_norm")
    x = x + latent_attention(u, layer, cfg)
    y, *counts = FFN[kind](norm(x, "mlp_norm"), layer, cfg)
    return (x + y, *counts)


def loss_and_counters(params, batch, cfg):
    """``batch``: ``tokens`` (B, L + 1).  Returns the next-token loss (mean
    over the B x L positions) and the step's counters."""
    inputs, targets = batch["tokens"][:, :-1], batch["tokens"][:, 1:]
    with jax.named_scope("model.embed"):
        x = params["embed"][inputs].astype(cfg.dtype)
    x, routed, idle, changed = layer_runs(
        x, (jnp.float32(0),) * 3, cfg.runs(), params["layers"],
        lambda x, leaves, kind: decoder_layer(x, leaves, cfg, kind))
    return next_token_loss(x, params, targets, cfg), {
        "routed_positions": routed, "idle_held_experts": idle, "bias_changed_positions": changed}


class DeepseekV3Experiment(LagunaExperiment):
    """Next-token training of one chip's share of Kanana-2-30B-A3B.

    Args (key:value), defaults = grid/configs/kanana2-30b-a3b-ep16-n3.json:
    vocab:16032 hidden:2048 heads:32 heads-held:16 qk-nope-head-dim:128
    qk-rope-head-dim:64 v-head-dim:128 kv-lora-rank:512 q-lora-rank:null layers:5
    first-k-dense-replace:1 dense-width:6144 experts:128 experts-per-token:6
    expert-width:768 n-shared-experts:2 routed-scaling-factor:2.448
    experts-held:0-7 rope-theta:1000000 norm-eps:1e-06 seq:4096 attn-chunk:256
    batch-size:1 corpus:256 dtype:float32.  The batch a worker is handed is
    ``{"tokens": (B, seq + 1)}``; feeds and metrics are models/laguna.py's.
    """

    #: the configuration's sizes that are arguments under their own names
    SIZES = tuple(field.name for field in dataclasses.fields(DeepseekV3Config)
                  if field.name not in ("experts_held", "dtype"))
    init_params = staticmethod(init_params)
    loss_and_counters = staticmethod(loss_and_counters)

    def __init__(self, args):
        Experiment.__init__(self, args)  # the arguments are this family's, not Laguna's
        base, dashed = DeepseekV3Config(), lambda name: name.replace("_", "-")
        kv = parse_keyval(args, strict=True, defaults=dict(
            {dashed(name): getattr(base, name) for name in self.SIZES},
            **{"q-lora-rank": "null", "experts-held": "0-7", "batch-size": 1, "corpus": 256,
               "dtype": "float32"}))
        if kv["q-lora-rank"] != "null":
            raise UserException("q-lora-rank:%s: the low-rank query path (q_lora_rank) is not "
                                "built; only null runs" % kv["q-lora-rank"])
        self.cfg = DeepseekV3Config(
            experts_held=_parse_held(kv["experts-held"]), dtype=check_dtype(kv["dtype"]),
            **{name: kv[dashed(name)] for name in self.SIZES}).check()
        self.batch_size = kv["batch-size"]
        self.corpus = seeded_corpus(kv["corpus"], self.cfg.seq, self.cfg.vocab)


register("deepseek_v3", DeepseekV3Experiment)
