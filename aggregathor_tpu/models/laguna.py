"""Laguna (``model_type: laguna``): a mixture-of-experts decoder whose layers
are not all alike, trained on the next token, as ONE CHIP'S SHARE of an
expert-parallel deployment.

Source: https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json.
The model is driven by three lists of the config, one entry a layer:
``layer_types`` (full or sliding attention), ``mlp_layer_types`` (dense or
sparse feed-forward) and ``num_attention_heads_per_layer``.  The layer, every
width as published, no bias anywhere:

    h = x + Attn_l(RMSNorm(x)),  y = h + FFN_l(RMSNorm(h))

- **Attn_l** — ``heads[l]`` query heads and ``kv_heads`` key/value heads of
  ``head_dim``; each key/value head serves ``heads[l] // kv_heads`` consecutive
  query heads; RoPE on q and k from the table of the layer's kind
  (``RopeTable``: a full layer turns half of each head by YaRN's blended
  frequencies and scales cos and sin, a sliding layer turns the whole head by
  the default ones); scores ``q.k / sqrt(head_dim)``; query i reads key j iff
  ``j <= i`` and, on a sliding layer, ``i - j < window``.
- **FFN_l, dense** — ``W_down(silu(W_gate u) * W_up u)`` of ``dense_width``.
- **FFN_l, sparse** — the shared expert (the same unit, ``shared_width``) plus
  ``routed_scale`` times the routed experts' sum: ``s = sigmoid(W_r u)`` over
  all ``experts``, the ``experts_per_token`` largest, their scores normalised
  to sum to one.  The layer is TOLD WHICH EXPERTS IT HOLDS (``experts_held``):
  the router scores and chooses over all of them, this chip computes its own
  experts' part (models/sdar.py ``held_experts``, the loop both models call)
  and the shared expert whole, and what the absent experts would add is left
  out (the model-configs guide, section 4).
- **Loss** — logits over the ids held here at every position; the mean over
  the L positions of ``-log softmax(logits_i)[token_{i+1}]``: a row of the
  corpus is L + 1 ids, its first L the inputs and its last L the targets.

How it is computed here: consecutive layers of one kind (attention, head
count and feed-forward alike) are a RUN whose leaves are stacked on a leading
axis; a run of several layers goes under ``lax.scan``, a run of one is called
as it is, each layer under ``jax.checkpoint``.  Attention has two forms and
one chooser (ops/attention.py ``attention_form``; no flag chooses).  **On a
TPU, for shapes the kernel takes** (L a multiple of its tiles, a head of 128
lanes: the configuration's) it is ops/attention.py's fused Pallas kernel,
forward and backward, full and sliding layers alike under ``Causal(window)``:
scores, running maximum and sums stay in VMEM, out come the output and one
log-sum-exp a query a head, and the backward pass makes a tile's scores again.
**Everywhere else** (the CPU, a length that does not divide; the parity oracle
of the kernel's tests) it is ``chunked_attention``, plain XLA: a scan of the
queries a chunk at a time that folds into the running softmax it shares with
models/transformer.py only the key chunks the chunk may read — all before it
and its own on a full layer, the window's on a sliding one; each chunk is
checkpointed too, so that a layer's backward pass holds one chunk's scores
and not the layer's (5 GB at three workers, 48 heads and L = 4096).  In either
form no L x L score tensor exists and the work follows the pairs the mask
allows.
"""

import dataclasses
import itertools
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import Experiment, register
from ..utils import UserException, parse_keyval
from ..ops.attention import Causal, attend
from .common import check_dtype
from .sdar import INIT_STD, _parse_held, held_experts
from .transformer import _NEG, online_softmax_step, rms_norm, rope

FULL, SLIDING = "full", "sliding"
DENSE, SPARSE = "dense", "sparse"


@dataclasses.dataclass(frozen=True)
class RopeTable:
    """One kind of layer's rotary table (``rope_parameters`` of the config)."""

    theta: float
    rotary: float = 1.0            # the share of each head that turns
    yarn_factor: float = 0.0       # 0: the default frequencies
    original: int = 4096           # positions the model was first trained on
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = None  # on cos and sin, where the table has one

    def inverse_frequencies(self, head_dim):
        """One inverse frequency a rotated pair, float32.  YaRN (as
        ``_compute_yarn_parameters`` of the published code): the default
        frequency ``theta^(-2i/width)`` where a pair turns more than
        ``beta_fast`` times over the ``original`` positions, that over
        ``yarn_factor`` where it turns fewer than ``beta_slow`` times, and
        between the two correction dimensions a linear ramp from one to the
        other."""
        width = int(head_dim * self.rotary)
        default = self.theta ** (-np.arange(0, width, 2, dtype=np.float64) / width)
        if not self.yarn_factor:
            return jnp.asarray(default, jnp.float32)

        def correction(turns):
            return (width * math.log(self.original / (turns * 2 * math.pi))
                    / (2 * math.log(self.theta)))

        low = max(math.floor(correction(self.beta_fast)), 0)
        high = min(math.ceil(correction(self.beta_slow)), width - 1)
        ramp = np.clip((np.arange(width // 2) - low) / max(high - low, 1e-3), 0, 1)
        return jnp.asarray(default / self.yarn_factor * ramp + default * (1 - ramp), jnp.float32)


@dataclasses.dataclass(frozen=True)
class LagunaConfig:
    """The published widths, and this chip's share of depth, experts and
    vocabulary (grid/configs/laguna-xs2-ep32-n3.json states the deployment)."""

    vocab: int = 12544
    hidden: int = 2048
    kv_heads: int = 8
    head_dim: int = 128
    layer_types: tuple = (FULL, SLIDING, SLIDING, SLIDING, FULL)
    mlp_types: tuple = (DENSE, SPARSE, SPARSE, SPARSE, SPARSE)
    heads: tuple = (48, 64, 64, 64, 48)
    window: int = 512
    dense_width: int = 8192
    experts: int = 256             # what the router scores
    experts_per_token: int = 8
    expert_width: int = 512
    shared_width: int = 512
    routed_scale: float = 2.5
    experts_held: tuple = tuple(range(8))
    full_rope: RopeTable = RopeTable(
        theta=5e5, rotary=0.5, yarn_factor=64.0, original=4096, beta_fast=64.0, beta_slow=1.0,
        attention_factor=1.4158883083359672)
    sliding_rope: RopeTable = RopeTable(theta=1e4)
    norm_eps: float = 1e-6
    seq: int = 4096
    attn_chunk: int = 256          # queries a chunk
    dtype: object = jnp.float32

    def check(self):
        if not len(self.layer_types) == len(self.mlp_types) == len(self.heads) > 0:
            raise UserException("layer-types, mlp-types and heads must name the same layers: "
                                "%r, %r, %r" % (self.layer_types, self.mlp_types, self.heads))
        for kinds, known in ((self.layer_types, (FULL, SLIDING)), (self.mlp_types, (DENSE, SPARSE))):
            if set(kinds) - set(known):
                raise UserException("a layer is one of %s, not %r" % (known, kinds))
        if any(heads % self.kv_heads for heads in self.heads):
            raise UserException("every layer's heads %r must be a multiple of kv-heads (%d)"
                                % (self.heads, self.kv_heads))
        if self.seq % self.attn_chunk:
            raise UserException("seq (%d) must divide into attn-chunk (%d)"
                                % (self.seq, self.attn_chunk))
        if not self.experts_held or not all(0 <= e < self.experts for e in self.experts_held):
            raise UserException("experts-held %r must name some of the %d experts"
                                % (self.experts_held, self.experts))
        return self

    def runs(self):
        """[((layer type, mlp type, heads), how many consecutive layers)]."""
        kinds = zip(self.layer_types, self.mlp_types, self.heads)
        return [(kind, len(list(alike))) for kind, alike in itertools.groupby(kinds)]


def run_shapes(cfg, kind, count):
    """{leaf: shape} of one run: its layers' leaves on a leading axis."""
    (_, mlp_type, heads), d, dh = kind, cfg.hidden, cfg.head_dim
    shapes = {
        "attn_norm": (count, d), "mlp_norm": (count, d),
        "wq": (count, d, heads * dh), "wk": (count, d, cfg.kv_heads * dh),
        "wv": (count, d, cfg.kv_heads * dh), "wo": (count, heads * dh, d),
    }
    prefix, width = ("w", cfg.dense_width) if mlp_type == DENSE else ("ws", cfg.shared_width)
    shapes.update({prefix + "_gate": (count, d, width), prefix + "_up": (count, d, width),
                   prefix + "_down": (count, width, d)})
    if mlp_type == SPARSE:
        held = len(cfg.experts_held)
        shapes.update({
            "router": (count, d, cfg.experts),
            "we_gate": (count, held, d, cfg.expert_width),
            "we_up": (count, held, d, cfg.expert_width),
            "we_down": (count, held, cfg.expert_width, d),
        })
    return shapes


def leaf_shapes(cfg):
    """The parameters' tree of shapes: the runs are a list under ``layers``."""
    return {"embed": (cfg.vocab, cfg.hidden), "head": (cfg.hidden, cfg.vocab),
            "final_norm": (cfg.hidden,),
            "layers": [run_shapes(cfg, kind, count) for kind, count in cfg.runs()]}


def init_params(cfg, key):
    return seeded_leaves(leaf_shapes(cfg), key)


def seeded_leaves(shapes, key):
    """A tree of shapes like ``leaf_shapes``'s into parameters: norm scales at
    one, every other leaf N(0, INIT_STD^2), leaf by leaf from ``fold_in(key,
    its place)``: the top-level leaves by sorted name, then each run's by sorted
    name, run after run (models/deepseek_v3.py's tree too)."""
    shapes = dict(shapes)
    runs = shapes.pop("layers")
    place = 0

    def leaves(group):
        nonlocal place
        made = {}
        for name, shape in sorted(group.items()):
            made[name] = (jnp.ones(shape, jnp.float32) if name.endswith("norm") else INIT_STD
                          * jax.random.normal(jax.random.fold_in(key, place), shape, jnp.float32))
            place += 1
        return made

    params = leaves(shapes)
    params["layers"] = [leaves(group) for group in runs]
    return params


# --------------------------------------------------------------------------- #
#  Attention: causal, and on a sliding layer inside a window                  #
# --------------------------------------------------------------------------- #


def allowed(q_pos, k_pos, window):
    """(q, k) booleans: may the query read the key?  ``window`` None: every
    key up to the query's own; else only the last ``window`` of them.  The
    predicate itself is ops/attention.py ``Causal``, the kernel's too."""
    return Causal(window)(q_pos[:, None], k_pos[None, :])


def key_offsets(chunk, nb_chunks, window):
    """(clear, edged): how many chunks back of a query chunk lie the key
    chunks it folds besides its own — ``clear`` those every query of which
    reads every key (no mask), ``edged`` those the window's far edge cuts.  A
    full layer's are all clear; a chunk that far back may not exist."""
    furthest = nb_chunks - 1
    if window is not None:
        furthest = min(furthest, (window + chunk - 2) // chunk)
    clear = [back for back in range(1, furthest + 1)
             if window is None or back * chunk + chunk - 1 < window]
    return clear, [back for back in range(1, furthest + 1) if back not in clear]


def _fold(carry, qi, keys, values, mask):
    """One chunk of keys into the running softmax of the queries ``qi``
    (B, C, G, R, Dh); ``mask`` (C, K) booleans, or None where all may be read.

    The product leaves as float32 and is not rounded to a narrower ``dtype``
    first: under ``dtype:bfloat16`` the rounded form gave q and k NaN
    gradients on the chip (not on the CPU; finite with the running maximum
    under ``stop_gradient``, so it is the maximum's gradient finding no score
    equal to it).  In float32 the two forms lower to the same text."""
    scores = jnp.einsum("bqgrd,bkgd->bgrqk", qi, keys, preferred_element_type=jnp.float32)
    scores = scores * (1.0 / math.sqrt(qi.shape[-1]))
    if mask is not None:
        scores = jnp.where(mask, scores, _NEG)
    values = values.astype(jnp.float32)
    return online_softmax_step(
        scores, lambda p: jnp.einsum("bgrqk,bkgd->bgrqd", p, values), *carry)


def causal_attention(q, k, v, cfg, window):
    """q (B, L, G, R, Dh), k and v (B, L, G, Dh) -> (B, L, G * R * Dh): the
    fused kernel where ops/attention.py ``attention_form`` says so (a TPU, and
    a shape the kernel takes), ``chunked_attention`` everywhere else."""
    return attend(q, k, v, Causal(window),
                  lambda q, k, v: chunked_attention(q, k, v, cfg, window))


def chunked_attention(q, k, v, cfg, window):
    """The same in plain XLA: the CPU's form, and what the kernel is held to.
    v may be narrower than q and k (models/deepseek_v3.py: scores over 192,
    values of 128): the scale is q's width's, the accumulator as wide as v.

    A scan over the query chunks; chunk i folds its own keys under the mask,
    then, in two inner scans, the clear and the edged key chunks behind it
    (``key_offsets``), each skipped where it would lie before the sequence: one
    compiled body a kind of fold, whatever L is, and no work for a pair of
    chunks the mask forbids whole.  The chunk is checkpointed: a layer's
    backward pass holds one chunk's scores."""
    (b, length, g, r, dh), dv = q.shape, v.shape[-1]
    chunk, nb_chunks = cfg.attn_chunk, length // cfg.attn_chunk
    within = jnp.arange(chunk)

    def keys_at(j):
        take = lambda a: jax.lax.dynamic_slice_in_dim(a, j * chunk, chunk, axis=1)
        return take(k), take(v)

    @jax.checkpoint
    def one_chunk(_, numbered):
        i, qi = numbered
        q_pos = i * chunk + within
        carry = (jnp.zeros((b, g, r, chunk, dv), jnp.float32),
                 jnp.zeros((b, g, r, chunk), jnp.float32),
                 jnp.full((b, g, r, chunk), _NEG, jnp.float32))
        carry = _fold(carry, qi, *keys_at(i), allowed(q_pos, q_pos, window))
        for offsets, edged in zip(key_offsets(chunk, nb_chunks, window), (False, True)):

            def behind(carry, back, edged=edged):
                j = i - back
                mask = allowed(q_pos, j * chunk + within, window) if edged else None
                return jax.lax.cond(j >= 0, lambda c: _fold(c, qi, *keys_at(j), mask),
                                    lambda c: c, carry), None

            if offsets:
                carry, _ = jax.lax.scan(behind, carry, jnp.asarray(offsets))
        num, den, _ = carry
        out = (num / jnp.maximum(den[..., None], 1e-30)).astype(q.dtype)
        return None, out.transpose(0, 3, 1, 2, 4).reshape(b, chunk, g * r * dv)

    chunks = q.reshape(b, nb_chunks, chunk, g, r, dh).swapaxes(0, 1)
    _, outs = jax.lax.scan(one_chunk, None, (jnp.arange(nb_chunks), chunks))
    return outs.swapaxes(0, 1).reshape(b, length, g * r * dv)


def attention(u, layer, cfg, kind):
    layer_type, _, heads = kind
    b, length, _ = u.shape
    g, r, dh = cfg.kv_heads, heads // cfg.kv_heads, cfg.head_dim
    w = lambda name: layer[name].astype(cfg.dtype)
    table = cfg.full_rope if layer_type == FULL else cfg.sliding_rope
    inv_freq = table.inverse_frequencies(dh)
    turn = lambda x: rope(x, jnp.arange(length), inv_freq, table.attention_factor)
    q = turn((u @ w("wq")).reshape(b, length, g * r, dh))
    k = turn((u @ w("wk")).reshape(b, length, g, dh))
    v = (u @ w("wv")).reshape(b, length, g, dh)
    window = None if layer_type == FULL else cfg.window
    return causal_attention(q.reshape(b, length, g, r, dh), k, v, cfg, window) @ w("wo")


# --------------------------------------------------------------------------- #
#  Feed-forward: a dense unit, or a shared expert beside the held experts     #
# --------------------------------------------------------------------------- #


def gated_unit(u, layer, prefix, dtype):
    w = lambda name: layer[prefix + name].astype(dtype)
    return (jax.nn.silu(u @ w("_gate")) * (u @ w("_up"))) @ w("_down")


def route(tokens, router, cfg):
    """(weights, experts), both (N, experts_per_token): a sigmoid score for
    each of ALL the experts, the largest few, normalised to sum to one."""
    scores = jax.nn.sigmoid((tokens @ router).astype(jnp.float32))
    top_s, top_e = jax.lax.top_k(scores, cfg.experts_per_token)
    return top_s / jnp.sum(top_s, axis=-1, keepdims=True), top_e


def sparse_ffn(u, layer, cfg):
    """(B, S, D) -> (the shared expert plus ``routed_scale`` times the held
    experts' part, positions routed to held experts, held experts idle)."""
    b, s, d = u.shape
    tokens = u.reshape(b * s, d)
    with jax.named_scope("model.router"):
        weights, chosen = route(tokens, layer["router"].astype(cfg.dtype), cfg)
    with jax.named_scope("model.experts"):
        out, routed, idle = held_experts(tokens, weights, chosen, layer, cfg.experts_held,
                                         cfg.dtype)
    with jax.named_scope("model.shared_expert"):
        out = gated_unit(tokens, layer, "ws", cfg.dtype) + cfg.routed_scale * out
    return out.reshape(b, s, d), routed, idle


# --------------------------------------------------------------------------- #
#  The model and its loss                                                     #
# --------------------------------------------------------------------------- #


def dense_ffn(u, layer, cfg):
    with jax.named_scope("model.dense_mlp"):
        return gated_unit(u, layer, "w", cfg.dtype), 0.0, 0.0


FFN = {DENSE: dense_ffn, SPARSE: sparse_ffn}


def decoder_layer(x, layer, cfg, kind):
    layer_type, mlp_type, _ = kind
    norm = lambda x, name: rms_norm(x, layer[name].astype(cfg.dtype), cfg.norm_eps)
    with jax.named_scope("model.attention_" + ("full" if layer_type == FULL else "window")):
        x = x + attention(norm(x, "attn_norm"), layer, cfg, kind)
    y, routed, idle = FFN[mlp_type](norm(x, "mlp_norm"), layer, cfg)
    return x + y, routed, idle


def layer_runs(x, counters, runs, groups, layer, policy=None):
    """``x`` through the model's runs of stacked layers: ``layer(x, leaves,
    kind) -> (x, *counts)`` once a layer under ``jax.checkpoint``, a run of
    several under ``lax.scan``; each count is added to its place in
    ``counters``.  Returns ``(x, *counters)``.  (``policy``: what a layer keeps.)"""
    carry = (x,) + tuple(counters)
    for (kind, count), group in zip(runs, groups):

        @(lambda body: jax.checkpoint(body, policy=policy))
        def body(carry, leaves, kind=kind):
            x, *counts = layer(carry[0], leaves, kind)
            return (x,) + tuple(so_far + more for so_far, more in zip(carry[1:], counts)), None

        if count == 1:
            carry, _ = body(carry, jax.tree.map(lambda leaf: leaf[0], group))
        else:
            carry, _ = jax.lax.scan(body, carry, group)
    return carry


def next_token_loss(x, params, targets, cfg):
    """The mean over the positions of ``-log softmax(logits)[target]``."""
    with jax.named_scope("model.head"):
        hidden = rms_norm(x, params["final_norm"].astype(cfg.dtype), cfg.norm_eps)
        logp = jax.nn.log_softmax((hidden @ params["head"].astype(cfg.dtype)).astype(jnp.float32))
        return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))


def loss_and_counters(params, batch, cfg):
    """``batch``: ``tokens`` (B, L + 1).  Returns the next-token loss (mean
    over the B x L positions) and the step's counters."""
    inputs, targets = batch["tokens"][:, :-1], batch["tokens"][:, 1:]
    with jax.named_scope("model.embed"):
        x = params["embed"][inputs].astype(cfg.dtype)
    x, routed, idle = layer_runs(
        x, (jnp.float32(0), jnp.float32(0)), cfg.runs(), params["layers"],
        lambda x, leaves, kind: decoder_layer(x, leaves, cfg, kind))
    return next_token_loss(x, params, targets, cfg), {
        "routed_positions": routed, "idle_held_experts": idle}


def seeded_corpus(rows, length, vocab, seed=0):
    """(rows, length + 1) int32 token rows from a seed: the machine has no
    network, and speed and agreement with the reference need no text."""
    return np.random.default_rng(seed).integers(0, vocab, size=(rows, length + 1), dtype=np.int32)


class LagunaExperiment(Experiment):
    """Next-token training of one chip's share of Laguna-XS.2.

    Args (key:value), defaults = grid/configs/laguna-xs2-ep32-n3.json:
    vocab:12544 hidden:2048 kv-heads:8 head-dim:128
    layer-types:full,sliding,sliding,sliding,full
    mlp-types:dense,sparse,sparse,sparse,sparse heads:48,64,64,64,48 window:512
    dense-width:8192 experts:256 experts-per-token:8 expert-width:512
    shared-width:512 routed-scale:2.5 experts-held:0-7 seq:4096 attn-chunk:256
    batch-size:1 corpus:256 dtype:float32; the two RoPE tables are the config's
    (``LagunaConfig``).  The batch a worker is handed is ``{"tokens": (B, seq + 1)}``.
    """

    #: the configuration's sizes that are arguments under their own names
    SIZES = ("vocab", "hidden", "kv_heads", "head_dim", "window", "dense_width", "experts",
             "experts_per_token", "expert_width", "shared_width", "routed_scale", "seq",
             "attn_chunk")

    def __init__(self, args):
        super().__init__(args)
        base, dashed = LagunaConfig(), lambda name: name.replace("_", "-")
        listed = lambda values: ",".join(map(str, values))
        kv = parse_keyval(args, strict=True, defaults=dict(
            {dashed(name): getattr(base, name) for name in self.SIZES},
            **{"layer-types": listed(base.layer_types), "mlp-types": listed(base.mlp_types),
               "heads": listed(base.heads), "experts-held": "0-7", "batch-size": 1, "corpus": 256,
               "dtype": "float32"}))
        self.cfg = LagunaConfig(
            layer_types=tuple(str(kv["layer-types"]).split(",")),
            mlp_types=tuple(str(kv["mlp-types"]).split(",")),
            heads=tuple(int(heads) for heads in str(kv["heads"]).split(",")),
            experts_held=_parse_held(kv["experts-held"]), dtype=check_dtype(kv["dtype"]),
            **{name: kv[dashed(name)] for name in self.SIZES}).check()
        self.batch_size = kv["batch-size"]
        self.corpus = seeded_corpus(kv["corpus"], self.cfg.seq, self.cfg.vocab)

    #: the family's own two functions of ``cfg``; another family trained on the next token
    #: from the same seeded rows (models/deepseek_v3.py) names its own and its ``__init__``
    init_params = staticmethod(init_params)
    loss_and_counters = staticmethod(loss_and_counters)

    def init(self, rng):
        return self.init_params(self.cfg, rng)

    def loss(self, params, batch):
        """(loss, counters): the engine carries the counters with the loss
        (``has_aux``, parallel/engine.py ``_worker_gradients``)."""
        return self.loss_and_counters(params, batch, self.cfg)

    loss.has_aux = True

    def metrics(self, params, batch):
        loss, _counters = self.loss_and_counters(params, batch, self.cfg)
        return {"loss": (loss, jnp.float32(1))}

    def device_transform(self):
        return None

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


register("laguna", LagunaExperiment)


# The token rows' start-up span (``startup.data_host``, obs/trace.py) goes on down here, import
# and all: a line added further up would move the frames of this file that the attention
# kernels' serialized bodies carry (file and line of each caller), and with them the key of the
# step program in the persistent compilation cache (PERF.md §6, PR 37).
from .datasets import data_host  # noqa: E402

seeded_corpus = data_host(lambda rows: (len(rows), rows.nbytes))(seeded_corpus)
