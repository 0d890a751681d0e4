"""Masked attention as one fused Pallas kernel, forward and backward.

What models/laguna.py's chunked form does in XLA — a scan of query chunks, each
folding 256 x 256 blocks of scores through HBM into a running softmax, and a
backward pass that stacks every fold's score-shaped residuals and adds every
fold's key gradient into a whole-length buffer — done here from VMEM:

- **Forward** (``causal_attention_fwd``): one grid step a (batch, kv head,
  query tile).  The ``R`` query heads that share the kv head are stacked on
  the tile's rows (R x q_tile rows of ``Dh``), so one product against a
  (k_tile, Dh) tile of keys serves them all; the head's whole K and V sit in
  VMEM for all its query tiles (read from HBM once a head), and the loop over
  key tiles runs inside the kernel: scores, running maximum, running sums and
  the accumulator never leave VMEM.  Out come the normalised output and ONE
  log-sum-exp a query a head — equal along 128 lanes, which is how the chip
  lays a last dimension of one out anyway: the row statistics are kept so
  inside the kernel too (``_fwd_kernel``), so that no fold spreads one across
  the lanes again.
- **Backward** (``causal_attention_bwd``): one kernel, the same grid and the
  same loop.  A tile's scores are made again from q, k and the log-sum-exp
  (five products a tile: scores, dP, dq, dk, dv); dq leaves a query tile at a
  time, dk and dv are accumulated in the head's VMEM-resident (L, Dh) output
  blocks and written to HBM once a head.  Nothing score-shaped and nothing
  whole-length-per-fold is written.

**The mask is an argument.**  ``mask(q_index, k_index)`` says, on broadcastable
integer arrays (numpy at trace time, vectors inside the kernel), whether the
query may read the key; ``Causal(window)`` is models/laguna.py's.  From it
``tile_table`` classes every (query tile, key tile) pair by brute force at
trace time — SKIPPED (no pair allowed: never looped over), CLEAR (all allowed:
no mask applied), EDGED (the mask applied) — and ``_slots`` turns each query
tile's row of it into a fixed sequence of key-tile ranges, edged and clear by
turns, whose bounds the kernel picks by its grid index.  A block-diffusion
mask is one more predicate, not another kernel.

**A mask may be data.**  ``Selected(k)`` (models/keye_vl2.py: each query reads
the ``k`` keys a learned indexer chose for it) names no predicate of two
indices: its allowed pairs are an array the step computes, handed to ``attend``
/ ``fused_attention`` as the operand ``pairs`` — int8, nonzero where the query
reads the key, one (L, L) a batch entry for all heads.  What is known of it at
trace time is its structure, the causal triangle: its tile table is
``Causal()``'s with every tile under the diagonal EDGED, and where an EDGED
tile of a predicate asks the predicate, one of ``Selected`` reads the pairs'
(q_tile, k_tile) block of the (batch, query tile) at hand (a query tile's whole
row of key tiles rides in VMEM beside it).  The same two kernels, under names
of their own (``selected_attention_fwd`` / ``_bwd``); every query has to read
some key, in any tile: rows of a tile that read none of its keys fold
nothing, whichever tile comes first.

**The same arithmetic as the XLA form, and no less**: float32 operands into
every product, float32 accumulation, scores, maxima, exponentials and sums; a
narrower input is widened on load, so its scores are float32 too.  The
products carry the process's matmul precision as XLA's do: at the default the
chip multiplies the float32 operands in one bfloat16 pass (read on the chip:
0.4 % from ``highest``, PERF.md section 6, PR 36), under ``highest`` in full.

**Two widths, each its own.**  Scores may be wider than values
(models/deepseek_v3.py's latent attention: 192 over 128): q, k, dq and dk are
cut at ``Dqk``, v, the output, its cotangent and dv at ``Dv``, out of the (B, L,
G * D) arrays as they lie in HBM — no padded copy in, no slice out.  A width
that is not whole lanes takes the fewest heads a grid step whose widths side by
side are (two at 192: blocks of 384 and 256 lanes), one head after another; a
head's scores, dq and dk run over the 128-lane columns it touches (``_span``:
256 lanes, 64 of them its neighbour's) against queries that hold zeros there,
so every fold reads and writes whole vregs and the MXU, which contracts 128
deep a pass, multiplies what it would for 192.

**One chooser** (``attention_form``): on a TPU, for shapes the kernel takes,
the kernel; else the caller's XLA form.  No flag and no environment variable;
``forced_form`` is the one scoped seam, for the tests and
scripts/pallas_tpu_check.py.  Off a TPU a forced kernel interprets.
"""

import contextlib
import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils import hw, info
from .pallas_kernels import LANE

#: The finite stand-in for minus infinity of a forbidden score (the running
#: softmax of models/transformer.py uses the same): exp(NEG - max) is 0.
NEG = -1e30

SKIPPED, CLEAR, EDGED = 0, 1, 2

#: Queries and keys a tile: the least forward + backward time of a step's
#: five layers in a sweep on the chip over 128-512 x 128-1,024 (PERF.md
#: section 6, PR 36).
Q_TILE = 256
K_TILE = 256

#: Queries and keys a tile where a key head serves ONE query head (latent
#: attention): a tile's products then stream Q_TILE rows, not R x Q_TILE, past
#: each key tile's weights, and at 256 the fold is the MXU's weight loads and
#: the row statistics; at 512 x 512 the forward kernel takes 0.56 of its time
#: and the backward 0.84 (a sweep on the chip over 128-2,048 x 256-1,024,
#: PERF.md section 6, PR 41).
LONE_TILE = 512

#: The kernel keeps a head's whole K and V (the backward pass dk and dv too) in
#: VMEM: L x Dh elements each.  Beyond this many the XLA form runs.
RESIDENT_MAX = 8192 * 128

#: What the compiler may use of VMEM (v5e: 128 MiB a core; its own default
#: is 16): the backward pass at L = 4096 and 8 query heads a kv head holds
#: ~40 MB, most of it K, V, dk and dv, double-buffered.
VMEM_LIMIT = 100 * 1024 * 1024


@dataclasses.dataclass(frozen=True)
class Causal:
    """Query i reads key j iff ``j <= i`` and, with a ``window``, ``i - j <
    window``."""

    window: int = None

    def __call__(self, q_index, k_index):
        back = q_index - k_index
        return back >= 0 if self.window is None else (back >= 0) & (back < self.window)


@dataclasses.dataclass(frozen=True)
class Selected:
    """Query i reads key j iff the call's ``pairs`` operand is nonzero at (i,
    j): ``k`` keys a query chosen by data among those up to its own (every one
    of them while there are no more than ``k``).  Called as a predicate it says
    where a pair MAY be allowed, which is all that trace time knows."""

    k: int

    def __call__(self, q_index, k_index):
        return q_index >= k_index


@functools.lru_cache(maxsize=None)
def tile_table(mask, length, q_tile, k_tile):
    """(length / q_tile, length / k_tile) of SKIPPED, CLEAR or EDGED: ``mask``
    over every pair of positions, tile by tile."""
    index = np.arange(length)
    with jax.ensure_compile_time_eval():  # a predicate may speak jnp: it also runs in the kernel
        allowed = np.asarray(mask(index[:, None], index[None, :]))
    tiles = allowed.reshape(length // q_tile, q_tile, length // k_tile, k_tile)
    table = np.where(tiles.all(axis=(1, 3)), CLEAR,
                     np.where(tiles.any(axis=(1, 3)), EDGED, SKIPPED)).astype(np.int8)
    if isinstance(mask, Selected):  # which of the pairs that may be allowed are: only the data says
        table[table == CLEAR] = EDGED
    table.setflags(write=False)
    return table


def table_counts(table):
    """{"clear", "edged", "skipped"}: tiles of each class a head."""
    return {name: int(np.sum(table == kind))
            for name, kind in (("clear", CLEAR), ("edged", EDGED), ("skipped", SKIPPED))}


def _slots(table):
    """The kernel's loops: ``((starts, stops), ...)``, slot s a range of key
    tiles a query tile — ``starts[i] <= j < stops[i]`` — EDGED for even s and
    CLEAR for odd; a query tile without a run of that class there has an empty
    range.  Every tile not SKIPPED lies in exactly one range."""
    rows = []
    for row in table:
        runs = []
        for j, kind in enumerate(row):
            if kind == SKIPPED:
                continue
            if runs and runs[-1][1] == j and runs[-1][2] == kind:
                runs[-1][1] = j + 1
            else:
                runs.append([j, j + 1, kind])
        placed = []
        for start, stop, kind in runs:
            while (EDGED if len(placed) % 2 == 0 else CLEAR) != kind:
                placed.append((0, 0))
            placed.append((start, stop))
        rows.append(placed)
    depth = max(len(placed) for placed in rows)
    rows = [placed + [(0, 0)] * (depth - len(placed)) for placed in rows]
    return tuple((tuple(placed[s][0] for placed in rows), tuple(placed[s][1] for placed in rows))
                 for s in range(depth))


def _pick(i, values):
    """``values[i]`` for the traced grid index ``i``: selects on the scalar
    unit, no table in memory."""
    if len(set(values)) == 1:
        return jnp.int32(values[0])
    return sum(jnp.where(i == n, jnp.int32(value), 0) for n, value in enumerate(values) if value)


def _interpret():
    return not hw.on_tpu()


def _whole_lanes(width):
    """``width`` rounded up to whole 128-lane columns."""
    return -(-width // LANE) * LANE


def _heads_a_step(kv_heads, dqk, dv):
    """Key heads a grid step: the fewest, dividing ``kv_heads``, whose scores'
    and values' widths side by side are whole lanes (one at widths of whole
    lanes; two at 192 over 128), so that a block of them is cut out of the
    (B, L, G * D) arrays as they lie; one where no count does (a shape only
    the interpreter takes)."""
    return next((h for h in range(1, kv_heads + 1)
                 if kv_heads % h == 0 and h * dqk % LANE == 0 and h * dv % LANE == 0), 1)


def _span(head, width, heads):
    """(first lane, lanes, lanes in front of the head) of the whole lanes that
    hold head ``head`` of ``width`` in a block of ``heads``: the 128-lane
    columns it touches — its own where the width is whole lanes; at 192, lanes
    0-255 for head 0 and 128-383 for head 1, 64 of the other head's in each.
    A block that is not whole lanes (the interpreter's) is cut lane by lane."""
    unit = 1 if heads * width % LANE else LANE
    lo = head * width
    first = lo // unit * unit
    return first, -(-(lo + width) // unit) * unit - first, lo - first


def _stack(ref, scr, head, rep, q_tile, width, scale=None, front=0):
    """The ``rep`` query heads of key head ``head`` in the (q_tile, heads * R *
    width) block of ``ref``, head beside head, into the rows of ``scr`` (R *
    q_tile, at least front + width), head under head, ``front`` lanes in, as
    float32."""
    for r in range(rep):
        at = (head * rep + r) * width
        piece = ref[:, at:at + width].astype(jnp.float32)
        scr[r * q_tile:(r + 1) * q_tile, front:front + width] = (
            piece if scale is None else piece * scale)


def _scores(qs, k_ref, lanes, j, i, edged, mask, rep, q_tile, k_tile, pairs_ref=None):
    """(R * q_tile, k_tile) float32 scores of the stacked, scaled queries
    against ``lanes`` of key tile ``j``, forbidden pairs at NEG where the tile
    is EDGED: those ``mask`` forbids, or with ``pairs_ref`` (the query tile's
    (q_tile, L) int8 rows of a mask that is data) those it holds a zero for."""
    keys = k_ref[pl.ds(pl.multiple_of(j * k_tile, k_tile), k_tile), lanes].astype(jnp.float32)
    s = jax.lax.dot_general(qs, keys, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if not edged:
        return s
    if pairs_ref is None:
        q_index = i * q_tile + jax.lax.broadcasted_iota(jnp.int32, (q_tile, k_tile), 0)
        k_index = j * k_tile + jax.lax.broadcasted_iota(jnp.int32, (q_tile, k_tile), 1)
        ok = mask(q_index, k_index)
    else:
        tile = pl.ds(pl.multiple_of(j * k_tile, k_tile), k_tile)
        ok = pairs_ref[:, tile].astype(jnp.int32) != 0
    return jnp.concatenate([jnp.where(ok, s[r * q_tile:(r + 1) * q_tile], NEG)
                            for r in range(rep)], axis=0)


def _loops(i, slots, fold):
    """``fold(j, edged)`` over every key tile of query tile ``i``'s ranges."""
    for slot, (starts, stops) in enumerate(slots):

        def body(j, carry, edged=slot % 2 == 0):
            fold(j, edged)
            return carry

        jax.lax.fori_loop(_pick(i, starts), _pick(i, stops), body, 0)


def _wide(stat, width):
    """A (rows, lanes) row statistic, equal along its lanes, as wide as a
    (rows, width) tile: the same vregs side by side, nothing moved."""
    lanes = stat.shape[1]
    return stat if width == lanes else jnp.concatenate([stat] * (width // lanes), axis=1)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, qs, m, l, acc, *, mask, slots, rep, heads,
                q_tile, k_tile, dqk, dv, pairs_ref=None):
    """The running maximum ``m`` is kept equal along 128 lanes, so no fold
    spreads it again; the running sum ``l`` is kept lane by lane (lane c holds
    the sum over the keys c, c + 128, ... of every tile) and summed across the
    lanes once, at the end.  The step's ``heads`` key heads one after another,
    each over its ``_span`` of the keys' lanes: the queries' lanes beside the
    head's own are zeros, so what the span holds of a neighbour multiplies
    nothing into the scores."""
    i, lanes, rows = pl.program_id(2), l.shape[1], rep * q_tile
    for h in range(heads):
        first, span, front = _span(h, dqk, heads)
        if span != dqk:
            qs[...] = jnp.zeros(qs.shape, jnp.float32)
        _stack(q_ref, qs, h, rep, q_tile, dqk, 1.0 / math.sqrt(dqk), front)
        m[...] = jnp.full(m.shape, NEG, jnp.float32)
        l[...] = jnp.zeros(l.shape, jnp.float32)
        acc[...] = jnp.zeros(acc.shape, jnp.float32)

        def fold(j, edged, across=slice(first, first + span), own=slice(h * dv, (h + 1) * dv)):
            s = _scores(qs[...], k_ref, across, j, i, edged, mask, rep, q_tile, k_tile, pairs_ref)
            values = v_ref[pl.ds(pl.multiple_of(j * k_tile, k_tile), k_tile), own].astype(
                jnp.float32)
            new_m = jnp.maximum(m[...], jnp.max(s, axis=1, keepdims=True))
            corr = jnp.exp(m[...] - new_m)
            p = jnp.exp(s - _wide(new_m, k_tile))
            l[...] = l[...] * corr + sum(p[:, c:c + lanes] for c in range(0, k_tile, lanes))
            acc[...] = acc[...] * _wide(corr, dv) + jnp.dot(p, values,
                                                            preferred_element_type=jnp.float32)
            m[...] = new_m

        _loops(i, slots, fold)
        total = jnp.broadcast_to(jnp.sum(l[...], axis=1, keepdims=True), l.shape)
        out = acc[...] / _wide(total, dv)
        for r in range(rep):
            at = (h * rep + r) * dv
            o_ref[:, at:at + dv] = out[r * q_tile:(r + 1) * q_tile].astype(o_ref.dtype)
        lse_ref[h * rows:(h + 1) * rows] = m[...] + jnp.log(total)   # head under head


def _bwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, do_ref, dq_ref, dk_ref, dv_ref, qs, dos,
                delta, dq, *, mask, slots, rep, heads, q_tile, k_tile, dqk, dv, pairs_ref=None):
    """Scores, dq and dk over a head's ``_span`` of the keys' lanes (the lanes
    beside the head's own: zeros in ``qs``, so nothing into the scores and
    zeros into the neighbour's dk; in ``dq`` what is dropped on the way out);
    dP and dv over the values' own width."""
    i, rows = pl.program_id(2), rep * q_tile
    scale = 1.0 / math.sqrt(dqk)
    for h in range(heads):
        first, span, front = _span(h, dqk, heads)
        if span != dqk:
            qs[...] = jnp.zeros(qs.shape, jnp.float32)
        _stack(q_ref, qs, h, rep, q_tile, dqk, scale, front)
        _stack(do_ref, dos, h, rep, q_tile, dv)
        _stack(o_ref, dq, h, rep, q_tile, dv)   # the output, in dq's room for a moment
        delta[...] = jnp.broadcast_to(
            jnp.sum(dos[...] * dq[:, :dv], axis=1, keepdims=True),
            delta.shape)
        dq[...] = jnp.zeros(dq.shape, jnp.float32)

        if h == 0:
            @pl.when(i == 0)
            def _():
                dk_ref[...] = jnp.zeros(dk_ref.shape, jnp.float32)
                dv_ref[...] = jnp.zeros(dv_ref.shape, jnp.float32)

        def fold(j, edged, across=slice(first, first + span), own=slice(h * dv, (h + 1) * dv),
                 stats=slice(h * rows, (h + 1) * rows)):
            tile = pl.ds(pl.multiple_of(j * k_tile, k_tile), k_tile)
            s = _scores(qs[...], k_ref, across, j, i, edged, mask, rep, q_tile, k_tile, pairs_ref)
            p = jnp.exp(s - _wide(lse_ref[stats], k_tile))
            dp = jax.lax.dot_general(dos[...], v_ref[tile, own].astype(jnp.float32),
                                     (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
            ds = p * (dp - _wide(delta[...], k_tile))
            dq[...] += jnp.dot(ds, k_ref[tile, across].astype(jnp.float32),
                               preferred_element_type=jnp.float32)
            over_rows = (((0,), (0,)), ((), ()))
            dk_ref[tile, across] += jax.lax.dot_general(ds, qs[...], over_rows,
                                                        preferred_element_type=jnp.float32)
            dv_ref[tile, own] += jax.lax.dot_general(p, dos[...], over_rows,
                                                     preferred_element_type=jnp.float32)

        _loops(i, slots, fold)
        for r in range(rep):
            at = (h * rep + r) * dqk
            dq_ref[:, at:at + dqk] = (dq[r * q_tile:(r + 1) * q_tile, front:front + dqk]
                                      * scale).astype(dq_ref.dtype)


def _lanes(k_tile, dv):
    """Lanes a row statistic is kept along: a vreg's 128 at the shapes the
    compiled kernel takes, fewer under the interpreter's small tiles."""
    return math.gcd(LANE, k_tile, dv)


def _specs(length, rep, heads, width, q_tile):
    """Block specs of the (B, L, G * R * width) queries' kind and the (B, L, G *
    width) keys' kind, ``heads`` key heads a step, on the grid (b, g, i)."""
    per_query = pl.BlockSpec((None, q_tile, heads * rep * width), lambda b, g, i: (b, i, g))
    per_head = pl.BlockSpec((None, length, heads * width), lambda b, g, i: (b, 0, g))
    return per_query, per_head


def _call(kernel, name, out_shape, in_specs, out_specs, scratch, grid, sequential, **static):
    return pl.pallas_call(
        functools.partial(kernel, **static), out_shape=out_shape, grid=grid, in_specs=in_specs,
        out_specs=out_specs, scratch_shapes=scratch, name=name, interpret=_interpret(),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary" if sequential else "parallel"),
            vmem_limit_bytes=VMEM_LIMIT))


def _static(q, v, plan):
    """What both kernels are told of the call, from its ``plan`` — (mask, its
    loops, queries a tile, keys a tile): (keyword arguments; block specs at the
    scores' width, at the values' and of the log-sum-exp; the grid; lanes of a
    row statistic; lanes of a head's span; float32 scratch of a head's stacked
    rows a tile, by width)."""
    mask, slots, q_tile, k_tile = plan
    b, length, g, rep, dqk = q.shape
    dv = v.shape[-1]
    heads, lanes, rows = _heads_a_step(g, dqk, dv), _lanes(k_tile, dv), rep * q_tile
    per_row = pl.BlockSpec((None, None, heads * rows, lanes), lambda b, g, i: (b, g, i, 0))
    return (dict(mask=mask, slots=slots, rep=rep, heads=heads, q_tile=q_tile, k_tile=k_tile,
                 dqk=dqk, dv=dv),
            _specs(length, rep, heads, dqk, q_tile) + _specs(length, rep, heads, dv, q_tile)
            + (per_row,), (b, g // heads, length // q_tile), lanes, _span(0, dqk, heads)[1],
            lambda width: pltpu.VMEM((rows, width), jnp.float32))


def _pairs_at(kernel, place, q_tile, length):
    """(``kernel`` taking the pairs' ref as its input number ``place``, that
    input's block spec): a query tile's (q_tile, L) rows of the (B, L, L) int8
    pairs, the same for every key head of the grid."""
    def with_pairs(*refs, **static):
        return kernel(*refs[:place], *refs[place + 1:], pairs_ref=refs[place], **static)

    return with_pairs, pl.BlockSpec((None, q_tile, length), lambda b, g, i: (b, i, 0))


def _forward(q, k, v, plan, pairs):
    """(the output (B, L, G * R * Dv), the log-sum-exp (B, G / heads a step, L *
    R * heads a step, lanes): a query tile's rows head under head).  With
    ``pairs`` (a mask that is data) they are one more input, and the call has
    its own name."""
    b, length, g, rep, dqk = q.shape
    dv = v.shape[-1]
    static, (q_wide, k_wide, o_wide, v_wide, per_row), grid, lanes, span, room = _static(q, v, plan)
    kernel, name, inputs, operands = _fwd_kernel, "causal_attention_fwd", [q_wide, k_wide, v_wide], ()
    if pairs is not None:
        kernel, spec = _pairs_at(_fwd_kernel, 3, static["q_tile"], length)
        name, inputs, operands = "selected_attention_fwd", inputs + [spec], (pairs,)
    return _call(
        kernel, name,
        (jax.ShapeDtypeStruct((b, length, g * rep * dv), q.dtype),
         jax.ShapeDtypeStruct((b, grid[1], length * rep * static["heads"], lanes), jnp.float32)),
        inputs, (o_wide, per_row),
        [room(span), room(lanes), room(lanes), room(dv)], grid, False, **static)(
            q.reshape(b, length, g * rep * dqk), k.reshape(b, length, g * dqk),
            v.reshape(b, length, g * dv), *operands)


def _backward(plan, kept, dout):
    """The rule's backward pass: (dq, dk, dv, the pairs' cotangent).  ``pairs``
    are int8 and take the zero of their tangent type, ``float0``; a
    predicate's ``None`` takes ``None``."""
    q, k, v, out, lse, pairs = kept
    b, length, g, rep, dqk = q.shape
    dv = v.shape[-1]
    static, (q_wide, k_wide, o_wide, v_wide, per_row), grid, lanes, span, room = _static(q, v, plan)
    kernel, name, operands = _bwd_kernel, "causal_attention_bwd", ()
    inputs = [q_wide, k_wide, v_wide, o_wide, per_row, o_wide]
    if pairs is not None:
        kernel, spec = _pairs_at(_bwd_kernel, 6, static["q_tile"], length)
        name, inputs, operands = "selected_attention_bwd", inputs + [spec], (pairs,)
    grads = _call(
        kernel, name,
        (jax.ShapeDtypeStruct((b, length, g * rep * dqk), q.dtype),
         jax.ShapeDtypeStruct((b, length, g * dqk), jnp.float32),
         jax.ShapeDtypeStruct((b, length, g * dv), jnp.float32)),
        inputs, (q_wide, k_wide, v_wide),
        [room(span), room(dv), room(lanes), room(span)], grid, True, **static)(
            q.reshape(b, length, g * rep * dqk), k.reshape(b, length, g * dqk),
            v.reshape(b, length, g * dv), out, lse, dout, *operands)
    no_tangent = None if pairs is None else np.zeros(pairs.shape, jax.dtypes.float0)
    return tuple(grad.reshape(a.shape).astype(a.dtype)
                 for grad, a in zip(grads, (q, k, v))) + (no_tangent,)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _fused(q, k, v, pairs, plan):
    return _forward(q, k, v, plan, pairs)[0]


def _fused_fwd(q, k, v, pairs, plan):
    out, lse = _forward(q, k, v, plan, pairs)
    return out, (q, k, v, out, lse, pairs)


_fused.defvjp(_fused_fwd, _backward)


def fused_attention(q, k, v, mask, q_tile, k_tile, pairs=None):
    """q (B, L, G, R, Dqk), k (B, L, G, Dqk) and v (B, L, G, Dv), Dv no wider
    than Dqk -> (B, L, G * R * Dv): the softmax over the keys ``mask`` allows
    of ``q . k / sqrt(Dqk)``, times v, with its own backward pass.  ``L`` is a
    multiple of both tiles; every query reads some key.  The tile table is made
    here, at trace time, from the static arguments alone; the kernels are
    handed its loops, and q, k and v at the widths they have.  Under
    ``Selected`` the allowed pairs are the operand ``pairs``, (B, L, L) int8,
    nonzero where the query (its row) reads the key."""
    plan = (mask, _slots(tile_table(mask, q.shape[1], q_tile, k_tile)), q_tile, k_tile)
    if isinstance(mask, Selected) != (pairs is not None):
        raise ValueError("%r and pairs %s: a mask that is data comes with its pairs, a "
                         "predicate without" % (mask, "given" if pairs is not None else "missing"))
    return _fused(q, k, v, pairs, plan)


# --------------------------------------------------------------------------- #
#  The chooser                                                                #
# --------------------------------------------------------------------------- #

#: The form ``forced_form`` holds ``attention_form`` to; ``None`` outside it.
_forced = None


@contextlib.contextmanager
def forced_form(form):
    """Hold ``attention_form`` to ``"kernel"`` or ``"xla"`` for what is TRACED
    inside the block.  The seam of the parity tests and of
    scripts/pallas_tpu_check.py's XLA column; no training path enters it."""
    global _forced
    if form not in ("kernel", "xla"):
        raise ValueError("forced_form takes 'kernel' or 'xla', got %r" % (form,))
    previous, _forced = _forced, form
    try:
        yield
    finally:
        _forced = previous


def tiles_for(length, rep):
    """(queries, keys) a tile for a sequence of ``length`` under ``rep`` query
    heads a key head: ``LONE_TILE`` for one, where it divides the length; else
    the kernel's own, or the whole of a shorter sequence."""
    if rep == 1 and length % LONE_TILE == 0:
        return LONE_TILE, LONE_TILE
    return min(Q_TILE, length), min(K_TILE, length)


def attention_form(length, head_dim, v_dim=None, kv_heads=1, rep=1):
    """``"kernel"`` or ``"xla"`` for a sequence of ``length`` under ``kv_heads``
    key heads of ``head_dim`` (values of ``v_dim``, where they differ), ``rep``
    query heads each: the kernel on a TPU (``utils.hw.on_tpu``) where it takes
    the shape — ``length`` a multiple of both tiles and of the 8 sublanes;
    values of whole lanes, no wider than the scores; scores of whole lanes, or
    one query head a key head and a count of key heads that ``_heads_a_step``
    divides into blocks of whole lanes (192: an even count); a head's K and V,
    each at its width rounded up to whole lanes, within ``RESIDENT_MAX`` a piece
    — and the caller's XLA form everywhere else.  Inside ``forced_form`` the
    forced form answers, for any shape whose length divides into the tiles."""
    q_tile, k_tile = tiles_for(length, rep)
    divides = length % q_tile == 0 and length % k_tile == 0
    if _forced is not None:
        if _forced == "kernel" and not divides:
            raise ValueError("the attention kernel takes a length that divides into its tiles "
                             "(%d, %d), not %d" % (q_tile, k_tile, length))
        return _forced
    v_dim = v_dim or head_dim
    blocks = head_dim % LANE == 0 or (
        rep == 1 and _heads_a_step(kv_heads, head_dim, v_dim) * head_dim % LANE == 0)
    takes = (divides and length % 8 == 0 and v_dim % LANE == 0 and v_dim <= head_dim and blocks
             and length * (_whole_lanes(head_dim) + _whole_lanes(v_dim)) <= 2 * RESIDENT_MAX)
    return "kernel" if hw.on_tpu() and takes else "xla"


@functools.lru_cache(maxsize=None)
def _announce(form, shape, v_dim, mask, tiles):
    counts, widths = "", ""
    if form == "kernel":
        counts = "; tiles of %dx%d a head: %d clear, %d edged, %d skipped" % (
            tiles + tuple(table_counts(tile_table(mask, shape[1], *tiles)).values()))
    if v_dim != shape[-1]:
        widths = " over values of %d" % v_dim
        if form == "kernel":
            form = "kernel at %d / %d" % (shape[-1], v_dim)
    info("attention form for q %s%s under %r: %s%s"
         % ("x".join(map(str, shape)), widths, mask, form, counts))


def attend(q, k, v, mask, xla_form, pairs=None):
    """``fused_attention`` where ``attention_form`` says so, else
    ``xla_form(q, k, v)``; v may be narrower than q and k (``fused_attention``).
    ``pairs`` are the allowed pairs of a mask that is data (``Selected``): the
    kernel's operand, and what the caller's ``xla_form`` holds itself.
    On a TPU each decision is logged once a shape and mask, with the tile
    table's three counts."""
    _, length, kv_heads, rep, head_dim = q.shape
    form = attention_form(length, head_dim, v.shape[-1], kv_heads, rep)
    tiles = tiles_for(length, rep)
    if hw.on_tpu():
        _announce(form, tuple(q.shape), v.shape[-1], mask, tiles)
    if form != "kernel":
        return xla_form(q, k, v)
    return fused_attention(q, k, v, mask, *tiles, pairs=pairs)
