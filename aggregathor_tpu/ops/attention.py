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

**The same arithmetic as the XLA form, and no less**: float32 operands into
every product, float32 accumulation, scores, maxima, exponentials and sums; a
narrower input is widened on load, so its scores are float32 too.  The
products carry the process's matmul precision as XLA's do: at the default the
chip multiplies the float32 operands in one bfloat16 pass (read on the chip:
0.4 % from ``highest``, PERF.md section 6, PR 36), under ``highest`` in full.

**Two widths by padding.**  The kernels carry one head width; scores wider
than values (models/deepseek_v3.py's latent attention: 192 over 128) run at the
next multiple of the lanes that holds both, zeros in the rest
(``fused_attention``): a third of the score product and half of the value
product multiply zeros until a kernel carries the two widths itself.

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


def _stack(ref, scr, rep, q_tile, dh, scale=None):
    """The (q_tile, R * Dh) block of ``ref``, head beside head, into the rows
    of ``scr`` (R * q_tile, Dh), head under head, as float32."""
    for r in range(rep):
        piece = ref[:, r * dh:(r + 1) * dh].astype(jnp.float32)
        scr[r * q_tile:(r + 1) * q_tile, :] = piece if scale is None else piece * scale


def _scores(qs, k_ref, j, i, edged, mask, rep, q_tile, k_tile):
    """(R * q_tile, k_tile) float32 scores of the stacked, scaled queries
    against key tile ``j``, forbidden pairs at NEG where the tile is EDGED."""
    keys = k_ref[pl.ds(pl.multiple_of(j * k_tile, k_tile), k_tile), :].astype(jnp.float32)
    s = jax.lax.dot_general(qs, keys, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if not edged:
        return s
    q_index = i * q_tile + jax.lax.broadcasted_iota(jnp.int32, (q_tile, k_tile), 0)
    k_index = j * k_tile + jax.lax.broadcasted_iota(jnp.int32, (q_tile, k_tile), 1)
    ok = mask(q_index, k_index)
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


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, qs, m, l, acc, *, mask, slots, rep,
                q_tile, k_tile, dh):
    """The running maximum ``m`` is kept equal along 128 lanes, so no fold
    spreads it again; the running sum ``l`` is kept lane by lane (lane c holds
    the sum over the keys c, c + 128, ... of every tile) and summed across the
    lanes once, at the end."""
    i, lanes = pl.program_id(2), l.shape[1]
    _stack(q_ref, qs, rep, q_tile, dh, 1.0 / math.sqrt(dh))
    m[...] = jnp.full(m.shape, NEG, jnp.float32)
    l[...] = jnp.zeros(l.shape, jnp.float32)
    acc[...] = jnp.zeros(acc.shape, jnp.float32)

    def fold(j, edged):
        s = _scores(qs[...], k_ref, j, i, edged, mask, rep, q_tile, k_tile)
        values = v_ref[pl.ds(pl.multiple_of(j * k_tile, k_tile), k_tile), :].astype(jnp.float32)
        new_m = jnp.maximum(m[...], jnp.max(s, axis=1, keepdims=True))
        corr = jnp.exp(m[...] - new_m)
        p = jnp.exp(s - _wide(new_m, k_tile))
        l[...] = l[...] * corr + sum(p[:, c:c + lanes] for c in range(0, k_tile, lanes))
        acc[...] = acc[...] * _wide(corr, dh) + jnp.dot(p, values,
                                                        preferred_element_type=jnp.float32)
        m[...] = new_m

    _loops(i, slots, fold)
    total = jnp.broadcast_to(jnp.sum(l[...], axis=1, keepdims=True), l.shape)
    out = acc[...] / _wide(total, dh)
    for r in range(rep):
        o_ref[:, r * dh:(r + 1) * dh] = out[r * q_tile:(r + 1) * q_tile].astype(o_ref.dtype)
    lse_ref[...] = m[...] + jnp.log(total)


def _bwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, do_ref, dq_ref, dk_ref, dv_ref, qs, dos,
                delta, dq, *, mask, slots, rep, q_tile, k_tile, dh):
    i = pl.program_id(2)
    scale = 1.0 / math.sqrt(dh)
    _stack(q_ref, qs, rep, q_tile, dh, scale)
    _stack(do_ref, dos, rep, q_tile, dh)
    _stack(o_ref, dq, rep, q_tile, dh)   # the output, in dq's room for a moment
    delta[...] = jnp.broadcast_to(jnp.sum(dos[...] * dq[...], axis=1, keepdims=True), delta.shape)
    dq[...] = jnp.zeros(dq.shape, jnp.float32)

    @pl.when(i == 0)
    def _():
        dk_ref[...] = jnp.zeros(dk_ref.shape, jnp.float32)
        dv_ref[...] = jnp.zeros(dv_ref.shape, jnp.float32)

    def fold(j, edged):
        rows = pl.ds(pl.multiple_of(j * k_tile, k_tile), k_tile)
        s = _scores(qs[...], k_ref, j, i, edged, mask, rep, q_tile, k_tile)
        p = jnp.exp(s - _wide(lse_ref[...], k_tile))
        dp = jax.lax.dot_general(dos[...], v_ref[rows, :].astype(jnp.float32),
                                 (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = p * (dp - _wide(delta[...], k_tile))
        dq[...] += jnp.dot(ds, k_ref[rows, :].astype(jnp.float32),
                           preferred_element_type=jnp.float32)
        over_rows = (((0,), (0,)), ((), ()))
        dk_ref[rows, :] += jax.lax.dot_general(ds, qs[...], over_rows,
                                               preferred_element_type=jnp.float32)
        dv_ref[rows, :] += jax.lax.dot_general(p, dos[...], over_rows,
                                               preferred_element_type=jnp.float32)

    _loops(i, slots, fold)
    for r in range(rep):
        dq_ref[:, r * dh:(r + 1) * dh] = (dq[r * q_tile:(r + 1) * q_tile] * scale).astype(
            dq_ref.dtype)


def _lanes(k_tile, dh):
    """Lanes a row statistic is kept along: a vreg's 128 at the shapes the
    compiled kernel takes, fewer under the interpreter's small tiles."""
    return math.gcd(LANE, k_tile, dh)


def _specs(length, rep, dh, q_tile, lanes):
    """Block specs of the (B, L, G * R * Dh) queries' kind, the (B, L, G * Dh)
    keys' kind and the (B, G, L * R, lanes) log-sum-exp, on the grid (b, g, i)."""
    per_query = pl.BlockSpec((None, q_tile, rep * dh), lambda b, g, i: (b, i, g))
    per_head = pl.BlockSpec((None, length, dh), lambda b, g, i: (b, 0, g))
    per_row = pl.BlockSpec((None, None, rep * q_tile, lanes), lambda b, g, i: (b, g, i, 0))
    return per_query, per_head, per_row


def _call(kernel, name, out_shape, in_specs, out_specs, scratch, grid, sequential, **static):
    return pl.pallas_call(
        functools.partial(kernel, **static), out_shape=out_shape, grid=grid, in_specs=in_specs,
        out_specs=out_specs, scratch_shapes=scratch, name=name, interpret=_interpret(),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary" if sequential else "parallel"),
            vmem_limit_bytes=VMEM_LIMIT))


def _static(q, plan):
    """What both kernels are told of the call, from its ``plan`` — (mask, its
    loops, queries a tile, keys a tile): (keyword arguments, block specs,
    stacked rows a tile, lanes of a row statistic)."""
    mask, slots, q_tile, k_tile = plan
    _, length, _, rep, dh = q.shape
    lanes = _lanes(k_tile, dh)
    return (dict(mask=mask, slots=slots, rep=rep, q_tile=q_tile, k_tile=k_tile, dh=dh),
            _specs(length, rep, dh, q_tile, lanes), rep * q_tile, lanes)


def _forward(q, k, v, plan):
    b, length, g, rep, dh = q.shape
    static, (per_query, per_head, per_row), rows, lanes = _static(q, plan)
    return _call(
        _fwd_kernel, "causal_attention_fwd",
        (jax.ShapeDtypeStruct((b, length, g * rep * dh), q.dtype),
         jax.ShapeDtypeStruct((b, g, length * rep, lanes), jnp.float32)),
        [per_query, per_head, per_head], (per_query, per_row),
        [pltpu.VMEM((rows, dh), jnp.float32), pltpu.VMEM((rows, lanes), jnp.float32),
         pltpu.VMEM((rows, lanes), jnp.float32), pltpu.VMEM((rows, dh), jnp.float32)],
        (b, g, length // plan[2]), False, **static)(
            q.reshape(b, length, g * rep * dh), k.reshape(b, length, g * dh),
            v.reshape(b, length, g * dh))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _fused(q, k, v, plan):
    return _forward(q, k, v, plan)[0]


def _fused_fwd(q, k, v, plan):
    out, lse = _forward(q, k, v, plan)
    return out, (q, k, v, out, lse)


def _fused_bwd(plan, kept, dout):
    q, k, v, out, lse = kept
    b, length, g, rep, dh = q.shape
    static, (per_query, per_head, per_row), rows, lanes = _static(q, plan)
    summed = jax.ShapeDtypeStruct((b, length, g * dh), jnp.float32)
    dq, dk, dv = _call(
        _bwd_kernel, "causal_attention_bwd",
        (jax.ShapeDtypeStruct((b, length, g * rep * dh), q.dtype), summed, summed),
        [per_query, per_head, per_head, per_query, per_row, per_query],
        (per_query, per_head, per_head),
        [pltpu.VMEM((rows, dh), jnp.float32), pltpu.VMEM((rows, dh), jnp.float32),
         pltpu.VMEM((rows, lanes), jnp.float32), pltpu.VMEM((rows, dh), jnp.float32)],
        (b, g, length // plan[2]), True, **static)(
            q.reshape(b, length, g * rep * dh), k.reshape(b, length, g * dh),
            v.reshape(b, length, g * dh), out, lse, dout)
    return (dq.reshape(q.shape), dk.reshape(k.shape).astype(k.dtype),
            dv.reshape(v.shape).astype(v.dtype))


_fused.defvjp(_fused_fwd, _fused_bwd)


def fused_attention(q, k, v, mask, q_tile, k_tile):
    """q (B, L, G, R, Dqk), k (B, L, G, Dqk) and v (B, L, G, Dv) -> (B, L, G *
    R * Dv): the softmax over the keys ``mask`` allows of ``q . k / sqrt(Dqk)``,
    times v, with its own backward pass.  ``L`` is a multiple of both tiles;
    every query reads some key.  The tile table is made here, at trace time,
    from the static arguments alone; the kernels are handed its loops.

    The kernels carry ONE head width.  Where scores and values differ in width
    (latent attention: 192 over 128) q, k and v are padded with zeros to
    ``kernel_width``, ``sqrt(width / Dqk)`` goes into q so that the kernel's own
    ``1 / sqrt(width)`` gives ``1 / sqrt(Dqk)``, and the padded end of each
    head's output is dropped: the same numbers, with the zeros multiplied."""
    plan = (mask, _slots(tile_table(mask, q.shape[1], q_tile, k_tile)), q_tile, k_tile)
    qk_dim, v_dim = q.shape[-1], v.shape[-1]
    if qk_dim == v_dim:
        return _fused(q, k, v, plan)
    width = kernel_width(qk_dim, v_dim)
    widened = lambda a: jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, width - a.shape[-1])])
    out = _fused(widened(q * math.sqrt(width / qk_dim)), widened(k), widened(v), plan)
    b, length, g, rep, _ = q.shape
    return out.reshape(b, length, g * rep, width)[..., :v_dim].reshape(b, length, g * rep * v_dim)


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


def tiles_for(length):
    """(queries, keys) a tile for a sequence of ``length``: the kernel's own,
    or the whole of a shorter sequence."""
    return min(Q_TILE, length), min(K_TILE, length)


def kernel_width(qk_dim, v_dim):
    """The one head width the kernel runs q, k and v at: their own where they
    agree, else the next multiple of the 128 lanes that holds both."""
    return qk_dim if qk_dim == v_dim else -(-max(qk_dim, v_dim) // LANE) * LANE


def attention_form(length, head_dim, v_dim=None):
    """``"kernel"`` or ``"xla"`` for a sequence of ``length`` under heads of
    ``head_dim`` (values of ``v_dim``, where they differ): the kernel on a TPU
    (``utils.hw.on_tpu``) where it takes the shape — ``length`` a multiple of
    both tiles and of the 8 sublanes, the head as the kernel runs it
    (``kernel_width``) of the 128 lanes, a head's K and V at that width within
    ``RESIDENT_MAX`` — and the caller's XLA form everywhere else.  Inside
    ``forced_form`` the forced form answers, for any shape whose length
    divides into the tiles."""
    q_tile, k_tile = tiles_for(length)
    divides = length % q_tile == 0 and length % k_tile == 0
    if _forced is not None:
        if _forced == "kernel" and not divides:
            raise ValueError("the attention kernel takes a length that divides into its tiles "
                             "(%d, %d), not %d" % (q_tile, k_tile, length))
        return _forced
    width = kernel_width(head_dim, v_dim or head_dim)
    takes = divides and length % 8 == 0 and width % LANE == 0 and length * width <= RESIDENT_MAX
    return "kernel" if hw.on_tpu() and takes else "xla"


@functools.lru_cache(maxsize=None)
def _announce(form, shape, v_dim, mask, tiles):
    counts, widths = "", ""
    if form == "kernel":
        counts = "; tiles of %dx%d a head: %d clear, %d edged, %d skipped" % (
            tiles + tuple(table_counts(tile_table(mask, shape[1], *tiles)).values()))
    if v_dim != shape[-1]:
        widths = " over values of %d" % v_dim + (
            ", padded to %d" % kernel_width(shape[-1], v_dim) if form == "kernel" else "")
    info("attention form for q %s%s under %r: %s%s"
         % ("x".join(map(str, shape)), widths, mask, form, counts))


def attend(q, k, v, mask, xla_form):
    """``fused_attention`` where ``attention_form`` says so, else
    ``xla_form(q, k, v)``; v may be narrower than q and k (``fused_attention``).
    On a TPU each decision is logged once a shape and mask, with the tile
    table's three counts."""
    length = q.shape[1]
    form, tiles = attention_form(length, q.shape[-1], v.shape[-1]), tiles_for(length)
    if hw.on_tpu():
        _announce(form, tuple(q.shape), v.shape[-1], mask, tiles)
    return fused_attention(q, k, v, mask, *tiles) if form == "kernel" else xla_form(q, k, v)
