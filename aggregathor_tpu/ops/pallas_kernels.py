"""Pallas TPU kernels for the GAR hot path.

The framework's counterpart of the reference's C++/CUDA custom ops
(native/op_krum/cpu.cpp:53-122, native/op_bulyan/cpu.cpp:52-188,
aggregators/deprecated_native/native.cpp:678-747).  Two hot shapes:

- **Pairwise squared distances** of the (n, d) gradient matrix — O(n²·d),
  streamed over column blocks so the whole matrix never sits in VMEM.  Two
  kernels, chosen from the row count.  Up to 64 rows (every cell of the grid)
  the exact difference form runs as one row tile, ``_dist_pairs_kernel``: one
  read of each (n, blk) block, blocks of up to 16,384 columns, each unordered
  pair of 8-row groups taken once, squared differences kept as 128-lane
  partials in VMEM and reduced across lanes once, at the last grid step.
  Beyond: the (i, j, k)-tiled MXU Gram form (``|a|² + |b|² − 2ab`` per block,
  median-centered against catastrophic cancellation — the same math the
  sharded engine psums, parallel/engine.py).
- **Coordinate-wise selection** (median / averaged-median / trimmed mean,
  Bulyan phase 3) — the reference's per-coordinate ``nth_element``
  (native.cpp:678-747) is control flow, which doesn't vectorize on TPU; here
  selection is reformulated as *rank computation*: ``rank(i) = #{j : key_j <
  key_i}`` (ties to the lower index), and "the median" is a masked sum over
  rows — no sort, no gather (SURVEY.md §7 hard part (a)).  One count in two
  forms, chosen from the row count (``_coordinate_call``).  Up to
  ``PLANE_ROWS_MAX`` = 32 rows every row is a *plane*: 1,024 of its columns
  fill one (8, 128) vreg, read out of the (n, blk) block as it lies by one
  strided load, and each unordered pair of rows is compared once —
  n·(n−1)/2 compares, nothing crossing sublanes, a vreg full whatever n
  (``_plane_call``; ``..._planes`` in the kernel's name).  Beyond, the rows
  stay on the sublanes of an (n, blk) slab and the ranks are n broadcast
  compare-accumulate passes over it (unrolled to 64 rows, a rolled loop
  above).

NaN conventions are identical to the jnp tier and the numpy oracle: a
non-finite value keys as +inf (sorts last); ties break by lower worker
index; a selected non-finite value is returned *as-is* (the original
NaN/inf poisons that coordinate, same identity in every tier).

Tile alignment (Mosaic lowers f32 in (8, 128) sublane x lane tiles): the
distance wrappers pad the worker dim to a multiple of 8 (zero rows, sliced
off before returning).  The coordinate kernels take the rows as they lie,
whatever their count — the block's row dimension is the array's.  The plane
form writes its one row of results dense, as the (d / 128, 128) array whose
flattening is the row; the slab form (more than 32 rows, and
``average_nan_columns``) writes a full (8, blk) tile where n is a multiple of
8, one row where it is not.  Rank thresholds use n, the rows there are.

Ragged widths: the coordinate kernels and the pair kernel take the rows as
wide as they are, on a grid over their ``d // blk`` whole blocks — no padded
copy of the matrix (3.3 GB a step in ResNet-50's Bulyan until PR 30).  The
fewer-than-a-block columns left over go through the same arithmetic as plain
jnp on a slice, added to the distances, appended to the slab form's row, or
written in place behind the plane form's blocks.  Only rows narrower than one
block are padded up to it.  (A grid of
``ceil(d / blk)`` blocks whose last one reads past the edge, masked in the
kernel, ran as fast but cost the one-chip ResNet-50 step 47 s more to start
in every process after a machine's first; PERF.md, PR 30.)

All kernels auto-fall back to interpreter mode off-TPU, so the same code
path is exercised by the CPU test suite.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.hw import on_tpu


def _interpret():
    return not on_tpu()


def _pad_axis(x, axis, multiple, value=0.0):
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


#: Lane width of the (8, 128) tiles, and the widest column block of the
#: coordinate kernels and of the tiled distance kernel: their blocks are a
#: multiple of the first and at most the second.  ``engine._block_width`` cuts
#: the four-chip column blocks on the same two numbers, so that every block
#: starts on a tile boundary and ends on a whole kernel block.
LANE = 128
MAX_BLOCK = 1024

#: The pair kernel (``_dist_pairs_kernel``) serves up to this many (padded)
#: rows — its lane-partial accumulator is (rows, rows, LANE) float32, 2 MB at
#: 64, and its comparator loop is unrolled like ``_ranks``' — in blocks of at
#: most this many columns.
PAIR_ROWS_MAX = 64
PAIR_MAX_BLOCK = 16384


def _clamp_block(blk, d, widest=MAX_BLOCK):
    blk = max(LANE, min(widest, (blk // LANE) * LANE))
    return min(blk, max(LANE, -(-d // LANE) * LANE))


def _whole_blocks(x, blk, fill):
    """``x`` as the kernels read it, and the width its whole blocks cover:
    rows padded with ``fill`` to the sublane multiple, columns left as they
    are — only rows narrower than one block are padded up to it."""
    xp = _pad_axis(x, 0, 8, fill)
    if xp.shape[1] < blk:
        xp = _pad_axis(xp, 1, blk)
    return xp, xp.shape[1] // blk * blk


#: Worker-row tile of the Gram distance kernel: above this many (padded) rows
#: the row axis is tiled so n=128..512 lowers without holding the whole
#: (n, d_block) slab pair — per grid cell only two (ROW_TILE, blk) input
#: tiles and one (ROW_TILE, ROW_TILE) output tile live in VMEM.
ROW_TILE = 128


def _pick_block_coord(n, d, vmem_budget=1 << 21):
    """Coordinate-kernel block: footprint is O(n·blk) (value slab + rank
    temporaries, ~8 live (n, blk) f32 buffers).  The coordinate kernels
    cannot tile the row axis (every rank needs all n comparators), so large n
    must come out of the column block instead: at n=512 this picks blk=128,
    ~2 MB of live slab, which lowers without spilling where twice the budget's
    blk=256 doubled it.  The Gram distance kernel sizes its blocks by the
    same count, n being its row tile."""
    return _clamp_block(vmem_budget // max(n * 4 * 8, 1), d)


# --------------------------------------------------------------------------- #
# Rank machinery (shared by the coordinate-wise kernels)

#: Worker count above which ``_ranks`` switches from the statically-unrolled
#: compare+accumulate loop to a ``fori_loop``: at n=512 the unrolled form
#: emits 512 fused passes into the kernel body — a compile-time blowup —
#: while the rolled loop compiles one pass.  The unrolled tier stays the
#: default at small n (one pass per comparator, no row extraction).
RANK_UNROLL_MAX = 64


def _ranks(key, n):
    """rank[i] = #{j : key_j < key_i, ties to lower j}, per coordinate.

    Two forms of one count, chosen by what ``key`` is.  A stack of PLANES
    ((n, sublanes, lanes): row i is ``key[i]``, whole vregs of one row's
    columns) takes each unordered pair i < j ONCE: ``key_j < key_i`` adds one
    to rank i, and its contrary one to rank j — the tie goes to the lower index
    by the choice of the compare, and rank j starts at j, the count of its
    contraries, so a pair is one compare, one convert, one add, one subtract,
    n·(n−1)/2 of them and nothing crossing sublanes (plain operations on planes
    taken out once: 120 pairs of ``jnp.where`` and ``key[j]`` cost
    ``resnet50_bulyan_1chip`` 2 s of its first dispatch in every process).  An
    (n, blk) SLAB (rows on the sublanes) takes n VPU passes of
    compare+accumulate over the whole slab, comparator row j broadcast over the
    sublanes: statically unrolled up to ``RANK_UNROLL_MAX`` comparators, a
    ``fori_loop`` beyond (identical selections: the loop body is the same
    compare+accumulate either way).  The rolled loop picks comparator row j
    with a masked max over the rows — Mosaic lowers no ``dynamic_slice`` of a
    VALUE (found on the chip at PR 21: "Unimplemented primitive in Pallas TPU
    lowering: dynamic_slice"), and ``key`` holds no NaN (non-finite is keyed
    +inf), so the max over one unmasked row is exactly that row.
    """
    if key.ndim == 3:
        planes = [key[i] for i in range(n)]
        ranks = [jnp.full(key.shape[1:], i, jnp.int32) for i in range(n)]
        for i in range(n):
            for j in range(i + 1, n):
                below = (planes[j] < planes[i]).astype(jnp.int32)
                ranks[i] += below
                ranks[j] -= below
        return jnp.stack(ranks)
    row = jax.lax.broadcasted_iota(jnp.int32, key.shape, 0)
    if n <= RANK_UNROLL_MAX:
        ranks = jnp.zeros(key.shape, jnp.int32)
        for j in range(n):
            kj = key[j, :][None, :]
            ranks = ranks + jnp.where((kj < key) | ((kj == key) & (j < row)), 1, 0)
        return ranks

    def body(j, ranks):
        kj = jnp.max(jnp.where(row == j, key, -jnp.inf), axis=0, keepdims=True)  # (1, blk)
        return ranks + jnp.where((kj < key) | ((kj == key) & (j < row)), 1, 0)

    return jax.lax.fori_loop(0, n, body, jnp.zeros(key.shape, jnp.int32))


def _select_rank(x, ranks, r):
    """Per coordinate, the value whose rank equals r (masked sum over rows:
    across the sublanes of a slab, vreg by vreg over a stack of planes)."""
    return jnp.sum(jnp.where(ranks == r, x, 0.0), axis=0)


def _inf_key(x):
    return jnp.where(jnp.isfinite(x), x, jnp.inf)


# --------------------------------------------------------------------------- #
# Coordinate-wise selection kernels

def _store_row(out_ref, row):
    # Full-tile store: writing all 8 sublanes of the (8, blk) output block
    # keeps the store aligned (no masked sub-tile write); the wrapper reads
    # row 0.
    out_ref[:] = jnp.broadcast_to(row[None, :], out_ref.shape)


# Each rule maps the n rows to their one row of results, whatever lies behind
# the row axis: an (n, w) slab gives (w,), a stack of planes (n, s, l) gives
# (s, l) — inside the kernel on what it reads from its ref, and as plain jnp on
# the few columns past the last whole block (``_coordinate_call``).

def _median_rule(n, x):
    return _select_rank(x, _ranks(_inf_key(x), n), n // 2)


def _averaged_median_rule(n, beta, x):
    med = _select_rank(x, _ranks(_inf_key(x), n), n // 2)
    dev_ranks = _ranks(_inf_key(jnp.abs(x - med[None])), n)
    chosen = jnp.where(dev_ranks < beta, x, 0.0)
    return jnp.sum(chosen, axis=0) / float(beta)


def _trimmed_mean_rule(n, trim, keep, x):
    # Mean of the CLEANED (+inf-mapped) values at ranks [trim, trim+keep):
    # an inf in the kept band poisons the sum -> NaN surfaced, matching
    # gars/trimmed_mean.trimmed_mean_columns.  Padded rows rank exactly n
    # (every real row outranks or index-ties below them), never selected.
    key = _inf_key(x)
    ranks = _ranks(key, n)
    sel = jnp.where((ranks >= trim) & (ranks < trim + keep), key, 0.0)
    mean = jnp.sum(sel, axis=0) / float(keep)
    return jnp.where(jnp.isfinite(mean), mean, jnp.nan)


def _average_nan_rule(x):
    finite = jnp.isfinite(x)  # NaN-padded rows count for nothing
    total = jnp.sum(jnp.where(finite, x, 0.0), axis=0)
    count = jnp.sum(finite.astype(jnp.float32), axis=0)
    return jnp.where(count > 0, total / jnp.maximum(count, 1.0), 0.0)


#: Widest column block of a slab-form coordinate kernel whose rows are no
#: multiple of the 8 sublanes: few rows make a block a thin slab.
THIN_MAX_BLOCK = 16384

#: The plane form of the rank rules.  ``PLANE`` columns of one row fill one
#: (8, LANE) vreg; the kernel reads them out of the (n, blk) block as it lies
#: with one strided load a row (``x_ref[j, pl.ds(off, PLANE)]``: the sublanes
#: of the vreg come from 8 successive tiles of the block) and ranks the n planes
#: against each other, ``_ranks``' pairs-once form.  It serves up to
#: ``PLANE_ROWS_MAX`` rows — the pairs are unrolled, 496 at 32 rows, where the
#: keys and ranks already spill — in blocks of ``PLANE_BLOCK_BYTES`` of rows
#: (double-buffered by the pipeline, beside a result of 1/n that size).
PLANE = 8 * LANE
PLANE_ROWS_MAX = 32
PLANE_BLOCK_BYTES = 1 << 21


def _plane_call(name, rule, x, block_d=None):
    """The rank rule ``rule`` over the columns of ``x`` (n <= ``PLANE_ROWS_MAX``
    rows, as they lie) with every row a plane: per ``PLANE`` columns the kernel
    stacks n vregs, one a row, and the rule runs on that (n, 8, LANE) stack —
    nothing crosses sublanes, whatever n.  The result leaves dense, as the
    (d / LANE, LANE) array whose flattening is the row: no broadcast tile.  The
    kernel fills the whole blocks; the fewer-than-a-block columns past them go
    through the same rule as jnp on the slice (as a slab: its n passes trace in
    a sixth of the time of the unrolled pairs, and every process traces the
    step) and into the same array in place — no block is read past the edge
    (PR 30), and no copy of the row joins the two (3 ms at 305 M columns)."""
    n, d = x.shape
    xp = x.astype(jnp.float32)
    if d < PLANE:  # only rows narrower than one plane are padded up to it
        xp = _pad_axis(xp, 1, PLANE)
    width = xp.shape[1]
    blk = block_d or PLANE_BLOCK_BYTES // (4 * n)
    blk = max(PLANE, min(blk, width) // PLANE * PLANE)
    whole = width // blk * blk

    def kernel(x_ref, out_ref):
        def piece(p, carry):
            off = pl.multiple_of(p * PLANE, PLANE)
            planes = [x_ref[j, pl.ds(off, PLANE)].reshape(8, LANE) for j in range(n)]
            out_ref[pl.ds(pl.multiple_of(p * 8, 8), 8), :] = rule(jnp.stack(planes))
            return carry

        jax.lax.fori_loop(0, blk // PLANE, piece, 0)

    out = pl.pallas_call(
        kernel,
        grid=(whole // blk,),
        in_specs=[pl.BlockSpec((n, blk), lambda i: (0, i), memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((blk // LANE, LANE), lambda i: (i, 0), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((-(-width // LANE), LANE), jnp.float32),
        interpret=_interpret(),
        name=name,
    )(xp)
    if whole < width:
        rest = _pad_axis(rule(xp[:, whole:]), 0, LANE)
        out = jax.lax.dynamic_update_slice(out, rest.reshape(-1, LANE), (whole // LANE, 0))
    return out.reshape(-1)[:d]


def _coordinate_call(name, rule, x, block_d=None, ranked=True):
    """Run the coordinate rule ``rule`` (the n rows -> their one row) over the
    columns of ``x``: as a Pallas kernel over the whole blocks of the rows as
    they are, and as jnp on the fewer-than-a-block columns left over.

    ``name`` is the public function's: what the ``pallas_call`` is called in
    a compiled program and a device trace, with ``_planes`` behind it where
    the plane form ran.  One algorithm in two forms, chosen from the row count:
    a rank rule (``ranked``) of up to ``PLANE_ROWS_MAX`` rows runs on planes
    (``_plane_call``); more rows, and the rule without ranks, run on the slab.

    The slab's block is all n rows as they lie (a block dimension may equal the
    array's) and the rule runs on that (n, blk) slab, whatever n: padding rows
    to the sublane multiple in front of the kernel would be a copy of the whole
    matrix.  Where n is a multiple of 8 the result goes out as a full (8, blk)
    tile, as it always has (``_store_row``); where it is not, as the one row
    it is.
    """
    n, d = x.shape
    if ranked and n <= PLANE_ROWS_MAX:
        return _plane_call(name + "_planes", rule, x, block_d)
    thin = n % 8 != 0
    blk = block_d or (_clamp_block((1 << 21) // (n * 4 * 8), d, widest=THIN_MAX_BLOCK)
                      if thin else _pick_block_coord(n, d))
    xp = x.astype(jnp.float32)
    if d < blk:  # only rows narrower than one block are padded up to it
        xp = _pad_axis(xp, 1, blk)
    whole = xp.shape[1] // blk * blk
    out_rows = 1 if thin else 8

    def kernel(x_ref, out_ref):
        _store_row(out_ref, rule(x_ref[:]))

    out = pl.pallas_call(
        kernel,
        grid=(whole // blk,),
        in_specs=[pl.BlockSpec((n, blk), lambda i: (0, i), memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((out_rows, blk), lambda i: (0, i), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((out_rows, whole), jnp.float32),
        interpret=_interpret(),
        name=name,
    )(xp)
    row = out[0]
    if whole < xp.shape[1]:
        row = jnp.concatenate([row, rule(xp[:, whole:])])
    return row[:d]


def coordinate_median(x, block_d=None):
    """(d,) upper median per column of an (n, d) matrix, non-finite last."""
    return _coordinate_call(
        "coordinate_median", functools.partial(_median_rule, x.shape[0]), x, block_d)


def coordinate_averaged_median(x, beta, block_d=None):
    """(d,) per-column mean of the ``beta`` values closest to the median."""
    return _coordinate_call(
        "coordinate_averaged_median",
        functools.partial(_averaged_median_rule, x.shape[0], int(beta)), x, block_d
    )


def coordinate_trimmed_mean(x, trim, keep, block_d=None):
    """(d,) per-column mean of the values at sorted ranks [trim, trim+keep)
    with non-finite mapped to +inf; NaN where the kept band is poisoned."""
    return _coordinate_call(
        "coordinate_trimmed_mean",
        functools.partial(_trimmed_mean_rule, x.shape[0], int(trim), int(keep)), x, block_d
    )


def average_nan_columns(x, block_d=None):
    """(d,) finite-only column mean (all-non-finite column -> 0)."""
    return _coordinate_call("average_nan_columns", _average_nan_rule, x, block_d, ranked=False)


# --------------------------------------------------------------------------- #
# Pairwise squared distances.  Above ``PAIR_ROWS_MAX`` rows: tiled over row
# pairs and streamed over column blocks.  The grid is (row tile i, row tile j,
# column block k) with k innermost, so each (i, j) output tile stays resident
# in VMEM while its column blocks accumulate — per grid cell only two (T, blk)
# input tiles and one (T, T) output tile are live, which is what lets
# n=128..512 lower without spilling.

def _dist_gram_kernel(xa_ref, xb_ref, out_ref):
    # Input is pre-centered by the NaN-ignoring coordinate median (see
    # pairwise_sq_distances): |a|²+|b|²−2ab stays conditioned, NaN rows
    # poison only their own rows/columns, and the kernel is pure MXU work.
    @pl.when(pl.program_id(2) == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    xa = xa_ref[:].astype(jnp.float32)
    xb = xb_ref[:].astype(jnp.float32)
    sqa = jnp.sum(xa * xa, axis=-1, keepdims=True)  # (T, 1)
    sqb = jnp.sum(xb * xb, axis=-1, keepdims=True)  # (T, 1)
    gram = jax.lax.dot_general(
        xa, xb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    out_ref[:] += sqa + jnp.transpose(sqb) - 2.0 * gram


#: Row groups (8 sublanes each) that one broadcast comparator row meets in a
#: pass of the pair kernel — 8 comparators x 4 groups = 32 accumulators held
#: in registers — and 128-lane chunks per iteration of its loop (Mosaic
#: unrolls a ``fori_loop`` whole or not at all, so the body repeats itself).
#: (32, 25,557,032) on a v5e, PR 30: 17.5 ms at 1 chunk, 10.4 at 4, 8.8 at
#: 16, 8.5 at 32; the groups move it by 1 %; 240 vector operations a chunk
#: are 8.0 ms.
PAIR_GROUPS = 4
PAIR_UNROLL = 16


def _pick_block_pairs(rows, d, vmem_budget=1 << 21):
    """Pair-kernel block: the (rows, blk) slab is all that is held (twice: the
    pipeline double-buffers it), so 2 MB buy 16,384 columns at 32 rows where
    a tile x tile x blk tensor of differences would buy 1,024 for 4 MB."""
    return _clamp_block(vmem_budget // (rows * 4), d, PAIR_MAX_BLOCK)


def _dist_pairs_kernel(x_ref, out_ref, acc_ref):
    """All pairs of the rows of one (rows, blk) column block per grid step,
    from ONE read of it, each unordered pair of 8-row groups once: comparator
    row j, broadcast over the sublanes, meets the groups that hold rows >= j
    (the idiom of ``_ranks``).  Squared differences add up chunk by 128-lane
    chunk into (8, LANE) lane partials, ``acc_ref[j, s, :]`` for the pair
    (j, s); the one cross-lane sum and the transpose into the lower triangle
    happen at the last step."""
    rows, blk = x_ref.shape
    groups, chunks = rows // 8, blk // LANE
    k = pl.program_id(0)

    @pl.when(k == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    for gj in range(groups):
        for g0 in range(gj, groups, PAIR_GROUPS):
            met = range(g0, min(g0 + PAIR_GROUPS, groups))

            def chunk(off, accs):
                xs = [x_ref[8 * g:8 * g + 8, pl.ds(off, LANE)] for g in met]
                out = []
                for jj in range(8):
                    xj = x_ref[pl.ds(8 * gj + jj, 1), pl.ds(off, LANE)]
                    for i, xg in enumerate(xs):
                        diff = xg - xj
                        out.append(accs[jj * len(met) + i] + diff * diff)
                return tuple(out)

            def several(c, accs):
                for u in range(PAIR_UNROLL):
                    accs = chunk(pl.multiple_of((c * PAIR_UNROLL + u) * LANE, LANE), accs)
                return accs

            accs = (jnp.zeros((8, LANE), jnp.float32),) * (8 * len(met))
            if chunks >= PAIR_UNROLL:
                accs = jax.lax.fori_loop(0, chunks // PAIR_UNROLL, several, accs)
            for c in range(chunks - chunks % PAIR_UNROLL, chunks):
                accs = chunk(c * LANE, accs)
            for jj in range(8):
                for i, g in enumerate(met):
                    acc_ref[8 * gj + jj, 8 * g:8 * g + 8, :] += accs[jj * len(met) + i]

    @pl.when(k == pl.num_programs(0) - 1)
    def _():
        upper = jnp.sum(acc_ref[:], axis=-1)  # [j, s] filled where s // 8 >= j // 8
        row = jax.lax.broadcasted_iota(jnp.int32, upper.shape, 0) // 8
        col = jax.lax.broadcasted_iota(jnp.int32, upper.shape, 1) // 8
        out_ref[:] = jnp.where(col >= row, upper, upper.T)


def pairwise_sq_distances(x, block_d=None):
    """(n, n) all-pairs squared L2 distances of the rows of (n, d).

    The form follows n.  Up to ``PAIR_ROWS_MAX`` rows the exact difference
    form runs as one row tile over the rows as they are
    (``_dist_pairs_kernel``: no padded copy, one read).  Beyond, the Gram
    form (one MXU matmul per tile pair) takes the rows in tiles of at most
    ROW_TILE, so the VMEM footprint is independent of the worker count.  NaN
    rows yield NaN entries (callers map to +inf), matching the jnp tier.
    """
    n, d = x.shape
    rows = n + (-n) % 8  # sublane-padded row count
    x = x.astype(jnp.float32)
    if rows <= PAIR_ROWS_MAX:
        blk = block_d or _pick_block_pairs(rows, d)
        xp, whole = _whole_blocks(x, blk, 0.0)  # zero rows are sliced off below
        out = pl.pallas_call(
            _dist_pairs_kernel,
            grid=(whole // blk,),
            in_specs=[pl.BlockSpec((rows, blk), lambda k: (0, k), memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec((rows, rows), lambda k: (0, 0), memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((rows, rows), jnp.float32),
            scratch_shapes=[pltpu.VMEM((rows, rows, LANE), jnp.float32)],
            interpret=_interpret(),
            name="pairwise_sq_distances",
        )(xp)[:n, :n]
        if whole < xp.shape[1]:  # fewer than a block of columns are left over
            diff = x[:, None, whole:] - x[None, :, whole:]
            out = out + jnp.sum(diff * diff, axis=-1)
        return out
    tile = min(rows, ROW_TILE)
    blk = block_d or _pick_block_coord(tile, d)
    # Robust centering outside the kernel (distances are translation-
    # invariant, one global center suffices): NaN-ignoring coordinate
    # median, same scheme as gars/common.py centered_gram_sq_distances.
    center = jnp.nan_to_num(jnp.nanmedian(jnp.where(jnp.isfinite(x), x, jnp.nan), axis=0))
    x = x - center[None, :]
    xp = _pad_axis(x, 1, blk)
    # Row-pad the worker dim to the tile multiple with zero rows; every
    # real-pair entry is computed rowwise-independently, so padded rows only
    # affect their own (sliced-off) rows/columns.
    xp = _pad_axis(xp, 0, tile, 0.0)
    rows_p = xp.shape[0]
    nt = rows_p // tile
    grid = (nt, nt, xp.shape[1] // blk)
    out = pl.pallas_call(
        _dist_gram_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile, blk), lambda i, j, k: (i, k), memory_space=pltpu.VMEM),
            pl.BlockSpec((tile, blk), lambda i, j, k: (j, k), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((tile, tile), lambda i, j, k: (i, j), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((rows_p, rows_p), jnp.float32),
        interpret=_interpret(),
        name="pairwise_sq_distances",
    )(xp, xp)
    # Column padding contributes zero to every distance.  The Gram form can
    # go slightly negative from cancellation — clamp it (NaN passes through
    # jnp.maximum); downstream scoring masks the diagonal itself.
    return jnp.maximum(out[:n, :n], 0.0)


# --------------------------------------------------------------------------- #
# The plane form over a gradient leaf as it lies (the step's in-place path,
# parallel/in_place.py).  New code below this line only: what stands above is
# what the rows path and the harness's probe compile, line for line.

def _lanes_from_rows(rows, lanes):
    """True where a leaf of ``rows`` x ``lanes`` goes to the kernel with its
    last two dimensions swapped: its lanes are not whole and its rows are (a
    head over a vocabulary slice of 18,992 or 16,032 ids, ``wkv_a``'s 576).
    The kernel then covers all of it and no slice is left to jnp — and that is
    how the backward pass leaves such a leaf (XLA lays a minor dimension that
    is not whole lanes second: the swap is a bitcast in the grid's three cells
    that have one, where the leaf as it is declared cost a transposing copy of
    394-622 MB a step; PERF.md section 6, PR 47)."""
    return lanes % LANE != 0 and rows % LANE == 0


def leaf_blocks(x):
    """The (ta, tb) block of the plane form's LEAF entry over the last two
    dimensions of ``x``, the (n, ..., A, B) stack of n workers' copies of one
    leaf (anything with a shape): ``ta`` a multiple of 8 that divides the
    whole sublane groups of A, ``tb`` a multiple of ``LANE`` that divides the
    whole lanes of B — no block reaches past an edge (PR 30) — with the n
    planes of a block inside ``PLANE_BLOCK_BYTES``: the widest such ``tb``,
    then the tallest ``ta`` that still fits (a wide block is long runs of
    whole tiles to its DMA).  None where the entry does not serve: a
    leaf of fewer than two dimensions a worker, more than ``PLANE_ROWS_MAX``
    workers, A under 8 or B under ``LANE``."""
    if len(x.shape) < 3:
        return None
    n, (rows, lanes) = x.shape[0], x.shape[-2:]
    if _lanes_from_rows(rows, lanes):
        rows, lanes = lanes, rows
    if n > PLANE_ROWS_MAX or rows < 8 or lanes < LANE:
        return None
    groups, vregs = rows // 8, lanes // LANE
    budget = PLANE_BLOCK_BYTES // (4 * n * PLANE)  # (8, LANE) vregs a worker a block
    wide = max(w for w in range(1, min(vregs, budget) + 1) if vregs % w == 0)
    high = max(h for h in range(1, min(groups, budget // wide) + 1) if groups % h == 0)
    return 8 * high, LANE * wide


def _plane_leaf_call(name, rule, x, interpret):
    """``_plane_call`` for a gradient leaf AS IT LIES: ``x`` is (n, ..., A, B),
    the n workers' copies of one leaf, in (8, LANE) tiles over A x B the way
    the backward pass left it; the result is the aggregated leaf, (..., A, B)
    float32.  The kernel's operand is (X, n, A, B): the worker axis moved to
    just above the two tiled dimensions and the others merged into X — both
    bitcasts where the leaf lies so, and it does: a scan over layers stacks a
    layer's (n, A, B) gradients layer-major, and the held experts' come out
    of their batched product [layer][expert][worker] (with the workers
    leading, XLA copied 0.9-2.4 GB a step in front of the kernels in the
    grid's language cells; PERF.md section 6, PR 47).  A block is
    ``leaf_blocks``' (1, n, ta, tb), and per (8, LANE) tile of it the kernel
    stacks the n workers' vregs — each read densely, as the tile it is — and
    runs the SAME ``rule`` closure on the same (n, 8, LANE) stack as
    ``_plane_call``: same selections, same ties, same non-finite handling.  No
    row of d columns is laid out in front of it and nothing is inflated behind
    it.  The rows past the last whole 8 and the lanes past the last whole
    ``LANE`` go through the same rule as jnp on the slice (as a slab, like
    ``_plane_call``'s) and into the result in place; a leaf whose rows are
    whole lanes where its lanes are not is handed over with the two swapped
    (``_lanes_from_rows``) and has no such slice."""
    if _lanes_from_rows(*x.shape[-2:]):
        return jnp.swapaxes(
            _plane_leaf_call(name, rule, jnp.swapaxes(x, -1, -2), interpret), -1, -2)
    n, shape = x.shape[0], x.shape[1:]
    rows, lanes = shape[-2:]
    ta, tb = leaf_blocks(x)
    xp = jnp.moveaxis(x.astype(jnp.float32), 0, -3).reshape(-1, n, rows, lanes)
    outer = xp.shape[0]
    whole_rows, whole_lanes = rows // 8 * 8, lanes // LANE * LANE

    def kernel(x_ref, out_ref):
        def group(r, carry):
            sub = pl.ds(pl.multiple_of(r * 8, 8), 8)

            def tile(c, carry):
                lane = pl.ds(pl.multiple_of(c * LANE, LANE), LANE)
                out_ref[0, sub, lane] = rule(jnp.stack([x_ref[0, j, sub, lane] for j in range(n)]))
                return carry

            return jax.lax.fori_loop(0, tb // LANE, tile, carry)

        jax.lax.fori_loop(0, ta // 8, group, 0)

    out = pl.pallas_call(
        kernel,
        grid=(outer, whole_rows // ta, whole_lanes // tb),
        in_specs=[pl.BlockSpec((1, n, ta, tb), lambda o, i, j: (o, 0, i, j),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((1, ta, tb), lambda o, i, j: (o, i, j), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((outer, rows, lanes), jnp.float32),
        interpret=interpret,
        name=name,
    )(xp)

    def as_slab(piece):  # the rule on a slice of the leaf, as (n, w) -> (w,)
        piece = jnp.moveaxis(piece, 1, 0)
        return rule(piece.reshape(n, -1)).reshape(piece.shape[1:])

    if whole_lanes < lanes:
        out = jax.lax.dynamic_update_slice(out, as_slab(xp[..., whole_lanes:]), (0, 0, whole_lanes))
    if whole_rows < rows:
        out = jax.lax.dynamic_update_slice(
            out, as_slab(xp[:, :, whole_rows:, :whole_lanes]), (0, whole_rows, 0))
    return out.reshape(shape)


# The leaf entries of the three rank rules (``_plane_leaf_call``): the n
# workers' copies of ONE gradient leaf, (n, ..., A, B), to the aggregated leaf,
# under the 2-D entry's kernel name: a device trace shows the rule with the
# leaf's shape.  They share one ``jit``, so that a step traces the wrapper
# once a leaf shape and not once a leaf (0.09 s each; PERF.md section 5);
# whether the kernel is interpreted is read outside it and is part of its key.

@functools.partial(jax.jit, static_argnames=("name", "rule", "args", "interpret"))
def _leaf_entry(x, name, rule, args, interpret):
    return _plane_leaf_call(name, functools.partial(rule, x.shape[0], *args), x, interpret)


def coordinate_median_leaf(x):
    """Upper median over the leading axis of (n, ..., A, B), non-finite last."""
    return _leaf_entry(x, "coordinate_median_planes", _median_rule, (), _interpret())


def coordinate_averaged_median_leaf(x, beta):
    """Mean over the leading axis of the ``beta`` values closest to the median."""
    return _leaf_entry(
        x, "coordinate_averaged_median_planes", _averaged_median_rule, (beta,), _interpret())


def coordinate_trimmed_mean_leaf(x, trim, keep):
    """Mean over the leading axis of the values at sorted ranks [trim, trim+keep)."""
    return _leaf_entry(
        x, "coordinate_trimmed_mean_planes", _trimmed_mean_rule, (trim, keep), _interpret())
