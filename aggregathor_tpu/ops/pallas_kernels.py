"""Pallas TPU kernels for the GAR hot path.

The framework's counterpart of the reference's C++/CUDA custom ops
(native/op_krum/cpu.cpp:53-122, native/op_bulyan/cpu.cpp:52-188,
aggregators/deprecated_native/native.cpp:678-747).  Two hot shapes:

- **Pairwise squared distances** of the (n, d) gradient matrix — O(n²·d),
  streamed over column blocks so the whole matrix never sits in VMEM.  Two
  kernels: an exact difference-form (VPU, reference-faithful accumulation
  order per block) and an MXU Gram-form (``|a|² + |b|² − 2ab`` per block,
  per-block median-centered against catastrophic cancellation — the same
  math the sharded engine psums, parallel/engine.py).
- **Coordinate-wise selection** (median / averaged-median, Bulyan phase 3) —
  the reference's per-coordinate ``nth_element`` (native.cpp:678-747) is
  control flow, which doesn't vectorize on TPU; here selection is
  reformulated as *rank computation*: ``rank(i) = #{j : key_j < key_i}``
  (ties to the lower index) is n fused VPU compare-accumulate passes over
  the whole block, and "the median" is a masked sum over rows — no sort, no
  gather, O(n²) vector ops per coordinate slab (SURVEY.md §7 hard part (a)).

NaN conventions are identical to the jnp tier and the numpy oracle: a
non-finite value keys as +inf (sorts last); ties break by lower worker
index; a selected non-finite value is returned *as-is* (the original
NaN/inf poisons that coordinate, same identity in every tier).

Tile alignment (Mosaic lowers f32 in (8, 128) sublane x lane tiles): the
host wrappers pad the worker dim to a multiple of 8 and the coordinate
kernels write full (8, blk) output tiles — no sub-tile block shapes reach
the compiler.  Worker padding is provably neutral: a padded row is all-NaN,
keys +inf at the highest indices, and its rank is exactly n (every real row
precedes it), strictly above every selection threshold (n//2 < n, beta <=
n); ``average_nan_columns`` ignores non-finite rows by construction, and
the distance wrappers slice padded rows/columns off before returning.

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


#: Lane width of the (8, 128) tiles, and the widest column block a kernel
#: takes: every kernel's block is a multiple of the first and at most the
#: second, so rows whose width is a multiple of ``MAX_BLOCK`` (of ``LANE``
#: when narrower) pass ``_pad_axis`` untouched at any power-of-two block.
LANE = 128
MAX_BLOCK = 1024


def _clamp_block(blk, d):
    blk = max(LANE, min(MAX_BLOCK, (blk // LANE) * LANE))
    return min(blk, max(LANE, -(-d // LANE) * LANE))


#: Worker-row tile of the distance kernels: above this many (padded) rows
#: the row axis is tiled so n=128..512 lowers without holding the whole
#: (n, d_block) slab pair — per grid cell only two (ROW_TILE, blk) input
#: tiles and one (ROW_TILE, ROW_TILE) output tile live in VMEM.
ROW_TILE = 128


def _pick_block_diff(tile, d, vmem_budget=1 << 22):
    """Diff-form distance block: the tile·tile·blk difference tensor sets
    the size (``tile`` is the ROW TILE, not n — row tiling keeps the
    budget independent of the worker count)."""
    return _clamp_block(vmem_budget // max(tile * tile * 4, 1), d)


def _pick_block_coord(n, d, vmem_budget=1 << 21):
    """Coordinate-kernel block: footprint is O(n·blk) (value slab + rank
    temporaries, ~8 live (n, blk) f32 buffers).  The budget is HALF the
    distance kernels' — the coordinate kernels cannot tile the row axis
    (every rank needs all n comparators), so large n must come out of the
    column block instead: at n=512 this picks blk=128, ~2 MB of live slab,
    which lowers without spilling where the old budget's blk=256 doubled it."""
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
    """rank[i, :] = #{j : key_j < key_i, ties to lower j}, per coordinate.

    n VPU passes of compare+accumulate over the (n, blk) slab; memory stays
    O(n·blk).  Statically unrolled up to ``RANK_UNROLL_MAX`` comparators, a
    ``fori_loop`` beyond (identical selections: the loop body is the same
    compare+accumulate either way).  The rolled loop picks comparator row j
    with a masked max over the rows — Mosaic lowers no ``dynamic_slice`` of
    a VALUE (found on the chip at PR 21: "Unimplemented primitive in Pallas
    TPU lowering: dynamic_slice"), and ``key`` holds no NaN (non-finite is
    keyed +inf), so the max over one unmasked row is exactly that row.
    """
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
    """Per coordinate, the value whose rank equals r (masked sum over rows)."""
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


def _median_kernel(n, x_ref, out_ref):
    x = x_ref[:]
    _store_row(out_ref, _select_rank(x, _ranks(_inf_key(x), n), n // 2))


def _averaged_median_kernel(n, beta, x_ref, out_ref):
    x = x_ref[:]
    med = _select_rank(x, _ranks(_inf_key(x), n), n // 2)
    dev_ranks = _ranks(_inf_key(jnp.abs(x - med[None, :])), n)
    chosen = jnp.where(dev_ranks < beta, x, 0.0)
    _store_row(out_ref, jnp.sum(chosen, axis=0) / float(beta))


def _trimmed_mean_kernel(n, trim, keep, x_ref, out_ref):
    # Mean of the CLEANED (+inf-mapped) values at ranks [trim, trim+keep):
    # an inf in the kept band poisons the sum -> NaN surfaced, matching
    # gars/trimmed_mean.trimmed_mean_columns.  Padded rows rank exactly n
    # (every real row outranks or index-ties below them), never selected.
    x = x_ref[:]
    key = _inf_key(x)
    ranks = _ranks(key, n)
    sel = jnp.where((ranks >= trim) & (ranks < trim + keep), key, 0.0)
    mean = jnp.sum(sel, axis=0) / float(keep)
    _store_row(out_ref, jnp.where(jnp.isfinite(mean), mean, jnp.nan))


def _coordinate_call(name, kernel, x, block_d=None):
    """Run a (n, blk) -> row coordinate kernel over column blocks.

    ``name`` is the public function's: what the ``pallas_call`` is called in
    a compiled program and a device trace.  Rank thresholds inside ``kernel``
    use the REAL n; the slab rows are padded to the f32 sublane multiple with
    NaN (neutral, module docstring).
    """
    n, d = x.shape
    rows = n + (-n) % 8  # the slab the kernel actually holds is padded
    blk = block_d or _pick_block_coord(rows, d)
    xp = _pad_axis(x.astype(jnp.float32), 1, blk)
    xp = _pad_axis(xp, 0, 8, jnp.nan)
    grid = xp.shape[1] // blk
    out = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec((rows, blk), lambda i: (0, i), memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((8, blk), lambda i: (0, i), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((8, xp.shape[1]), jnp.float32),
        interpret=_interpret(),
        name=name,
    )(xp)
    return out[0, :d]


def coordinate_median(x, block_d=None):
    """(d,) upper median per column of an (n, d) matrix, non-finite last."""
    n = x.shape[0]
    return _coordinate_call(
        "coordinate_median", functools.partial(_median_kernel, n), x, block_d)


def coordinate_averaged_median(x, beta, block_d=None):
    """(d,) per-column mean of the ``beta`` values closest to the median."""
    n = x.shape[0]
    return _coordinate_call(
        "coordinate_averaged_median",
        functools.partial(_averaged_median_kernel, n, int(beta)), x, block_d
    )


def coordinate_trimmed_mean(x, trim, keep, block_d=None):
    """(d,) per-column mean of the values at sorted ranks [trim, trim+keep)
    with non-finite mapped to +inf; NaN where the kept band is poisoned."""
    n = x.shape[0]
    return _coordinate_call(
        "coordinate_trimmed_mean",
        functools.partial(_trimmed_mean_kernel, n, int(trim), int(keep)), x, block_d
    )


def average_nan_columns(x, block_d=None):
    """(d,) finite-only column mean (all-non-finite column -> 0)."""

    def kernel(x_ref, out_ref):
        v = x_ref[:]
        finite = jnp.isfinite(v)  # NaN-padded rows count for nothing
        total = jnp.sum(jnp.where(finite, v, 0.0), axis=0)
        count = jnp.sum(finite.astype(jnp.float32), axis=0)
        _store_row(out_ref, jnp.where(count > 0, total / jnp.maximum(count, 1.0), 0.0))

    return _coordinate_call("average_nan_columns", kernel, x, block_d)


# --------------------------------------------------------------------------- #
# Pairwise squared distances, tiled over row pairs and streamed over column
# blocks.  The grid is (row tile i, row tile j, column block k) with k
# innermost, so each (i, j) output tile stays resident in VMEM while its
# column blocks accumulate — per grid cell only two (T, blk) input tiles and
# one (T, T) output tile are live, which is what lets n=128..512 lower
# without spilling (a single-tile grid reproduces the old full-slab kernels
# bit-for-bit: same per-block accumulation order).

def _dist_diff_kernel(xa_ref, xb_ref, out_ref):
    @pl.when(pl.program_id(2) == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    xa = xa_ref[:].astype(jnp.float32)
    xb = xb_ref[:].astype(jnp.float32)
    diff = xa[:, None, :] - xb[None, :, :]
    out_ref[:] += jnp.sum(diff * diff, axis=-1)


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


def pairwise_sq_distances(x, block_d=None, use_mxu=None, row_tile=None):
    """(n, n) all-pairs squared L2 distances of the rows of (n, d).

    ``use_mxu=None`` picks the difference-form (exact) when the per-block
    tile²·blk intermediate is cheap and the Gram-form (one MXU matmul per
    tile pair) otherwise.  NaN rows yield NaN entries (callers map to +inf),
    matching the jnp tier.  Rows are processed in ``row_tile``-sized tiles
    (default: one tile up to ROW_TILE rows, ROW_TILE beyond) so the VMEM
    footprint is independent of the worker count.
    """
    n, d = x.shape
    rows = n + (-n) % 8  # sublane-padded row count
    tile = row_tile or (rows if rows <= ROW_TILE else ROW_TILE)
    tile = max(8, tile + (-tile) % 8)
    if use_mxu is None:
        use_mxu = n > 64
    x = x.astype(jnp.float32)
    if use_mxu:
        kernel = _dist_gram_kernel
        blk = block_d or _pick_block_coord(tile, d)
        # Robust centering outside the kernel (distances are translation-
        # invariant, one global center suffices): NaN-ignoring coordinate
        # median, same scheme as gars/common.py centered_gram_sq_distances.
        center = jnp.nan_to_num(jnp.nanmedian(jnp.where(jnp.isfinite(x), x, jnp.nan), axis=0))
        x = x - center[None, :]
    else:
        kernel = _dist_diff_kernel
        blk = block_d or _pick_block_diff(tile, d)
    xp = _pad_axis(x, 1, blk)
    # Row-pad the worker dim to the tile multiple with zero rows; every
    # real-pair entry is computed rowwise-independently, so padded rows only
    # affect their own (sliced-off) rows/columns.
    xp = _pad_axis(xp, 0, tile, 0.0)
    rows_p = xp.shape[0]
    nt = rows_p // tile
    grid = (nt, nt, xp.shape[1] // blk)
    out = pl.pallas_call(
        kernel,
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
    out = out[:n, :n]
    # Column padding contributes zero to every distance.  The Gram form can
    # go slightly negative from cancellation — clamp it (NaN passes through
    # jnp.maximum); downstream scoring masks the diagonal itself.
    return jnp.maximum(out, 0.0) if use_mxu else out
