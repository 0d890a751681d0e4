"""The ``topk`` keys of largest score a query, chosen by COUNTING: one Pallas
kernel that reads a chunk's index scores and writes its int8 pairs.

What models/keye_vl2.py's ``top_keys`` finds by a stable ``lax.sort`` of each
query's negated causal scores with the keys' positions as payload — the
``topk``-th entry is the last key in, and a key is in iff its (negated score,
position) is not after that entry's — needs no order: it needs the ``topk``-th
SMALLEST value of a row and, among the keys equal to it, how many of the lowest
positions go in.  Here (``select_threshold``), a tile of queries at a time, the
tile's whole rows in VMEM from the first pass to the last:

1. **A monotone integer key** a (query, key): the float32 bits of the negated
   score — made by the float operations ``top_keys`` makes (``where(s == 0, 0,
   -s)``) and canonicalised as ``lax.sort``'s comparator canonicalises (a zero
   of either sign +0, any NaN the one positive NaN) — mapped so that signed
   int32 order is the comparator's total order (``bits ^ ((bits >> 31) &
   0x7fffffff)``); a key past the query is +inf.  Written once to a (rows, L)
   int32 scratch.
2. **The threshold by bisection on the bits**: ``T``, the ``topk``-th smallest
   key of a row, is the largest ``t`` with ``count(key < t) <= topk - 1``;
   built from the sign bit down, 32 passes, each one compare of the tile with a
   per-row candidate, a sum of the hits of each lane group into a (rows, 128)
   partial and ONE cross-lane reduction.  No data-dependent exit.
3. **Ties to the lower key, the same primitive again**: one pass rewrites the
   scratch as a code — -1 where ``key < T``, the key's position where ``key ==
   T`` and the key is causal, INT32_MAX elsewhere — and log2(L) more passes
   find the largest position ``p`` with ``count(code < p) <= topk - 1``: the
   position of the last tie that goes in.
4. **The pairs**: ``code <= p``, written as int8 straight into the chunk's
   block.

One saving, fixed in time: the lane loops stop at the tile's last causal key,
rounded up to a whole turn of the counting loop; beyond it every key is the
same +inf, counted by arithmetic.  (A tile none of whose queries has more than
``topk`` causal keys could write ``causal`` at once but for the NaNs, which the
sort form leaves out even there; those tiles are the short ones, a twelfth of
the work, and take the passes like the others.)

NaNs fall where the sort form puts them: a NaN score is a key after every
other, never below a threshold and never equal to one; a row whose threshold
IS the NaN (fewer than ``topk`` keys that are not) selects nothing, as ``x <
nan`` and ``x == nan`` select nothing.

**One chooser** (``select_form``), in the idiom of ops/attention.py's
``attention_form``: the kernel on a TPU where it takes the shape, the caller's
sort form everywhere else (the CPU's path and the tests' oracle).  No flag and
no environment variable; ``forced_form`` is the one scoped seam, for the tests
and scripts/pallas_tpu_check.py.  Off a TPU a forced kernel interprets.
"""

import contextlib
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils import hw, info
from .attention import VMEM_LIMIT, _interpret
from .pallas_kernels import LANE

#: Queries a tile: 64 rows of 8,192 float32 scores are 2 MiB, double-buffered
#: 4, their keys' scratch 2 and the int8 block 1.  At 64 a pass's candidates
#: and partial sums are 8 vregs each and stay in registers beside a turn's
#: loads; at 128 the compiler spilled them (its bundles, read off the chip).
ROWS = 64

#: Lane groups a turn of the counting loop: the loop's own bundles (its branch,
#: the carried partials' copies) are paid once a turn, 73 bundles for 64 loads
#: against 25 for 16 a lane group a turn.  The lane loops stop at the last
#: causal key rounded up to a whole turn.
UNROLL = 8

#: What a tile may hold of VMEM: its scores twice, its keys, its pairs twice
#: (14 bytes a pair of query and key).
TILE_BYTES = 64 * 1024 * 1024

#: The keys of +inf and of the comparator's one NaN, and what no key is
INF_KEY, NAN_KEY = 0x7f800000, 0x7fc00000
INT_MIN, INT_MAX = -2 ** 31, 2 ** 31 - 1


def _kernel(last_ref, scores_ref, q_ref, out_ref, key_ref, *, topk, rows, length, lane):
    """One tile: ``rows`` queries' (rows, L) scores -> their (rows, L) int8
    pairs.  ``last_ref`` (SMEM) holds every tile's largest query position,
    ``q_ref`` the tile's (rows, 1) positions; ``key_ref`` is the (rows, L) int32
    scratch of the keys, then of the codes."""
    groups, unroll = length // lane, math.gcd(length // lane, UNROLL)
    last = last_ref[pl.program_id(1)]
    # lane groups up to the last that holds a causal key, whole strides of them
    live = jnp.clip(last // (lane * unroll) + 1, 0, groups // unroll) * unroll
    q_pos = q_ref[...]
    lanes = jax.lax.broadcasted_iota(jnp.int32, (rows, lane), 1)

    def over(first, end, body):
        """``body(offset, positions)`` a lane group of [first, end)."""
        def step(j, _):
            at = pl.multiple_of(j * lane, lane)
            body(at, lanes + at)
        jax.lax.fori_loop(first, end, step, None)

    def write(at, value):
        out_ref[:, pl.ds(at, lane)] = value.astype(jnp.int8)

    over(live, groups, lambda at, pos: write(at, jnp.zeros((rows, lane), jnp.int32)))

    def make_keys(at, pos):
        score = scores_ref[:, pl.ds(at, lane)]
        negated = jnp.where(score == 0, 0.0, -score)
        bits = jax.lax.bitcast_convert_type(negated, jnp.int32)
        bits = jnp.where(negated == 0, 0, jnp.where(negated != negated, NAN_KEY, bits))
        key = bits ^ ((bits >> 31) & INT_MAX)
        key_ref[:, pl.ds(at, lane)] = jnp.where(pos <= q_pos, key, INF_KEY)

    over(0, live, make_keys)

    def largest(bits, start, beyond):
        """The largest ``t``, ``start`` with some of its ``bits`` low bits
        flipped, high to low, with ``count(key_ref < t) <= topk - 1`` a
        row; ``beyond(t)`` counts the lane groups that are not read."""
        def one_pass(done, t):
            candidate = t ^ jnp.left_shift(jnp.int32(1), bits - 1 - done)
            wide = jnp.broadcast_to(candidate, (rows, lane))

            def hits(j, partial):
                for group in range(unroll):
                    at = pl.multiple_of((j * unroll + group) * lane, lane)
                    partial += jnp.where(key_ref[:, pl.ds(at, lane)] < wide, 1.0, 0.0)
                return partial

            partial = jax.lax.fori_loop(0, live // unroll, hits,
                                        jnp.zeros((rows, lane), jnp.float32))
            count = jnp.sum(partial, axis=-1, keepdims=True) + beyond(candidate)
            return jnp.where(count <= topk - 1, candidate, t)

        return jax.lax.fori_loop(0, bits, one_pass, start)

    unread = ((groups - live) * lane).astype(jnp.float32)      # keys of +inf, every one
    threshold = largest(32, jnp.full((rows, 1), INT_MIN, jnp.int32),
                        lambda t: jnp.where(t > INF_KEY, unread, 0.0))
    threshold = jnp.where(threshold == NAN_KEY, INT_MIN, threshold)   # nothing is < or == NaN
    wide = jnp.broadcast_to(threshold, (rows, lane))

    def make_codes(at, pos):
        key = key_ref[:, pl.ds(at, lane)]
        code = jnp.where(key < wide, -1, jnp.where((key == wide) & (pos <= q_pos), pos, INT_MAX))
        key_ref[:, pl.ds(at, lane)] = code

    over(0, live, make_codes)
    place = largest((length - 1).bit_length(), jnp.zeros((rows, 1), jnp.int32), lambda t: 0.0)
    wide_place = jnp.broadcast_to(place, (rows, lane))
    over(0, live, lambda at, pos: write(
        at, jnp.where(key_ref[:, pl.ds(at, lane)] <= wide_place, 1, 0)))


def tile_rows(chunk, length):
    """Queries a tile for chunks of ``chunk`` queries over ``length`` keys:
    ``ROWS``, or the most that divide the chunk and whose whole rows fit
    ``TILE_BYTES``."""
    rows = math.gcd(chunk, ROWS)
    while rows > 1 and rows * length * 14 > TILE_BYTES:
        rows //= 2
    return rows


def passes(length):
    """(passes that find the threshold, passes that place the last tie)."""
    return 32, (length - 1).bit_length()


def select_threshold(scores, q_pos, topk):
    """(B, C, L) float32 scores, the C queries' positions, ``topk`` < L -> (B,
    C, L) int8, one where models/keye_vl2.py's ``top_keys`` is true — the
    ``topk`` keys ``s <= q_pos`` of largest score a query, every causal key
    where there are no more, equal scores to the lower ``s`` — bit for bit."""
    b, chunk, length = scores.shape
    rows, lane = tile_rows(chunk, length), math.gcd(LANE, length)
    q_pos = q_pos.astype(jnp.int32)
    per_tile = pl.BlockSpec((None, rows, length), lambda b, i, last: (b, i, 0))
    return pl.pallas_call(
        functools.partial(_kernel, topk=topk, rows=rows, length=length, lane=lane),
        out_shape=jax.ShapeDtypeStruct(scores.shape, jnp.int8),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, chunk // rows),
            in_specs=[per_tile, pl.BlockSpec((rows, 1), lambda b, i, last: (i, 0))],
            out_specs=per_tile,
            scratch_shapes=[pltpu.VMEM((rows, length), jnp.int32)]),
        name="select_threshold", interpret=_interpret(),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"), vmem_limit_bytes=VMEM_LIMIT),
    )(jnp.max(q_pos.reshape(chunk // rows, rows), axis=1),
      jax.lax.stop_gradient(scores.astype(jnp.float32)),    # the pairs are a choice: no tangent
      q_pos[:, None])


# --------------------------------------------------------------------------- #
#  The chooser                                                                #
# --------------------------------------------------------------------------- #

#: The form ``forced_form`` holds ``select_form`` to; ``None`` outside it.
_forced = None


@contextlib.contextmanager
def forced_form(form):
    """Hold ``select_form`` to ``"kernel"`` or ``"xla"`` for what is TRACED
    inside the block.  The seam of the parity tests and of
    scripts/pallas_tpu_check.py's sort column; no training path enters it."""
    global _forced
    if form not in ("kernel", "xla"):
        raise ValueError("forced_form takes 'kernel' or 'xla', got %r" % (form,))
    previous, _forced = _forced, form
    try:
        yield
    finally:
        _forced = previous


def select_form(chunk, length, topk):
    """``"kernel"`` or ``"xla"`` for the selection of ``topk`` of ``length``
    keys for each of ``chunk`` queries: the kernel on a TPU
    (``utils.hw.on_tpu``) where ``length`` is whole lanes and a tile of whole
    rows, whole int8 sublane tiles of them (32), fits ``TILE_BYTES``; the
    caller's sort form everywhere else, and where ``topk >= length`` (every
    causal key: the caller calls neither).  Inside ``forced_form`` the forced
    form answers for any ``topk < length``."""
    if topk >= length:
        return "xla"
    if _forced is not None:
        return _forced
    takes = length % LANE == 0 and tile_rows(chunk, length) % 32 == 0
    return "kernel" if hw.on_tpu() and takes else "xla"


@functools.lru_cache(maxsize=None)
def _announce(form, shape, topk):
    tiles = ""
    if form == "kernel":
        tiles = "; tiles of %d queries, %d + %d passes" % (
            (tile_rows(*shape[1:]),) + passes(shape[2]))
    info("select form for scores %s, k = %d: %s%s" % ("x".join(map(str, shape)), topk, form, tiles))


def chosen_form(shape, topk):
    """``select_form`` of (B, C, L) scores, logged once a shape on a TPU."""
    form = select_form(shape[1], shape[2], topk)
    if hw.on_tpu():
        _announce(form, tuple(shape), topk)
    return form
