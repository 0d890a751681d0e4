"""Shared GAR numerics: distances, NaN conventions, rank selections.

NaN conventions follow the reference: a non-finite pairwise distance counts as
+inf for scoring (reference: aggregators/krum.py:71-73), and non-finite
coordinates sort *last* (as if +inf) in the coordinate-wise rules (reference:
aggregators/deprecated_native/native.cpp:691-697).  XLA is instructed not to
strip this handling by using explicit ``isfinite`` masking rather than NaN
comparisons.
"""

import contextlib
import functools

import jax
import jax.numpy as jnp
from jax._src.interpreters.batching import BatchTracer

from ..utils import info
from ..utils.hw import on_tpu


def nonfinite_to_inf(x):
    """Replace every non-finite entry with +inf (NaN-last ordering convention)."""
    return jnp.where(jnp.isfinite(x), x, jnp.inf)


#: Column count above which the Pallas coordinate kernels serve a TPU block:
#: below ~16k columns a per-call pad+launch is not worth displacing one small
#: fused sort.  One constant for every rule; the per-rule crossover has not
#: been measured on this machine (ROADMAP S5).
PALLAS_MIN_COLUMNS = 16384


def _is_batched_tracer(x):
    """True when ``x`` is being traced under ``jax.vmap`` (batching trace).

    Both engines call the rules under vmap on their bucketed paths
    (engine._aggregate_per_leaf, the sharded per-bucket loop); a
    vmapped ``pallas_call`` lowers through Pallas' batching rule.
    Detecting the batching trace centrally means no call site can forget an
    opt-out wrapper; ``forced_tier("pallas")`` remains the one way to
    exercise the vmapped Pallas path end to end
    (``tests/test_pallas.py::test_batched_tracer_detected_under_vmap`` fails
    loudly if the tracer class moves and detection stops firing).
    """
    return isinstance(x, BatchTracer)


#: The tier ``forced_tier`` holds ``kernel_tier`` to; ``None`` outside it.
_forced = None


@contextlib.contextmanager
def forced_tier(tier):
    """Hold ``kernel_tier`` to ``"pallas"`` or ``"jnp"`` for what is TRACED
    inside the block.  The seam of the tier-parity tests and of
    scripts/pallas_tpu_check.py's jnp column; no training path enters it."""
    global _forced
    if tier not in ("pallas", "jnp"):
        raise ValueError("forced_tier takes 'pallas' or 'jnp', got %r" % (tier,))
    previous, _forced = _forced, tier
    try:
        yield
    finally:
        _forced = previous


def kernel_tier(block):
    """Which tier serves a coordinate-wise / distance call on ``block``:
    ``"pallas"``, ``"jnp"`` or ``"jnp (vmapped)"``.

    Mirrors the reference's tier policy — the C++ custom op serves the rule
    when loadable, the graph tier otherwise (aggregators/median.py:40-48) —
    re-targeted at XLA: on TPU (``utils.hw.on_tpu``, the same answer the
    kernels' interpret switch uses), large column blocks go to the
    hand-written Pallas rank-selection kernels (ops/pallas_kernels.py),
    which make the SAME selections as the jnp tier (same ranks, same
    tie-breaks) and agree numerically to float tolerance — the summation
    order of averaged means differs, so low bits can (asserted on
    NaN-poisoned inputs by tests/test_pallas.py and on the chip by
    scripts/pallas_tpu_check.py).  A vmapped call stays on the jnp tier
    (ROADMAP S3 lifts that).  Inside ``forced_tier`` the forced tier
    answers; the ``pallas`` force outranks the vmap diversion, ``jnp`` does
    not.
    """
    if _forced == "pallas":
        return "pallas"
    if _is_batched_tracer(block):
        return "jnp (vmapped)"
    if _forced == "jnp":
        return "jnp"
    if on_tpu() and block.ndim == 2 and block.shape[1] >= PALLAS_MIN_COLUMNS:
        return "pallas"
    return "jnp"


@functools.lru_cache(maxsize=None)
def _announce_tier(tier, shape):
    info("GAR kernel tier for a %s block: %s" % ("x".join(map(str, shape)), tier))


def use_pallas_coordinate_tier(block):
    """True when the Pallas tier serves ``block`` (see ``kernel_tier``).

    Called at trace time only; on a TPU each distinct (shape, tier)
    decision is logged once, so a run's log says which tier served its rule
    — including the silent ``jnp (vmapped)`` diversion of the leaf paths.
    """
    tier = kernel_tier(block)
    if on_tpu():
        _announce_tier(tier, tuple(block.shape))
    return tier == "pallas"


#: n²·d element budget above which ``centered_gram_sq_distances`` chunks its
#: Gram matmul over the coordinate axis: at large n (the hier/bucketing
#: regime, n=128..512) one monolithic (n, d)x(d, n) contraction forces the
#: scheduler to stage the whole centered operand through fast memory at
#: once, while d-chunked accumulation bounds the working set without
#: changing the O(n²·d) arithmetic.
GRAM_CHUNK_BUDGET = 1 << 31


def centered_gram_sq_distances(g, chunk_budget=GRAM_CHUNK_BUDGET):
    """Gram-form all-pairs squared distances of (n, d) rows, median-centered.

    The Gram form ``|a|² + |b|² - 2·a·b`` is one MXU matmul but suffers
    catastrophic cancellation when rows share a large common mode, so rows
    are first centered by their coordinate-wise (NaN-ignoring) median —
    distances are translation-invariant and the robust center keeps the
    conditioning independent of Byzantine outliers.  Shared by the dense tier
    below and the sharded engine's per-block partial distances.

    When ``n²·d`` exceeds ``chunk_budget`` the (n, n) Gram is accumulated
    over coordinate chunks with one ``lax.scan`` (zero-padded tail — the
    padding is applied AFTER centering, so it contributes nothing to norms
    or inner products); within a chunked run the float accumulation order
    differs from the monolithic matmul by ordinary non-associativity, same
    as any blocking choice XLA could make itself.
    """
    n, d = g.shape
    center = jnp.nan_to_num(jnp.nanmedian(jnp.where(jnp.isfinite(g), g, jnp.nan), axis=0))
    g = g - center[None, :]
    sq_norms = jnp.sum(g * g, axis=-1)
    if n * n * d <= chunk_budget:
        gram = jax.lax.dot_general(
            g, g, (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST
        )
    else:
        chunk = max(128, min(d, chunk_budget // max(n * n, 1)))
        pad = (-d) % chunk
        gp = jnp.pad(g, ((0, 0), (0, pad))) if pad else g
        chunks = gp.reshape(n, (d + pad) // chunk, chunk).transpose(1, 0, 2)

        def body(acc, block):
            partial = jax.lax.dot_general(
                block, block, (((1,), (1,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
            )
            return acc + partial, None

        gram, _ = jax.lax.scan(body, jnp.zeros((n, n), jnp.float32), chunks)
    return sq_norms[:, None] + sq_norms[None, :] - 2.0 * gram


def pairwise_sq_distances(grads, direct_threshold=1 << 22):
    """All-pairs squared L2 distances of the rows of an (n, d) matrix.

    Two regimes:
    - small n²·d (tests, tiny models): the direct broadcasted ``sum((a-b)²)``,
      bitwise-faithful to the reference's CPU loop (op_krum/cpu.cpp:53-122);
    - large d: the Gram form ``|a|² + |b|² - 2·a·b`` so the O(n²·d) work is a
      single (n, d)x(d, n) matmul on the MXU.  The Gram form suffers
      catastrophic cancellation when vectors share a large common mode, so
      rows are first centered by their coordinate-wise (NaN-ignoring) median —
      distances are translation-invariant and the robust center keeps the
      conditioning independent of Byzantine outliers.

    NaN rows propagate to NaN distances, which downstream scoring maps to
    +inf, matching the reference's convention.  Accumulates in float32.
    """
    g = grads.astype(jnp.float32)
    n, d = g.shape
    if n * n * d <= direct_threshold:
        diff = g[:, None, :] - g[None, :, :]
        return jnp.sum(diff * diff, axis=-1)
    if use_pallas_coordinate_tier(g):
        from ..ops import pallas_kernels as pk

        return pk.pairwise_sq_distances(g)
    dist2 = centered_gram_sq_distances(g)
    return jnp.maximum(dist2, 0.0)  # clamp matmul-form negatives; NaN passes through


def smallest_k_sum(values, k, axis=-1):
    """Sum of the k smallest entries along ``axis`` (non-finite counts as +inf)."""
    if axis != -1:
        raise ValueError("smallest_k_sum supports axis=-1 only")
    clean = nonfinite_to_inf(values)
    return jnp.sum(jnp.sort(clean, axis=axis)[..., :k], axis=axis)


def smallest_k_mask(scores, k):
    """Boolean (n,) mask of the k smallest scores (ties broken by lowest index).

    Non-finite scores count as +inf.  Implemented with a rank comparison so it
    lowers to pure vector ops (no gather/scatter) — cheap on TPU.
    """
    clean = nonfinite_to_inf(scores)
    n = clean.shape[0]
    idx = jnp.arange(n)
    # rank(i) = number of entries strictly smaller, plus earlier-index ties
    smaller = (clean[None, :] < clean[:, None]) | ((clean[None, :] == clean[:, None]) & (idx[None, :] < idx[:, None]))
    ranks = jnp.sum(smaller, axis=1)
    return ranks < k


def selection_mean_weights(scores, k):
    """(n,) weights averaging the k smallest-scoring rows: mask / k.

    ``k`` may be a Python int or a traced scalar (Bulyan's lax.scan passes
    the round index)."""
    return smallest_k_mask(scores, k).astype(jnp.float32) / jnp.asarray(k, jnp.float32)


def alive_rows(rows, axis_name=None):
    """Global row liveness for NaN-absorbing iterative rules.

    Returns ``(alive, safe)``: the (n,) float mask of rows with NO
    non-finite coordinate (counted across dimension blocks by psum when
    ``axis_name`` is given, so every shard agrees) and the rows with dead
    entries zero-filled.  The average-nan convention: dead rows weigh 0."""
    nb_bad = jnp.sum(~jnp.isfinite(rows), axis=-1).astype(jnp.float32)
    if axis_name is not None:
        nb_bad = jax.lax.psum(nb_bad, axis_name)
    alive = (nb_bad == 0.0).astype(jnp.float32)
    return alive, jnp.where((alive > 0.0)[:, None], rows, 0.0)


def masked_coordinate_median(rows, alive):
    """Coordinate-wise median of the alive rows (0 where all rows are dead).
    Per-coordinate: needs no cross-block information."""
    return jnp.nan_to_num(
        jnp.nanmedian(jnp.where((alive > 0.0)[:, None], rows, jnp.nan), axis=0)
    )


def global_row_sq_norms(deviation, axis_name=None):
    """(n,) squared row norms, completed across dimension blocks by psum."""
    sqn = jnp.sum(deviation * deviation, axis=-1)
    if axis_name is not None:
        sqn = jax.lax.psum(sqn, axis_name)
    return sqn


def memo_by_identity(method):
    """Memoize a one-argument method on argument IDENTITY.

    ``aggregate_block`` and ``worker_participation`` both derive from
    ``selection_weights(dist2)`` within the same traced step; without this,
    the selection graph (O(n² log n) rank sort + the Bulyan t-round loop) is
    traced twice and dedup relies on XLA CSE.  Identity keying is
    trace-safe: a retrace passes a fresh tracer, misses, and overwrites the
    stale entry (which is never used again).

    The entry holds a (tracer-arg, tracer-result) tuple, so the OUTER call
    must drop it once the pass is done (``_GAR._drop_memos``, called from
    ``aggregate``/``aggregate_block_and_participation``) — a stale entry
    keeps the traced selection graph alive for the instance's lifetime and
    trips ``jax.check_tracer_leaks``."""
    import functools

    attr = "_memo_" + method.__name__

    @functools.wraps(method)
    def wrapped(self, arg):
        cached = getattr(self, attr, None)
        if cached is not None and cached[0] is arg:
            return cached[1]
        out = method(self, arg)
        setattr(self, attr, (arg, out))
        return out

    return wrapped


def select_combine(weights, block):
    """Weighted row combination that ignores NaNs in *unselected* rows.

    ``weights @ block`` alone would propagate NaN from rows with weight 0
    (0 x NaN = NaN), which would let an excluded Byzantine/NaN worker poison
    the output.  The reference's gather-then-mean never touches unselected
    rows (krum.py:93); to reproduce that with matmuls: sanitize non-finite
    entries to 0 for the combine, then re-poison exactly the coordinates
    where a row with *nonzero* weight was non-finite.

    Args:
      weights: (n,) or (t, n) selection weights.
      block:   (n, d_block) gradient rows.
    Returns:
      (d_block,) or (t, d_block) combined rows, NaN-faithful.
    """
    w = weights if weights.ndim == 2 else weights[None, :]
    finite = jnp.isfinite(block)
    safe = jnp.where(finite, block, 0.0)
    out = w.astype(jnp.float32) @ safe.astype(jnp.float32)
    touched = (jnp.abs(w) > 0).astype(jnp.float32) @ (~finite).astype(jnp.float32)
    out = jnp.where(touched > 0, jnp.nan, out)
    return out if weights.ndim == 2 else out[0]


# --------------------------------------------------------------------------- #
# The in-place tier: a gradient leaf reduced as it lies (parallel/in_place.py).
# New code below this line only (``kernel_tier`` above is the rows path's).

def leaf_tier(gar, leaf):
    """Which tier of ``gar`` reduces the (n, ...) stack of ONE gradient leaf
    across its workers in place (``GAR.aggregate_leaf``; what the engine's log
    counts its leaves by): ``"kernel"`` — ``gar.leaf_kernel``, the plane
    kernels' leaf entry (``ops/pallas_kernels._plane_leaf_call``) — or
    ``"jnp"``, the rule's own ``aggregate_block`` on ``leaf.reshape(n, -1)``.
    A rule without a leaf kernel has the one tier; the kernel can take a leaf
    of up to ``PLANE_ROWS_MAX`` workers whose last two dimensions hold a
    whole (8, 128) tile, and never a vmapped one; on a TPU it does from
    ``PALLAS_MIN_COLUMNS`` elements a worker, inside ``forced_tier("pallas")``
    at any size, inside ``forced_tier("jnp")`` and off a TPU never."""
    from math import prod

    from ..ops.pallas_kernels import leaf_blocks

    if (gar.leaf_kernel is None or _forced == "jnp" or _is_batched_tracer(leaf)
            or leaf_blocks(leaf) is None):
        return "jnp"
    if _forced == "pallas" or (on_tpu() and prod(leaf.shape[1:]) >= PALLAS_MIN_COLUMNS):
        return "kernel"
    return "jnp"
