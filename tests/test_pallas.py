"""Pallas kernel tier: interpret-mode equivalence with the numpy oracle.

The CPU suite runs every kernel in interpreter mode — the same kernel body
that compiles on TPU — and cross-checks against gars/oracle.py, the same
ground truth used by the jnp and native tiers (SURVEY.md §4 point 3).
"""

import numpy as np
import pytest

from aggregathor_tpu.gars import oracle
from aggregathor_tpu.ops import pallas_kernels as pk


def _rand(n, d, seed, nan_frac=0.0, block=128, edges=False):
    """``block`` is the test's ``block_d``, not the rows'.  ``edges`` plants,
    at both ends of the rows (the first block and the leftover columns): a
    column of ties, one tied over half its rows, one holding NaN, +inf and
    -inf, and one with no finite value."""
    rng = np.random.default_rng(seed)
    g = rng.normal(size=(n, d)).astype(np.float32)
    if nan_frac:
        g[rng.random(size=g.shape) < nan_frac] = np.nan
    if edges:
        for first in (1, d - 5):
            g[:, first] = 0.5
            g[: n // 2 + 1, first + 1] = -0.25
            g[:3, first + 2] = [np.nan, np.inf, -np.inf][:n]
            g[:, first + 3] = np.resize([np.nan, np.inf, -np.inf], n)
    return g


CASES = [
    dict(n=8, d=40, seed=0, nan_frac=0.0),
    dict(n=8, d=300, seed=1, nan_frac=0.1),
    dict(n=15, d=130, seed=2, nan_frac=0.0),
    dict(n=16, d=7, seed=3, nan_frac=0.2),
    # widths off the block and off the lane, which the kernels take unpadded
    dict(n=32, d=1000, seed=4, nan_frac=0.05),
    dict(n=13, d=3 * 128 - 7, seed=5, nan_frac=0.1),
]

# The plane form (up to ``pk.PLANE_ROWS_MAX`` rows): widths of blk - 1, blk,
# blk + 1 and 1,024 k + 40, with the columns ``_rand`` plants at both ends; and
# the bound on n at which the form changes, one case each side.
PLANE_CASES = [
    dict(n=3, d=1023, seed=20, nan_frac=0.1, block=1024, edges=True),
    dict(n=4, d=1024, seed=21, nan_frac=0.0, block=1024, edges=True),
    dict(n=4, d=2049, seed=22, nan_frac=0.2, block=2048, edges=True),
    dict(n=4, d=3 * 1024 + 40, seed=23, nan_frac=0.1, block=1024, edges=True),
    dict(n=5, d=1025, seed=24, nan_frac=0.1, block=1024, edges=True),
    dict(n=8, d=2047, seed=25, nan_frac=0.1, block=2048, edges=True),
    dict(n=16, d=2048 + 40, seed=26, nan_frac=0.1, block=2048, edges=True),
    dict(n=17, d=1024 + 40, seed=27, nan_frac=0.05, block=1024, edges=True),
    dict(n=pk.PLANE_ROWS_MAX, d=1024, seed=28, nan_frac=0.05, block=1024, edges=True),
    dict(n=pk.PLANE_ROWS_MAX + 1, d=1024, seed=29, nan_frac=0.05, block=1024, edges=True),
]
CASES += PLANE_CASES


@pytest.mark.parametrize("case", CASES)
def test_coordinate_median(case):
    g = _rand(**case)
    out = np.asarray(pk.coordinate_median(g, block_d=case.get("block", 128)))
    # a selection: equal to the bit, NaN for NaN and infinity for infinity
    np.testing.assert_array_equal(out, oracle.median(g).astype(np.float32))


@pytest.mark.parametrize("case", CASES)
def test_coordinate_averaged_median(case):
    g = _rand(**case)
    f = 2
    out = np.asarray(pk.coordinate_averaged_median(g, g.shape[0] - f, block_d=case.get("block", 128)))
    with np.errstate(invalid="ignore"):  # the oracle's mean over +inf and -inf
        ref = oracle.averaged_median(g, f)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("d,block", [(2 * 1024 + 40, 1024), (3 * 2048 + 7, 2048)])
def test_averaged_median_at_three_rows_against_the_jnp_tier(d, block):
    """n = 3, f = 1 (the mean of the median and the nearer of the other two):
    the plane form over the whole blocks and the leftover columns, against the
    jnp tier of gars/averaged_median.py, with NaN scattered, the columns
    ``_rand`` plants at both ends, and columns whose two outer values lie
    equally far from the median (the lower row wins) in a block and in the
    leftover."""
    import jax.numpy as jnp

    from aggregathor_tpu.gars.averaged_median import averaged_median_columns

    g = _rand(3, d, seed=40, nan_frac=0.1, block=block, edges=True)
    for column in (7, block + 9, d - 9):
        g[:, column] = (1.0, 2.0, 3.0)
        g[:, column + 1] = (3.0, 2.0, 1.0)
        g[:, column + 2] = (0.0, 0.0, 5.0)  # two workers whose tokens reached no expert
    out = np.asarray(pk.coordinate_averaged_median(g, 2, block_d=block))
    with np.errstate(invalid="ignore"):
        jnp_tier = np.asarray(averaged_median_columns(jnp.asarray(g), 3, 2))
    np.testing.assert_allclose(out, jnp_tier, rtol=1e-6, atol=0, equal_nan=True)
    for column in (7, block + 9, d - 9):
        assert list(out[column:column + 3]) == [1.5, 2.5, 0.0]


@pytest.mark.parametrize("case", CASES)
def test_average_nan_columns(case):
    g = _rand(**case)
    out = np.asarray(pk.average_nan_columns(g, block_d=case.get("block", 128)))
    np.testing.assert_allclose(out, oracle.average_nan(g), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("n", [12, 72], ids=["pairs", "gram"])
def test_pairwise_distances(n):
    g = _rand(n, 500, 7)
    out = np.array(pk.pairwise_sq_distances(g, block_d=128))
    ref = oracle._pairwise_sq_distances(g.astype(np.float64))
    np.fill_diagonal(out, 0.0)  # oracle pins the diagonal; kernels leave ~0
    tol = 1e-4 if n > pk.PAIR_ROWS_MAX else 1e-5
    np.testing.assert_allclose(out, ref, rtol=tol, atol=tol)


@pytest.mark.parametrize("n,d,block_d", [(8, 64, 128), (13, 1000, 128), (32, 1000, 256)])
@pytest.mark.parametrize("poison", ["nan-coordinate", "nan-row", "inf-row", "huge-row"])
def test_pairwise_distances_nan_row(n, d, block_d, poison):
    """One Byzantine row, whatever it holds, spoils its own row and column
    of the matrix and nothing else: the difference form never mixes rows, so
    the honest pairs stay exact."""
    g = _rand(n, d, 9)
    if poison == "nan-coordinate":
        g[3, 10] = np.nan
    elif poison == "nan-row":
        g[3] = np.nan
    elif poison == "inf-row":
        g[3] = np.inf
    else:
        g[3] = 1e30 * np.where(np.arange(d) % 2, 1.0, -1.0)  # squares overflow float32
    out = np.asarray(pk.pairwise_sq_distances(g, block_d=block_d))
    honest = np.arange(n) != 3
    assert not np.any(np.isfinite(out[3, honest])) and not np.any(np.isfinite(out[honest, 3]))
    if poison.startswith("nan"):
        assert np.all(np.isnan(out[3, :])) and np.all(np.isnan(out[:, 3]))
    ref = oracle._pairwise_sq_distances(g[honest].astype(np.float64))
    np.testing.assert_allclose(out[np.ix_(honest, honest)], ref, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize(
    "name,f",
    [("median-pallas", 2), ("averaged-median-pallas", 2), ("average-nan-pallas", 2),
     ("krum-pallas", 2), ("bulyan-pallas", 1), ("trimmed-mean-pallas", 2)],
)
def test_registered_pallas_tier_matches_jnp(name, f):
    import jax.numpy as jnp

    from aggregathor_tpu import gars

    n = 11
    g = _rand(n, 90, 21, nan_frac=0.05)
    base = name.replace("-pallas", "")
    a = np.asarray(gars.instantiate(base, n, f).aggregate(jnp.asarray(g)))
    b = np.asarray(gars.instantiate(name, n, f).aggregate(jnp.asarray(g)))
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5, equal_nan=True)


def test_majority_nan_column_tiers_agree():
    """Median-slot non-finite: jnp, pallas, and oracle return the same value."""
    import jax.numpy as jnp

    from aggregathor_tpu import gars

    g = _rand(5, 12, 5)
    g[0:3, 4] = np.nan  # majority-NaN column: median slot is NaN
    g[0:4, 7] = np.inf  # majority-inf column: median slot is +inf
    ref = oracle.median(g)
    jnp_out = np.asarray(gars.instantiate("median", 5, 1).aggregate(jnp.asarray(g)))
    pls_out = np.asarray(gars.instantiate("median-pallas", 5, 1).aggregate(jnp.asarray(g)))
    np.testing.assert_array_equal(np.isnan(jnp_out), np.isnan(ref))
    np.testing.assert_array_equal(np.isnan(pls_out), np.isnan(ref))
    mask = ~np.isnan(ref)
    np.testing.assert_allclose(jnp_out[mask], ref[mask], rtol=1e-6)
    np.testing.assert_allclose(pls_out[mask], ref[mask], rtol=1e-6)


def test_gram_distance_nan_poisons_only_its_rows():
    """Majority-NaN column must not poison the whole Gram distance matrix
    (more than ``PAIR_ROWS_MAX`` rows: the form that centers on a median)."""
    g = _rand(72, 64, 6)
    g[0:37, 10] = np.nan
    out = np.array(pk.pairwise_sq_distances(g, block_d=128))
    clean = np.ix_(range(37, 72), range(37, 72))
    assert np.all(np.isfinite(out[clean]))
    assert np.all(np.isnan(out[0, 37:]))


def test_pallas_krum_rejects_outlier():
    g = _rand(12, 200, 33)
    g[0] = 1e6
    from aggregathor_tpu import gars

    out = np.asarray(gars.instantiate("krum-pallas", 12, 2).aggregate(g))
    honest = np.mean(g[1:], axis=0)
    # The selected-subset mean differs from the full honest mean by O(1);
    # what matters is the attacker (distance ~1e6·sqrt(d)) was excluded.
    assert np.linalg.norm(out - honest) < 1e-3 * np.linalg.norm(g[0] - honest)


# Tile-boundary shapes: d exactly one lane tile (128), an exact multiple,
# and one past the boundary — the shapes where Mosaic block specs and the
# grid iteration must agree (ops/pallas_kernels.py block_d handling); plus
# the n=2 minimum.
TILE_CASES = [
    dict(n=2, d=128, seed=10, nan_frac=0.0),
    dict(n=9, d=256, seed=11, nan_frac=0.1),
    dict(n=8, d=129, seed=12, nan_frac=0.0),
    dict(n=3, d=384, seed=13, nan_frac=0.3),
    dict(n=16, d=1000, seed=16, nan_frac=0.1),
    dict(n=32, d=3 * 128 - 7, seed=17, nan_frac=0.0),
    # the plane form's blocks of 1,024 and 2,048: one short, whole, one over
    dict(n=4, d=1023, seed=30, nan_frac=0.1, block=1024, edges=True),
    dict(n=16, d=1024, seed=31, nan_frac=0.1, block=1024, edges=True),
    dict(n=4, d=1025, seed=32, nan_frac=0.0, block=1024, edges=True),
    dict(n=5, d=2 * 2048 + 1, seed=33, nan_frac=0.1, block=2048, edges=True),
    dict(n=2, d=2 * 1024 + 40, seed=34, nan_frac=0.3, block=1024, edges=True),
]


@pytest.mark.parametrize("case", TILE_CASES)
def test_coordinate_kernels_at_tile_boundaries(case):
    g = _rand(**case)
    block = case.get("block", 128)
    np.testing.assert_array_equal(
        np.asarray(pk.coordinate_median(g, block_d=block)), oracle.median(g).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(pk.average_nan_columns(g, block_d=block)), oracle.average_nan(g),
        rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("n,form", [
    (4, "_planes"), (16, "_planes"), (pk.PLANE_ROWS_MAX, "_planes"), (pk.PLANE_ROWS_MAX + 1, "")])
def test_kernel_name_says_the_form(n, form):
    """The form follows the row count and nothing else, and the name of the
    ``pallas_call`` — what a compiled step and the ledger's ``breakdown``
    show — says which ran: the rank rules' planes up to ``PLANE_ROWS_MAX``
    rows, the slab beyond, and for the rule without ranks."""
    import jax

    g = np.zeros((n, 2048), np.float32)
    for name, rule in [
            ("coordinate_median", pk.coordinate_median),
            ("coordinate_averaged_median", lambda x: pk.coordinate_averaged_median(x, n - 1)),
            ("coordinate_trimmed_mean", lambda x: pk.coordinate_trimmed_mean(x, 1, n - 2))]:
        assert " name=%s%s\n" % (name, form) in str(jax.make_jaxpr(rule)(g)) + "\n"
    assert " name=average_nan_columns\n" in str(jax.make_jaxpr(pk.average_nan_columns)(g)) + "\n"


# (n, d, block_d): the three boundary widths at n=6, then widths that are a
# multiple neither of the block nor of the lane — blk + 1 and 3·blk − 7 at
# blocks wide enough to run the pair kernel's chunk loop, and one at the
# block the wrapper picks itself — at n = 8, 13, 32, 64.  The Gram form
# takes the same widths at 72 rows (one row tile) and 136 (two).
DISTANCE_WIDTHS = [
    (6, 128, 128), (6, 129, 128), (6, 256, 128),
    (8, 129, 128), (13, 1000, 128), (32, 1000, 256), (64, 3 * 128 - 7, 128),
    (32, 2048 + 1, 2048), (8, 3 * 4096 - 7, 4096), (16, pk.PAIR_MAX_BLOCK + 1, None),
]


@pytest.mark.parametrize("gram", [False, True], ids=["pairs", "gram"])
@pytest.mark.parametrize("n,d,block_d", DISTANCE_WIDTHS)
def test_pairwise_distances_at_tile_boundaries(gram, n, d, block_d):
    if gram:
        n = 72 if n < 32 else 136
    g = _rand(n, d, 14)
    out = np.array(pk.pairwise_sq_distances(g, block_d=block_d))
    ref = oracle._pairwise_sq_distances(g.astype(np.float64))
    if not gram:  # exact: each pair once, mirrored; a row against itself is 0
        np.testing.assert_array_equal(out, out.T)
        np.testing.assert_array_equal(np.diag(out), 0.0)
    np.fill_diagonal(out, 0.0)  # oracle pins the diagonal; the Gram form leaves ~0
    tol = 1e-4 if gram else 1e-5
    np.testing.assert_allclose(out, ref, rtol=tol, atol=tol * np.sqrt(d / 128))


@pytest.mark.parametrize("n,grid_rank", [(8, 1), (64, 1), (65, 3), (136, 3)])
def test_the_distance_form_follows_the_row_count(n, grid_rank):
    """Up to ``PAIR_ROWS_MAX`` rows the pair kernel, a grid over column blocks;
    above it the Gram form, a grid over (row tile, row tile, column block), of
    ``ROW_TILE`` rows a tile once there are more: nothing but n chooses."""
    import jax

    jaxpr = jax.make_jaxpr(pk.pairwise_sq_distances)(np.zeros((n, 300), np.float32)).jaxpr
    [call] = [eqn for eqn in jaxpr.eqns if eqn.primitive.name == "pallas_call"]
    grid = call.params["grid_mapping"].grid
    assert len(grid) == grid_rank
    if grid_rank == 3:
        assert grid[:2] == ((1, 1) if n <= pk.ROW_TILE else (2, 2))


def test_pallas_krum_excludes_fully_nan_row_like_jnp():
    """A worker whose whole row is NaN (total datagram loss) must be treated
    identically by the pallas and jnp tiers: excluded from selection, finite
    aggregate out."""
    import jax.numpy as jnp

    from aggregathor_tpu import gars

    g = _rand(9, 160, 15)
    g[2, :] = np.nan
    a = np.asarray(gars.instantiate("krum", 9, 2).aggregate(jnp.asarray(g)))
    b = np.asarray(gars.instantiate("krum-pallas", 9, 2).aggregate(jnp.asarray(g)))
    assert np.all(np.isfinite(a)) and np.all(np.isfinite(b))
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def _tier_under_vmap(stack):
    """What ``kernel_tier`` answers for the (n, d) blocks of ``stack`` under vmap."""
    import jax

    from aggregathor_tpu.gars import common

    tiers = []
    jax.vmap(lambda x: tiers.append(common.kernel_tier(x)) or x.sum())(stack)
    return tiers


def test_engine_auto_tier_matches_jnp():
    """The round-4 backend auto-dispatch (gars/common.use_pallas_coordinate_tier):
    ``forced_tier("pallas")`` routes median/averaged-median/bulyan-final
    selections AND the engine's partial distances through the Pallas kernels
    (interpret mode on CPU) inside the full shard_map step — and the result
    matches the default jnp tier."""
    import jax
    from aggregathor_tpu import gars, models
    from aggregathor_tpu.core import build_optimizer, build_schedule
    from aggregathor_tpu.gars.common import forced_tier
    from aggregathor_tpu.parallel import RobustEngine, make_mesh

    def run():
        exp = models.instantiate("mnist", ["batch-size:8"])
        # bulyan: needs_distances (the engine's partial-distance dispatch)
        # AND an averaged-median final phase (the coordinate dispatch)
        gar = gars.instantiate("bulyan", 8, 1)
        tx = build_optimizer("sgd", build_schedule("fixed", ["initial-rate:0.05"]))
        engine = RobustEngine(make_mesh(nb_workers=4), gar, nb_workers=8)
        step = engine.build_step(exp.loss, tx)
        state = engine.init_state(exp.init(jax.random.PRNGKey(5)), tx, seed=2)
        it = exp.make_train_iterator(8, seed=7)
        for _ in range(2):
            state, metrics = step(state, engine.shard_batch(next(it)))
        return np.concatenate(
            [np.ravel(np.asarray(x)) for x in jax.tree_util.tree_leaves(state.params)]
        )

    with forced_tier("pallas"):
        through_pallas = run()
    with forced_tier("jnp"):
        through_jnp = run()
    np.testing.assert_allclose(through_pallas, through_jnp, rtol=1e-5, atol=1e-6)


def test_use_pallas_tier_forced():
    from aggregathor_tpu.gars.common import forced_tier, use_pallas_coordinate_tier

    block = np.zeros((8, 4), np.float32)
    with forced_tier("pallas"):
        assert use_pallas_coordinate_tier(block)
        with forced_tier("jnp"):
            assert not use_pallas_coordinate_tier(block)
        assert use_pallas_coordinate_tier(block)  # the outer force is restored
    with pytest.raises(ValueError, match="'pallas' or 'jnp'"):
        with forced_tier("auto"):
            pass
    # nothing forced, CPU backend: the jnp tier regardless of size
    assert not use_pallas_coordinate_tier(block)
    assert not use_pallas_coordinate_tier(np.zeros((8, 1 << 20), np.float32))


@pytest.mark.parametrize("exported", ["jnp", "pallas"])
def test_kernel_tier_ignores_the_environment(monkeypatch, exported):
    """A shell that exports GRAFT_GAR_TIER (the switch read here until PR 29)
    moves no decision: on a 'tpu' backend and on the CPU, plain and under
    vmap, ``kernel_tier`` answers as with the variable unset."""
    import jax

    from aggregathor_tpu.gars import common

    big = np.zeros((8, common.PALLAS_MIN_COLUMNS), np.float32)

    def answers():
        return [common.kernel_tier(big), common.kernel_tier(big[:, :128])] + _tier_under_vmap(big[None])

    monkeypatch.delenv("GRAFT_GAR_TIER", raising=False)
    unset_cpu = answers()
    monkeypatch.setenv("GRAFT_GAR_TIER", exported)
    assert answers() == unset_cpu == ["jnp", "jnp", "jnp (vmapped)"]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert answers() == ["pallas", "jnp", "jnp (vmapped)"]


def test_use_pallas_tier_suspends_under_vmap(monkeypatch):
    """The auto-dispatch detects a batching trace centrally: even on a
    'tpu' backend with a large block, a vmapped rule call stays on the
    jnp tier — while the same call outside vmap dispatches.  The ``jnp``
    force does not outrank the diversion's name; the ``pallas`` force does."""
    import jax

    from aggregathor_tpu.gars import common

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    decisions = []

    def probe(x):
        decisions.append(common.use_pallas_coordinate_tier(x))
        return x.sum()

    big = np.zeros((2, 8, common.PALLAS_MIN_COLUMNS), np.float32)
    jax.vmap(probe)(big)          # batched (8, d) block -> suspended
    probe(big[0])                 # same block, plain call -> dispatches
    assert decisions == [False, True]
    with common.forced_tier("jnp"):
        assert _tier_under_vmap(big) == ["jnp (vmapped)"]
    with common.forced_tier("pallas"):
        assert _tier_under_vmap(big) == ["pallas"]


def test_batched_tracer_detected_under_vmap():
    """The vmap diversion must not silently die with a JAX upgrade:
    _is_batched_tracer fires under vmap (an import failure of the tracer
    class already fails the whole module at collection)."""
    import jax

    from aggregathor_tpu.gars import common

    seen = []

    def probe(x):
        seen.append(common._is_batched_tracer(x))
        return x

    jax.vmap(probe)(np.zeros((2, 4), np.float32))
    probe(np.zeros((4,), np.float32))
    assert seen == [True, False]


def test_kernel_tier_names_the_served_tier(monkeypatch):
    """One decision, three answers: pallas on a TPU-sized block, jnp below
    the column threshold or off-TPU, and 'jnp (vmapped)' where the batching
    trace diverts — the string the runner's log line carries."""
    import jax

    from aggregathor_tpu.gars import common

    big = np.zeros((8, common.PALLAS_MIN_COLUMNS), np.float32)
    assert common.kernel_tier(big) == "jnp"  # CPU backend
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert common.kernel_tier(big) == "pallas"
    assert common.kernel_tier(big[:, :128]) == "jnp"
    assert _tier_under_vmap(big[None]) == ["jnp (vmapped)"]


@pytest.mark.parametrize("case", CASES)
def test_coordinate_trimmed_mean(case):
    g = _rand(**case)
    n = g.shape[0]
    trim = min(2, (n - 1) // 2)
    out = np.asarray(pk.coordinate_trimmed_mean(g, trim, n - 2 * trim, block_d=case.get("block", 128)))
    np.testing.assert_allclose(
        out, oracle.trimmed_mean(g, trim), rtol=1e-5, atol=1e-6, equal_nan=True)


def test_coordinate_trimmed_mean_poisoned_band():
    """More than trim non-finite entries in a column -> NaN out, both tiers."""
    g = _rand(8, 40, 11)
    g[:3, 7] = np.nan  # 3 poisoned > trim=2: the kept band holds an inf
    out = np.asarray(pk.coordinate_trimmed_mean(g, 2, 4, block_d=128))
    ref = oracle.trimmed_mean(g, 2)
    assert np.isnan(out[7]) and np.isnan(ref[7])
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6, equal_nan=True)


def test_pallas_tpu_check_callable_in_interpret_mode(monkeypatch):
    """scripts/pallas_tpu_check.run_check — chip_smoke's leg C — run off-TPU
    at a tiny d: every row comes back parity-ok through ``emit``, the jnp
    force is released, and without --allow-interpret it refuses to interpret."""
    import os
    import sys

    scripts = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "scripts")
    monkeypatch.syspath_prepend(scripts)
    import pallas_tpu_check

    from aggregathor_tpu.gars import common

    rows = []
    failed = pallas_tpu_check.run_check(
        8, 2, [256], rules=("median", "krum"), reps=1,
        allow_interpret=True, emit=rows.append)
    assert failed == []
    assert [r["rule"] for r in rows] == [
        "median", "krum", "median-vmap4", "averaged-median-vmap4",
        "trimmed-mean-vmap4", "pairwise-dist-vmap4"]
    assert all(r["parity"] == "ok" for r in rows)
    assert common._forced is None
    with pytest.raises(RuntimeError, match="needs a TPU"):
        pallas_tpu_check.run_check(8, 2, [256], rules=("median",), reps=1)
    sys.modules.pop("pallas_tpu_check", None)

