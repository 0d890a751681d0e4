"""GAR property and cross-tier equivalence tests (the pyramid of SURVEY.md §4)."""

import numpy as np
import pytest

from aggregathor_tpu import gars
from aggregathor_tpu.gars import oracle

RULES = ["average", "average-nan", "median", "averaged-median", "krum", "bulyan",
         "trimmed-mean", "centered-clip", "geometric-median"]
ORACLES = {
    "average": oracle.average,
    "average-nan": oracle.average_nan,
    "median": oracle.median,
    "averaged-median": oracle.averaged_median,
    "krum": oracle.krum,
    "bulyan": oracle.bulyan,
    "trimmed-mean": oracle.trimmed_mean,
    "centered-clip": oracle.centered_clip,
    "geometric-median": oracle.geometric_median,
    "dnc": oracle.dnc,
}


def make_grads(rng, n=11, d=37, scale=1.0):
    return rng.normal(size=(n, d)).astype(np.float32) * scale


def params_for(rule):
    # bulyan needs n >= 4f + 3; krum n >= f + 3; trimmed-mean n > 2f;
    # centered-clip f < n/2
    return {"bulyan": (11, 2), "krum": (11, 3)}.get(rule, (11, 3))


@pytest.mark.parametrize("rule", RULES)
def test_matches_numpy_oracle(rule, rng):
    n, f = params_for(rule)
    grads = make_grads(rng, n=n)
    gar = gars.instantiate(rule, n, f)
    got = np.asarray(gar.aggregate(grads))
    want = ORACLES[rule](grads, f)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("rule", RULES)
def test_permutation_equivariance(rule, rng):
    """Shuffling workers must not change the aggregate (worker identity is meaningless)."""
    n, f = params_for(rule)
    grads = make_grads(rng, n=n)
    gar = gars.instantiate(rule, n, f)
    base = np.asarray(gar.aggregate(grads))
    perm = rng.permutation(n)
    shuffled = np.asarray(gar.aggregate(grads[perm]))
    np.testing.assert_allclose(shuffled, base, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize(
    "rule", ["median", "averaged-median", "krum", "bulyan", "trimmed-mean",
             "centered-clip", "geometric-median", "dnc"]  # dnc: 1e6 colluders = strong spectrum
)
def test_byzantine_robustness(rule, rng):
    """With f adversarial rows pushing a huge vector, the aggregate must stay
    within the honest cloud (Byzantine-bound sanity; SURVEY.md §4)."""
    n, f = params_for(rule)
    grads = make_grads(rng, n=n)
    attacked = grads.copy()
    attacked[:f] = 1e6  # f colluding outliers
    gar = gars.instantiate(rule, n, f)
    out = np.asarray(gar.aggregate(attacked))
    honest_max = np.abs(grads[f:]).max() * n
    assert np.all(np.abs(out) <= honest_max), "%s leaked the Byzantine direction" % rule


@pytest.mark.parametrize("rule", RULES)
def test_average_consensus(rule, rng):
    """When every worker submits the same gradient, every rule returns it."""
    n, f = params_for(rule)
    g = rng.normal(size=(37,)).astype(np.float32)
    grads = np.tile(g, (n, 1))
    gar = gars.instantiate(rule, n, f)
    np.testing.assert_allclose(np.asarray(gar.aggregate(grads)), g, rtol=1e-5, atol=1e-6)


def test_average_nan_ignores_nans(rng):
    grads = make_grads(rng, n=8)
    grads[0, :10] = np.nan
    grads[3, 5:15] = np.inf
    gar = gars.instantiate("average-nan", 8, 0)
    got = np.asarray(gar.aggregate(grads))
    want = oracle.average_nan(grads)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    assert np.all(np.isfinite(got))


def test_median_nan_last(rng):
    grads = make_grads(rng, n=7)
    grads[2, :] = np.nan
    gar = gars.instantiate("median", 7, 1)
    got = np.asarray(gar.aggregate(grads))
    want = oracle.median(grads)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("rule", ["krum", "bulyan"])
def test_nan_worker_never_selected(rule, rng):
    """A worker submitting NaNs has +inf distances, hence worst score, and must
    not contaminate the output (krum.py:71-73 convention)."""
    n, f = params_for(rule)
    grads = make_grads(rng, n=n)
    grads[1, :] = np.nan
    gar = gars.instantiate(rule, n, f)
    out = np.asarray(gar.aggregate(grads))
    assert np.all(np.isfinite(out))
    want = ORACLES[rule](grads, f)
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-5)


def test_krum_selects_smallest_scores(rng):
    n, f = 9, 2
    grads = make_grads(rng, n=n)
    scores = oracle.krum_scores(grads, f)
    m = n - f - 2
    selected = np.argsort(scores)[:m]
    want = np.mean(grads[selected], axis=0)
    gar = gars.instantiate("krum", n, f)
    np.testing.assert_allclose(np.asarray(gar.aggregate(grads)), want, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("n,f", [(11, 2), (64, 15), (128, 31), (512, 127)])
def test_bulyan_scales_matches_oracle(n, f, rng):
    """The sort-based pruning path must match the numpy oracle at scale
    (the previous (n, n, n) rank tensor was a 2 GB wall at n=1024; the
    previous trace-time-unrolled selection loop was a compile-time wall at
    n=512, where t = n - 2f - 2 = 256 rounds — now one lax.scan)."""
    grads = make_grads(rng, n=n, d=257)
    gar = gars.instantiate("bulyan", n, f)
    got = np.asarray(gar.aggregate(grads))
    want = ORACLES["bulyan"](grads, f)
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-4)


def test_invalid_nf_relations():
    from aggregathor_tpu.utils import UserException

    with pytest.raises(UserException):
        gars.instantiate("krum", 4, 2)  # needs n >= f + 3
    with pytest.raises(UserException):
        gars.instantiate("bulyan", 8, 2)  # needs n >= 4f + 3


def test_registry_lists_all_rules():
    names = gars.itemize()
    for rule in RULES:
        assert rule in names


def test_trimmed_mean_nan_columns(rng):
    """A column with more than `trim` poisoned entries surfaces NaN, never a
    silently-huge mean; columns within the trim budget stay clean."""
    grads = make_grads(rng, n=9)
    grads[:2, 0] = np.inf  # within trim=2 budget
    grads[:3, 1] = np.nan  # exceeds it
    gar = gars.instantiate("trimmed-mean", 9, 2)
    out = np.asarray(gar.aggregate(grads))
    assert np.isfinite(out[0])
    assert np.isnan(out[1])


def test_trimmed_mean_trim_arg(rng):
    grads = make_grads(rng, n=9)
    default = np.asarray(gars.instantiate("trimmed-mean", 9, 2).aggregate(grads))
    explicit = np.asarray(gars.instantiate("trimmed-mean", 9, 2, ["trim:2"]).aggregate(grads))
    np.testing.assert_allclose(default, explicit)
    wider = np.asarray(gars.instantiate("trimmed-mean", 9, 2, ["trim:4"]).aggregate(grads))
    assert not np.allclose(default, wider)


def test_centered_clip_bias_bound(rng):
    """f Byzantine rows can displace the center by at most iters*f*tau/n."""
    n, f, tau, iters = 11, 3, 1.0, 3
    grads = make_grads(rng, n=n, scale=0.1)
    attacked = grads.copy()
    attacked[:f] = 1e6
    gar = gars.instantiate("centered-clip", n, f, ["tau:%s" % tau, "iters:%d" % iters])
    clean = np.asarray(gar.aggregate(grads))
    dirty = np.asarray(gar.aggregate(attacked))
    displacement = np.linalg.norm(dirty - clean)
    assert displacement <= iters * f * tau / n + 1.0, displacement


def test_centered_clip_excludes_nonfinite_rows(rng):
    grads = make_grads(rng, n=8)
    grads[1, 3] = np.nan
    gar = gars.instantiate("centered-clip", 8, 1)
    out = np.asarray(gar.aggregate(grads))
    assert np.all(np.isfinite(out))
    # removing the poisoned row entirely gives a nearby center
    alone = np.asarray(gars.instantiate("centered-clip", 7, 1).aggregate(grads[[0] + list(range(2, 8))]))
    np.testing.assert_allclose(out, alone, rtol=1e-3, atol=1e-4)


def test_geometric_median_blockwise_exact(rng):
    """uses_axis rules on the sharded engine match the dense tier EXACTLY:
    n=8 over 8, 4 and 1 devices yields the same aggregate (global row norms
    via psum — no block-local approximation)."""
    import jax

    from aggregathor_tpu.core.flatten import FlatMap  # noqa: F401 (engine dep)
    from aggregathor_tpu.parallel.engine import RobustEngine
    from aggregathor_tpu.parallel.mesh import make_mesh
    import optax

    from aggregathor_tpu import models

    ex = models.instantiate("mnist", ["batch-size:8"])
    batch = next(ex.make_train_iterator(8, seed=3))
    results = {}
    for rule in ("geometric-median", "centered-clip"):
        for nb_devices in (8, 4, 1):
            eng = RobustEngine(make_mesh(nb_workers=nb_devices), gars.instantiate(rule, 8, 2), 8)
            tx = optax.sgd(1e-2)
            state = eng.init_state(ex.init(jax.random.PRNGKey(0)), tx)
            state, m = eng.build_step(ex.loss, tx)(state, eng.shard_batch(batch))
            results[nb_devices] = jax.device_get(state.params)
        for d in (4, 1):
            for a, b in zip(
                jax.tree_util.tree_leaves(results[8]), jax.tree_util.tree_leaves(results[d])
            ):
                np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6, err_msg=rule)


def test_geometric_median_nan_rows_ignored(rng):
    """Rows with any non-finite coordinate get weight 0 (average-nan
    convention); all-dead yields zeros."""
    grads = make_grads(rng, n=9)
    grads[2, 5] = np.nan
    grads[6, :] = np.inf
    gar = gars.instantiate("geometric-median", 9, 2)
    out = np.asarray(gar.aggregate(grads))
    assert np.all(np.isfinite(out))
    honest = np.delete(grads, (2, 6), axis=0)
    want = oracle.geometric_median(honest, 2)
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-5)
    dead = np.full((5, 7), np.nan, dtype=np.float32)
    np.testing.assert_array_equal(np.asarray(gars.instantiate("geometric-median", 5, 1).aggregate(dead)), 0.0)


def test_geometric_median_participation_downweights_outlier(rng):
    """The final Weiszfeld weights expose the outlier: its participation is
    far below every honest worker's.  (Weights come back from the same pass
    as the aggregate — no state stashed between calls.)"""
    import jax

    grads = make_grads(rng, n=9)
    grads[0] = 1e4
    gar = gars.instantiate("geometric-median", 9, 2)
    agg, part = jax.jit(gar.aggregate_block_and_participation)(grads)
    np.testing.assert_allclose(np.asarray(agg), np.asarray(gar.aggregate(grads)), rtol=1e-5)
    part = np.asarray(jax.device_get(part))
    assert part.shape == (9,)
    np.testing.assert_allclose(part.sum(), 1.0, rtol=1e-4)
    assert part[0] < 0.1 * part[1:].min()


def test_bucketing_matches_oracle_composition(rng):
    """bucketing(inner=krum) == numpy bucket means (same permutation) fed to
    the krum oracle; key=None uses the identity permutation."""
    import jax

    n, s, f = 12, 2, 1
    grads = make_grads(rng, n=n)
    gar = gars.instantiate("bucketing", n, f, ["s:2", "inner:krum"])
    key = jax.random.PRNGKey(5)
    got = np.asarray(jax.jit(gar.aggregate)(grads, key=key))
    perm = np.asarray(jax.random.permutation(key, n))
    want = oracle.bucketing(grads, f, perm, s, oracle.krum)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    got_id = np.asarray(gar.aggregate(grads))
    want_id = oracle.bucketing(grads, f, np.arange(n), s, oracle.krum)
    np.testing.assert_allclose(got_id, want_id, rtol=1e-4, atol=1e-5)
    # the key really drives the permutation (different key -> different buckets)
    assert not np.allclose(got, got_id)


def test_bucketing_robustness_and_participation(rng):
    """f huge outliers corrupt at most f buckets: the inner krum never picks
    them, the aggregate stays in the honest cloud, and the scattered-back
    participation is 0 for every attacker."""
    import jax

    n, f = 12, 2
    grads = make_grads(rng, n=n)
    attacked = grads.copy()
    attacked[:f] = 1e6
    gar = gars.instantiate("bucketing", n, f, ["s:2", "inner:krum"])
    key = jax.random.PRNGKey(9)
    dist2 = None
    agg, part = jax.jit(
        lambda g: gar.aggregate_block_and_participation(g, dist2, key=key)
    )(attacked)
    agg, part = np.asarray(agg), np.asarray(part)
    honest_max = np.abs(grads[f:]).max() * n
    assert np.all(np.abs(agg) <= honest_max)
    assert part.shape == (n,)
    np.testing.assert_allclose(part.sum(), 1.0, rtol=1e-5)
    np.testing.assert_allclose(part[:f], 0.0, atol=1e-7)


def test_bucketing_validation():
    import pytest

    from aggregathor_tpu.utils import UserException

    with pytest.raises(UserException):
        gars.instantiate("bucketing", 10, 1, ["s:3"])  # s must divide n
    with pytest.raises(UserException):
        # inner krum feasibility at n/s rows: 8/2=4 buckets < f+3
        gars.instantiate("bucketing", 8, 2, ["s:2", "inner:krum"])
    gar = gars.instantiate("bucketing", 8, 1, ["s:2", "inner:median"])
    assert gar.nb_buckets == 4


def test_bucketing_engine_device_invariance(rng):
    """The per-step permutation key is replicated: n=8 over 8 and 1 devices
    produce identical params, and per-step permutations actually differ."""
    import jax
    import optax

    from aggregathor_tpu import models
    from aggregathor_tpu.parallel.engine import RobustEngine
    from aggregathor_tpu.parallel.mesh import make_mesh

    ex = models.instantiate("mnist", ["batch-size:8"])
    batches = [next(ex.make_train_iterator(8, seed=6)) for _ in range(3)]
    outs = {}
    for nb_devices in (8, 1):
        eng = RobustEngine(
            make_mesh(nb_workers=nb_devices),
            gars.instantiate("bucketing", 8, 1, ["s:2", "inner:krum"]), 8,
        )
        tx = optax.sgd(1e-2)
        state = eng.init_state(ex.init(jax.random.PRNGKey(0)), tx)
        step = eng.build_step(ex.loss, tx)
        for b in batches:
            state, _ = step(state, eng.shard_batch(b))
        outs[nb_devices] = jax.device_get(state.params)
    for a, b in zip(jax.tree_util.tree_leaves(outs[8]), jax.tree_util.tree_leaves(outs[1])):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6)


def test_nested_bucketing_forwards_key(rng):
    """inner:bucketing re-randomizes too: with a key the nested permutation
    differs from identity, so the output differs from the key=None run."""
    import jax

    grads = make_grads(rng, n=16, d=23)
    gar = gars.instantiate("bucketing", 16, 1, ["s:2", "inner:bucketing"])
    with_key = np.asarray(gar.aggregate(grads, key=jax.random.PRNGKey(3)))
    identity = np.asarray(gar.aggregate(grads))
    assert with_key.shape == identity.shape == (23,)
    assert not np.allclose(with_key, identity)


def test_global_granularity_rejected_for_iterative_rules():
    import pytest

    from aggregathor_tpu.parallel.mesh import make_mesh
    from aggregathor_tpu.parallel import RobustEngine
    from aggregathor_tpu.utils import UserException

    mesh = make_mesh(nb_workers=2, model_parallelism=2, pipeline_parallelism=2)
    for rule in ("geometric-median", "bucketing"):
        with pytest.raises(UserException):
            RobustEngine(mesh, gars.instantiate(rule, 2, 0), granularity="global", sharding="sharded")


def test_dnc_drops_colluders_and_reports_participation(rng):
    """DnC's spectral scores concentrate on a colluding direction: the f
    coordinated outliers (and a NaN row) are dropped, the kept mean matches
    the oracle, and the participation weights expose the drop."""
    import jax

    n, f = 12, 3
    grads = make_grads(rng, n=n)
    grads[:f] += 50.0 * rng.normal(size=(1, grads.shape[1])).astype(np.float32)  # common direction
    grads[5, 7] = np.nan
    gar = gars.instantiate("dnc", n, f)
    agg, part = jax.jit(gar.aggregate_block_and_participation)(grads)
    agg, part = np.asarray(agg), np.asarray(part)
    want = oracle.dnc(grads, f)
    np.testing.assert_allclose(agg, want, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(part[:f], 0.0, atol=1e-7)  # colluders dropped
    np.testing.assert_allclose(part[5], 0.0, atol=1e-7)   # dead row dropped
    np.testing.assert_allclose(part.sum(), 1.0, rtol=1e-5)
    # remove: arg overrides the default f
    wider = gars.instantiate("dnc", n, f, ["remove:5"])
    assert float(np.asarray(wider.aggregate_block_and_participation(grads)[1]).astype(bool).sum()) <= n - 5


def test_dnc_regime_properties(rng):
    """DnC's flat-spectrum selection is precision-sensitive (the top singular
    direction of pure noise is ill-defined), so the RULES-wide oracle and
    permutation comparisons exclude it; under a genuine colluding signal the
    spectrum is decisive and both properties hold."""
    import jax

    n, f = 12, 3
    grads = make_grads(rng, n=n)
    grads[:f] += 50.0 * rng.normal(size=(1, grads.shape[1])).astype(np.float32)
    gar = gars.instantiate("dnc", n, f)
    base = np.asarray(gar.aggregate(grads))
    np.testing.assert_allclose(base, oracle.dnc(grads, f), rtol=1e-4, atol=1e-5)
    perm = rng.permutation(n)
    np.testing.assert_allclose(np.asarray(gar.aggregate(grads[perm])), base, rtol=1e-4, atol=1e-4)
    # consensus: zero spectrum, index tie-break — every rule returns the input
    g = rng.normal(size=(37,)).astype(np.float32)
    np.testing.assert_allclose(np.asarray(gar.aggregate(np.tile(g, (n, 1)))), g, rtol=1e-5, atol=1e-6)
    assert "dnc" in gars.itemize()


def test_dnc_more_dead_than_budget_yields_zero(rng):
    """When fewer live rows remain than the removal budget keeps, both tiers
    refuse to average anything (zeros) rather than keeping live colluders."""
    grads = make_grads(rng, n=12)
    grads[:8] = np.nan  # 4 alive, remove=5
    gar = gars.instantiate("dnc", 12, 3, ["remove:5"])
    np.testing.assert_array_equal(np.asarray(gar.aggregate(grads)), 0.0)
    np.testing.assert_array_equal(oracle.dnc(grads, 3, remove=5), 0.0)


@pytest.mark.parametrize("rule", ["krum", "bulyan"])
def test_no_memo_survives_aggregation(rule, rng):
    """memo_by_identity entries must not outlive the aggregation call — a
    stale (tracer, tracer) tuple keeps the traced selection graph alive and
    trips jax.check_tracer_leaks (ADVICE r2 finding 2)."""
    import jax

    n, f = params_for(rule)
    gar = gars.instantiate(rule, n, f)
    grads = make_grads(rng, n=n)
    from aggregathor_tpu.gars.common import pairwise_sq_distances

    dist2 = pairwise_sq_distances(jax.numpy.asarray(grads))
    with jax.check_tracer_leaks():
        jax.jit(gar.aggregate)(grads).block_until_ready()
        agg, part = jax.jit(gar.aggregate_block_and_participation)(grads, dist2)
        # the engines' direct dispatch point — the default
        # (worker_metrics=False) step path bypasses both entries above
        jax.jit(lambda g, d: gar._call_aggregate(g, d))(grads, dist2).block_until_ready()
    assert not [a for a in vars(gar) if a.startswith("_memo_")]
