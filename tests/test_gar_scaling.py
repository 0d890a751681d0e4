"""Large-n scaling tests: hierarchical GAR composition, ragged bucketing,
row-tiled distance kernels, worker/device decoupling in both engines, and the
``aggregathor.gar.scaling.v1`` schema contract (docs/gar_scaling.md)."""

import copy

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from aggregathor_tpu import gars, models
from aggregathor_tpu.core import build_optimizer, build_schedule
from aggregathor_tpu.gars import oracle, parse_spec, scaling
from aggregathor_tpu.models import transformer as tfm
from aggregathor_tpu.ops import pallas_kernels as pk
from aggregathor_tpu.parallel import RobustEngine, make_mesh
from aggregathor_tpu.utils import UserException


def make_grads(rng, n, d=48, scale=1.0):
    return rng.normal(size=(n, d)).astype(np.float32) * scale


# --------------------------------------------------------------------------- #
# Spec parsing


def test_parse_spec_three_forms():
    assert parse_spec("krum") == ("krum", [])
    assert parse_spec("hier:g=16,inner=median,outer=krum") == (
        "hier", ["g:16", "inner:median", "outer:krum"])
    assert parse_spec("hier(g=16,inner=median,outer=krum)") == (
        "hier", ["g:16", "inner:median", "outer:krum"])


def test_parse_spec_keeps_nested_commas_attached():
    name, args = parse_spec("bucketing:s=2,inner=hier(g=8,inner=median,outer=krum)")
    assert name == "bucketing"
    assert args == ["s:2", "inner:hier(g=8,inner=median,outer=krum)"]


def test_parse_spec_rejects_bare_argument():
    with pytest.raises(UserException):
        parse_spec("hier:g=16,median")


# --------------------------------------------------------------------------- #
# Hierarchical feasibility (parse-time Byzantine bookkeeping)


def test_hier_rejects_infeasible_outer():
    # 16 workers in groups of 4 -> outer krum over 4 rows with f=2 needs
    # n >= f + 3 = 5: the composition must be rejected BEFORE any training
    with pytest.raises(UserException):
        gars.instantiate("hier:g=4,inner=median,outer=krum", 16, 2)


def test_hier_rejects_group_size_not_dividing_n():
    with pytest.raises(UserException):
        gars.instantiate("hier:g=5,inner=median,outer=krum", 16, 1)


def test_hier_rejects_inner_f_beyond_group():
    with pytest.raises(UserException):
        gars.instantiate("hier:g=4,inner=median,outer=krum,inner_f=5", 32, 1)


def test_hier_inner_f_defaults_to_group_clamp():
    gar = gars.instantiate("hier:g=4,inner=krum,outer=krum,inner_f=1", 64, 2)
    assert gar.inner_f == 1
    gar = gars.instantiate("hier:g=8,inner=median,outer=krum", 64, 2)
    assert gar.inner_f == 2  # min(f, g-1)
    assert gar.outer.nb_workers == 8
    assert gar.outer.nb_byz_workers == 2  # the SAME declared f at the outer level


# --------------------------------------------------------------------------- #
# Hierarchical semantics


def test_hier_matches_manual_two_level_composition(rng):
    """hier:inner=median,outer=krum == krum over per-group medians (neither
    child rule is randomized, so the tree is exactly the manual pipeline)."""
    n, g, f = 32, 4, 2
    grads = make_grads(rng, n)
    gar = gars.instantiate("hier:g=%d,inner=median,outer=krum" % g, n, f)
    got = np.asarray(gar.aggregate(grads))
    summaries = np.stack([
        oracle.median(grads[i * g:(i + 1) * g], 0) for i in range(n // g)
    ])
    want = oracle.krum(summaries, f)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_hier_nan_absorbed_by_tolerant_inner(rng):
    """A NaN row dies at the GROUP level when the inner rule excludes it."""
    n = 64  # 8 groups of 8: both krum levels feasible at f=2
    grads = make_grads(rng, n)
    grads[3] = np.nan  # one dead worker in group 0
    gar = gars.instantiate("hier:g=8,inner=krum,outer=krum", n, 2)
    assert gar.nan_row_tolerant
    out = np.asarray(gar.aggregate(grads))
    assert np.all(np.isfinite(out))


def test_hier_nan_poisons_group_then_outer_excludes(rng):
    """A non-tolerant inner (average) lets the NaN poison its group summary;
    the tolerant outer (krum) then excludes that GROUP row — the two-level
    propagation convention of gars/hierarchical.py."""
    n, g = 64, 8  # 8 groups: outer krum feasible at f=2
    grads = make_grads(rng, n)
    grads[5] = np.nan
    gar = gars.instantiate("hier:g=%d,inner=average,outer=krum" % g, n, 2)
    assert gar.nan_row_tolerant  # via the outer level
    out = np.asarray(gar.aggregate(grads))
    assert np.all(np.isfinite(out))
    # the poisoned group contributes nothing: equal to dropping it manually
    summaries = np.stack([np.mean(grads[i * g:(i + 1) * g], axis=0) for i in range(n // g)])
    want = oracle.krum(summaries, 2)
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-5)


def test_hier_participation_scatters_through_tree(rng):
    n, g = 64, 8
    grads = make_grads(rng, n)
    gar = gars.instantiate("hier:g=%d,inner=median,outer=krum" % g, n, 2)
    agg, part = gar.aggregate_block_and_participation(
        jnp.asarray(grads), key=jax.random.PRNGKey(0))
    part = np.asarray(part)
    assert part.shape == (n,)
    np.testing.assert_allclose(part.sum(), 1.0, rtol=1e-6)
    # (multi-)krum selects nb_selected of the 8 groups uniformly; a median
    # inner spreads each group's weight uniformly over its g members — so
    # exactly nb_selected whole groups carry 1/(nb_selected*g) each
    sel = gar.outer.nb_selected
    nonzero = np.flatnonzero(part)
    assert len(nonzero) == sel * g
    chosen_groups = sorted(set(nonzero // g))
    assert len(chosen_groups) == sel  # whole groups, never partial ones
    np.testing.assert_allclose(part[nonzero], 1.0 / (sel * g), rtol=1e-6)


def test_hier_nests_with_bucketing_both_directions(rng):
    # n=64 keeps every level feasible at f=2: 32 buckets -> 16 hier groups
    # for the first spec, 16 groups -> 8 buckets for the second
    grads = make_grads(rng, 64)
    for spec in (
        "bucketing:s=2,inner=hier(g=2,inner=median,outer=krum)",
        "hier:g=4,inner=median,outer=bucketing(s=2,inner=krum)",
    ):
        gar = gars.instantiate(spec, 64, 2)
        agg, part = gar.aggregate_block_and_participation(
            jnp.asarray(grads), key=jax.random.PRNGKey(1))
        assert np.all(np.isfinite(np.asarray(agg))), spec
        np.testing.assert_allclose(np.asarray(part).sum(), 1.0, rtol=1e-5,
                                   err_msg=spec)


def test_hier_bit_deterministic_replay(rng):
    """Same rows + same key -> bitwise-identical aggregate and participation
    (randomized meta-rules must redraw deterministically from the step key)."""
    grads = jnp.asarray(make_grads(rng, 64))
    gar = gars.instantiate("hier:g=8,inner=median,outer=krum", 64, 2)
    key = jax.random.PRNGKey(7)
    a1, p1 = gar.aggregate_block_and_participation(grads, key=key)
    a2, p2 = gar.aggregate_block_and_participation(grads, key=key)
    assert np.array_equal(np.asarray(a1), np.asarray(a2))
    assert np.array_equal(np.asarray(p1), np.asarray(p2))


# --------------------------------------------------------------------------- #
# Ragged bucketing (satellite: s no longer must divide n)


def test_bucketing_ragged_pads_with_nan_bucket(rng):
    n, s, f = 16, 3, 1
    grads = make_grads(rng, n)
    gar = gars.instantiate("bucketing:s=%d,inner=krum" % s, n, f)
    assert gar.nb_padded == 2 and gar.nb_buckets == 6
    # f-accounting: the always-NaN padding bucket costs one extra declared row
    assert gar.inner.nb_byz_workers == f + 1
    agg, part = gar.aggregate_block_and_participation(
        jnp.asarray(grads), key=jax.random.PRNGKey(3))
    assert np.all(np.isfinite(np.asarray(agg)))
    part = np.asarray(part)
    assert part.shape == (n,)
    np.testing.assert_allclose(part.sum(), 1.0, rtol=1e-5)


def test_bucketing_ragged_rejects_non_tolerant_inner():
    # the guaranteed-NaN padding bucket would poison every step under a
    # non-excluding inner rule: refused at parse time
    with pytest.raises(UserException):
        gars.instantiate("bucketing:s=3,inner=average", 16, 1)


def test_bucketing_exact_division_unchanged(rng):
    """s | n keeps the historical semantics: no padding, same inner f."""
    gar = gars.instantiate("bucketing:s=2,inner=krum", 16, 2)
    assert gar.nb_padded == 0 and gar.nb_buckets == 8
    assert gar.inner.nb_byz_workers == 2


# --------------------------------------------------------------------------- #
# Row-tiled distance kernels (interpret mode on CPU, same body as TPU)


def test_pairwise_distances_over_two_tiles_match_the_oracle(rng):
    """n > ROW_TILE exercises the (i, j, k) grid: 136 rows are two row tiles
    of the Gram form."""
    g = make_grads(rng, 136, d=160)
    out = np.asarray(pk.pairwise_sq_distances(g, block_d=128))
    ref = oracle._pairwise_sq_distances(g.astype(np.float64))
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=1e-3)


def test_a_nan_row_over_two_tiles_spoils_only_itself(rng):
    """A NaN row spoils its own row and column in every row tile it meets, and
    nothing else (the Gram form centers on a NaN-ignoring median)."""
    g = make_grads(rng, 136, d=128)
    g[7] = np.nan
    out = np.asarray(pk.pairwise_sq_distances(g))
    assert np.all(np.isnan(out[7, :])) and np.all(np.isnan(out[:, 7]))
    mask = np.ones(136, bool)
    mask[7] = False
    assert np.all(np.isfinite(out[np.ix_(mask, mask)]))


@pytest.mark.parametrize("n,d", [(32, 256), (8, 129), (13, 1000), (32, 3 * 256 - 7), (64, 256 + 1)])
def test_pairwise_distances_match_the_oracle_at_ragged_widths(rng, n, d):
    """The pair kernel on the rows as they are, at widths that are whole
    blocks and at ragged ones."""
    g = make_grads(rng, n, d=d)
    ref = oracle._pairwise_sq_distances(g.astype(np.float64))
    np.testing.assert_allclose(pk.pairwise_sq_distances(g, block_d=256), ref, rtol=1e-5, atol=1e-4)


def test_ranks_rolled_loop_matches_unrolled(rng):
    """n > RANK_UNROLL_MAX flips _ranks to the fori_loop form — selections
    must be identical (here: via the coordinate median at n=96)."""
    assert pk.RANK_UNROLL_MAX < 96
    g = make_grads(rng, 96, d=130)
    # non-finite entries key as +inf: the rolled loop's masked-max row pick
    # must carry them exactly like the unrolled row slice does
    g[3, ::7] = np.nan
    g[40, 5] = np.inf
    out = np.asarray(pk.coordinate_median(g, block_d=128))
    np.testing.assert_allclose(out, oracle.median(g, 0), rtol=1e-5, atol=1e-5)
    out = np.asarray(pk.coordinate_averaged_median(g, 96 - 20, block_d=128))
    np.testing.assert_allclose(out, oracle.averaged_median(g, 20), rtol=1e-5, atol=1e-5)


def test_centered_gram_chunked_matches_monolithic(rng):
    from aggregathor_tpu.gars.common import centered_gram_sq_distances

    g = jnp.asarray(make_grads(rng, 24, d=700))
    full = np.asarray(centered_gram_sq_distances(g))
    # force the d-chunked accumulation path with a tiny budget
    chunked = np.asarray(centered_gram_sq_distances(g, chunk_budget=1))
    np.testing.assert_allclose(chunked, full, rtol=1e-4, atol=1e-3)


# --------------------------------------------------------------------------- #
# Engines at large n: workers decoupled from devices, zero recompiles


def _flat_setup(gar_spec, n, f, nb_devices):
    exp = models.instantiate("mnist", ["batch-size:4"])
    gar = gars.instantiate(gar_spec, n, f)
    tx = build_optimizer("sgd", build_schedule("fixed", ["initial-rate:0.05"]))
    engine = RobustEngine(make_mesh(nb_workers=nb_devices), gar, nb_workers=n)
    step = engine.build_step(exp.loss, tx)
    state = engine.init_state(exp.init(jax.random.PRNGKey(42)), tx, seed=1)
    return exp, engine, step, state


def test_flat_engine_n128_zero_recompiles():
    exp, engine, step, state = _flat_setup(
        "hier:g=16,inner=median,outer=krum", 128, 4, nb_devices=1)
    it = exp.make_train_iterator(128, seed=3)
    losses = []
    for _ in range(3):
        state, metrics = step(state, engine.shard_batch(next(it)))
        losses.append(float(metrics["total_loss"]))
    assert all(np.isfinite(losses))
    assert step._cache_size() == 1, "large-n steady state must not retrace"


def test_flat_engine_hier_device_count_invariance(rng):
    """n=32 logical workers on 8 devices == on 1 device under hier (the
    decoupling contract: device placement is a layout, not semantics)."""
    results = []
    for nb_devices in (8, 1):
        exp, engine, step, state = _flat_setup(
            "hier:g=4,inner=median,outer=krum", 32, 2, nb_devices)
        it = exp.make_train_iterator(32, seed=5)
        for _ in range(2):
            state, _ = step(state, engine.shard_batch(next(it)))
        results.append(np.concatenate([
            np.ravel(np.asarray(x)) for x in jax.tree_util.tree_leaves(state.params)
        ]))
    np.testing.assert_allclose(results[0], results[1], rtol=1e-5, atol=1e-6)


TINY_CFG = tfm.TransformerConfig(vocab_size=17, d_model=8, n_heads=2, n_layers=1)


def _merge_stages(params):
    """(S, Lp, ...) stage-stacked leaves -> (1, S*Lp, ...) single-stage layout
    (the dense-oracle conversion, same as tests/test_transformer.py)."""
    out = {}
    for k, v in params.items():
        if k in tfm.NON_STACKED_LEAVES:
            out[k] = v
        else:
            out[k] = np.asarray(v).reshape((1, v.shape[0] * v.shape[1]) + v.shape[2:])
    return out


def _sharded_batch(rng, n, bsz=2, seq=8):
    return {
        "tokens": rng.integers(0, 17, size=(n, bsz, seq)).astype(np.int32),
        "targets": rng.integers(0, 17, size=(n, bsz, seq)).astype(np.int32),
    }


def test_sharded_engine_n128_zero_recompiles(rng):
    """128 logical workers over a 2-slot worker axis (k=64 vmapped per
    submesh): compiles once, loss finite, probe worker flags sized (n,)."""
    mesh = make_mesh(nb_workers=2)
    gar = gars.instantiate("hier:g=16,inner=median,outer=krum", 128, 4)
    eng = RobustEngine(mesh, gar, nb_workers=128, granularity="layer", sharding="sharded")
    assert eng.workers_per_device == 64
    tx = optax.sgd(0.05)
    state = eng.init_state(
        lambda k: tfm.init_params(TINY_CFG, k, n_stages=1),
        tfm.param_specs(TINY_CFG), tx)
    loss_fn = tfm.make_pipeline_loss(TINY_CFG, n_stages=1, microbatches=1)
    step = eng.build_step(loss_fn, tx, state)
    for _ in range(3):
        state, metrics = step(state, eng.shard_batch(_sharded_batch(rng, 128)))
    assert np.isfinite(float(jax.device_get(metrics["total_loss"])))
    assert np.asarray(jax.device_get(metrics["probe"]["worker_nan_rows"])).shape == (128,)
    assert step._cache_size() == 1, "large-n steady state must not retrace"


def test_sharded_engine_k_per_slot_matches_manual_sgd(rng):
    """n=4 logical workers on a 2-slot axis (k=2): one average step equals
    the dense per-worker-grads oracle — the vmapped fan-out is semantics-
    preserving, not just shape-compatible."""
    mesh = make_mesh(nb_workers=2)
    gar = gars.instantiate("average", 4, 0)
    eng = RobustEngine(mesh, gar, nb_workers=4, granularity="layer", sharding="sharded")
    tx = optax.sgd(0.1)
    state = eng.init_state(
        lambda k: tfm.init_params(TINY_CFG, k, n_stages=1),
        tfm.param_specs(TINY_CFG), tx)
    params0 = jax.device_get(state.params)
    batch = _sharded_batch(rng, 4)
    loss_fn = tfm.make_pipeline_loss(TINY_CFG, n_stages=1, microbatches=1)
    step = eng.build_step(loss_fn, tx, state)
    state, metrics = step(state, eng.shard_batch(batch))
    got = _merge_stages(jax.device_get(state.params))

    dense0 = _merge_stages(params0)
    grads = [
        jax.grad(lambda p, b: tfm.loss_dense(p, b, TINY_CFG))(
            dense0, jax.tree.map(lambda x: jnp.asarray(x[i]), batch))
        for i in range(4)
    ]
    mean = jax.tree.map(lambda *g: sum(np.asarray(x) for x in g) / 4, *grads)
    for key in dense0:
        want = np.asarray(dense0[key]) - 0.1 * np.asarray(mean[key])
        np.testing.assert_allclose(np.asarray(got[key]), want, rtol=5e-4,
                                   atol=1e-5, err_msg=key)
    # and the reported loss is the sum over all 4 logical workers
    per_worker = [float(tfm.loss_dense(dense0, jax.tree.map(
        lambda x: jnp.asarray(x[i]), batch), TINY_CFG)) for i in range(4)]
    np.testing.assert_allclose(
        float(jax.device_get(metrics["total_loss"])), np.sum(per_worker), rtol=1e-4)


def test_sharded_engine_rejects_indivisible_workers():
    mesh = make_mesh(nb_workers=2)
    gar = gars.instantiate("median", 3, 1)
    with pytest.raises(UserException):
        RobustEngine(mesh, gar, nb_workers=3, granularity="layer", sharding="sharded")


# --------------------------------------------------------------------------- #
# GAR probes (the gar_seconds_total measurement instrument)


def test_flat_engine_gar_probe_runs_and_is_deterministic():
    _, engine, _, _ = _flat_setup("hier:g=4,inner=median,outer=krum", 32, 2, 1)
    probe = engine.build_gar_probe(d=96)
    out1 = np.asarray(jax.block_until_ready(probe(3)))
    out2 = np.asarray(jax.block_until_ready(probe(3)))
    assert out1.shape[0] >= 96 and np.all(np.isfinite(out1))
    assert np.array_equal(out1, out2)


def test_sharded_engine_gar_probe_runs(rng):
    mesh = make_mesh(nb_workers=2)
    gar = gars.instantiate("krum", 8, 1)
    eng = RobustEngine(mesh, gar, nb_workers=8, granularity="layer", sharding="sharded")
    out = np.asarray(jax.block_until_ready(eng.build_gar_probe(d=64)(0)))
    assert out.shape == (64,) and np.all(np.isfinite(out))


# --------------------------------------------------------------------------- #
# Scaling sweep + schema contract


def _tiny_sweep():
    return scaling.run_sweep(
        (8, 16), d=128, f=1, reps=1,
        rules=[
            ("krum", "flat", None, lambda n: "krum"),
            ("hier-krum", "composite", "krum",
             lambda n: scaling.hier_spec(n, outer="krum", outer_rows=4)),
        ],
    )


def test_scaling_sweep_emits_valid_doc():
    doc = _tiny_sweep()
    scaling.validate_scaling_doc(doc)
    assert doc["schema"] == scaling.SCHEMA
    assert doc["ns"] == [8, 16]
    hier = [e for e in doc["rules"] if e["kind"] == "composite"][0]
    assert hier["flat_ref"] == "krum" and "speedup_at_nmax" in hier
    assert all(ms > 0 for e in doc["rules"] for ms in e["ms"])


def test_scaling_schema_validator_rejects_corruptions():
    doc = _tiny_sweep()
    bad = copy.deepcopy(doc)
    bad["schema"] = "aggregathor.gar.scaling.v0"
    with pytest.raises(AssertionError):
        scaling.validate_scaling_doc(bad)
    bad = copy.deepcopy(doc)
    bad["rules"][0]["ms"][0] = 0.0  # the unsynced-timer signature
    with pytest.raises(AssertionError):
        scaling.validate_scaling_doc(bad)
    bad = copy.deepcopy(doc)
    bad["rules"] = [e for e in bad["rules"] if e["kind"] == "flat"]
    with pytest.raises(AssertionError):
        scaling.validate_scaling_doc(bad)
    bad = copy.deepcopy(doc)
    bad["verdict"]["composite_sublinear_in_n2"] = (
        not bad["verdict"]["composite_sublinear_in_n2"])
    with pytest.raises(AssertionError):
        scaling.validate_scaling_doc(bad)


def test_hier_spec_generator_feasible_across_grid():
    for n in (8, 32, 128, 512):
        spec = scaling.hier_spec(n, outer="krum")
        gars.instantiate(spec, n, 1)  # must not raise
        spec = scaling.nested_spec(n, outer="krum")
        gars.instantiate(spec, n, 1)


# --------------------------------------------------------------------------- #
# Campaign at n >= 128 (the f-breakdown acceptance cell) — slow tier


@pytest.mark.slow
def test_campaign_n128_breakdown_under_hier():
    from aggregathor_tpu.chaos import campaign

    args = campaign.build_parser().parse_args([
        "--experiment", "mnist", "--experiment-args", "batch-size:8",
        "--nb-workers", "128", "--nb-decl-byz-workers", "4",
        "--nb-real-byz-workers", "4",
        "--gars", "hier:g=16,inner=median,outer=krum",
        "--attacks", "empire,epsilon=2.0",
        "--nb-steps", "20", "--breakdown",
    ])
    matrix = campaign.run_campaign(args)
    for cell in matrix["cells"]:
        assert cell["compile_count"] == 1, cell["gar"]
    (entry,) = matrix["breakdown"]
    assert entry["within_converged"] is True
    assert entry["beyond_converged"] is False
    assert entry["bound_holds"] is True
    assert entry["within_compile_count"] == 1
    assert entry["beyond_compile_count"] == 1
