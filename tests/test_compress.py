"""Compressed robust gradient exchange (parallel/compress.py, ISSUE 14).

Codec unit contracts (quantization error bounds, top-k selection, error
feedback telescoping, spec parsing), the wire_roundtrip dedup helper, the
codec-before-lossy ordering (a dropped packet of int8 payload is still a
NaN coordinate run), the fused-engine and bounded-wait integrations
(zero steady-state recompiles with compression + secure + momentum + EF
composed), EF state lifecycle (checkpoint -> restore -> rollback preserves
the residuals bit-exactly), the incremental as-rows-land aggregation
(numerics identical to the stacked barrier, overlap measured), the
graftcheck GC005 int8-wire probe, and the checked-in
``aggregathor.compress.sweep.v1`` document."""

import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks"))

from aggregathor_tpu import gars, models
from aggregathor_tpu.core import build_optimizer, build_schedule
from aggregathor_tpu.parallel import RobustEngine, compress, make_mesh
from aggregathor_tpu.parallel.bounded import BoundedWaitStep, HostStragglerModel
from aggregathor_tpu.parallel.compress import (
    Int8Codec,
    TopKCodec,
    parse_exchange_spec,
    wire_roundtrip,
)
from aggregathor_tpu.parallel.lossy import LossyLink
from aggregathor_tpu.utils import UserException
from conftest import build_engine_stack, assert_zero_recompiles

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --------------------------------------------------------------------- #
# spec parsing


def test_parse_exchange_specs():
    assert parse_exchange_spec(None) == (None, None)
    assert parse_exchange_spec("f32") == (None, None)
    assert parse_exchange_spec("float32") == (None, None)
    dt, codec = parse_exchange_spec("bf16")
    assert dt == jnp.bfloat16 and codec is None
    dt, codec = parse_exchange_spec("int8")
    assert dt is None and codec.name == "int8" and not codec.uses_ef
    _, codec = parse_exchange_spec("int8:ef")
    assert codec.uses_ef and codec.spec() == "int8:ef"
    _, codec = parse_exchange_spec("topk:k=64,ef")
    assert codec.k == 64 and codec.uses_ef
    _, codec = parse_exchange_spec("topk:frac=0.0625")
    assert codec._k_for(1024) == 64 and not codec.uses_ef
    # an already-constructed codec passes through (the benchmark surface)
    same = TopKCodec(k=4)
    assert parse_exchange_spec(same) == (None, same)


def test_parse_exchange_rejects():
    for bad in ("int4", "topk", "topk:k=4,frac=0.1", "topk:whatever=1",
                "int8:k=3", "bf16:ef", 17,
                # ef is a bare flag: an explicit value reads as intent to
                # disable — silently enabling would change the TrainState
                # layout behind the operator's back
                "int8:ef=0", "topk:k=4,ef=false"):
        with pytest.raises(UserException):
            parse_exchange_spec(bad)
    with pytest.raises(UserException):
        TopKCodec(k=0)
    with pytest.raises(UserException):
        TopKCodec(frac=1.5)
    with pytest.raises(UserException):
        TopKCodec(k=200).validate_d(100)  # budget beyond the model
    with pytest.raises(UserException, match="INFLATES"):
        # past d/2 the value+index payload EXCEEDS the raw f32 wire
        TopKCodec(frac=0.9).validate_d(1000)


# --------------------------------------------------------------------- #
# codec numerics


def test_int8_roundtrip_error_bound(rng):
    row = jnp.asarray(rng.normal(size=(513,)).astype(np.float32))
    image = Int8Codec().roundtrip(row)
    scale = float(jnp.max(jnp.abs(row))) / 127.0
    assert float(jnp.max(jnp.abs(image - row))) <= scale * 0.5 + 1e-7
    # zero rows encode to zero, not NaN (scale 0 guards the division)
    assert not np.asarray(Int8Codec().roundtrip(jnp.zeros((16,)))).any()


def test_int8_nonfinite_rows_become_nan_rows(rng):
    row = jnp.asarray(rng.normal(size=(64,)).astype(np.float32))
    for poison in (jnp.nan, jnp.inf):
        image = np.asarray(Int8Codec().roundtrip(row.at[3].set(poison)))
        # int8 has no inf: a non-encodable row is a NaN row on the wire,
        # absorbed by the NaN-tolerant rules inside the same f budget
        assert np.isnan(image).all()


def test_topk_keeps_largest_and_transmits_nan(rng):
    row = jnp.asarray(rng.normal(size=(101,)).astype(np.float32))
    image = np.asarray(TopKCodec(k=7).roundtrip(row))
    kept = np.flatnonzero(image)
    assert len(kept) == 7
    expected = np.argsort(-np.abs(np.asarray(row)))[:7]
    assert set(kept) == set(expected)
    # a NaN coordinate sorts as +inf magnitude: it CROSSES the wire (and
    # lands in the GAR's NaN accounting) instead of silently vanishing
    image = np.asarray(TopKCodec(k=7).roundtrip(row.at[5].set(jnp.nan)))
    assert np.isnan(image[5])


def test_error_feedback_telescopes(rng):
    """sum(decoded) + residual == sum(inputs): nothing the sparsifier
    drops is ever lost, only delayed — the convergence argument for EF."""
    codec = TopKCodec(k=8, ef=True)
    ef = jnp.zeros((257,))
    total_in = np.zeros((257,), np.float64)
    total_out = np.zeros((257,), np.float64)
    for _ in range(50):
        g = jnp.asarray(rng.normal(size=(257,)).astype(np.float32))
        decoded, ef = codec.ef_roundtrip(g, ef)
        total_in += np.asarray(g, np.float64)
        total_out += np.asarray(decoded, np.float64)
    residual = total_in - (total_out + np.asarray(ef, np.float64))
    assert np.abs(residual).max() < 1e-3


def test_wire_roundtrip_matches_legacy_dtype_cast(rng):
    """Satellite: the dedup helper owns the wire-dtype precision-loss
    semantics bit-exactly (the three engine call sites it replaced)."""
    rows = jnp.asarray(rng.normal(size=(6, 33)).astype(np.float32))
    legacy = rows.astype(jnp.bfloat16).astype(jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(wire_roundtrip(rows, dtype=jnp.bfloat16)),
        np.asarray(legacy))
    np.testing.assert_array_equal(
        np.asarray(wire_roundtrip(rows)), np.asarray(rows))
    image = wire_roundtrip(rows, codec=Int8Codec())
    np.testing.assert_array_equal(
        np.asarray(image), np.asarray(Int8Codec().roundtrip_rows(rows)))


def test_bytes_accounting():
    d = 8192
    assert compress.bytes_per_row(d) == 4 * d
    assert compress.bytes_per_row(d, dtype=jnp.bfloat16) == 2 * d
    assert compress.bytes_per_row(d, codec=Int8Codec()) == d + 4
    assert compress.bytes_per_row(d, codec=TopKCodec(k=64)) == 64 * 8
    assert compress.compression_ratio(d, codec=Int8Codec()) >= 3.5
    assert compress.compression_ratio(d, codec=TopKCodec(frac=0.0625)) == pytest.approx(8.0)
    assert compress.describe(codec=TopKCodec(k=4, ef=True)) == "topk:k=4,ef"
    assert compress.describe(dtype=jnp.bfloat16) == "bfloat16"
    assert compress.describe() == "float32"


# --------------------------------------------------------------------- #
# ordering vs the lossy link (satellite: mask DECODED rows)


def test_lossy_masks_decoded_rows_not_payload(rng):
    """Codec THEN lossy (the engine's order): NaN lands on exactly the
    dropped packet's coordinate run of the decoded image.  The inverse
    order — masking before int8 encode — poisons the WHOLE row, because
    the per-row scale reads the NaN (the bug the ordering rule exists
    to prevent; parallel/lossy.py module docstring)."""
    d, packet = 4000, 100  # 40 packets: a 0.5 drop rate leaves survivors
    link = LossyLink(1, ["drop-rate:1.0", "packet-coords:%d" % packet,
                         "min-coords:1"])
    row = jnp.asarray(rng.normal(size=(d,)).astype(np.float32))
    key = jax.random.PRNGKey(7)
    # the engine's order: encode/decode, then the transport drops packets
    image = Int8Codec().roundtrip(row)
    masked = np.asarray(link.apply(image, key, 0))
    assert np.isnan(masked).all()  # drop-rate 1: every packet lost
    partial = np.asarray(link.apply(
        image, key, 0, drop_rate=jnp.float32(0.5)))
    runs = np.isnan(partial).reshape(-1, packet)
    assert runs.all(axis=1).sum() + (~runs).all(axis=1).sum() == d // packet, \
        "NaN must cover whole packet runs of the DECODED row"
    assert 0 < runs.all(axis=1).sum() < d // packet
    # the WRONG order: a NaN-masked row cannot int8-encode (NaN scale)
    poisoned = np.asarray(Int8Codec().roundtrip(jnp.asarray(partial)))
    assert np.isnan(poisoned).all()


def test_engine_lossy_plus_codec_absorbed():
    """End to end: int8 wire + a lossy link on worker 0, NaN-tolerant
    rule — the packet runs land on decoded rows and the run stays
    finite (the in-engine twin of the ordering test above)."""
    exp, engine, tx, step, make_state = build_engine_stack(
        experiment="digits", experiment_args=("batch-size:8",),
        gar="average-nan", n=4, f=1, exchange="int8",
        lossy=(1, "drop-rate:0.4", "packet-coords:64", "min-coords:1"))
    state = make_state()
    it = exp.make_train_iterator(4, seed=3)
    losses = []
    for _ in range(4):
        state, m = step(state, engine.shard_batch(next(it)))
        losses.append(float(jax.device_get(m["total_loss"])))
    assert np.isfinite(losses).all()


# --------------------------------------------------------------------- #
# fused-engine integration


def test_fused_int8_ef_secure_momentum_zero_recompiles():
    """ACCEPTANCE: compression + error feedback + --secure digests +
    worker momentum composed on the fused flat engine — converging, and
    exactly ONE compile (scales, payloads, residuals are data, never
    shapes)."""
    exp, engine, tx, step, make_state = build_engine_stack(
        experiment="digits", experiment_args=("batch-size:8",), gar="krum",
        n=8, f=2, exchange="int8:ef", worker_momentum=0.9, secure=True)
    state = make_state()
    it = exp.make_train_iterator(8, seed=3)
    losses = []
    for _ in range(6):
        state, m = step(state, engine.shard_batch(next(it)))
        losses.append(float(jax.device_get(m["total_loss"])))
    assert_zero_recompiles(step)
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
    sec = jax.device_get(m["secure"])
    assert np.asarray(sec["digest_sent"]).shape == (8, 4)
    ef = np.asarray(jax.device_get(state.ef))
    assert ef.shape[0] == 8 and np.abs(ef).max() > 0


def test_fused_topk_ef_residual_moves_and_converges():
    exp, engine, tx, step, make_state = build_engine_stack(
        experiment="digits", experiment_args=("batch-size:8",),
        gar="average", n=4, f=0, exchange="topk:frac=0.05,ef")
    state = make_state()
    it = exp.make_train_iterator(4, seed=3)
    ef_norms, losses = [], []
    for _ in range(5):
        state, m = step(state, engine.shard_batch(next(it)))
        losses.append(float(jax.device_get(m["total_loss"])))
        ef_norms.append(float(np.abs(np.asarray(jax.device_get(state.ef))).sum()))
    assert_zero_recompiles(step)
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
    # the residual is alive: it accumulates what the sparsifier dropped
    # and changes as submissions drain it back out
    assert ef_norms[0] > 0 and len(set(ef_norms)) > 1


@pytest.mark.parametrize("spec,dtype", [("bf16", jnp.dtype(jnp.bfloat16)), ("f32", None)])
def test_a_wire_dtype_is_an_exchange_spec(spec, dtype):
    """``exchange="bf16"`` / ``"f32"`` are the one way to a wire dtype: no
    codec, the engine's ``exchange_dtype`` (None compiles no cast in), and
    under bf16 the rows cast down before the collective and up after it."""
    exp, engine, tx, step, make_state = build_engine_stack(
        experiment="digits", experiment_args=("batch-size:8",), gar="median", n=4, f=1,
        nb_devices=2, cache=False, exchange=spec)
    assert engine.codec is None and engine.exchange_dtype == dtype
    batch = engine.shard_batch(next(exp.make_train_iterator(4, seed=1)))
    text = str(jax.make_jaxpr(step)(make_state(), batch))
    casts = len(re.findall(r"convert_element_type\[\s*new_dtype=bfloat16", text))
    # bf16: the rows before the all_to_all and the aggregate before the all_gather, at least
    assert (casts == 0) if dtype is None else (casts >= 2)


def test_codec_feasibility_refusals():
    mesh = make_mesh(nb_workers=1)
    gar = gars.instantiate("krum", 8, 2)
    # sharded engine refuses the codec wire (bf16 dtype stays available)
    with pytest.raises(UserException, match="flat engine"):
        RobustEngine(mesh, gar, 8, sharding="sharded", exchange="int8")
    # the masked fixed-point path refuses loudly at construction — which
    # is also the guardian escalation REBUILD path (build_training
    # re-applies enable_masking, then re-constructs the engine)
    from aggregathor_tpu.secure import GroupMasking, enable_masking

    masked = gars.instantiate("bucketing:s=2,inner=krum", 8, 1)
    enable_masking(masked, GroupMasking.from_secret(b"s3"))
    with pytest.raises(UserException, match="mask"):
        RobustEngine(mesh, masked, 8, exchange="int8")
    # an infeasible top-k budget refuses once d is known (init_state)
    exp = models.instantiate("digits", ["batch-size:8"])
    tx = build_optimizer("sgd", build_schedule("fixed", ["initial-rate:0.05"]))
    engine = RobustEngine(mesh, gars.instantiate("average", 4, 0), 4,
                          exchange="topk:k=1000000")
    with pytest.raises(UserException, match="exceeds the model dimension"):
        engine.init_state(exp.init(jax.random.PRNGKey(0)), tx)


def test_ef_checkpoint_restore_rollback_bit_exact(tmp_path):
    """ACCEPTANCE (EF lifecycle): the residual survives the serialize ->
    restore -> rollback-restore chain bit-exactly, and a pre-EF snapshot
    restores into an EF engine with the zeroed buffer standing in."""
    from aggregathor_tpu.obs import Checkpoints

    exp, engine, tx, step, make_state = build_engine_stack(
        experiment="digits", experiment_args=("batch-size:8",),
        gar="average", n=4, f=0, exchange="int8:ef")
    state = make_state()
    it = exp.make_train_iterator(4, seed=3)
    for _ in range(3):
        state, _ = step(state, engine.shard_batch(next(it)))
    ef_live = np.asarray(jax.device_get(state.ef))
    assert np.abs(ef_live).max() > 0

    ck = Checkpoints(str(tmp_path), "model", 3)
    ck.save(jax.device_get(state), step=3)
    # restore path (cli/runner.py): fresh template, then put_state
    template = jax.device_get(make_state())
    restored, offstep = ck.restore(template)
    assert offstep == 3
    placed = engine.put_state(restored)
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(placed.ef)), ef_live)
    # rollback path (guardian do_rollback): ANOTHER fresh template reads
    # the same snapshot — the residual is state, not scratch
    rolled, _ = ck.restore(jax.device_get(make_state()))
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(engine.put_state(rolled).ef)), ef_live)
    # pre-EF snapshot (no 'ef' entry) restores into an EF target: the
    # zeroed buffer stands in, exactly a fresh codec's state
    legacy_dir = tmp_path / "legacy"
    ck2 = Checkpoints(str(legacy_dir), "model", 3)
    ck2.save(jax.device_get(state.replace(ef=None)), step=7)
    restored2, _ = ck2.restore(jax.device_get(make_state()))
    assert not np.asarray(restored2.ef).any()
    # training resumes from the restored residual at steady state
    state2 = placed
    state2, m = step(state2, engine.shard_batch(next(it)))
    assert np.isfinite(float(jax.device_get(m["total_loss"])))
    assert_zero_recompiles(step)


# --------------------------------------------------------------------- #
# bounded-wait + incremental


def _bounded_stack(gar_name="krum", n=8, f=2, exchange=None, stall=0.0,
                   rate=0.0, nb_eligible=0, deadline=0.25, **step_kw):
    engine_kw = {
        key: step_kw.pop(key)
        for key in ("worker_momentum", "secure") if key in step_kw
    }
    exp = models.instantiate("digits", ["batch-size:8"])
    gar = gars.instantiate(gar_name, n, f)
    tx = build_optimizer("sgd", build_schedule("fixed", ["initial-rate:0.05"]))
    engine = RobustEngine(make_mesh(nb_workers=1), gar, n,
                          exchange=exchange, **engine_kw)
    state = engine.init_state(exp.init(jax.random.PRNGKey(0)), tx, seed=1)
    model = None
    if stall > 0:
        model = HostStragglerModel(n, stall, rate=rate,
                                   nb_eligible=nb_eligible)
    step = BoundedWaitStep(engine, exp.loss, tx, jax.device_get(state.params),
                           deadline=deadline, straggler_model=model, **step_kw)
    return exp, engine, step, state


def test_bounded_incremental_matches_stacked_bitwise():
    """Incremental folds are the same decoder on the same rows: the two
    modes must agree numerically (calm round, every submission arrives)."""
    results = {}
    for incremental in (False, True):
        exp, engine, step, state = _bounded_stack(
            exchange="int8", incremental=incremental)
        it = exp.make_train_iterator(8, seed=3)
        losses = []
        try:
            for _ in range(4):
                state, m = step(state, next(it))
                losses.append(float(jax.device_get(m["total_loss"])))
            assert_zero_recompiles(step)
        finally:
            step.close()
        results[incremental] = losses
    np.testing.assert_allclose(results[False], results[True], rtol=1e-6)


def test_bounded_compress_all_features_zero_recompiles():
    """ACCEPTANCE: int8 + error feedback + --secure + worker momentum +
    stale infill + INCREMENTAL folding under real stragglers — still one
    compile per bounded executable, finite losses, overlap measured."""
    from aggregathor_tpu.obs.metrics import MetricsRegistry

    reg = MetricsRegistry()
    exp, engine, step, state = _bounded_stack(
        exchange="int8:ef", worker_momentum=0.9, secure=True,
        stall=0.6, rate=1.0, nb_eligible=2,
        stale_infill=True, stale_max_age=3, incremental=True, registry=reg)
    it = exp.make_train_iterator(8, seed=3)
    losses = []
    try:
        for _ in range(6):
            state, m = step(state, next(it))
            losses.append(float(jax.device_get(m["total_loss"])))
        assert_zero_recompiles(step)
        assert np.isfinite(losses).all()
        assert step.timeouts_total.sum() > 0
        assert step.overlapped_folds_total > 0
        sec = jax.device_get(m["secure"])
        assert np.asarray(sec["digest_sent"]).shape == (8, 4)
    finally:
        step.close()
    prom = reg.render_prometheus()
    assert "exchange_overlap_fraction" in prom
    assert "exchange_folds_total" in prom


def test_bounded_ef_frozen_for_timed_out_worker():
    """A timed-out worker's submission never shipped, so its residual
    never updated (the momentum write-back convention)."""
    exp, engine, step, state = _bounded_stack(
        exchange="topk:frac=0.05,ef", stall=1.0, rate=1.0, nb_eligible=1,
        deadline=0.2)
    it = exp.make_train_iterator(8, seed=3)
    try:
        # round 0 is the compile round (no deadline): EVERY worker's
        # residual updates once — capture it, then let the warm rounds
        # time worker 0 out
        state, _ = step(state, next(it))
        ef_warmup = np.asarray(jax.device_get(state.ef))
        for _ in range(3):
            state, m = step(state, next(it))
        assert step.timeouts_total[0] >= 2  # worker 0 persistently late
    finally:
        step.close()
    ef = np.asarray(jax.device_get(state.ef))
    np.testing.assert_array_equal(
        ef[0], ef_warmup[0],
        "timed-out worker's EF must stay frozen at its last-arrived value")
    assert np.abs(ef[1:] - ef_warmup[1:]).max() > 0


def test_incremental_refuses_grouped_mode():
    exp = models.instantiate("digits", ["batch-size:8"])
    gar = gars.instantiate("krum", 8, 2)
    tx = build_optimizer("sgd", build_schedule("fixed", ["initial-rate:0.05"]))
    engine = RobustEngine(make_mesh(nb_workers=1), gar, 8,
                          sharding="sharded", granularity="global")
    state = engine.init_state(
        exp.init, jax.tree.map(
            lambda _: jax.sharding.PartitionSpec(),
            exp.init(jax.random.PRNGKey(0))), tx)
    with pytest.raises(UserException, match="per-WORKER"):
        BoundedWaitStep(engine, exp.loss, tx, jax.device_get(state.params),
                        deadline=0.2, incremental=True)


# --------------------------------------------------------------------- #
# graftcheck GC005: the int8-wire contract probe


def test_gc005_trips_on_quantization_fragile_rule():
    """A rule that is finite on fresh gaussian rows but breaks on
    int8-roundtripped ones (quantization creates EXACT zeros) must be a
    GC005 finding — registration enters the sweep, so a silently fragile
    rule is a graftcheck failure, not a surprise at the first compressed
    run."""
    from aggregathor_tpu.analysis import gar_contract

    class _QuantFragileGAR(gars.GAR):
        coordinate_wise = True

        def aggregate_block(self, block, dist2=None):
            mean = jnp.mean(block, axis=0)
            # gaussian floats are never exactly 0; int8-quantized small
            # coordinates are — the seeded "breaks under the wire" rule
            return jnp.where(jnp.any(block == 0.0), jnp.nan, mean)

    name = "quant-fragile-gar-fixture"
    gars.gars._register[name] = _QuantFragileGAR
    try:
        findings = gar_contract.check_spec(name)
    finally:
        del gars.gars._register[name]
    codes = [f.code for f in findings]
    assert codes == ["GC005"], findings
    assert "int8" in findings[0].message


def test_gc005_clean_on_core_rules():
    from aggregathor_tpu.analysis import gar_contract

    for spec in ("krum", "average", "median"):
        findings = gar_contract.check_spec(spec)
        assert not findings, (spec, findings)


# --------------------------------------------------------------------- #
# the sweep schema + the checked-in document


def test_compress_sweep_checked_in_document():
    import compress_sweep

    doc = compress_sweep.load(os.path.join(REPO, "COMPRESS_r14.json"))
    assert doc["verdict"]["int8_ratio_ok"]
    assert doc["verdict"]["int8_equal_loss"]
    assert doc["verdict"]["overlap_nonzero"]
    assert doc["incremental"]["overlap_fraction"] > 0
    # the research answer is recorded per bit-width, whatever it reads
    assert set(doc["verdict"]["breakdown_by_exchange"]) >= {"f32", "int8"}
    int8_cells = [c for c in doc["cells"] if c["exchange"] == "int8"]
    assert int8_cells and all(c["compression_ratio"] >= 3.5 for c in int8_cells)


def test_compress_sweep_validator_rejects():
    import compress_sweep

    doc = compress_sweep.load(os.path.join(REPO, "COMPRESS_r14.json"))
    bad = dict(doc)
    bad["schema"] = "aggregathor.other.v1"
    with pytest.raises(ValueError):
        compress_sweep.validate(bad)
    bad = json.loads(json.dumps(doc))
    bad["cells"][0]["exchange"] = "int4"
    with pytest.raises(ValueError):
        compress_sweep.validate(bad)
    bad = json.loads(json.dumps(doc))
    del bad["verdict"]["pass"]
    with pytest.raises(ValueError):
        compress_sweep.validate(bad)
