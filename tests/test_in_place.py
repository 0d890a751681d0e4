"""The flat step's in-place path (parallel/in_place.py): a coordinate-wise
rule on one device, with nothing around it that needs an (n, d) row, reduces
each gradient leaf where it lies — against the rows path on the same arguments
(through ``forced_rows``, the one seam), the plane kernels' leaf entry against
their 2-D entry, who takes which path, and the rows path's program held to what
the parent traced."""

import contextlib
import hashlib
import inspect
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from aggregathor_tpu import gars
from aggregathor_tpu.chaos import ChaosSchedule
from aggregathor_tpu.gars.common import forced_tier, leaf_tier
from aggregathor_tpu.ops import pallas_kernels as pk
from aggregathor_tpu.parallel import RobustEngine, attacks, in_place, lossy, make_mesh

N, F = 3, 1


def one_device_mesh():
    return make_mesh(nb_workers=1, devices=jax.devices()[:1])


def init_params(key):
    """A 4-D leaf of whole tiles, a leaf whose lanes are not whole (200), a
    1-D leaf and a scalar."""
    keys = jax.random.split(key, 4)
    return {"experts": jax.random.normal(keys[0], (2, 2, 16, 256)),
            "head": jax.random.normal(keys[1], (10, 200)),
            "bias": jax.random.normal(keys[2], (7,)),
            "scale": jax.random.normal(keys[3], ())}


def linear_loss(params, batch):
    """A loss whose gradient is the worker's batch, leaf for leaf: what the
    rule sees is what the test planted."""
    return sum(jnp.sum(params[name] * batch[name]) for name in params)


def planted_batch(n, planted, seed=2):
    """Small whole numbers (every sum of them is exact in float32) and, if
    ``planted``, NaN, +inf, -inf and tied values in kernel blocks, ragged lanes
    and the small leaves."""
    rng = np.random.default_rng(seed)
    batch = {name: np.round(4 * rng.standard_normal((n,) + leaf.shape)).astype(np.float32)
             for name, leaf in init_params(jax.random.PRNGKey(0)).items()}
    if planted:
        batch["experts"][0, 0, 0, 0, :7] = np.nan
        batch["experts"][1, 1, 1, 3, 5:9] = np.inf
        batch["experts"][2, 0, 1, 3, 7:12] = -np.inf
        batch["experts"][:, 1, 0, 5, :] = 3.0
        batch["head"][1, 9, 190:] = np.nan
        batch["head"][:, 3, :] = 2.0
        batch["bias"][2, 3] = np.inf
    return {name: jnp.asarray(value) for name, value in batch.items()}


def one_step(rule, rows, planted):
    """(parameters, metrics) after one step of ``rule`` on one device, on the
    in-place path or, through the seam, on the rows path; kernels interpreted."""
    with in_place.forced_rows() if rows else contextlib.nullcontext():
        engine = RobustEngine(one_device_mesh(), gars.instantiate(rule, N, F), nb_workers=N)
    assert engine.gradient_path == ("rows" if rows else "in place")
    tx = optax.sgd(0.5)  # a power of two: the update is exact, fused or not
    state = engine.init_state(init_params(jax.random.PRNGKey(0)), tx, seed=1)
    with forced_tier("pallas"):
        state, metrics = engine.build_step(linear_loss, tx)(
            state, engine.shard_batch(planted_batch(N, planted)))
    return jax.tree.map(np.asarray, (state.params, metrics))


@pytest.mark.parametrize("planted", [False, True], ids=["clean", "planted"])
@pytest.mark.parametrize("rule", ["median", "averaged-median", "trimmed-mean", "average"])
def test_a_step_in_place_is_the_step_on_rows(rule, planted):
    """Parameters, ``grad_norm``, ``total_loss`` and the probe's fields after
    one step: to the bit for the three rank rules (the same ``rule`` closure on
    the same n values of every coordinate), to float tolerance for the mean
    (``jnp.mean`` of a leaf and of a row need not add in one order)."""
    (params, metrics), (row_params, row_metrics) = (
        one_step(rule, rows, planted) for rows in (False, True))
    assert jax.tree.structure(metrics) == jax.tree.structure(row_metrics)
    if planted and rule == "average":  # what was planted reaches the optimizer
        assert np.isnan(params["experts"]).any() and np.isinf(params["bias"]).any()
    compare = (np.testing.assert_array_equal if rule != "average"
               else lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6))
    for ours, theirs in zip(jax.tree.leaves((params, metrics)),
                            jax.tree.leaves((row_params, row_metrics))):
        compare(ours, theirs)
    np.testing.assert_array_equal(metrics["probe"]["worker_nan_rows"],
                                  [planted, planted, planted])


def test_the_metrics_without_a_probe_and_with_counters():
    """The metrics dict is the rows path's: no ``probe`` without the health
    probe, and a model's counters ride as they did."""
    def loss(params, batch):
        return linear_loss(params, batch), {"seen": jnp.sum(batch["bias"])}
    loss.has_aux = True
    results = []
    for rows in (False, True):
        with in_place.forced_rows() if rows else contextlib.nullcontext():
            engine = RobustEngine(one_device_mesh(), gars.instantiate("median", N, F),
                                  nb_workers=N, health_probe=False)
        tx = optax.sgd(0.5)
        state = engine.init_state(init_params(jax.random.PRNGKey(0)), tx, seed=1)
        _, metrics = engine.build_step(loss, tx)(
            state, engine.shard_batch(planted_batch(N, False)))
        results.append(jax.tree.map(np.asarray, metrics))
    assert sorted(results[0]) == sorted(results[1]) == ["grad_norm", "model_counters", "total_loss"]
    for ours, theirs in zip(jax.tree.leaves(results[0]), jax.tree.leaves(results[1])):
        np.testing.assert_array_equal(ours, theirs)


def test_a_narrow_leaf_is_reduced_in_float32_and_cast_back():
    """The rule computes in float32 whatever the leaf's dtype, and the result
    is cast as ``FlatMap.inflate`` casts it."""
    def run(rows):
        with in_place.forced_rows() if rows else contextlib.nullcontext():
            engine = RobustEngine(one_device_mesh(), gars.instantiate("averaged-median", N, F),
                                  nb_workers=N)
        params = {"w": jnp.ones((4, 8), jnp.bfloat16), "b": jnp.ones((3,), jnp.float32)}
        batch = {"w": jnp.asarray(np.arange(N * 32).reshape(N, 4, 8) / 7, jnp.bfloat16),
                 "b": jnp.asarray(np.arange(N * 3).reshape(N, 3) / 3, jnp.float32)}
        tx = optax.sgd(0.5)
        state = engine.init_state(params, tx, seed=1)
        state, _ = engine.build_step(
            lambda p, b: jnp.sum(p["w"] * b["w"]).astype(jnp.float32) + jnp.sum(p["b"] * b["b"]),
            tx)(state, engine.shard_batch(batch))
        return jax.tree.map(np.asarray, state.params)

    ours, theirs = run(False), run(True)
    assert ours["w"].dtype == theirs["w"].dtype == jnp.bfloat16
    for name in ours:
        np.testing.assert_array_equal(ours[name], theirs[name])


# --------------------------------------------------------------------------- #
#  The plane kernels' leaf entry against their 2-D entry                      #
# --------------------------------------------------------------------------- #

def poisoned(n, shape, seed):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n,) + shape).astype(np.float32)
    x[0, ..., 5] = np.nan
    x[1, ..., 7] = np.inf
    x[n - 1, ..., 9] = -np.inf
    x[:, ..., 11] = 1.0  # ties: the lower index wins
    x[n // 2, ..., 1, :] = np.nan  # a worker's whole row of a tile
    return jnp.asarray(x)


#: n -> how many of the n a rule declares Byzantine (the averaged median keeps
#: n - f, the trimmed mean n - 2f: two values at n = 3 and 4, whose sum has one order)
DECLARED = {3: 1, 4: 1, 16: 4, 32: 8}

LEAF_ENTRIES = {
    "median": (lambda x, n: pk.coordinate_median_leaf(x),
               lambda x, n: pk.coordinate_median(x)),
    "averaged-median": (
        lambda x, n: pk.coordinate_averaged_median_leaf(x, beta=max(2, n - 2 * DECLARED[n])),
        lambda x, n: pk.coordinate_averaged_median(x, max(2, n - 2 * DECLARED[n]))),
    "trimmed-mean": (
        lambda x, n: pk.coordinate_trimmed_mean_leaf(x, trim=DECLARED[n], keep=n - 2 * DECLARED[n]),
        lambda x, n: pk.coordinate_trimmed_mean(x, DECLARED[n], n - 2 * DECLARED[n])),
}


@pytest.mark.parametrize("n,shape", [
    (n, shape) for n in (3, 4, 16, 32) for shape in ((2, 16, 256), (19, 300), (2, 128, 200))
    if n < 32 or shape == (19, 300)],  # 496 unrolled pairs take the interpreter 4-10 s a case
    ids=lambda value: "x".join(map(str, value)) if isinstance(value, tuple) else str(value))
@pytest.mark.parametrize("rule", sorted(LEAF_ENTRIES))
def test_the_leaf_entry_is_the_2d_entry_on_the_flattened_leaf(rule, n, shape):
    """Same selections, same ties, same non-finite handling, on a leaf of whole
    tiles, on one ragged in rows (19) and in lanes (300) and on one whose rows
    are whole lanes where its lanes are not (handed over swapped): the median to the
    bit at every n; the two means to the bit where they add at most two values
    (n = 3, 4) and to an ulp where they add more (the kernel adds plane by
    plane, the few columns past a block as a slab: which columns those are
    differs), with the non-finite results in the same places."""
    leaf_entry, flat_entry = LEAF_ENTRIES[rule]
    x = poisoned(n, shape, seed=n)
    ours = np.asarray(leaf_entry(x, n))
    theirs = np.asarray(flat_entry(x.reshape(n, -1), n)).reshape(shape)
    assert ours.shape == shape and ours.dtype == np.float32
    if rule == "median" or n <= 4:
        np.testing.assert_array_equal(ours, theirs)
    else:
        np.testing.assert_array_equal(np.isfinite(ours), np.isfinite(theirs))
        np.testing.assert_array_equal(np.isnan(ours), np.isnan(theirs))
        finite = np.isfinite(ours)
        np.testing.assert_allclose(ours[finite], theirs[finite], rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("n,rows,lanes,expected", [
    (4, 768, 2048, (64, 2048)), (4, 2048, 768, (128, 768)),      # the held experts
    (4, 18992, 2048, (16, 2048)), (4, 2048, 18992, (16, 2048)),  # SDAR's and Keye's embedding, head
    (3, 16032, 2048, (48, 2048)), (3, 2048, 16032, (48, 2048)),  # Kanana's (the head swapped)
    (3, 2056, 18992, (8, 18944)),                                 # neither whole: as it lies
    (3, 2048, 576, (72, 2048)),                                   # wkv_a, swapped: 576 rows of 2,048
    (32, 64, 2048, (8, 2048)), (3, 2056, 256, (8, 256)),
    (3, 7, 128, None), (3, 8, 127, None), (33, 8, 128, None),
])
def test_leaf_blocks(n, rows, lanes, expected):
    """A block divides the whole tiles of the leaf (none reaches past an edge),
    holds its n planes in ``PLANE_BLOCK_BYTES``, and is the widest of those,
    then the tallest."""
    assert pk.leaf_blocks(jax.ShapeDtypeStruct((n, 2, rows, lanes), jnp.float32)) == expected
    assert pk.leaf_blocks(jax.ShapeDtypeStruct((n, rows * lanes), jnp.float32)) is None
    if expected is not None:
        ta, tb = expected
        if pk._lanes_from_rows(rows, lanes):
            rows, lanes = lanes, rows
        assert ta % 8 == 0 and tb % pk.LANE == 0
        assert (rows // 8 * 8) % ta == 0 and (lanes // pk.LANE * pk.LANE) % tb == 0
        assert 4 * n * ta * tb <= pk.PLANE_BLOCK_BYTES


def test_the_leaf_entry_traces_once_a_shape_and_keeps_the_kernel_s_name():
    """Equal shapes share one trace of the wrapper (a step of 38 leaves in 17
    shapes traces 17), and a device trace shows the rule under the 2-D entry's
    name with the leaf's shape."""
    x = jnp.zeros((3, 2, 8, 128))
    jaxpr = jax.make_jaxpr(lambda a, b: (pk.coordinate_median_leaf(a), pk.coordinate_median_leaf(b)))(x, x)
    calls = [eqn for eqn in jaxpr.jaxpr.eqns if eqn.primitive.name in ("pjit", "jit")]
    assert len(calls) == 2 and calls[0].params["jaxpr"] is calls[1].params["jaxpr"]
    text = str(jaxpr)
    assert "coordinate_median_planes" in text and "f32[3,2,8,128]" in text


def test_leaf_tier():
    """Off a TPU no leaf goes to the kernel; inside ``forced_tier("pallas")``
    every leaf whose last two dimensions hold a whole tile does, at any size;
    a vmapped leaf never."""
    big, thin, flat = jnp.zeros((3, 4, 64, 256)), jnp.zeros((3, 2040, 64)), jnp.zeros((3, 4096))
    median, average = gars.instantiate("median", 3, 1), gars.instantiate("average", 3, 0)
    assert [leaf_tier(median, leaf) for leaf in (big, thin, flat)] == ["jnp"] * 3
    with forced_tier("pallas"):
        assert [leaf_tier(median, leaf) for leaf in (big, thin, flat)] == ["kernel", "jnp", "jnp"]
        assert leaf_tier(median, jnp.zeros((3, 2048, 64))) == "kernel"  # 64 rows of 2,048 lanes, swapped
        assert leaf_tier(median, jnp.zeros((33, 8, 128))) == "jnp"
        assert leaf_tier(average, big) == "jnp"  # a rule with no leaf kernel
        jax.vmap(lambda leaf: np.testing.assert_equal(leaf_tier(median, leaf), "jnp"))(big[None])
    with forced_tier("jnp"):
        assert leaf_tier(median, big) == "jnp"


# --------------------------------------------------------------------------- #
#  Who takes which path                                                       #
# --------------------------------------------------------------------------- #

#: name -> (rule, n, f, engine arguments, what ``rows_reason`` must name)
ROWS_CASES = {
    "attack": ("median", 4, 1, lambda: dict(
        nb_real_byz=1, attack=attacks.instantiate("signflip", 4, 1)), "attack"),
    "lossy_link": ("median", 4, 1, lambda: dict(
        lossy_link=lossy.LossyLink(4, ["drop-rate:0.2", "packet-coords:16"])), "lossy_link"),
    "chaos": ("median", 4, 1, lambda: dict(chaos=ChaosSchedule("0:calm 5:drop=0.5", 4)), "chaos"),
    "codec": ("median", 4, 1, lambda: dict(exchange="int8"), "exchange"),
    "exchange_dtype": ("median", 4, 1, lambda: dict(exchange="bf16"), "exchange"),  # no codec
    "momentum": ("median", 4, 1, lambda: dict(worker_momentum=0.9), "worker_momentum"),
    "carry": ("median", 4, 1, lambda: dict(
        lossy_link=lossy.LossyLink(4, ["drop-rate:0.2", "packet-coords:16", "clever:true"])),
        "carries_gradients"),
    "error_feedback": ("median", 4, 1, lambda: dict(exchange="int8:ef"), "carries_ef"),
    "secure": ("median", 4, 1, lambda: dict(secure=True), "secure"),
    "worker_metrics": ("median", 4, 1, lambda: dict(worker_metrics=True), "worker_metrics"),
    "reputation": ("median", 4, 1, lambda: dict(reputation_decay=0.9), "reputation_decay"),
    "quarantine": ("average-nan", 4, 1, lambda: dict(
        reputation_decay=0.9, quarantine_threshold=0.5), "quarantine_threshold"),
    "granularity_leaf": ("median", 4, 1, lambda: dict(granularity="leaf"), "granularity:leaf"),
    "two_devices": ("median", 4, 1, lambda: dict(devices=2), "2 devices on the worker axis"),
    "krum": ("krum", 8, 2, dict, "the rule KrumGAR"),
    "bulyan": ("bulyan", 11, 2, dict, "the rule BulyanGAR"),
    "geometric-median": ("geometric-median", 4, 1, dict, "the rule"),
    "bucketing": ("bucketing:s=2,inner=median", 4, 1, dict, "the rule BucketingGAR"),
    "forced_rows": ("median", 4, 1, dict, "forced_rows"),
}
IN_PLACE_CASES = {
    "median": ("median", 4, 1, dict),
    "averaged-median": ("averaged-median", 3, 1, dict),
    "trimmed-mean": ("trimmed-mean", 4, 1, dict),
    "average": ("average", 32, 0, dict),
    "average-nan": ("average-nan", 4, 1, dict),
    "median-pallas": ("median-pallas", 4, 1, dict),
    "augmented": ("median", 4, 1, lambda: dict(batch_transform=lambda batch, key: batch)),
    "no_probe": ("median", 4, 1, lambda: dict(health_probe=False)),
}


def build_engine(rule, n, f, arguments):
    arguments = dict(arguments())
    devices = arguments.pop("devices", 1)
    return RobustEngine(make_mesh(nb_workers=devices, devices=jax.devices()[:devices]),
                        gars.instantiate(rule, n, f), nb_workers=n, **arguments)


@pytest.mark.parametrize("case", sorted(ROWS_CASES) + sorted(IN_PLACE_CASES))
def test_who_takes_which_path(case):
    """Each thing that needs a row, alone, sends the engine to the rows path
    and is named as the reason; a coordinate-wise rule on one device with none
    of them reduces in place.  Decided when the engine is built, and read-only."""
    if case in IN_PLACE_CASES:
        engine = build_engine(*IN_PLACE_CASES[case])
        assert (engine.gradient_path, in_place.rows_reason(engine)) == ("in place", None)
    else:
        rule, n, f, arguments, reason = ROWS_CASES[case]
        with in_place.forced_rows() if case == "forced_rows" else contextlib.nullcontext():
            engine = build_engine(rule, n, f, arguments)
        assert engine.gradient_path == "rows" and reason in engine._rows_reason
    with pytest.raises(AttributeError):
        engine.gradient_path = "in place"


#: Every argument of ``RobustEngine.__init__``, by what the in-place body does
#: about it.  ``rows``: it reads or writes a (k, d) row (or decides who holds
#: one), ``rows_reason`` names it and a case of ``ROWS_CASES`` sets it alone.
#: ``in place``: the in-place body honours it as the rows path does, or it
#: cannot be set on an engine that reduces in place.
ARGUMENTS = {
    "mesh": ("rows", ["two_devices"]),
    "gar": ("rows", ["krum", "bulyan", "geometric-median", "bucketing"]),
    "attack": ("rows", ["attack"]),
    "lossy_link": ("rows", ["lossy_link", "carry"]),
    "exchange": ("rows", ["codec", "error_feedback", "exchange_dtype"]),
    "worker_momentum": ("rows", ["momentum"]),
    "worker_metrics": ("rows", ["worker_metrics"]),
    "reputation_decay": ("rows", ["reputation"]),
    "quarantine_threshold": ("rows", ["quarantine"]),
    "granularity": ("rows", ["granularity_leaf"]),
    "chaos": ("rows", ["chaos"]),
    "secure": ("rows", ["secure"]),
    "sharding": ("rows", []),  # test_the_sharded_engine_keeps_its_own_dataflow
    "nb_workers": ("in place", "the leading axis of every leaf"),
    "nb_real_byz": ("in place", "read by an attack only, and an attack needs the rows"),
    "batch_transform": ("in place", "applied a worker under the rows path's keys (IN_PLACE_CASES)"),
    "health_probe": ("in place", "worker_nan read off the leaves (IN_PLACE_CASES, the parity tests)"),
    "flight": ("in place", "written by the shared _finalize_step"),
    "l1_regularize": ("in place", "refused by a flat engine"),
    "l2_regularize": ("in place", "refused by a flat engine"),
}


def test_every_argument_of_the_engine_has_chosen_its_path():
    """A new argument of ``RobustEngine.__init__`` that reads or writes a row
    would be silently ignored by the in-place body (``make_body`` never looks
    at it) unless ``rows_reason`` names it: this fails until its author files
    it in ``ARGUMENTS`` — as ``rows``, with a reason in
    ``in_place.rows_reason`` and a case in ``ROWS_CASES``, or as ``in place``,
    with what the in-place body does about it."""
    import inspect

    parameters = set(inspect.signature(RobustEngine.__init__).parameters) - {"self"}
    assert parameters == set(ARGUMENTS), (
        "unfiled: %s; gone: %s" % (sorted(parameters - set(ARGUMENTS)), sorted(set(ARGUMENTS) - parameters)))
    for name, (path, cases) in ARGUMENTS.items():
        assert path in ("rows", "in place"), name
        if path == "rows":
            assert set(cases) <= set(ROWS_CASES), name
        else:
            assert cases, name
    # and every case of the matrix belongs to an argument (the seam aside)
    filed = {case for path, cases in ARGUMENTS.values() if path == "rows" for case in cases}
    assert filed == set(ROWS_CASES) - {"forced_rows"}


def test_the_sharded_engine_keeps_its_own_dataflow():
    mesh = make_mesh(nb_workers=2, model_parallelism=2, devices=jax.devices()[:4])
    engine = RobustEngine(mesh, gars.instantiate("median", 2, 0), granularity="layer")
    assert engine.gradient_path == "rows" and "sharding:sharded" in engine._rows_reason


def test_the_seam_ends_with_its_block():
    with in_place.forced_rows():
        pass
    assert build_engine("median", 4, 1, dict).gradient_path == "in place"


def test_the_decision_is_logged_once_a_build(capsys):
    """``step reduces gradients in place: ... leaves, ... by kernel (...), ...
    as jnp (...)`` once a build, however often the body is traced; the rows
    path names what needs the rows."""
    engine = build_engine("median", N, F, dict)
    tx = optax.sgd(0.5)
    state = engine.init_state(init_params(jax.random.PRNGKey(0)), tx, seed=1)
    with forced_tier("pallas"):
        multi = engine.build_multi_step(linear_loss, tx, repeat_steps=2)
        multi(state, engine.shard_batch(planted_batch(N, False)))
    out = capsys.readouterr().out
    assert out.count("step reduces gradients in place") == 1
    assert ("step reduces gradients in place: 4 leaves, 2 by kernel (18,384 elements), "
            "2 as jnp (8)") in out
    engine = build_engine("median", 4, 1, ROWS_CASES["attack"][3])
    engine.build_step(linear_loss, tx)
    assert ("step lays gradients out as (n, d) rows: needed by attack"
            in capsys.readouterr().out)


def test_the_in_place_step_holds_no_row_and_names_its_phases():
    """No ``concatenate`` and no ``dynamic_slice`` of a (d,) vector in the
    in-place step; its phases are the rows path's names, ``flatten`` left
    empty (``PHASES`` and ``PHASES_REVISION`` stay)."""
    from aggregathor_tpu.parallel import engine as engine_module

    engine = build_engine("average", N, 0, dict)
    tx = optax.sgd(0.5)
    state = engine.init_state(init_params(jax.random.PRNGKey(0)), tx, seed=1)
    step = engine.build_step(linear_loss, tx)
    text = str(jax.make_jaxpr(step)(state, engine.shard_batch(planted_batch(N, False))))
    assert "concatenate" not in text and "dynamic_slice" not in text
    placed = re.findall(r'with phase\("(\w+)"\)', inspect.getsource(in_place))
    assert placed == ["augment", "grad", "gar", "apply", "epilogue"]
    assert set(placed) <= set(engine_module.PHASES) and engine_module.PHASES_REVISION == 1


# --------------------------------------------------------------------------- #
#  The rows path is the parent's program                                      #
# --------------------------------------------------------------------------- #

#: sha256 (first 16 hex digits) of the jaxpr of one flat step on the small
#: model above, read at the parent of the PR that added the in-place path
#: (PR 47), kernels interpreted: nothing that keeps its rows may trace anything
#: else.
PARENT_STEP_JAXPRS = {
    "krum": "4196e10fb479da05",
    "bulyan": "e75047c268e52745",
    "median_under_attack": "2bdd58734d1e6706",
}


def rows_step_jaxpr(case):
    rule, n, f, arguments = {
        "krum": ("krum", 8, 2, dict),
        "bulyan": ("bulyan", 11, 2, dict),
        "median_under_attack": ("median", 4, 1, lambda: dict(
            nb_real_byz=1, attack=attacks.instantiate("signflip", 4, 1))),
    }[case]
    engine = build_engine(rule, n, f, arguments)
    tx = optax.sgd(0.5)
    state = engine.init_state(init_params(jax.random.PRNGKey(0)), tx, seed=1)
    with forced_tier("pallas"):
        text = str(jax.make_jaxpr(engine.build_step(linear_loss, tx))(
            state, engine.shard_batch(planted_batch(n, True))))
    # a frozenset prints in the order of its hashes, which differs a process
    return re.sub(r"frozenset\(\{([^}]*)\}\)",
                  lambda m: "frozenset({%s})" % ", ".join(sorted(m.group(1).split(", "))), text)


@pytest.mark.parametrize("case", sorted(PARENT_STEP_JAXPRS))
def test_the_rows_path_traces_the_program_it_traced(case):
    """Krum, Bulyan and the median under an attack keep their rows, and their
    step is to the byte the jaxpr the parent traced on the same arguments (the
    text carries no file and no line; the kernels' bodies are in it)."""
    text = rows_step_jaxpr(case)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == PARENT_STEP_JAXPRS[case]
