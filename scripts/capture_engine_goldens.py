"""Capture fixed-seed golden outputs of the training engines.

A regression pin ON ONE INSTALLATION: the digests are exact float bits, so
they hold only under the JAX that captured them (stamped into the file as
``jax_version``).  Re-run after an intended numerics change or a JAX
upgrade; tests/test_engine.py::test_unified_engine_bit_identical_to_*
assert the engine still reproduces them bit-exactly.

Writes tests/data/golden_engine.json: per-step losses/grad norms as float
hex strings (lossless) and a SHA-256 over the final parameter bytes.
"""

import hashlib
import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np
import optax

from aggregathor_tpu import gars, models
from aggregathor_tpu.core import build_optimizer, build_schedule
from aggregathor_tpu.parallel import RobustEngine, attacks, make_mesh


def param_digest(state):
    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(jax.device_get(state.params)):
        h.update(np.ascontiguousarray(leaf).tobytes())
    return h.hexdigest()


def run_flat(granularity, secure=False, momentum=None, attack_name=None,
             worker_metrics=False, reputation_decay=None, nb_devices=2):
    n, f, r = 6, 1, (1 if attack_name else 0)
    exp = models.instantiate("digits", ["batch-size:8"])
    gar = gars.instantiate("krum", n, f)
    tx = build_optimizer("sgd", build_schedule("fixed", ["initial-rate:0.05"]))
    attack = attacks.instantiate(attack_name, n, r) if attack_name else None
    engine = RobustEngine(
        make_mesh(nb_workers=nb_devices), gar, n, nb_real_byz=r, attack=attack,
        worker_momentum=momentum, worker_metrics=worker_metrics,
        reputation_decay=reputation_decay, granularity=granularity,
        secure=secure,
    )
    step = engine.build_step(exp.loss, tx)
    state = engine.init_state(exp.init(jax.random.PRNGKey(42)), tx, seed=1)
    it = exp.make_train_iterator(n, seed=3)
    losses, norms = [], []
    for _ in range(4):
        state, m = step(state, engine.shard_batch(next(it)))
        losses.append(float(jax.device_get(m["total_loss"])).hex())
        norms.append(float(jax.device_get(m["grad_norm"])).hex())
    # one scanned chunk through build_multi_step on top
    multi = engine.build_multi_step(exp.loss, tx)
    chunk = jax.tree_util.tree_map(
        lambda *xs: np.stack(xs), *[next(it) for _ in range(3)])
    state, many = multi(state, engine.shard_batches(chunk))
    losses += [float(v).hex() for v in np.asarray(jax.device_get(many["total_loss"]))]
    return {"losses": losses, "grad_norms": norms, "params_sha256": param_digest(state)}


def run_sharded(granularity, l1=None, l2=None, momentum=None, gar_name="krum",
                f=1, nb_workers=4):
    from aggregathor_tpu.models import transformer as tfm

    cfg = tfm.TransformerConfig(vocab_size=17, d_model=8, n_heads=2, n_layers=2)
    mesh = make_mesh(nb_workers=2, model_parallelism=2)
    gar = gars.instantiate(gar_name, nb_workers, f)
    eng = RobustEngine(
        mesh, gar, nb_workers=nb_workers, granularity=granularity,
        l1_regularize=l1, l2_regularize=l2, worker_momentum=momentum,
        sharding="sharded",
    )
    tx = optax.sgd(0.05)
    state = eng.init_state(
        lambda k: tfm.init_params(cfg, k, n_stages=1), tfm.param_specs(cfg), tx)
    loss_fn = tfm.make_pipeline_loss(cfg, n_stages=1, microbatches=1)
    step = eng.build_step(loss_fn, tx, state)
    rng = np.random.default_rng(0xA66)
    losses, norms = [], []
    for _ in range(3):
        batch = {
            "tokens": rng.integers(0, 17, size=(nb_workers, 2, 8)).astype(np.int32),
            "targets": rng.integers(0, 17, size=(nb_workers, 2, 8)).astype(np.int32),
        }
        state, m = step(state, eng.shard_batch(batch))
        losses.append(float(jax.device_get(m["total_loss"])).hex())
        norms.append(float(jax.device_get(m["grad_norm"])).hex())
    return {"losses": losses, "grad_norms": norms, "params_sha256": param_digest(state)}


def main():
    goldens = {
        "jax_version": jax.__version__,
        "flat_vector_rich": run_flat(
            "vector", secure=True, momentum=0.9, attack_name="signflip",
            worker_metrics=True, reputation_decay=0.9),
        "flat_leaf": run_flat("leaf"),
        "sharded_layer": run_sharded("layer", l1=1e-4, l2=1e-4, momentum=0.9),
        "sharded_global": run_sharded("global"),
    }
    out = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                       "tests", "data", "golden_engine.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as fd:
        json.dump(goldens, fd, indent=2, sort_keys=True)
    print("goldens -> %s" % out)
    for name, doc in goldens.items():
        if name != "jax_version":
            print("  %s: %d losses, params %s..." % (
                name, len(doc["losses"]), doc["params_sha256"][:16]))


if __name__ == "__main__":
    main()
