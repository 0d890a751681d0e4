"""The flat step's second dataflow: gradients reduced IN PLACE, leaf by leaf.

The rows path (engine.py's module docstring) lays the workers' gradients out
as an (n, d) matrix because most of what the engine does is written against a
row: distances, attacks, the lossy link, the wire codec, momentum, the carries,
authenticated submission, reputation, the all_to_all over several chips.  A
coordinate-wise rule with none of that around it needs no row: it is
elementwise across the workers whatever the shape.  Laying the leaves out as
rows and slicing the aggregate back into leaves is then the whole of
``flatten`` and a part of ``apply`` — 24 to 47 ms a step of 230 to 980 in the
grid's five large-d cells, four to five times the rule they feed (PERF.md
section 6, PR 47) — and 4n of the step's peak bytes a parameter.

Here the step hands each gradient leaf, (n, ...) as the vmapped backward pass
left it, to the rule's ``aggregate_leaf`` (gars/__init__.py) and the optimizer
the tree of reduced leaves: no ``concatenate``, no ``FlatMap.inflate``.  The
rank rules read a large leaf in its own (8, 128) tiles
(ops/pallas_kernels ``_plane_leaf_call``); the mean is ``jnp.mean`` over the
leading axis.

Which engine takes it is decided once, when the engine is built, from its own
arguments (``rows_reason``): nothing here is reachable by a flag or an
environment variable, and an engine that needs its rows runs the rows path's
code untouched.  Two paths and not one adaptive one, because the needs
conflict: every feature listed in ``rows_reason`` reads or writes a (k, d) row.
"""

import contextlib
import functools
import math

import jax
import jax.numpy as jnp
import optax

from ..gars.common import leaf_tier
from ..utils import info
from .engine import phase

#: True inside ``forced_rows``.
_forced_rows = False


@contextlib.contextmanager
def forced_rows():
    """Hold every engine BUILT inside the block to the rows path.  The seam of
    the path-parity tests (tests/test_in_place.py), which alone enter it; it is
    no argument of the engine and no training path or script reaches it."""
    global _forced_rows
    previous, _forced_rows = _forced_rows, True
    try:
        yield
    finally:
        _forced_rows = previous


def rows_reason(engine):
    """Why ``engine``'s step needs the workers' gradients as (n, d) rows — the
    names of what needs them, comma-separated — or None where nothing does and
    the step reduces them in place."""
    gar = engine.gar
    needs = [name for name, needed in (
        ("forced_rows", _forced_rows),
        ("sharding:sharded", engine.sharded),
        ("granularity:%s" % engine.granularity, engine.granularity != "vector"),
        ("%d devices on the worker axis" % engine.nb_devices, engine.nb_devices != 1),
        ("the rule %s" % type(gar).__name__,
         not gar.coordinate_wise or gar.needs_distances or gar.uses_axis or gar.uses_key),
        ("attack", engine.attack is not None),
        ("lossy_link", engine.lossy_link is not None),
        ("chaos", engine.chaos is not None),
        ("exchange", engine.codec is not None or engine.exchange_dtype is not None),
        ("worker_momentum", engine.worker_momentum is not None),
        ("carries_gradients", engine.carries_gradients),
        ("carries_ef", engine.carries_ef),
        ("secure", engine.secure),
        ("worker_metrics", engine.worker_metrics),
        ("reputation_decay", engine.reputation_decay is not None),
        ("quarantine_threshold", bool(engine.quarantine_threshold)),
    ) if needed]
    return ", ".join(needs) or None


def _announce(gar, leaves):
    """The build's one log line: the leaves by the tier that reduces them,
    with their elements a worker."""
    by_tier = {"kernel": [], "jnp": []}
    for leaf in leaves:
        by_tier[leaf_tier(gar, leaf)].append(math.prod(leaf.shape[1:]))
    info("step reduces gradients in place: %d leaves, %d by kernel (%s elements), %d as jnp (%s)" % (
        len(leaves), len(by_tier["kernel"]), format(sum(by_tier["kernel"]), ","),
        len(by_tier["jnp"]), format(sum(by_tier["jnp"]), ",")))


def make_body(engine, loss_fn, tx):
    """The per-step body of an engine whose ``rows_reason`` is None — what
    ``RobustEngine._make_flat_body`` returns for it, with the same signature,
    state, metrics, phases and PRNG streams as the rows path's body on the same
    arguments.  One device holds all n workers, so nothing here is a
    collective."""
    has_aux = getattr(loss_fn, "has_aux", False)
    gar = engine.gar
    announced = []

    def body(state, batch):
        key = jax.random.fold_in(state.rng, state.step)
        if engine.batch_transform is not None:

            def aug_one(worker_batch, j):
                # fold tag 3 of the (key, global worker) pair, as on the rows path
                return engine.batch_transform(
                    worker_batch, jax.random.fold_in(jax.random.fold_in(key, j), 3))

            with phase("augment"):
                batch = jax.vmap(aug_one)(batch, jnp.arange(engine.nb_workers))

        def one(worker_batch):
            return jax.value_and_grad(loss_fn, has_aux=has_aux)(state.params, worker_batch)

        with phase("grad"):
            losses, grads = jax.vmap(one)(batch)
        losses, counters = losses if has_aux else (losses, None)
        leaves, treedef = jax.tree_util.tree_flatten(grads)
        if not announced:  # once a build, however often the body is traced
            _announce(gar, leaves)
            announced.append(True)
        with phase("gar"):
            reduced = [gar.aggregate_leaf(leaf) for leaf in leaves]  # float32
        with phase("apply"):
            agg_tree = treedef.unflatten(
                [agg.astype(leaf.dtype) for agg, leaf in zip(reduced, leaves)])
            updates, opt_state = tx.update(agg_tree, state.opt_state, state.params)
            params = optax.apply_updates(state.params, updates)
        with phase("epilogue"):
            worker_nan = None
            if engine.health_probe:
                # the rows path's per-worker flags, read off the leaves
                worker_nan = functools.reduce(jnp.logical_or, [
                    jnp.any(~jnp.isfinite(leaf), axis=tuple(range(1, leaf.ndim)))
                    for leaf in leaves])
            new_state, metrics = engine._finalize_step(
                state, params=params, opt_state=opt_state, new_carry=None,
                new_momentum=None, new_momentum_steps=None, total_loss=jnp.sum(losses),
                update_norm=jnp.sqrt(sum(jnp.sum(jnp.square(agg)) for agg in reduced)),
                worker_nan=worker_nan, rep_dist=None, wdist=None, participation=None,
                secure_metrics=None, ridx=None,
            )
            if counters is not None:
                metrics["model_counters"] = counters  # one value a worker, worker-major
            return new_state, metrics

    return body
