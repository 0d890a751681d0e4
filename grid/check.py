"""The comparison that decides ``correct``.

The timed path is one compiled K-step dispatch and its state.  Set-up drives
that object from the seeded state through its first dispatch and keeps what it
reports of each step (the loss, the norm of the aggregated gradient as the
optimizer gets it) and the parameters it leaves.  After the window the plain
reference follows those steps from the same seed — its own weights, its own
restatement of which rows each worker draws, a Python loop of ``jax.grad`` over
the plain model, the plain rule, the plain optimizer — for as many steps and at
the product precision the cell's limits file states (``reference``), and the
numbers that file lists (``limits``) are held to it:

``narrow_products``  how many convolutions and matmuls of the timed program, as
                   traced, take an operand narrower than the configuration's
                   ``dtype``.  Exact: the limit is 0.  The control — the
                   program's own ``dtype:bfloat16`` path — fails here, and on
                   this chip only here: a float32 product already multiplies in
                   bfloat16, and the norms below do not tell the two apart
                   (PERF.md, section 6, PR 23).
``loss_gap``       worst followed step: |program's loss - reference's| /
                   reference's.  What a part of the batch left out moves, and,
                   from the second step on, an update of the wrong size or sign.
``grad_norm_gap``  first step: the same for the norm of the aggregated gradient,
                   the one step at which program and reference hold the same
                   parameters.  Under Krum and Bulyan it swings where two workers
                   are near-tied and the program chooses the other (later steps
                   inherit every such choice, and are not compared); held against
                   a broken rule or exchange.
``dparam_gap``     by the worst leaf: |norm of the program's change of that leaf
                   over the dispatch - the reference's| / the larger of the
                   reference's norm of that leaf and of its median leaf.  Needs a
                   reference that follows all K steps of the dispatch.
``dparam_own_gap`` for a cell whose reference stops before K: |ln(rate x sum
                   over the K steps of the program's aggregated-gradient norm /
                   norm of the parameters' change)|.  Plain SGD can move the
                   parameters by at most what it was handed; a state returned
                   unchanged reads infinite.  The size and sign of the update are
                   then held by ``loss_gap`` at the second and third step.
"""

import contextlib
import json

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree

from cell import load_module

PRODUCTS = ("conv_general_dilated", "dot_general")


def program_record(metrics, params):
    """What the first dispatch reported and left, copied to the host."""
    return {
        "losses": np.asarray(metrics["total_loss"], np.float64).reshape(-1),
        "grad_norms": np.asarray(metrics["grad_norm"], np.float64).reshape(-1),
        "params": jax.device_get(params),
    }


def _subjaxprs(eqn):
    for value in eqn.params.values():
        for inner in (value if isinstance(value, (list, tuple)) else (value,)):
            inner = getattr(inner, "jaxpr", inner)
            if hasattr(inner, "eqns"):
                yield inner


def _count_narrow(jaxpr, bits):
    count = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in PRODUCTS and any(
                jnp.issubdtype(v.aval.dtype, jnp.floating) and jnp.finfo(v.aval.dtype).bits < bits
                for v in eqn.invars):
            count += 1
        count += sum(_count_narrow(inner, bits) for inner in _subjaxprs(eqn))
    return count


def narrow_products(cell, state, data):
    """Products of the timed program (traced again from the shapes of what the
    window handed it; nothing runs) with an operand narrower than the
    configuration's ``dtype``."""
    shapes = lambda tree: jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
    jaxpr = jax.make_jaxpr(cell.multi)(shapes(state), shapes(data))
    return _count_narrow(jaxpr.jaxpr, jnp.finfo(cell.spec["config_data"]["dtype"]).bits)


class PlainReference:
    """The plain reference of one cell: its jitted loss-and-gradient is built
    once and serves every seed."""

    def __init__(self, cell):
        config, traffic = cell.spec["config_data"], cell.spec["traffic_data"]
        stated = cell.spec["limits"]["reference"]
        self.cell, self.config = cell, config
        self.steps = cell.unroll if stated["steps"] == "all" else int(stated["steps"])
        if not 1 <= self.steps <= cell.unroll:
            raise SystemExit("limits of %r: the reference follows %r steps of a dispatch of %d"
                             % (cell.spec["name"], stated["steps"], cell.unroll))
        if stated["precision"] not in ("default", "highest"):
            raise SystemExit("limits of %r: reference precision %r is neither 'default' nor "
                             "'highest'" % (cell.spec["name"], stated["precision"]))
        self.precision = None if stated["precision"] == "default" else stated["precision"]
        self.feed = load_module("references", "feed_" + traffic["input_source"])
        self.rule = load_module("rules", traffic["aggregator"])
        # Byzantine workers' rows come from grid/attacks/<name>.py: rows(honest, real_byz, key)
        self.attack = load_module("attacks", traffic["attack"]) if traffic["attack"] else None
        self.nb_real_byz = traffic["nb_real_byz_workers"]
        self.optimizer = load_module("optimizers", config["optimizer"])
        self.schedule = load_module("schedules", config["learning_rate"])
        template = cell.seeded_params(0)
        _, unravel = ravel_pytree(template)
        self.ravel = jax.jit(lambda tree: ravel_pytree(tree)[0])
        self.leaf_sizes = [leaf.size for leaf in jax.tree_util.tree_leaves(template)]
        loss = cell.reference.loss
        self.loss_and_gradient = jax.jit(lambda flat, images, labels: jax.value_and_grad(
            lambda v: loss(unravel(v), images, labels))(flat))
        self.set_row = jax.jit(lambda rows, i, row: rows.at[i].set(row), donate_argnums=0)
        self.dataset = {name: jnp.asarray(value) for name, value in cell.arrays.items()}

    def follow(self, seed, aggregate=None):
        """The first ``self.steps`` steps from ``seed``: per-step loss and
        aggregated-gradient norm, and the parameters before and after as flat
        float32 vectors on the host.  ``aggregate(rows, f, step)`` stands in
        for the rule where given (readings.py: a near-tie decided the other
        way)."""
        stated = (jax.default_matmul_precision(self.precision) if self.precision
                  else contextlib.nullcontext())
        with stated:  # read while the products trace, and part of their cache key
            return self._follow(seed, aggregate or (lambda rows, f, step:
                                                    self.rule.aggregate(rows, f)))

    def _follow(self, seed, aggregate):
        config = self.config
        n, f = config["nb_workers"], config["nb_decl_byz_workers"]
        run_key = jax.random.PRNGKey(seed)
        theta = self.ravel(self.cell.seeded_params(seed))
        theta0 = np.asarray(theta)
        state = self.optimizer.init(theta, config["optimizer_args"])
        losses, norms, rate_sum = [], [], 0.0
        for step in range(self.steps):
            rows = jnp.zeros((n, theta.shape[0]), jnp.float32)
            step_losses = []
            for worker in range(n):
                images, labels = self.feed.worker_batch(
                    self.dataset, run_key, step, worker,
                    batch_size=config["batch_per_worker"], augment=config["augment"])
                value, gradient = self.loss_and_gradient(theta, images, labels)
                rows = self.set_row(rows, worker, gradient)
                step_losses.append(value)
            if self.attack is not None:
                rows = self.attack.rows(rows, self.nb_real_byz, jax.random.fold_in(run_key, step))
            aggregated = aggregate(rows, f, step)
            del rows
            losses.append(sum(float(value) for value in step_losses))
            norms.append(float(jnp.linalg.norm(aggregated)))
            rate = self.schedule.rate(step, config["learning_rate_args"])
            rate_sum += rate
            theta, state = self.optimizer.step(theta, aggregated, state, rate)
        return {"losses": np.array(losses), "grad_norms": np.array(norms),
                "theta0": theta0, "theta": np.asarray(theta),
                "mean_rate": rate_sum / self.steps, "leaf_sizes": self.leaf_sizes,
                "followed_all": self.steps == self.cell.unroll}


def stand_in_record(steps):
    """A reference's own steps (``PlainReference.follow``) put in the
    program's place: how readings.py reads what a near-tie is worth."""
    return {"losses": steps["losses"], "grad_norms": steps["grad_norms"],
            "params": steps["theta"]}


def _leaf_norms(vector, sizes):
    bounds = np.cumsum([0] + list(sizes))
    return np.array([np.linalg.norm(vector[lo:hi].astype(np.float64))
                     for lo, hi in zip(bounds[:-1], bounds[1:])])


def compare(record, reference, wanted):
    """The numbers named in ``wanted``, from the program's record and the
    reference's steps."""
    steps = len(reference["losses"])
    params = record["params"]
    if not isinstance(params, np.ndarray):  # a tree of leaves, in ravel_pytree's order
        params = np.concatenate([np.ravel(leaf) for leaf in jax.tree_util.tree_leaves(params)])
    moved = params - reference["theta0"]
    numbers = {}
    for name in wanted:
        if name == "loss_gap":
            value = np.max(np.abs(record["losses"][:steps] - reference["losses"])
                           / np.abs(reference["losses"]))
        elif name == "grad_norm_gap":
            value = (abs(record["grad_norms"][0] - reference["grad_norms"][0])
                     / reference["grad_norms"][0])
        elif name == "dparam_gap":
            if not reference["followed_all"]:
                raise SystemExit("dparam_gap needs a reference that follows the whole dispatch "
                                 "(\"steps\": \"all\" in the cell's limits file)")
            sizes = reference["leaf_sizes"]
            ours = _leaf_norms(moved, sizes)
            theirs = _leaf_norms(reference["theta"] - reference["theta0"], sizes)
            value = np.max(np.abs(ours - theirs) / np.maximum(theirs, np.median(theirs)))
        elif name == "dparam_own_gap":
            handed = reference["mean_rate"] * float(np.sum(record["grad_norms"]))
            with np.errstate(divide="ignore"):
                value = abs(np.log(np.float64(handed)
                                   / np.linalg.norm(moved.astype(np.float64))))
        elif name == "narrow_products":
            continue  # counted from the program alone (narrow_products)
        else:
            raise SystemExit("no number named %r: check.py compares loss_gap, grad_norm_gap, "
                             "dparam_gap, dparam_own_gap and narrow_products" % name)
        numbers[name] = float(value)
    return numbers


def verdict(numbers, limits):
    """True when every limit has its number, finite and within it; prints each
    number beside its limit."""
    ok = True
    for name, limit in limits.items():
        value = numbers.get(name, float("nan"))
        within = bool(np.isfinite(value) and value <= limit)
        ok = ok and within
        print("grid compare %s" % json.dumps(
            {"number": name, "value": value, "limit": limit, "within": within}), flush=True)
    return ok
