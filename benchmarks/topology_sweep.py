"""Topology sweep: the aggregation tree at n=256 — naming, loss, breakdown.

The topology subsystem (topology/, gars/tree.py) replaces the PS star
with L levels of untrusted sub-aggregators; this sweep asks the four
questions the subsystem's claims rest on, every arm at **n >= 256**:

- **Naming.**  A corrupted sub-aggregator (chaos ``corrupt-agg``: signs
  its wire image WITHOUT the session secret) must be named by its
  (level, unit) on the forensics ledger's sub-aggregator surface — and
  NO leaf worker may pick up the blame.  Driven through the real host
  plane (``TreeAggregator.process_round``: emissions, custody chain,
  per-level verdicts) with the redundant shadow reconstructing the
  forged unit.
- **Equal loss.**  The tree at r = f (gaussian coalition) must land at
  the same final loss as the flat star under the same attack — the
  hierarchy buys wire/naming/bounded-wait structure, not accuracy.
  Real fused-engine training cells, flat vs tree.
- **Per-level breakdown.**  The parse-time composition arithmetic
  (``b_{l+1} = min(b_l, m_l) + agg_f_l``) is probed empirically per
  level: an r = f + 1 coalition PACKED so one level-l unit absorbs two
  of its rows stays contained (the partition bound wastes the surplus
  on one outer row), while the same coalition fully SPREAD captures the
  root order statistic.  Crafted rows through the in-graph tree.
- **Zero recompiles.**  The tree composed with the worker int8:ef
  exchange codec AND secure digests must hold a steady-state compile
  count of 1 (training cell), and the host plane's per-level emission
  executables likewise (forensics arm).

Output schema ``aggregathor.topology.sweep.v1``::

    {schema, generated_at, config: {...},
     cells: [{topology, spec, attack, nb_real_byz, steps_per_s,
              final_loss, losses_finite, loss_decreased, compile_count}],
     forensics: {spec, rounds, corrupt_subaggregators, workers_blamed,
                 reconstructions, exclusions, chain_steps,
                 host_cache_size, link_ratio},
     breakdown: {spec, nb_attackers_at_f, at_f_spread_contained,
                 at_f_plus_1_spread_poisoned,
                 per_level: {level: packed_contained}},
     verdict: {forensics_named, equal_loss_at_f, breakdown_per_level,
               zero_recompiles, pass}}

Usage::

    python benchmarks/topology_sweep.py [--steps 8] [--out TOPO_r18.json]
"""

import argparse
import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SCHEMA = "aggregathor.topology.sweep.v1"

#: equal-final-loss tolerance, the campaign convention (trajectories
#: legitimately differ step by step; the claim is where they land)
LOSS_RTOL = 0.10
LOSS_ATOL = 0.5

#: the breakdown tree: average inner levels (any attacker corrupts its
#: group row — the sharpest instrument for counting contaminated rows),
#: median root (order statistic captured at exactly half the rows).
#: n=256, g=2x2 -> 64 root rows; the root upper median (index 32) is
#: captured by 32 corrupted rows, so f = 31 is the exact boundary.
BREAKDOWN_SPEC = "tree:g=2x2,rules=average-nan>average-nan>median"
BREAKDOWN_F = 31

#: the training tree (equal-loss + zero-recompile arms): median damage
#: control per level, int8 on every inter-level link
TRAIN_SPEC = "tree:g=2x2,rules=average-nan>average-nan>median,link=int8"

#: the WORKER wire of every training cell (the leaf links): int8 with
#: error feedback — EF is per-worker residual state, legal on the leaf
#: wire; the tree's own inter-level links refuse it (spec.py)
WORKER_EXCHANGE = "int8:ef"

#: the custody/naming arm (host plane): the deep tree with redundancy —
#: level budgets via agg-f, krum root sized at parse time
FORENSICS_SPEC = ("tree:g=16x4,rules=median>trimmed-mean>krum,link=int8,"
                  "redundancy=2,agg-f=1x0")


def make_iterator(exp, nb_workers, seed=3):
    return exp.make_train_iterator(nb_workers, seed=seed)


def run_cell(args, topology, spec, attack=None, nb_real_byz=0):
    """One fused-engine training cell (flat star or in-graph tree),
    secure digests + the int8:ef worker exchange composed on every arm."""
    import jax
    import numpy as np

    from aggregathor_tpu import gars, models
    from aggregathor_tpu.core import build_optimizer, build_schedule
    from aggregathor_tpu.parallel import RobustEngine, attacks, make_mesh

    n, f = args.nb_workers, args.nb_byz
    exp = models.instantiate("digits", ["batch-size:%d" % args.batch_size])
    gar = gars.instantiate(spec, n, f)
    tx = build_optimizer("sgd", build_schedule("fixed", ["initial-rate:0.05"]))
    atk = (attacks.instantiate(attack, n, nb_real_byz, ["deviation:10000.0"])
           if attack else None)
    engine = RobustEngine(
        make_mesh(nb_workers=1), gar, n, attack=atk, nb_real_byz=nb_real_byz,
        exchange=WORKER_EXCHANGE, secure=True,
    )
    state = engine.init_state(exp.init(jax.random.PRNGKey(0)), tx, seed=1)
    step = engine.build_step(exp.loss, tx)
    it = make_iterator(exp, n)
    losses = []
    state, m = step(state, engine.shard_batch(next(it)))  # compile round
    losses.append(float(jax.device_get(m["total_loss"])))
    begin = time.perf_counter()
    for _ in range(args.steps):
        state, m = step(state, engine.shard_batch(next(it)))
        losses.append(float(jax.device_get(m["total_loss"])))
    jax.block_until_ready(state.params)
    elapsed = time.perf_counter() - begin
    return {
        "topology": topology,
        "spec": spec,
        "attack": attack or "none",
        "nb_real_byz": nb_real_byz,
        "steps_per_s": args.steps / elapsed,
        "final_loss": float(losses[-1]),
        "losses_finite": bool(np.isfinite(losses).all()),
        "loss_decreased": bool(np.isfinite(losses).all()
                               and losses[-1] < losses[0]),
        "compile_count": int(step._cache_size()),
    }


def run_forensics(args):
    """The naming arm: real host plane at n, chaos corrupt-agg forging
    unit (1, 0)'s custody tag every round, shadow reconstruction, chain
    verification — the corrupt node must be NAMED, no worker blamed."""
    import jax.numpy as jnp
    import numpy as np

    from aggregathor_tpu.chaos import ChaosSchedule
    from aggregathor_tpu.obs.forensics import ForensicsLedger
    from aggregathor_tpu.topology import TreeAggregator, parse_topology_spec

    n, d = args.nb_workers, args.dim
    spec = parse_topology_spec(FORENSICS_SPEC, n, 0)
    agg = TreeAggregator(spec)
    agg.bind(n, d)
    agg.schedule = ChaosSchedule("0:corrupt-agg=1.0", n,
                                 allow_topology_faults=True)
    ledger = ForensicsLedger(n)
    agg.ledger = ledger
    rng = np.random.default_rng(17)
    for step in range(args.rounds):
        rows = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
        arrived, stale = agg.process_round(
            step, np.ones(n, bool), np.zeros(n, bool),
            np.full(n, 0.05), rows, leaf_window=5.0)
        assert arrived.all(), "reconstruction must not exclude any worker"
    report = ledger.report()
    recs = report["sub_aggregators"]
    return {
        "spec": FORENSICS_SPEC,
        "rounds": int(agg.rounds_total),
        "corrupt_subaggregators": report["corrupt_subaggregators"],
        "workers_blamed": report["suspects"],
        "reconstructions": int(sum(
            r["evidence"].get("reconstructed", 0) for r in recs)),
        "exclusions": int(sum(
            1 for r in recs if r["evidence"].get("excluded", 0))),
        "chain_steps": int(agg.chain()["steps"]),
        "host_cache_size": int(agg.cache_size()),
        "link_ratio": float(spec.link_ratio(d)),
    }


def _probe(attacker_leaves, n, d=64, k=1000.0):
    """Aggregate crafted rows (honest ~N(0, 0.1), attackers at +k)
    through the breakdown tree; contained iff the output stays small."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from aggregathor_tpu import gars

    tree = gars.instantiate(BREAKDOWN_SPEC, n, BREAKDOWN_F)
    rows = np.random.default_rng(23).normal(size=(n, d)).astype(np.float32)
    rows *= 0.1
    for leaf in attacker_leaves:
        rows[leaf] = k
    out = np.asarray(tree.aggregate(jnp.asarray(rows),
                                    key=jax.random.PRNGKey(5)))
    return bool(np.abs(out).max() < 10.0)


def run_breakdown(args):
    """The per-level composition boundary at n: spread r = f contained,
    spread r = f + 1 poisoned, and the SAME r = f + 1 coalition packed
    so one level-l unit absorbs two of its rows contained — per level."""
    n, f = args.nb_workers, BREAKDOWN_F
    # level-2 subtrees have width 4 (g=2x2): leaf 4k sits in its own
    # level-1 pair AND its own level-2 unit — maximal spread
    spread_f = [4 * k for k in range(f)]
    spread_f1 = [4 * k for k in range(f + 1)]
    # packed at level 1: leaves {0, 1} share ONE level-1 group (one
    # corrupted level-1 row for two attackers)
    packed_l1 = [0, 1] + [4 * k for k in range(1, f)]
    # packed at level 2: leaves {0, 2} sit in two DIFFERENT level-1
    # groups of the SAME level-2 unit (two corrupted level-1 rows, one
    # corrupted level-2 row)
    packed_l2 = [0, 2] + [4 * k for k in range(1, f)]
    return {
        "spec": BREAKDOWN_SPEC,
        "nb_attackers_at_f": f,
        "at_f_spread_contained": _probe(spread_f, n),
        "at_f_plus_1_spread_poisoned": not _probe(spread_f1, n),
        "per_level": {
            "1": _probe(packed_l1, n),
            "2": _probe(packed_l2, n),
        },
    }


def validate(doc):
    """Schema check for round-tripping consumers (the smoke script and
    tests/test_topology.py's checked-in-document test)."""
    if doc.get("schema") != SCHEMA:
        raise ValueError("not a %s document" % SCHEMA)
    for key in ("config", "cells", "forensics", "breakdown", "verdict"):
        if key not in doc:
            raise ValueError("missing %r" % key)
    if doc["config"].get("nb_workers", 0) < 256:
        raise ValueError("the topology sweep's claims are sized at "
                         "n >= 256 (got n=%r)" % doc["config"].get("nb_workers"))
    for cell in doc["cells"]:
        for key in ("topology", "spec", "attack", "nb_real_byz",
                    "steps_per_s", "final_loss", "losses_finite",
                    "loss_decreased", "compile_count"):
            if key not in cell:
                raise ValueError("cell missing %r" % key)
        if cell["topology"] not in ("flat", "tree"):
            raise ValueError("bad topology %r" % cell["topology"])
    for key in ("spec", "rounds", "corrupt_subaggregators",
                "workers_blamed", "reconstructions", "exclusions",
                "chain_steps", "host_cache_size", "link_ratio"):
        if key not in doc["forensics"]:
            raise ValueError("forensics missing %r" % key)
    br = doc["breakdown"]
    for key in ("spec", "nb_attackers_at_f", "at_f_spread_contained",
                "at_f_plus_1_spread_poisoned", "per_level"):
        if key not in br:
            raise ValueError("breakdown missing %r" % key)
    for level, contained in br["per_level"].items():
        if not isinstance(contained, bool):
            raise ValueError("breakdown per_level[%s] wants a bool" % level)
    for key in ("forensics_named", "equal_loss_at_f", "breakdown_per_level",
                "zero_recompiles", "pass"):
        if not isinstance(doc["verdict"].get(key), bool):
            raise ValueError("verdict missing bool %r" % key)
    return doc


def load(path):
    with open(path) as fd:
        return validate(json.load(fd))


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--steps", type=int, default=8,
                        help="measured steps per training cell "
                             "(after 1 compile step)")
    parser.add_argument("--rounds", type=int, default=6,
                        help="host-plane rounds of the forensics arm")
    parser.add_argument("--nb-workers", type=int, default=256,
                        help="leaf workers (the sweep's claims are sized "
                             "at n >= 256)")
    parser.add_argument("--nb-byz", type=int, default=8,
                        help="declared f of the training cells")
    parser.add_argument("--batch-size", type=int, default=4)
    parser.add_argument("--dim", type=int, default=2048,
                        help="row width of the host-plane forensics arm")
    parser.add_argument("--out", default=None, help="write the JSON here")
    args = parser.parse_args(argv)
    if args.nb_workers < 256:
        raise SystemExit("the topology sweep runs at n >= 256 "
                         "(got --nb-workers %d)" % args.nb_workers)
    if args.nb_workers % 4:
        raise SystemExit("--nb-workers must divide by 4 (g=2x2 trees)")

    cells = []
    for topology, spec in (("flat", "median"), ("tree", TRAIN_SPEC)):
        for attack, byz in ((None, 0), ("gaussian", args.nb_byz)):
            cell = run_cell(args, topology, spec, attack=attack,
                            nb_real_byz=byz)
            cells.append(cell)
            print("%-4s %-9s r=%-3d %6.2f steps/s  final=%-8.3f "
                  "compiles=%d %s" % (
                      cell["topology"], cell["attack"], cell["nb_real_byz"],
                      cell["steps_per_s"], cell["final_loss"],
                      cell["compile_count"],
                      "finite" if cell["losses_finite"] else "NON-FINITE"))

    forensics = run_forensics(args)
    print("forensics: corrupt=%s blamed_workers=%s reconstructions=%d "
          "cache=%d ratio=%.2fx" % (
              forensics["corrupt_subaggregators"],
              forensics["workers_blamed"], forensics["reconstructions"],
              forensics["host_cache_size"], forensics["link_ratio"]))
    breakdown = run_breakdown(args)
    print("breakdown: at_f=%s at_f+1_spread_poisoned=%s per_level=%s" % (
        breakdown["at_f_spread_contained"],
        breakdown["at_f_plus_1_spread_poisoned"], breakdown["per_level"]))

    def pick(topology, attack):
        return next(c for c in cells
                    if c["topology"] == topology and c["attack"] == attack)

    flat_at_f = pick("flat", "gaussian")
    tree_at_f = pick("tree", "gaussian")
    equal_loss = bool(
        tree_at_f["losses_finite"] and flat_at_f["losses_finite"]
        and abs(tree_at_f["final_loss"] - flat_at_f["final_loss"])
        <= LOSS_RTOL * abs(flat_at_f["final_loss"]) + LOSS_ATOL
    )
    doc = {
        "schema": SCHEMA,
        "generated_at": time.time(),
        "config": {
            "nb_workers": args.nb_workers, "nb_byz": args.nb_byz,
            "batch_size": args.batch_size, "steps": args.steps,
            "rounds": args.rounds, "dim": args.dim,
            "worker_exchange": WORKER_EXCHANGE,
            "train_spec": TRAIN_SPEC, "forensics_spec": FORENSICS_SPEC,
            "breakdown_spec": BREAKDOWN_SPEC, "breakdown_f": BREAKDOWN_F,
            "loss_rtol": LOSS_RTOL, "loss_atol": LOSS_ATOL,
            "platform": os.environ.get("JAX_PLATFORMS", ""),
        },
        "cells": cells,
        "forensics": forensics,
        "breakdown": breakdown,
        "verdict": {
            "forensics_named": bool(
                forensics["corrupt_subaggregators"] == ["1.0"]
                and forensics["workers_blamed"] == []
                and forensics["reconstructions"] >= args.rounds),
            "equal_loss_at_f": equal_loss,
            "breakdown_per_level": bool(
                breakdown["at_f_spread_contained"]
                and breakdown["at_f_plus_1_spread_poisoned"]
                and all(breakdown["per_level"].values())),
            "zero_recompiles": bool(
                tree_at_f["compile_count"] == 1
                and forensics["host_cache_size"] == 1),
        },
    }
    doc["verdict"]["pass"] = bool(
        doc["verdict"]["forensics_named"]
        and doc["verdict"]["equal_loss_at_f"]
        and doc["verdict"]["breakdown_per_level"]
        and doc["verdict"]["zero_recompiles"])
    validate(doc)
    print("verdict: named=%s equal_loss=%s breakdown=%s zero_recompiles=%s "
          "-> %s" % (
              doc["verdict"]["forensics_named"],
              doc["verdict"]["equal_loss_at_f"],
              doc["verdict"]["breakdown_per_level"],
              doc["verdict"]["zero_recompiles"],
              "PASS" if doc["verdict"]["pass"] else "FAIL"))
    if args.out:
        with open(args.out, "w") as fd:
            json.dump(doc, fd, indent=1)
            fd.write("\n")
        print("sweep -> %s" % args.out)
    return 0 if doc["verdict"]["pass"] else 1


if __name__ == "__main__":
    sys.exit(main())
