"""Straggler sweep v3: age-reweighted stale correction on the compressed wire.

v2 (STRAGGLER_r12.json, retired) measured sync vs fixed vs ADAPTIVE windows
over straggler regimes.  v3 measures what nobody had: how the PR-12 stale
carry, the PR-14 wire codec and the new age reweighting COMPOSE — the
bounded-wait v3 campaign (ISSUE 20):

- **The reweight grid**: arm (naive | reweight) x straggle rate x rule x
  exchange codec (f32 | int8:ef) x stale-max-age, every cell the full
  adaptive protocol (percentile controller + stale infill), judged like v2
  on the per-ARRIVED-worker mean loss.  The scenario is the laundering one
  the declared-f budget exists for: an IN-BUDGET coalition (r = f) runs a
  moderate local gaussian attack AND straggles with the grid's rate, so
  the stale carry holds an ATTACK row.  Naive infill re-enters that row at
  FULL WEIGHT round after round; damping by c(a) = 1/(1+a)
  (arXiv:2505.23523's unbiased-estimator framing) bounds what a carried
  row can keep injecting.  The grid answers WHERE that buys back final
  loss: on rules where the carry enters the estimate (the average family)
  the reweighted arm wins decisively at high rates; selection rules (krum)
  flatten the gap to zero — both findings are the campaign.  On honest
  stragglers (convex digits) the carry stays a useful descent direction at
  any age and neither arm wins — which is why the verdict is judged on the
  averaging-family pairs, where the mechanism under test is live.
- **The breakdown probe, reweighting ON**: the r coalition workers run a
  local gaussian attack AND straggle persistently so their attack rows
  re-enter via the stale carry, DAMPED.  The f-accounting is not relaxed
  by the damping: krum and trimmed-mean must still hold at r = f, and
  trimmed-mean (exact-f trim budget) must still break at r = f + 1 — a
  deviation-10000 row damped by 1/(1+a) is still a poison row.
- **The EF break scan**: error feedback freezes a stale worker's residual
  while its naive carry re-enters at full weight round after round — at
  what stale-max-age does the compounding stop the loss from decreasing?
  Scanned on average-nan (no robustness to hide behind) over int8:ef with
  a milder-deviation coalition than the grid's, so the break age lands
  INSIDE the scan instead of at its first point.
- **The submesh cell**: bounded-wait over a NONTRIVIAL (pipe x model) mesh
  (4,2,1) — per-submesh collective programs (engine.build_submesh_grad),
  the straggling submesh forfeits its k = 2 rows AS A UNIT, zero
  steady-state recompiles.  The old loud refusal is gone; this cell is the
  proof.

Output schema ``aggregathor.straggler.sweep.v3``::

    {schema, generated_at, config: {...},
     cells: [{arm: "naive"|"reweight", rate, gar, exchange, stale_max_age,
              steps_per_s, losses_finite, final_loss, loss_decreased,
              timeouts_total, stale_total, window_final}...],
     pairs: [{rate, gar, exchange, stale_max_age, naive_loss,
              reweight_loss, reweight_wins}...],
     breakdown: {at_f_krum_ok, at_f_trimmed_ok, over_f_broken},
     ef_break: {gar, ages_scanned, losses_by_age, break_age},
     submesh: {mesh, completed, unit_forfeit_ok, compile_count_ok,
               losses_finite, timeouts_total, final_loss},
     verdict: {reweight_beats_naive, breakdown_holds, submesh_ok, pass}}

Usage::

    python benchmarks/straggler_sweep.py [--steps 10] [--deadline 0.25]
        [--stall 0.6] [--rates 0.5,1.0] [--gars average-nan,krum]
        [--exchanges f32,int8:ef] [--ages 2,8] [--deviation 20]
        [--out STRAGGLER_r20.json]
"""

import argparse
import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# the submesh cell needs a (4, 2, 1) mesh = 8 devices; force them BEFORE
# jax imports (append-safe: an operator's existing XLA_FLAGS survive)
_xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _xla_flags:
    os.environ["XLA_FLAGS"] = (
        _xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SCHEMA = "aggregathor.straggler.sweep.v3"

ARMS = ("naive", "reweight")
EXCHANGES = ("f32", "int8:ef")

#: the submesh cell's mesh: W=4 worker submeshes x 2 pipe stages (n=8
#: logical workers, k=2 per submesh — k == f, so one forfeited unit
#: exactly spends the budget)
SUBMESH_AXES = (4, 2, 1)


def _make_stack(gar_name, exchange, args, attack=None, nb_real_byz=0,
                deviation=10000.0):
    """Flat engine + optimizer + digits experiment for one cell."""
    import jax

    from aggregathor_tpu import gars, models
    from aggregathor_tpu.core import build_optimizer, build_schedule
    from aggregathor_tpu.parallel import (RobustEngine, attacks, make_mesh)

    n, f = args.nb_workers, args.nb_byz
    exp = models.instantiate("digits", ["batch-size:%d" % args.batch_size])
    gar = gars.instantiate(gar_name, n, f)
    tx = build_optimizer("sgd", build_schedule("fixed", ["initial-rate:0.05"]))
    atk = (attacks.instantiate(attack, n, nb_real_byz,
                               ["deviation:%g" % deviation])
           if attack else None)
    engine = RobustEngine(make_mesh(nb_workers=1), gar, n, attack=atk,
                          nb_real_byz=nb_real_byz, exchange=exchange)
    state = engine.init_state(exp.init(jax.random.PRNGKey(0)), tx, seed=1)
    return exp, engine, tx, state


def _drive(step, state, exp, args, steps):
    """Warmup + measured rounds; returns (losses, elapsed) with losses the
    per-ARRIVED-worker means (total_loss sums only arrived workers, so
    cells with different timeout counts stay comparable)."""
    import jax

    n = args.nb_workers
    it = exp.make_train_iterator(n, seed=3)
    losses = []

    def mean_arrived_loss(metrics):
        total = float(jax.device_get(metrics["total_loss"]))
        arrived = n - int(jax.device_get(metrics["nb_timeouts"]))
        return total / max(arrived, 1)

    state, m = step(state, next(it))  # warmup: compiles, deadline off
    losses.append(mean_arrived_loss(m))
    begin = time.perf_counter()
    for _ in range(steps):
        state, m = step(state, next(it))
        losses.append(mean_arrived_loss(m))
    elapsed = time.perf_counter() - begin
    return losses, elapsed


def run_cell(arm, rate, gar_name, exchange, stale_max_age, args,
             attack=None, nb_real_byz=0, straggler_model="rate", steps=None,
             deviation=10000.0):
    """One grid cell: the full adaptive protocol (controller + stale
    infill), ``arm`` choosing naive full-weight carries vs age-reweighted
    ones.  ``straggler_model="rate"`` builds the grid's model (the first f
    workers late with probability ``rate``, stall >> every window) — the
    same first-f indices the attack coalition occupies, so an attacking
    cell's stale carry holds attack rows."""
    import jax
    import numpy as np

    from aggregathor_tpu.parallel.bounded import (BoundedWaitStep,
                                                  HostStragglerModel)
    from aggregathor_tpu.parallel.deadline import DeadlineController

    n, f = args.nb_workers, args.nb_byz
    steps = steps or args.steps
    exp, engine, tx, state = _make_stack(gar_name, exchange, args,
                                         attack=attack,
                                         nb_real_byz=nb_real_byz,
                                         deviation=deviation)
    if straggler_model == "rate":
        model = (HostStragglerModel(n, args.stall, rate=rate, nb_eligible=f,
                                    seed=0) if rate > 0 else None)
    else:
        model = straggler_model
    controller = DeadlineController(
        args.deadline, percentile=args.percentile, floor=args.floor, ema=0.5,
    )
    step = BoundedWaitStep(
        engine, exp.loss, tx, jax.device_get(state.params),
        deadline=args.deadline, straggler_model=model, controller=controller,
        stale_infill=True, stale_max_age=stale_max_age,
        stale_reweight=arm == "reweight",
    )
    try:
        losses, elapsed = _drive(step, state, exp, args, steps)
        timeouts = int(step.timeouts_total.sum())
        stale = int(step.stale_total.sum())
    finally:
        step.close()
    return {
        "arm": arm,
        "rate": float(rate),
        "gar": gar_name,
        "exchange": exchange,
        "stale_max_age": int(stale_max_age),
        "steps_per_s": steps / elapsed,
        "losses_finite": bool(np.isfinite(losses).all()),
        "final_loss": float(losses[-1]),
        "loss_decreased": bool(np.isfinite(losses).all()
                               and losses[-1] < losses[0]),
        "timeouts_total": timeouts,
        "stale_total": stale,
        "window_final": controller.window,
    }


def run_breakdown(args):
    """The stale-laundering budget boundary WITH REWEIGHTING ON
    (tests/test_bounded.py twin): the r coalition workers run a local
    gaussian attack AND straggle persistently, so their DAMPED attack rows
    re-enter via the stale carry.  At r = f both rules hold; at r = f + 1
    trimmed-mean (exact-f trim budget) is poisoned — c(a) never exceeds 1,
    so a damped deviation-10000 row is still a poison row and the f
    accounting must not be relaxed.  (Krum's selection degrades gracefully
    past f for uncoordinated rows — docs/engine.md.)"""
    from aggregathor_tpu.parallel.bounded import HostStragglerModel

    n, f = args.nb_workers, args.nb_byz
    steps = max(3, min(args.steps, 5))

    def probe(gar_name, r):
        model = HostStragglerModel(n, max(args.deadline * 4, 0.5), rate=1.0,
                                   nb_eligible=r, seed=0)
        cell = run_cell("reweight", 1.0, gar_name, "f32", 100, args,
                        attack="gaussian", nb_real_byz=r,
                        straggler_model=model, steps=steps)
        return cell["loss_decreased"]

    return {
        "at_f_krum_ok": probe("krum", f),
        "at_f_trimmed_ok": probe("trimmed-mean", f),
        "over_f_broken": not probe("trimmed-mean", f + 1),
    }


def run_ef_break(args):
    """Where does EF + NAIVE stale compounding break?  average-nan (no
    robust trim to hide behind) over int8:ef, the persistent laundering
    coalition at a MILDER deviation than the grid's (``--ef-deviation``):
    the frozen-residual workers' attack carries re-enter at full weight for
    up to stale-max-age rounds, so a small age caps the injected mass and
    the loss still decreases, while a large age lets the compounding win.
    ``break_age`` is the smallest scanned age whose loss stopped
    decreasing (null: no break observed in the scan — itself a measured
    answer)."""
    ages = [int(a) for a in args.ef_ages.split(",") if a]
    losses_by_age = {}
    break_age = None
    for age in ages:
        cell = run_cell("naive", 1.0, args.ef_gar, "int8:ef", age, args,
                        attack="gaussian", nb_real_byz=args.nb_byz,
                        deviation=args.ef_deviation)
        losses_by_age[str(age)] = cell["final_loss"]
        if break_age is None and not cell["loss_decreased"]:
            break_age = age
    return {
        "gar": args.ef_gar,
        "ages_scanned": ages,
        "losses_by_age": losses_by_age,
        "break_age": break_age,
    }


def run_submesh(args):
    """The v3 acceptance cell: bounded-wait over the NONTRIVIAL (4, 2, 1)
    mesh — one collective program per worker-axis submesh
    (engine.build_submesh_grad), each with its own deadline.  The first
    submesh's k = 2 workers straggle persistently: the unit forfeits BOTH
    rows every warm round (never one without the other), reweighted stale
    carries re-enter, and the steady state never recompiles."""
    import jax
    import numpy as np
    from jax.sharding import PartitionSpec

    from aggregathor_tpu import gars, models
    from aggregathor_tpu.core import build_optimizer, build_schedule
    from aggregathor_tpu.parallel import RobustEngine, make_mesh
    from aggregathor_tpu.parallel.bounded import (BoundedWaitStep,
                                                  HostStragglerModel)

    W, pipe, model_par = SUBMESH_AXES
    n, f = args.nb_workers, args.nb_byz
    exp = models.instantiate("digits", ["batch-size:%d" % args.batch_size])
    tx = build_optimizer("sgd", build_schedule("fixed", ["initial-rate:0.05"]))
    engine = RobustEngine(
        make_mesh(nb_workers=W, pipeline_parallelism=pipe,
                  model_parallelism=model_par),
        gars.instantiate("krum", n, f), n,
        sharding="sharded", granularity="global",
    )
    k = engine.workers_per_device
    specs = jax.tree.map(lambda _: PartitionSpec(),
                         exp.init(jax.random.PRNGKey(0)))
    state = engine.init_state(exp.init, specs, tx, seed=1)
    model = HostStragglerModel(n, args.stall, rate=1.0, nb_eligible=k, seed=0)
    step = BoundedWaitStep(
        engine, exp.loss, tx, jax.device_get(state.params),
        deadline=args.deadline, straggler_model=model,
        stale_infill=True, stale_max_age=8, stale_reweight=True,
    )
    try:
        losses, _ = _drive(step, state, exp, args,
                           max(3, min(args.steps, 6)))
        tmo = np.asarray(step.timeouts_total)
        cache = step._cache_size()
    finally:
        step.close()
    # forfeit-as-a-unit: the straggling submesh's k members timed out the
    # SAME number of rounds (one collective program — together or not at
    # all), and no other submesh ever timed out
    unit_ok = bool(tmo[:k].min() == tmo[:k].max() and tmo[:k].min() > 0
                   and tmo[k:].sum() == 0)
    return {
        "mesh": "%d,%d,%d" % SUBMESH_AXES,
        "completed": True,
        "unit_forfeit_ok": unit_ok,
        "compile_count_ok": bool(cache == 1),
        "losses_finite": bool(np.isfinite(losses).all()),
        "timeouts_total": int(tmo.sum()),
        "final_loss": float(losses[-1]),
    }


def validate(doc):
    """Schema check for round-tripping consumers (the smoke script and
    tests/test_bounded.py's checked-in-document test)."""
    if doc.get("schema") != SCHEMA:
        raise ValueError("not a %s document" % SCHEMA)
    for key in ("config", "cells", "pairs", "breakdown", "ef_break",
                "submesh", "verdict"):
        if key not in doc:
            raise ValueError("missing %r" % key)
    for cell in doc["cells"]:
        for key in ("arm", "rate", "gar", "exchange", "stale_max_age",
                    "steps_per_s", "losses_finite", "final_loss",
                    "loss_decreased", "timeouts_total", "stale_total",
                    "window_final"):
            if key not in cell:
                raise ValueError("cell missing %r" % key)
        if cell["arm"] not in ARMS:
            raise ValueError("bad arm %r" % cell["arm"])
        if cell["exchange"] not in EXCHANGES:
            raise ValueError("bad exchange %r" % cell["exchange"])
    for pair in doc["pairs"]:
        for key in ("rate", "gar", "exchange", "stale_max_age",
                    "naive_loss", "reweight_loss", "reweight_wins"):
            if key not in pair:
                raise ValueError("pair missing %r" % key)
    for key in ("at_f_krum_ok", "at_f_trimmed_ok", "over_f_broken"):
        if not isinstance(doc["breakdown"].get(key), bool):
            raise ValueError("breakdown missing bool %r" % key)
    for key in ("gar", "ages_scanned", "losses_by_age", "break_age"):
        if key not in doc["ef_break"]:
            raise ValueError("ef_break missing %r" % key)
    for key in ("mesh", "completed", "unit_forfeit_ok", "compile_count_ok",
                "losses_finite"):
        if key not in doc["submesh"]:
            raise ValueError("submesh missing %r" % key)
    for key in ("reweight_beats_naive", "breakdown_holds", "submesh_ok",
                "pass"):
        if not isinstance(doc["verdict"].get(key), bool):
            raise ValueError("verdict missing bool %r" % key)
    return doc


def load(path):
    with open(path) as fd:
        return validate(json.load(fd))


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--steps", type=int, default=10,
                        help="measured steps per cell (after 1 warmup)")
    parser.add_argument("--deadline", type=float, default=0.25,
                        help="fixed ceiling = adaptive initial window")
    parser.add_argument("--stall", type=float, default=0.6,
                        help="straggler stall (seconds, >> every window)")
    parser.add_argument("--percentile", type=float, default=70.0,
                        help="adaptive target arrival percentile "
                             "(<= 100*(n-f-1)/(n-1) so the budgeted "
                             "coalition cannot pin the ceiling)")
    parser.add_argument("--floor", type=float, default=0.02,
                        help="adaptive window floor (seconds)")
    parser.add_argument("--rates", default="0.5,1.0",
                        help="comma-separated straggle rates (grid axis)")
    parser.add_argument("--gars", default="average-nan,krum",
                        help="comma-separated rules (grid axis); the "
                             "verdict judges the averaging-family entries, "
                             "selection rules ride along as the "
                             "robustness-flattens-the-gap contrast")
    parser.add_argument("--deviation", type=float, default=20.0,
                        help="the grid coalition's gaussian attack scale "
                             "(moderate: hurts averaging rules without "
                             "destroying finiteness; the breakdown probe "
                             "keeps its own 10000)")
    parser.add_argument("--exchanges", default="f32,int8:ef",
                        help="comma-separated wire codecs (grid axis)")
    parser.add_argument("--ages", default="2,8",
                        help="comma-separated stale-max-ages (grid axis)")
    parser.add_argument("--ef-ages", default="2,8,32",
                        help="EF break scan's stale-max-ages")
    parser.add_argument("--ef-gar", default="average-nan",
                        help="EF break scan's rule (no robust trim)")
    parser.add_argument("--ef-deviation", type=float, default=5.0,
                        help="EF break scan's coalition attack scale — "
                             "milder than the grid's so the break AGE is "
                             "an interior point of the scan")
    parser.add_argument("--skip-submesh", action="store_true",
                        help="skip the (4,2,1) submesh cell (needs 8 "
                             "devices)")
    parser.add_argument("--nb-workers", type=int, default=8)
    parser.add_argument("--nb-byz", type=int, default=2,
                        help="declared f (the timeout + stale budget)")
    parser.add_argument("--batch-size", type=int, default=8)
    parser.add_argument("--out", default=None, help="write the JSON here")
    args = parser.parse_args(argv)
    rates = [float(r) for r in args.rates.split(",") if r]
    gar_names = [g for g in args.gars.split(",") if g]
    exchanges = [e for e in args.exchanges.split(",") if e]
    for exchange in exchanges:
        if exchange not in EXCHANGES:
            raise SystemExit("unknown exchange %r (know: %s)"
                             % (exchange, ", ".join(EXCHANGES)))
    ages = [int(a) for a in args.ages.split(",") if a]

    cells, pairs = [], []
    for rate in rates:
        for gar_name in gar_names:
            for exchange in exchanges:
                for age in ages:
                    by_arm = {}
                    for arm in ARMS:
                        # the laundering scenario: the straggling coalition
                        # (first f workers) IS the in-budget attack
                        # coalition, so the stale carry holds attack rows
                        cell = run_cell(arm, rate, gar_name, exchange, age,
                                        args, attack="gaussian",
                                        nb_real_byz=args.nb_byz,
                                        deviation=args.deviation)
                        cells.append(cell)
                        by_arm[arm] = cell
                        print("%-8s rate=%.2f %-12s %-7s age=%-3d "
                              "%6.2f steps/s  stale=%-3d final=%.3f %s" % (
                                  cell["arm"], rate, gar_name, exchange, age,
                                  cell["steps_per_s"], cell["stale_total"],
                                  cell["final_loss"],
                                  "finite" if cell["losses_finite"]
                                  else "NON-FINITE"))
                    pairs.append({
                        "rate": rate, "gar": gar_name, "exchange": exchange,
                        "stale_max_age": age,
                        "naive_loss": by_arm["naive"]["final_loss"],
                        "reweight_loss": by_arm["reweight"]["final_loss"],
                        "reweight_wins": bool(
                            by_arm["reweight"]["losses_finite"]
                            and by_arm["reweight"]["final_loss"]
                            < by_arm["naive"]["final_loss"]),
                    })

    breakdown = run_breakdown(args)
    ef_break = run_ef_break(args)
    submesh = ({"mesh": "%d,%d,%d" % SUBMESH_AXES, "completed": False,
                "unit_forfeit_ok": False, "compile_count_ok": False,
                "losses_finite": False, "timeouts_total": 0,
                "final_loss": float("nan")}
               if args.skip_submesh else run_submesh(args))

    # The reweight claim lives at HIGH straggle rates on the rules where
    # the carry actually ENTERS the estimate (the averaging family) — a
    # selection rule like krum just never picks the damped-or-not attack
    # row, flattening both arms to the same loss (itself a grid finding,
    # visible in the krum pairs).  At the top rate the reweighted arm must
    # win the majority of averaging-family (codec x age) pairs AND the
    # mean final loss over them.
    verdict_gars = [g for g in gar_names
                    if g in ("average", "average-nan")] or gar_names
    top = max(rates)
    top_pairs = [p for p in pairs
                 if p["rate"] == top and p["gar"] in verdict_gars]
    wins = [p for p in top_pairs if p["reweight_wins"]]
    mean_naive = (sum(p["naive_loss"] for p in top_pairs)
                  / max(len(top_pairs), 1))
    mean_reweight = (sum(p["reweight_loss"] for p in top_pairs)
                     / max(len(top_pairs), 1))
    reweight_beats = bool(top_pairs
                          and len(wins) * 2 >= len(top_pairs)
                          and mean_reweight < mean_naive)
    breakdown_holds = all(breakdown.values())
    submesh_ok = bool(submesh["completed"] and submesh["unit_forfeit_ok"]
                      and submesh["compile_count_ok"]
                      and submesh["losses_finite"])
    doc = {
        "schema": SCHEMA,
        "generated_at": time.time(),
        "config": {
            "nb_workers": args.nb_workers, "nb_byz": args.nb_byz,
            "deadline": args.deadline, "stall": args.stall,
            "percentile": args.percentile, "floor": args.floor,
            "steps": args.steps, "batch_size": args.batch_size,
            "rates": rates, "gars": gar_names, "exchanges": exchanges,
            "ages": ages, "attack": "gaussian", "deviation": args.deviation,
            "nb_real_byz": args.nb_byz, "verdict_gars": verdict_gars,
            "ef_ages": args.ef_ages, "ef_gar": args.ef_gar,
            "ef_deviation": args.ef_deviation,
            "platform": os.environ.get("JAX_PLATFORMS", ""),
        },
        "cells": cells,
        "pairs": pairs,
        "breakdown": breakdown,
        "ef_break": ef_break,
        "submesh": submesh,
        "top_rate_mean_loss": {"naive": mean_naive,
                               "reweight": mean_reweight},
        "verdict": {
            "reweight_beats_naive": reweight_beats,
            "breakdown_holds": breakdown_holds,
            "submesh_ok": submesh_ok,
            "pass": bool(reweight_beats and breakdown_holds and submesh_ok),
        },
    }
    validate(doc)
    print("breakdown: %s" % breakdown)
    print("ef_break: break_age=%s losses=%s"
          % (ef_break["break_age"], ef_break["losses_by_age"]))
    print("submesh: %s" % submesh)
    print("verdict: reweight_beats_naive=%s (%d/%d %s pairs at rate %.2f, "
          "mean %.3f vs %.3f) breakdown_holds=%s submesh_ok=%s -> %s" % (
              reweight_beats, len(wins), len(top_pairs),
              "/".join(verdict_gars), top,
              mean_reweight, mean_naive, breakdown_holds, submesh_ok,
              "PASS" if doc["verdict"]["pass"] else "FAIL"))
    if args.out:
        with open(args.out, "w") as fd:
            json.dump(doc, fd, indent=1)
            fd.write("\n")
        print("sweep -> %s" % args.out)
    return 0 if doc["verdict"]["pass"] else 1


if __name__ == "__main__":
    sys.exit(main())
