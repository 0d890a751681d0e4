"""Compression sweep: which GARs keep their breakdown point on a lossy wire.

The campaign harness exists to answer research-grade questions; this sweep
asks the one the compressed exchange (parallel/compress.py, docs/engine.md
"The wire") opens: **which rules survive which bit-widths, against which
attacks, on which data distributions** — and what the bytes actually cost.
Grid over exchange x rule x attack x IID/non-IID shards, every cell on the
REAL fused engine (digits MLP, n=8, f=2):

- ``exchange``   f32 (the uncompressed wire), bf16 (the dtype twin), int8
                 (per-row symmetric quantization), topk (magnitude top-k
                 with error feedback — the biased-without-EF codec);
- ``gar``        average (the f=0 baseline every attack poisons) and krum
                 (the selection rule whose breakdown point is the claim);
- ``attack``     none / gaussian (coalition of r=f, deviation 10000);
- ``shards``     iid (every worker samples the full corpus) / noniid
                 (label-sorted contiguous shards: honest gradients
                 legitimately disagree — the regime where distance-based
                 selection is weakest, and where quantization noise eats
                 the remaining margin first).

Per cell: steps/s, final loss, bytes-per-step on the wire and the
compression ratio (static accounting — ``compress.bytes_per_row``).  The
**breakdown probe** re-checks the r = f boundary per bit-width: krum must
converge at r = f under the attack (the property survives the wire) while
average is poisoned by the same coalition.  The **incremental cell** runs
the bounded-wait protocol with ``incremental=True`` under a straggler
regime and reports the measured ``overlap_fraction`` (folds issued while
submissions were still outstanding).

Output schema ``aggregathor.compress.sweep.v1``::

    {schema, generated_at, config: {...},
     cells: [{exchange, gar, attack, shards, steps_per_s, final_loss,
              losses_finite, loss_decreased, bytes_per_step,
              compression_ratio}...],
     breakdown: {exchange: {at_f_krum_ok, at_f_average_broken}},
     incremental: {exchange, overlap_fraction, steps_per_s,
                   timeouts_total, losses_finite},
     verdict: {int8_ratio_ok, int8_equal_loss, breakdown_by_exchange,
               overlap_nonzero, pass}}

Usage::

    python benchmarks/compress_sweep.py [--steps 12] [--out COMPRESS_r14.json]
        [--exchanges f32,bf16,int8,topk] [--shards iid,noniid]
"""

import argparse
import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SCHEMA = "aggregathor.compress.sweep.v1"

EXCHANGES = ("f32", "bf16", "int8", "topk")
#: the CLI spec each sweep arm maps to (topk: 1/16 of coordinates + EF)
EXCHANGE_SPECS = {
    "f32": "f32",
    "bf16": "bf16",
    "int8": "int8",
    "topk": "topk:frac=0.0625,ef",
}
GARS = ("average", "krum")
ATTACKS = (None, "gaussian")
SHARDS = ("iid", "noniid")

#: equal-final-loss tolerance of the compressed-vs-f32 comparison (the
#: campaign's convergence tolerance: quantized trajectories legitimately
#: differ step by step, the claim is about where they land)
LOSS_RTOL = 0.10
LOSS_ATOL = 0.5


class ShardIterator:
    """Worker-major batches from per-worker shards.

    ``noniid``: the corpus is label-sorted and cut into n contiguous
    shards, so each worker's gradient estimates a label-skewed loss —
    honest disagreement by construction.  ``iid`` gives every worker the
    whole corpus (the ``WorkerBatchIterator`` stream shape, reimplemented
    here so both arms flow through identical code)."""

    def __init__(self, x, y, nb_workers, batch_size, noniid, seed=0):
        import numpy as np

        if noniid:
            order = np.argsort(y, kind="stable")
            x, y = x[order], y[order]
        bounds = np.linspace(0, len(y), nb_workers + 1).astype(int)
        self.shards = (
            [(x[a:b], y[a:b]) for a, b in zip(bounds[:-1], bounds[1:])]
            if noniid else [(x, y)] * nb_workers
        )
        self.batch_size = batch_size
        self.rngs = [np.random.default_rng([seed, w]) for w in range(nb_workers)]

    def __iter__(self):
        return self

    def __next__(self):
        import numpy as np

        images, labels = [], []
        for (sx, sy), rng in zip(self.shards, self.rngs):
            idx = rng.integers(0, len(sy), size=self.batch_size)
            images.append(sx[idx])
            labels.append(sy[idx])
        return {"image": np.stack(images), "label": np.stack(labels)}


def build_stack(args, exchange, gar_name, attack, nb_real_byz):
    import jax

    from aggregathor_tpu import gars, models
    from aggregathor_tpu.core import build_optimizer, build_schedule
    from aggregathor_tpu.parallel import RobustEngine, attacks, make_mesh

    n, f = args.nb_workers, args.nb_byz
    exp = models.instantiate("digits", ["batch-size:%d" % args.batch_size])
    gar = gars.instantiate(gar_name, n, f)
    tx = build_optimizer("sgd", build_schedule("fixed", ["initial-rate:0.05"]))
    atk = (attacks.instantiate(attack, n, nb_real_byz, ["deviation:10000.0"])
           if attack else None)
    engine = RobustEngine(
        make_mesh(nb_workers=1), gar, n, attack=atk, nb_real_byz=nb_real_byz,
        exchange=EXCHANGE_SPECS[exchange],
    )
    state = engine.init_state(exp.init(jax.random.PRNGKey(0)), tx, seed=1)
    return exp, engine, tx, state


def run_cell(args, exchange, gar_name, attack, shards, nb_real_byz=0,
             steps=None):
    import jax
    import numpy as np

    from aggregathor_tpu.parallel import compress

    exp, engine, tx, state = build_stack(args, exchange, gar_name, attack,
                                         nb_real_byz)
    step = engine.build_step(exp.loss, tx)
    it = ShardIterator(exp.dataset.x_train, exp.dataset.y_train,
                       args.nb_workers, args.batch_size,
                       noniid=shards == "noniid", seed=3)
    d = sum(int(np.prod(leaf.shape))
            for leaf in jax.tree_util.tree_leaves(state.params))
    steps = steps or args.steps
    losses = []
    state, m = step(state, engine.shard_batch(next(it)))  # compile round
    losses.append(float(jax.device_get(m["total_loss"])))
    begin = time.perf_counter()
    for _ in range(steps):
        state, m = step(state, engine.shard_batch(next(it)))
        losses.append(float(jax.device_get(m["total_loss"])))
    jax.block_until_ready(state.params)
    elapsed = time.perf_counter() - begin
    return {
        "exchange": exchange,
        "gar": gar_name,
        "attack": attack or "none",
        "shards": shards,
        "steps_per_s": steps / elapsed,
        "losses_finite": bool(np.isfinite(losses).all()),
        "final_loss": float(losses[-1]),
        "loss_decreased": bool(np.isfinite(losses).all()
                               and losses[-1] < losses[0]),
        "bytes_per_step": args.nb_workers * compress.bytes_per_row(
            d, dtype=engine.exchange_dtype, codec=engine.codec),
        "compression_ratio": compress.compression_ratio(
            d, dtype=engine.exchange_dtype, codec=engine.codec),
    }


def run_breakdown(args, exchange):
    """The r = f boundary under this bit-width: krum (sized for f) must
    converge against the r = f gaussian coalition ON THE QUANTIZED WIRE,
    while average — with no Byzantine budget at all — is poisoned by the
    same coalition.  "Survives the bit-width" = both hold."""
    at_f = run_cell(args, exchange, "krum", "gaussian", "iid",
                    nb_real_byz=args.nb_byz,
                    steps=max(4, min(args.steps, 8)))
    baseline = run_cell(args, exchange, "average", "gaussian", "iid",
                        nb_real_byz=args.nb_byz,
                        steps=max(4, min(args.steps, 8)))
    return {
        "at_f_krum_ok": at_f["loss_decreased"],
        "at_f_average_broken": not baseline["loss_decreased"],
    }


def run_incremental(args, exchange="int8"):
    """Bounded-wait + incremental fold under a straggler regime: the
    overlap_fraction gauge must read nonzero (decode work really lands
    while submissions are outstanding)."""
    import jax
    import numpy as np

    from aggregathor_tpu.parallel.bounded import (
        BoundedWaitStep,
        HostStragglerModel,
    )

    exp, engine, tx, state = build_stack(args, exchange, "krum", None, 0)
    model = HostStragglerModel(args.nb_workers, args.deadline * 2.0,
                               rate=1.0, nb_eligible=args.nb_byz, seed=0)
    step = BoundedWaitStep(
        engine, exp.loss, tx, jax.device_get(state.params),
        deadline=args.deadline, straggler_model=model, incremental=True,
    )
    it = ShardIterator(exp.dataset.x_train, exp.dataset.y_train,
                       args.nb_workers, args.batch_size, noniid=False, seed=3)
    steps = max(4, min(args.steps, 8))
    losses = []
    try:
        state, m = step(state, next(it))  # compile round, deadline off
        losses.append(float(jax.device_get(m["total_loss"])))
        begin = time.perf_counter()
        for _ in range(steps):
            state, m = step(state, next(it))
            losses.append(float(jax.device_get(m["total_loss"])))
        elapsed = time.perf_counter() - begin
        overlap = (step.overlapped_folds_total / step.folds_total
                   if step.folds_total else 0.0)
        timeouts = int(step.timeouts_total.sum())
    finally:
        step.close()
    return {
        "exchange": exchange,
        "overlap_fraction": overlap,
        "steps_per_s": steps / elapsed,
        "timeouts_total": timeouts,
        "losses_finite": bool(np.isfinite(losses).all()),
    }


def validate(doc):
    """Schema check for round-tripping consumers (the smoke script and
    tests/test_compress.py's checked-in-document test)."""
    if doc.get("schema") != SCHEMA:
        raise ValueError("not a %s document" % SCHEMA)
    for key in ("config", "cells", "breakdown", "incremental", "verdict"):
        if key not in doc:
            raise ValueError("missing %r" % key)
    for cell in doc["cells"]:
        for key in ("exchange", "gar", "attack", "shards", "steps_per_s",
                    "losses_finite", "final_loss", "loss_decreased",
                    "bytes_per_step", "compression_ratio"):
            if key not in cell:
                raise ValueError("cell missing %r" % key)
        if cell["exchange"] not in EXCHANGES:
            raise ValueError("bad exchange %r" % cell["exchange"])
        if cell["shards"] not in SHARDS:
            raise ValueError("bad shards %r" % cell["shards"])
    for exchange, probe in doc["breakdown"].items():
        if exchange not in EXCHANGES:
            raise ValueError("bad breakdown exchange %r" % exchange)
        for key in ("at_f_krum_ok", "at_f_average_broken"):
            if not isinstance(probe.get(key), bool):
                raise ValueError("breakdown[%s] missing bool %r" % (exchange, key))
    for key in ("overlap_fraction", "steps_per_s", "timeouts_total",
                "losses_finite"):
        if key not in doc["incremental"]:
            raise ValueError("incremental missing %r" % key)
    for key in ("int8_ratio_ok", "int8_equal_loss", "overlap_nonzero", "pass"):
        if not isinstance(doc["verdict"].get(key), bool):
            raise ValueError("verdict missing bool %r" % key)
    return doc


def load(path):
    with open(path) as fd:
        return validate(json.load(fd))


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--steps", type=int, default=12,
                        help="measured steps per cell (after 1 compile step)")
    parser.add_argument("--exchanges", default=",".join(EXCHANGES))
    parser.add_argument("--gars", default=",".join(GARS))
    parser.add_argument("--shards", default=",".join(SHARDS))
    parser.add_argument("--skip-attacks", action="store_true",
                        help="grid only the attack-free cells (the "
                             "breakdown probe still runs)")
    parser.add_argument("--deadline", type=float, default=0.25,
                        help="incremental cell's bounded-wait deadline")
    parser.add_argument("--nb-workers", type=int, default=8)
    parser.add_argument("--nb-byz", type=int, default=2)
    parser.add_argument("--batch-size", type=int, default=8)
    parser.add_argument("--out", default=None, help="write the JSON here")
    args = parser.parse_args(argv)
    exchanges = [e for e in args.exchanges.split(",") if e]
    for e in exchanges:
        if e not in EXCHANGES:
            raise SystemExit("unknown exchange %r (know: %s)"
                             % (e, ", ".join(EXCHANGES)))
    gars_sel = [g for g in args.gars.split(",") if g]
    shards_sel = [s for s in args.shards.split(",") if s]
    attacks_sel = (None,) if args.skip_attacks else ATTACKS

    cells = []
    for shards in shards_sel:
        for gar_name in gars_sel:
            for attack in attacks_sel:
                for exchange in exchanges:
                    cell = run_cell(
                        args, exchange, gar_name, attack, shards,
                        nb_real_byz=args.nb_byz if attack else 0,
                    )
                    cells.append(cell)
                    print("%-5s %-8s %-9s %-7s %6.2f steps/s  "
                          "%8d B/step (%.2fx)  final=%-8.3f %s" % (
                              cell["exchange"], cell["gar"], cell["attack"],
                              cell["shards"], cell["steps_per_s"],
                              cell["bytes_per_step"],
                              cell["compression_ratio"], cell["final_loss"],
                              "finite" if cell["losses_finite"]
                              else "NON-FINITE"))

    breakdown = {e: run_breakdown(args, e) for e in exchanges}
    for e, probe in breakdown.items():
        print("breakdown[%s]: krum@f ok=%s, average@f broken=%s"
              % (e, probe["at_f_krum_ok"], probe["at_f_average_broken"]))
    incremental = run_incremental(
        args, "int8" if "int8" in exchanges else exchanges[0])
    print("incremental[%s]: overlap=%.2f  %0.2f steps/s  timeouts=%d" % (
        incremental["exchange"], incremental["overlap_fraction"],
        incremental["steps_per_s"], incremental["timeouts_total"]))

    def pick(exchange, gar_name, attack, shards):
        return next(
            (c for c in cells if c["exchange"] == exchange
             and c["gar"] == gar_name and c["attack"] == attack
             and c["shards"] == shards), None)

    # the headline claim: int8 ships >= 3.5x fewer bytes than f32 AND
    # lands at the same final loss (campaign tolerance) on >= 1 cell
    int8_ratio_ok = False
    int8_equal_loss = False
    for shards in shards_sel:
        for gar_name in gars_sel:
            ref = pick("f32", gar_name, "none", shards)
            q = pick("int8", gar_name, "none", shards)
            if ref is None or q is None:
                continue
            int8_ratio_ok = int8_ratio_ok or q["compression_ratio"] >= 3.5
            int8_equal_loss = int8_equal_loss or (
                q["losses_finite"]
                and abs(q["final_loss"] - ref["final_loss"])
                <= LOSS_RTOL * abs(ref["final_loss"]) + LOSS_ATOL
            )
    doc = {
        "schema": SCHEMA,
        "generated_at": time.time(),
        "config": {
            "nb_workers": args.nb_workers, "nb_byz": args.nb_byz,
            "batch_size": args.batch_size, "steps": args.steps,
            "deadline": args.deadline, "exchanges": exchanges,
            "exchange_specs": {e: EXCHANGE_SPECS[e] for e in exchanges},
            "gars": gars_sel, "shards": shards_sel,
            "loss_rtol": LOSS_RTOL, "loss_atol": LOSS_ATOL,
            "platform": os.environ.get("JAX_PLATFORMS", ""),
        },
        "cells": cells,
        "breakdown": breakdown,
        "incremental": incremental,
        "verdict": {
            "int8_ratio_ok": bool(int8_ratio_ok),
            "int8_equal_loss": bool(int8_equal_loss),
            "breakdown_by_exchange": {
                e: bool(probe["at_f_krum_ok"] and probe["at_f_average_broken"])
                for e, probe in breakdown.items()
            },
            "overlap_nonzero": bool(incremental["overlap_fraction"] > 0),
            "pass": bool(int8_ratio_ok and int8_equal_loss
                         and incremental["overlap_fraction"] > 0),
        },
    }
    validate(doc)
    print("verdict: int8_ratio_ok=%s int8_equal_loss=%s overlap_nonzero=%s "
          "breakdown=%s -> %s" % (
              doc["verdict"]["int8_ratio_ok"],
              doc["verdict"]["int8_equal_loss"],
              doc["verdict"]["overlap_nonzero"],
              doc["verdict"]["breakdown_by_exchange"],
              "PASS" if doc["verdict"]["pass"] else "FAIL"))
    if args.out:
        with open(args.out, "w") as fd:
            json.dump(doc, fd, indent=1)
            fd.write("\n")
        print("sweep -> %s" % args.out)
    return 0 if doc["verdict"]["pass"] else 1


if __name__ == "__main__":
    sys.exit(main())
