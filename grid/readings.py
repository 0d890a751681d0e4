"""Readings for the limits of ``correct``, many seeds in one process (set-up is
long, so the program's dozen seeds and the control's are read together):

    python3 grid/readings.py --workload NAME --seeds 1,2,3 [--control-seeds 1,2,3]
        [--swap-seeds 1,2] [--out chiprun_out/readings_NAME.jsonl]

For each seed: the cell's compiled dispatch run once from the seeded state, the
plain reference's steps, and the numbers of check.py.  ``--control-seeds``
reads the control — the program's own ``dtype:bfloat16`` path — and its
verdict against the cell's limits, which has to be false.  ``--swap-seeds``
reads what one near-tie decided the other way is worth under Multi-Krum: the
reference put in the program's place with, at its first step, the last worker
the rule chooses left out for the first it does not.  One JSON line per reading goes to
standard output and, with the per-step numbers, to ``--out``.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

BF16_PATH_ARGS = ("dtype:bfloat16",)
NUMBERS = ("loss_gap", "grad_norm_gap", "dparam_gap", "dparam_own_gap")


def krum_swapped(rows, f):
    """Multi-Krum with the last worker it chooses swapped for the first it
    does not."""
    import jax.numpy as jnp
    import numpy as np

    from rules._distances import krum_scores, pairwise_sq_distances

    n = rows.shape[0]
    order = np.argsort(krum_scores(pairwise_sq_distances(rows), f), kind="stable")
    chosen = list(order[: n - f - 3]) + [order[n - f - 2]]
    weights = np.zeros((n,), np.float32)
    weights[chosen] = 1.0 / len(chosen)
    return jnp.einsum("n,nd->d", jnp.asarray(weights), rows, precision="highest")


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--control-seeds", default="")
    parser.add_argument("--swap-seeds", default="")
    parser.add_argument("--out", default=None)
    args = parser.parse_args()
    listed = lambda text: [int(s) for s in text.split(",") if s]

    import jax

    import check
    from cell import Cell, cell_spec
    from run import require_chips

    from aggregathor_tpu.utils.compile_cache import place_compile_cache

    spec = cell_spec(args.workload)
    devices = jax.devices()
    require_chips(devices, spec["chips"])
    place_compile_cache()
    limits = spec["limits"]["limits"]
    wanted = [name for name in NUMBERS
              if name != "dparam_gap" or spec["limits"]["reference"]["steps"] == "all"]
    cell = Cell(spec, devices)
    reference = check.PlainReference(cell)
    followed = {}

    def reference_steps(seed, later):
        """The reference's steps from ``seed``, kept only while one of the
        ``later`` passes' seeds will ask for them again: a seed's vectors are
        2.4 GB of the host's memory at d = 305 M."""
        steps = followed.pop(seed, None) or reference.follow(seed)
        if seed in later:
            followed[seed] = steps
        return steps

    def emit(reading, steps, record):
        print("grid reading %s" % json.dumps(reading), flush=True)
        if args.out:
            with open(args.out, "a") as fd:
                fd.write(json.dumps(dict(
                    reading, losses=list(record["losses"]), grad_norms=list(record["grad_norms"]),
                    reference_losses=list(steps["losses"]),
                    reference_grad_norms=list(steps["grad_norms"]))) + "\n")

    def read(cell, seeds, what, later):
        cell.feed.start()
        narrow = check.narrow_products(cell, cell.seeded_state(0), cell.feed.next())
        for seed in seeds:
            begin = time.perf_counter()
            state, metrics = cell.multi(cell.seeded_state(seed), cell.feed.next())
            record = check.program_record(metrics, state.params)
            del state, metrics
            program_s = time.perf_counter() - begin
            begin = time.perf_counter()
            steps = reference_steps(seed, later)
            numbers = dict(check.compare(record, steps, wanted), narrow_products=narrow)
            within = check.verdict(numbers, limits)
            emit(dict(numbers, what=what, workload=spec["name"], seed=seed, correct=within,
                      program_s=program_s, reference_s=time.perf_counter() - begin),
                 steps, record)
        cell.feed.close()

    swap_seeds, control_seeds = listed(args.swap_seeds), listed(args.control_seeds)
    read(cell, listed(args.seeds), "program", later=set(swap_seeds + control_seeds))
    for seed in swap_seeds:
        steps = reference_steps(seed, later=set(control_seeds))
        swapped = reference.follow(seed, aggregate=lambda rows, f, step: (
            krum_swapped(rows, f) if step == 0 else reference.rule.aggregate(rows, f)))
        record = check.stand_in_record(swapped)
        emit(dict(check.compare(record, steps, wanted), what="reference_swapped",
                  workload=spec["name"], seed=seed), steps, record)
    if control_seeds:
        del cell
        read(Cell(spec, devices, extra_experiment_args=BF16_PATH_ARGS),
             control_seeds, "control_bf16_path", later=set())


if __name__ == "__main__":
    main()
