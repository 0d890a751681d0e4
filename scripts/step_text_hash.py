"""Is a cell's program still the program it was?  The sha256 of ONE grid cell's
lowered step text and of its lowered rule probe, for a described (not
attached) ``v5e:2x2``, off the chip.

    JAX_PLATFORMS=cpu python3 scripts/step_text_hash.py --workload NAME [--dump DIR]

The step is what ``grid/run.py`` dispatches (``cell.multi``, the K-step
trainer, at full size); the probe is the harness's ``jax.jit(cell.gar.aggregate)``
on the (n, d) rows one chip aggregates (``grid/run.py`` ``gar_probe``: what
``gar_device_ms`` and ``gar_roofline_pct`` time).  Both texts are the StableHLO
JAX hands the compiler, with ``backend_config`` (a Mosaic kernel's serialized
body, which carries the file and line of every frame that called it) and
``loc(...)`` masked: two trees whose hashes agree hand the compiler the same
operations on the same shapes in the same order, kernels by name.  A change
that must leave a cell alone is held to that by running this on the parent and
on the change and comparing the lines (CHANGES.md, PR 46 and PR 47).

ONE cell a process: lowering several in one process changes a kernel step's
text through JAX's cached helper jaxprs (PERF.md section 7).  ``--dump DIR``
writes the two masked texts there, to diff when a hash moved.  Prints one JSON
line: ``{"workload", "step_sha256", "probe_sha256", "step_bytes",
"probe_bytes", "gradient_path"}``.
"""

import argparse
import hashlib
import json
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "grid"), ROOT]

_MASKS = (
    (re.compile(r'backend_config = "(?:[^"\\]|\\.)*"'), 'backend_config = "..."'),
    (re.compile(r"backend_config = \{[^\n]*"), "backend_config = {...}"),
    (re.compile(r"loc\((?:[^()]|\([^()]*\))*\)"), "loc(...)"),
)


def masked(text):
    """``text`` with every ``backend_config`` and ``loc(...)`` blanked."""
    for pattern, blank in _MASKS:
        text = pattern.sub(blank, text)
    return text


def lowered_texts(workload):
    """(step text, probe text, the engine's gradient path) of ``workload``,
    lowered for the first chip(s) of a described ``v5e:2x2``."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

    from aggregathor_tpu.core.train_state import TrainState
    from cell import Cell, cell_spec
    from rehearse_compile import steer_to_tpu

    topology = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    steer_to_tpu()
    spec = cell_spec(workload)
    config = spec["config_data"]
    cell = Cell(spec, list(topology.devices))
    replicated = NamedSharding(cell.engine.mesh, PartitionSpec())

    def make_state(key):
        state = TrainState.create(
            cell.reference.init(key, config["image_size"], config["classes"]), cell.tx, rng=key)
        return state.replace(loss_ema=jnp.float32(0))

    def described(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=replicated), tree)

    state = described(jax.eval_shape(make_state, jax.random.PRNGKey(0)))
    dataset = described({name: jax.ShapeDtypeStruct(a.shape, a.dtype)
                         for name, a in cell.arrays.items()})
    step = cell.multi.lower(state, dataset).as_text()

    def grid_gar_probe(block):  # grid/run.py gar_probe's wrapper, by its name
        return cell.gar.aggregate(block)

    rows = jax.ShapeDtypeStruct(
        (cell.nb_workers, -(-config["parameters"] // len(cell.devices))), jnp.float32,
        sharding=SingleDeviceSharding(topology.devices[0]))
    probe = jax.jit(grid_gar_probe).lower(rows).as_text()
    return step, probe, getattr(cell.engine, "gradient_path", "rows")


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--dump", default=None, help="directory for the two masked texts")
    args = parser.parse_args()
    step, probe, path = lowered_texts(args.workload)
    line = {"workload": args.workload, "gradient_path": path}
    for name, text in (("step", masked(step)), ("probe", masked(probe))):
        line[name + "_sha256"] = hashlib.sha256(text.encode()).hexdigest()
        line[name + "_bytes"] = len(text)
        if args.dump:
            os.makedirs(args.dump, exist_ok=True)
            with open(os.path.join(args.dump, "%s.%s.txt" % (args.workload, name)), "w") as fd:
                fd.write(text)
    print("step_text_hash %s" % json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
