"""Compile-only rehearsal: each cell's step program lowered and compiled for a
described (not attached) ``v5e:2x2`` topology at full size, with the compiler's
memory analysis printed.  Costs no chip time; says nothing about results or
times.  Run it from the checkout's root on a machine with no TPU:

    JAX_PLATFORMS=cpu python3 grid/rehearse_compile.py [--workload NAME ...]
        [--batch-per-worker B]   # try another per-worker batch (ResNet-50 sizing)

The program asks ``utils.hw.on_tpu()`` which GAR tier and which Pallas mode to
take; here that answer is steered to "TPU" from outside, so that the program
compiled is the one the chip runs (Mosaic kernels included).
"""

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def steer_to_tpu():
    import importlib

    for name in ("aggregathor_tpu.utils.hw", "aggregathor_tpu.gars.common",
                 "aggregathor_tpu.ops.pallas_kernels", "aggregathor_tpu.parallel.engine"):
        module = importlib.import_module(name)
        if hasattr(module, "on_tpu"):
            module.on_tpu = lambda: True


def compile_cell(spec, topology_devices):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from aggregathor_tpu.core.train_state import TrainState
    from cell import Cell

    cell = Cell(spec, topology_devices)
    replicated = NamedSharding(cell.engine.mesh, PartitionSpec())

    def make_state(key):
        state = TrainState.create(cell.reference.init(
            key, spec["config_data"]["image_size"], spec["config_data"]["classes"]),
            cell.tx, rng=key)
        return state.replace(loss_ema=jnp.float32(0))

    def described(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=replicated), tree)

    state = described(jax.eval_shape(make_state, jax.random.PRNGKey(0)))
    dataset = described({name: jax.ShapeDtypeStruct(a.shape, a.dtype)
                         for name, a in cell.arrays.items()})
    begin = time.perf_counter()
    compiled = cell.multi.lower(state, dataset).compile()
    analysis = compiled.memory_analysis()
    text = compiled.as_text()
    return {
        "workload": spec["name"], "chips": spec["chips"],
        "batch_per_worker": spec["config_data"]["batch_per_worker"],
        "compile_s": time.perf_counter() - begin,
        "argument_bytes": analysis.argument_size_in_bytes,
        "output_bytes": analysis.output_size_in_bytes,
        "temp_bytes": analysis.temp_size_in_bytes,
        "alias_bytes": analysis.alias_size_in_bytes,
        "live_bytes_estimate": analysis.argument_size_in_bytes + analysis.output_size_in_bytes
        + analysis.temp_size_in_bytes - analysis.alias_size_in_bytes,
        "mosaic_calls": text.count("tpu_custom_call"),
        "collectives": {name: text.count(" %s(" % name) + text.count(" %s-start(" % name)
                        for name in ("all-to-all", "all-reduce", "all-gather")},
    }


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", action="append", default=None)
    parser.add_argument("--batch-per-worker", type=int, default=None)
    args = parser.parse_args()

    from cell import cell_spec, load_json, ROOT

    names = args.workload or [w["name"] for w in load_json(
        os.path.join(ROOT, "BENCHMARK.json"), "manifest")["workloads"]]
    from jax.experimental import topologies

    topology = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    steer_to_tpu()
    for name in names:
        spec = cell_spec(name)
        if args.batch_per_worker:
            config = spec["config_data"]
            config["batch_per_worker"] = args.batch_per_worker
            config["experiment_args"] = ["batch-size:%d" % args.batch_per_worker] + [
                a for a in config["experiment_args"] if not a.startswith("batch-size:")]
        print("grid rehearse %s" % json.dumps(compile_cell(spec, list(topology.devices))),
              flush=True)


if __name__ == "__main__":
    main()
