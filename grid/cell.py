"""One cell's system under test, built from its data files the way
``cli.runner`` builds it: experiment, rule, mesh, engine, optimizer, and the
feed that hands each dispatch of the K-step trainer its data.

Everything that belongs to one configuration, traffic mix, input source, model
family, rule, optimizer or per-layer metric is a file found by the name
``BENCHMARK.json`` or the cell's data files give; a missing one fails by name.
"""

import importlib.util
import json
import os
import sys

GRID = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(GRID)
if GRID not in sys.path:  # references/ and rules/ import their siblings by name
    sys.path.insert(0, GRID)
if ROOT not in sys.path:  # the system under test
    sys.path.insert(1, ROOT)


def load_module(kind, name):
    """``grid/<kind>/<name>.py`` as a module."""
    path = os.path.join(GRID, kind, name + ".py")
    if not os.path.exists(path):
        raise SystemExit("no %s named %r: expected the file %s" % (kind, name, path))
    spec = importlib.util.spec_from_file_location("%s.%s" % (kind, name), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_json(path, what):
    if not os.path.exists(path):
        raise SystemExit("no %s: expected the file %s" % (what, path))
    with open(path) as fd:
        return json.load(fd)


def cell_spec(workload, manifest_path=os.path.join(ROOT, "BENCHMARK.json")):
    """The manifest's entry for ``workload`` with its configuration, traffic
    mix and limits loaded beside it."""
    manifest = load_json(manifest_path, "manifest")
    cells = {cell["name"]: cell for cell in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit("no workload %r in the manifest; it has: %s"
                         % (workload, ", ".join(sorted(cells))))
    cell = dict(cells[workload])
    configs = {config["name"]: config for config in manifest["configs"]}
    if cell["config"] not in configs:
        raise SystemExit("workload %r names the configuration %r, which the "
                         "manifest lacks" % (workload, cell["config"]))
    cell["config_data"] = load_json(os.path.join(ROOT, configs[cell["config"]]["file"]),
                                    "configuration %r" % cell["config"])
    cell["traffic_data"] = load_json(
        os.path.join(GRID, "traffic", cell["traffic"] + ".json"),
        "traffic mix %r" % cell["traffic"])
    cell["limits"] = load_json(os.path.join(GRID, "limits", workload + ".json"),
                               "limits of %r" % workload)
    cell["manifest"] = manifest
    return cell


def peaks(device_kind):
    """Published per-chip peaks of ``device_kind``; an unknown kind raises."""
    table = load_json(os.path.join(GRID, "peaks.json"), "table of peaks")
    if device_kind not in table:
        raise SystemExit("no published peaks for device_kind %r in grid/peaks.json "
                         "(it has: %s)" % (device_kind, ", ".join(sorted(table))))
    return table[device_kind]


def flops_per_step(cell):
    """Model FLOPs of one step: forward and backward (x3) of every image, two
    per multiply-accumulate.  The rule's own operations are not model FLOPs."""
    config = cell["config_data"]
    macs = load_module("flops", config["family"]).forward_macs(
        config["image_size"], config["classes"])
    return 2 * 3 * macs * config["nb_workers"] * config["batch_per_worker"]


class Cell:
    """The built system: ``multi(state, dataset) -> (state, metrics)`` runs
    ``unroll`` steps in one dispatch and donates ``state``."""

    def __init__(self, spec, devices, extra_experiment_args=()):
        import jax

        from aggregathor_tpu import gars, models
        from aggregathor_tpu.core import build_optimizer, build_schedule
        from aggregathor_tpu.parallel import RobustEngine, attacks, make_mesh

        config, traffic = spec["config_data"], spec["traffic_data"]
        self.spec = spec
        self.nb_workers = config["nb_workers"]
        self.nb_byz = config["nb_decl_byz_workers"]
        self.unroll = traffic["unroll"]
        self.reference = load_module("references", config["family"])
        self.experiment = models.instantiate(
            config["experiment"], list(config["experiment_args"]) + list(extra_experiment_args))
        if self.experiment.batch_size != config["batch_per_worker"]:
            raise SystemExit("configuration %r: batch_per_worker %r but the experiment "
                             "arguments give %r" % (spec["config"], config["batch_per_worker"],
                                                    self.experiment.batch_size))
        self.arrays = self.experiment.train_arrays()  # host arrays, or None
        gar = gars.instantiate(traffic["aggregator"], self.nb_workers, self.nb_byz)
        nb_real_byz = traffic["nb_real_byz_workers"]
        attack = (attacks.instantiate(traffic["attack"], self.nb_workers, nb_real_byz)
                  if traffic["attack"] else None)
        self.devices = list(devices[:spec["chips"]])  # the mesh is as wide as the cell's chips
        self.engine = RobustEngine(
            make_mesh(nb_workers=len(self.devices), devices=self.devices), gar, self.nb_workers,
            nb_real_byz=nb_real_byz, attack=attack,
            batch_transform=self.experiment.device_transform())
        self.gar = gar
        self.tx = build_optimizer(
            config["optimizer"],
            build_schedule(config["learning_rate"], config["learning_rate_args"]),
            config["optimizer_args"])
        self.feed = load_module("feeds", traffic["input_source"]).Feed(self)
        self.multi = self.feed.multi
        self._init = jax.jit(lambda key: self.reference.init(
            key, config["image_size"], config["classes"]))
        key = jax.random.PRNGKey(0)
        shapes = lambda init: jax.tree.map(lambda a: a.shape, jax.eval_shape(init, key))
        if shapes(self.experiment.init) != shapes(self._init):
            raise SystemExit("the plain reference %r and the experiment %r disagree on the "
                             "parameters' shapes" % (config["family"], config["experiment"]))

    def seeded_params(self, seed):
        """The run's first parameters: one jitted call on the device, from the
        seed, in the reference's own initialiser (the program gets them as its
        input; the reference makes the same ones for itself)."""
        import jax

        return self._init(jax.random.PRNGKey(seed))

    def seeded_state(self, seed):
        return self.engine.init_state(self.seeded_params(seed), self.tx, seed=seed)
