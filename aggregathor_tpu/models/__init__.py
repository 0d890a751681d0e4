"""Experiments: model + dataset plugins.

An experiment bundles a model family with its input pipeline and evaluation
metrics, mirroring the reference's ``_Experiment`` contract —
``__init__(args)``, per-worker ``losses``, ``accuracy`` returning a dict of
name -> value (reference: experiments/__init__.py:40-71) — re-expressed
functionally for JAX:

- ``init(rng)``                  -> parameter pytree (one canonical copy;
                                    sharing across workers is automatic since
                                    SPMD replicates params, the equivalent of
                                    the reference's AUTO_REUSE variable scopes,
                                    experiments/mnist.py:83-104)
- ``loss(params, batch)``        -> scalar (per-worker; vmapped by the engine)
- ``metrics(params, batch)``     -> dict name -> (sum, count) accumulators
- ``make_train_iterator(...)``   -> infinite worker-major batch iterator
- ``make_eval_iterator(...)``    -> finite epoch over the held-out split

Experiments self-register by name at import time (reference:
experiments/__init__.py:76-85).
"""

from ..obs import trace
from ..utils import ClassRegister, import_directory

experiments = ClassRegister("experiment")


def register(name, cls):
    return experiments.register(name, cls)


def itemize():
    return experiments.itemize()


def get(name):
    """The experiment class registered under ``name`` (not instantiated)."""
    return experiments.get(name)


def instantiate(name, args=None):
    """Build the experiment registered under ``name`` from key:value args: a
    ``startup.experiment`` of the start-up record (obs/trace.py), with the
    data set's ``startup.data_host`` inside it."""
    with trace.startup("startup.experiment", experiment=name):
        return experiments.get(name)(args or [])


class Experiment:
    """Base experiment (see module docstring for the contract)."""

    #: True if the experiment publishes the sharded-engine hooks the CLI's
    #: ``--mesh`` path needs: ``sharded_init(n_stages) -> (key -> params)``,
    #: ``sharded_specs() -> PartitionSpec pytree``, and
    #: ``sharded_loss(n_stages, microbatches) -> shard_map local-partial
    #: loss``.  See models/transformer.py for the reference implementation.
    supports_sharded = False

    def __init__(self, args):
        self.args = args

    def init(self, rng):
        raise NotImplementedError

    def loss(self, params, batch):
        raise NotImplementedError

    def metrics(self, params, batch):
        raise NotImplementedError

    def predict_logits(self, params, x):
        """The inference apply path: ``(params, (B, *sample_shape)) -> (B,
        classes)`` logits.  This is the single hook ``serve/engine.py`` jits —
        the training-only heads (aux logits, label smoothing, weight decay)
        never enter the serving graph.  Default: the bare ``model.apply``,
        which is the logits path for every bundled experiment family (mnist/
        digits MLPs, cnnet, the zoo); experiments whose apply signature
        differs override this.
        """
        model = getattr(self, "model", None)
        if model is None:
            raise NotImplementedError(
                "Experiment %r keeps no .model; override predict_logits()"
                % type(self).__name__
            )
        return model.apply(params, x)

    def make_train_iterator(self, nb_workers, seed=0):
        raise NotImplementedError

    def make_eval_iterator(self, nb_workers):
        raise NotImplementedError

    def device_transform(self):
        """Optional jnp train-batch transform run INSIDE the jitted step.

        Experiments that support ``augment:device`` return the in-step
        augmentation here (models/preprocessing.py ``device_transform``) and
        leave their host iterator transform-free; the engine applies it per
        worker with (seed, step, worker)-keyed randomness.  Default: the
        in-step tier of ``self.preprocessing`` when the experiment opted
        into ``augment:device`` (the cnnet/zoo convention: ``self.augment``
        is ``"host"`` or ``"device"``); none otherwise.
        """
        if getattr(self, "augment", "host") != "device":
            return None
        from .preprocessing import device_transform

        return device_transform(self.preprocessing)

    def train_arrays(self):
        """Optional array-backed training corpus for DEVICE-SIDE sampling.

        Returns the full training split as a batch-structured pytree (same
        keys as ``make_train_iterator``'s batches, leading axis = examples)
        when — and only when — a uniform in-graph row gather reproduces the
        iterator's stream semantics: i.i.d.-with-replacement draws and NO
        host-side transform (poisoning, host augmentation, windowing).
        ``None`` (the default) keeps the experiment on the streaming path.

        Consumers: ``RobustEngine.build_sampled_multi_step`` and the CLI's
        ``--input-source device`` — a dataset transferred once removes the
        per-step host->device transfer.

        Default: the ``self.dataset`` train split for experiments whose
        host input path is a plain gather — augmentation moved in-step
        (``augment:device``) or a host tier that is the identity
        (``preprocessing:none``/``lenet``); None otherwise (a stateful host
        transform — augmentation streams, poisoning — must see every batch).
        """
        augment = getattr(self, "augment", None)
        if augment == "device":
            eligible = True
        elif augment == "host":
            from .preprocessing import PREPROCESSING, none_preprocessing

            eligible = (
                PREPROCESSING.get(getattr(self, "preprocessing", None))
                is none_preprocessing
            )
        else:
            eligible = False
        if not eligible:
            return None
        dataset = getattr(self, "dataset", None)
        if dataset is None:
            return None
        return {"image": dataset.x_train, "label": dataset.y_train}

    def route_augmentation_to_device(self):
        """Move a host-tier augmentation to its in-step device twin
        (models/preprocessing.py ``DEVICE_PREPROCESSING``), making the host
        input path a plain gather so DEVICE-RESIDENT sampling
        (``train_arrays`` + ``RobustEngine.build_sampled_multi_step``) can
        serve augmented training too.  Returns True when the experiment now
        augments in-step (or already did); False when it has no
        re-routable augmentation machinery — a stateful non-augmentation
        transform (poisoning, streaming corpus) stays host-bound and
        ``train_arrays`` keeps returning None.  Note the augmentation
        STREAM changes (numpy per-worker generators -> in-step
        (seed, step, worker) keys) — same distribution, different draws,
        exactly like the device sampling it enables."""
        if getattr(self, "augment", None) == "device":
            return True
        name = getattr(self, "preprocessing", None)
        if getattr(self, "augment", None) != "host" or name is None:
            return False
        from .preprocessing import DEVICE_PREPROCESSING

        if name not in DEVICE_PREPROCESSING:
            return False
        self.augment = "device"
        return True


import_directory(__name__, __path__, skip=("datasets",))
