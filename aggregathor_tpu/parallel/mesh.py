"""Device mesh construction.

The reference greedily allocates TF devices to worker/ps/eval roles across
tasks (cluster.py:147-221).  On TPU the device topology is static and the
allocation problem collapses to axis sizing: an ``n_workers``-wide ``worker``
axis (data parallelism across Byzantine workers) optionally times a ``model``
axis (tensor parallelism within each worker, for models that shard).

``jax.make_mesh`` lays axes out so that the fastest-varying axis rides ICI
neighbours; multi-host (DCN) meshes come from JAX's multi-process runtime
(`jax.distributed.initialize`) with the same axis names — nothing in the
engine changes between one chip and a multi-host pod.
"""

import jax

from .. import config
from ..obs import trace

worker_axis = config.worker_axis
pipe_axis = config.pipe_axis
model_axis = config.model_axis


@trace.startup("startup.mesh")
def make_mesh(nb_workers=None, model_parallelism=1, pipeline_parallelism=1, devices=None):
    """Build a Mesh with axes ``(worker, pipe, model)``.

    Args:
      nb_workers: size of the worker axis; defaults to all devices divided by
        ``model_parallelism * pipeline_parallelism``.
      model_parallelism: size of the tensor-parallel axis inside each stage
        (sequence and expert parallelism ride this axis too).
      pipeline_parallelism: number of pipeline stages inside each worker.
      devices: explicit device list (defaults to ``jax.devices()``).
    Returns:
      ``jax.sharding.Mesh`` with named axes (worker, pipe, model).
    """
    devices = list(devices if devices is not None else jax.devices())
    per_worker = model_parallelism * pipeline_parallelism
    if nb_workers is None:
        nb_workers = len(devices) // per_worker
    need = nb_workers * per_worker
    if need > len(devices):
        from ..utils import UserException

        raise UserException(
            "Mesh needs %d devices (%d workers x %d pipe x %d model) but only %d are available"
            % (need, nb_workers, pipeline_parallelism, model_parallelism, len(devices))
        )
    # Auto axes, on purpose: every collective in the engine is hand-placed
    # under ``shard_map(check_vma=False)`` and nothing reasons about
    # sharding-in-types.  ``jax.make_mesh`` defaults to Explicit axes, under
    # which avals carry ``@worker`` — an extra steady-state compile of the
    # bounded-wait aggregate and a ShardingTypeError out of ``jnp.nanmedian``
    # (tests/test_engine.py::test_mesh_axes_are_auto pins the choice).
    return jax.make_mesh(
        (nb_workers, pipeline_parallelism, model_parallelism),
        (worker_axis, pipe_axis, model_axis),
        axis_types=(jax.sharding.AxisType.Auto,) * 3,
        devices=devices[:need],
    )


def factor_devices(n_devices):
    """Split ``n_devices`` into (workers, pipe, model) axis sizes.

    Used by the multi-chip dry run to always exercise every parallelism axis
    the device count allows: the odd part widens the worker axis, then the
    factors of two go round-robin to the axes that are still 1 — so even
    counts always light up at least a second axis. 8 -> (2, 2, 2),
    4 -> (2, 2, 1), 6 -> (3, 2, 1), 12 -> (3, 2, 2), 2 -> (2, 1, 1).
    """
    sizes = [1, 1, 1]
    remaining = int(n_devices)
    while remaining % 2 == 0:
        remaining //= 2
        sizes[0] *= 2
    odd, twos = remaining, sizes[0]
    sizes = [odd, 1, 1]
    slot = 1 if odd > 1 else 0
    while twos > 1:
        sizes[slot] *= 2
        twos //= 2
        slot = (slot + 1) % 3
    return tuple(sizes)
