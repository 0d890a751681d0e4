"""Distributed engine: mesh construction, worker isolation, the sharded GAR
path, Byzantine attack injection and the lossy-link simulator.

This package replaces the reference's entire distribution stack — the
parameter-server cluster manager (cluster.py), the replicated graph
construction (graph.py:204-315) and the gRPC/MPI/UDP transports
(tf_patches/) — with a single-controller JAX SPMD design over a
`jax.sharding.Mesh`:

- ``mesh``:    mesh construction over ICI/DCN with a ``worker`` axis; the
               reference's device allocator (cluster.py:147-221) becomes axis
               sizing over `jax.devices()`.
- ``engine``:  the robust training step.  Per-worker gradients are computed in
               isolation under ``shard_map``; an ``all_to_all`` reshards the
               implicit (n, d) gradient matrix from worker-sharded to
               *dimension-sharded* column blocks; pairwise distances reduce
               with an O(n²) ``psum``; the GAR combine runs blockwise; an
               ``all_gather`` restores the aggregated (d,) vector.  Per-device
               memory stays O(d) and the bytes on the wire are ~2x one
               allreduce — this is the TPU equivalent of the reference's
               worker->PS gradient push (SURVEY.md §2.6).
- ``attacks``: Byzantine gradient attacks applied to a worker's *own* slot
               (implements the runner.py:345 TODO for real).
- ``lossy``:   NaN-masking lossy-link simulator reproducing the UDP
               transport's packet-loss semantics
               (mpi_rendezvous_mgr.patch:833-841).
"""

from .mesh import make_mesh, worker_axis  # noqa: F401
from .engine import RobustEngine  # noqa: F401
from . import attacks  # noqa: F401
from . import lossy  # noqa: F401
