"""Multi-host deployment: the reference's ``deploy.py`` re-based on JAX.

The reference bootstraps a TF server per node over SSH/mpirun and wires a
ClusterSpec of ps/worker/eval jobs (reference: deploy.py:190-309).  A JAX
multi-host program needs none of that choreography: every host runs the SAME
single-controller SPMD program; ``jax.distributed.initialize`` connects the
hosts (coordinator + process ranks) and the global device mesh spans all of
them over ICI/DCN.  This shim does exactly that and then hands over to the
runner — deployment collapses from 329 lines of SSH plumbing to "initialize,
then run".

Usage, one invocation per host (what SLURM/GKE/`gcloud compute tpus ssh
--worker=all` would issue)::

  python3 -m aggregathor_tpu.cli.deploy \
      --coordinator-address HOST0:1234 --num-processes 4 --process-id $RANK \
      -- --experiment mnist --aggregator krum --nb-workers 32 ...

On Cloud TPU the three flags can be omitted entirely
(``jax.distributed.initialize`` auto-detects the pod topology from the TPU
metadata); arguments after ``--`` go to the runner verbatim.

``--local-simulate K`` instead forks K local processes that form a K-process
CPU "cluster" on localhost — the single-machine deployment story of the
reference (README.md:141-146) and the integration-test hook for the DCN path.

``--cluster SPEC`` resolves the three flags from the reference's cluster-spec
forms (inline JSON / file / ``G5k`` reading ``$OAR_FILE_NODES`` —
tools/cluster.py:48-91) via ``utils.cluster.cluster_spec``.
"""

import argparse
import os
import subprocess
import sys


def build_parser():
    parser = argparse.ArgumentParser(
        prog="aggregathor-tpu deploy", description="Multi-host bring-up for the runner"
    )
    parser.add_argument("--coordinator-address", default=None, help="host:port of process 0")
    parser.add_argument("--num-processes", type=int, default=None, help="total process count")
    parser.add_argument("--process-id", type=int, default=None, help="this process' rank")
    parser.add_argument(
        "--cluster", default=None, metavar="SPEC",
        help="resolve the bring-up triple from a cluster spec instead of the "
             "three flags above: inline JSON ('[\"h0\",\"h1\"]' or "
             "'{\"hosts\": [...], \"port\": N}'), a nodefile/JSON path, or "
             "'G5k' to read $OAR_FILE_NODES — the reference's --cluster "
             "forms (tools/cluster.py:48-91) mapped to SPMD bring-up; this "
             "host's rank comes from hostname match or $AGGREGATHOR_PROCESS_ID",
    )
    parser.add_argument(
        "--local-simulate", type=int, default=0, metavar="K",
        help="fork K local processes forming a cluster on localhost "
             "(single-machine parity).  CPU-only by construction: every "
             "child is started with JAX_PLATFORMS=cpu — an accelerator "
             "belongs to one process at a time, so K processes cannot "
             "share it",
    )
    parser.add_argument("--devices-per-process", type=int, default=1,
                        help="(--local-simulate only) virtual CPU devices "
                             "per forked process, so a K-process x D-device "
                             "cluster — the reference's multi-node multi-GPU "
                             "shape (deploy.py:244-309) — is testable on one "
                             "machine")
    parser.add_argument("--port", type=int, default=None,
                        help="coordinator port when the spec names none (default 7000, "
                             "the reference's fixed port, tools/cluster.py:60)")
    parser.add_argument("runner_args", nargs=argparse.REMAINDER, help="arguments after -- go to the runner")
    return parser


def _strip_separator(rest):
    return rest[1:] if rest and rest[0] == "--" else rest


def local_simulate(nb_processes, port, runner_args, devices_per_process=1):
    """Fork a K-process localhost cluster (CPU devices) running the runner."""
    procs = []
    for rank in range(nb_processes):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("XLA_FLAGS", None)  # default: the cluster IS the mesh
        if devices_per_process > 1:
            env["XLA_FLAGS"] = (
                "--xla_force_host_platform_device_count=%d" % devices_per_process)
        cmd = [
            sys.executable, "-m", "aggregathor_tpu.cli.deploy",
            "--coordinator-address", "127.0.0.1:%d" % port,
            "--num-processes", str(nb_processes),
            "--process-id", str(rank),
            "--",
        ] + runner_args
        procs.append(subprocess.Popen(cmd, env=env))
    code = 0
    for proc in procs:
        code = proc.wait() or code
    return code


def main(argv=None):
    args = build_parser().parse_args(argv)
    runner_args = _strip_separator(args.runner_args)
    if args.devices_per_process != 1 and args.local_simulate <= 0:
        from ..utils import UserException

        raise UserException(
            "--devices-per-process shapes the forked --local-simulate "
            "cluster only; for a real cluster set XLA_FLAGS="
            "--xla_force_host_platform_device_count (or run on real chips) "
            "in each process' environment"
        )
    if args.local_simulate > 0:
        from ..utils.cluster import DEFAULT_PORT

        return local_simulate(args.local_simulate, args.port or DEFAULT_PORT,
                              runner_args, args.devices_per_process)
    if args.cluster is not None:
        if (
            args.coordinator_address is not None
            or args.num_processes is not None
            or args.process_id is not None
        ):
            from ..utils import UserException

            raise UserException(
                "--cluster and --coordinator-address/--num-processes/"
                "--process-id are two ways to name the same thing; pass one "
                "(a spec'd host's rank can be pinned via "
                "$AGGREGATHOR_PROCESS_ID)"
            )
        from ..utils.cluster import cluster_spec

        (args.coordinator_address, args.num_processes, args.process_id) = cluster_spec(
            args.cluster, port=args.port
        )

    import jax

    platform = os.environ.get("JAX_PLATFORMS", "").strip().lower()
    if platform:
        # The env var alone can be overridden by an ambient accelerator
        # plugin, sending jax.distributed.initialize into that plugin's
        # coordination bootstrap (which can hang); the config-level pin wins
        # as long as no backend has been initialized yet (cli/runner.py does
        # the same dance).
        jax.config.update("jax_platforms", platform)

    kwargs = {}
    if args.coordinator_address is not None:
        kwargs = {
            "coordinator_address": args.coordinator_address,
            "num_processes": args.num_processes,
            "process_id": args.process_id,
        }
    jax.distributed.initialize(**kwargs)

    from . import runner

    return runner.main(runner_args)


def cli():
    from . import console_entry

    return console_entry(main)


if __name__ == "__main__":
    sys.exit(cli())
