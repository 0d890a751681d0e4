"""End-to-end CLI runner tests on the virtual 8-device CPU mesh.

The reference's only correctness harness is end-to-end experiment runs
(experiments.sh); these tests formalize that pattern (SURVEY.md §4).
"""

import json
import os
import socket
import subprocess
import sys

import pytest

from aggregathor_tpu.cli import runner
from aggregathor_tpu.utils import UserException


def run(args):
    return runner.main(args)


def _free_port():
    """An ephemeral port for a throwaway localhost cluster."""
    with socket.socket() as sock:
        sock.bind(("127.0.0.1", 0))
        return sock.getsockname()[1]


_MULTIPROC_CPU = None


def _multiprocess_cpu_supported():
    """Capability probe, cached per session: can THIS jaxlib run a 2-process
    CPU collective?  Some builds refuse with "Multiprocess computations
    aren't implemented on the CPU backend" — a property of the wheel, not of
    the code under test, so the deploy tests skip instead of failing red.
    The probe forks two tiny processes that broadcast one int32; on a
    refusing build it fails in a few seconds."""
    global _MULTIPROC_CPU
    if _MULTIPROC_CPU is None:
        port = _free_port()
        script = (
            "import sys, jax, numpy as np;"
            "jax.distributed.initialize('127.0.0.1:%d', 2, int(sys.argv[1]));"
            "from jax.experimental import multihost_utils;"
            "multihost_utils.broadcast_one_to_all(np.int32(1))" % port
        )
        procs = []
        for rank in (0, 1):
            env = dict(os.environ)
            env["JAX_PLATFORMS"] = "cpu"
            env.pop("XLA_FLAGS", None)
            procs.append(subprocess.Popen(
                [sys.executable, "-c", script, str(rank)],
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, env=env,
            ))
        try:
            for proc in procs:
                proc.communicate(timeout=120)
            _MULTIPROC_CPU = all(proc.returncode == 0 for proc in procs)
        except subprocess.TimeoutExpired:
            for proc in procs:
                proc.kill()
            _MULTIPROC_CPU = False
    return _MULTIPROC_CPU


def _require_multiprocess_cpu():
    if not _multiprocess_cpu_supported():
        pytest.skip(
            "this jaxlib refuses multiprocess CPU collectives "
            "(known-environmental; the deploy path needs a build with "
            "cross-process CPU support)"
        )


def test_runner_end_to_end(tmp_path):
    eval_file = str(tmp_path / "eval.tsv")
    ckpt_dir = str(tmp_path / "ckpt")
    sum_dir = str(tmp_path / "sum")
    assert 0 == run([
        "--experiment", "mnist", "--experiment-args", "batch-size:16",
        "--aggregator", "krum",
        "--nb-workers", "8", "--nb-decl-byz-workers", "2",
        "--nb-real-byz-workers", "2", "--attack", "signflip",
        "--max-step", "12",
        "--learning-rate-args", "initial-rate:0.05",
        "--evaluation-delta", "10", "--evaluation-period", "-1",
        "--evaluation-file", eval_file,
        "--checkpoint-dir", ckpt_dir, "--checkpoint-delta", "10",
        "--summary-dir", sum_dir, "--summary-delta", "5",
    ])
    # eval TSV written with walltime/step/metric fields
    lines = [l.split("\t") for l in open(eval_file).read().strip().splitlines()]
    assert all(len(fields) >= 3 for fields in lines)
    assert int(lines[-1][1]) == 12  # final fire at stop
    # checkpoints exist, including the final one
    assert any(name.endswith("-12.ckpt") for name in os.listdir(ckpt_dir))
    # summaries parse as JSONL with scalar keys
    sum_files = os.listdir(sum_dir)
    assert len(sum_files) == 1
    events = [json.loads(l) for l in open(os.path.join(sum_dir, sum_files[0]))]
    assert all("total_loss" in ev for ev in events)


def test_runner_steady_state_cadences(tmp_path):
    """Longer run where delta cadences fire repeatedly in steady state (not
    just the fire-at-start and final-fire paths): 60 steps with deltas 10/20
    must produce the full arithmetic progression of firings."""
    eval_file = str(tmp_path / "eval.tsv")
    ckpt_dir = str(tmp_path / "ckpt")
    assert 0 == run([
        "--experiment", "mnist", "--experiment-args", "batch-size:8",
        "--aggregator", "average", "--nb-workers", "4",
        "--learning-rate-args", "initial-rate:0.01",
        "--max-step", "60",
        "--evaluation-delta", "20", "--evaluation-period", "-1",
        "--evaluation-file", eval_file,
        "--checkpoint-dir", ckpt_dir, "--checkpoint-delta", "10",
        "--checkpoint-period", "-1", "--checkpoint-keep", "0",
    ])
    eval_steps = [int(line.split("\t")[1]) for line in open(eval_file).read().strip().splitlines()]
    # fires at start (step 1), then every >= 20 steps, then the final fire
    assert eval_steps == [1, 21, 41, 60], eval_steps
    ckpt_steps = sorted(int(n.split("-")[1].split(".")[0]) for n in os.listdir(ckpt_dir))
    assert ckpt_steps == [1, 11, 21, 31, 41, 51, 60], ckpt_steps


def test_runner_resume(tmp_path):
    ckpt_dir = str(tmp_path / "ckpt")
    base = [
        "--experiment", "mnist", "--experiment-args", "batch-size:16",
        "--aggregator", "average", "--nb-workers", "4",
        "--learning-rate-args", "initial-rate:0.05",
        "--evaluation-delta", "-1", "--evaluation-period", "-1",
        "--checkpoint-dir", ckpt_dir,
    ]
    assert 0 == run(base + ["--max-step", "5"])
    assert 0 == run(base + ["--max-step", "8"])
    steps = sorted(int(n.split("-")[1].split(".")[0]) for n in os.listdir(ckpt_dir))
    assert 8 in steps  # resumed from 5 and reached 8


def test_deploy_local_simulate(tmp_path):
    """The multi-host path for real: --local-simulate 2 forks a 2-process CPU
    cluster connected via jax.distributed (reference single-machine story,
    deploy.py:190-309 / README.md:141-146), runs mnist+krum over the spanning
    mesh, and only process 0 writes the eval file."""
    _require_multiprocess_cpu()
    port = _free_port()
    eval_file = tmp_path / "eval.tsv"
    proc = subprocess.run(
        [sys.executable, "-m", "aggregathor_tpu.cli.deploy",
         "--local-simulate", "2", "--port", str(port), "--",
         "--experiment", "mnist", "--experiment-args", "batch-size:16",
         "--aggregator", "krum", "--nb-workers", "4", "--nb-decl-byz-workers", "1",
         "--max-step", "5", "--learning-rate-args", "initial-rate:0.05",
         "--session-secret", "launch-secret",
         "--evaluation-file", str(eval_file), "--evaluation-delta", "5"],
        capture_output=True, text=True, timeout=300,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = eval_file.read_text().strip().splitlines()
    steps = [int(line.split("\t")[1]) for line in lines]
    assert steps == sorted(set(steps)), "duplicate eval rows: several processes wrote the file"
    assert steps[-1] == 5


def test_prefetch_does_not_change_training(tmp_path):
    """The background prefetcher preserves batch order: final params are
    byte-identical with and without it."""
    blobs = []
    for depth in ("0", "3"):
        ckpt = str(tmp_path / ("ckpt" + depth))
        assert 0 == run([
            "--experiment", "mnist", "--experiment-args", "batch-size:16",
            "--aggregator", "median", "--nb-workers", "4", "--nb-decl-byz-workers", "1",
            "--max-step", "7", "--prefetch", depth,
            "--evaluation-delta", "-1", "--evaluation-period", "-1",
            "--checkpoint-dir", ckpt, "--checkpoint-delta", "-1", "--checkpoint-period", "-1",
        ])
        [name] = [n for n in os.listdir(ckpt) if n.endswith("-7.ckpt")]
        blobs.append(open(os.path.join(ckpt, name), "rb").read())
    assert blobs[0] == blobs[1]


def test_reference_compat_flags(tmp_path):
    """The reference README's local-deployment flags run unchanged: dissolved
    topology flags (--server/--*-job-name/--MPI/--no-wait) are accepted as
    warned no-ops and --use-gpu degrades to CPU when no GPU backend exists
    (reference README.md:141-146, runner.py:196-211)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env.pop("JAX_PLATFORMS", None)
    proc = subprocess.run(
        [sys.executable, "-m", "aggregathor_tpu.cli.runner",
         "--experiment", "mnist", "--aggregator", "average", "--nb-workers", "4",
         "--max-step", "3", "--evaluation-delta", "-1", "--evaluation-period", "-1",
         "--server", '{"local": ["127.0.0.1:7000"]}',
         "--ps-job-name", "local", "--wk-job-name", "local", "--ev-job-name", "local",
         "--MPI", "--no-wait", "--use-gpu"],
        capture_output=True, text=True, timeout=300, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert proc.returncode == 0, proc.stderr[-1500:]
    out = proc.stdout + proc.stderr
    assert "Compat no-op flags ignored" in out
    assert "Mesh:" in out


def test_runner_rejects_bad_nf():
    with pytest.raises(UserException):
        run(["--experiment", "mnist", "--aggregator", "krum",
             "--nb-workers", "4", "--nb-decl-byz-workers", "2",  # krum needs n >= f+3
             "--max-step", "1"])


def test_runner_rejects_more_byz_than_workers():
    with pytest.raises(UserException):
        run(["--experiment", "mnist", "--aggregator", "average",
             "--nb-workers", "2", "--nb-real-byz-workers", "3",
             "--max-step", "1"])


def test_runner_nan_divergence_abort():
    # An all-NaN attacker against plain averaging must trip the divergence
    # abort (reference: runner.py:570-574): aggregate NaN -> params NaN ->
    # non-finite loss.
    with pytest.raises(UserException):
        run(["--experiment", "mnist", "--aggregator", "average",
             "--nb-workers", "4", "--nb-decl-byz-workers", "0",
             "--nb-real-byz-workers", "1", "--attack", "inf",
             "--max-step", "5",
             "--evaluation-delta", "-1", "--evaluation-period", "-1"])


def test_unroll_prefetch_equivalence(tmp_path):
    """The unrolled chunk prefetcher preserves training exactly: final params
    after 25 steps (2x10-chunks + 5-step tail, exercising the chunk->per-step
    producer handoff) are byte-identical to the same unrolled run without the
    prefetcher.  (Same executables — a scanned-vs-per-step comparison would
    differ in f32 fusion order, not in sample streams.)"""
    blobs = []
    for extra in (["--unroll", "10", "--prefetch", "0"], ["--unroll", "10", "--prefetch", "2"]):
        ckpt = str(tmp_path / ("ckpt%d" % len(blobs)))
        assert 0 == run([
            "--experiment", "mnist", "--experiment-args", "batch-size:8",
            "--aggregator", "krum", "--nb-workers", "4", "--nb-decl-byz-workers", "1",
            "--max-step", "25",
            "--evaluation-delta", "-1", "--evaluation-period", "-1",
            "--checkpoint-dir", ckpt, "--checkpoint-delta", "-1", "--checkpoint-period", "-1",
        ] + extra)
        [name] = [n for n in os.listdir(ckpt) if n.endswith("-25.ckpt")]
        blobs.append(open(os.path.join(ckpt, name), "rb").read())
    assert blobs[0] == blobs[1]


def test_worker_metrics_summaries(tmp_path):
    """--worker-metrics lands per-worker suspicion vectors in the summary
    JSONL with a suspect_worker index."""
    sum_dir = str(tmp_path / "sum")
    assert 0 == run([
        "--experiment", "mnist", "--experiment-args", "batch-size:8",
        "--aggregator", "krum", "--nb-workers", "4", "--nb-decl-byz-workers", "1",
        "--nb-real-byz-workers", "1", "--attack", "gaussian", "--attack-args", "deviation:100",
        "--worker-metrics", "--max-step", "6",
        "--evaluation-delta", "-1", "--evaluation-period", "-1",
        "--summary-dir", sum_dir, "--summary-delta", "2",
    ])
    [name] = os.listdir(sum_dir)
    events = [json.loads(l) for l in open(os.path.join(sum_dir, name))]
    assert events, "no summary events written"
    for ev in events:
        assert len(ev["worker_sq_dist"]) == 4
        assert len(ev["worker_participation"]) == 4
        # the deviation-100 attacker, serialized as a usable integer index
        assert ev["suspect_worker"] == 0 and isinstance(ev["suspect_worker"], int)


def test_granularity_leaf_cli(tmp_path):
    """--granularity leaf trains end to end and reports per-worker metrics."""
    sum_dir = str(tmp_path / "sum")
    assert 0 == run([
        "--experiment", "mnist", "--experiment-args", "batch-size:8",
        "--aggregator", "krum", "--granularity", "leaf",
        "--nb-workers", "8", "--nb-decl-byz-workers", "2",
        "--nb-real-byz-workers", "2", "--attack", "gaussian", "--attack-args", "deviation:100",
        "--worker-metrics", "--max-step", "6",
        "--evaluation-delta", "-1", "--evaluation-period", "-1",
        "--summary-dir", sum_dir, "--summary-delta", "3",
    ])
    [name] = os.listdir(sum_dir)
    events = [json.loads(l) for l in open(os.path.join(sum_dir, name))]
    assert events, "no summary events written"
    for ev in events:
        assert len(ev["worker_sq_dist"]) == 8
        assert len(ev["worker_participation"]) == 8
        assert ev["suspect_worker"] in (0, 1)  # one of the two forgers
        assert isinstance(ev["suspect_worker"], int)


def test_runner_sharded_mesh_end_to_end(tmp_path):
    """--mesh W,PP,TP routes through RobustEngine(sharding="sharded"): a tiny transformer
    trains on a (2,2,2) mesh through the real CLI with the cadence machinery
    live — eval TSV, checkpoints (save AND sharded restore via put_state),
    summaries — then resumes from the snapshot (VERDICT r2 next-step 3)."""
    eval_file = str(tmp_path / "eval.tsv")
    ckpt_dir = str(tmp_path / "ckpt")
    sum_dir = str(tmp_path / "sum")
    base = [
        "--experiment", "transformer",
        "--experiment-args", "d-model:16", "heads:2", "layers:2", "seq:16",
        "batch-size:2", "vocab:32", "corpus:4096",
        "--aggregator", "median",
        "--nb-workers", "2", "--mesh", "2,2,2",
        "--nb-real-byz-workers", "1", "--attack", "signflip",
        "--worker-metrics",
        "--checkpoint-dir", ckpt_dir, "--checkpoint-delta", "4",
    ]
    assert 0 == run(base + [
        "--max-step", "5",
        "--evaluation-delta", "4", "--evaluation-period", "-1",
        "--evaluation-file", eval_file,
        "--summary-dir", sum_dir, "--summary-delta", "2",
    ])
    lines = [l.split("\t") for l in open(eval_file).read().strip().splitlines()]
    assert int(lines[-1][1]) == 5  # final fire at stop
    assert any("loss:" in field for field in lines[-1])
    # dense-replica metrics on the sharded path (stage collapse)
    assert any("accuracy:" in field for field in lines[-1])
    assert any("nll:" in field for field in lines[-1])
    assert any(name.endswith("-5.ckpt") for name in os.listdir(ckpt_dir))
    sum_files = os.listdir(sum_dir)
    events = [json.loads(l) for l in open(os.path.join(sum_dir, sum_files[0]))]
    assert all("total_loss" in ev for ev in events)
    assert any("worker_sq_dist" in ev for ev in events)
    # resume: restores step 5 (sharded put_state) and continues to 7
    assert 0 == run(base + ["--max-step", "7"])
    assert any(name.endswith("-7.ckpt") for name in os.listdir(ckpt_dir))


def test_runner_rejects_orphan_jitter_and_dead_microbatches():
    """Loud-misconfiguration convention: --straggler-jitter outside
    bounded-wait mode and --microbatches under sharded --step-deadline
    (the bounded submission body computes full-batch per-worker grads)
    are refused, not silently ignored."""
    with pytest.raises(UserException, match="bounded-wait"):
        run(["--experiment", "digits", "--aggregator", "average",
             "--nb-workers", "4", "--straggler-jitter", "1.2",
             "--max-step", "1"])
    # jitter scales an injected stall: with a deadline but no stall
    # source it would inject nothing — loud, not a silently calm fleet
    with pytest.raises(UserException, match="stall source"):
        run(["--experiment", "digits", "--aggregator", "average-nan",
             "--nb-workers", "4", "--step-deadline", "0.3",
             "--straggler-jitter", "1.2", "--max-step", "1"])
    with pytest.raises(UserException, match="microbatches"):
        run(["--experiment", "transformer",
             "--experiment-args", "d-model:16", "heads:2", "layers:2",
             "seq:16", "batch-size:2", "vocab:32", "corpus:4096",
             "--aggregator", "median", "--nb-workers", "2",
             "--mesh", "2,1,1", "--step-deadline", "0.2",
             "--microbatches", "2", "--max-step", "1"])


def test_runner_rejects_orphan_stale_reweight():
    """--stale-reweight rescales STALE CARRY rows: without --stale-infill
    there is no carry to reweight, and outside bounded-wait mode entirely
    the flag is an orphan — both are parse-time refusals, never silently
    ignored (ISSUE 20 v3)."""
    base = ["--experiment", "digits", "--aggregator", "krum",
            "--nb-workers", "4", "--nb-decl-byz-workers", "1",
            "--max-step", "1"]
    # bounded-wait mode, but no stale infill: nothing to reweight
    with pytest.raises(UserException, match="stale-infill"):
        run(base + ["--step-deadline", "0.3", "--stale-reweight"])
    # no bounded-wait mode at all: the orphan-flag refusal names the flag
    with pytest.raises(UserException, match="stale-reweight"):
        run(base + ["--stale-reweight"])


def test_runner_sharded_mesh_rejections():
    """--mesh surface validation: W != n, unsupported experiment."""
    base = ["--aggregator", "median", "--nb-workers", "2"]
    with pytest.raises(UserException):
        run(["--experiment", "transformer", "--mesh", "4,2,1"] + base + ["--max-step", "1"])
    with pytest.raises(UserException):
        run(["--experiment", "mnist", "--mesh", "2,2,2"] + base + ["--max-step", "1"])
    with pytest.raises(UserException):  # flat engine cannot do layer/global
        run(["--experiment", "mnist", "--granularity", "layer"] + base + ["--max-step", "1"])
    with pytest.raises(UserException):  # malformed mesh triple
        run(["--experiment", "transformer", "--mesh", "2,2"] + base + ["--max-step", "1"])


def test_runner_sharded_mesh_unroll_and_regularization(tmp_path):
    """One CLI, every knob (reference runner.py:80-231): --unroll and
    --l1/--l2-regularize now drive the sharded engine too (VERDICT r3
    next-step 6).  max-step 5 with unroll 2 exercises BOTH the scanned-chunk
    dispatch (2x2 steps) and the per-step tail (1 step)."""
    eval_file = str(tmp_path / "eval.tsv")
    assert 0 == run([
        "--experiment", "transformer",
        "--experiment-args", "d-model:16", "heads:2", "layers:2", "seq:16",
        "batch-size:2", "vocab:32", "corpus:4096",
        "--aggregator", "median",
        "--nb-workers", "2", "--mesh", "2,2,2",
        "--unroll", "2", "--l1-regularize", "1e-5", "--l2-regularize", "1e-4",
        "--max-step", "5",
        "--evaluation-delta", "4", "--evaluation-period", "-1",
        "--evaluation-file", eval_file,
    ])
    lines = [l.split("\t") for l in open(eval_file).read().strip().splitlines()]
    assert int(lines[-1][1]) == 5  # the tail step ran after the chunks


def test_deploy_session_secret_mismatch_rejected():
    """Host-boundary authentication for real: a 2-process cluster where one
    process holds the wrong --session-secret must ABORT at the bring-up
    handshake (no training step runs with an unauthenticated host) —
    VERDICT r2 next-step 7; reference parity: signed worker->PS pushes
    (mpi_rendezvous_mgr.patch:585-627)."""
    _require_multiprocess_cpu()
    port = _free_port()
    common = [
        "--experiment", "mnist", "--experiment-args", "batch-size:8",
        "--aggregator", "average", "--nb-workers", "2", "--max-step", "2",
        "--evaluation-delta", "-1", "--evaluation-period", "-1",
    ]
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    procs = []
    for rank, secret in ((0, "launch-secret"), (1, "attacker-guess")):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("XLA_FLAGS", None)
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "aggregathor_tpu.cli.deploy",
             "--coordinator-address", "127.0.0.1:%d" % port,
             "--num-processes", "2", "--process-id", str(rank), "--"]
            + common + ["--session-secret", secret],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, cwd=repo,
        ))
    outs = [p.communicate(timeout=300)[0] for p in procs]
    assert all(p.returncode != 0 for p in procs), outs
    assert any("authentication FAILED" in out for out in outs), outs


def test_deploy_multidevice_restore_mid_run(tmp_path):
    """VERDICT r4 task 7: the deploy path's claims under PROCESS separation,
    not only threads — a 2-process x 4-device jax.distributed cluster (the
    reference's multi-node multi-GPU shape, deploy.py:244-309) runs the FULL
    runner with checkpointing to step 6, then a second 2-process launch
    RESTORES mid-campaign (process 0's latest-step choice broadcast, the
    post-restore encrypted digest handshake agreeing across processes) and
    continues to step 12.  Only process 0 writes artifacts."""
    _require_multiprocess_cpu()
    port = _free_port()
    ckpt_dir = str(tmp_path / "ckpt")
    eval_file = tmp_path / "eval.tsv"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    common = [
        sys.executable, "-m", "aggregathor_tpu.cli.deploy",
        "--local-simulate", "2", "--devices-per-process", "4",
        "--port", str(port), "--",
        "--experiment", "mnist", "--experiment-args", "batch-size:8",
        "--aggregator", "krum", "--nb-workers", "8", "--nb-decl-byz-workers", "2",
        "--learning-rate-args", "initial-rate:0.05",
        "--session-secret", "launch-secret",
        "--checkpoint-dir", ckpt_dir, "--checkpoint-delta", "3",
        "--evaluation-file", str(eval_file), "--evaluation-delta", "6",
    ]
    for max_step in ("6", "12"):
        proc = subprocess.run(
            common + ["--max-step", max_step],
            capture_output=True, text=True, timeout=420, cwd=repo,
        )
        assert proc.returncode == 0, proc.stderr[-2000:] or proc.stdout[-2000:]
    steps = sorted(int(n.split("-")[1].split(".")[0]) for n in os.listdir(ckpt_dir))
    assert 6 in steps and 12 in steps, steps  # second launch RESUMED from 6
    lines = eval_file.read_text().strip().splitlines()
    eval_steps = [int(line.split("\t")[1]) for line in lines]
    assert eval_steps == sorted(set(eval_steps)), (
        "duplicate eval rows: several processes wrote the file")
    assert eval_steps[-1] == 12


def test_deploy_cluster_spec_two_process():
    """--cluster resolves the bring-up triple from a spec (the reference's
    tools/cluster.py input forms): a 2-process localhost cluster trains to
    completion with ranks from $AGGREGATHOR_PROCESS_ID."""
    _require_multiprocess_cpu()
    port = _free_port()
    spec = '["127.0.0.1:%d", "127.0.0.1"]' % port
    common = [
        "--experiment", "mnist", "--experiment-args", "batch-size:8",
        "--aggregator", "average", "--nb-workers", "2", "--max-step", "2",
        "--evaluation-delta", "-1", "--evaluation-period", "-1",
    ]
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    procs = []
    for rank in (0, 1):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("XLA_FLAGS", None)
        env["AGGREGATHOR_PROCESS_ID"] = str(rank)
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "aggregathor_tpu.cli.deploy",
             "--cluster", spec, "--"] + common,
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, cwd=repo,
        ))
    outs = [p.communicate(timeout=300)[0] for p in procs]
    assert all(p.returncode == 0 for p in procs), outs


def test_runner_session_secret_tags_checkpoints(tmp_path):
    """--session-secret also HMAC-tags snapshots: resume verifies, and a
    tampered checkpoint aborts loudly instead of silently seeding training."""
    ckpt = str(tmp_path / "ckpt")
    base = [
        "--experiment", "mnist", "--experiment-args", "batch-size:8",
        "--aggregator", "average", "--nb-workers", "4",
        "--evaluation-delta", "-1", "--evaluation-period", "-1",
        "--checkpoint-dir", ckpt, "--session-secret", "launch-secret",
    ]
    assert 0 == run(base + ["--max-step", "3"])
    assert any(n.endswith(".tag") for n in os.listdir(ckpt))
    assert 0 == run(base + ["--max-step", "5"])  # verified resume
    [newest] = [n for n in os.listdir(ckpt) if n.endswith("-5.ckpt")]
    with open(os.path.join(ckpt, newest), "r+b") as fd:
        fd.seek(100)
        fd.write(b"\xff\xff\xff")
    with pytest.raises(UserException, match="HMAC"):
        run(base + ["--max-step", "7"])


def test_runner_encrypted_checkpoints(tmp_path):
    """--encrypt-checkpoints: snapshots hit disk as ciphertext, resume
    decrypts transparently, and the flag demands --session-secret (the
    executable confidentiality story for state at rest — the TLS row of
    docs/transport.md; reference: grpc_channel.patch:70-85)."""
    ckpt = str(tmp_path / "ckpt")
    base = [
        "--experiment", "mnist", "--experiment-args", "batch-size:8",
        "--aggregator", "average", "--nb-workers", "4",
        "--evaluation-delta", "-1", "--evaluation-period", "-1",
        "--checkpoint-dir", ckpt, "--session-secret", "launch-secret",
        "--encrypt-checkpoints",
    ]
    assert 0 == run(base + ["--max-step", "3"])
    [snap] = [n for n in os.listdir(ckpt) if n.endswith("-3.ckpt")]
    with open(os.path.join(ckpt, snap), "rb") as fd:
        blob = fd.read()
    assert blob.startswith(b"ATPC1")  # ciphertext container, not msgpack
    assert 0 == run(base + ["--max-step", "5"])  # decrypting resume
    with pytest.raises(UserException, match="session-secret"):
        run([
            "--experiment", "mnist", "--aggregator", "average",
            "--nb-workers", "4", "--encrypt-checkpoints",
            "--checkpoint-dir", ckpt, "--max-step", "1",
        ])


@pytest.mark.slow  # 12 s of transformer compiles; the sharded CLI branch
def test_runner_sharded_mesh_full_composition(tmp_path):  # stays covered by
    # test_runner_sharded_mesh_end_to_end + _unroll_and_regularization in
    # tier-1 (ISSUE 10 wall-time budget; see CHANGES.md PR 10)
    """Every engine extension composes through the --mesh CLI path in one
    run: worker momentum, bf16 wire exchange, lossy link (NaN infill),
    reputation + quarantine, suspicion metrics."""
    sum_dir = str(tmp_path / "sum")
    assert 0 == run([
        "--experiment", "transformer",
        "--experiment-args", "d-model:16", "heads:2", "layers:2", "seq:16",
        "batch-size:2", "vocab:32", "corpus:4096",
        "--aggregator", "average-nan",
        "--nb-workers", "2", "--nb-decl-byz-workers", "1", "--mesh", "2,2,2",
        "--worker-momentum", "0.9", "--exchange", "bf16",
        "--UDP", "1", "--UDP-args", "min-coords:0",
        "--worker-metrics", "--reputation-decay", "0.9",
        "--quarantine-threshold", "0.2",
        "--max-step", "4",
        "--evaluation-delta", "-1", "--evaluation-period", "-1",
        "--summary-dir", sum_dir, "--summary-delta", "2",
    ])
    [name] = os.listdir(sum_dir)
    events = [json.loads(l) for l in open(os.path.join(sum_dir, name))]
    assert all("total_loss" in ev for ev in events)
    assert any("worker_reputation" in ev for ev in events)
    assert any("nb_quarantined" in ev for ev in events)


def test_runner_digits_real_data_end_to_end(tmp_path):
    """The real-data experiment through the full CLI: 120 steps of Multi-Krum
    on the sklearn digits corpus must clear 60% REAL test accuracy in the
    eval TSV (reaches 0.96 at 4000 steps — docs/robustness.md)."""
    pytest.importorskip("sklearn")
    eval_file = str(tmp_path / "eval.tsv")
    assert 0 == run([
        "--experiment", "digits", "--experiment-args", "batch-size:32",
        "--aggregator", "krum",
        "--nb-workers", "8", "--nb-decl-byz-workers", "2",
        "--max-step", "120",
        "--learning-rate-args", "initial-rate:0.1",
        "--evaluation-delta", "120", "--evaluation-period", "-1",
        "--evaluation-file", eval_file,
    ])
    lines = [l.split("\t") for l in open(eval_file).read().strip().splitlines()]
    assert int(lines[-1][1]) == 120
    metrics = dict(kv.split(":", 1) for kv in lines[-1][2:])
    assert float(metrics["accuracy"]) > 0.6, metrics


def test_runner_input_source_device(tmp_path):
    """--input-source device: the training split lives on the accelerator and
    the unrolled trainer draws fresh in-graph batches — the run trains to a
    sane accuracy through the full CLI (eval/summaries/checkpoints intact)."""
    eval_file = str(tmp_path / "eval.tsv")
    assert 0 == run([
        "--experiment", "mnist", "--experiment-args", "batch-size:16",
        "--aggregator", "krum",
        "--nb-workers", "8", "--nb-decl-byz-workers", "2",
        "--nb-real-byz-workers", "2", "--attack", "signflip",
        "--max-step", "120", "--unroll", "10",
        "--input-source", "device",
        "--learning-rate-args", "initial-rate:0.05",
        "--evaluation-delta", "60", "--evaluation-period", "-1",
        "--evaluation-file", eval_file,
    ])
    lines = [l.split("\t") for l in open(eval_file).read().strip().splitlines()]
    assert int(lines[-1][1]) == 120
    # fields past walltime/step are metric:value pairs; accuracy above chance
    metrics = dict(field.split(":") for field in lines[-1][2:])
    assert float(metrics["accuracy"]) > 0.2


def test_runner_input_source_device_rejects_host_transform():
    """Experiments whose stream needs a host transform (mnistAttack poisons
    each batch) must refuse device sampling instead of training on clean data."""
    with pytest.raises(UserException, match="train_arrays"):
        run([
            "--experiment", "mnistAttack", "--aggregator", "average",
            "--nb-workers", "4", "--nb-decl-byz-workers", "0",
            "--max-step", "4", "--input-source", "device",
        ])


def test_runner_digits_real_data_device_sampled(tmp_path):
    """REAL data + device sampling: the sklearn digits corpus lives on the
    accelerator and the unrolled trainer draws in-graph — same accuracy bar
    as the streamed real-data run."""
    pytest.importorskip("sklearn")
    eval_file = str(tmp_path / "eval.tsv")
    assert 0 == run([
        "--experiment", "digits", "--experiment-args", "batch-size:32",
        "--aggregator", "krum",
        "--nb-workers", "8", "--nb-decl-byz-workers", "2",
        "--max-step", "120", "--unroll", "10", "--input-source", "device",
        "--learning-rate-args", "initial-rate:0.1",
        "--evaluation-delta", "120", "--evaluation-period", "-1",
        "--evaluation-file", eval_file,
    ])
    lines = [l.split("\t") for l in open(eval_file).read().strip().splitlines()]
    metrics = dict(kv.split(":", 1) for kv in lines[-1][2:])
    assert float(metrics["accuracy"]) > 0.6, metrics


@pytest.mark.parametrize("flag", ["--trace", "--trace-ops"])
def test_runner_retired_tracing_flags_are_unknown(flag):
    """``--trace`` (a jax.profiler window that switched the loop to per-step
    dispatch) and ``--trace-ops`` (host callbacks narrating the phases) went:
    ``--xprof A:B`` profiles the program the run executes, and the step's
    phases are named scopes (tests/test_phases.py)."""
    with pytest.raises(SystemExit) as refused:
        runner.build_parser().parse_args(
            ["--experiment", "mnist", "--aggregator", "krum", "--nb-workers", "4", flag])
    assert refused.value.code == 2


def test_chip_smoke_refuses_a_cpu():
    """chip_smoke.py needs the chip: on a CPU it exits non-zero at once,
    names the platform it found, and prints no result line."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "chip_smoke.py")],
        capture_output=True, text=True, timeout=120, cwd=repo,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode != 0
    assert "platform=cpu" in proc.stdout
    assert "needs a TPU" in proc.stderr and "'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout

