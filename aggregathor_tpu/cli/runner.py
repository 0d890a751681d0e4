"""Training runner: the reference's ``runner.py`` re-based on the SPMD engine.

Argument-compatible surface (reference: runner.py:80-231): experiment /
aggregator selection with ``key:value`` sub-args, n/f worker counts and their
sanity checks (runner.py:253-260), optimizer + learning-rate registries,
l1/l2 regularization (graph.py:125-139), attack plumbing (implementing the
TODO at runner.py:345), lossy-UDP worker simulation (deploy.py:119-122),
evaluation / checkpoint / summary cadences (config.py:54-61), NaN-loss
divergence abort (runner.py:570-574) and the end-of-run performance report
with the first (compilation) step excluded (runner.py:586-598).

What is *gone*, by design: cluster specs, job names, tf.train.Server
plumbing — one SPMD program over a device mesh replaces the PS/worker
process topology.  Multi-host runs wrap this same runner with
``cli.deploy`` (jax.distributed) instead of SSH'd server processes.

Example::

  python3 -m aggregathor_tpu.cli.runner --experiment mnist --aggregator krum \
      --nb-workers 8 --nb-decl-byz-workers 2 --max-step 2000 \
      --learning-rate-args initial-rate:0.05 --evaluation-period 10
"""

import argparse
import contextlib
import os
import signal
import sys
import time


def build_parser():
    parser = argparse.ArgumentParser(
        prog="aggregathor-tpu runner", description="Byzantine-resilient SPMD training on TPU"
    )
    # Experiment / aggregation (reference: runner.py:94-137)
    parser.add_argument("--experiment", required=True, help="experiment name (see models registry)")
    parser.add_argument("--experiment-args", nargs="*", default=[], help="key:value experiment arguments")
    parser.add_argument("--aggregator", required=True, help="GAR name (see gars registry)")
    parser.add_argument("--aggregator-args", nargs="*", default=[], help="key:value GAR arguments")
    parser.add_argument("--nb-workers", type=int, required=True, help="number n of logical workers")
    parser.add_argument("--nb-decl-byz-workers", type=int, default=0, help="declared Byzantine count f")
    parser.add_argument("--nb-real-byz-workers", type=int, default=0, help="actual attacking worker count")
    parser.add_argument("--attack", default=None, help="gradient attack name (reference TODO runner.py:345)")
    parser.add_argument("--attack-args", nargs="*", default=[], help="key:value attack arguments")
    # Optimization (reference: runner.py:157-183)
    parser.add_argument("--optimizer", default="sgd", help="optimizer name")
    parser.add_argument("--optimizer-args", nargs="*", default=[], help="key:value optimizer arguments")
    parser.add_argument("--learning-rate", default="fixed", help="learning-rate schedule name")
    parser.add_argument("--learning-rate-args", nargs="*", default=[], help="key:value schedule arguments")
    parser.add_argument("--l1-regularize", type=float, default=None, help="l1 loss regularization")
    parser.add_argument("--l2-regularize", type=float, default=None, help="l2 loss regularization")
    parser.add_argument("--max-step", type=int, default=None, help="train step count (default config.py)")
    parser.add_argument(
        "--unroll", type=int, default=1,
        help="scan this many steps per dispatch (cadences then fire at chunk granularity)",
    )
    parser.add_argument(
        "--exchange", default=None, metavar="SPEC",
        help="wire codec of the gradient exchange (parallel/compress.py, "
             "docs/engine.md 'The wire'): f32 | bf16 | int8[:ef] | "
             "topk:k=K[,ef] | topk:frac=F[,ef].  int8 quantizes each row "
             "symmetrically with a traced per-row scale (~4x fewer bytes); "
             "topk ships only the k largest-|value| coordinates; ef adds "
             "per-worker error feedback (the residual rides TrainState.ef, "
             "checkpointed).  Rows are encoded after the worker-local "
             "attacks and decoded at the aggregation boundary, so every "
             "GAR sees float32; digests sign the wire image; "
             "bytes_on_wire_total / exchange_compression_ratio land on the "
             "metrics registry.  int8/topk need the flat engine and refuse "
             "--secure-mask (the fixed-point pads need the exact rows)",
    )
    parser.add_argument(
        "--worker-momentum", type=float, default=None, metavar="BETA",
        help="workers send momenta (beta in (0,1)) instead of raw gradients — "
             "history-aware robustness (Karimireddy et al. 2021)",
    )
    parser.add_argument(
        "--mesh", default=None, metavar="W,PP,TP",
        help="route training through the fully-sharded engine on a logical "
             "(worker x pipeline x tensor) mesh: per-layer robust aggregation "
             "on sharded gradients, the (n, d) matrix never materialized "
             "(needs an experiment that publishes sharded hooks, e.g. "
             "transformer). W must equal --nb-workers.",
    )
    parser.add_argument(
        "--microbatches", type=int, default=None,
        help="pipeline microbatches per step (sharded engine only; "
             "default 2).  Rejected under sharded --step-deadline: the "
             "bounded submission body computes per-worker FULL-batch "
             "gradients over experiment.loss, so the knob would be dead",
    )
    parser.add_argument(
        "--granularity", default="vector", choices=["vector", "leaf", "layer", "global"],
        help="apply the rule to the whole flattened gradient (vector — the "
             "reference's semantics, graph.py:144-168) or per parameter "
             "leaf (leaf — per-layer selection; each layer picks its own "
             "honest set)",
    )
    parser.add_argument(
        "--reputation-decay", type=float, default=None, metavar="BETA",
        help="track a per-worker reputation EMA (1 = trusted) of a rank "
             "signal: was the worker's raw gradient among the n-f closest "
             "to the applied aggregate this step",
    )
    parser.add_argument(
        "--quarantine-threshold", type=float, default=0.0, metavar="T",
        help="workers whose reputation falls below T are excluded from "
             "aggregation (row masked NaN — needs a NaN-tolerant rule); "
             "they are re-admitted automatically when their raw gradients "
             "re-approach the aggregate (requires --reputation-decay)",
    )
    parser.add_argument(
        "--worker-metrics", action="store_true",
        help="record per-worker suspicion diagnostics each summary: squared "
             "distance to the aggregate and, for selection rules, the "
             "worker's participation weight (detects persistent attackers)",
    )
    parser.add_argument(
        "--gar-probe", action="store_true",
        help="measure the GAR's wall time at each summary fire: one jitted "
             "rule-only aggregation at the run's exact (n, d) is timed under "
             "a gar.aggregate span and exported as gar_seconds_total / "
             "gar_probe_seconds on the metrics registry (the cost model "
             "behind docs/gar_scaling.md, measured instead of presumed; "
             "compiled once, outside the training step's jit cache)",
    )
    parser.add_argument(
        "--prefetch", type=int, default=2, metavar="DEPTH",
        help="device-ready input batches/chunks prepared ahead of the "
             "training dispatch (0 disables): per-step runs use a "
             "background prefetch thread, --unroll runs the three-stage "
             "chunk pipeline (parallel sharded gather into ping-pong "
             "buffers, sliced async transfer, device-side assemble — "
             "docs/input_pipeline.md)",
    )
    parser.add_argument(
        "--input-slices", type=int, default=4, metavar="S",
        help="transfer slices per --unroll chunk in the input pipeline: "
             "each slice's host->device copy is issued as soon as it is "
             "gathered, so the wire starts moving after 1/S of the chunk "
             "(1 = one monolithic transfer per chunk)",
    )
    parser.add_argument(
        "--input-source", default="stream", choices=["stream", "device"],
        help="stream: per-step host batches (the reference's input path, "
             "runner.py:562-576). device: hold the training split on the "
             "accelerator (transferred once) and gather each worker's fresh "
             "i.i.d. batch in-graph — no per-step host->device transfer; "
             "needs an experiment exposing train_arrays() (no host-side "
             "transform) and the flat engine, single process",
    )
    parser.add_argument(
        "--step-deadline", type=float, default=None, metavar="SECONDS",
        help="bounded-wait aggregation (parallel/bounded.py, docs/engine.md): "
             "dispatch each worker's gradient as its own async submission "
             "and close every round at this host-side deadline — workers "
             "that miss it contribute NaN rows within the same declared-f "
             "budget as Byzantine rows (timeouts + attacks <= f), land as "
             "straggler_timeout forensics evidence, and sustained "
             "over-budget timeouts are a guardian escalation input.  Needs "
             "the flat engine, --unroll 1, a NaN-tolerant rule, and no "
             "in-graph transport simulation (--UDP/non-straggler --chaos)",
    )
    parser.add_argument(
        "--topology", default=None, metavar="SPEC",
        help="aggregation-tree topology (topology/, docs/topology.md): "
             "replace the PS star with L levels of untrusted sub-"
             "aggregators, e.g. tree:g=16x4,rules=median>trimmed-mean>"
             "krum,link=int8,redundancy=2,agg-f=1x0.  The tree IS the "
             "aggregation rule (pass --aggregator tree; the spec "
             "substitutes into the guardian's Overrides record): "
             "f-budgets compose through the levels at parse time, every "
             "inter-level link rides the declared wire codec, each level "
             "closes its own bounded-wait round, sub-aggregator custody "
             "is chain-verified (a forged emission NAMES its (level, "
             "unit) in forensics — never laundered into worker blame), "
             "and redundancy=r serves a faulted unit from a sibling "
             "shadow.  Needs the flat engine; implies bounded-wait "
             "dispatch (add --step-deadline for real per-level windows)",
    )
    parser.add_argument(
        "--straggler-stall", type=float, default=0.0, metavar="SECONDS",
        help="bounded-wait straggler injection: a worker drawn late holds "
             "its submission this long before dispatching (the chaos "
             "straggler regimes' wall-clock twin; with --chaos the per-"
             "regime straggle rates schedule WHO is late, otherwise "
             "--straggler-rate does)",
    )
    parser.add_argument(
        "--straggler-rate", type=float, default=0.0, metavar="P",
        help="bounded-wait: flat per-(step, worker) lateness probability "
             "when no --chaos schedule provides regime rates",
    )
    parser.add_argument(
        "--straggler-jitter", type=float, default=0.0, metavar="SIGMA",
        help="bounded-wait straggler injection: heavy-tail the stall — a "
             "late worker sleeps stall * exp(SIGMA * N(0,1)) (lognormal, "
             "median = --straggler-stall) instead of exactly the stall; "
             "with --chaos the per-regime jitter=SIGMA takes precedence",
    )
    parser.add_argument(
        "--deadline-percentile", type=float, default=None, metavar="P",
        help="adaptive bounded-wait window (parallel/deadline.py, "
             "docs/engine.md): track the per-worker arrival distribution "
             "and set each round's window to its P-th percentile, "
             "EMA-smoothed and clamped into [--deadline-floor, "
             "--deadline-ceiling].  Requires --step-deadline (the initial "
             "window and the default ceiling).  Choose P at or below "
             "100*(n-f-1)/(n-1) (e.g. 71.4 for n=8, f=2) so a persistent "
             "straggler coalition inside the declared budget cannot pin "
             "the window at the ceiling",
    )
    parser.add_argument(
        "--deadline-floor", type=float, default=0.01, metavar="SECONDS",
        help="adaptive deadline: smallest window the controller may emit",
    )
    parser.add_argument(
        "--deadline-ceiling", type=float, default=None, metavar="SECONDS",
        help="adaptive deadline: largest window (default: --step-deadline "
             "— the fixed protocol's declared worst-case wait); a "
             "controller pinned here for ceiling-patience steps is a "
             "guardian escalation input",
    )
    parser.add_argument(
        "--deadline-ema", type=float, default=0.3, metavar="ALPHA",
        help="adaptive deadline: weight of each new round's percentile "
             "target in (0, 1] — smoothing so a single spiked round "
             "cannot whipsaw the window",
    )
    parser.add_argument(
        "--stale-infill", action="store_true",
        help="bounded-wait: a timed-out worker re-enters its CLEVER carry "
             "row (the last submission this aggregator received from it) "
             "instead of a NaN drop.  Stale rows SPEND the declared-f "
             "budget exactly like timeouts and attacks (stale + timeouts "
             "+ attacks <= f — a Byzantine straggler re-enters its carried "
             "attack row), and land as stale_infill forensics evidence",
    )
    parser.add_argument(
        "--stale-max-age", type=int, default=4, metavar="ROUNDS",
        help="bounded-wait stale infill: a carry older than this many "
             "consecutive missed rounds degrades back to a NaN drop",
    )
    parser.add_argument(
        "--stale-reweight", action="store_true",
        help="bounded-wait v3: damp each stale carry row by its age — a "
             "carry of age a enters aggregation scaled by 1/(1+a) (the "
             "unbiased-estimator framing of arXiv:2505.23523) instead of "
             "at full weight.  Requires --stale-infill; the damped row "
             "still SPENDS the declared-f budget, and every reweighted "
             "re-entry is a stale_reweight journal event",
    )
    parser.add_argument(
        "--incremental-aggregation", action="store_true",
        help="bounded-wait: fold each submission's decoded row into the "
             "aggregate-side device buffer the instant it lands instead of "
             "stacking at the round barrier — decode/transfer overlaps the "
             "submissions still outstanding (exchange_overlap_fraction on "
             "the registry measures it).  Needs --step-deadline and the "
             "flat engine; numerics identical to the stacked path",
    )
    parser.add_argument("--seed", type=int, default=0, help="base PRNG seed")
    parser.add_argument(
        "--session-secret", default=None, metavar="SECRET",
        help="shared secret authenticating the multi-host boundary: every "
             "process HMAC-tags a digest of its post-init parameters and "
             "verifies every peer's tag at bring-up; any process launched "
             "without the secret (or with a tampered payload) aborts the "
             "cluster (reference: signed worker->PS pushes + TLS channels, "
             "mpi_rendezvous_mgr.patch:585-627, grpc_channel.patch:70-85)",
    )
    parser.add_argument(
        "--secure", action="store_true",
        help="authenticated gradient submission (secure/, docs/security.md): "
             "every worker's per-step row is digest-tagged under a per-"
             "(worker, step) HMAC key from --session-secret, verified before "
             "aggregation; a failed tag becomes a NaN row AND a named "
             "'forgery' forensics evidence entry (reject-and-name); custody "
             "manifests are written beside every checkpoint and verified on "
             "restore; zero added recompiles (requires --session-secret)",
    )
    parser.add_argument(
        "--secure-mask", action="store_true",
        help="bucket-level additive masking (Bonawitz-style, secure/"
             "masking.py): individual gradient rows are one-time-padded and "
             "the pads cancel EXACTLY inside bucket/hier group means — "
             "requires a mean-inner meta-GAR spec (bucketing:..., or "
             "hier:inner=average,...) and --session-secret; a worker that "
             "drops mid-step NaNs its whole group",
    )
    parser.add_argument(
        "--allow-unsigned", action="store_true",
        help="let a --secure run restore checkpoints that carry NO custody "
             "manifest (e.g. resuming a directory written before --secure "
             "was enabled): provenance is then unverified for that restore; "
             "new snapshots are signed as usual",
    )
    parser.add_argument(
        "--no-legacy-checkpoint-tags", action="store_true",
        help="refuse snapshots tagged under the pre-context-separation key "
             "scheme instead of accepting + re-tagging them once; set this "
             "when no pre-upgrade snapshots exist to close the downgrade "
             "acceptance entirely",
    )
    parser.add_argument(
        "--encrypt-checkpoints", action="store_true",
        help="encrypt snapshot bytes at rest under a key derived from "
             "--session-secret (SHAKE-256 keystream, encrypt-then-MAC with "
             "the HMAC tag) — the framework-side counterpart of the "
             "reference's TLS channels (grpc_channel.patch:70-85) for state "
             "that outlives the run; requires --session-secret",
    )
    # Cadences (reference: runner.py:184-215)
    parser.add_argument("--evaluation-file", default=None, help="TSV evaluation log path")
    parser.add_argument("--evaluation-delta", type=int, default=None, help="eval every this many steps")
    parser.add_argument("--evaluation-period", type=float, default=None, help="eval every this many seconds")
    parser.add_argument("--checkpoint-dir", default=None, help="checkpoint directory")
    parser.add_argument("--checkpoint-base-name", default=None, help="checkpoint file base name")
    parser.add_argument("--checkpoint-delta", type=int, default=None)
    parser.add_argument("--checkpoint-period", type=float, default=None)
    parser.add_argument("--checkpoint-keep", type=int, default=5, help="snapshots to keep")
    parser.add_argument("--summary-dir", default=None, help="JSONL scalar summary directory")
    parser.add_argument("--summary-delta", type=int, default=None)
    parser.add_argument("--summary-period", type=float, default=None)
    # Transport simulation + tracing (reference: deploy.py:119-122, runner.py:216-219)
    parser.add_argument("--UDP", type=int, default=0, dest="udp", help="first k workers use the lossy link")
    parser.add_argument("--UDP-args", nargs="*", default=[], dest="udp_args", help="key:value lossy-link arguments")
    parser.add_argument(
        "--chaos", default=None, metavar="SCHEDULE",
        help="time-varying fault-regime schedule (chaos/ DSL, e.g. "
             "'0:calm 500:drop=0.3 1000:attack=empire'): regime switches "
             "happen inside the jitted step with zero recompilation; "
             "subsumes the static --attack/--UDP knobs",
    )
    parser.add_argument(
        "--chaos-args", nargs="*", default=[],
        help="key:value schedule-wide chaos options (packet-coords:N, "
             "min-coords:N, straggle-workers:K)",
    )
    parser.add_argument(
        "--guardian", action="store_true",
        help="in-loop divergence watchdog + rollback-and-escalate recovery "
             "(guardian/, docs/guardian.md): on sustained divergence, restore "
             "the last-known-good snapshot, perturb the RNG and climb the "
             "escalation ladder (raise f -> stronger GAR -> quarantine -> "
             "damp lr) with bounded retries; needs --checkpoint-dir",
    )
    parser.add_argument(
        "--guardian-args", nargs="*", default=[],
        help="key:value watchdog options (patience:N, spike:X, retries:N, "
             "backoff:B, recover:N, ladder:RUNG,RUNG,... — see "
             "docs/guardian.md for the ladder grammar)",
    )
    parser.add_argument("--trace-dir", default="trace",
                        help="where --xprof writes its jax.profiler capture")
    parser.add_argument(
        "--trace-file", default=None, metavar="PATH",
        help="whole-run HOST span trace (obs/trace): dispatch / block / "
             "host-gap / input / eval / checkpoint spans as Chrome "
             "trace-event JSON, Perfetto-loadable; zero added recompiles; "
             "multi-process runs suffix non-lead files with .<process>",
    )
    parser.add_argument(
        "--forensics", default=None, metavar="JSON",
        help="write a Byzantine forensics attribution report here at exit "
             "(schema aggregathor.obs.forensics.v1, plus a .md rendering): "
             "a per-worker suspicion timeline built from the engines' "
             "per-step diagnostics + guardian verdicts + chaos regime "
             "context (docs/observability.md); implies --worker-metrics",
    )
    parser.add_argument(
        "--journal", default=None, metavar="JSONL",
        help="causal run journal (obs/events.py, docs/observability.md "
             "'The control room'): append every decision event — guardian "
             "rollbacks/escalations, deadline-window moves, bounded-wait "
             "timeouts/stale infill, forgery verdicts, flight post-mortems "
             "— as typed JSONL (schema aggregathor.obs.events.v2) with "
             "run_id, step, wall+monotonic time; cross-referenced from the "
             "forensics report and served fleet-wide by obs/fleet.py; "
             "host-side only, zero added recompiles; lead process only",
    )
    parser.add_argument(
        "--metrics-file", default=None, metavar="PATH",
        help="dump the process-wide metrics registry as Prometheus text "
             "exposition here at every summary fire and at exit (the "
             "training-side counterpart of serve's /metrics endpoint); the "
             "final flush runs on normal exit, SIGTERM and divergence alike",
    )
    from . import add_causal_flags

    add_causal_flags(parser)
    parser.add_argument(
        "--flight", type=int, default=0, metavar="CAPACITY",
        help="flight recorder (obs/flight.py, docs/observability.md): carry "
             "a CAPACITY-row ring of per-step telemetry lanes (loss, update "
             "norm, probe flags, per-worker distances/NaN rows, chaos "
             "regime, secure verdicts) as a device-side TrainState buffer "
             "written INSIDE the jitted scan, fetched once per summary fire "
             "and dumped post-mortem on rollback/crash; zero added "
             "recompiles; 0 disables",
    )
    parser.add_argument(
        "--flight-dump", default=None, metavar="JSON",
        help="write the flight-recorder window here on guardian rollback or "
             "crash (schema aggregathor.obs.flight.v1) — exact per-step "
             "evidence for the window that killed the run; rollback dumps "
             "suffix .rollback-<step> before the extension (requires "
             "--flight)",
    )
    parser.add_argument(
        "--xprof", default=None, metavar="A:B",
        help="programmatic jax.profiler device capture over steps [A, B) "
             "into --trace-dir (obs/profiler.py): dispatches inside the "
             "window carry StepTraceAnnotations so the host span trace "
             "joins the device timeline per step, and every obs/trace span "
             "of the program lands in the capture under its own name; "
             "under --unroll the window lands on chunk boundaries.  Cut "
             "the capture by step phase with obs.profiler.phase_table "
             "(docs/observability.md)",
    )
    parser.add_argument(
        "--live-port", type=int, default=None, metavar="PORT",
        help="serve a live exporter for THIS training run (obs/live.py): "
             "/metrics (Prometheus text of the one registry), /status "
             "(step progress, steps/s, the latest flight window, the SLO "
             "verdict), /healthz; 0 binds an ephemeral port; lead process "
             "only",
    )
    parser.add_argument(
        "--live-host", default="127.0.0.1", metavar="HOST",
        help="bind address of the live exporter",
    )
    parser.add_argument(
        "--live-ready-file", default=None, metavar="PATH",
        help="write 'host port' here once the live exporter is bound (the "
             "smoke scripts' handshake; requires --live-port)",
    )
    parser.add_argument(
        "--slo-baseline", default=None, metavar="JSON",
        help="regression sentinel (obs/slo.py): load this baseline document "
             "(schema aggregathor.obs.slo.v1, seeded via --slo-capture on a "
             "healthy run) and emit a PASS/REGRESS verdict on steps/s, "
             "gar_seconds_total and input_overlap_fraction at run end (an "
             "slo_verdict summary event + info line)",
    )
    parser.add_argument(
        "--slo-verdict", default=None, metavar="JSON",
        help="also write the sentinel verdict document here (requires "
             "--slo-baseline)",
    )
    parser.add_argument(
        "--slo-capture", default=None, metavar="JSON",
        help="capture THIS run's end-state throughput metrics as a fresh "
             "SLO baseline document here (what --slo-baseline loads)",
    )
    parser.add_argument(
        "--run-id", default=None, metavar="ID",
        help="run id stamped on every summary line, the trace metadata and "
             "the forensics report so the streams join after the fact "
             "(default: generated)",
    )
    # Mesh (replaces cluster/job flags, reference: runner.py:81-93, 220-231)
    parser.add_argument("--nb-devices", type=int, default=None, help="devices on the worker mesh axis")
    parser.add_argument("--platform", default=None,
                        help="force a JAX platform (tpu/cpu); fatal when it "
                             "cannot initialize — nothing falls back")
    parser.add_argument("--stdout-to", default=None, help="replicate stdout to this file")
    parser.add_argument("--stderr-to", default=None, help="replicate stderr to this file")
    # Device-preference flags (reference: runner.py:196-211): map to a JAX
    # platform priority list when --platform is not forced.
    parser.add_argument("--use-tpu", action="store_true",
                        help="prefer TPU devices if available, else CPU "
                             "(reference allocator semantics; the Mesh: line "
                             "says what the run got)")
    parser.add_argument("--use-gpu", action="store_true", help="prefer GPU devices if available")
    parser.add_argument("--reuse-tpu", action="store_true",
                        help="compat: implies --use-tpu (device sharing is inherent under SPMD)")
    parser.add_argument("--reuse-gpu", action="store_true",
                        help="compat: implies --use-gpu (device sharing is inherent under SPMD)")
    # Drop-in compatibility: flags whose mechanism dissolved under the
    # single-controller SPMD design (docs/transport.md) — accepted so the
    # reference's driver scripts run unchanged, warned about once.
    for flag, meta in (
        ("--client", "TARGET"), ("--server", "SPEC"), ("--ps-job-name", "NAME"),
        ("--ev-job-name", "NAME"), ("--wk-job-name", "NAME"),
    ):
        parser.add_argument(flag, default=None, metavar=meta,
                            help="compat no-op: cluster/session topology dissolved under SPMD")
    parser.add_argument("--MPI", action="store_true", dest="mpi",
                        help="compat no-op: transport is XLA collectives over ICI/DCN")
    parser.add_argument("--no-wait", action="store_true",
                        help="compat no-op: there is no server process to linger")
    return parser


def main(argv=None):
    args = build_parser().parse_args(argv)
    mesh_axes = None
    if args.mesh:
        try:
            mesh_axes = tuple(int(x) for x in args.mesh.split(","))
            if len(mesh_axes) != 3 or any(a < 1 for a in mesh_axes):
                raise ValueError
        except ValueError:
            from ..utils import UserException

            raise UserException("--mesh wants W,PP,TP positive integers (got %r)" % args.mesh)
    device_preference = None
    if not args.platform and (args.use_tpu or args.use_gpu or args.reuse_tpu or args.reuse_gpu):
        # preference order like the reference's allocator (runner.py:282-287):
        # TPU > GPU > CPU among the requested kinds, CPU always the fallback
        device_preference = []
        if args.use_tpu or args.reuse_tpu:
            device_preference.append("tpu")
        if args.use_gpu or args.reuse_gpu:
            device_preference.append("gpu")
    if args.platform:
        os.environ["JAX_PLATFORMS"] = args.platform

    # Heavy imports after the platform choice is pinned.
    import jax
    import jax.numpy as jnp
    import numpy as np

    # How many devices this run needs: the flat engine's worker axis, or the
    # full W*PP*TP product of a --mesh request.
    requested_devices = mesh_axes[0] * mesh_axes[1] * mesh_axes[2] if mesh_axes else args.nb_devices

    def want_cpu_devices():
        # The virtual-CPU device count must be configured BEFORE any backend
        # initializes (a post-init update raises); honor an ambient
        # XLA_FLAGS force if one exists.
        return (
            requested_devices and requested_devices > 1
            and "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", "")
        )

    if args.platform:
        # jax may already be imported (an embedding process, pytest plugins):
        # the config update holds as long as no backend is initialized yet.
        jax.config.update("jax_platforms", args.platform)
        if args.platform == "cpu" and want_cpu_devices():
            jax.config.update("jax_num_cpu_devices", requested_devices)
    elif device_preference is not None:
        # "use X if available" (reference allocator semantics): try the
        # preference list; when this installation cannot even name the
        # backend, fall through to CPU like the reference does when no such
        # device exists in the cluster.  The probe initializes a backend, so
        # the CPU device count is set first (the fallback may land there).
        if want_cpu_devices():
            jax.config.update("jax_num_cpu_devices", requested_devices)
        # JAX's platform list is strict (one uninitializable backend fails the
        # whole list), so retry progressively shorter suffixes: a GPU host
        # without libtpu still lands on its GPU, not on CPU.
        candidates = device_preference + ["cpu"]
        for start in range(len(candidates)):
            args.platform = ",".join(candidates[start:])
            jax.config.update("jax_platforms", args.platform)
            try:
                jax.devices()
                break
            except RuntimeError:
                continue
    elif os.environ.get("JAX_PLATFORMS", "") == "cpu" and want_cpu_devices():
        jax.config.update("jax_num_cpu_devices", requested_devices)

    from ..utils.compile_cache import place_compile_cache

    place_compile_cache()

    from .. import config, gars, models
    from ..core import build_optimizer, build_schedule
    from ..obs import (
        CadenceTrigger,
        Checkpoints,
        EvalFile,
        ForensicsLedger,
        PerfReport,
        SummaryWriter,
        trace,
    )
    from ..obs import events as obs_events
    from ..obs import flight as obs_flight
    from ..obs import live as obs_live
    from ..obs import metrics as obs_metrics
    from ..obs import profiler as obs_profiler
    from ..obs import slo as obs_slo
    from ..obs.summaries import make_run_id
    from ..parallel import RobustEngine, attacks, make_mesh
    from ..parallel import compress
    from ..parallel.lossy import LossyLink
    from ..utils import Context, UserException, info, replicate_streams, warning

    replicate_streams(args.stdout_to, args.stderr_to)

    run_id = args.run_id if args.run_id else make_run_id()
    registry = obs_metrics.REGISTRY
    if (args.secure or args.secure_mask) and not args.session_secret:
        raise UserException(
            "--secure/--secure-mask derive their per-worker keys and mask "
            "pads from --session-secret; pass it"
        )
    # The wire codec (--exchange, parallel/compress.py): parsed up front so
    # a bad spec or an infeasible composition fails before any compilation.
    # (bf16/f32 are wire dtypes, no codec: they work on BOTH engines.)
    _, exchange_codec = compress.parse_exchange_spec(args.exchange)
    if exchange_codec is not None:
        if args.mesh:
            raise UserException(
                "--exchange %s needs the flat engine (drop --mesh): the "
                "sharded per-(worker, leaf) submissions would need per-leaf "
                "codec state — --exchange bf16 works everywhere"
                % exchange_codec.spec()
            )
        if args.secure_mask:
            raise UserException(
                "--exchange %s + --secure-mask is not supported: the "
                "fixed-point pairwise pads cancel exactly over the EXACT "
                "float32 rows, and a lossy wire codec would corrupt the "
                "cancellation — run masking on the f32/bf16 wire"
                % exchange_codec.spec()
            )
    if args.flight < 0:
        raise UserException("--flight wants a nonnegative ring capacity")
    if args.flight_dump and not args.flight:
        raise UserException("--flight-dump needs --flight CAPACITY")
    if args.live_ready_file and args.live_port is None:
        raise UserException("--live-ready-file needs --live-port")
    if args.slo_verdict and not args.slo_baseline:
        raise UserException("--slo-verdict needs --slo-baseline")
    # Sentinel baseline loads AT STARTUP: a missing/garbled document must
    # fail before an hour of training, not at the verdict.
    sentinel = obs_slo.Sentinel(args.slo_baseline) if args.slo_baseline else None

    # Stop handlers install FIRST (satellite: preempted runs must not exit
    # empty-handed): a SIGTERM during backend init, graph build or the
    # first compile sets the flag, the loop exits at its next check, and
    # the shutdown path flushes --metrics-file/forensics/trace like any
    # normal exit.  The originals are restored at shutdown; a failure
    # before the train loop leaves this benign flag-setter installed only
    # while the process unwinds.
    stop = {"requested": False}

    def on_signal(signum, frame):
        if stop["requested"]:
            # second signal: force-exit escalation — with handlers now
            # installed before backend init, a hung init/compile would
            # otherwise be un-interruptible short of SIGKILL
            warning("Interrupted twice: aborting now")
            raise KeyboardInterrupt
        stop["requested"] = True
        warning("Interrupted: finishing current step then shutting down "
                "(interrupt again to abort immediately)")

    try:
        previous_handlers = {
            signal.SIGINT: signal.signal(signal.SIGINT, on_signal),
            signal.SIGTERM: signal.signal(signal.SIGTERM, on_signal),
        }
    except ValueError:
        # not the main thread (an embedded runner — tests, notebooks):
        # signal handling stays with the host application
        previous_handlers = {}
    if args.forensics and not args.worker_metrics:
        # the ledger's distance evidence rides worker_sq_dist
        info("--forensics implies --worker-metrics: enabling the per-worker "
             "suspicion diagnostics")
        args.worker_metrics = True

    ignored = [flag for flag, value in (
        ("--client", args.client), ("--server", args.server),
        ("--ps-job-name", args.ps_job_name), ("--ev-job-name", args.ev_job_name),
        ("--wk-job-name", args.wk_job_name), ("--MPI", args.mpi), ("--no-wait", args.no_wait),
    ) if value]
    if ignored:
        warning(
            "Compat no-op flags ignored (cluster topology and transport dissolved "
            "under single-controller SPMD, see docs/transport.md): %s" % " ".join(ignored)
        )

    # Worker-count sanity (reference: runner.py:253-260)
    n, f, r = args.nb_workers, args.nb_decl_byz_workers, args.nb_real_byz_workers
    if n < 1:
        raise UserException("Need at least 1 worker (got %d)" % n)
    if r > n:
        raise UserException("More real Byzantine workers (%d) than workers (%d)" % (r, n))
    if r > f:
        warning("More real Byzantine workers (%d) than declared (%d): the GAR bound is void" % (r, f))
    if n <= 2 * f:
        warning("n = %d <= 2f = %d: most GARs offer no guarantee at this ratio" % (n, 2 * f))

    with Context("cluster"):
        devices = jax.devices()
        if mesh_axes is not None:
            w_axis, pp_axis, tp_axis = mesh_axes
            if n % w_axis != 0:
                raise UserException(
                    "--mesh worker axis W=%d must divide --nb-workers %d "
                    "(k = n/W logical Byzantine workers are vmapped per "
                    "(pipe x model) submesh — the large-n regime, "
                    "docs/gar_scaling.md)" % (w_axis, n)
                )
            mesh = make_mesh(
                nb_workers=w_axis, model_parallelism=tp_axis,
                pipeline_parallelism=pp_axis, devices=devices[:requested_devices],
            )
            info(
                "Sharded mesh: %d worker slot(s) x %d pipeline stage(s) x %d-way "
                "tensor parallelism on %d %s device(s) (%s), %d logical worker(s)/slot"
                % (w_axis, pp_axis, tp_axis, requested_devices,
                   devices[0].platform, devices[0].device_kind, n // w_axis)
            )
        else:
            nb_devices = args.nb_devices
            if nb_devices is None:
                nb_devices = max(d for d in range(1, len(devices) + 1) if n % d == 0)
            mesh = make_mesh(nb_workers=nb_devices, devices=devices[:nb_devices])
            info(
                "Mesh: %d x %s device(s) (%s), %d worker(s)/device"
                % (nb_devices, devices[0].platform, devices[0].device_kind,
                   n // nb_devices)
            )

    # Host span tracing (obs/trace.py, docs/observability.md): installed
    # BEFORE the graph/restore phases so their spans are captured too.  Each
    # process writes its own file (suffixed for non-lead processes) — one
    # shared path would clobber.
    if args.trace_file:
        path = args.trace_file
        if jax.process_index() != 0:
            path = "%s.%d" % (path, jax.process_index())
        if args.run_id is None and jax.process_count() > 1:
            # summaries/forensics are lead-only, so the lead's streams still
            # join — but each process GENERATES its own id, so non-lead
            # trace files won't carry the lead's without an explicit id
            warning(
                "Multi-process run without --run-id: per-process trace files "
                "carry independent run_ids; pass --run-id to join them"
            )
        trace.install(path, run_id=run_id)
        info("Span tracing to %r (run_id %s)" % (path, run_id))

    # Causal run journal (obs/events.py): installed BEFORE the graph phase
    # so escalation/deadline/forgery decisions from step 0 on land in one
    # timeline.  Lead-only, like summaries/forensics — the decisions it
    # records are host policy, which is lead-side by construction.
    if args.journal and jax.process_index() == 0:
        from . import parse_cause_flag

        obs_events.install(args.journal, run_id=run_id,
                           max_bytes=args.journal_max_bytes)
        obs_events.emit(
            "run_start", role="train", experiment=args.experiment,
            aggregator=args.aggregator, nb_workers=n, declared_f=f,
            pid=os.getpid(), cause=parse_cause_flag(args.cause),
        )
        info("Run journal to %r (run_id %s)" % (args.journal, run_id))

    # Guardian recovery layer (guardian/, docs/guardian.md): parsed up front
    # so a bad ladder/threshold fails before any compilation.
    from ..guardian import (
        RESEED_STRIDE,
        RNG_PERTURB_TAG,
        GuardianConfig,
        Overrides,
        Watchdog,
        note_escalation,
    )
    from ..guardian import probe as health

    guardian = None
    if args.guardian:
        guardian = GuardianConfig(args.guardian_args)
        if not args.checkpoint_dir:
            raise UserException(
                "--guardian rolls back to on-disk snapshots; pass --checkpoint-dir"
            )
        if jax.process_count() > 1:
            raise UserException(
                "--guardian is single-process for now: rollback decisions would "
                "need a cross-host broadcast to keep the SPMD step counts aligned"
            )
    watchdog = Watchdog(guardian) if guardian is not None else None

    # Aggregation topology (--topology, topology/): the tree spec parses
    # and runs its f-composition arithmetic HERE, before anything compiles,
    # and substitutes for --aggregator in the Overrides record — so a
    # guardian escalation that swaps the rule for a ladder rung also
    # retires the host tree plane (a flat rung has no sub-aggregators to
    # supervise; rolling back to the tree rung reactivates it).
    topology_spec = None
    topology = None
    if args.topology is not None:
        from ..topology import parse_topology_spec

        if args.aggregator != "tree":
            raise UserException(
                "--topology replaces the aggregation rule with the tree "
                "spec; pass --aggregator tree (got %r)" % args.aggregator
            )
        if args.aggregator_args:
            raise UserException(
                "--topology carries the tree's arguments inline "
                "(tree:g=...,rules=...); drop --aggregator-args"
            )
        topology_spec = parse_topology_spec(args.topology, n, f)
        info("Topology: %s" % topology_spec.describe())

    # The escalation ladder overrides exactly these knobs; everything else
    # about the run is immutable.  The training stack is built FROM an
    # Overrides record so a guardian rollback can rebuild it mid-run (one
    # recompile per escalation, paid only on the rare recovery path).
    overrides = Overrides(
        f,
        args.topology if topology_spec is not None else args.aggregator,
        () if topology_spec is not None else tuple(args.aggregator_args),
        reputation_decay=args.reputation_decay,
        quarantine_threshold=args.quarantine_threshold,
    )
    unroll = max(1, args.unroll)

    # Bounded-wait mode flag (parallel/bounded.py), needed before the
    # flight-recorder lane set: under a deadline the chaos schedule moves
    # to the host clock, so the in-graph regime lane does not exist.
    bounded_wait = (args.step_deadline is not None
                    or args.straggler_stall > 0
                    or args.topology is not None)

    # Flight recorder (obs/flight.py): the ring's lane set mirrors exactly
    # what the engine will compute (validated again by the engine itself).
    # Constructed once and shared across guardian rebuilds — the layout is
    # immutable; the BUFFERS are per-state and re-init on every rollback.
    flight_rec = None
    if args.flight:
        flight_rec = obs_flight.FlightRecorder(
            args.flight, n, probe=True, worker_metrics=args.worker_metrics,
            chaos=bool(args.chaos) and not bounded_wait, secure=args.secure,
        )
        if args.flight < unroll:
            warning(
                "--flight capacity %d < --unroll %d: a summary fetch cannot "
                "cover the whole last chunk; size the ring to at least the "
                "unroll (ideally the summary delta)" % (args.flight, unroll)
            )
    # Programmatic profiler window (--xprof A:B): parsed up front so a bad
    # spec fails before any compilation.
    xprof = None
    if args.xprof:
        xprof = obs_profiler.ProfilerWindow(args.xprof, args.trace_dir)

    with Context("graph"):
        experiment = models.instantiate(args.experiment, args.experiment_args)
        attack = attacks.instantiate(args.attack, n, r, args.attack_args) if args.attack else None
        lossy = LossyLink(args.udp, args.udp_args) if args.udp > 0 else None
        chaos = None
        if args.chaos:
            from ..chaos import ChaosSchedule

            chaos = ChaosSchedule(
                args.chaos, n, nb_real_byz=r, args=args.chaos_args,
                allow_topology_faults=args.topology is not None,
            )
            info("Chaos schedule: %d regime(s): %s" % (
                len(chaos), "  ".join("%d:%s" % t for t in chaos.transitions())
            ))
            if topology_spec is not None:
                # every corrupt-agg/straggle-agg target must name a node
                # the declared tree actually has — rejected here, loudly,
                # before any compilation
                for regime in chaos.regimes:
                    for lvl, unit in regime.agg_corrupt + regime.agg_straggle:
                        topology_spec.validate_fault_target(lvl, unit)

        base_schedule = build_schedule(args.learning_rate, args.learning_rate_args)

        # One-time validations and warnings — outside the (re)builder so an
        # escalation rebuild never repeats them.
        if mesh_axes is not None:
            if args.input_source == "device":
                raise UserException(
                    "--input-source device needs the flat engine (the sharded "
                    "engine's batches flow through the pipeline stages); drop "
                    "--mesh or use --input-source stream"
                )
            if not getattr(experiment, "supports_sharded", False):
                raise UserException(
                    "Experiment %r does not publish sharded hooks (sharded_init/"
                    "sharded_specs/sharded_loss); --mesh currently works with: %s"
                    % (args.experiment, ", ".join(
                        name for name in models.itemize()
                        if getattr(models.get(name), "supports_sharded", False)) or "none")
                )
        else:
            if args.granularity in ("layer", "global"):
                raise UserException(
                    "--granularity %s needs the sharded engine: pass --mesh W,PP,TP"
                    % args.granularity
                )
            if args.input_source == "device":
                if jax.process_count() > 1:
                    raise UserException(
                        "--input-source device is single-process for now: "
                        "replicating the dataset would device_put onto "
                        "non-addressable devices; use --input-source stream"
                    )
                if (experiment.train_arrays() is None
                        and experiment.route_augmentation_to_device()):
                    # host-tier augmentation with an in-step device twin
                    # (models/preprocessing.py): re-route it so augmented
                    # training gets device sampling too (the augmentation
                    # STREAM changes — in-step keyed draws — exactly like
                    # the sample stream device sampling already changes)
                    info(
                        "--input-source device: routing %r augmentation "
                        "through the in-step device tier"
                        % getattr(experiment, "preprocessing", "host")
                    )
                if experiment.train_arrays() is None:
                    raise UserException(
                        "--input-source device: experiment %r keeps a host-side "
                        "batch transform or a streaming corpus (train_arrays() "
                        "is None), so an in-graph gather cannot reproduce its "
                        "input stream; use --input-source stream" % args.experiment
                    )

        # Bounded-wait aggregation (--step-deadline, parallel/bounded.py):
        # per-worker async submissions against a host deadline; stalls
        # without a deadline drive the SYNCHRONOUS baseline the straggler
        # sweep compares against.  Validated before any compilation.
        straggler_model = None
        deadline_controller = None
        if bounded_wait:
            from ..parallel.bounded import BoundedWaitStep, HostStragglerModel

            # bounded-wait v3: nontrivial (pipe x model) submeshes are
            # supported — engine.build_submesh_grad compiles one collective
            # program per worker-axis submesh, so each of the W submissions
            # carries its own deadline (docs/engine.md, "v3: submesh
            # deadlines and age reweighting")
            if args.incremental_aggregation and mesh_axes is not None:
                raise UserException(
                    "--incremental-aggregation folds per-WORKER rows; the "
                    "sharded mode's per-submesh submissions need a "
                    "per-group fold layout — run the flat engine"
                )
            if args.incremental_aggregation and args.step_deadline is None:
                raise UserException(
                    "--incremental-aggregation overlaps decode with the "
                    "deadline window; pass --step-deadline"
                )
            if mesh_axes is not None and args.microbatches is not None:
                raise UserException(
                    "--step-deadline on the sharded engine computes per-"
                    "worker FULL-batch gradients over experiment.loss; "
                    "--microbatches only shapes the fused pipeline loss — "
                    "drop it (the bounded path would silently ignore it)"
                )
            if unroll > 1:
                raise UserException(
                    "--step-deadline closes every round on the host clock; "
                    "a scanned --unroll chunk cannot be interrupted — use "
                    "--unroll 1"
                )
            if args.input_source == "device":
                raise UserException(
                    "--step-deadline dispatches per-worker host batches; use "
                    "--input-source stream"
                )
            if args.secure_mask:
                raise UserException(
                    "--step-deadline + --secure-mask is not supported: the "
                    "pairwise pads are added inside the fused submission "
                    "pipeline and would not cancel across per-worker "
                    "dispatches (--secure digests DO ride the bounded path)"
                )
            if args.udp > 0:
                raise UserException(
                    "--step-deadline replaces the simulated lossy transport; "
                    "drop --UDP (real timeouts produce the NaN rows)"
                )
            if jax.process_count() > 1:
                raise UserException(
                    "--step-deadline is single-process (the submission "
                    "threads poll one host's device streams)"
                )
            # a schedule whose only content is topology faults belongs to
            # the TREE plane (topology.schedule above); the worker-plane
            # straggler model consumes straggler regimes and refuses
            # in-graph fault kinds — hand it the schedule only when there
            # is worker-plane content to consume or refuse
            chaos_worker = chaos
            if chaos is not None and not (
                    chaos.has_stragglers or chaos.has_attacks
                    or chaos.has_drop or chaos.has_forgery):
                chaos_worker = None
            if (args.straggler_stall > 0 or args.straggler_rate > 0
                    or chaos_worker is not None):
                straggler_model = HostStragglerModel(
                    n, args.straggler_stall, rate=args.straggler_rate,
                    chaos=chaos_worker, seed=args.seed,
                    jitter=args.straggler_jitter,
                )
            elif args.straggler_jitter > 0:
                raise UserException(
                    "--straggler-jitter scales an injected stall; without "
                    "--straggler-stall/--straggler-rate or a --chaos "
                    "straggler regime it injects nothing — drop it or add "
                    "a stall source"
                )
            if args.deadline_percentile is not None:
                from ..parallel.deadline import DeadlineController

                if args.step_deadline is None:
                    raise UserException(
                        "--deadline-percentile needs --step-deadline (the "
                        "controller's initial window and default ceiling)"
                    )
                # constructed ONCE, outside the guardian rebuild path: the
                # learned window is host policy state that must survive an
                # escalation (and its registry instruments register once)
                deadline_controller = DeadlineController(
                    args.step_deadline,
                    percentile=args.deadline_percentile,
                    floor=args.deadline_floor,
                    ceiling=args.deadline_ceiling,
                    ema=args.deadline_ema,
                    registry=registry,
                )
            if args.stale_infill and args.step_deadline is None:
                raise UserException(
                    "--stale-infill needs --step-deadline: the synchronous "
                    "protocol never times anyone out"
                )
            if args.stale_reweight and not args.stale_infill:
                raise UserException(
                    "--stale-reweight rescales STALE CARRY rows; without "
                    "--stale-infill every miss is a NaN drop and there is "
                    "nothing to reweight — pass --stale-infill"
                )
            if topology_spec is not None:
                if mesh_axes is not None:
                    raise UserException(
                        "--topology needs the flat engine: the tree's "
                        "custody plane signs the stacked per-worker wire "
                        "rows, which the sharded submesh submissions never "
                        "materialize — drop --mesh"
                    )
                if args.incremental_aggregation:
                    raise UserException(
                        "--topology and --incremental-aggregation are "
                        "mutually exclusive: the tree's custody plane "
                        "signs the stacked wire rows at the round "
                        "barrier, which the incremental fold never "
                        "materializes"
                    )
                from ..topology import TreeAggregator

                # constructed ONCE, outside the guardian rebuild path,
                # exactly like the deadline controller: the custody chain
                # head and the learned per-level windows are host protocol
                # state that must survive an escalation (per-level
                # controllers carry no registry instruments of their own —
                # the TreeAggregator's labeled counters are the metrics
                # surface, so they cannot collide with the leaf
                # controller's gauges)
                topology = TreeAggregator(
                    topology_spec, registry=registry,
                    session_secret=(args.session_secret.encode()
                                    if args.session_secret else None),
                    deadline=args.step_deadline,
                    deadline_opts=(dict(
                        percentile=args.deadline_percentile,
                        floor=args.deadline_floor,
                        ceiling=args.deadline_ceiling,
                        ema=args.deadline_ema,
                    ) if args.deadline_percentile is not None else None),
                )
                topology.schedule = chaos
        elif (args.deadline_percentile is not None or args.stale_infill
                or args.stale_reweight or args.straggler_jitter > 0
                or args.incremental_aggregation):
            raise UserException(
                "--deadline-percentile/--stale-infill/--stale-reweight/"
                "--straggler-jitter/--incremental-aggregation are "
                "bounded-wait options; pass --step-deadline (or "
                "--straggler-stall for the synchronous baseline)"
            )
        if (exchange_codec is not None and exchange_codec.uses_ef
                and jax.process_count() > 1):
            raise UserException(
                "--exchange %s is single-process: the error-feedback "
                "residual is a worker-sharded buffer the checkpoint path "
                "serializes (a multi-host device_get cannot see every "
                "shard) — drop :ef or run one process"
                % exchange_codec.spec()
            )

        def make_regularized_loss(base_loss, l1, l2):
            # l1/l2 regularization wraps the per-worker loss (reference:
            # graph.py:125-139) — the ONE wrapper shared by the flat
            # engine and the sharded bounded-wait submission body, so the
            # two arms cannot silently diverge.  A loss that carries its
            # model's counters (``has_aux``: models/sdar.py, laguna.py)
            # keeps them through the wrapper
            has_aux = getattr(base_loss, "has_aux", False)

            def loss_fn(params, batch):
                loss = base_loss(params, batch)
                loss, counters = loss if has_aux else (loss, None)
                leaves = jax.tree_util.tree_leaves(params)
                if l1:
                    loss = loss + l1 * sum(jnp.sum(jnp.abs(p)) for p in leaves)
                if l2:
                    loss = loss + l2 * sum(jnp.sum(p * p) for p in leaves)
                return (loss, counters) if has_aux else loss

            loss_fn.has_aux = has_aux
            return loss_fn

        class TrainingStack:
            """The rebuildable half of the run: engine + jitted step/eval
            programs + optimizer, derived from an Overrides record.  A
            guardian escalation builds a new one; everything else (mesh,
            experiment, chaos schedule, cadences) is immutable."""

        # Bucket-level masking (secure/masking.py): the pad key material
        # derives from the session secret; spec feasibility (mean-inner
        # meta-GAR) is validated inside enable_masking at parse time — and
        # again on every guardian escalation rebuild, so a ladder rung that
        # swaps to an unmaskable rule is rejected, not silently unmasked.
        group_masking = None
        if args.secure_mask:
            from ..secure import GroupMasking

            group_masking = GroupMasking.from_secret(args.session_secret.encode())

        def build_training(ov):
            ts = TrainingStack()
            ts.overrides = ov
            gar = gars.instantiate(ov.gar_name, n, ov.f, list(ov.gar_args))
            if group_masking is not None:
                from ..secure import enable_masking

                enable_masking(gar, group_masking)
            if ov.lr_scale != 1.0:
                # escalation's lr damping composes with the named schedule
                def schedule(s, _base=base_schedule, _x=ov.lr_scale):
                    return _base(s) * _x
            else:
                schedule = base_schedule
            tx = build_optimizer(args.optimizer, schedule, args.optimizer_args)
            ts.gar, ts.schedule, ts.tx = gar, schedule, tx
            ts.device_dataset = None
            ts.sampled_tail = None
            ts.bounded_step = None
            if mesh_axes is not None:
                # ---- sharded mode of the ONE engine (per-layer GAR on
                # sharded grads; docs/engine.md) ----
                # ``vector`` (the flat default) means whole-vector selection,
                # which the sharded mode spells ``global`` (one global (n, n)
                # distance matrix accumulated across shards).
                gran = "global" if args.granularity == "vector" else args.granularity
                engine = RobustEngine(
                    mesh, gar, nb_workers=n, sharding="sharded",
                    nb_real_byz=r, attack=attack, lossy_link=lossy,
                    granularity=gran, exchange=args.exchange,
                    worker_momentum=args.worker_momentum,
                    worker_metrics=args.worker_metrics,
                    reputation_decay=ov.reputation_decay,
                    quarantine_threshold=ov.quarantine_threshold,
                    # The sharded loss is a LOCAL PARTIAL under shard_map, so
                    # the engine applies l1/l2 analytically on the completed
                    # gradients instead of wrapping the loss (docs/engine.md)
                    l1_regularize=args.l1_regularize,
                    l2_regularize=args.l2_regularize,
                    # under bounded-wait the straggler schedule moved to the
                    # HOST clock (straggler_model); in-graph chaos is off
                    chaos=None if bounded_wait else chaos,
                    secure=args.secure,
                    flight=flight_rec,
                )
                loss_fn = experiment.sharded_loss(
                    mesh_axes[1],
                    2 if args.microbatches is None else args.microbatches,
                )

                def make_fresh_state(seed=args.seed):
                    return engine.init_state(
                        experiment.sharded_init(mesh_axes[1]), experiment.sharded_specs(),
                        tx, seed=seed,
                    )

                state0 = make_fresh_state()
                if bounded_wait:
                    # the sharded bounded-wait variant: per-submesh
                    # submission streams, per-group deadlines — on a
                    # nontrivial (pipe x model) mesh each unit is one
                    # collective program with its own window (v3,
                    # engine.build_submesh_grad).  The submission body
                    # needs the GLOBAL per-worker loss — the plain loss IS
                    # the local partial (GSPMD partitions it over the
                    # in-group axes), with l1/l2 folded in like the flat
                    # branch (the sharded engine's analytic reg path
                    # belongs to the fused step body).
                    bounded_loss = make_regularized_loss(
                        experiment.loss, args.l1_regularize, args.l2_regularize)

                    ts.bounded_step = BoundedWaitStep(
                        engine, bounded_loss, tx, state0.params,
                        deadline=args.step_deadline,
                        straggler_model=straggler_model, registry=registry,
                        controller=deadline_controller,
                        stale_infill=args.stale_infill,
                        stale_max_age=args.stale_max_age,
                        stale_reweight=args.stale_reweight,
                    )
                    ts.step_fn = ts.bounded_step
                else:
                    ts.step_fn = engine.build_step(loss_fn, tx, state0)
                ts.multi_fn = (
                    engine.build_multi_step(loss_fn, tx, state0) if unroll > 1 else None
                )
                ts.eval_fn = None  # metric sums need a dense replica; eval reports loss
                ts.eval_loss_fn = engine.build_eval(loss_fn, state0)
            else:
                engine = RobustEngine(
                    mesh, gar, n, nb_real_byz=r, attack=attack, lossy_link=lossy,
                    exchange=args.exchange,
                    worker_momentum=args.worker_momentum,
                    batch_transform=experiment.device_transform(),
                    worker_metrics=args.worker_metrics,
                    reputation_decay=ov.reputation_decay,
                    quarantine_threshold=ov.quarantine_threshold,
                    granularity=args.granularity,
                    # under bounded-wait the straggler schedule moved to the
                    # HOST clock (straggler_model); in-graph chaos is off
                    chaos=None if bounded_wait else chaos,
                    secure=args.secure,
                    flight=flight_rec,
                )

                loss_fn = make_regularized_loss(
                    experiment.loss, args.l1_regularize, args.l2_regularize)

                def make_fresh_state(seed=args.seed):
                    # params ALWAYS init from the run seed; ``seed`` only moves
                    # the RNG stream (guardian's from-scratch retry path)
                    return engine.init_state(
                        experiment.init(jax.random.PRNGKey(args.seed)), tx, seed=seed
                    )

                state0 = make_fresh_state()
                if bounded_wait:
                    # per-worker async submissions + deadline-closed rounds
                    # (the guardian rebuild path constructs this exactly
                    # like the fused step: one stack, one engine; the
                    # deadline CONTROLLER is shared across rebuilds — its
                    # learned window survives an escalation)
                    ts.bounded_step = BoundedWaitStep(
                        engine, loss_fn, tx, state0.params,
                        deadline=args.step_deadline,
                        straggler_model=straggler_model, registry=registry,
                        controller=deadline_controller,
                        stale_infill=args.stale_infill,
                        stale_max_age=args.stale_max_age,
                        stale_reweight=args.stale_reweight,
                        incremental=args.incremental_aggregation,
                        # the tree rides only its own rung: an escalation
                        # that swaps the rule retires the host plane with
                        # it (nothing to supervise under a flat rule)
                        topology=(topology if topology is not None
                                  and ov.gar_name == args.topology else None),
                    )
                    ts.step_fn = ts.bounded_step
                else:
                    ts.step_fn = engine.build_step(loss_fn, tx)
                if args.input_source == "device":
                    # The whole train split lives on the accelerator; the
                    # unrolled branch dispatches the in-graph sampling trainer
                    # (one scan per chunk, zero per-step host transfer).
                    ts.device_dataset = engine.replicate(experiment.train_arrays())
                    ts.multi_fn = engine.build_sampled_multi_step(
                        loss_fn, tx, repeat_steps=unroll,
                        batch_size=experiment.batch_size,
                    )
                    tail_fns = {}

                    def sampled_tail(nb_steps, _cache=tail_fns):
                        # The final (max_step - offstep) % unroll steps run
                        # device-sampled too, through ONE tail-sized
                        # executable (the remainder is invariant across the
                        # run — chunks advance by unroll and rollbacks land
                        # on chunk boundaries — so this compiles once; a
                        # compile-count test asserts it).
                        fn = _cache.get(nb_steps)
                        if fn is None:
                            fn = engine.build_sampled_multi_step(
                                loss_fn, tx, repeat_steps=nb_steps,
                                batch_size=experiment.batch_size,
                            )
                            _cache[nb_steps] = fn
                        return fn

                    ts.sampled_tail = sampled_tail
                else:
                    ts.multi_fn = engine.build_multi_step(loss_fn, tx) if unroll > 1 else None
                ts.eval_fn = engine.build_eval_sums(experiment.metrics)
                ts.eval_loss_fn = None
            ts.engine = engine
            ts.make_fresh_state = make_fresh_state
            ts.initial_state = state0
            # --gar-probe instrument (built lazily at the first summary fire
            # so unprobed runs pay nothing): the rule's wall time at the
            # run's exact (n, d), d = the whole model dimension.
            ts.model_dim = sum(
                int(np.prod(leaf.shape))
                for leaf in jax.tree_util.tree_leaves(state0.params)
            )
            ts.gar_probe_fn = None
            return ts

        ts = build_training(overrides)
        state = ts.initial_state

    # Cadences with config.py defaults (reference: config.py:54-61)
    def pick(value, default):
        return default if value is None else value

    # Multi-host discipline: evaluation is a *collective* (every process runs
    # the SPMD eval program), so its firing must be step-deterministic —
    # wall-clock cadences can disagree across hosts and deadlock the
    # collective.  File/snapshot writes are process-0-only (the reference has
    # exactly one evaluator and one PS writing state, runner.py:318-330).
    nb_processes = jax.process_count()
    lead = jax.process_index() == 0
    eval_period = pick(args.evaluation_period, config.default_evaluation_period)
    eval_delta = pick(args.evaluation_delta, config.default_evaluation_delta)
    if nb_processes > 1 and eval_period >= 0.0:
        if eval_delta < 0:
            warning(
                "Multi-process run: wall-period eval is not host-deterministic and "
                "is DISABLED; pass --evaluation-delta to evaluate"
            )
        else:
            warning("Multi-process run: ignoring --evaluation-period (keeping the step delta)")
        eval_period = -1.0

    eval_trigger = CadenceTrigger(eval_delta, eval_period)
    ckpt_trigger = CadenceTrigger(
        pick(args.checkpoint_delta, config.default_checkpoint_delta),
        pick(args.checkpoint_period, config.default_checkpoint_period),
    )
    summary_trigger = CadenceTrigger(
        pick(args.summary_delta, config.default_summary_delta),
        pick(args.summary_period, config.default_summary_period),
    )
    ckpt_auth = None
    ckpt_cipher = None
    if args.encrypt_checkpoints and not args.session_secret:
        raise UserException(
            "--encrypt-checkpoints derives its key from --session-secret; "
            "pass both"
        )
    if args.session_secret and args.checkpoint_dir:
        # The session secret also tags snapshots: a swapped/corrupted
        # checkpoint fails verification at restore instead of silently
        # seeding training (reference parity: the same key material signs
        # gradients and would sign any persisted state).
        from ..parallel.auth import GradientAuthenticator

        # context=b"ckpt" keeps checkpoint-tag keys disjoint from the
        # bring-up handshake's (same secret, separate key family)
        ckpt_auth = GradientAuthenticator(args.session_secret.encode(), 1, context=b"ckpt")
        if args.encrypt_checkpoints:
            from ..parallel.crypto import SnapshotCipher

            ckpt_cipher = SnapshotCipher(args.session_secret.encode())
    # Authenticated gradient submission (secure/submit.py): the host-side
    # aggregator role — per-(worker, step) HMAC sign/verify over the
    # in-graph digests, fed one dispatch behind like the forensics ledger.
    # Lead-only: the digests are replicated, every process would verify
    # identical material.
    secure_auth = None
    if args.secure and lead:
        from ..secure import SubmissionAuthenticator

        secure_auth = SubmissionAuthenticator(
            args.session_secret.encode(), n, registry=registry
        )
    # Chain of custody (secure/custody.py): signed lineage manifests beside
    # every snapshot, verified by this runner's auto-restore and the
    # guardian rollback restore — the training end of train -> sign -> serve.
    custody = None
    if args.secure and args.checkpoint_dir:
        from ..secure import ChainOfCustody
        from ..secure.custody import data_digest_for

        identity = "%s|%s|seed=%d|n=%d" % (
            args.experiment, " ".join(args.experiment_args), args.seed, n,
        )
        custody = ChainOfCustody(
            args.session_secret.encode(), run_id=run_id,
            experiment=args.experiment,
            gar_spec=overrides.describe(),
            data_digest=data_digest_for(experiment, identity),
            submission=secure_auth,
            allow_unsigned=args.allow_unsigned,
        )
    checkpoints = Checkpoints(
        args.checkpoint_dir,
        pick(args.checkpoint_base_name, config.default_checkpoint_base_name),
        args.checkpoint_keep,
        authenticator=ckpt_auth,
        cipher=ckpt_cipher,
        custody=custody,
        allow_legacy_tags=not args.no_legacy_checkpoint_tags,
        # Serialization + disk I/O run on a writer thread (the host fetch
        # stays synchronous — the step donates the state buffers); wait()
        # joins at every later fire and at exit, so a failing write surfaces
        # within one cadence and a returned run is fully flushed.
        background=True,
    ) if args.checkpoint_dir else None
    save_snapshots = checkpoints is not None and lead
    eval_file = EvalFile(args.evaluation_file if lead else None)
    summaries = SummaryWriter(args.summary_dir if lead else None, run_id=run_id)

    # Byzantine forensics ledger (obs/forensics.py): fed one dispatch behind
    # (the same lag as the NaN-abort check, so the feed never blocks the
    # in-flight step), written at exit.  Lead-only — the diagnostics are
    # replicated, every process would ledger identical evidence.
    ledger = None
    if args.forensics and lead:
        ledger = ForensicsLedger(n, run_id=run_id)
    if topology is not None and ledger is not None:
        # the tree's custody verdicts land on the run ledger's SEPARATE
        # sub-aggregator surface (obs/forensics.py) — a forged emission
        # names its (level, unit), never a worker
        topology.ledger = ledger

    # Compile observability (obs/profiler.py): every compile-cache miss of
    # a wrapped executable becomes a named counter + a tagged summary event
    # carrying the offending abstract shapes; jax.monitoring additionally
    # counts every backend compile in the process.  Host-side polling only
    # — the jitted programs are never touched.
    compile_watch = obs_profiler.CompileWatch(
        # ``step`` is the train loop's local below; the provider only runs
        # when a wrapped dispatch fires, by which point it is assigned
        registry, summaries=summaries, step_provider=lambda: step
    )
    obs_profiler.install_compile_listener(registry)
    nb_mem_devices = obs_profiler.install_memory_gauges(registry)
    if nb_mem_devices:
        info("Device memory gauges live on %d device(s)" % nb_mem_devices)

    def instrument_stack(stack):
        """Wrap a TrainingStack's dispatches in the compile watch (called
        on the initial stack and on every guardian escalation rebuild)."""
        stack.step_fn = compile_watch.wrap("train_step", stack.step_fn)
        if stack.multi_fn is not None:
            stack.multi_fn = compile_watch.wrap("train_multi_step", stack.multi_fn)
        if stack.eval_fn is not None:
            stack.eval_fn = compile_watch.wrap("eval_step", stack.eval_fn)
        if stack.eval_loss_fn is not None:
            stack.eval_loss_fn = compile_watch.wrap("eval_loss", stack.eval_loss_fn)
        if stack.sampled_tail is not None:
            inner_tail = stack.sampled_tail
            stack.sampled_tail = lambda nb: compile_watch.wrap(
                "train_sampled_tail[%d]" % nb, inner_tail(nb)
            )
        return stack

    instrument_stack(ts)

    def dump_metrics_file():
        if not args.metrics_file or not lead:
            return
        tmp = args.metrics_file + ".tmp"
        with open(tmp, "w") as fd:
            fd.write(registry.render_prometheus())
        os.replace(tmp, args.metrics_file)

    # Auto-restore the latest checkpoint (reference: runner.py:514-525).
    # Every process must make the SAME restore decision or the SPMD step
    # counts diverge and the collectives deadlock, so process 0's choice is
    # broadcast and the others must be able to see that snapshot (shared
    # filesystem) — failing loudly beats hanging.
    offstep = 0
    if checkpoints is not None:
        steps_on_disk = checkpoints.steps()
        target_step = steps_on_disk[-1] if steps_on_disk else -1
        if nb_processes > 1:
            from jax.experimental import multihost_utils

            target_step = int(multihost_utils.broadcast_one_to_all(np.int32(target_step)))
            if target_step >= 0 and not checkpoints.can_restore(target_step):
                raise UserException(
                    "Process %d cannot see checkpoint step %d: multi-host resume needs "
                    "--checkpoint-dir on a filesystem shared with process 0"
                    % (jax.process_index(), target_step)
                )
        if target_step >= 0:
            with Context("restore"):
                # The worker-sharded side buffers (CLEVER carry, momentum) may
                # span hosts and are never serialized: keep the live zeroed
                # buffers aside and restore into a stripped host template.
                carry, momentum = state.carry, state.momentum
                template = jax.device_get(state.replace(carry=None, momentum=None))
                restored, offstep = checkpoints.restore(template, step=target_step)
                state = ts.engine.put_state(restored.replace(carry=carry, momentum=momentum))
            if lead:
                # Rows beyond the restored step belong to a timeline this
                # run is about to overwrite; appending after them would
                # leave duplicate/interleaved step columns in the TSV.
                dropped = eval_file.truncate_after(offstep)
                if dropped:
                    info(
                        "Trimmed %d stale eval row(s) beyond restored step %d"
                        % (dropped, offstep)
                    )
            if watchdog is not None and offstep > 0:
                # The snapshot this run just trusted enough to resume FROM is
                # the guardian's initial last-known-good: a divergence before
                # the first healthy in-run save must roll back here, not wipe
                # the directory and restart from scratch.
                checkpoints.pin(offstep)

    # Multi-host boundary authentication (reference parity: every worker->PS
    # push is signed, mpi_rendezvous_mgr.patch:585-627; here the surface is
    # process bring-up — see parallel/auth.py docstring). After restore, so
    # the digest covers the parameters training will actually start from.
    if args.session_secret:
        from ..parallel.auth import authenticate_processes

        with Context("auth"):
            authenticate_processes(
                args.session_secret.encode(), state.params, step=offstep,
                verify_equal=mesh_axes is None,
            )
            info("Host handshake OK: %d process(es) authenticated" % nb_processes)
    elif nb_processes > 1:
        warning(
            "Multi-process run without --session-secret: the host boundary is "
            "UNAUTHENTICATED (the reference signs every worker->PS tensor, "
            "mpi_rendezvous_mgr.patch:585-627); pass the same --session-secret "
            "on every host to enable the bring-up handshake"
        )

    max_step = pick(args.max_step, config.default_max_step)
    train_iter = None
    prefetcher = None
    chunk_pipeline = None

    def next_chunk():
        """K distinct batches as one (K, n, ...) stack for the unrolled path
        (one contiguous gather via next_many when the iterator provides it)."""
        if hasattr(train_iter, "next_many"):
            return train_iter.next_many(unroll)
        return jax.tree_util.tree_map(
            lambda *xs: np.stack(xs), *[next(train_iter) for _ in range(unroll)]
        )

    def reset_input(start_step, reseed=0):
        """(Re)build the input pipeline positioned at ``start_step``.

        Called at startup (start_step = the auto-restored step) and after a
        guardian rollback.  The stream is FAST-FORWARDED to ``start_step``
        so a resumed run consumes exactly the batches the uninterrupted run
        would have — the last piece of bit-identical resume (the serialized
        step/params/opt-state/RNG already restore exactly).  A rollback
        passes ``reseed`` > 0 instead: it draws the replay window's batches
        from a fresh stream, one more way a retry differs from the
        deterministic trajectory that just diverged."""
        nonlocal train_iter, prefetcher, chunk_pipeline
        if prefetcher is not None:
            prefetcher.close()
            prefetcher = None
        if chunk_pipeline is not None:
            chunk_pipeline.close()
            chunk_pipeline = None
        train_iter = experiment.make_train_iterator(
            n, seed=args.seed + 1 + RESEED_STRIDE * reseed
        )
        if start_step and not reseed:
            if hasattr(train_iter, "skip"):
                train_iter.skip(start_step)
            else:
                if start_step > 1000:
                    warning(
                        "Resume fast-forward: this iterator has no skip(), so "
                        "%d batches are drawn and discarded to realign the "
                        "sample stream — expect a slow startup" % start_step
                    )
                for _ in range(start_step):
                    next(train_iter)
        if args.prefetch > 0 and nb_processes == 1 and ts.device_dataset is None:
            # Overlap host batch assembly + host->device transfer with compute
            # (the reference's fetcher/batcher threads + prefetch queue,
            # cnnet.py:115-146).  Disabled in multi-process runs: a background
            # device_put would interleave differently on each host, breaking the
            # strict cross-process ordering collectives require.
            from ..models.datasets import (
                ChunkPipeline, DevicePrefetcher, supports_buffered_next_many)

            if unroll == 1:
                prefetcher = DevicePrefetcher(
                    train_iter, ts.engine.shard_batch, depth=args.prefetch
                )
            else:
                # The three-stage chunk pipeline (docs/input_pipeline.md):
                # parallel sharded gather into ping-pong host buffers,
                # sliced async transfer, device-side assemble — overlap is
                # exported through the metrics registry (input_* family).
                # FINITE producer: exactly the chunks the loop will consume
                # ((max_step-start_step) // unroll — the loop's unrolled-branch
                # count is deterministic).  An infinite producer would over-draw
                # from the shared train_iter and the tail handoff would discard
                # a thread-timing-dependent number of draws, skipping the tail's
                # sample stream ahead nondeterministically.  By the time the
                # per-step tail starts, all chunks were consumed, so the
                # producer has exhausted its iterator and exited — the tail's
                # direct train_iter use cannot race the daemon.
                chunks_total = max(0, (max_step - start_step)) // unroll
                if chunks_total > 0 and supports_buffered_next_many(train_iter):
                    chunk_pipeline = ChunkPipeline(
                        train_iter, unroll, chunks_total,
                        put=ts.engine.shard_batches,
                        assemble=ts.engine.assemble_batches,
                        depth=args.prefetch, slices=args.input_slices,
                        registry=registry,
                    )
                elif chunks_total > 0:
                    # iterators without a buffered next_many(k, out=...)
                    # (plugin experiments, possibly on the pre-pipeline
                    # signature) keep the legacy whole-chunk prefetch thread

                    def chunk_source():
                        for _ in range(chunks_total):
                            yield next_chunk()

                    chunk_pipeline = DevicePrefetcher(
                        chunk_source(), ts.engine.shard_batches, depth=args.prefetch
                    )

    reset_input(offstep)

    def fold_metric_sums(sums, folded):
        """Accumulate one batch's (total, count) metric sums."""
        if sums is None:
            return folded
        return jax.tree_util.tree_map(lambda a, b: a + b, sums, folded)

    def normalize_metric_sums(sums):
        return {name: float(total) / max(float(count), 1.0) for name, (total, count) in sums.items()}

    dense_metrics_fn = None
    if ts.eval_fn is None and nb_processes == 1 and hasattr(experiment, "sharded_to_dense_params"):
        # Jitted once; the dense replica's params live on device between
        # eval batches instead of re-uploading per batch.
        dense_metrics_fn = jax.jit(experiment.metrics)

    @trace.span("eval", cat="eval")
    def run_eval(step):
        if ts.eval_fn is None:
            # Sharded engine: the sharded loss is always reported; when the
            # experiment can collapse its stage-stacked params to the dense
            # layout (and this is a single process that can see every
            # shard), a dense replica also reports the real metric dict
            # (accuracy/nll — the reference's evaluation contract).
            values, sums = [], None
            dense_params = None
            if dense_metrics_fn is not None:
                dense_params = jax.device_put(
                    experiment.sharded_to_dense_params(jax.device_get(state.params))
                )
            for batch in experiment.make_eval_iterator(n):
                values.append(
                    float(jax.device_get(ts.eval_loss_fn(state, ts.engine.shard_batch(batch))))
                )
                if dense_params is not None:
                    flat = jax.tree_util.tree_map(
                        lambda x: x.reshape((-1,) + x.shape[2:]), batch
                    )  # fold the worker dim: the dense replica sees one big batch
                    sums = fold_metric_sums(
                        sums, jax.device_get(dense_metrics_fn(dense_params, flat))
                    )
            metrics = {"loss": sum(values) / max(len(values), 1)}
            if sums is not None:
                metrics.update(normalize_metric_sums(sums))
        else:
            sums = None
            for batch in experiment.make_eval_iterator(n):
                sums = fold_metric_sums(
                    sums, jax.device_get(ts.eval_fn(state, ts.engine.shard_batch(batch)))
                )
            metrics = normalize_metric_sums(sums)
        if chaos is not None:
            # the regime column: the regime that governed the LAST COMPLETED
            # training step (``step`` counts completed steps, so the final
            # step's in-graph index is step - 1 — an eval landing exactly on
            # a switch step reports the regime its metrics were trained
            # under, not the one about to start)
            metrics["chaos_regime"] = chaos.regime_at(max(step - 1, 0))
        info("Evaluation at step %d: %s" % (step, "  ".join("%s=%.4f" % kv for kv in sorted(metrics.items()))))
        eval_file.append(step, metrics)
        return metrics

    perf = PerfReport(registry=registry)
    # Live view shared by the exporter's /status and the flight fetches —
    # plain dict writes under the GIL; scrape threads only read.
    live_state = {"step": offstep, "flight": None, "slo": None}
    live = None
    if args.live_port is not None and lead:

        def live_status():
            return {
                "step": live_state["step"],
                "max_step": max_step,
                "steps_per_s": perf.steps_per_s_excl_first(),
                "overrides": overrides.describe(),
                "flight": live_state["flight"],
                "slo": live_state["slo"],
            }

        live = obs_live.LiveExporter(
            registry=registry, status_provider=live_status, run_id=run_id,
            host=args.live_host, port=args.live_port,
        )
        live_addr = live.serve_background()
        if args.live_ready_file:
            # atomic publish, like serve's --ready-file handshake
            ready_dir = os.path.dirname(args.live_ready_file)
            if ready_dir:
                os.makedirs(ready_dir, exist_ok=True)
            tmp = args.live_ready_file + ".tmp"
            with open(tmp, "w") as fd:
                fd.write("%s %d\n" % live_addr)
            os.replace(tmp, args.live_ready_file)
    # Training gauges on the process-wide registry (obs/metrics.py): the
    # same values the summary stream carries, updated at every summary fire
    # and dumped as Prometheus text by --metrics-file.
    g_loss = registry.gauge("train_loss", "Last summarized total training loss")
    g_grad_norm = registry.gauge("train_grad_norm", "Last summarized aggregate norm")
    g_lr = registry.gauge("train_learning_rate", "Learning rate at the last summary")
    g_steps_per_s = registry.gauge(
        "train_steps_per_second", "Throughput excluding the first (compile) step"
    )
    g_regime = registry.gauge("train_chaos_regime", "Active chaos regime index")
    g_quarantined = registry.gauge("train_quarantined_workers", "Workers under quarantine")
    g_worker_dist = registry.gauge(
        "train_worker_sq_dist", "Per-worker squared distance to the aggregate",
        labelnames=("worker",),
    )
    g_worker_rep = registry.gauge(
        "train_worker_reputation", "Per-worker reputation EMA (1 = trusted)",
        labelnames=("worker",),
    )
    # GAR cost instrumentation (--gar-probe, docs/gar_scaling.md): wall time
    # of ONE rule application at the run's exact (n, d), measured on a jitted
    # rule-only executable so the composite-vs-flat scaling claim is checked
    # against the live run, not just the offline benchmark.
    c_gar_seconds = registry.counter(
        "gar_seconds_total", "Cumulative measured GAR aggregation wall time"
    )
    g_gar_probe = registry.gauge(
        "gar_probe_seconds", "Last measured single-aggregation GAR wall time"
    )
    # Wire accounting (parallel/compress.py, docs/engine.md "The wire"):
    # bytes of the (n, d) submission stack per step under the configured
    # exchange — a static function of the run's geometry, counted per
    # dispatched step so the compression win is a number, not a claim.
    # Constant across guardian rebuilds (the ladder never changes d or the
    # exchange), so computed once here.
    c_wire_bytes = registry.counter(
        "bytes_on_wire_total",
        "Gradient-exchange submission bytes shipped over the wire",
    )
    g_wire_ratio = registry.gauge(
        "exchange_compression_ratio",
        "f32-wire bytes over configured-exchange bytes (>= 1)",
    )
    wire_step_bytes = n * compress.bytes_per_row(
        ts.model_dim, dtype=ts.engine.exchange_dtype, codec=ts.engine.codec
    )
    g_wire_ratio.set(compress.compression_ratio(
        ts.model_dim, dtype=ts.engine.exchange_dtype, codec=ts.engine.codec
    ))
    # flight-recorder fetch accounting (obs/flight.py): one amortized host
    # copy per summary fire instead of per-dispatch pulls
    c_flight_fetches = registry.counter(
        "flight_fetches_total", "Flight-recorder ring fetches"
    )
    g_flight_rows = registry.gauge(
        "flight_window_steps", "Rows in the last fetched flight window"
    )
    g_flight_last = registry.gauge(
        "flight_last_step", "Completed step of the newest fetched flight row"
    )
    metrics = {}
    diverged = False
    with Context("train"):
        step = offstep
        # NaN divergence is checked with a ONE-STEP LAG: blocking on the
        # current step's loss every iteration would serialize host and device
        # and defeat async dispatch; checking the previous step's (by now
        # materialized) loss keeps one step in flight with the same abort
        # guarantee one step later (the reference checks synchronously only
        # because sess.run already blocked, runner.py:570-574).  The guardian
        # watchdog rides the same lag: ``pending_metrics`` keeps the whole
        # previous dispatch so the probe can be observed per sub-step.
        pending_loss = None
        pending_metrics = None
        pending_start = 0

        def time_gar_probe(step):
            """One timed GAR-only aggregation (--gar-probe): the executable
            is built and warmed at the first fire (compile excluded from the
            timing — it is a separate jit cache, so the TRAINING step's
            compile count is untouched), then each fire measures one
            blocked-on aggregation and feeds the registry."""
            from aggregathor_tpu.gars.scaling import sync_fetch

            if ts.gar_probe_fn is None:
                ts.gar_probe_fn = ts.engine.build_gar_probe(ts.model_dim)
                sync_fetch(ts.gar_probe_fn(0))  # compile + full drain
            with trace.span("gar.aggregate", cat="train"):
                begin = time.perf_counter()
                sync_fetch(ts.gar_probe_fn(step))
                elapsed = time.perf_counter() - begin
            c_gar_seconds.inc(elapsed)
            g_gar_probe.set(elapsed)
            return elapsed

        def summary_scalars(step, metrics):
            """The summary event payload — shared by the cadence fires and
            the final fire, so worker diagnostics never silently drop out of
            the last event."""
            scalars = {
                "total_loss": float(jax.device_get(metrics["total_loss"])),
                "grad_norm": float(jax.device_get(metrics["grad_norm"])),
                "learning_rate": float(ts.schedule(step)),
                "steps_per_s": perf.steps_per_s_excl_first(),
            }
            if "worker_sq_dist" in metrics:
                wd = np.asarray(jax.device_get(metrics["worker_sq_dist"]))
                scalars["worker_sq_dist"] = wd
                # Masked rows (lossy NaN infill, quarantine) carry non-finite
                # distance sums; np.argmax would return the FIRST such index,
                # flagging a masked worker instead of the most distant live
                # one. Masked workers are already surfaced via
                # nb_quarantined/participation — suspicion ranks the live set.
                # With NO finite entry (every row masked) there is no live set
                # to rank — argmax over all -inf would arbitrarily flag worker
                # 0, so the field is omitted instead.
                if np.any(np.isfinite(wd)):
                    scalars["suspect_worker"] = int(
                        np.argmax(np.where(np.isfinite(wd), wd, -np.inf))
                    )
            if "worker_participation" in metrics:
                scalars["worker_participation"] = np.asarray(
                    jax.device_get(metrics["worker_participation"])
                )
            if "worker_reputation" in metrics:
                scalars["worker_reputation"] = np.asarray(
                    jax.device_get(metrics["worker_reputation"])
                )
            if "nb_quarantined" in metrics:
                scalars["nb_quarantined"] = int(jax.device_get(metrics["nb_quarantined"]))
            if "chaos_regime" in metrics:
                scalars["chaos_regime"] = int(jax.device_get(metrics["chaos_regime"]))
            if "nb_timeouts" in metrics:
                # bounded-wait deadline verdicts for this dispatch's step
                scalars["straggler_timeouts"] = int(jax.device_get(metrics["nb_timeouts"]))
            if "nb_stale" in metrics:
                scalars["stale_infill_rows"] = int(jax.device_get(metrics["nb_stale"]))
            if ts.bounded_step is not None and ts.bounded_step.controller is not None:
                scalars["deadline_window_seconds"] = (
                    ts.bounded_step.controller.window
                )
            if args.gar_probe:
                scalars["gar_seconds"] = time_gar_probe(step)
            if flight_rec is not None:
                # ONE amortized ring fetch per summary fire: the last
                # dispatch already materialized the state, so this is a
                # host copy, not a device sync (the recorder's whole
                # host-side cost).
                window = flight_rec.fetch(state.flight)
                c_flight_fetches.inc()
                nb_rows = int(window["step"].size)
                g_flight_rows.set(nb_rows)
                if nb_rows:
                    g_flight_last.set(int(window["step"][-1]) + 1)
                live_state["flight"] = obs_flight.summarize_window(window)
                scalars["flight_rows"] = nb_rows
            # mirror into the registry — one metrics surface (obs/metrics.py)
            g_loss.set(scalars["total_loss"])
            g_grad_norm.set(scalars["grad_norm"])
            g_lr.set(scalars["learning_rate"])
            g_steps_per_s.set(scalars["steps_per_s"])
            if "chaos_regime" in scalars:
                g_regime.set(scalars["chaos_regime"])
            if "nb_quarantined" in scalars:
                g_quarantined.set(scalars["nb_quarantined"])
            if "worker_sq_dist" in scalars:
                for w, value in enumerate(scalars["worker_sq_dist"]):
                    g_worker_dist.labels(worker=str(w)).set(
                        float(value) if np.isfinite(value) else float("inf")
                    )
            if "worker_reputation" in scalars:
                for w, value in enumerate(scalars["worker_reputation"]):
                    g_worker_rep.labels(worker=str(w)).set(float(value))
            return scalars

        def check_divergence():
            nonlocal diverged
            # ``pending_loss`` is the full per-step loss vector when unrolled,
            # so a mid-chunk divergence is caught at the next chunk boundary
            # rather than up to 2K-1 steps late via the last element only.
            if pending_loss is None:
                return
            with trace.span("block.loss_fetch", cat="train"):
                values = np.asarray(jax.device_get(pending_loss))
            if not np.all(np.isfinite(values)):
                if watchdog is not None:
                    return  # the guardian owns divergence: rollback, not abort
                diverged = True
                raise UserException("Training diverged (non-finite loss around step %d)" % step)

        def flight_postmortem(reason, at_step):
            """Fetch + dump the in-scan ring: exact per-step evidence for
            the window that killed the run (obs/flight.py), attached to the
            forensics report.  Called on guardian rollback and on
            crash/divergence, BEFORE the state is discarded."""
            if flight_rec is None:
                return None
            try:
                window = flight_rec.fetch(state.flight)
            except Exception as exc:
                warning("flight: post-mortem fetch failed: %s" % exc)
                return None
            summary = obs_flight.summarize_window(window)
            path = None
            if args.flight_dump and lead:
                path = args.flight_dump
                if reason == "guardian_rollback":
                    # every rollback keeps its own dump; the final
                    # crash/divergence dump owns the bare path
                    root, ext = os.path.splitext(path)
                    path = "%s.rollback-%d%s" % (root, int(at_step), ext or ".json")
                obs_flight.dump_window(
                    path, window, run_id=run_id, reason=reason,
                    capacity=flight_rec.capacity,
                    extra={"at_step": int(at_step)},
                )
                info("Flight post-mortem (%s) -> %r (%d row(s))"
                     % (reason, path, summary.get("rows", 0)))
            if ledger is not None:
                ledger.attach_flight(at_step, reason, path=path,
                                     window_summary=summary)
            # journal cross-ref: the event points at the dump that holds
            # the per-step evidence (one file -> the other)
            obs_events.emit("flight_postmortem", step=at_step, reason=reason,
                            path=path, rows=summary.get("rows", 0))
            return path

        # Secure submission feed (secure/submit.py): the host-side HMAC
        # sign/verify over the previous dispatch's digests — the same
        # one-dispatch lag as the forensics feed, so the crypto never blocks
        # the in-flight step.  Verdicts are keyed by step for the forensics
        # feed to attach as named ``forgery`` evidence.
        secure_fed = {"start": None}
        secure_verdicts = {}

        def feed_pending_secure():
            if secure_auth is None or pending_metrics is None:
                return
            if "secure" not in pending_metrics:
                return
            if secure_fed["start"] == pending_start:
                return
            secure_fed["start"] = pending_start
            sec = {
                name: np.asarray(jax.device_get(value))
                for name, value in pending_metrics["secure"].items()
            }
            sent, recv = sec["digest_sent"], sec["digest_recv"]
            forged, rejected = sec["forged"], sec["rejected"]
            if sent.ndim == 2:  # single step -> one-step chunk
                sent, recv = sent[None], recv[None]
                forged, rejected = forged[None], rejected[None]
            for i in range(sent.shape[0]):
                at_step = pending_start + i + 1
                ok = secure_auth.process_step(
                    at_step, sent[i], recv[i], forged=forged[i]
                )
                if not np.array_equal(~ok, rejected[i].astype(bool)):
                    # cannot happen by construction (the in-graph
                    # rejection models exactly the tag-verification
                    # outcome) — if it does, the simulation drifted
                    warning(
                        "secure: host verification disagrees with the "
                        "in-graph rejection at step %d" % at_step
                    )
                if ledger is not None:
                    secure_verdicts[at_step] = ~ok

        # Forensics feed: one ledger observation per completed step, taken
        # from the PREVIOUS dispatch (the same one-step lag as the NaN-abort
        # check — by feed time the values are materialized, so the fetch
        # costs a host copy, not a device sync).  ``fed_start`` dedups: the
        # same pending dispatch is visible from several call sites.
        forensics_fed = {"start": None}

        def feed_pending_forensics():
            if ledger is None or pending_metrics is None:
                return
            if forensics_fed["start"] == pending_start:
                return
            forensics_fed["start"] = pending_start
            with trace.span("forensics.feed", cat="obs"):
                def fetch(value):
                    return None if value is None else np.asarray(jax.device_get(value))

                dist = fetch(pending_metrics.get("worker_sq_dist"))
                rep = fetch(pending_metrics.get("worker_reputation"))
                regime = fetch(pending_metrics.get("chaos_regime"))
                timeouts = fetch(pending_metrics.get("straggler_timeout"))
                stale_rows = fetch(pending_metrics.get("stale_infill"))
                probe = pending_metrics.get(health.PROBE_KEY)
                nan_rows = (
                    fetch(probe.get("worker_nan_rows")) if probe is not None else None
                )

                def rows(vector):
                    # (n,) -> one step; (K, n) -> one row per scanned step
                    if vector is None:
                        return None
                    return vector[None] if vector.ndim == 1 else vector
                dist, rep, nan_rows = rows(dist), rows(rep), rows(nan_rows)
                timeouts, stale_rows = rows(timeouts), rows(stale_rows)
                regime = None if regime is None else np.atleast_1d(regime)
                nb = max(
                    v.shape[0] for v in (dist, rep, nan_rows, regime, timeouts)
                    if v is not None
                ) if any(
                    v is not None for v in (dist, rep, nan_rows, regime, timeouts)
                ) else 0
                for i in range(nb):
                    ridx = None if regime is None else int(regime[min(i, regime.shape[0] - 1)])
                    ledger.observe(
                        pending_start + i + 1,
                        worker_sq_dist=None if dist is None else dist[i],
                        worker_nan=None if nan_rows is None else nan_rows[i],
                        reputation=None if rep is None else rep[i],
                        regime=ridx,
                        regime_desc=(
                            chaos.describe(ridx)
                            if (ridx is not None and chaos is not None) else None
                        ),
                        # named forgery evidence from the submission
                        # authenticator (reject-and-name, secure/submit.py)
                        forgery=secure_verdicts.pop(pending_start + i + 1, None),
                        # bounded-wait deadline verdicts (straggler_timeout
                        # evidence; explains the timed-out rows' NaN flags)
                        timeout=None if timeouts is None else timeouts[i],
                        # stale infills: named stale_infill evidence, so
                        # late-but-honest stays distinguishable (they still
                        # spent the f budget — docs/engine.md)
                        stale=None if stale_rows is None else stale_rows[i],
                    )

        def probe_clean(dispatch_metrics):
            """Is the state this dispatch produced healthy by the probe?
            Gates the last-known-good pin at checkpoint time."""
            view = health.host_view(dispatch_metrics)
            if view is None:
                return True
            return bool(
                np.all(view["loss_finite"])
                and np.all(np.isfinite(view["update_norm"]))
                and np.all(np.asarray(view["spike"]) <= guardian.spike_factor)
            )

        def do_rollback(at_step):
            """Rollback-and-escalate: restore last-known-good, perturb the
            RNG, climb one ladder rung, discard the abandoned timeline."""
            nonlocal state, step, ts, overrides, chaos_regime_seen
            nonlocal pending_loss, pending_metrics, diverged
            reason = watchdog.last_reason or "divergence"
            if watchdog.exhausted:
                diverged = True
                raise UserException(
                    "guardian: run failed — %s after %d recovery attempt(s) "
                    "(ladder %s)" % (reason, watchdog.attempts,
                                     guardian.ladder.describe())
                )
            checkpoints.wait()  # writer queue flushed before reading targets
            target = checkpoints.pinned_step()
            rstep = target if target is not None else 0
            attempt = watchdog.note_rollback(rstep)
            warning(
                "guardian: %s — rolling back from step %d to %s (attempt %d/%d)"
                % (reason, at_step,
                   "step %d" % rstep if target is not None else "a fresh state",
                   attempt + 1, guardian.retries)
            )
            summaries.event(at_step, "guardian_rollback", {
                "reason": reason, "from_step": int(at_step), "to_step": int(rstep),
                "attempt": attempt, "restored_snapshot": target is not None,
            })
            # the ring still holds the diverged timeline's per-step rows —
            # dump them before the restore wipes the state
            flight_postmortem("guardian_rollback", at_step)
            if ledger is not None:
                # the replay window re-observes the truncated steps; the
                # rollback event (stamped at the restore step so it survives
                # the truncation) keeps the audit trail of WHY
                ledger.truncate_after(rstep)
                forensics_fed["start"] = None
                ledger.note_guardian(rstep, "rollback", {
                    "reason": reason, "from_step": int(at_step),
                    "attempt": attempt,
                })
            # abandoned verdicts: the replay window re-verifies its steps
            # (the tag chain keeps the abandoned timeline — it is an
            # append-only audit of everything the aggregator verified)
            secure_verdicts.clear()
            secure_fed["start"] = None
            rung = guardian.ladder.rung(attempt)
            if rung is not None:
                try:
                    new_overrides = rung.apply(overrides)
                    with Context("escalate"):
                        new_ts = instrument_stack(build_training(new_overrides))
                    if ts.bounded_step is not None:
                        ts.bounded_step.close()  # retire the old pool
                    overrides, ts = new_overrides, new_ts
                    if custody is not None:
                        # manifests saved from here on sign the new spec
                        custody.gar_spec = overrides.describe()
                    info("guardian: escalated — %s (now %s)"
                         % (rung.describe(), overrides.describe()))
                    summaries.event(rstep, "guardian_escalation", {
                        "rung": rung.describe(), "attempt": attempt,
                        "overrides": overrides.describe(),
                    })
                    note_escalation(rstep, rung, overrides)
                    if ledger is not None:
                        ledger.note_guardian(rstep, "escalation", {
                            "rung": rung.describe(),
                            "overrides": overrides.describe(),
                        })
                except UserException as exc:
                    warning(
                        "guardian: escalation rung %r rejected (%s); retrying "
                        "with the current configuration" % (rung.describe(), exc)
                    )
            # RNG perturbation breaks deterministic re-divergence: the same
            # snapshot + the same streams would replay the exact trajectory
            # that just failed.  Restored runs fold the attempt into the
            # restored key; from-scratch retries move the seed.
            fresh = ts.make_fresh_state(
                args.seed if target is not None
                else args.seed + RESEED_STRIDE * (attempt + 1)
            )
            if target is not None:
                carry, momentum = fresh.carry, fresh.momentum
                template = jax.device_get(fresh.replace(carry=None, momentum=None))
                restored, rstep = checkpoints.restore(template, step=target)
                restored = restored.replace(rng=jax.device_get(
                    jax.random.fold_in(jnp.asarray(restored.rng), RNG_PERTURB_TAG + attempt)
                ))
                state = ts.engine.put_state(
                    restored.replace(carry=carry, momentum=momentum)
                )
            else:
                state = fresh
            step = rstep
            pending_loss = pending_metrics = None
            # the abandoned timeline: snapshots and eval rows beyond the
            # restore point would otherwise poison a later auto-restore /
            # interleave with the retry's rows
            checkpoints.discard_after(rstep)
            eval_file.truncate_after(rstep)
            for trigger in (eval_trigger, ckpt_trigger, summary_trigger):
                if trigger.last_step is not None and trigger.last_step > rstep:
                    trigger.last_step = rstep
            reset_input(rstep, reseed=attempt + 1)
            if chaos is not None:
                chaos_regime_seen = chaos.regime_at(step)

        def observe_pending():
            """Feed the forensics ledger and the watchdog the previous
            dispatch's diagnostics, one observation per completed step.
            Returns True when a rollback happened — the caller discards its
            in-flight results."""
            nonlocal pending_loss, pending_metrics
            feed_pending_secure()
            feed_pending_forensics()
            if watchdog is None or pending_metrics is None:
                return False
            with trace.span("block.probe_fetch", cat="guardian"):
                view = health.host_view(pending_metrics)
                losses = np.atleast_1d(np.asarray(jax.device_get(pending_loss)))
                timeouts = pending_metrics.get("nb_timeouts")
                if timeouts is not None:
                    timeouts = np.atleast_1d(np.asarray(jax.device_get(timeouts)))
            start = pending_start
            pending_loss = pending_metrics = None
            if view is None:  # engine built without the probe
                return False
            finite = np.atleast_1d(view["loss_finite"]).astype(bool)
            spikes = np.atleast_1d(view["spike"]).astype(np.float64)
            for i in range(losses.shape[0]):
                action = watchdog.observe(
                    start + i + 1, float(losses[i]), bool(finite[i]), float(spikes[i])
                )
                if action is None and timeouts is not None:
                    # bounded-wait escalation input: timeouts beyond the
                    # declared budget, sustained, roll back and climb the
                    # ladder (f+K re-sizes the budget for the observed tail)
                    action = watchdog.observe_timeouts(
                        start + i + 1, int(timeouts[i]), overrides.f
                    )
                if (action is None and ts.bounded_step is not None
                        and ts.bounded_step.controller is not None):
                    # adaptive-deadline escalation input: a controller
                    # pinned at its ceiling means the arrival tail outgrew
                    # the budgeted window (parallel/deadline.py)
                    action = watchdog.observe_ceiling(
                        start + i + 1, ts.bounded_step.controller.at_ceiling
                    )
                if action == "recovered":
                    info("guardian: recovered — %d healthy step(s) since the "
                         "last rollback" % guardian.recover_after)
                    summaries.event(start + i + 1, "guardian_recovered", {
                        "attempt": watchdog.attempts - 1,
                        "overrides": overrides.describe(),
                    })
                    if ledger is not None:
                        ledger.note_guardian(start + i + 1, "recovered", {
                            "attempt": watchdog.attempts - 1,
                        })
                elif action == "rollback":
                    do_rollback(start + i + 1)
                    return True
            return False

        # Host-gap span: the wall time between one dispatch returning and
        # the next one starting (input, cadences, watchdog) — the "off-
        # graph" slice of the perf report, now visible per step in the
        # trace.  Manual start/stop because its lifetime spans loop turns.
        gap = {"span": None}

        def gap_open():
            if trace.installed() is not None:
                gap["span"] = trace.span("host_gap", cat="train").start()

        def gap_close():
            if gap["span"] is not None:
                gap["span"].stop()
                gap["span"] = None

        startup_said = False  # the start-up line, once the first dispatch is out
        # Chaos regime transition logging: host-side tracking of the regime
        # governing the NEXT step to dispatch (under --unroll, transitions
        # inside a chunk surface at the chunk boundary).
        chaos_regime_seen = None
        if chaos is not None:
            chaos_regime_seen = chaos.regime_at(step)
            info("Chaos regime at step %d: %s" % (step, chaos.describe(chaos_regime_seen)))
        try:
            while True:
                if step >= max_step or stop["requested"]:
                    # Exit drains the lagged observation first: a guardian
                    # rollback here re-enters training from the restored
                    # step instead of returning with a poisoned tail.
                    if observe_pending() and step < max_step and not stop["requested"]:
                        continue
                    check_divergence()
                    break
                if xprof is not None:
                    # programmatic device capture over an explicit step
                    # window; under --unroll the boundary lands on the
                    # chunk boundary (a compiled scan is never split)
                    xprof.maybe_start(step)
                chunk = 1
                if ts.multi_fn is not None and max_step - step >= unroll:
                    # Unrolled dispatch: K distinct batches, one executable
                    # (device-sampled: the resident dataset IS the input and
                    # the trainer draws its own fresh per-step batches)
                    with trace.span("input", cat="train"):
                        if ts.device_dataset is not None:
                            device_chunk = ts.device_dataset
                        elif chunk_pipeline is not None:
                            device_chunk = next(chunk_pipeline)
                        else:
                            device_chunk = ts.engine.shard_batches(next_chunk())
                    gap_close()
                    perf.step_begin()
                    with xprof.annotate(step) if xprof is not None else contextlib.nullcontext():
                        state, many = ts.multi_fn(state, device_chunk)
                    if observe_pending():
                        continue  # previous chunk diverged: this one is abandoned
                    check_divergence()
                    metrics = jax.tree_util.tree_map(lambda x: x[-1], many)
                    perf.step_end(unroll)
                    gap_open()
                    chunk = unroll
                    pending_loss = many["total_loss"]  # full vector: see check_divergence
                    pending_metrics = many
                    pending_start = step
                elif ts.sampled_tail is not None:
                    # Device-sampled tail: the final (max_step - step) <
                    # unroll steps run through a tail-sized SAMPLED executable.  Every
                    # step of a device-input run is device-sampled; no
                    # host-batch fallback remains.  The tail length is a
                    # pure function of (max_step, offstep, unroll), so the
                    # executable compiles once per run (asserted by
                    # tests/test_input_pipeline.py's compile-count test).
                    nb_steps = max_step - step
                    tail_fn = ts.sampled_tail(nb_steps)
                    gap_close()
                    perf.step_begin()
                    with xprof.annotate(step) if xprof is not None else contextlib.nullcontext():
                        state, many = tail_fn(state, ts.device_dataset)
                    if observe_pending():
                        continue  # previous chunk diverged: this one is abandoned
                    check_divergence()
                    metrics = jax.tree_util.tree_map(lambda x: x[-1], many)
                    perf.step_end(nb_steps)
                    gap_open()
                    chunk = nb_steps
                    pending_loss = many["total_loss"]
                    pending_metrics = many
                    pending_start = step
                else:
                    if chunk_pipeline is not None:
                        # Entering the per-step tail: retire the chunk
                        # producer FIRST — its daemon shares train_iter and
                        # numpy Generators are not thread-safe.
                        chunk_pipeline.close()
                        chunk_pipeline = None
                    with trace.span("input", cat="train"):
                        batch = next(prefetcher) if prefetcher is not None else ts.engine.shard_batch(next(train_iter))
                    gap_close()
                    perf.step_begin()
                    with xprof.annotate(step) if xprof is not None else contextlib.nullcontext():
                        state, metrics = ts.step_fn(state, batch)
                    if observe_pending():
                        continue  # previous step diverged: this one is abandoned
                    check_divergence()
                    perf.step_end()
                    gap_open()
                    pending_loss = metrics["total_loss"]
                    pending_metrics = metrics
                    pending_start = step
                step += chunk
                if startup_said is False:
                    # the process's way to its first dispatch, by parts
                    # (docs/observability.md "Reading a start-up")
                    startup_said = True
                    info(obs_profiler.startup_summary())
                c_wire_bytes.inc(chunk * wire_step_bytes)
                live_state["step"] = step
                if xprof is not None:
                    xprof.maybe_stop(step)
                if chaos is not None:
                    regime_now = chaos.regime_at(step)
                    if regime_now != chaos_regime_seen:
                        chaos_regime_seen = regime_now
                        info("Chaos regime switch at step %d: now %s"
                             % (step, chaos.describe(regime_now)))
                        summaries.event(step, "chaos_regime_switch", {
                            "regime": regime_now,
                            "spec": chaos.describe(regime_now),
                        })
                if eval_trigger.should_fire(step):
                    check_divergence()
                    run_eval(step)
                    eval_trigger.fired(step)
                if save_snapshots and ckpt_trigger.should_fire(step):
                    check_divergence()
                    checkpoints.wait()  # surface a previous write's failure
                    checkpoints.save(state, step)
                    if watchdog is not None and watchdog.healthy and probe_clean(
                        pending_metrics if pending_metrics is not None else metrics
                    ):
                        # last-known-good: this snapshot survives pruning and
                        # is the rollback target (obs/checkpoint.py pin).
                        # pending_metrics is the WHOLE last dispatch — under
                        # --unroll every sub-step must read clean, not just
                        # the chunk's final slice
                        checkpoints.pin(step)
                    ckpt_trigger.fired(step)
                if summary_trigger.should_fire(step):
                    check_divergence()
                    with trace.span("summaries", cat="obs"):
                        summaries.scalars(step, summary_scalars(step, metrics))
                    dump_metrics_file()
                    summary_trigger.fired(step)
        finally:
            for signum, handler in previous_handlers.items():
                signal.signal(signum, handler)
            if xprof is not None:
                xprof.close()
            aborting = sys.exc_info()[0] is not None
            # Final fire of every daemon (reference: runner.py:356-494 at
            # stop) — skipped on divergence (evaluating or checkpointing the
            # NaN state would poison the next run's auto-restore) and when
            # the trigger already fired at this exact step.
            if step > offstep and not diverged:
                if eval_trigger.enabled and eval_trigger.last_step != step:
                    run_eval(step)
                if save_snapshots and ckpt_trigger.last_step != step:
                    checkpoints.save(state, step)
                if metrics and summary_trigger.last_step != step:
                    summaries.scalars(step, summary_scalars(step, metrics))
            if (step > offstep and not diverged and not aborting
                    and not stop["requested"]):
                # Regression sentinel at run end (obs/slo.py): judge the
                # run's measured throughput metrics against the stored
                # baseline, and/or capture a fresh baseline.  Before
                # summaries.close() — the verdict is a summary event too.
                # Signal-interrupted runs are NOT judged: a truncated run's
                # throughput is meaningless against a full-run baseline, and
                # a supervisor's graceful retune restart must not synthesize
                # a REGRESS verdict (docs/operations.md).
                if sentinel is not None or args.slo_capture:
                    slo_current = obs_slo.collect_current(registry, perf)
                if sentinel is not None:
                    verdict = sentinel.verdict(slo_current, run_id=run_id)
                    live_state["slo"] = verdict
                    info(obs_slo.describe_verdict(verdict))
                    summaries.event(step, "slo_verdict", {
                        "verdict": verdict["verdict"],
                        "regressed": verdict["regressed"],
                        "checks": verdict["checks"],
                    })
                    if args.slo_verdict and lead:
                        obs_slo.save_verdict(args.slo_verdict, verdict)
                        info("SLO verdict -> %r" % args.slo_verdict)
                if args.slo_capture and lead:
                    doc = obs_slo.capture(args.slo_capture, slo_current,
                                          run_id=run_id)
                    info("SLO baseline -> %r (metrics: %s)" % (
                        args.slo_capture, ", ".join(sorted(doc["metrics"]))))
            if prefetcher is not None:
                prefetcher.close()
            if chunk_pipeline is not None:
                chunk_pipeline.close()
            if ts.bounded_step is not None:
                ts.bounded_step.close()
            eval_file.close()
            summaries.close()
            gap_close()
            # Telemetry flush — last observations (a diverged tail IS
            # evidence), attribution report, metrics dump, trace.  Every
            # step is INDEPENDENT: a failing ledger save must not skip the
            # metrics dump (a preempted run must never exit with an empty
            # --metrics-file), and during an abort no flush failure may
            # mask the propagating training error.
            flush_errors = []

            def flush(label, fn):
                try:
                    fn()
                except Exception as exc:
                    # always LOGGED here (a later cleanup failure must not
                    # erase the record); re-raised at the very end unless
                    # an exception is already propagating
                    warning("Telemetry flush (%s) failed: %s" % (label, exc))
                    if not aborting:
                        flush_errors.append((label, exc))

            if aborting or diverged:
                # the ring holds the exact per-step window that killed the
                # run — dump it before anything else can fail
                flush("flight-postmortem", lambda: flight_postmortem(
                    "divergence" if diverged else "crash", step))
            # Drain the lagged feeds BEFORE the report is written: the
            # final dispatch's evidence — and its secure verdict lane —
            # must reach the ledger (they sit one dispatch behind by
            # design, so shutdown is the only place they can land).
            flush("secure-drain", feed_pending_secure)
            flush("forensics-drain", feed_pending_forensics)
            if args.journal and obs_events.installed() is not None:
                # run_end closes the causal timeline BEFORE the forensics
                # report is written, so the report's journal section counts
                # every event of the run (incl. this one)
                def journal_run_end():
                    journal = obs_events.installed()
                    obs_events.emit(
                        "run_end", step=step, diverged=diverged,
                        aborting=aborting,
                        forensics=args.forensics if ledger is not None else None,
                    )
                    if ledger is not None:
                        ledger.note_journal(
                            journal.path, journal.counts_by_type()
                        )

                flush("journal-end", journal_run_end)
            if ledger is not None:
                def save_forensics():
                    md_path = (
                        args.forensics[:-5] + ".md"
                        if args.forensics.endswith(".json") else args.forensics + ".md"
                    )
                    report = ledger.save(args.forensics, markdown_path=md_path)
                    suspects = report["suspects"]
                    info("Forensics report -> %r (%s)" % (
                        args.forensics,
                        "Byzantine worker(s): %s" % ", ".join(map(str, suspects))
                        if suspects else "no worker attributed Byzantine",
                    ))

                flush("forensics-report", save_forensics)
            flush("metrics-file", dump_metrics_file)
            if args.trace_file:
                def save_span_trace():
                    written = trace.uninstall(save=True)
                    if written:
                        info("Span trace -> %r (run_id %s)" % (written, run_id))

                flush("trace", save_span_trace)
            if args.journal and obs_events.installed() is not None:
                def close_journal():
                    written = obs_events.uninstall()
                    if written:
                        info("Run journal -> %r (run_id %s)" % (written, run_id))

                flush("journal-close", close_journal)
            if live is not None:
                flush("live-exporter", live.shutdown_all)
            perf.report()
            if checkpoints is not None:
                # LAST cleanup step, so a flush failure can no longer skip
                # the closes/report above: a returned run is fully flushed
                # to disk.  If an exception is already propagating, the
                # flush failure must not mask it — log it instead.
                if aborting:
                    try:
                        checkpoints.wait(shutdown=True)
                    except Exception as exc:
                        warning("Checkpoint write failed during abort: %s" % exc)
                else:
                    checkpoints.wait(shutdown=True)
            if flush_errors:
                # surfaced LAST so a telemetry write failure can no longer
                # skip the report or the checkpoint flush (it still fails
                # the run: silent telemetry loss is how evidence vanishes)
                label, exc = flush_errors[0]
                if len(flush_errors) > 1:
                    warning("%d more telemetry flush step(s) failed after %r"
                            % (len(flush_errors) - 1, label))
                raise exc
    return 0


def cli():
    from . import console_entry

    return console_entry(main)


if __name__ == "__main__":
    sys.exit(cli())
