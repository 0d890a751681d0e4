"""Long-haul soak: a real train+serve+router fleet under drifting chaos,
kept alive by the fleet supervisor with ZERO human action — judged on
recovery, quarantine, retune, custody rollback and client-visible
consistency.

The PR-17 acceptance harness (docs/operations.md "The self-driving
run").  One driver process plays the whole story end to end:

1. **fleet**: real subprocesses — a ``cli.runner`` training run (median
   GAR, authenticated submissions + custody-signed snapshots, a chaos
   schedule drifting through an attack wave and a heavy-tail straggler
   wave, an adaptive bounded-wait deadline, a FORCED-impossible SLO
   baseline so the sentinel must judge REGRESS at run end), two
   ``cli.serve`` backends following the shared checkpoint directory on
   PINNED ports, one ``cli.router`` in front, and a deliberate
   crash-looper — all spawned and owned by an in-process
   :class:`~aggregathor_tpu.supervisor.FleetSupervisor` (the benchmark
   SUBJECT, exactly what ``cli.supervise`` runs);
2. **chaos**: the driver walks a PROCESS-plane chaos schedule (the
   ``kill=``/``hang=`` DSL keys, parsed with ``allow_process_faults=True``
   — ticks are its steps): SIGKILL a backend mid-traffic, SIGSTOP another
   to wedge it; the crash-looper flaps on its own;
3. **load**: sticky closed-loop clients fire ``/predict`` at the router
   for the whole soak, recording every ``weights_step`` they observe;
4. **judge**: hard verdicts only —
   **kills_recovered** (every killed/hung instance restarted and scraped
   back up, the crash-looper excepted),
   **recovery_in_envelope** (each restart fired inside its backoff
   envelope: the action's own ``backoff_s`` + detection + tick slack),
   **crash_looper_quarantined** (flap damping escalated, attempts ==
   max-restarts, and the looper STAYED down),
   **regress_rolled_back** (the forced REGRESS produced a
   ``supervisor_rollback`` through the custody-verified path: the
   regressed checkpoint tail is gone, the restore target verified),
   **zero_step_regressions** (no client's step sequence ever decreased —
   across the kill, the hang, the retune restart and the rollback),
   **journal_causal** (the supervisor journal loads EV001-clean, every
   action event carries its triggering evidence, every kill strictly
   precedes its restart event, the rollback cites the verdict it acted
   on),
   **postmortem_closes** (every journal the fleet wrote — supervisor,
   trainer, both serve replicas, router — replays through the SHARED
   causal checker (``obs/causal.py``, exactly what ``cli.postmortem``
   runs): zero dangling cause references, zero orphan actions, every
   supervised respawn answered by a ``run_start`` citing the
   ``supervisor_restart``/``supervisor_retune`` that spawned it — the
   ``--cause`` argv injection crossing the process boundary for real).
   A ``supervisor_retune`` (the straggler wave pinning the deadline
   controller at its ceiling) is reported, and hard-required unless
   ``--no-require-retune``.

Emits one ``aggregathor.soak.v2`` document (``validate``/``load`` below
are the round-trip the smoke and tests assert); exit status is the
overall verdict.  The checked-in ``SOAK_r17.json`` at the repo root is a
passing v1 run of this benchmark (PR 17, pre-causal-plane) on the 1-core
CI box; v2 adds the ``postmortem`` section and verdict leg.

Example (CPU)::

    python benchmarks/soak.py --ticks 160 --out soak.json
"""

import argparse
import json
import os
import signal
import sys
import threading
import time

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO_ROOT)

SCHEMA = "aggregathor.soak.v2"


def validate(doc):
    """Schema check for round-tripping consumers (the smoke script and
    tests assert this shape on the checked-in SOAK_r17.json)."""
    if doc.get("schema") != SCHEMA:
        raise ValueError("not a %s document" % SCHEMA)
    for key in ("config", "fleet", "recovery", "rollback", "traffic",
                "journal", "verdict"):
        if key not in doc:
            raise ValueError("missing %r" % key)
    fleet = doc["fleet"]
    for key in ("instances", "process_faults", "quarantined", "restarts"):
        if key not in fleet:
            raise ValueError("fleet missing %r" % key)
    for entry in doc["recovery"]:
        for key in ("target", "kind", "tick", "restart_after_s",
                    "envelope_s", "within_envelope", "recovered"):
            if key not in entry:
                raise ValueError("recovery entry missing %r" % key)
    rollback = doc["rollback"]
    for key in ("events", "restore_step", "custody_verified"):
        if key not in rollback:
            raise ValueError("rollback missing %r" % key)
    traffic = doc["traffic"]
    for key in ("requests", "ok", "sheds", "dropped", "clients",
                "monotonic_clients", "observed_steps"):
        if key not in traffic:
            raise ValueError("traffic missing %r" % key)
    journal = doc["journal"]
    for key in ("events", "evidence_complete", "kill_before_restart",
                "rollback_cites_verdict"):
        if key not in journal:
            raise ValueError("journal missing %r" % key)
    postmortem = doc.get("postmortem")
    if not isinstance(postmortem, dict):
        raise ValueError("missing 'postmortem'")
    for key in ("verdict", "failing", "instances", "edges", "chains",
                "skew_pairs"):
        if key not in postmortem:
            raise ValueError("postmortem missing %r" % key)
    verdict = doc["verdict"]
    for key in ("kills_recovered", "recovery_in_envelope",
                "crash_looper_quarantined", "regress_rolled_back",
                "zero_step_regressions", "journal_causal",
                "postmortem_closes", "pass"):
        if not isinstance(verdict.get(key), bool):
            raise ValueError("verdict missing bool %r" % key)
    return doc


def load(path):
    with open(path) as fd:
        return validate(json.load(fd))


def build_parser():
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--experiment", default="digits")
    parser.add_argument("--experiment-args", nargs="*",
                        default=["batch-size:16"])
    parser.add_argument("--train-steps", type=int, default=5000,
                        help="trainer max-step (checkpoints every "
                             "--checkpoint-delta; the sentinel judges at "
                             "run end).  Sized so the run outlives every "
                             "process fault: the forced rollback must be "
                             "the LAST act — a serve restart after the "
                             "tail discard would legitimately re-expose "
                             "the older step to its pinned clients")
    parser.add_argument("--checkpoint-delta", type=int, default=100)
    parser.add_argument("--ticks", type=int, default=160,
                        help="supervisor sense->decide->act rounds")
    parser.add_argument("--tick-interval", type=float, default=0.5)
    parser.add_argument("--process-chaos",
                        default="0:calm 24:kill=serve-b 25:calm "
                                "70:hang=serve-a 71:calm",
                        help="PROCESS-plane chaos schedule (kill=/hang= "
                             "DSL, ticks as steps)")
    parser.add_argument("--train-chaos",
                        default="0:calm 400:straggle=1.0,"
                                "straggle-mode=stale,jitter=2.0 4000:calm",
                        help="device-plane chaos handed to the trainer. "
                             "Straggler regimes ONLY: bounded-wait rejects "
                             "attack=/drop= schedules (Byzantine pressure "
                             "comes from the static --byz-count worker), "
                             "and the straggler pool is capped at 1 worker "
                             "so timeouts + stale + byz stay within the "
                             "declared f=2 — the engine's f-accounting")
    parser.add_argument("--clients", type=int, default=4,
                        help="closed-loop HTTP clients (sticky X-Client-Id)")
    parser.add_argument("--request-rows", type=int, default=2)
    parser.add_argument("--supervisor-args", nargs="*",
                        default=["patience:1", "backoff:2", "max-restarts:3",
                                 "flap-window:10", "retune-streak:3",
                                 "retune-cooldown:30"])
    parser.add_argument("--down-after", type=int, default=2)
    parser.add_argument("--max-seconds", type=float, default=420.0,
                        help="hard wall bound on the whole soak")
    parser.add_argument("--settle-ticks", type=int, default=40,
                        help="extra ticks granted after --ticks while the "
                             "rollback has not landed yet")
    parser.add_argument("--no-require-retune", action="store_true",
                        help="report the retune leg without judging it "
                             "(constrained boxes where the straggler wave "
                             "cannot pin the deadline controller)")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--out", default=None, help="write the JSON here")
    parser.add_argument("--workdir", default=None,
                        help="scratch + checkpoint directory "
                             "(default: a fresh tempdir)")
    parser.add_argument("--platform", default="cpu")
    return parser


def _free_port():
    import socket

    sock = socket.socket()
    sock.bind(("127.0.0.1", 0))
    port = sock.getsockname()[1]
    sock.close()
    return port


def main(argv=None):
    args = build_parser().parse_args(argv)
    if args.platform == "tpu":
        # One process per chip: this harness is a fleet of concurrent JAX
        # processes (and the parent itself imports JAX), so it cannot share
        # one — it measures the host planes, on the CPU.
        raise SystemExit("%s spawns several JAX processes; a TPU belongs to "
                         "one process at a time — run it with --platform cpu"
                         % os.path.basename(__file__))
    if args.platform:
        os.environ["JAX_PLATFORMS"] = args.platform

    import tempfile
    import urllib.error
    import urllib.request

    from aggregathor_tpu.chaos import ChaosSchedule
    from aggregathor_tpu.obs import events as obs_events
    from aggregathor_tpu.obs import slo
    from aggregathor_tpu.obs.checkpoint import Checkpoints
    from aggregathor_tpu.supervisor import (
        FleetSupervisor,
        InstanceSpec,
        Quarantine,
        Restart,
        Retune,
        Rollback,
        SupervisorConfig,
    )

    workdir = args.workdir or tempfile.mkdtemp(prefix="soak_")
    os.makedirs(workdir, exist_ok=True)
    ckpt_dir = os.path.join(workdir, "ckpt")
    secret = "soak-session-secret"

    # the PROCESS-plane chaos schedule: the gated DSL keys, ticks as steps
    process_chaos = ChaosSchedule(args.process_chaos, nb_workers=4,
                                  allow_process_faults=True)
    faults_by_tick = {}
    for start, kills, hangs in process_chaos.process_faults():
        entry = faults_by_tick.setdefault(start, [])
        entry.extend(("kill", name) for name in kills)
        entry.extend(("hang", name) for name in hangs)

    # the FORCED-impossible baseline: no CPU box trains 1e9 steps/s, so
    # the sentinel MUST judge REGRESS at trainer run end — the rollback
    # trigger, with zero human action
    baseline_path = os.path.join(workdir, "impossible_baseline.json")
    slo.capture(baseline_path,
                {"steps_per_s": 1.0e9, "gar_seconds_total": 1.0e-9,
                 "input_overlap_fraction": 1.0},
                run_id="soak-impossible")
    verdict_path = os.path.join(workdir, "train_verdict.json")

    port_a, port_b, port_router = _free_port(), _free_port(), _free_port()
    names = ("train", "serve-a", "serve-b", "router", "looper")

    def serve_spec(name, port):
        return InstanceSpec(
            name, "serve",
            ["{python}", "-m", "aggregathor_tpu.cli.serve",
             "--experiment", args.experiment,
             "--experiment-args", *args.experiment_args,
             "--ckpt-dir", ckpt_dir, "--replicas", "1", "--gar", "none",
             "--max-batch", "8", "--lanes", "2", "--queue-bound", "256",
             "--follow", "--follow-interval", "0.2",
             "--session-secret", secret,
             "--port", str(port),   # PINNED: a supervised restart must
             "--ready-file", os.path.join(workdir, "ready_%s" % name),
             "--journal", os.path.join(workdir, "journal_%s.jsonl" % name),
             "--run-id", "soak-%s" % name,
             "--platform", args.platform or "cpu"],
            cwd=_REPO_ROOT,
            url="127.0.0.1:%d" % port,
            ready_file=os.path.join(workdir, "ready_%s" % name),
            journal=os.path.join(workdir, "journal_%s.jsonl" % name),
            log=os.path.join(workdir, "log_%s.txt" % name),
            cause_flag=True,        # respawns cite the restart that spawned
        )                           # ...come back on the SAME host:port

    def train_argv(max_step, checkpoint_delta, seed_phase=False):
        argv = [
            "{python}", "-m", "aggregathor_tpu.cli.runner",
            "--experiment", args.experiment,
            "--experiment-args", *args.experiment_args,
            "--aggregator", "median", "--nb-workers", "6",
            "--nb-decl-byz-workers", "2", "--nb-real-byz-workers", "1",
            "--nb-devices", "1", "--max-step", str(max_step),
            "--learning-rate-args", "initial-rate:0.05", "--prefetch", "0",
            "--evaluation-delta", "-1", "--evaluation-period", "-1",
            "--summary-delta", str(checkpoint_delta),
            "--summary-period", "-1",
            "--checkpoint-dir", ckpt_dir,
            "--checkpoint-delta", str(checkpoint_delta),
            "--checkpoint-period", "-1", "--checkpoint-keep", "50",
            "--secure", "--session-secret", secret,
            "--seed", str(args.seed),
            "--platform", args.platform or "cpu",
        ]
        if not seed_phase:
            argv += [
                "--chaos", args.train_chaos,
                "--chaos-args", "straggle-workers:1",
                "--step-deadline", "0.05", "--deadline-percentile", "95",
                "--deadline-floor", "0.001",
                "--straggler-stall", "0.08", "--stale-infill",
                "--journal", os.path.join(workdir, "journal_train.jsonl"),
                "--run-id", "soak-train",
                "--slo-baseline", baseline_path,
                "--slo-verdict", verdict_path,
                "--live-port", "0",
                "--live-ready-file", os.path.join(workdir, "ready_train"),
            ]
        return argv

    # The trainer is spawned LAST (spec order = spawn order): the serve
    # replicas and router take tens of seconds of ready-file handshakes,
    # and a trainer racing ahead during that window would hit its chaos
    # wave — and even finish — before the tick loop is in control.
    specs = [
        serve_spec("serve-a", port_a),
        serve_spec("serve-b", port_b),
        InstanceSpec(
            "router", "router",
            ["{python}", "-m", "aggregathor_tpu.cli.router",
             "--backend", "a=127.0.0.1:%d" % port_a,
             "--backend", "b=127.0.0.1:%d" % port_b,
             "--port", str(port_router), "--poll-interval", "0.2",
             "--down-after", "2", "--step-wait", "10",
             "--request-timeout", "15",
             "--ready-file", os.path.join(workdir, "ready_router"),
             "--journal", os.path.join(workdir, "journal_router.jsonl"),
             "--run-id", "soak-router"],
            cwd=_REPO_ROOT,
            url="127.0.0.1:%d" % port_router,
            ready_file=os.path.join(workdir, "ready_router"),
            journal=os.path.join(workdir, "journal_router.jsonl"),
            log=os.path.join(workdir, "log_router.txt"),
            cause_flag=True,
        ),
        # the deliberate crash-looper: exits 3 forever — flap damping bait
        InstanceSpec(
            "looper", "aux",
            ["{python}", "-c", "import sys, time; time.sleep(0.2); "
                               "sys.exit(3)"],
            cwd=_REPO_ROOT,
            log=os.path.join(workdir, "log_looper.txt"),
        ),
        InstanceSpec(
            "train", "train",
            train_argv(args.train_steps, args.checkpoint_delta),
            cwd=_REPO_ROOT,
            ready_file=os.path.join(workdir, "ready_train"),
            journal=os.path.join(workdir, "journal_train.jsonl"),
            verdict=verdict_path,
            checkpoint_dir=ckpt_dir,
            session_secret=secret,
            retunes=("step-deadline*10",),
            log=os.path.join(workdir, "log_train.txt"),
            cause_flag=True,        # a retune respawn cites the retune
        ),
    ]

    supervisor_journal = os.path.join(workdir, "journal_supervisor.jsonl")
    obs_events.install(supervisor_journal, run_id="soak-supervisor")
    obs_events.emit("run_start", role="supervisor", instances=sorted(names),
                    pid=os.getpid())
    config = SupervisorConfig(args.supervisor_args)
    supervisor = FleetSupervisor(
        specs, config=config, down_after=args.down_after,
        scrape_timeout=1.0,
    )

    # ---- seed the checkpoint stream BEFORE the fleet spawns -------------
    # serve restores at startup and would crash-loop (and get quarantined)
    # on an empty directory; a 2-step pre-run of the SAME cli.runner with
    # the SAME secret writes custody-signed snapshots at steps 1 and 2 the
    # backends restore immediately and the supervised trainer resumes from
    import subprocess

    started = time.monotonic()
    print("seeding checkpoint stream (workdir %s)..." % workdir)
    seed_argv = train_argv(2, 1, seed_phase=True)
    seed_argv[0] = sys.executable
    seeded = subprocess.run(
        seed_argv, cwd=_REPO_ROOT,
        stdout=open(os.path.join(workdir, "log_seed.txt"), "w"),
        stderr=subprocess.STDOUT, timeout=180)
    if seeded.returncode != 0:
        print("seed run failed (rc %d) — see %s"
              % (seeded.returncode, os.path.join(workdir, "log_seed.txt")))
        return 1
    print("seeded in %.1fs; fleet spinning up..."
          % (time.monotonic() - started,))
    supervisor.start()
    print("fleet up in %.1fs: router on 127.0.0.1:%d"
          % (time.monotonic() - started, port_router))

    # ---- closed-loop load ------------------------------------------------
    import numpy as np

    from aggregathor_tpu import models

    experiment = models.instantiate(args.experiment, args.experiment_args)
    rng = np.random.default_rng(args.seed)
    x_eval = np.asarray(experiment.dataset.x_test, np.float32)
    probe = x_eval[rng.choice(len(x_eval), size=args.request_rows,
                              replace=False)]
    body = json.dumps({"inputs": probe.tolist()}).encode()
    base = "http://127.0.0.1:%d" % port_router
    counts = {"ok": 0, "shed": 0, "dropped": 0}
    per_client_steps = [[] for _ in range(args.clients)]
    lock = threading.Lock()
    stop_load = threading.Event()

    def client(index):
        request = urllib.request.Request(
            base + "/predict", data=body,
            headers={"Content-Type": "application/json",
                     "X-Client-Id": "soak-client-%d" % index},
        )
        while not stop_load.is_set():
            try:
                with urllib.request.urlopen(request, timeout=30) as response:
                    out = json.loads(response.read())
                    code = response.status
            except urllib.error.HTTPError as exc:
                code = exc.code
                out = {}
            except Exception:
                code, out = -1, {}
            with lock:
                if code == 200:
                    counts["ok"] += 1
                    per_client_steps[index].append(out.get("weights_step"))
                elif code == 429:
                    counts["shed"] += 1
                else:
                    counts["dropped"] += 1
            time.sleep(0.05)

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(args.clients)]
    for thread in threads:
        thread.start()

    # ---- the soak loop: inject faults, let the supervisor drive ---------
    deadline = started + args.max_seconds
    injected = []        # {target, kind, tick, t_wall, t_mono}
    recovery = []        # one entry per injected fault, filled as it heals
    actions_seen = {"restart": 0, "quarantine": 0, "retune": 0,
                    "rollback": 0}
    rollback_seen = False
    tick = 0
    while time.monotonic() < deadline:
        if tick >= args.ticks and (
                rollback_seen or tick >= args.ticks + args.settle_ticks):
            break
        for kind, target in faults_by_tick.get(tick, ()):
            pid = supervisor.pid_of(target)
            if pid is None:
                continue             # already down: the fault is moot
            sig = signal.SIGKILL if kind == "kill" else signal.SIGSTOP
            os.kill(pid, sig)
            stamp = {"target": target, "kind": kind, "tick": tick,
                     "t_wall": time.time(), "t_mono": time.monotonic()}
            injected.append(stamp)
            recovery.append({
                "target": target, "kind": kind, "tick": tick,
                "restart_after_s": None, "envelope_s": None,
                "within_envelope": False, "recovered": False,
            })
            print("tick %d: %s %s (pid %d)" % (tick, kind, target, pid))
        # elapsed-to-restart is measured at the DECISION timestamp (the
        # tick that fired the Restart), not after the actuator's ready-file
        # handshake: the envelope bounds the supervisor's reaction
        # (detection + backoff grace + tick slack) — the respawned
        # process's own boot-to-ready time (tens of seconds for a serve
        # replica on a loaded box) is not the supervisor's latency
        decide_at = time.monotonic()
        actions = supervisor.tick()
        for action in actions:
            if isinstance(action, Restart):
                actions_seen["restart"] += 1
                for stamp, entry in zip(injected, recovery):
                    if (entry["target"] == action.instance
                            and entry["restart_after_s"] is None):
                        elapsed = decide_at - stamp["t_mono"]
                        detect = (args.down_after
                                  * (args.tick_interval + 1.0)
                                  if entry["kind"] == "hang" else 0.0)
                        envelope = (action.backoff_s + detect
                                    + 3.0 * args.tick_interval + 2.0)
                        entry["restart_after_s"] = round(elapsed, 2)
                        entry["envelope_s"] = round(envelope, 2)
                        entry["within_envelope"] = elapsed <= envelope
                        break
                print("tick %d: restarted %s (reason %s, attempt %d)"
                      % (tick, action.instance, action.reason,
                         action.attempt))
            elif isinstance(action, Quarantine):
                actions_seen["quarantine"] += 1
                print("tick %d: QUARANTINED %s after %d attempts"
                      % (tick, action.instance, action.attempts))
            elif isinstance(action, Retune):
                actions_seen["retune"] += 1
                print("tick %d: retuned %s -> %s (%s)"
                      % (tick, action.instance, action.rung, action.reason))
            elif isinstance(action, Rollback):
                actions_seen["rollback"] += 1
                rollback_seen = True
                print("tick %d: ROLLBACK %s (%s)"
                      % (tick, action.instance, action.reason))
        for entry in recovery:
            if not entry["recovered"] and entry["restart_after_s"] is not None:
                if (supervisor.pid_of(entry["target"]) is not None
                        and supervisor.up_of(entry["target"]) is not False):
                    entry["recovered"] = True
        tick += 1
        time.sleep(args.tick_interval)
    elapsed_total = time.monotonic() - started

    stop_load.set()
    for thread in threads:
        thread.join(timeout=35)
    # one last recovery sweep before teardown
    for entry in recovery:
        if not entry["recovered"] and entry["restart_after_s"] is not None:
            if (supervisor.pid_of(entry["target"]) is not None
                    and supervisor.up_of(entry["target"]) is not False):
                entry["recovered"] = True
    quarantined = [n for n in names if supervisor.is_quarantined(n)]
    restarts = {n: supervisor.restarts_of(n) for n in names}
    supervisor.stop()
    obs_events.emit("run_end", role="supervisor")
    obs_events.uninstall()

    # ---- judge -----------------------------------------------------------
    records = obs_events.load_journal(supervisor_journal)   # EV001-clean
    by_type = {}
    for record in records:
        by_type.setdefault(record["type"], []).append(record)
    action_types = ("supervisor_restart", "supervisor_quarantine",
                    "supervisor_retune", "supervisor_rollback")
    evidence_complete = all(
        isinstance(r.get("evidence"), dict) and r["evidence"]
        for t in action_types for r in by_type.get(t, ())
    ) and all(len(by_type.get(t, ())) == actions_seen[k]
              for t, k in zip(action_types,
                              ("restart", "quarantine", "retune",
                               "rollback")))
    kill_before_restart = all(
        any(r["instance"] == stamp["target"]
            and r["t_wall"] >= stamp["t_wall"] - 0.5
            for r in by_type.get("supervisor_restart", ()))
        for stamp in injected
    )
    rollbacks = by_type.get("supervisor_rollback", [])
    try:
        with open(verdict_path) as fd:
            final_verdict = json.load(fd)
    except OSError:
        final_verdict = None
    rollback_cites_verdict = bool(rollbacks) and all(
        r["evidence"].get("judged_at") is not None for r in rollbacks)
    ckpt_steps = Checkpoints(ckpt_dir).steps() if os.path.isdir(
        ckpt_dir) else []
    restore_steps = [r["restore_step"] for r in rollbacks]
    tail_discarded = bool(rollbacks) and all(
        r["discarded_steps"] for r in rollbacks)

    with lock:
        monotonic_clients = all(
            all(a <= b for a, b in zip(seq, seq[1:]))
            for seq in per_client_steps
        )
        observed = sorted({s for seq in per_client_steps for s in seq
                           if s is not None})
    looper_quarantines = [r for r in by_type.get("supervisor_quarantine", ())
                          if r["instance"] == "looper"]
    faulted = sorted({e["target"] for e in recovery})

    # ---- the causal plane: every fleet journal through the SHARED
    # postmortem checker (obs/causal.py — exactly what cli.postmortem
    # runs), replacing nothing above but PROVING what the hand-written
    # assertions can't: the cross-process edges.  The supervisor's
    # --cause injection means every respawned serve/router/train run's
    # run_start must cite the supervisor_restart/supervisor_retune that
    # spawned it; the crash-looper keeps no journal so its spawn chain is
    # unobservable by design (not a violation).
    from aggregathor_tpu.obs import causal

    pm_sources = {"supervisor": supervisor_journal}
    for spec in specs:
        if spec.journal:
            pm_sources[spec.name] = spec.journal
    postmortem = causal.run_postmortem(pm_sources)

    verdict = {
        "kills_recovered": bool(recovery) and all(
            e["recovered"] for e in recovery),
        "recovery_in_envelope": bool(recovery) and all(
            e["within_envelope"] for e in recovery),
        "crash_looper_quarantined": "looper" in quarantined
        and bool(looper_quarantines)
        and all(r["evidence"].get("attempts") == config.max_restarts
                or r["attempts"] == config.max_restarts
                for r in looper_quarantines),
        "regress_rolled_back": bool(rollbacks)
        and all(r["custody_verified"] is True for r in rollbacks)
        and tail_discarded,
        "zero_step_regressions": monotonic_clients and counts["ok"] > 0,
        "journal_causal": evidence_complete and kill_before_restart
        and rollback_cites_verdict,
        "postmortem_closes": postmortem["verdict"] == "PASS",
    }
    retune_ok = actions_seen["retune"] >= 1
    if not args.no_require_retune:
        verdict["retune_applied"] = retune_ok
    verdict["pass"] = all(verdict.values())

    doc = {
        "schema": SCHEMA,
        "config": {
            "experiment": args.experiment,
            "train_steps": args.train_steps,
            "ticks": tick,
            "tick_interval_s": args.tick_interval,
            "process_chaos": args.process_chaos,
            "train_chaos": args.train_chaos,
            "supervisor": config.describe(),
            "down_after": args.down_after,
            "clients": args.clients,
            "duration_s": round(elapsed_total, 1),
        },
        "fleet": {
            "instances": sorted(names),
            "process_faults": [
                {"target": s["target"], "kind": s["kind"], "tick": s["tick"]}
                for s in injected],
            "quarantined": quarantined,
            "restarts": restarts,
        },
        "recovery": recovery,
        "retune": {
            "events": len(by_type.get("supervisor_retune", ())),
            "rungs": [r["rung"] for r in
                      by_type.get("supervisor_retune", ())],
            "required": not args.no_require_retune,
        },
        "rollback": {
            "events": len(rollbacks),
            "restore_step": restore_steps[-1] if restore_steps else None,
            "custody_verified": bool(rollbacks) and all(
                r["custody_verified"] is True for r in rollbacks),
            "final_ckpt_steps": ckpt_steps,
            "verdict_judged_at": (final_verdict or {}).get("judged_at"),
        },
        "traffic": {
            "requests": counts["ok"] + counts["shed"] + counts["dropped"],
            "ok": counts["ok"],
            "sheds": counts["shed"],
            "dropped": counts["dropped"],
            "clients": args.clients,
            "monotonic_clients": monotonic_clients,
            "observed_steps": observed,
        },
        "journal": {
            "events": {etype: len(rows) for etype, rows in
                       sorted(by_type.items())},
            "evidence_complete": evidence_complete,
            "kill_before_restart": kill_before_restart,
            "rollback_cites_verdict": rollback_cites_verdict,
        },
        "postmortem": {
            "verdict": postmortem["verdict"],
            "failing": postmortem["failing"],
            "instances": {name: entry.get("events", 0) for name, entry in
                          postmortem["instances"].items()},
            "events": postmortem["events_total"],
            "edges": postmortem["edges_total"],
            "chains": [{"kind": c["kind"],
                        "type": c["action"]["type"],
                        "subject": c["action"].get("subject"),
                        "seq": c["action"]["seq"]}
                       for c in postmortem["chains"]],
            "violations": {key: len(entries) for key, entries in
                           postmortem["violations"].items()},
            "skew_pairs": postmortem["skew"]["pairs"],
        },
        "verdict": verdict,
    }
    validate(doc)
    print("soak: %d ticks in %.0fs; faults %r; restarts %r; "
          "quarantined %r; retunes %d; rollbacks %d"
          % (tick, elapsed_total, faulted, restarts, quarantined,
             actions_seen["retune"], actions_seen["rollback"]))
    print("traffic: %d ok, %d shed, %d dropped; steps %r; monotone %s"
          % (counts["ok"], counts["shed"], counts["dropped"], observed,
             monotonic_clients))
    print("postmortem: %s — %d event(s), %d edge(s), %d chain(s)%s"
          % (postmortem["verdict"], postmortem["events_total"],
             postmortem["edges_total"], len(postmortem["chains"]),
             " (failing: %s)" % ", ".join(postmortem["failing"])
             if postmortem["failing"] else ""))
    print("verdict: %s — %s"
          % (" ".join("%s=%s" % (k, v) for k, v in sorted(verdict.items())
                      if k != "pass"),
             "PASS" if verdict["pass"] else "FAIL"))
    if args.out:
        with open(args.out, "w") as fd:
            json.dump(doc, fd, indent=1)
            fd.write("\n")
    return 0 if verdict["pass"] else 1


if __name__ == "__main__":
    sys.exit(main())
