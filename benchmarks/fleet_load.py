"""Closed-loop fleet load benchmark: N real serving processes behind the
traffic plane, one backend killed mid-run, mid-run weight swaps — judged
on the router's four guarantees.

The PR-16 acceptance harness (docs/serving.md "The traffic plane").  One
driver process plays the whole fleet story end to end:

1. **train**: a short real digits run whose snapshots at three increasing
   steps become the checkpoint stream (``serve_load.train_with_snapshots``
   — the first is served at startup, the other two land on disk MID-LOAD
   and reach every backend through its own checkpoint watcher);
2. **fleet**: ``--backends`` REAL ``cli/serve.py`` subprocesses (own
   interpreters, own ports) all following the shared checkpoint
   directory with ``--follow``, fronted by an in-process
   :class:`~aggregathor_tpu.serve.FleetRouter` + ``RouterServer`` with
   the causal journal installed — clients speak real HTTP to the router,
   the router speaks real HTTP to the backends;
3. **load**: ``--clients`` closed-loop clients (each with a sticky
   ``X-Client-Id``) fire ``/predict`` for ``--duration`` seconds while
   the driver lands snapshot 2 at 1/3, SIGKILLs one backend at 1/2, and
   lands snapshot 3 at 2/3 — kill and swaps overlap live traffic;
4. **judge**: hard verdicts only, no latency SLO —
   **zero dropped requests** (the killed backend's in-flight requests
   re-dispatch exactly once; every client sees 200 or an honest 429),
   **fleet-monotone weights_step** (no client's step sequence ever
   decreases, across replicas AND across the kill),
   **zero recompiles per backend** (each backend's ``serve_compile_count``
   == its bucket-ladder length; the killed backend is judged from the
   router's HELD last scrape),
   **journal chain** (the router journal replays the causal kill story:
   ``router_backend_down`` for the killed backend strictly before the
   ``router_retry``/``router_route`` that moved its traffic).

Emits one ``aggregathor.fleet.load.v1`` document (``validate``/``load``
below are the round-trip the smoke and tests assert); exit status is the
overall verdict.  The checked-in ``FLEET_r16.json`` at the repo root is a
passing run of this benchmark on the 1-core CI box.

Example (CPU)::

    python benchmarks/fleet_load.py --duration 8 --clients 6 \
        --out FLEET_r16.json
"""

import argparse
import json
import os
import signal
import subprocess
import sys
import threading
import time

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO_ROOT)

SCHEMA = "aggregathor.fleet.load.v1"


def validate(doc):
    """Schema check for round-tripping consumers (the smoke script and
    tests assert this shape on the checked-in FLEET_r16.json)."""
    if doc.get("schema") != SCHEMA:
        raise ValueError("not a %s document" % SCHEMA)
    for key in ("config", "traffic", "fleet", "swaps", "journal", "verdict"):
        if key not in doc:
            raise ValueError("missing %r" % key)
    traffic = doc["traffic"]
    for key in ("requests", "ok", "sheds", "dropped", "req_per_s",
                "p50_ms", "p99_ms"):
        if key not in traffic:
            raise ValueError("traffic missing %r" % key)
    fleet = doc["fleet"]
    for key in ("backends", "killed", "kill_at_s", "compile_counts",
                "nb_buckets"):
        if key not in fleet:
            raise ValueError("fleet missing %r" % key)
    swaps = doc["swaps"]
    for key in ("steps", "observed", "monotonic_clients"):
        if key not in swaps:
            raise ValueError("swaps missing %r" % key)
    journal = doc["journal"]
    for key in ("events", "kill_chain"):
        if key not in journal:
            raise ValueError("journal missing %r" % key)
    verdict = doc["verdict"]
    for key in ("zero_dropped", "fleet_monotonic", "swaps_ok",
                "zero_recompiles", "journal_chain", "pass"):
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
    parser.add_argument("--train-steps", type=int, default=60,
                        help="in-process training steps (snapshots at 1/3, "
                             "2/3 and the end)")
    parser.add_argument("--learning-rate", type=float, default=0.05)
    parser.add_argument("--backends", type=int, default=3,
                        help="serving subprocesses behind the router")
    parser.add_argument("--max-batch", type=int, default=8,
                        help="per-backend bucket ladder top")
    parser.add_argument("--lanes", type=int, default=2)
    parser.add_argument("--queue-bound", type=int, default=512)
    parser.add_argument("--clients", type=int, default=6,
                        help="closed-loop HTTP clients (sticky X-Client-Id)")
    parser.add_argument("--request-rows", type=int, default=4)
    parser.add_argument("--duration", type=float, default=8.0,
                        help="load seconds (swap at 1/3 and 2/3, kill at 1/2)")
    parser.add_argument("--kill-index", type=int, default=None,
                        help="which backend to SIGKILL mid-run "
                             "(default: the last)")
    parser.add_argument("--startup-timeout", type=float, default=180.0,
                        help="per-fleet bound on subprocess warmup+bind")
    parser.add_argument("--step-wait", type=float, default=15.0,
                        help="router step-pin swap-window bound")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--out", default=None, help="write the JSON here")
    parser.add_argument("--workdir", default=None,
                        help="shared checkpoint directory + scratch "
                             "(default: a fresh tempdir)")
    parser.add_argument("--platform", default="cpu")
    return parser


def _read_ready(path, deadline):
    while time.monotonic() < deadline:
        if os.path.exists(path):
            host, port, pid = open(path).read().split()
            return host, int(port), int(pid)
        time.sleep(0.1)
    raise RuntimeError("backend never became ready: %s" % path)


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

    import numpy as np

    from aggregathor_tpu import models
    from aggregathor_tpu.obs import Checkpoints, LatencyHistogram
    from aggregathor_tpu.obs import events as obs_events
    from aggregathor_tpu.obs.metrics import MetricsRegistry, parse_prometheus
    from aggregathor_tpu.serve import FleetRouter, RouterServer, bucket_ladder
    from serve_load import train_with_snapshots

    if args.backends < 2:
        raise SystemExit("--backends must be >= 2 (a kill needs a survivor)")
    experiment = models.instantiate(args.experiment, args.experiment_args)

    # ---- phase 1: train, seed the shared checkpoint stream --------------
    t0 = time.perf_counter()
    snapshots = train_with_snapshots(
        experiment, args.train_steps, args.learning_rate, args.seed
    )
    steps = [step for step, _ in snapshots]
    print("trained %d step(s) in %.1fs; snapshot stream: %r"
          % (args.train_steps, time.perf_counter() - t0, steps))
    workdir = args.workdir or tempfile.mkdtemp(prefix="fleet_load_")
    checkpoints = Checkpoints(workdir)
    checkpoints.save(snapshots[0][1], step=snapshots[0][0])

    # ---- phase 2: the fleet — real cli.serve subprocesses + the router --
    names = [chr(ord("a") + i) for i in range(args.backends)]
    kill_index = (args.kill_index if args.kill_index is not None
                  else args.backends - 1)
    killed_name = names[kill_index]
    procs, ready_files = {}, {}
    env = dict(os.environ, JAX_PLATFORMS=args.platform or "cpu")
    for name in names:
        ready_files[name] = os.path.join(workdir, "ready_%s" % name)
        procs[name] = subprocess.Popen(
            [sys.executable, "-m", "aggregathor_tpu.cli.serve",
             "--experiment", args.experiment,
             "--experiment-args", *args.experiment_args,
             "--ckpt-dir", workdir, "--replicas", "1", "--gar", "none",
             "--max-batch", str(args.max_batch),
             "--lanes", str(args.lanes),
             "--queue-bound", str(args.queue_bound),
             "--follow", "--follow-interval", "0.2",
             "--port", "0", "--ready-file", ready_files[name],
             "--journal", os.path.join(workdir, "journal_%s.jsonl" % name),
             "--run-id", "fleet-%s" % name,
             "--platform", args.platform or "cpu"],
            cwd=_REPO_ROOT, env=env,
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
        )
    deadline = time.monotonic() + args.startup_timeout
    backends = {}
    for name in names:
        host, port, _pid = _read_ready(ready_files[name], deadline)
        backends[name] = "%s:%d" % (host, port)
    print("fleet up: %s" % ", ".join(
        "%s=%s" % (n, backends[n]) for n in names))

    router_journal = os.path.join(workdir, "journal_router.jsonl")
    obs_events.install(router_journal, run_id="fleet-router")
    obs_events.emit("run_start", role="router", backends=names,
                    pid=os.getpid())
    router = FleetRouter(
        backends, registry=MetricsRegistry(), poll_interval=0.1,
        down_after=2, step_wait_s=args.step_wait,
    )
    server = RouterServer(router)
    router.start()
    host, port = server.serve_background()
    base = "http://%s:%d" % (host, port)

    # ---- phase 3: closed-loop load + swap/kill schedule -----------------
    rng = np.random.default_rng(args.seed)
    x_eval = np.asarray(experiment.dataset.x_test, np.float32)
    probe = x_eval[rng.choice(len(x_eval), size=args.request_rows,
                              replace=False)]
    body = json.dumps({"inputs": probe.tolist()}).encode()
    hist = LatencyHistogram(capacity=8192)
    lock = threading.Lock()
    counts = {"ok": 0, "shed": 0, "dropped": 0}
    per_client_steps = [[] for _ in range(args.clients)]
    errors = []
    stop_at = time.monotonic() + args.duration

    def client(index):
        request = urllib.request.Request(
            base + "/predict", data=body,
            headers={"Content-Type": "application/json",
                     "X-Client-Id": "client-%d" % index},
        )
        while time.monotonic() < stop_at:
            started = time.perf_counter()
            try:
                with urllib.request.urlopen(request, timeout=60) as response:
                    out = json.loads(response.read())
                    code = response.status
            except urllib.error.HTTPError as exc:
                try:
                    out = json.loads(exc.read())
                except Exception:
                    out = {}
                code = exc.code
            except Exception as exc:
                code, out = -1, {"error": repr(exc)}
            elapsed = time.perf_counter() - started
            with lock:
                if code == 200:
                    counts["ok"] += 1
                    hist.record(elapsed)
                    per_client_steps[index].append(out.get("weights_step"))
                elif code == 429:
                    counts["shed"] += 1
                else:
                    counts["dropped"] += 1
                    errors.append((code, out.get("error")))

    def live_known_steps():
        status = router.status_payload()["backends"]
        return {name: entry["known_step"]
                for name, entry in status.items() if entry["up"]}

    def wait_fleet_at(step, bound_s):
        observe_by = time.monotonic() + bound_s
        while time.monotonic() < observe_by:
            known = live_known_steps()
            if known and all(value == step for value in known.values()):
                return True
            time.sleep(0.05)
        return False

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(args.clients)]
    started = time.perf_counter()
    for thread in threads:
        thread.start()

    kill_at = None
    third = args.duration / 3
    # swap 1 at 1/3 (all backends observe it), kill at 1/2, swap 2 at 2/3
    schedule = [
        (1 * third, "swap", snapshots[1]),
        (1.5 * third, "kill", None),
        (2 * third, "swap", snapshots[2]),
    ]
    for at, action, payload in schedule:
        delay = started + at - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        if action == "swap":
            step, state = payload
            checkpoints.save(state, step=step)
            print("snapshot step %d landed at t=%.1fs"
                  % (step, time.perf_counter() - started))
            wait_fleet_at(step, third)
        else:
            kill_at = time.perf_counter() - started
            procs[killed_name].send_signal(signal.SIGKILL)
            print("SIGKILL backend %r at t=%.1fs" % (killed_name, kill_at))
    for thread in threads:
        thread.join()
    elapsed = time.perf_counter() - started

    # ---- phase 4: teardown + per-backend forensics ----------------------
    # the killed backend's compile count comes from the router collector's
    # HELD last scrape (down != dropped, the PR-15 staleness contract)
    fleet_text = router.collector.render_metrics()
    compile_samples = parse_prometheus(fleet_text).get(
        "serve_compile_count", {"samples": []})["samples"]
    compile_counts = {labels["instance"]: int(value)
                      for _name, labels, value in compile_samples
                      if labels.get("instance") in backends}
    final_steps = live_known_steps()
    server.shutdown_all()
    router.close()
    obs_events.emit("run_end", role="router")
    obs_events.uninstall()
    for name, proc in procs.items():
        if name != killed_name:
            proc.send_signal(signal.SIGTERM)  # the drain path
    for name, proc in procs.items():
        try:
            proc.wait(timeout=20)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=10)

    # ---- phase 5: judge --------------------------------------------------
    records = obs_events.load_journal(router_journal)
    by_type = {}
    for record in records:
        by_type.setdefault(record["type"], []).append(record)
    downs = [r for r in by_type.get("router_backend_down", ())
             if r["backend"] == killed_name]
    moved = (by_type.get("router_retry", [])
             + [r for r in by_type.get("router_route", ())
                if r.get("reason") == "backend_down"])
    kill_chain = bool(downs) and any(
        record["seq"] > downs[0]["seq"] for record in moved)

    tail = hist.percentiles() or {"p50": float("inf"), "p99": float("inf")}
    req_per_s = counts["ok"] / max(elapsed, 1e-9)
    monotonic = all(
        all(a <= b for a, b in zip(seq, seq[1:]))
        for seq in per_client_steps
    )
    observed = sorted({s for seq in per_client_steps for s in seq})
    nb_buckets = len(bucket_ladder(args.max_batch))
    survivors = [name for name in names if name != killed_name]
    verdict = {
        "zero_dropped": counts["dropped"] == 0 and counts["ok"] > 0,
        "fleet_monotonic": monotonic
        and all(s in steps for s in observed),
        "swaps_ok": all(final_steps.get(name) == steps[-1]
                        for name in survivors)
        and len([s for s in observed if s != steps[0]]) >= 1
        and observed[-1] == steps[-1],
        "zero_recompiles": set(compile_counts) == set(names)
        and all(count == nb_buckets for count in compile_counts.values()),
        "journal_chain": kill_chain,
    }
    verdict["pass"] = all(verdict.values())

    doc = {
        "schema": SCHEMA,
        "config": {
            "experiment": args.experiment,
            "backends": args.backends,
            "clients": args.clients,
            "request_rows": args.request_rows,
            "duration_s": args.duration,
            "max_batch": args.max_batch,
            "lanes": args.lanes,
            "snapshot_steps": steps,
        },
        "traffic": {
            "requests": counts["ok"] + counts["shed"] + counts["dropped"],
            "ok": counts["ok"],
            "sheds": counts["shed"],
            "dropped": counts["dropped"],
            "req_per_s": round(req_per_s, 2),
            "p50_ms": round(tail["p50"] * 1e3, 3),
            "p99_ms": round(tail["p99"] * 1e3, 3),
        },
        "fleet": {
            "backends": names,
            "killed": killed_name,
            "kill_at_s": round(kill_at, 2) if kill_at is not None else None,
            "compile_counts": compile_counts,
            "nb_buckets": nb_buckets,
            "final_steps": final_steps,
        },
        "swaps": {
            "steps": steps,
            "observed": observed,
            "monotonic_clients": monotonic,
        },
        "journal": {
            "events": {etype: len(rows) for etype, rows in
                       sorted(by_type.items())},
            "kill_chain": kill_chain,
        },
        "verdict": verdict,
    }
    validate(doc)
    print("fleet load: %d ok (%.1f req/s, p99 %.1f ms), %d shed, %d dropped"
          % (counts["ok"], req_per_s, tail["p99"] * 1e3, counts["shed"],
             counts["dropped"]))
    if errors:
        print("dropped outcomes: %r" % errors[:5])
    print("observed steps %r; compile %r (ladder %d); kill chain %s — %s"
          % (observed, compile_counts, nb_buckets, kill_chain,
             "PASS" if verdict["pass"] else "FAIL"))
    if args.out:
        with open(args.out, "w") as fd:
            json.dump(doc, fd, indent=1)
            fd.write("\n")
    return 0 if verdict["pass"] else 1


if __name__ == "__main__":
    sys.exit(main())
