"""Experiment-scale robustness table: accuracy under attack, per rule.

The reference's entire reason to exist is that robust GARs keep training
under Byzantine gradients while plain averaging does not (SysML'19;
experiments.sh:19-53 is its harness).  The unit suite proves this at toy
scale; this harness produces the experiment-scale evidence: cnnet CIFAR-10,
n=8 workers, f=2 declared / 2 real attackers, {average, krum, median} x
{none, little, empire}, final evaluation accuracy after a fixed step budget
— driven through the REAL CLI as subprocesses.

Expected shape of the result: under ``little``/``empire`` the robust rules
keep learning while ``average`` is dragged (or NaN-aborts, which the runner
surfaces as a divergence error — recorded here as ``diverged``).

Usage::

    python benchmarks/robustness.py [--steps 300] [--batch 32] [--platform cpu]
                                    [--rules average,krum,median]
                                    [--attacks none,little,empire]

Prints one JSON line per cell and a final markdown table (paste into
docs/robustness.md).
"""

import argparse
import itertools
import json
import os
import shlex
import shutil
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_cell(rule, attack, steps, batch, platform, timeout, experiment, extra_args=(),
             experiment_args=()):
    eval_dir = tempfile.mkdtemp(prefix="aggregathor_rob_")
    eval_file = os.path.join(eval_dir, "eval.tsv")
    cmd = [
        sys.executable, "-m", "aggregathor_tpu.cli.runner",
        "--experiment", experiment,
        "--experiment-args", "batch-size:%d" % batch, *experiment_args,
        "--aggregator", rule,
        "--nb-workers", "8", "--nb-decl-byz-workers", "2",
        "--max-step", str(steps),
        "--learning-rate-args", "initial-rate:0.05",
        "--evaluation-file", eval_file,
        "--evaluation-delta", str(max(steps // 4, 1)), "--evaluation-period", "-1",
    ]
    if attack != "none":
        cmd += ["--attack", attack, "--nb-real-byz-workers", "2"]
    env = dict(os.environ)
    if platform:
        cmd += ["--platform", platform]
        env["JAX_PLATFORMS"] = platform
    # LAST, so user-supplied flags win an argparse last-wins conflict with
    # anything the harness appended (e.g. --platform)
    cmd += list(extra_args)
    if platform == "cpu":
        env.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    # One process per chip: this parent is stdlib-only (it never imports
    # JAX), so the runner child is the only process that touches the device;
    # cells run one after another.  Keep it so.
    try:
        proc = subprocess.run(
            cmd, cwd=REPO, env=env, capture_output=True, text=True, timeout=timeout
        )
    except subprocess.TimeoutExpired:
        shutil.rmtree(eval_dir, ignore_errors=True)
        # Full row schema (the table printer and the watcher's stage
        # accounting read these keys on every row)
        return {"metric": "robustness_accuracy", "experiment": experiment,
                "platform": platform or "ambient", "rule": rule, "attack": attack,
                "accuracy": None, "diverged": False, "error": "timeout"}
    accuracy, last_step = None, None
    try:
        for line in open(eval_file):
            fields = line.strip().split("\t")
            last_step = int(fields[1])
            for kv in fields[2:]:
                name, _, value = kv.partition(":")
                if name == "accuracy":
                    accuracy = float(value)
    except OSError:
        pass
    shutil.rmtree(eval_dir, ignore_errors=True)
    diverged = proc.returncode != 0 and "diverg" in (proc.stdout + proc.stderr).lower()
    row = {
        "metric": "robustness_accuracy",
        "experiment": experiment,
        "platform": platform or "ambient",
        "rule": rule, "attack": attack,
        "n": 8, "f": 2, "real_byz": 0 if attack == "none" else 2,
        "steps": steps, "batch": batch,
        "accuracy": accuracy, "eval_step": last_step,
        "diverged": bool(diverged),
    }
    if proc.returncode != 0 and not diverged:
        row["error"] = (proc.stderr or proc.stdout).strip()[-300:]
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--rules", default="average,krum,median")
    ap.add_argument("--attacks", default="none,little,empire")
    ap.add_argument("--experiment", default="cnnet")
    ap.add_argument("--platform", default="cpu")
    ap.add_argument("--timeout", type=int, default=3600, help="per-cell seconds")
    ap.add_argument("--resume-file", default=None,
                    help="JSON path recording completed cells: a re-run skips "
                         "them (and REPRINTS their rows, so the final "
                         "invocation still emits the full table).  Lets a "
                         "scarce TPU up-window make incremental progress "
                         "instead of restarting the 12-cell grid each time.")
    ap.add_argument("--runner-args", default="",
                    help="extra flags appended to every runner invocation, as "
                         "ONE quoted string (argparse cannot nest leading "
                         "dashes): --runner-args '--worker-momentum 0.9'")
    ap.add_argument("--experiment-args-extra", default="",
                    help="extra key:value tokens APPENDED to the harness's "
                         "own --experiment-args (which carries batch-size "
                         "from --batch — so batch stays single-sourced): "
                         "--experiment-args-extra 'augment:device'")
    ap.add_argument("--seeds", default=None,
                    help="comma list of --seed values; each cell runs once "
                         "per seed and the table reports mean ± half-range "
                         "(the docs/robustness.md multi-seed protocol). "
                         "Default: single run at the runner's default seed.")
    args = ap.parse_args()
    args.runner_args = shlex.split(args.runner_args)
    args.experiment_args_extra = shlex.split(args.experiment_args_extra)

    sys.path.insert(0, REPO)
    from aggregathor_tpu.utils.state import load_json, save_json_atomic

    rules = args.rules.split(",")
    attacks = args.attacks.split(",")
    seeds = args.seeds.split(",") if args.seeds else [None]
    resume = load_json(args.resume_file) if args.resume_file else {}
    rows = []
    for rule, attack in itertools.product(rules, attacks):
        per_seed = []
        for seed in seeds:
            extra = args.runner_args + (["--seed", seed] if seed is not None else [])
            # EVERY measurement condition is in the key — a row cached under
            # one platform/batch/seed/runner-args must never answer for
            # another.
            key = "%s|%s|%s|%d|%d|%s|%s" % (
                args.experiment, rule, attack, args.steps, args.batch,
                args.platform or "ambient",
                " ".join(args.experiment_args_extra + extra))
            row = resume.get(key)
            if row is None or row.get("error"):
                row = run_cell(rule, attack, args.steps, args.batch, args.platform,
                               args.timeout, args.experiment, extra_args=extra,
                               experiment_args=args.experiment_args_extra)
                if seed is not None:
                    row["seed"] = seed
                if args.resume_file and not row.get("error"):
                    resume[key] = row
                    save_json_atomic(args.resume_file, resume)
            per_seed.append(row)
            print(json.dumps(row), flush=True)
        rows.append((rule, attack, per_seed))

    print("\n| rule | " + " | ".join(attacks) + " |")
    print("|------|" + "---|" * len(attacks))
    for rule in rules:
        cells = []
        for attack in attacks:
            per_seed = next(ps for r, a, ps in rows if r == rule and a == attack)
            if any(r.get("diverged") for r in per_seed):
                cells.append("diverged (NaN abort)")
                continue
            accs = [r["accuracy"] for r in per_seed if r.get("accuracy") is not None]
            if not accs:
                cells.append(per_seed[0].get("error", "error"))
            elif len(accs) == 1:
                cells.append("%.3f" % accs[0])
            else:
                cells.append("%.3f ± %.3f" % (
                    sum(accs) / len(accs), (max(accs) - min(accs)) / 2))
        print("| %s | %s |" % (rule, " | ".join(cells)))


if __name__ == "__main__":
    main()
