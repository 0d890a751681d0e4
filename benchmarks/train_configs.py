"""BASELINE.json training-config benchmark: steps/s through the real CLI.

Each entry launches ``aggregathor_tpu.cli.runner`` as a subprocess — the
exact surface a user drives, paying the full input pipeline, host->device
transfer, and metric plumbing — and parses the end-of-run performance report
(the reference's own metric: steps/s excluding the first/compilation step,
reference runner.py:595-597).

Configs follow BASELINE.md's protocol, sized per worker so the largest ones
fit a single chip; the JSON output records every sizing knob so numbers are
only ever compared like-for-like.

Usage::

    python benchmarks/train_configs.py [--configs 1,2,3,4] [--steps 40]
                                       [--platform tpu]
"""

import argparse
import glob
import json
import os
import re
import shutil
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: BASELINE.md config table (batch = per-worker batch size)
CONFIGS = {
    "1": {
        "name": "mnist_average_n4_f0",
        "note": "BASELINE config 1 (single-host CPU reference)",
        "args": ["--experiment", "mnist", "--aggregator", "average",
                 "--nb-workers", "4", "--nb-decl-byz-workers", "0",
                 "--experiment-args", "batch-size:50"],
        "platform": "cpu",  # the config IS the CPU reference
    },
    "2": {
        "name": "cnnet_krum_n8_f2",
        "note": "BASELINE config 2 (bench.py measures this too, in-process)",
        "args": ["--experiment", "cnnet", "--aggregator", "krum",
                 "--nb-workers", "8", "--nb-decl-byz-workers", "2",
                 "--experiment-args", "batch-size:128"],
    },
    "2b": {
        "name": "cnnet_krum_n8_f2_bf16_deviceaug",
        "note": "config 2 with the TPU-lean options on: bfloat16 compute, "
                "device-side augmentation (the f32/host-augment row stays "
                "the like-for-like baseline)",
        "args": ["--experiment", "cnnet", "--aggregator", "krum",
                 "--nb-workers", "8", "--nb-decl-byz-workers", "2",
                 "--experiment-args", "batch-size:128", "dtype:bfloat16", "augment:device"],
    },
    "2d": {
        "name": "cnnet_krum_n8_f2_bf16_devicesampled",
        "note": "config 2b with --input-source device: the train split "
                "lives on-chip and fresh i.i.d. per-worker batches are "
                "gathered in-graph, so no step pays a host->device transfer",
        "args": ["--experiment", "cnnet", "--aggregator", "krum",
                 "--nb-workers", "8", "--nb-decl-byz-workers", "2",
                 "--unroll", "10", "--input-source", "device",
                 "--experiment-args", "batch-size:128", "dtype:bfloat16", "augment:device"],
    },
    "2c": {
        "name": "cnnet_bucketing_krum_n8_f1",
        "note": "config 2's model with the bucketing meta-rule (s=2, inner "
                "krum over 4 buckets needs f <= 1): extension-rule throughput",
        "args": ["--experiment", "cnnet", "--aggregator", "bucketing",
                 "--aggregator-args", "s:2", "inner:krum",
                 "--nb-workers", "8", "--nb-decl-byz-workers", "1",
                 "--experiment-args", "batch-size:128"],
    },
    "3": {
        "name": "resnet50_bulyan_n32_f7",
        "note": "BASELINE config 3 prescribes Bulyan at (n=32, f=8), which "
                "violates Bulyan's own feasibility bound n >= 4f+3 = 35 "
                "(reference op_bulyan/cpu.cpp:57-58: b = n-4f-2 would be "
                "negative — the reference aborts identically); measured at "
                "the nearest feasible f=7. Per-worker batch 4 at 128x128 to "
                "fit one chip. Data: real slim-layout TFRecord shards when "
                "on disk (PIL decode, capped subset — "
                "models/datasets.load_imagenet), else ImageNet-shaped "
                "synthetic stand-in (THROUGHPUT ONLY, no accuracy claim) — "
                "the JSON row records which",
        "args": ["--experiment", "slim-resnet_v1_50-imagenet", "--aggregator", "bulyan",
                 "--nb-workers", "32", "--nb-decl-byz-workers", "7",
                 "--experiment-args", "batch-size:4", "image-size:128", "dtype:bfloat16"],
    },
    "3k": {
        "name": "resnet50_krum_n32_f8",
        "note": "BASELINE.json's metric line also names Krum at (n=32, f=8), "
                "which IS feasible (krum needs n >= f+3): the companion row "
                "at the prescribed f. Same data policy as config 3",
        "args": ["--experiment", "slim-resnet_v1_50-imagenet", "--aggregator", "krum",
                 "--nb-workers", "32", "--nb-decl-byz-workers", "8",
                 "--experiment-args", "batch-size:4", "image-size:128", "dtype:bfloat16"],
    },
    "3d": {
        "name": "resnet50_krum_n32_f8_devicesampled",
        "note": "config 3k with augment:device + --input-source device "
                "--unroll 5: ImageNet-shaped batches gathered on-chip "
                "instead of 25 MB/step from the host",
        "args": ["--experiment", "slim-resnet_v1_50-imagenet", "--aggregator", "krum",
                 "--nb-workers", "32", "--nb-decl-byz-workers", "8",
                 "--unroll", "5", "--input-source", "device",
                 "--experiment-args", "batch-size:4", "image-size:128",
                 "dtype:bfloat16", "augment:device"],
    },
    "6": {
        "name": "resnet50_cifar10_leaf_krum_n8_f2",
        "note": "per-LAYER granularity at ResNet-50 scale (~160 leaves, "
                "bucketed by shape into O(#distinct sizes) collectives): "
                "the flagship per-layer story past toy models",
        "args": ["--experiment", "slim-resnet_v1_50-cifar10", "--aggregator", "krum",
                 "--nb-workers", "8", "--nb-decl-byz-workers", "2",
                 "--granularity", "leaf",
                 "--experiment-args", "batch-size:8", "dtype:bfloat16"],
    },
    "2t": {
        "name": "cnnet_krum_n8_f2_traced",
        "note": "config 2b sizing with a jax.profiler trace captured to "
                "benchmarks/trace_r03 — an up-window leaves an analyzable "
                "artifact behind for MFU cost attribution even without a "
                "live chip afterwards",
        "args": ["--experiment", "cnnet", "--aggregator", "krum",
                 "--nb-workers", "8", "--nb-decl-byz-workers", "2",
                 "--experiment-args", "batch-size:128", "dtype:bfloat16", "augment:device",
                 "--xprof", "2:5", "--trace-dir", "benchmarks/trace_r03"],
    },
    "6u": {
        "name": "resnet50_cifar10_leaf_krum_n8_f2_unrolled",
        "note": "config 6 with --leaf-bucketing off: the per-leaf loop "
                "(numerically equivalent results) — the bucketed-vs-unrolled A/B on "
                "whatever backend runs it (BENCHMARKS.md row 6b has the CPU "
                "side; on CPU the loop wins, the bucketed form is the "
                "TPU-shaped program)",
        "args": ["--experiment", "slim-resnet_v1_50-cifar10", "--aggregator", "krum",
                 "--nb-workers", "8", "--nb-decl-byz-workers", "2",
                 "--granularity", "leaf", "--leaf-bucketing", "off",
                 "--experiment-args", "batch-size:8", "dtype:bfloat16"],
    },
    "5f": {
        "name": "transformer_leaf_krum_n8_f2_single_chip",
        "note": "BASELINE config 5 (stretch) at single-chip scale: per-layer "
                "Krum on a real transformer via the FLAT engine's leaf path "
                "(8 vmapped workers on one chip, ~50 leaves bucketed by "
                "shape) — the per-layer-GAR-on-a-transformer capability "
                "measured without a pod; the dp x pp x tp version is "
                "benchmarks/sharded_transformer.py",
        "args": ["--experiment", "transformer",
                 "--experiment-args", "d-model:256", "heads:4", "layers:8",
                 "seq:256", "batch-size:8", "vocab:1024", "corpus:65536",
                 "--aggregator", "krum",
                 "--nb-workers", "8", "--nb-decl-byz-workers", "2",
                 "--granularity", "leaf"],
    },
    "4": {
        "name": "inception_v3_median_little_n32_f8",
        "note": "BASELINE config 4: coordinate-median under a real 'little' "
                "omniscient attack from 8 of 32 workers. Same ImageNet data "
                "policy as config 3 (synthetic stand-in = throughput only)",
        "args": ["--experiment", "slim-inception_v3-imagenet", "--aggregator", "median",
                 "--nb-workers", "32", "--nb-decl-byz-workers", "8",
                 "--nb-real-byz-workers", "8", "--attack", "little",
                 "--experiment-args", "batch-size:4", "image-size:128", "dtype:bfloat16"],
    },
}

_PERF_RE = re.compile(r"steps/s \(excl\. 1st\)\s+([0-9.]+)")


def run_config(key, steps, platform, timeout):
    cfg = CONFIGS[key]
    env = dict(os.environ)
    use_platform = cfg.get("platform", platform)
    summary_dir = tempfile.mkdtemp(prefix="aggregathor_bench_sum_%s_" % cfg["name"])
    try:
        return _run_config(cfg, steps, use_platform, timeout, env, summary_dir, key)
    finally:
        shutil.rmtree(summary_dir, ignore_errors=True)


def _run_config(cfg, steps, use_platform, timeout, env, summary_dir, key):
    cmd = [sys.executable, "-m", "aggregathor_tpu.cli.runner"] + cfg["args"] + [
        "--max-step", str(steps),
        "--evaluation-delta", "-1", "--evaluation-period", "-1",
        "--summary-dir", summary_dir, "--summary-delta", str(steps),
    ]
    if use_platform:
        cmd += ["--platform", use_platform]
        env["JAX_PLATFORMS"] = use_platform
    if use_platform == "cpu":
        env.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
        cmd += ["--nb-devices", "4" if key == "1" else "8"]
    # One process per chip: this parent is stdlib-only (it never imports
    # JAX), so the runner child is the only process that touches the device;
    # configs run one after another.  Keep it so.
    proc = subprocess.run(cmd, capture_output=True, text=True, timeout=timeout, cwd=REPO, env=env)
    out = proc.stdout + proc.stderr
    match = _PERF_RE.search(out)
    result = {
        "metric": "train_steps_per_s",
        "config": cfg["name"],
        "note": cfg["note"],
        "steps": steps,
        "platform": use_platform or "ambient",
        "value": float(match.group(1)) if match else None,
        "unit": "steps/s",
        "rc": proc.returncode,
        # Synthetic stand-in data = throughput-only row, no accuracy claim
        # (the runner warns loudly when a dataset is not on disk)
        "data": "synthetic" if "synthetic stand-in" in out else "real",
    }
    # final summary JSONL has the last total_loss
    try:
        events = []
        for path in glob.glob(os.path.join(summary_dir, "*")):
            events += [json.loads(line) for line in open(path)]
        if events:
            result["final_loss"] = events[-1].get("total_loss")
    except Exception:
        pass
    if proc.returncode != 0 and match is None:
        result["error"] = out.strip()[-500:]
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--configs", default="1,2,3,4")
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--platform", default=None, help="platform for non-CPU configs (default ambient)")
    ap.add_argument("--timeout", type=int, default=1200)
    ap.add_argument("--resume-file", default=None,
                    help="JSON path recording completed configs: a re-run "
                         "skips them (and reprints their rows) so a scarce "
                         "TPU up-window resumes instead of restarting the "
                         "whole sweep.")
    args = ap.parse_args()
    sys.path.insert(0, REPO)
    from aggregathor_tpu.utils.state import load_json, save_json_atomic

    resume = load_json(args.resume_file) if args.resume_file else {}
    for key in args.configs.split(","):
        key = key.strip()
        rkey = "%s|%d|%s" % (key, args.steps, args.platform or "ambient")
        result = resume.get(rkey)
        if result is not None and not result.get("error"):
            print(json.dumps(result), flush=True)
            continue
        # One hung config (e.g. a wedged accelerator) or a bad key must not
        # abort the sweep: every requested config gets exactly one JSON line.
        try:
            result = run_config(key, args.steps, args.platform, args.timeout)
        except KeyError:
            result = {"metric": "train_steps_per_s", "config": key, "value": None,
                      "error": "unknown config (have: %s)" % ",".join(sorted(CONFIGS))}
        except subprocess.TimeoutExpired:
            result = {"metric": "train_steps_per_s", "config": CONFIGS[key]["name"],
                      "value": None, "error": "timed out after %ds" % args.timeout}
        if args.resume_file and not result.get("error"):
            resume[rkey] = result
            save_json_atomic(args.resume_file, resume)
        print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
