"""MFU probe: robust training in a COMPUTE-DENSE configuration.

The BASELINE configs cannot demonstrate high MFU on one chip — measured
r4 envelopes (XLA cost analysis, BENCHMARKS.md):

- config 2 (cnnet 32px): arithmetic intensity ~8 FLOP/byte — the model
  itself is HBM-bound at ~3% of bf16 peak;
- config 3 (n=32 x ResNet-50): the GAR's n*d gradient traffic (32 x
  25.6M params, several passes) is 311 GB/step against 1.06e12 FLOPs
  (intensity 3.4) — robust aggregation's data movement is
  batch-INDEPENDENT, so at batch 4/worker it dwarfs the conv FLOPs.

Conv FLOPs scale with batch while gradient traffic does not, so MFU is
maximized by fewer workers x bigger per-worker batch x bigger images.
This probe measures exactly that shape: ResNet-50 at 224 px, n=8
Multi-Krum (f=2), batch 16/worker, bfloat16 compute, device-sampled
input (the r4 input path: the dataset lives on-chip), scanned steps.
It is labeled what it is — an MFU demonstration of the robust engine,
not a BASELINE row — and prints one JSON line with steps/s, the cost
model's FLOPs/bytes, mfu_pct, and pct_of_hbm_roofline.

Usage::

    python benchmarks/mfu_probe.py [--platform tpu] [--steps 30]
        [--batch 16] [--image-size 224] [--workers 8] [--unroll 10]
"""

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from aggregathor_tpu.utils.hw import peaks  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--platform", default=None)
    ap.add_argument("--steps", type=int, default=30, help="timed steps")
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--image-size", type=int, default=224)
    ap.add_argument("--workers", type=int, default=8)
    ap.add_argument("--byz", type=int, default=2)
    ap.add_argument("--unroll", type=int, default=10)
    args = ap.parse_args()

    if args.platform:
        os.environ["JAX_PLATFORMS"] = args.platform
    import jax

    if args.platform:
        jax.config.update("jax_platforms", args.platform)

    row = _measure(args, args.batch)
    failed = bool(row.get("error"))
    if _looks_oom(row.get("error")):
        # One retry at half batch: an HBM miss must not waste the scarce
        # up-window.  BOTH rows are printed — the retry labeled by its own
        # batch_size_per_worker + oom_at_batch — and on a successful retry
        # the full-batch row's error is demoted to a non-error ``oom``
        # field so the watcher retires the stage on the half-batch datum
        # (a full-batch re-attempt would just re-OOM) while the record
        # still shows what was tried.
        retry = _measure(args, max(1, args.batch // 2))
        retry["oom_at_batch"] = args.batch
        if not retry.get("error"):
            row["oom"] = row.pop("error")
            failed = False
        print(json.dumps(row), flush=True)
        row = retry
    print(json.dumps(row), flush=True)
    sys.exit(1 if failed or row.get("error") else 0)


def _looks_oom(error):
    text = (error or "").lower()
    return "resource_exhausted" in text or "out of memory" in text


def _measure(args, batch):
    import jax
    import numpy as np
    import optax

    from aggregathor_tpu import gars, models
    from aggregathor_tpu.parallel.engine import RobustEngine
    from aggregathor_tpu.parallel.mesh import make_mesh

    row = {
        "metric": "mfu_probe_resnet50_krum",
        "platform": "uninitialized",
        "workers": args.workers, "byz": args.byz,
        "batch_size_per_worker": batch,
        "image_size": args.image_size,
        "unroll": args.unroll,
        "unit": "steps/s",
    }
    platform = None
    try:
        # inside the try: the contract is ONE JSON line, errors included
        platform = row["platform"] = jax.devices()[0].platform
        exp = models.instantiate(
            "slim-resnet_v1_50-imagenet",
            ["batch-size:%d" % batch, "image-size:%d" % args.image_size,
             "dtype:bfloat16", "augment:device",
             "eval-batch-size:%d" % batch],
        )
        gar = gars.instantiate("krum", args.workers, args.byz)
        mesh = make_mesh(nb_workers=1, devices=jax.devices()[:1])
        engine = RobustEngine(mesh, gar, args.workers,
                              batch_transform=exp.device_transform())
        tx = optax.sgd(1e-2)
        state = engine.init_state(exp.init(jax.random.PRNGKey(0)), tx)

        # cost model on the single-step program (scan bodies are counted
        # once regardless of trip count — bench.py's convention)
        it = exp.make_train_iterator(args.workers, seed=0)
        resident = engine.shard_batch(next(it))
        step = engine.build_step(exp.loss, tx)
        try:
            cost = step.lower(state, resident).cost_analysis()
            if isinstance(cost, (list, tuple)):
                cost = cost[0]
            row["flops_per_step"] = float(cost["flops"])
            row["bytes_per_step"] = float(cost.get("bytes accessed", 0.0) or 0.0)
        except Exception:
            pass

        multi = engine.build_sampled_multi_step(
            exp.loss, tx, repeat_steps=args.unroll, batch_size=batch)
        data = engine.replicate(exp.train_arrays())

        def sync(m):
            return float(np.asarray(m["total_loss"]).reshape(-1)[-1])

        t0 = time.perf_counter()
        state, m = multi(state, data)  # compile + first chunk (excluded)
        sync(m)
        row["first_dispatch_s"] = round(time.perf_counter() - t0, 2)
        n_dispatch = max(1, args.steps // args.unroll)
        t1 = time.perf_counter()
        for _ in range(n_dispatch):
            state, m = multi(state, data)
        final_loss = sync(m)  # host fetch: the timing fence
        rate = n_dispatch * args.unroll / (time.perf_counter() - t1)
        row["value"] = round(rate, 3)
        row["timed_steps"] = n_dispatch * args.unroll
        row["final_loss"] = final_loss
        if row.get("flops_per_step") and platform == "tpu":
            chip = peaks(jax.devices()[0])  # by device_kind; unknown raises
            row["device_kind"] = jax.devices()[0].device_kind
            row["mfu_pct"] = round(
                100.0 * row["flops_per_step"] * rate / chip.bf16_flops, 2)
            if row.get("bytes_per_step"):
                row["pct_of_hbm_roofline"] = round(
                    100.0 * row["bytes_per_step"] * rate / chip.hbm_bytes_per_s, 1)
    except Exception as exc:
        row["error"] = "%s: %s" % (type(exc).__name__, str(exc)[:300])
    return row


if __name__ == "__main__":
    main()
