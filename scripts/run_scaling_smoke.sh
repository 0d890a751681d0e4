#!/usr/bin/env bash
# Large-n scaling smoke on CPU (<60 s), docs/gar_scaling.md: one n=64
# hierarchical-GAR training cell through the REAL CLI with the GAR cost
# probe on — then assert
#   1. the run finishes with a FINITE loss (every summary line),
#   2. the probe measured real work: gar_seconds_total > 0 on the metrics
#      registry (and the gar_probe_seconds gauge is populated).
# The n-sweep harness and its schema contract (gars/scaling.py) are held by
# tests/test_gar_scaling.py, not here.
set -euo pipefail
cd "$(dirname "$0")/.."

out="${1:-/tmp/aggregathor_scaling}"
rm -rf "$out"
mkdir -p "$out/sum"

# 1+2: the n=64 hier:outer=krum cell (8 groups of 8; krum feasible at
# (8, 2)) with --gar-probe wiring gar.aggregate spans into the registry.
JAX_PLATFORMS=cpu python -m aggregathor_tpu.cli.runner \
  --experiment mnist --experiment-args batch-size:8 \
  --aggregator "hier:g=8,inner=median,outer=krum" \
  --nb-workers 64 --nb-decl-byz-workers 2 \
  --max-step 12 --learning-rate-args initial-rate:0.05 --prefetch 0 \
  --evaluation-delta -1 --evaluation-period -1 \
  --summary-dir "$out/sum" --summary-delta 4 \
  --gar-probe --metrics-file "$out/train.prom"

python - "$out" <<'EOF'
import json, math, os, sys

out = sys.argv[1]

# ---- finite loss on every summary fire -------------------------------- #
sum_dir = os.path.join(out, "sum")
lines = [json.loads(line)
         for name in os.listdir(sum_dir)
         for line in open(os.path.join(sum_dir, name))]
losses = [line["total_loss"] for line in lines if "total_loss" in line]
assert losses, "no summary lines with total_loss"
assert all(math.isfinite(v) for v in losses), losses
print("loss OK: %d summary fires, final %.4f" % (len(losses), losses[-1]))

# ---- the probe measured real GAR work --------------------------------- #
from aggregathor_tpu.obs.metrics import parse_prometheus

parsed = parse_prometheus(open(os.path.join(out, "train.prom")).read())
total = dict((n, v) for n, l, v in parsed["gar_seconds_total"]["samples"])
assert total["gar_seconds_total"] > 0.0, total
gauge = dict((n, v) for n, l, v in parsed["gar_probe_seconds"]["samples"])
assert gauge["gar_probe_seconds"] > 0.0, gauge
gar_fires = [line["gar_seconds"] for line in lines if "gar_seconds" in line]
assert gar_fires and all(v > 0 for v in gar_fires), gar_fires
print("gar probe OK: %d fires, %.3f s cumulative (last %.3f s)"
      % (len(gar_fires), total["gar_seconds_total"], gauge["gar_probe_seconds"]))
EOF

echo "scaling smoke OK: $out"
