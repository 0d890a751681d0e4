#!/usr/bin/env bash
# Static-analysis gate: ruff (lint + import sort) + the four graftcheck
# checkers, with a machine-readable report (docs/analysis.md).
#
#   scripts/run_analysis.sh            # run everything, report, exit status
#   scripts/run_analysis.sh --check    # explicit gate mode (same exit
#                                      # contract, named for pre-commit use)
#   REPORT=path.json scripts/run_analysis.sh   # choose the report path
#
# Exit nonzero on: any unbaselined graftcheck finding, any stale or
# unjustified baseline entry, any ruff violation (when ruff is present —
# the container this repo grows in does not ship it, so its absence is a
# SKIP, never a pass-by-crash; config lives in pyproject.toml).
# Budget: < 30 s CPU (measured ~20 s on the 1-core CI box, dominated by
# the GAR contract probes).

set -euo pipefail
cd "$(dirname "$0")/.."

REPORT="${REPORT:-$(mktemp /tmp/graftcheck_report.XXXXXX.json)}"
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

echo "== ruff (lint + import sort; pyproject.toml [tool.ruff]) =="
if command -v ruff >/dev/null 2>&1; then
    ruff check aggregathor_tpu tests benchmarks scripts chip_smoke.py
elif python -m ruff --version >/dev/null 2>&1; then
    python -m ruff check aggregathor_tpu tests benchmarks scripts chip_smoke.py
else
    echo "ruff not installed in this environment: SKIPPED" \
         "(pip install -e '.[lint]' to enable)"
fi

echo "== graftcheck: retrace + prng + concurrency + gar-contract + events =="
python -m aggregathor_tpu.analysis --check --json "$REPORT"

echo "== report schema round-trip (aggregathor.analysis.report.v1) =="
python - "$REPORT" <<'PYEOF'
import json, sys

from aggregathor_tpu.analysis.report import validate_report

doc = validate_report(json.load(open(sys.argv[1])))
print("report ok: %s — %d finding(s), clean=%s -> %s"
      % (doc["schema"], doc["counts"]["total"], doc["clean"], sys.argv[1]))
PYEOF
