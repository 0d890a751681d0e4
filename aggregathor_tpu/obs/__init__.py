"""Observability: cadenced side-duties of the training loop.

The reference runs evaluation / checkpointing / summaries as polling daemon
threads sharing the TF session (reference: runner.py:356-494, cadence knobs at
config.py:54-61).  A jitted SPMD step has no session to share — the idiomatic
translation is cadence *triggers* checked between steps on the host, firing
the same step-delta / wall-period policies, plus a final fire at shutdown.

- ``CadenceTrigger``  step-delta / wall-period firing policy
- ``Checkpoints``     step-indexed train-state snapshots, auto-restore latest
- ``EvalFile``        the reference's TSV evaluation log format
- ``SummaryWriter``   JSONL scalar event log (summary-file parity), every
  line stamped with the writer's ``run_id``
- ``PerfReport``      steps/s report, first (compilation) step excluded
- ``LatencyHistogram``  bounded-reservoir p50/p95/p99 tail latency (shared by
  ``PerfReport`` and the serving ``/metrics`` endpoint)

The telemetry pillars (docs/observability.md):

- ``trace``           host-side span tracer -> Chrome trace-event JSON
  (Perfetto-loadable); ``span(...)`` context manager/decorator, zero
  recompiles, near-zero cost disabled
- ``metrics``         process-wide counter/gauge/histogram registry with
  Prometheus text exposition (``MetricsRegistry``, default ``REGISTRY``)
- ``ForensicsLedger`` per-worker suspicion timeline -> Byzantine
  attribution report (schema ``aggregathor.obs.forensics.v1``)

The device-side layer (docs/observability.md "Device-side observability"):

- ``flight``          in-scan flight-recorder rings: per-step telemetry
  lanes written inside the jitted scan, fetched once per summary fire,
  dumped post-mortem (schema ``aggregathor.obs.flight.v1``)
- ``profiler``        step-windowed ``jax.profiler`` captures (``--xprof``),
  ``phase_table`` (the compiled step cut by its ``step.<phase>`` scopes),
  compile-cache-miss observability, device memory gauges
- ``live``            ``LiveExporter`` — the training run's own
  ``/metrics`` + ``/status`` HTTP endpoint
- ``slo``             regression sentinel: baseline documents (schema
  ``aggregathor.obs.slo.v1``) judged PASS/REGRESS at run end

The control room (docs/observability.md "The control room"):

- ``events``          causal run journal — typed, append-only JSONL
  decision events (schema ``aggregathor.obs.events.v2``): guardian
  rollbacks/escalations, deadline-window moves, stale infill, forgery
  verdicts, autoscale actions, weight swaps — ONE ``emit()`` API, every
  event type declared (graftcheck EV001 proves it statically) and every
  action event citing its cause (EV002)
- ``causal``          the causal plane — edge-respecting fleet journal
  merge + the postmortem audit (``cli.postmortem``; report schema
  ``aggregathor.obs.postmortem.v1``)
- ``fleet``           one-scrape federation — ``FleetCollector`` polls N
  child ``/metrics`` + ``/status`` endpoints and serves
  ``/fleet/metrics`` / ``/fleet/status`` / ``/fleet/journal`` from one
  port; a dead instance reads ``down`` with its last sample HELD

The causal plane (docs/observability.md "The causal plane"):

- ``causal``          the reader half of schema v2's ``cause`` edges —
  the edge-respecting deterministic fleet merge, the causal DAG audit
  and the ``aggregathor.obs.postmortem.v1`` checker behind
  ``cli.postmortem`` (exit code = verdict)
"""

from . import causal  # noqa: F401
from . import events  # noqa: F401
from . import flight  # noqa: F401
from . import live  # noqa: F401
from . import metrics  # noqa: F401
from . import profiler  # noqa: F401
from . import slo  # noqa: F401
from . import trace  # noqa: F401
from .cadence import CadenceTrigger  # noqa: F401
from .checkpoint import Checkpoints  # noqa: F401
from .evalfile import EvalFile  # noqa: F401
from .flight import FlightRecorder  # noqa: F401
from .forensics import ForensicsLedger  # noqa: F401
from .live import LiveExporter  # noqa: F401
from .summaries import SummaryWriter  # noqa: F401
from .perf import LatencyHistogram, PerfReport  # noqa: F401
