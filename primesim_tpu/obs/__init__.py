"""Unified telemetry subsystem (DESIGN.md §15).

Three coupled layers, all host-side and all strictly read-only with
respect to the simulated machine (the device computation is untouched,
so `--obs off` is bit-exact by construction and `basic`/`full` only add
host bookkeeping at chunk boundaries the engines already cross):

- **Metric time-series** (`metrics.MetricStore`): a bounded ring buffer
  of per-chunk samples — counter DELTAS plus wall-clock phase timings —
  fed by the engine/fleet/stream chunk loops; dumpable as JSONL. The
  fused `Engine.run` commits ONE sample a job, after its results are on
  the host: the counters' and the stat rows' totals, its host spans'
  seconds (`span.span`, the one helper that opens them), the sizes the
  stat ratios divide by. It goes to the attached `Recorder`, else to
  `process_store()`.
- **Flight recorder** (`trace.TraceWriter`): Chrome trace-event JSON
  (loads in Perfetto / chrome://tracing) with B/E spans for sim chunks,
  instant events for supervisor decisions (checkpoint, retry, preempt,
  guard, chaos) and serve scheduler events (admit, dispatch, retire,
  per-job checkpoint, journal fsync) — one correlated timeline across
  engine, supervisor, and daemon.
- **Serve metrics surface** (`prom.render_prometheus`): Prometheus
  text exposition over the scheduler's live stats (queue depth, jobs by
  state, per-bucket occupancy, latency histogram, journal fsync
  latency, throughput) — the `metrics` protocol verb and
  `serve-status --watch` render the same numbers.

`Recorder` is the facade the CLI wires in: one per run, levels
`off|basic|full` (off = no Recorder at all — engines carry a plain
`obs = None` attribute and the chunked loops skip every telemetry
branch).
"""

from .metrics import Histogram, MetricStore, process_store
from .prom import render_prometheus
from .recorder import LEVELS, Recorder
from .trace import TraceWriter

__all__ = [
    "Histogram",
    "LEVELS",
    "MetricStore",
    "Recorder",
    "TraceWriter",
    "process_store",
    "render_prometheus",
]
