"""Observability: spans, histograms, labeled metrics, Prometheus export.

The reference's only observability is glog lines (SURVEY.md §5); this
package is the layer the ROADMAP's production north star needs — the
answer to "where does a shard spend its time and which peer is degrading"
has to come from structured telemetry, not log archaeology:

- :mod:`obs.metrics` — counters, fixed-bucket histograms with
  p50/p90/p99 extraction, timers (absorbs ``utils.metrics``);
- :mod:`obs.registry` — the labeled metric-family registry plus the
  declarative metric-name registry (``METRICS``) every exported series
  must appear in (``tools/check_metrics.py`` enforces it);
- :mod:`obs.trace` — the in-process span tracer: ``span("decode",
  key=...)`` records per-stage timings keyed by message/stream identity
  into a ring buffer, with a dump API;
- :mod:`obs.profiling` — per-kernel throughput counters and the XLA
  trace hook (absorbs ``utils.profiling``);
- :mod:`obs.export` — Prometheus text-format exposition;
- :mod:`obs.server` — the optional stdlib-``http.server`` stats
  endpoint and the periodic reporter thread the CLI flags drive;
- :mod:`obs.collector` — distributed trace collection: pull peer
  ``/spans`` dumps, align clocks, merge spans into fleet-wide traces;
- :mod:`obs.perfetto` — Chrome trace-event (Perfetto) export of merged
  traces;
- :mod:`obs.health` — end-to-end outcome recording and the rolling SLO
  evaluator whose verdict drives ``/healthz``;
- :mod:`obs.device` — device telemetry: per-dispatch latency with a
  compile/execute split read from JAX's own compile events, recompile
  counters, program cost (cost_analysis FLOPs/bytes) and HBM gauges;
- :mod:`obs.sampler` — the always-on ~50 Hz folded-stack sampling
  profiler behind ``GET /profile``;
- :mod:`obs.events` — the wide structured-event log: every
  load-bearing decision (demotion, shed, hedge, breaker flip) as one
  trace-correlated record behind ``GET /events``;
- :mod:`obs.diagnose` — the rule-table diagnosis engine that joins
  events, registry deltas and kept traces into ranked cause verdicts
  behind ``GET /diagnose``.

``utils.metrics`` / ``utils.profiling`` remain as compatible re-export
shims, so existing imports keep working.
"""

from noise_ec_tpu.obs.collector import TraceCollector
from noise_ec_tpu.obs.diagnose import DiagnosisEngine
from noise_ec_tpu.obs.events import EventLog, default_event_log, event
from noise_ec_tpu.obs.device import (
    analyze_program,
    device_op,
    hbm_snapshot,
    peak_hbm_gbps,
    roofline_summary,
)
from noise_ec_tpu.obs.health import SLOEvaluator, default_slo, record_e2e
from noise_ec_tpu.obs.metrics import Counters, Histogram, Timer
from noise_ec_tpu.obs.perfetto import to_chrome_trace, write_chrome_trace
from noise_ec_tpu.obs.registry import (
    METRICS,
    PIPELINE_STAGES,
    Registry,
    default_registry,
    set_build_info,
)
from noise_ec_tpu.obs.sampler import StackSampler, default_sampler
from noise_ec_tpu.obs.trace import Tracer, default_tracer, node_attrs, span

__all__ = [
    "Counters",
    "DiagnosisEngine",
    "EventLog",
    "Histogram",
    "METRICS",
    "PIPELINE_STAGES",
    "Registry",
    "SLOEvaluator",
    "StackSampler",
    "Timer",
    "TraceCollector",
    "Tracer",
    "analyze_program",
    "default_event_log",
    "default_registry",
    "default_sampler",
    "default_slo",
    "default_tracer",
    "device_op",
    "event",
    "hbm_snapshot",
    "node_attrs",
    "peak_hbm_gbps",
    "record_e2e",
    "roofline_summary",
    "set_build_info",
    "span",
    "to_chrome_trace",
    "write_chrome_trace",
]
