"""Registry + docs discipline rules (the former tools/check_metrics.py).

These port the metric-name lints that have gated every PR since PR 1
into the analysis framework, as first-class rules with per-line
suppressions and corpus pins. ``tools/check_metrics.py`` remains as a
thin CLI shim over this module so existing invocations (and
tests/test_obs.py's ``check()``/``scan_source()`` contract) keep
working.

Rules:

- **metric-name** — every ``reg.counter("x")`` / ``.gauge`` /
  ``.histogram`` literal must be declared in ``obs.registry.METRICS``
  with the matching type (a typo forks a time series silently in looser
  systems; here the runtime raises, but only when the code path runs).
- **span-stage** — every ``span("x")`` literal must appear in
  ``PIPELINE_STAGES`` (span names become bounded ``stage`` label
  values).
- **metric-registry** — registry-level hygiene: no unused declarations,
  counters end in ``_total`` (and nothing else does), histogram
  generated series (``_bucket``/``_sum``/``_count``) collide with no
  declared family.
- **docs-observability** — every declared family and every span/dump
  schema field is documented in docs/observability.md.
- **docs-subsystem** — the two-home rule: each subsystem's families and
  operator surfaces (flags, endpoints, wire magics, class names) must
  appear in the doc that owns their semantics (resilience, device,
  object, cache, fleet, datapath, mesh, panel, wire, LRC).
- **docs-catalog** — docs/static-analysis.md's rule catalog matches the
  registered rule set, both directions.
"""

from __future__ import annotations

import ast
import re

from noise_ec_tpu.analysis.core import (
    Finding,
    Project,
    call_name,
    const_str,
    rule,
)

__all__ = [
    "scan_metric_calls",
    "scan_span_calls",
    "SUBSYSTEM_DOCS",
]

_METRIC_FACTORIES = ("counter", "gauge", "histogram")


def scan_metric_calls(project: Project) -> dict[str, list]:
    """name -> [(rel path, line, requested type), ...] across sources."""
    used: dict[str, list] = {}
    for sf in project.files:
        for node in ast.walk(sf.tree):
            if not isinstance(node, ast.Call):
                continue
            mtype = call_name(node)
            if mtype not in _METRIC_FACTORIES or not node.args:
                continue
            if not isinstance(node.func, ast.Attribute):
                continue
            name = const_str(node.args[0])
            if name is not None:
                used.setdefault(name, []).append((sf.rel, node.lineno, mtype))
    return used


def scan_span_calls(project: Project) -> dict[str, list]:
    """span stage literal -> [(rel path, line), ...]. Only bare
    ``span("x")`` calls count — method spans (``tracer.span``) are the
    tracer's own API, the bare name is the package-wide helper."""
    used: dict[str, list] = {}
    for sf in project.files:
        for node in ast.walk(sf.tree):
            if not isinstance(node, ast.Call):
                continue
            if not (isinstance(node.func, ast.Name)
                    and node.func.id == "span"):
                continue
            if not node.args:
                continue
            name = const_str(node.args[0])
            if name is not None:
                used.setdefault(name, []).append((sf.rel, node.lineno))
    return used


def _registry_line(project: Project, name: str) -> tuple[str, int]:
    """Anchor a registry-level finding at the declaration line."""
    rel = "noise_ec_tpu/obs/registry.py"
    for sf in project.files:
        if sf.rel == rel:
            for i, line in enumerate(sf.lines, start=1):
                if f'"{name}"' in line:
                    return rel, i
    return rel, 1


@rule(
    "metric-name",
    scope="project",
    invariant="every metric name used in source is declared in "
              "obs.registry.METRICS with the matching type",
    motivation="PR 1 (declared-name registry; a typo forks a series "
               "silently in looser systems)",
)
def check_metric_names(project: Project):
    metrics = project.metrics
    for name, sites in sorted(scan_metric_calls(project).items()):
        decl = metrics.get(name)
        for rel, line, mtype in sites:
            if decl is None:
                yield Finding(
                    "metric-name", rel, line,
                    f"undeclared metric {name!r} (used as {mtype}); "
                    "declare it in noise_ec_tpu/obs/registry.py METRICS",
                )
            elif mtype != decl[0]:
                yield Finding(
                    "metric-name", rel, line,
                    f"metric {name!r} declared {decl[0]} but requested "
                    f"as {mtype}",
                )


@rule(
    "span-stage",
    scope="project",
    invariant="every span(\"x\") literal appears in "
              "obs.registry.PIPELINE_STAGES",
    motivation="PR 1/PR 2 (span names become 'stage' label values; the "
               "label set stays bounded only if the tuple is the single "
               "source of truth)",
)
def check_span_stages(project: Project):
    stages = project.pipeline_stages
    for stage, sites in sorted(scan_span_calls(project).items()):
        if stage in stages:
            continue
        for rel, line in sites:
            yield Finding(
                "span-stage", rel, line,
                f"span stage {stage!r} is not declared in "
                "obs.registry.PIPELINE_STAGES",
            )


@rule(
    "metric-registry",
    scope="project",
    invariant="no unused declarations; counters end in _total (nothing "
              "else does); histogram suffixes collide with no family",
    motivation="PR 1/PR 2 (dead registry entries rot the docs; "
               "Prometheus conventions; generated-series aliasing)",
)
def check_metric_registry(project: Project):
    metrics = project.metrics
    used = scan_metric_calls(project)
    for name in metrics:
        if name not in used:
            rel, line = _registry_line(project, name)
            yield Finding(
                "metric-registry", rel, line,
                f"declared metric {name!r} has no call site; remove it "
                "from METRICS or wire it up",
            )
    names = set(metrics)
    for name, (mtype, _, _) in metrics.items():
        rel, line = _registry_line(project, name)
        if mtype == "histogram":
            for g in (f"{name}_bucket", f"{name}_sum", f"{name}_count"):
                if g in names:
                    yield Finding(
                        "metric-registry", rel, line,
                        f"histogram {name!r} generates {g!r}, which is "
                        "also declared as its own metric",
                    )
        if mtype == "counter" and not name.endswith("_total"):
            yield Finding(
                "metric-registry", rel, line,
                f"counter {name!r} must end in '_total' (Prometheus "
                "convention)",
            )
        if mtype != "counter" and name.endswith("_total"):
            yield Finding(
                "metric-registry", rel, line,
                f"{mtype} {name!r} must not end in '_total'",
            )


@rule(
    "docs-observability",
    scope="project",
    invariant="every registry family and every span/dump schema field "
              "is documented in docs/observability.md",
    motivation="PR 3 (an undocumented series is invisible to the "
               "operator the docs' metric table exists for)",
)
def check_docs_observability(project: Project):
    doc = "docs/observability.md"
    text = project.doc_text(doc)
    if text is None:
        yield Finding("docs-observability", doc, 1, f"docs file {doc} missing")
        return
    for name in project.metrics:
        if not re.search(rf"\b{re.escape(name)}\b", text):
            yield Finding(
                "docs-observability", doc, 1,
                f"metric {name!r} is not documented in {doc} "
                "(registry table)",
            )
    try:
        from noise_ec_tpu.obs.server import SPANS_DOC_FIELDS
        from noise_ec_tpu.obs.trace import SPAN_FIELDS
    except Exception:  # pragma: no cover — synthetic projects
        return
    for field in SPAN_FIELDS:
        if f"`{field}`" not in text:
            yield Finding(
                "docs-observability", doc, 1,
                f"span field {field!r} (obs.trace.SPAN_FIELDS) is not "
                f"documented in {doc}",
            )
    for field in SPANS_DOC_FIELDS:
        if f"`{field}`" not in text:
            yield Finding(
                "docs-observability", doc, 1,
                f"/spans document key {field!r} "
                f"(obs.server.SPANS_DOC_FIELDS) is not documented in {doc}",
            )


# ------------------------------------------------------- subsystem parity

# The two-home rule, one row per subsystem: (doc path, metric-name
# prefixes that must ALSO appear there, exact extra family names, and
# the operator surfaces — flags/endpoints/magics/identifiers — that
# exist only as strings in the code so the METRICS walk cannot see them
# drift). The tables match tools/check_metrics.py's historical checks.
SUBSYSTEM_DOCS: dict[str, dict] = {
    "resilience": {
        "doc": "docs/resilience.md",
        "prefixes": ("noise_ec_peer_", "noise_ec_reconnect_",
                     "noise_ec_nack_", "noise_ec_codec_"),
        "extras": ("noise_ec_store_announces_total",),
        "tokens": (),
    },
    "device": {
        "doc": "docs/observability.md",
        "prefixes": (),
        "extras": (),
        "tokens": ("/profile", "/xprof", "-xprof-dir", "-profile",
                   "tools/bench_gate.py", "cost_analysis",
                   "DEVICE_LATENCY_BUCKETS"),
    },
    "object": {
        "doc": "docs/object-service.md",
        "prefixes": ("noise_ec_object_",),
        "extras": (),
        "tokens": ("/objects", "-object-port", "-tenants", "Retry-After",
                   "noise-ec-manifest/1"),
    },
    "cache": {
        "doc": "docs/object-service.md",
        "prefixes": (),
        "extras": (),
        "tokens": ("Read path", "DecodedObjectCache", "noise-ec-warmset/1",
                   "submit_shared", "X-NoiseEC-Route", "-object-cache-mb",
                   "object_get_hot_mb_per_s", "object_get_hit_rate"),
    },
    "fleet": {
        "doc": "docs/fleet.md",
        "prefixes": ("noise_ec_fleet_", "noise_ec_backpressure_"),
        "extras": (),
        "tokens": ("-fleet-profile", "-fleet-size", "-fleet-report",
                   "/fleet", "churn@", "Retry-After", "slow@",
                   "noisy=", "hedge="),
    },
    "datapath": {
        "doc": "docs/design.md",
        "prefixes": ("noise_ec_coalesce_", "noise_ec_device_buffer_pool_"),
        "extras": (),
        "tokens": ("CoalescingDispatcher", "DeviceBufferPool",
                   "donate_argnums", "copy_to_host_async", "submit_many",
                   "submit_shared", "matmul_stripes_many"),
    },
    "mesh": {
        "doc": "docs/design.md",
        "prefixes": ("noise_ec_mesh_",),
        "extras": (),
        "tokens": ("MeshRouter", "configure_mesh_router", "shard_map",
                   "pjit", "in_shardings", "out_shardings"),
    },
    "panel": {
        "doc": "docs/design.md",
        "prefixes": ("noise_ec_kernel_tile_",
                     "noise_ec_kernel_sublaunch_"),
        "extras": ("noise_ec_compile_cache_hits_total",),
        "tokens": ("gf2_matmul_pallas_panel_rows", "panel_plan",
                   "split_bits_rows_panels", "pack_words_lanes_blocked",
                   "decode1_words_bytesliced", "PANEL_TEMP_ALIVE_FRACTION",
                   "pl.when", "PANEL_XOR_BUDGET",
                   "PANEL_SUBLAUNCH_XOR_BUDGET", "sublaunch_count",
                   "input_output_aliases", "default_compile_cache",
                   "prewarm_ladder"),
    },
    "wire": {
        "doc": "docs/design.md",
        "prefixes": ("noise_ec_wire_",),
        "extras": (),
        "tokens": ("recv_into", "sendmsg", "SO_REUSEPORT", "verify_batch",
                   "SHARD_BATCH", "-recv-shards", "_FrameRing",
                   "broadcast_many"),
    },
    "federation": {
        "doc": "docs/observability.md",
        "prefixes": ("noise_ec_federate_",),
        "extras": (),
        "tokens": ("/fleet/metrics", "-federate", "parse_prometheus",
                   "MetricsFederator", "GAUGE_POLICIES"),
    },
    "incident": {
        "doc": "docs/observability.md",
        "prefixes": ("noise_ec_incident_",),
        "extras": (),
        "tokens": ("/incident", "-incident-dir", "FlightRecorder",
                   "--incident", "min_bundle_interval"),
    },
    "tenant-attribution": {
        "doc": "docs/object-service.md",
        "prefixes": (),
        "extras": ("noise_ec_object_op_seconds",
                   "noise_ec_object_tenant_shed_total"),
        "tokens": ("Tenant attribution", "object_get_p99_ms",
                   "tenant_isolation_p99_ratio"),
    },
    "hedge-qos": {
        "doc": "docs/object-service.md",
        "prefixes": ("noise_ec_hedge_", "noise_ec_lane_"),
        "extras": ("noise_ec_peer_fetch_seconds",),
        "tokens": ("Hedged", "X-NoiseEC-Hedge", "hedge_extra",
                   "hedge_floor_seconds", "hedge_ceiling_seconds",
                   "lane=", "weight=", "background_floor",
                   "object_get_p99_hedged_ms"),
    },
    "request-tracing": {
        "doc": "docs/observability.md",
        "prefixes": ("noise_ec_trace_",),
        "extras": (),
        "tokens": ("Request tracing", "X-NoiseEC-Trace", "request_trace",
                   "trace_id=", "--op", "hold_max_bytes", "sample_n",
                   "trace_overhead_pct", "trace_keep_rate",
                   "span-coverage"),
    },
    "placement": {
        "doc": "docs/placement.md",
        "prefixes": ("noise_ec_placement_",),
        "extras": (),
        "tokens": ("Topology.parse", "-topology", "domains@",
                   "killdomain@", "PlacementRing", "TargetedDelivery",
                   "Rebalancer", "straw2", "placement_fanout_ratio",
                   "rebalance_amplification", "prev_stripes",
                   "SHARD_BATCH"),
    },
    "wide-events": {
        "doc": "docs/observability.md",
        "prefixes": ("noise_ec_events_", "noise_ec_event_"),
        "extras": (),
        "tokens": ("/events", "EventLog", "EVENT_NAMES",
                   "event-on-swallow", "event_log_overhead_pct",
                   "suppressed"),
    },
    "diagnosis": {
        "doc": "docs/observability.md",
        "prefixes": ("noise_ec_diagnose_",),
        "extras": (),
        "tokens": ("/diagnose", "DiagnosisEngine", "slow-peer",
                   "noisy-tenant", "tools/diagnose.py",
                   "diagnose_verdict_ms", "add_flip_listener"),
    },
    "lrc": {
        "doc": "docs/lrc.md",
        "prefixes": ("noise_ec_lrc_", "noise_ec_convert_"),
        "extras": ("noise_ec_store_repair_shards_read_total",),
        "tokens": ("LocalReconstructionCode", "ConversionEngine",
                   "ConversionPolicy", "lrc:K/G+R", "archive=", "lrc@",
                   "-convert-interval", "repair_fetch_amplification",
                   "convert_mb_per_s", "prev_stripes"),
    },
}


@rule(
    "docs-subsystem",
    scope="project",
    invariant="each subsystem's metric families and operator surfaces "
              "appear in the doc that owns their semantics (the "
              "two-home rule)",
    motivation="PR 2 onward (every subsystem doc owns the fault model / "
               "API its series instrument)",
)
def check_docs_subsystem(project: Project):
    metrics = project.metrics
    for sub, spec in SUBSYSTEM_DOCS.items():
        names = [n for n in metrics if n.startswith(spec["prefixes"])] \
            if spec["prefixes"] else []
        names += [n for n in spec["extras"] if n in metrics]
        if not names and not spec["tokens"]:
            continue
        text = project.doc_text(spec["doc"])
        if text is None:
            if names:
                yield Finding(
                    "docs-subsystem", spec["doc"], 1,
                    f"docs file {spec['doc']} missing "
                    f"({sub} metrics exist)",
                )
            continue
        for n in names:
            if not re.search(rf"\b{re.escape(n)}\b", text):
                yield Finding(
                    "docs-subsystem", spec["doc"], 1,
                    f"{sub} metric {n!r} is not documented in "
                    f"{spec['doc']}",
                )
        for tok in spec["tokens"]:
            if tok not in text:
                yield Finding(
                    "docs-subsystem", spec["doc"], 1,
                    f"{sub} surface {tok} is not documented in "
                    f"{spec['doc']}",
                )


@rule(
    "docs-catalog",
    scope="project",
    invariant="docs/static-analysis.md's rule catalog matches the "
              "registered rule set, both directions",
    motivation="this PR (an analyzer whose rules drift from its catalog "
               "repeats the docs-drift failure mode it exists to catch)",
)
def check_docs_catalog(project: Project):
    from noise_ec_tpu.analysis.core import all_rules

    doc = "docs/static-analysis.md"
    text = project.doc_text(doc)
    if text is None:
        yield Finding(
            "docs-catalog", doc, 1,
            f"docs file {doc} missing (the rule catalog lives there)",
        )
        return
    registered = set(all_rules())
    for rid in sorted(registered):
        if f"`{rid}`" not in text:
            yield Finding(
                "docs-catalog", doc, 1,
                f"rule {rid!r} is not documented in {doc} (catalog "
                "table)",
            )
    # Stale catalog rows: ids documented as rules but not registered.
    for m in re.finditer(r"^\|\s*`([a-z0-9-]+)`", text, re.MULTILINE):
        rid = m.group(1)
        if rid not in registered:
            yield Finding(
                "docs-catalog", doc, 1,
                f"catalog documents rule {rid!r}, which is not "
                "registered in the analysis framework",
            )
