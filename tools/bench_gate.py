#!/usr/bin/env python
"""Bench regression gate: diff a fresh bench run against the recorded
trajectory and fail on regressions past per-metric tolerance.

klauspost/reedsolomon ships per-geometry throughput benchmarks as its
regression oracle; this repo records the same trajectory as
``BENCH_r*.json`` (per-round stats) next to ``BASELINE.json`` (the
north-star bar) — but until this tool nothing *noticed* when
``rs200_56_encode_gbps`` (the weakest geometry) slid. The gate:

- knows each metric's **direction** from its name (``*_gbps`` /
  ``*_per_s`` are higher-better; ``*_ms`` / ``*_s`` are lower-better;
  identity/meta keys are skipped);
- applies a **per-metric tolerance**: 10% for device-kernel throughput
  (slope-timed, stable round over round), 35% for host-path stats (the
  single-core box has documented 10-40% load tails — BASELINE.md).
  ``host_node_large_object_device_mb_per_s`` (the device tier of the
  node-to-node large-object stream) rides the tight 10% device
  tolerance although its ``host_node_`` prefix would grant the
  load-tail one: it once slid 9.3 -> 4.1 -> 3.1 MB/s while skipped;
- checks the headline against the ``BASELINE.json`` north star
  (``vs_baseline >= 1``) when a headline line is present;
- on fresh runs, flags ``batch_mesh_devices`` regressing back to 1 when
  the recorded ``MULTICHIP_r*.json`` rounds prove the rig runs an
  N-device mesh (:func:`mesh_rig_check` — the ISSUE-9 guard; the
  ``batch_mesh_*`` sweep keys themselves ride the tight device
  tolerance, the host-staged ``mesh_*`` stats the load-tail one);
- on fresh runs, holds the tiered read path to its bars
  (:func:`cache_hot_check` — the ISSUE-12 guard: hot cached GETs >= 10x
  the degraded decode path at >= 90% hit rate);
- on fresh runs, holds the LRC tier to its fetch-amplification bar
  (:func:`lrc_repair_check` — the ISSUE-13 guard: a single-loss heal on
  LRC reads >= 5x fewer shards than equal-overhead RS, i.e.
  ``repair_fetch_amplification`` <= 0.2);
- on fresh runs from a rig with a MULTICHIP record, holds the panel
  tier to the ROADMAP item-1 bars (:func:`panel_rig_check` — the
  ISSUE-15 guard: ``rs200_56_encode_gbps`` >= 150 through the K-grid
  sub-launch panel pipeline, ``gf65536_vs_gf256_decode_ratio`` <= 1.25,
  and ``rs200_56_route`` must not regress off ``panel`` — a silent
  probe demotion to the MXU is exactly the 38.4 GB/s cliff the split
  path exists to close).

Modes:

- default: run ``python bench.py`` fresh, parse its stats, diff against
  the newest recorded ``BENCH_r*.json``; exit 1 on regression;
- ``--current FILE`` / ``--against FILE``: diff recorded stats files
  instead of running (FILE is either a raw stats dict or a BENCH_r
  document with a ``parsed`` key);
- ``--check``: self-test replaying the recorded ``BENCH_r*.json``
  series — verifies the two newest rounds' deltas pass, a synthetic 20%
  throughput regression (and a 20% latency inflation) is flagged, and
  direction parsing is sane. ``--repo DIR`` reads the records from DIR
  (tier-1 replays the synthetic series in tests/data/bench_gate/).
"""

from __future__ import annotations

import argparse
import json
import re
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

# Keys that are identity/config, not performance.
SKIP_KEYS = {
    "backend", "kernel", "data_bytes", "tpu_smoke", "batch_mesh_devices",
    "store_repair_stripes_per_batch", "encode_s",
}
# encode_s is the headline's raw timing — the headline gbps already
# carries it with the proper direction and the north-star check.

# The device tier of the 64 MiB node-to-node stream: gated at the tight
# device tolerance despite its host_ prefix (metric_tolerance).
LARGE_OBJECT_DEVICE_KEY = "host_node_large_object_device_mb_per_s"

HIGHER_BETTER_SUFFIXES = ("_gbps", "_mb_per_s", "_msgs_per_s", "_per_s")
# "_ratio" keys are cost ratios (e.g. gf65536_vs_gf256_decode_ratio:
# wide-field decode time over gf256 decode time at equal data volume):
# gated DOWNWARD-ONLY — an increase past tolerance regresses, a decrease
# is the improvement the panel/packed-layout work exists to buy. They
# ride the tight device tolerance (both sides are slope-timed kernels;
# the wide-geometry sweep keys rs100_30_encode_gbps /
# rs200_56_decode_corrupt_p50_ms get device tolerance from their
# suffixes the same way).
LOWER_BETTER_SUFFIXES = ("_ms", "_s", "_ratio", "_amplification")
# "_amplification" keys are read-cost ratios like "_ratio"
# (repair_fetch_amplification: LRC shards read per heal over RS shards
# read per heal — docs/lrc.md): lower is the whole point, and a rise
# past tolerance means single-loss repair stopped being local.

DEFAULT_TOLERANCE = 0.10
# Host-path stats ride a single shared core with measured 10-40% load
# tails; a tight gate there would cry wolf every round.
HOST_TOLERANCE = 0.35
# "mesh_" covers the host-STAGED mesh stats (mesh_repair_gbps,
# mesh_decode_corrupt_p50_ms: payloads cross the host boundary per
# call, so load tails apply); the device-resident sweep keys are
# "batch_mesh_*" and deliberately do NOT match — they ride the tight
# device tolerance like every other slope-timed kernel stat.
HOST_PREFIXES = (
    "host_node_", "decode_corrupt_", "cpu_shim_", "partition_recovery_",
    "store_repair_", "object_", "fleet_", "mesh_", "wire_",
    # Redundant with "object_" but explicit: the hot-read cache stat is
    # a host-path number (RAM-tier serve through the Python service
    # layer) and must never accidentally land under device tolerance.
    "object_get_hot",
    # Conversion throughput crosses the Python service layer per stripe
    # (gather + manifest swap), so load tails apply. NOTE:
    # repair_fetch_amplification deliberately does NOT ride a host
    # prefix — it is an exact shard count ratio, deterministic round
    # over round, and gets the tight device tolerance.
    "convert_",
    # tenant_isolation_p99_ratio is a noisy-neighbor contention ratio
    # measured through the Python service layer under a live talker
    # thread — the noisiest stat in the file; host tolerance, and its
    # "_ratio" suffix already flips it to lower-better.
    "tenant_",
    # Placement-ring fleet stats (targeted-delivery fanout, rebalance
    # amplification) run a whole in-process fleet through the Python
    # service layer — host tolerance; their "_ratio"/"_amplification"
    # suffixes flip them to lower-better.
    "placement_",
)

# The ISSUE-12 hot-read acceptance bars (cache_hot_check, fresh runs):
# the cache tier must serve hot GETs >= 10x the degraded decode path at
# >= 90% hit rate under the zipfian mix — below either bar the cache is
# not amortizing and the read path regressed to codec speed.
CACHE_HOT_FACTOR = 10.0
CACHE_HOT_HIT_RATE = 0.90

# The ISSUE-13 LRC acceptance bar (lrc_repair_check, fresh runs): a
# single-loss heal on the LRC tier must read >= 5x fewer shards than
# the equal-overhead RS geometry — repair_fetch_amplification (LRC
# reads per heal / RS reads per heal, docs/lrc.md) <= 0.2. Above it the
# local-repair tier is not engaging and repair cost regressed to k.
LRC_FETCH_AMPLIFICATION_MAX = 0.2

# The ISSUE-11 wire hot-loop rig bars (ROADMAP transport item): applied
# by wire_rig_check on fresh runs once the recorded MULTICHIP rounds
# prove a real rig — the next MULTICHIP round is where the loop must
# prove ≥ 50k msgs/s and a roundtrip MB/s within 4x of the large-object
# host path. (Dev boxes without a MULTICHIP record are exempt: the
# pure-Python Ed25519 fallback caps them far below the bar.)
WIRE_RIG_MSGS_PER_S = 50_000.0
WIRE_RIG_MBPS_FACTOR = 4.0

# The ISSUE-15 panel-tier rig bars (panel_rig_check, fresh runs on rigs
# with a MULTICHIP record): the unconfirmed PR-10 bars from ROADMAP
# item 1, now owned by the K-grid sub-launch pipeline — RS(200,56) must
# encode >= 150 GB/s through the panel route (it sat at 38.4 on the MXU
# demotion at r05) and wide-field decode must stay within 1.25x of
# GF(2^8) at equal volume. Dev boxes without a MULTICHIP record are
# exempt (interpret-mode panel routing is deliberately narrower).
PANEL_RIG_RS200_GBPS = 150.0
PANEL_RIG_DECODE_RATIO_MAX = 1.25

# The ISSUE-17 placement acceptance bar (placement_rig_check, fresh
# runs): targeted delivery must keep per-message data-shard wire sends
# within 1.5x of the n-shard ideal — above it the ring is leaking
# broadcast traffic and the peers-to-n fanout cut is not real
# (docs/placement.md).
PLACEMENT_FANOUT_RATIO_MAX = 1.5

# The ISSUE-18 tracing acceptance bar (trace_overhead_check, fresh
# runs): hot cached GETs with the tail sampler ARMED must run within 3%
# of the same mix with tracing disabled — above it request tracing is
# taxing the clean path it exists to observe
# (docs/observability.md "Request tracing"). The keep-rate bar holds
# tail sampling honest: clean-path traces sample 1-in-sample_n (5% at
# the default 20), so a keep rate past 25% on the all-hot bench mix
# means the sampler is keeping traces it should drop.
TRACE_OVERHEAD_PCT_MAX = 3.0
TRACE_KEEP_RATE_MAX = 0.25

# The ISSUE-20 wide-event bar (event_overhead_check, fresh runs): the
# hot cached GET mix with the event log armed must run within 1% of
# the same mix with the log disabled. Events fire only at decision
# points, so the clean path crosses no emit at all — a measurable gap
# means an event call site leaked onto the per-request path
# (docs/observability.md "Wide events").
EVENT_OVERHEAD_PCT_MAX = 1.0

# ISSUE-19 acceptance bars for the hedged read tier and tenant QoS
# (docs/object-service.md "Read path"). The hedged-fleet bench runs a
# 120 ms straggler peer; with the hedge engine racing a spare source the
# fleet-tenant GET p99 lands ~250 ms (vs ~2 s unhedged, which stacks
# the straggler across both stripes of each read) — 600 ms is real
# headroom on a loaded CI box while still far below the unhedged tail.
# The isolation ratio (quiet-tenant p99 contended / solo, lower-better)
# rides power-of-2 buckets, so one-bucket jitter is a 2x swing; 4.0
# only trips when the noisy neighbor genuinely moves the quiet tail.
HEDGE_P99_MS_MAX = 600.0
TENANT_ISOLATION_RATIO_MAX = 4.0


def metric_direction(name: str) -> str | None:
    """'up' (higher better), 'down' (lower better), or None (skip)."""
    if name in SKIP_KEYS or name.endswith("_error"):
        return None
    if name.startswith(("device_", "hbm_")):
        return None  # telemetry describing the run, not the perf contract
    if name.endswith(HIGHER_BETTER_SUFFIXES):
        return "up"
    if name.endswith(LOWER_BETTER_SUFFIXES):
        return "down"
    return None


def metric_tolerance(name: str) -> float:
    if name == LARGE_OBJECT_DEVICE_KEY:
        # It slid 9.3 -> 4.1 -> 3.1 MB/s over three rounds while it was
        # skipped; the data path (pinned donated buffers, parity-only
        # fetch, double-buffered dispatch) is code, so the tight gate.
        return DEFAULT_TOLERANCE
    if name.startswith(HOST_PREFIXES):
        return HOST_TOLERANCE
    return DEFAULT_TOLERANCE


def compare(old: dict, new: dict) -> list[dict]:
    """Per-metric findings for every comparable metric present in both
    runs. ``regressed`` is True when the move exceeds tolerance in the
    bad direction."""
    findings = []
    for name in sorted(set(old) & set(new)):
        direction = metric_direction(name)
        if direction is None:
            continue
        try:
            a, b = float(old[name]), float(new[name])
        except (TypeError, ValueError):
            continue
        if a <= 0:
            continue
        delta = (b - a) / a
        bad = -delta if direction == "up" else delta
        findings.append({
            "metric": name,
            "old": a,
            "new": b,
            "delta_pct": round(delta * 100, 2),
            "direction": direction,
            "tolerance_pct": round(metric_tolerance(name) * 100, 1),
            "regressed": bad > metric_tolerance(name),
        })
    return findings


def newest_multichip_devices(repo: Path = REPO) -> int:
    """n_devices of the newest green MULTICHIP_r*.json round (0 = no
    recorded multichip capability)."""
    best = 0
    for path in sorted(repo.glob("MULTICHIP_r*.json")):
        try:
            doc = json.loads(path.read_text())
        except (OSError, ValueError):
            continue
        if doc.get("ok") and not doc.get("skipped"):
            best = int(doc.get("n_devices", 0))
    return best


def mesh_rig_check(stats: dict, repo: Path = REPO) -> list[str]:
    """Flag ``batch_mesh_devices`` regressing back to 1 on a rig whose
    recorded MULTICHIP rounds prove an N-device mesh runs there.

    This is the guard ISSUE 9 exists for: rounds r02–r05 shipped
    ``batch_mesh_devices: 1`` next to a green 8-device MULTICHIP file
    and nothing noticed. Applied to FRESH runs only (main() skips it
    for --current replays of recorded rounds, which genuinely carry the
    old value)."""
    rig = newest_multichip_devices(repo)
    if rig <= 1:
        return []
    devices = stats.get("batch_mesh_devices")
    try:
        devices = int(devices)
    except (TypeError, ValueError):
        devices = 0
    if devices > 1:
        return []
    return [
        f"batch_mesh_devices is {devices or 'missing'} but the recorded "
        f"MULTICHIP rounds show this rig runs a {rig}-device mesh — the "
        "mesh dispatch tier regressed to single-device"
    ]


def wire_rig_check(stats: dict, repo: Path = REPO) -> list[str]:
    """ISSUE-11 acceptance bars for the wire hot loop, on rigs only.

    Like :func:`mesh_rig_check`, this bites on FRESH runs when the
    recorded MULTICHIP rounds prove the box is a real rig (OpenSSL
    crypto, multiple cores): ``host_node_roundtrip_msgs_per_s`` must
    clear 50k and the roundtrip MB/s must land within 4x of the
    large-object host path — the ROADMAP transport-item bars."""
    if newest_multichip_devices(repo) <= 1:
        return []
    problems = []
    msgs = stats.get("host_node_roundtrip_msgs_per_s")
    try:
        msgs = float(msgs)
    except (TypeError, ValueError):
        msgs = None
    if msgs is not None and msgs < WIRE_RIG_MSGS_PER_S:
        problems.append(
            f"host_node_roundtrip_msgs_per_s {msgs} below the wire "
            f"hot-loop rig bar {WIRE_RIG_MSGS_PER_S:.0f} (ROADMAP "
            "transport item)"
        )
    try:
        rt = float(stats["host_node_roundtrip_mb_per_s"])
        big = float(stats["host_node_large_object_mb_per_s"])
    except (KeyError, TypeError, ValueError):
        return problems
    if rt > 0 and big / rt > WIRE_RIG_MBPS_FACTOR:
        problems.append(
            f"host_node_roundtrip_mb_per_s {rt} is {big / rt:.1f}x below "
            f"the large-object host path ({big}); the rig bar is "
            f"{WIRE_RIG_MBPS_FACTOR:.0f}x"
        )
    return problems


def cache_hot_check(stats: dict) -> list[str]:
    """ISSUE-12 acceptance bars for the tiered read path, fresh runs
    only (recorded rounds before the decoded-object cache genuinely
    lack the keys — and a replay must stay green)."""
    try:
        hot = float(stats["object_get_hot_mb_per_s"])
        degraded = float(stats["object_get_degraded_mb_per_s"])
    except (KeyError, TypeError, ValueError):
        return []
    problems = []
    if degraded > 0 and hot < CACHE_HOT_FACTOR * degraded:
        problems.append(
            f"object_get_hot_mb_per_s {hot} is only {hot / degraded:.1f}x "
            f"the degraded decode path ({degraded}); the cache-tier bar "
            f"is {CACHE_HOT_FACTOR:.0f}x (docs/object-service.md)"
        )
    try:
        rate = float(stats["object_get_hit_rate"])
    except (KeyError, TypeError, ValueError):
        return problems
    if rate < CACHE_HOT_HIT_RATE:
        problems.append(
            f"object_get_hit_rate {rate} below the {CACHE_HOT_HIT_RATE} "
            "bar under the zipfian GET mix — the hot-read number is not "
            "being served by the cache tier"
        )
    return problems


def lrc_repair_check(stats: dict) -> list[str]:
    """ISSUE-13 acceptance bar for the LRC tier, fresh runs only
    (recorded rounds before the LRC tier genuinely lack the key)."""
    try:
        amp = float(stats["repair_fetch_amplification"])
    except (KeyError, TypeError, ValueError):
        return []
    if amp > LRC_FETCH_AMPLIFICATION_MAX:
        return [
            f"repair_fetch_amplification {amp} above the "
            f"{LRC_FETCH_AMPLIFICATION_MAX} bar — LRC single-loss heals "
            "are not staying local (docs/lrc.md; the >= 5x fewer-fetches "
            "acceptance bar)"
        ]
    return []


def placement_rig_check(stats: dict) -> list[str]:
    """ISSUE-17 acceptance bars for the placement ring, fresh runs only
    (recorded rounds before the placement subsystem genuinely lack the
    keys). Two bars — ``placement_fanout_ratio`` (targeted-delivery
    data sends per message over the n-shard ideal, docs/placement.md)
    must stay <= 1.5x ideal, and ``rebalance_amplification`` (bytes the
    rebalancer moved over the ideal ownership-delta bytes) is gated
    lower-better by its suffix; here it only has to be finite and
    positive to prove the churn drill converged."""
    problems = []
    try:
        ratio = float(stats["placement_fanout_ratio"])
    except (KeyError, TypeError, ValueError):
        ratio = None
    if ratio is not None and ratio > PLACEMENT_FANOUT_RATIO_MAX:
        problems.append(
            f"placement_fanout_ratio {ratio} above the "
            f"{PLACEMENT_FANOUT_RATIO_MAX} bar — targeted delivery is "
            "sending data shards beyond their ring owners "
            "(docs/placement.md; the peers-to-n fanout contract)"
        )
    try:
        amp = float(stats["rebalance_amplification"])
    except (KeyError, TypeError, ValueError):
        return problems
    if not amp > 0:
        problems.append(
            f"rebalance_amplification {amp} is not a positive ratio — "
            "the churn rebalance drill did not move (or did not "
            "measure) the ownership delta"
        )
    return problems


def trace_overhead_check(stats: dict) -> list[str]:
    """ISSUE-18 acceptance bars for request tracing, fresh runs only
    (recorded rounds before the tail sampler genuinely lack the keys).
    ``trace_overhead_pct`` (armed vs disabled hot-GET wall time) must
    stay <= 3%, and ``trace_keep_rate`` (kept share of the armed legs'
    requests) must stay <= 0.25 — the clean path samples 1-in-sample_n,
    so a higher keep rate means the sampler stopped dropping."""
    problems = []
    try:
        pct = float(stats["trace_overhead_pct"])
    except (KeyError, TypeError, ValueError):
        pct = None
    if pct is not None and pct > TRACE_OVERHEAD_PCT_MAX:
        problems.append(
            f"trace_overhead_pct {pct} above the "
            f"{TRACE_OVERHEAD_PCT_MAX:g}% bar — armed tail sampling is "
            "taxing the hot GET path (docs/observability.md "
            '"Request tracing")'
        )
    try:
        rate = float(stats["trace_keep_rate"])
    except (KeyError, TypeError, ValueError):
        return problems
    if rate > TRACE_KEEP_RATE_MAX:
        problems.append(
            f"trace_keep_rate {rate} above the {TRACE_KEEP_RATE_MAX} "
            "bar — the tail sampler is keeping clean-path traces it "
            "should drop"
        )
    return problems


def event_overhead_check(stats: dict) -> list[str]:
    """ISSUE-20 acceptance bar for the wide-event log, fresh runs only
    (recorded rounds before the event log genuinely lack the key).
    ``event_log_overhead_pct`` (armed vs disabled hot-GET wall time)
    must stay <= 1% — the hot cache-hit path crosses no emit, so a
    real gap means an event call site leaked onto the per-request
    path."""
    problems = []
    try:
        pct = float(stats["event_log_overhead_pct"])
    except (KeyError, TypeError, ValueError):
        return problems
    if pct > EVENT_OVERHEAD_PCT_MAX:
        problems.append(
            f"event_log_overhead_pct {pct} above the "
            f"{EVENT_OVERHEAD_PCT_MAX:g}% bar — the wide-event log is "
            "taxing the hot GET path (docs/observability.md "
            '"Wide events")'
        )
    return problems


def hedge_rig_check(stats: dict) -> list[str]:
    """ISSUE-19 acceptance bars for hedged reads and tenant QoS, fresh
    runs only (recorded rounds before the hedge tier genuinely lack the
    keys). ``object_get_p99_hedged_ms`` — the straggler-fleet GET p99
    with the hedge engine on — must stay under HEDGE_P99_MS_MAX (the
    unhedged tail is ~3x the bar; crossing it means hedges stopped
    firing or stopped winning). ``tenant_isolation_p99_ratio`` — the
    quiet tenant's contended-over-solo p99 — must stay under
    TENANT_ISOLATION_RATIO_MAX (above it the noisy neighbor is moving
    the quiet tail and the QoS lanes are not isolating)."""
    problems = []
    try:
        p99 = float(stats["object_get_p99_hedged_ms"])
    except (KeyError, TypeError, ValueError):
        p99 = None
    if p99 is not None and p99 > HEDGE_P99_MS_MAX:
        problems.append(
            f"object_get_p99_hedged_ms {p99} above the "
            f"{HEDGE_P99_MS_MAX:g} ms bar — the straggler is back in "
            "the GET tail; hedged fan-out is not racing the slow "
            'source (docs/object-service.md "Read path")'
        )
    try:
        ratio = float(stats["tenant_isolation_p99_ratio"])
    except (KeyError, TypeError, ValueError):
        return problems
    if ratio > TENANT_ISOLATION_RATIO_MAX:
        problems.append(
            f"tenant_isolation_p99_ratio {ratio} above the "
            f"{TENANT_ISOLATION_RATIO_MAX} bar — a noisy tenant is "
            "moving the quiet tenant's GET p99 through the shared "
            'lanes (docs/object-service.md "QoS lanes")'
        )
    return problems


def panel_rig_check(stats: dict, repo: Path = REPO) -> list[str]:
    """ISSUE-15 acceptance bars for the wide-geometry panel tier, on
    rigs only (module docstring): applied to FRESH runs when the
    recorded MULTICHIP rounds prove real hardware. Three bars —
    ``rs200_56_route`` off ``panel`` (a probe demotion to the MXU, the
    regression the sub-launch split exists to prevent),
    ``rs200_56_encode_gbps`` below 150, and
    ``gf65536_vs_gf256_decode_ratio`` above 1.25."""
    if newest_multichip_devices(repo) <= 1:
        return []
    problems = []
    route = stats.get("rs200_56_route")
    if isinstance(route, str) and route != "panel":
        problems.append(
            f"rs200_56_route is {route!r}, not 'panel' — the wide "
            "geometry demoted off the K-grid sub-launch panel pipeline "
            "(docs/design.md §14); check the compile-probe escalation "
            "logs"
        )
    gbps = stats.get("rs200_56_encode_gbps")
    try:
        gbps = float(gbps)
    except (TypeError, ValueError):
        gbps = None
    if gbps is not None and gbps < PANEL_RIG_RS200_GBPS:
        problems.append(
            f"rs200_56_encode_gbps {gbps} below the panel-tier rig bar "
            f"{PANEL_RIG_RS200_GBPS:.0f} (ROADMAP item 1)"
        )
    ratio = stats.get("gf65536_vs_gf256_decode_ratio")
    try:
        ratio = float(ratio)
    except (TypeError, ValueError):
        return problems
    if ratio > PANEL_RIG_DECODE_RATIO_MAX:
        problems.append(
            f"gf65536_vs_gf256_decode_ratio {ratio} above the "
            f"{PANEL_RIG_DECODE_RATIO_MAX} bar — wide-field decode is "
            "not riding the packed byte-sliced panel pipeline "
            "(ROADMAP item 1)"
        )
    return problems


def north_star_check(stats: dict) -> list[str]:
    """The headline must clear the BASELINE.json bar when present."""
    headline = stats.get("headline_rs10_4_encode_gbps")
    if headline is None:
        return []
    try:
        import bench

        bar = float(bench.NORTH_STAR_GBPS)
    except Exception:  # noqa: BLE001 — recorded-file mode without bench.py
        bar = 40.0
    if float(headline) < bar:
        return [
            f"headline rs10_4 encode {headline} GB/s below the "
            f"BASELINE.json north star {bar} GB/s"
        ]
    return []


def gate(old: dict, new: dict) -> tuple[list[str], list[dict]]:
    """(problems, findings). Empty problems = the gate passes."""
    findings = compare(old, new)
    problems = [
        f"{f['metric']}: {f['old']} -> {f['new']} "
        f"({f['delta_pct']:+.1f}%, tolerance {f['tolerance_pct']}%, "
        f"{'higher' if f['direction'] == 'up' else 'lower'} is better)"
        for f in findings
        if f["regressed"]
    ]
    problems.extend(north_star_check(new))
    return problems, findings


# --------------------------------------------------------------- load/record


_HEADLINE = re.compile(
    r'\{"metric": "rs10_4_encode_throughput".*?\}'
)


def _stats_from_bench_doc(doc: dict) -> dict | None:
    """A recorded BENCH_r*.json -> flat stats dict (parsed + headline)."""
    stats = doc.get("parsed")
    if not isinstance(stats, dict):
        return None
    stats = dict(stats)
    m = _HEADLINE.search(doc.get("tail", ""))
    if m:
        try:
            stats["headline_rs10_4_encode_gbps"] = float(
                json.loads(m.group(0))["value"]
            )
        except (ValueError, KeyError):
            pass
    return stats


def load_stats(path: Path) -> dict:
    """Either a raw stats dict or a BENCH_r document."""
    doc = json.loads(path.read_text())
    if "parsed" in doc or "tail" in doc:
        stats = _stats_from_bench_doc(doc)
        if stats is None:
            raise ValueError(f"{path} has no parsed stats")
        return stats
    return doc


def recorded_series(repo: Path = REPO) -> list[tuple[str, dict]]:
    """(name, stats) for every recorded round with parsed stats."""
    out = []
    for path in sorted(repo.glob("BENCH_r*.json")):
        doc = json.loads(path.read_text())
        stats = _stats_from_bench_doc(doc)
        if stats:
            out.append((path.name, stats))
    return out


def run_bench() -> dict:
    """One fresh ``python bench.py``; stats from the last stderr JSON
    line, headline from stdout."""
    proc = subprocess.run(
        [sys.executable, str(REPO / "bench.py")],
        capture_output=True, text=True, timeout=3600,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"bench.py exited {proc.returncode}:\n{proc.stderr[-2000:]}"
        )
    stats = None
    for line in reversed(proc.stderr.splitlines()):
        line = line.strip()
        if line.startswith("{"):
            stats = json.loads(line)
            break
    if stats is None:
        raise RuntimeError("bench.py printed no stats JSON on stderr")
    m = _HEADLINE.search(proc.stdout)
    if m:
        stats["headline_rs10_4_encode_gbps"] = float(
            json.loads(m.group(0))["value"]
        )
    return stats


# ------------------------------------------------------------------ selfcheck


def self_check(verbose: bool = True, repo: Path = REPO) -> list[str]:
    """Replay the recorded series under ``repo``; empty list = the gate
    behaves.

    Three properties, all device-free:

    - the two newest rounds' recorded deltas pass;
    - a synthetic 20% cut of every throughput metric — including the
      known weakest geometry, rs200_56 — is flagged, as is a 20%
      latency inflation;
    - improvements are never flagged (direction parsing).
    """
    errors: list[str] = []
    series = recorded_series(repo)
    if len(series) < 2:
        return ["fewer than 2 recorded BENCH_r*.json rounds to replay"]

    (prev_name, prev), (last_name, last) = series[-2:]
    problems, _ = gate(prev, last)
    if problems:
        errors.append(
            f"the recorded {prev_name} -> {last_name} deltas must pass the "
            "gate; flagged: " + "; ".join(problems)
        )

    latest_name, latest = series[-1]
    # Device-kernel throughput (tight 10% tolerance): a 20% cut must
    # flag every one. Host-path metrics carry the 35% load-tail
    # tolerance, so a 20% cut legitimately passes there.
    gbps_metrics = [
        n for n in latest
        if metric_direction(n) == "up"
        and metric_tolerance(n) < 0.2
        and isinstance(latest[n], (int, float))
    ]
    if not gbps_metrics:
        errors.append(f"{latest_name} has no device throughput metrics")
    weakest = min(gbps_metrics, key=lambda n: float(latest[n]), default=None)
    synthetic = dict(latest)
    for n in gbps_metrics:
        synthetic[n] = float(latest[n]) * 0.8
    problems, findings = gate(latest, synthetic)
    flagged = {p.split(":", 1)[0] for p in problems}
    missing = set(gbps_metrics) - flagged
    if missing:
        errors.append(
            f"synthetic 20% throughput regression not flagged for: "
            f"{sorted(missing)}"
        )
    if weakest and weakest not in flagged:
        errors.append(
            f"the weakest metric {weakest!r} survived a 20% synthetic cut"
        )

    lat_metrics = [n for n in latest if metric_direction(n) == "down"]
    if lat_metrics:
        inflated = dict(latest)
        for n in lat_metrics:
            inflated[n] = float(latest[n]) * 2.0  # past even HOST_TOLERANCE
        problems, _ = gate(latest, inflated)
        flagged = {p.split(":", 1)[0] for p in problems}
        if set(lat_metrics) - flagged:
            errors.append(
                "doubled latency metrics not flagged: "
                f"{sorted(set(lat_metrics) - flagged)}"
            )

    improved = {
        n: (float(v) * 1.5 if metric_direction(n) == "up"
            else float(v) * 0.5 if metric_direction(n) == "down" else v)
        for n, v in latest.items()
        if isinstance(v, (int, float))
    }
    problems, _ = gate(latest, improved)
    if problems:
        errors.append(f"improvements were flagged as regressions: {problems}")

    if verbose and not errors:
        print(
            f"bench_gate --check: OK ({len(series)} rounds replayed, "
            f"weakest metric {weakest!r} = {latest.get(weakest)})"
        )
    return errors


# ----------------------------------------------------------------------- cli


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(
        prog="bench_gate",
        description="fail the build when bench.py regresses vs the "
        "recorded trajectory",
    )
    p.add_argument("--check", action="store_true",
                   help="self-test on the recorded BENCH_r*.json series "
                   "(no device needed)")
    p.add_argument("--repo", metavar="DIR", type=Path, default=REPO,
                   help="directory holding the BENCH_r*/MULTICHIP_r* "
                   "records (default: the repo root)")
    p.add_argument("--current", metavar="FILE",
                   help="stats to gate (skip running bench.py)")
    p.add_argument("--against", metavar="FILE",
                   help="reference stats (default: newest BENCH_r*.json)")
    p.add_argument("--json", action="store_true",
                   help="print the full findings table as JSON")
    args = p.parse_args(argv)

    if args.check:
        errors = self_check(repo=args.repo)
        for e in errors:
            print(f"bench_gate --check: {e}", file=sys.stderr)
        return 1 if errors else 0

    try:
        if args.against:
            against = load_stats(Path(args.against))
            against_name = args.against
        else:
            series = recorded_series(args.repo)
            if not series:
                print("bench_gate: no recorded BENCH_r*.json to gate "
                      "against", file=sys.stderr)
                return 2
            against_name, against = series[-1]
        current = (
            load_stats(Path(args.current)) if args.current else run_bench()
        )
    except (OSError, ValueError, RuntimeError) as exc:
        print(f"bench_gate: {exc}", file=sys.stderr)
        return 2

    problems, findings = gate(against, current)
    if not args.current:
        # Fresh-run-only rig checks (recorded rounds before the mesh tier
        # genuinely carry batch_mesh_devices: 1 and pre-§15 roundtrip
        # numbers; replays must stay green).
        problems.extend(mesh_rig_check(current, args.repo))
        problems.extend(wire_rig_check(current, args.repo))
        problems.extend(cache_hot_check(current))
        problems.extend(lrc_repair_check(current))
        problems.extend(panel_rig_check(current, args.repo))
        problems.extend(placement_rig_check(current))
        problems.extend(trace_overhead_check(current))
        problems.extend(event_overhead_check(current))
        problems.extend(hedge_rig_check(current))
    if args.json:
        print(json.dumps(
            {"against": against_name, "findings": findings,
             "problems": problems},
            indent=1,
        ))
    for f in findings:
        if f["regressed"]:
            print(f"bench_gate: REGRESSION {f['metric']}: {f['old']} -> "
                  f"{f['new']} ({f['delta_pct']:+.1f}%)", file=sys.stderr)
    if problems:
        print(f"bench_gate: {len(problems)} regression(s) vs "
              f"{against_name}", file=sys.stderr)
        return 1
    print(f"bench_gate: OK ({len(findings)} metrics vs {against_name})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
