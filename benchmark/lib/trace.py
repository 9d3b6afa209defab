"""Reduce a profiler trace (``.xplane.pb``) to device busy and idle time,
kernel time and the ``breakdown``.

The window is the traced run's client operations: from the start of the
first ``bench.<op>`` annotation (written by the benchmark's own code,
``jax.profiler.TraceAnnotation``) to the end of the last. Busy is the
union of the device-op intervals inside it; kernel time is the union of
those that are not host<->device transfers. Which runtime op names count
as transfers, and which planes and lines hold device ops, is data:
``trace_names.json`` beside this file.
"""

from __future__ import annotations

import json
import re
from collections import Counter, defaultdict
from dataclasses import dataclass, field
from pathlib import Path

NAMES = json.loads((Path(__file__).with_name("trace_names.json")).read_text())
ANNOTATION_PREFIX = "bench."


@dataclass
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float
    kernel_s: float
    transfer_s: float
    chips: int
    device_ops: list = field(default_factory=list)
    idle_gaps: list = field(default_factory=list)
    op_events: int = 0
    unmatched_s: float = 0.0  # device time outside every op line


def read_events(path: str) -> list[Event]:
    import jax

    pd = jax.profiler.ProfileData.from_file(str(path))
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                out.append(Event(plane.name, line.name, ev.name,
                                 float(ev.start_ns), float(ev.duration_ns)))
    return out


def union(intervals: list) -> list:
    """Merged, sorted (start, end) intervals."""
    merged: list = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def clip(intervals: list, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def total(intervals: list) -> float:
    return sum(e - s for s, e in intervals)


def is_device_plane(name: str) -> bool:
    return re.fullmatch(NAMES["device_plane"], name) is not None


def is_transfer(name: str) -> bool:
    return any(re.search(p, name) for p in NAMES["transfer_ops"])


def summarize(events: list[Event], top: int = 10) -> TraceSummary:
    ann = [e for e in events if e.name.startswith(ANNOTATION_PREFIX)
           and not is_device_plane(e.plane)]
    if not ann:
        raise ValueError("no bench.* annotations in the trace")
    lo = min(e.start_ns for e in ann)
    hi = max(e.end_ns for e in ann)
    window_ns = hi - lo
    planes = sorted({e.plane for e in events if is_device_plane(e.plane)})
    ops = [e for e in events if is_device_plane(e.plane)
           and e.line in NAMES["op_lines"]]
    busy = kernel = transfer = 0.0
    gaps_all: list = []
    for plane in planes:
        mine = [e for e in ops if e.plane == plane]
        busy_iv = union(clip([(e.start_ns, e.end_ns) for e in mine], lo, hi))
        kern_iv = union(clip([(e.start_ns, e.end_ns) for e in mine
                              if not is_transfer(e.name)], lo, hi))
        xfer_iv = union(clip([(e.start_ns, e.end_ns) for e in mine
                              if is_transfer(e.name)], lo, hi))
        busy += total(busy_iv)
        kernel += total(kern_iv)
        transfer += total(xfer_iv)
        edges = [lo] + [x for iv in busy_iv for x in iv] + [hi]
        gaps_all += [(edges[i], edges[i + 1])
                     for i in range(0, len(edges), 2)
                     if edges[i + 1] > edges[i]]
    chips = max(1, len(planes))
    by_name: dict = defaultdict(float)
    for e in ops:
        s, t = max(e.start_ns, lo), min(e.end_ns, hi)
        if t > s:
            by_name[short_name(e.name)] += (t - s) / 1e9
    device_ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(gaps_all, key=lambda g: g[0] - g[1])[:top]
    host = [e for e in events if not is_device_plane(e.plane)
            and not e.name.startswith(ANNOTATION_PREFIX)]
    idle_gaps = [[_in_flight(ann, host, (s + e) / 2), (e - s) / 1e9]
                 for s, e in gaps]
    other = [e for e in events if is_device_plane(e.plane)
             and e.line not in NAMES["op_lines"]
             and e.line in NAMES.get("module_lines", [])]
    unmatched = max(0.0, total(union(clip(
        [(e.start_ns, e.end_ns) for e in other], lo, hi))) - busy)
    return TraceSummary(
        window_s=window_ns / 1e9,
        busy_s=busy / chips / 1e9,
        kernel_s=kernel / chips / 1e9,
        transfer_s=transfer / chips / 1e9,
        chips=len(planes),
        device_ops=[[n, s] for n, s in device_ops],
        idle_gaps=idle_gaps,
        op_events=len(ops),
        unmatched_s=unmatched / chips / 1e9,
    )


def short_name(hlo: str) -> str:
    """``custom-call:tpu_custom_call u32[4,262144]`` from a device op's
    HLO text (``%f.1 = u32[4,262144]{1,0:T(4,128)} custom-call(...),
    custom_call_target="tpu_custom_call", ...``); other names unchanged."""
    m = re.match(r"%?[\w.\-]+ = (\w+\[[\d,]*\])\S* ([\w\-]+)\(", hlo)
    if m is None:
        return hlo
    target = re.search(r'custom_call_target="([^"]+)"', hlo)
    op = m.group(2) + (f":{target.group(1)}" if target else "")
    return f"{op} {m.group(1)}"


def _in_flight(ann: list, host: list, t: float) -> str:
    """What the clients were doing at time ``t`` (``put*4``), and which
    runtime events the host was inside (``| TransferToDevice``); a gap
    with none is Python work between runtime calls."""
    kinds = Counter(e.name[len(ANNOTATION_PREFIX):] for e in ann
                    if e.start_ns <= t < e.end_ns)
    ops = "+".join(f"{k}*{n}" for k, n in sorted(kinds.items()))
    inside = sorted({e.name for e in host if e.start_ns <= t < e.end_ns})
    return f"{ops or 'no client operation'} | " + (
        ",".join(inside[:3]) if inside else "python")


def shape(events: list[Event]) -> dict:
    """Planes, their lines and event counts: printed so a reader can see
    what the reduction found."""
    out: dict = defaultdict(Counter)
    for e in events:
        out[e.plane][e.line] += 1
    return {p: dict(c) for p, c in out.items()}
