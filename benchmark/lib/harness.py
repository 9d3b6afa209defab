"""One run of one cell: set-up, the measured window, the check, the result.

    set-up   devices, compile cache, the two nodes, the traffic's own
             set-up (objects loaded, slots lost), one operation of every
             kind through the real path, then the batch sizes the
             coalescer can form for every (matrix, shape) that operation
             dispatched;
    window   closed-loop clients for ``seconds``; every operation started
             in it runs to completion, none starts after it;
    check    the device's peak memory is read, then ``check.check``
             compares every answer (check.py);
    result   stderr gets the detail lines and, last, each compared number
             beside its limit; stdout's last line is the one JSON result.
"""

from __future__ import annotations

import contextlib
import json
import shutil
import sys
import time
from dataclasses import dataclass
from pathlib import Path

import numpy as np

from . import check as checks
from . import cluster as cluster_mod
from . import counters, spec
from .payload import Payloads
from .traffic import Traffic

CACHE_DIR = spec.ROOT / ".bench_cache"
PEAKS = json.loads((Path(__file__).with_name("peaks.json")).read_text())


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclass
class Context:
    """What a metric reader (``benchmark/metrics/<name>.py``) reads."""

    config: dict
    ops: list
    elapsed_s: float  # window start to the last completion
    setup_s: float
    delta: counters.Delta
    peaks: dict
    lost: dict  # stripe key -> shard slots the traffic dropped
    trace: object = None  # trace.TraceSummary of a --trace 1 run

    def ok(self, *kinds: str) -> list:
        return [op for op in self.ops if op.ok and op.kind in kinds]

    def gb(self, *kinds: str) -> float:
        return sum(op.nbytes for op in self.ok(*kinds)) / 1e9


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def devices(chips: int, require_tpu: bool) -> dict:
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_tpu and info["platform"] != "tpu":
        raise NoChip(f"JAX platform is {info['platform']!r}, not a TPU")
    if info["count"] < chips:
        raise NoChip(f"{info['count']} devices visible, the cell needs "
                     f"{chips}")
    if require_tpu and info["kind"] not in PEAKS:
        raise NoChip(f"device kind {info['kind']!r} is not in peaks.json")
    return info


def arm_compile_cache() -> str:
    """JAX's persistent cache at a fixed path inside the checkout, handed
    to the program's own arming call (which keeps a directory it is
    given)."""
    import jax

    from noise_ec_tpu.ops.dispatch import default_compile_cache

    path = CACHE_DIR / "jax"
    path.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(path))
    return default_compile_cache()


class CompileWatch:
    """Counts JAX's compile and cache events (jax.monitoring), so a
    compile inside the window shows on stderr."""

    def __init__(self):
        import jax

        self.events: dict = {}
        self.on = False

        def on_event(name, **kw):
            if self.on:
                fun = kw.get("fun_name")
                key = f"{name}({fun})" if fun else name
                self.events[key] = self.events.get(key, 0) + 1

        def on_duration(name, secs, **kw):
            on_event(name, **kw)

        jax.monitoring.register_event_listener(on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)


def warm_batches(traffic: Traffic) -> int:
    """Run the traffic's warm-up, noting every (matrix, shape) the codec
    multiplies, then drive each one that goes through the coalescer at
    every power-of-two batch up to the client count (a client has at most
    one multiply in flight). Returns the batched programs warmed."""
    from noise_ec_tpu.codec.rs import ReedSolomon
    from noise_ec_tpu.ops.coalesce import coalesce_cutoff_bytes

    seen: dict = {}
    original = ReedSolomon._mul

    def spy(self, M, D):
        D = np.asarray(D)
        seen.setdefault(self._mul_key(M, D.shape, D.dtype),
                        (self, np.asarray(M), D.shape, D.dtype))
        return original(self, M, D)

    ReedSolomon._mul = spy
    try:
        traffic.warm()
    finally:
        ReedSolomon._mul = original
    top = 1 << (traffic.clients - 1).bit_length()
    warmed = 0
    for rs, M, shape, dtype in seen.values():
        if int(np.prod(shape)) * np.dtype(dtype).itemsize > \
                coalesce_cutoff_bytes():
            continue
        batch = 2
        while batch <= top:
            rs.matmul_many(M, [np.zeros(shape, dtype)] * batch)
            warmed += 1
            batch *= 2
    return warmed


@contextlib.contextmanager
def profiled(enabled: bool, where: Path):
    if not enabled:
        yield None
        return
    import jax

    shutil.rmtree(where, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(str(where), profiler_options=opts)
    try:
        yield where
    finally:
        jax.profiler.stop_trace()


def annotator(enabled: bool):
    if not enabled:
        return lambda kind: contextlib.nullcontext()
    import jax

    return lambda kind: jax.profiler.TraceAnnotation(f"bench.{kind}")


def memory_peak() -> int:
    import jax

    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_process: float, require_tpu: bool = True, plant=None,
             overrides: dict | None = None) -> dict:
    """One run; returns the result object. ``plant`` (tests only) breaks
    the built cluster on purpose; ``overrides`` (tests only) shrinks the
    configuration and traffic keys it names."""
    phases = {"start": time.perf_counter() - t_process}

    def phase(label: str) -> None:
        phases[label] = time.perf_counter() - t_process - sum(
            phases.values())

    cell = spec.load_cell(name)
    config = dict(cell.config, **(overrides or {}).get("config", {}))
    tspec = dict(cell.traffic, **(overrides or {}).get("traffic", {}))
    device = devices(cell.chips, require_tpu)
    phase("devices")
    log(f"device: {device}")
    log(f"compile cache: {arm_compile_cache()}")
    watch = CompileWatch()

    nodes = cluster_mod.build(config)
    if plant is not None:
        plant(nodes)
    k = int(config["k"])
    stripe = int(config["stripe_bytes"])
    capacity = max(k, stripe - stripe % k)
    payloads = Payloads(seed, int(tspec["object_bytes"]), capacity)
    traffic = Traffic(tspec, config, nodes, payloads, seed)
    phase("build")
    traffic.preload()
    dropped = traffic.lose_slots()
    phase("preload")
    batched = warm_batches(traffic)
    phase("warm")
    before = counters.snapshot()
    setup_s = time.perf_counter() - t_process
    log(f"set-up: {setup_s:.3f} s "
        f"({', '.join(f'{k} {v:.3f}' for k, v in phases.items())}), "
        f"objects loaded {traffic.preload_objects}, slots dropped {dropped}"
        f", batched programs warmed {batched}, set-up failures "
        f"{traffic.setup_failures}")

    watch.on = True
    trace_dir = CACHE_DIR / "trace" / name
    with profiled(trace, trace_dir):
        ops, t_start = traffic.run(seconds, annotator(trace))
    watch.on = False
    after = counters.snapshot()
    t_last = max((op.t1 for op in ops), default=t_start)
    device["memory_peak_bytes"] = memory_peak()

    summary = None
    if trace:
        from . import trace as trace_mod

        xplanes = sorted(trace_dir.glob("**/*.xplane.pb"))
        events = trace_mod.read_events(str(xplanes[-1]))
        log(f"trace shape: {json.dumps(trace_mod.shape(events))}")
        summary = trace_mod.summarize(events)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        log(f"trace: window {summary.window_s:.6f} s, busy "
            f"{summary.busy_s:.6f} s, kernel {summary.kernel_s:.6f} s, "
            f"transfer {summary.transfer_s:.6f} s, device op events "
            f"{summary.op_events}, module time outside ops "
            f"{summary.unmatched_s:.6f} s")

    ctx = Context(config=config, ops=ops, elapsed_s=t_last - t_start,
                  setup_s=setup_s, delta=counters.Delta(before, after),
                  peaks=PEAKS.get(device["kind"], {}), lost=traffic.lost,
                  trace=summary)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = spec.metric_reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    by_kind: dict = {}
    for op in ops:
        n_ok, n_bad = by_kind.get(op.kind, (0, 0))
        by_kind[op.kind] = (n_ok + op.ok, n_bad + (not op.ok))
    log(f"window: {seconds} s asked, {ctx.elapsed_s:.6f} s to the last "
        f"completion; operations (ok, failed) {by_kind}; skipped "
        f"{traffic.skipped}; compile events in window {watch.events}; "
        f"device ops in window by route "
        f"{dict(ctx.delta.device_op_by_route)}")
    for kind in sorted(by_kind):
        lat = sorted(op.t1 - op.t0 for op in ops if op.kind == kind)
        pick = lambda q: lat[max(0, -(-len(lat) * q // 100) - 1)] * 1e3
        log(f"latency {kind}: {len(lat)} samples, p50 {pick(50):.3f} ms, "
            f"p95 {pick(95):.3f} ms, max {lat[-1] * 1e3:.3f} ms")
    step = max(1.0, seconds / 10)
    for kind in sorted(by_kind):
        cols: dict = {}
        for op in ops:
            if op.kind == kind:
                cols.setdefault(int((op.t0 - t_start) // step), []).append(
                    op.t1 - op.t0)
        log(f"timeline {kind} (per {step:g} s of start: count, p50 ms): "
            + " ".join(f"{len(v)}:{sorted(v)[len(v) // 2] * 1e3:.1f}"
                       for _, v in sorted(cols.items())))
    for op in ops:
        if op.error:
            log(f"failed {op.kind} {op.name}: {op.error}")
            break

    t0 = time.perf_counter()
    numbers = checks.check(nodes, traffic, ops, config)
    log(f"check: {time.perf_counter() - t0:.3f} s over "
        f"{len(traffic.puts)} acknowledged PUTs and {len(ops)} operations")
    for key, (value, limit) in numbers.items():
        log(f"check {key} = {value} (limit {limit})")
    result = {
        "correct": checks.passed(numbers),
        "attempted": len(ops),
        "failed": sum(not op.ok for op in ops),
        "metrics": metrics,
        "device": device,
    }
    if summary is not None:
        result["breakdown"] = {"device_ops": summary.device_ops,
                               "idle_gaps": summary.idle_gaps}
    result["checks"] = {key: {"value": v, "limit": lim}
                        for key, (v, lim) in numbers.items()}
    return result
