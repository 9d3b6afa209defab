"""Device telemetry: dispatch latency, compile tracking, roofline, HBM.

The one layer the obs package could not see before this module was the
TPU hot path itself: ``record_kernel`` counted calls and bytes, but a
recompile storm (geometry churn re-jitting per (matrix, shape) key) was
indistinguishable from a slow device, and memory headroom was invisible
until an OOM. Four surfaces close that gap:

- **Dispatch latency with a compile/execute split** —
  :func:`device_op` wraps every ``DeviceCodec`` dispatch and records it
  into ``noise_ec_device_op_seconds{kernel,route}``. The route comes
  from JAX's own events: one ``jax.monitoring`` listener
  (:func:`install_compile_listener`) counts backend compiles per thread,
  and a dispatch inside whose window JAX ran one records as
  ``route="compile"`` and feeds ``noise_ec_jit_compiles_total{kernel}``
  plus the compile-seconds histogram; every other dispatch — a program
  JAX already holds, however new its key — records as
  ``route="execute"``. The same listener lands JAX's trace and compile
  durations as finished ``jax_trace`` / ``backend_compile`` spans on the
  calling thread. While a ``jax.profiler`` session records, each
  dispatch also opens a host ``TraceMe`` named ``dispatch``.
- **Roofline** — :func:`analyze_program` pulls
  ``fn.lower(*args).compile().cost_analysis()`` FLOPs / bytes-accessed
  for a freshly compiled program (cheap: the AOT path reuses the jit
  compilation cache — measured ~17 ms after a 330 ms first call) and
  exports per-kernel program-cost and operational-intensity gauges.
  Achieved bandwidth is the device trace's to measure, not the host's.
- **HBM accounting** — :func:`hbm_snapshot` sums ``jax.live_arrays()``
  and folds in the allocator's ``memory_stats()`` where the backend
  reports them (TPU does; CPU returns None and falls back to the
  live-array high-water mark). Exported as callback gauges on
  ``/metrics`` and folded into the ``/healthz`` details (obs/server.py).
- **xprof capture** — the ``-xprof-dir`` CLI flag plus the stats
  server's ``/xprof?seconds=N`` endpoint wrap
  :func:`~noise_ec_tpu.obs.profiling.device_trace` so a live node can
  capture a TensorBoard/xprof trace of a decode burst on demand.

Hot-path budget: a dispatch pays one perf_counter pair, two reads of a
thread-local compile count, one profiler-enabled check and one
cached-child histogram observe — no lock — on a path whose cheapest op
(a 14 us reconstruct) is many times the overhead.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Optional

from noise_ec_tpu.obs.registry import Registry, default_registry
from noise_ec_tpu.obs.trace import default_tracer, host_traceme

__all__ = [
    "DeviceOpTimer",
    "analyze_program",
    "device_op",
    "hbm_snapshot",
    "install_compile_listener",
    "install_hbm_gauges",
    "maybe_analyze_program",
    "peak_hbm_gbps",
    "record_tile_dispatch",
    "reset_dispatch_tracking",
    "roofline_summary",
    "set_analysis_interval",
    "set_peak_hbm_gbps",
    "tile_achieved_gbps",
    "tile_summary",
]

log = logging.getLogger("noise_ec_tpu.obs")

_lock = threading.Lock()
# (kernel, route) -> histogram child; kernel -> (counter, hist) children.
# Default-registry only (the health.py pattern): a transient Registry must
# not pin stale children.
_op_children: dict[tuple[str, str], object] = {}
_compile_children: dict[str, tuple] = {}
_gauges_installed = False
_live_high_water = 0

# Published per-chip peaks by ``device_kind`` (the string JAX reports as
# ``jax.devices()[0].device_kind``). Source: Google Cloud documentation,
# "TPU v5e" — 819 GB/s HBM2e bandwidth, 393 TOP/s int8, 16 GB HBM per
# chip. A kind missing from the table has no peak (None) rather than a
# guessed one; set_peak_hbm_gbps pins one.
DEVICE_PEAKS = {
    "TPU v5 lite": {"hbm_gbps": 819.0, "int8_tops": 393.0},
}
_peak_override: Optional[float] = None


def set_peak_hbm_gbps(gbps: Optional[float]) -> None:
    """Pin the peak-bandwidth figure (None restores the
    per-``device_kind`` table lookup)."""
    global _peak_override
    _peak_override = gbps


def peak_hbm_gbps() -> Optional[float]:
    """Peak HBM GB/s of the default device, or None when its kind is not
    in :data:`DEVICE_PEAKS` (and no override is pinned)."""
    if _peak_override is not None:
        return _peak_override
    import jax

    peaks = DEVICE_PEAKS.get(jax.devices()[0].device_kind)
    return peaks["hbm_gbps"] if peaks else None


def reset_dispatch_tracking() -> None:
    """Forget per-tile stats and analysis rate limits (tests)."""
    with _lock:
        _tile_stats.clear()
        _last_analysis.clear()


# --------------------------------------------- JAX's compile events

# The duration events JAX records around a jit trace and a backend
# compile (jax._src.dispatch); a persistent-cache load is timed under
# the backend-compile event too, since it stands in for one.
JAXPR_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_EVENT_SPANS = {
    JAXPR_TRACE_EVENT: "jax_trace",
    BACKEND_COMPILE_EVENT: "backend_compile",
}
# Backend compiles run so far on each thread: a dispatch window compares
# the count at its entry and exit.
_compiles = threading.local()
_listener_installed = False


def _compiles_on_thread() -> int:
    return getattr(_compiles, "n", 0)


def _on_jax_duration(event: str, duration: float, **kwargs) -> None:
    """jax.monitoring duration listener: JAX calls it on the thread that
    traced or compiled, as the work finishes."""
    stage = _EVENT_SPANS.get(event)
    if stage is None:
        return
    if event == BACKEND_COMPILE_EVENT:
        _compiles.n = _compiles_on_thread() + 1
    fun = kwargs.get("fun_name")
    if fun is None:
        default_tracer().record(stage, duration)
    else:
        default_tracer().record(stage, duration, fun=str(fun))


def install_compile_listener() -> None:
    """Register the JAX-event listener once per process (idempotent).
    :func:`device_op` calls it, so every dispatching process routes by
    what JAX did."""
    global _listener_installed
    if _listener_installed:
        return
    with _lock:
        if _listener_installed:
            return
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(_on_jax_duration)
        default_tracer().declare(*_EVENT_SPANS.values())
        _listener_installed = True


class DeviceOpTimer:
    """Times one dispatch and routes it compile/execute on exit.

    Class-based context manager for the same reason Span is: the
    generator machinery costs ~3x on a path measured in microseconds.

    ``tile`` is the per-dispatch tile-config attribution hook: a
    dispatch that runs a block-panel kernel sets it to the plan's
    ``tile_label`` (e.g. ``kb128_rb32_tl512``) before the window
    closes, and the exit path feeds the ``noise_ec_kernel_tile_*``
    counters, so dispatches and bytes are attributable per config.
    """

    __slots__ = ("entry", "nbytes", "registry", "route", "elapsed",
                 "tile", "_t0", "_n0", "_tm")

    def __init__(self, entry: str, nbytes: int,
                 registry: Optional[Registry]):
        self.entry = entry
        self.nbytes = nbytes
        self.registry = registry
        self.route = ""
        self.elapsed = 0.0
        self.tile = ""

    def compiled(self) -> bool:
        """True once JAX has run a backend compile on this thread inside
        the window (readable mid-dispatch, after the program call)."""
        return _compiles_on_thread() != self._n0

    def __enter__(self) -> "DeviceOpTimer":
        self._tm = host_traceme("dispatch")
        self._n0 = _compiles_on_thread()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.elapsed = time.perf_counter() - self._t0
        if self._tm is not None:
            self._tm.__exit__(None, None, None)
            self._tm = None
        self.route = "compile" if self.compiled() else "execute"
        if exc is not None:
            return False  # a failed dispatch records nothing
        reg = self.registry
        if reg is None:
            op = _op_children.get((self.entry, self.route))
            if op is None:
                op = _op_children[(self.entry, self.route)] = (
                    default_registry().histogram(
                        "noise_ec_device_op_seconds"
                    ).labels(kernel=self.entry, route=self.route)
                )
        else:
            op = reg.histogram("noise_ec_device_op_seconds").labels(
                kernel=self.entry, route=self.route
            )
        op.observe(self.elapsed)
        if self.route == "compile":
            self._record_compile(reg)
        if self.tile:
            record_tile_dispatch(
                self.entry, self.tile, self.nbytes, self.elapsed,
                route=self.route, registry=reg,
            )
        return False

    def _record_compile(self, reg: Optional[Registry]) -> None:
        if reg is None:
            pair = _compile_children.get(self.entry)
            if pair is None:
                r = default_registry()
                pair = _compile_children[self.entry] = (
                    r.counter("noise_ec_jit_compiles_total").labels(
                        kernel=self.entry
                    ),
                    r.histogram("noise_ec_jit_compile_seconds").labels(
                        kernel=self.entry
                    ),
                )
        else:
            pair = (
                reg.counter("noise_ec_jit_compiles_total").labels(
                    kernel=self.entry
                ),
                reg.histogram("noise_ec_jit_compile_seconds").labels(
                    kernel=self.entry
                ),
            )
        pair[0].add(1)
        pair[1].observe(self.elapsed)


def device_op(entry: str, nbytes: int = 0,
              registry: Optional[Registry] = None) -> DeviceOpTimer:
    """``with device_op("matmul_words", nbytes):`` around one
    DeviceCodec dispatch. Also installs the JAX compile listener and the
    HBM gauges on first use, so any process that dispatches routes by
    JAX's events and exports memory headroom."""
    if not _listener_installed:
        install_compile_listener()
    install_hbm_gauges(registry)
    return DeviceOpTimer(entry, nbytes, registry)


# -------------------------------------------------- per-tile attribution
#
# The block-panel kernels are auto-tuned: the planner picks a
# (KB, RB, TL) tile triple per geometry from the VMEM cost model — but
# the choice is invisible on /metrics. These counters make the chosen
# config a LABEL (dispatches and bytes per (kernel entry, tile)), and
# tile_summary() folds the host-timed per-tile bandwidth into bench.py's
# report.

# (entry, tile) -> [execute_bytes_total, execute_seconds_total]
_tile_stats: dict[tuple[str, str], list] = {}
_tile_children: dict[tuple[str, str], tuple] = {}


def tile_achieved_gbps(entry: str, tile: str) -> float:
    """Cumulative execute-route payload bandwidth for one (kernel
    entry, tile config) pair (0.0 until a warm dispatch lands)."""
    with _lock:
        st = _tile_stats.get((entry, tile))
    if not st or st[1] <= 0:
        return 0.0
    return st[0] / st[1] / 1e9


def record_tile_dispatch(entry: str, tile: str, nbytes: int,
                         seconds: float, *, route: str = "execute",
                         registry: Optional[Registry] = None) -> None:
    """Attribute one dispatch to its tile config (module comment).
    Compile-route dispatches count calls/bytes but stay out of the
    bandwidth stats — a first-call trace+compile is not kernel time."""
    if registry is None:
        pair = _tile_children.get((entry, tile))
        if pair is None:
            r = default_registry()
            pair = _tile_children[(entry, tile)] = (
                r.counter("noise_ec_kernel_tile_dispatches_total").labels(
                    entry=entry, tile=tile
                ),
                r.counter("noise_ec_kernel_tile_bytes_total").labels(
                    entry=entry, tile=tile
                ),
            )
    else:
        pair = (
            registry.counter(
                "noise_ec_kernel_tile_dispatches_total"
            ).labels(entry=entry, tile=tile),
            registry.counter(
                "noise_ec_kernel_tile_bytes_total"
            ).labels(entry=entry, tile=tile),
        )
    pair[0].add(1)
    pair[1].add(nbytes)
    if route != "execute":
        return
    with _lock:
        st = _tile_stats.setdefault((entry, tile), [0.0, 0.0])
        st[0] += nbytes
        st[1] += seconds


def tile_summary() -> dict:
    """Flat per-(entry, tile) achieved GB/s for bench/report output."""
    out: dict = {}
    with _lock:
        keys = list(_tile_stats)
    for entry, tile in keys:
        a = tile_achieved_gbps(entry, tile)
        if a > 0:
            out[f"device_tile_{entry}_{tile}_gbps"] = round(a, 2)
    return out


# Dispatch-time analysis rate limit: the AOT lower walk is cheap for a
# plain jit matmul (~17 ms measured) but NOT free for big fused programs,
# and geometry churn — the exact scenario the recompile counter exists to
# expose — would otherwise pay it on every fresh geometry (measured +50%
# on the interpret-mode CPU test files). One analysis per kernel entry
# per window keeps the gauges fresh without riding the churn.
_ANALYSIS_INTERVAL_S = 60.0
_last_analysis: dict[str, float] = {}


def set_analysis_interval(seconds: float) -> None:
    """Min seconds between dispatch-time cost analyses per kernel entry
    (tests shrink it; 0 analyzes every compile)."""
    global _ANALYSIS_INTERVAL_S
    _ANALYSIS_INTERVAL_S = seconds


def maybe_analyze_program(entry: str, fn, *args,
                          registry: Optional[Registry] = None
                          ) -> Optional[dict]:
    """Rate-limited :func:`analyze_program` — the dispatch-path entry.
    Returns None when skipped by the per-entry interval."""
    now = time.monotonic()
    with _lock:
        last = _last_analysis.get(entry)
        if last is not None and now - last < _ANALYSIS_INTERVAL_S:
            return None
        _last_analysis[entry] = now
    return analyze_program(entry, fn, *args, registry=registry)


def analyze_program(entry: str, fn, *args,
                    registry: Optional[Registry] = None) -> Optional[dict]:
    """Pull XLA ``cost_analysis()`` for a jitted callable's program and
    export per-kernel program-cost gauges.

    Call AFTER the first dispatch: ``fn.lower(*args).compile()`` then
    reuses the jit compilation cache instead of compiling twice. Returns
    ``{"flops", "bytes", "intensity"}`` or None when the backend offers
    no analysis (never raises — this is telemetry).
    """
    try:
        cost = fn.lower(*args).compile().cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        flops = float(cost.get("flops", 0.0))
        nbytes = float(cost.get("bytes accessed", 0.0))
    except Exception as exc:  # noqa: BLE001 — cost analysis is best-effort
        log.debug("cost_analysis unavailable for %s: %s", entry, exc)
        return None
    reg = registry if registry is not None else default_registry()
    try:
        reg.gauge("noise_ec_device_program_flops").labels(
            kernel=entry
        ).set(flops)
        reg.gauge("noise_ec_device_program_bytes").labels(
            kernel=entry
        ).set(nbytes)
        intensity = flops / nbytes if nbytes > 0 else 0.0
        reg.gauge("noise_ec_roofline_intensity").labels(
            kernel=entry
        ).set(intensity)
    except Exception:  # noqa: BLE001
        return None
    return {"flops": flops, "bytes": nbytes, "intensity": intensity}


# ------------------------------------------------------------------- HBM


def hbm_snapshot() -> dict:
    """Live/peak/limit device bytes. ``live_bytes`` sums
    ``jax.live_arrays()``; ``bytes_in_use`` / ``peak_bytes_in_use`` /
    ``bytes_limit`` come from the allocator when the backend reports
    memory_stats (TPU), else peak falls back to the high-water mark of
    live scans and limit reads 0. Empty dict when jax is absent."""
    global _live_high_water
    try:
        import jax
    except Exception:  # noqa: BLE001 — telemetry without jax
        return {}
    try:
        live = int(sum(getattr(a, "nbytes", 0) for a in jax.live_arrays()))
    except Exception:  # noqa: BLE001
        live = 0
    with _lock:
        _live_high_water = max(_live_high_water, live)
        high = _live_high_water
    out = {"live_bytes": live, "peak_bytes": high, "limit_bytes": 0}
    try:
        stats = jax.devices()[0].memory_stats()
    except Exception:  # noqa: BLE001
        stats = None
    if stats:
        out["bytes_in_use"] = int(stats.get("bytes_in_use", live))
        out["peak_bytes"] = int(stats.get("peak_bytes_in_use", high))
        out["limit_bytes"] = int(stats.get("bytes_limit", 0))
    return out


def install_hbm_gauges(registry: Optional[Registry] = None) -> None:
    """Install the collect-time HBM callback gauges (idempotent for the
    default registry; explicit registries always install)."""
    global _gauges_installed
    if registry is None:
        if _gauges_installed:
            return
        with _lock:
            if _gauges_installed:
                return
            _gauges_installed = True
    reg = registry if registry is not None else default_registry()
    try:
        reg.gauge("noise_ec_hbm_live_bytes").set_callback(
            lambda: hbm_snapshot().get("live_bytes", 0)
        )
        reg.gauge("noise_ec_hbm_peak_bytes").set_callback(
            lambda: hbm_snapshot().get("peak_bytes", 0)
        )
        reg.gauge("noise_ec_hbm_limit_bytes").set_callback(
            lambda: hbm_snapshot().get("limit_bytes", 0)
        )
    except Exception:  # noqa: BLE001 — gauge install must not fail callers
        log.debug("hbm gauge install failed")


def roofline_summary() -> dict:
    """Flat dict for bench/report output: the HBM snapshot (MiB)."""
    out: dict = {}
    hbm = hbm_snapshot()
    if hbm:
        out["hbm_live_mib"] = round(hbm.get("live_bytes", 0) / 2**20, 1)
        out["hbm_peak_mib"] = round(hbm.get("peak_bytes", 0) / 2**20, 1)
    return out
