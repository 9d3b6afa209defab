"""Geometry-cached device codec: the bridge from GF matrices to TPU kernels.

The reference changes RS geometry (k, n) at runtime per message
(/root/reference/main.go:185-191), so kernels must be re-jitted per geometry
with bounded caching (SURVEY.md §7.4 "dynamic geometry"). ``DeviceCodec``
caches one fused (pack -> GF(2) matmul -> unpack) compiled program per
(matrix, stripe-length, kernel) signature.

Kernel selection:

- "pallas" (default on TPU): the geometry-specialized sparse Pallas kernel —
  the matrix's bit pattern is baked into the program as XOR chains; runs at
  the HBM roofline on v5e.
- "xla": masked AND/XOR fori_loop — portable, used for CPU tests and as the
  shape-generic fallback.
- "pallas_interpret": Pallas interpreter mode (CPU debugging).
"""

from __future__ import annotations

import functools
import logging
import threading
import time
import weakref
from collections import deque
from pathlib import Path
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from noise_ec_tpu.gf.bitmatrix import (
    expand_generator_bits,
    expand_generator_masks_cached,
)
from noise_ec_tpu.gf.field import GF, GF256, GF65536
from noise_ec_tpu.ops.bitops import pack_bitplanes_jax, unpack_bitplanes_jax
from noise_ec_tpu.ops.gf2mm import gf2_matmul_jax
from noise_ec_tpu.ops.pallas_gf2mm import (
    PANEL_XOR_BUDGET,
    bits_to_rows,
    gf2_matmul_pallas_panel_rows,
    gf2_matmul_pallas_sparse_rows,
    panel_plan,
    planes_to_tiled,
    tiled_to_planes,
)
from noise_ec_tpu.obs.device import device_op, maybe_analyze_program
from noise_ec_tpu.obs.profiling import record_kernel
from noise_ec_tpu.obs.trace import default_tracer, span
from noise_ec_tpu.ops.coalesce import QOS_LANES, current_qos

_FIELDS = {"gf256": GF256, "gf65536": GF65536}

log = logging.getLogger("noise_ec_tpu.ops")

# Jitted shape-generic planes-level matmul (retraces per shape, cached by jit).
_gf2_matmul_jax_jit = jax.jit(gf2_matmul_jax)


# ------------------------------------------------- codec graceful degradation
#
# The device is ONE process-wide resource: when a dispatch fails (XLA
# runtime error, preempted/recycled device, injected fault), every codec
# sharing it will fail the same way — so the circuit breaker guarding the
# device route is process-wide too. codec callers (codec/rs.py _mul)
# consult it around each device matmul: a failure is retried once
# in-call (transient allocator hiccups recover), a second failure trips
# the breaker and the call — and every call while it is open — runs the
# golden host arithmetic instead (noise_ec_codec_fallback_total{reason}).
# A background prober re-tries a tiny canary matmul on the breaker's
# widening half-open schedule and closes it when the device answers
# correctly again (noise_ec_codec_circuit_state 1 -> 2 -> 0).

_codec_breaker = None
_codec_breaker_lock = threading.Lock()
_fallback_children: dict[str, object] = {}
_prober_thread: Optional[threading.Thread] = None
_probe_dev = None


def codec_breaker():
    """The process-wide device-route breaker (lazy singleton)."""
    global _codec_breaker
    with _codec_breaker_lock:
        if _codec_breaker is None:
            from noise_ec_tpu.obs.registry import default_registry
            from noise_ec_tpu.resilience.breakers import CircuitBreaker

            _codec_breaker = CircuitBreaker(
                failure_threshold=1,  # the in-call retry already absorbed
                # one failure; a second is a tripped route
                reset_timeout=5.0,
                max_reset_timeout=60.0,
            )
            default_registry().gauge(
                "noise_ec_codec_circuit_state"
            ).set_callback(lambda: _codec_breaker.state_code())
        return _codec_breaker


def configure_codec_breaker(**kwargs):
    """Replace the process breaker (tests shrink the timeouts; a fresh
    instance also resets state). Returns the new breaker."""
    global _codec_breaker
    from noise_ec_tpu.obs.registry import default_registry
    from noise_ec_tpu.resilience.breakers import CircuitBreaker

    with _codec_breaker_lock:
        _codec_breaker = CircuitBreaker(
            failure_threshold=kwargs.pop("failure_threshold", 1), **kwargs
        )
        default_registry().gauge("noise_ec_codec_circuit_state").set_callback(
            lambda: _codec_breaker.state_code()
        )
        return _codec_breaker


def record_codec_fallback(reason: str) -> None:
    child = _fallback_children.get(reason)
    if child is None:
        from noise_ec_tpu.obs.registry import default_registry

        child = _fallback_children[reason] = default_registry().counter(
            "noise_ec_codec_fallback_total"
        ).labels(reason=reason)
    child.add(1)
    from noise_ec_tpu.obs.events import event

    event("codec.fallback", "warn", reason=reason)


def _probe_device() -> None:
    """Canary: one tiny encode-shaped matmul, checked against the host
    truth. Raises when the device route is still broken."""
    global _probe_dev
    if _probe_dev is None:
        _probe_dev = DeviceCodec(field="gf256")
    M = np.array([[1, 1], [1, 2]], dtype=np.uint8)
    D = np.arange(2 * 64, dtype=np.uint8).reshape(2, 64)
    out = np.asarray(_probe_dev.matmul_stripes(M, D))
    from noise_ec_tpu.matrix.hostmath import host_matvec

    want = host_matvec(_probe_dev.gf, M, D)
    if out.shape != want.shape or not np.array_equal(out, want):
        raise RuntimeError("codec probe produced wrong bytes")


def ensure_codec_prober() -> None:
    """Run the background half-open prober while the breaker is not
    closed (idempotent; the thread exits once the breaker closes)."""
    global _prober_thread
    with _codec_breaker_lock:
        if _prober_thread is not None and _prober_thread.is_alive():
            return
        _prober_thread = threading.Thread(
            target=_probe_loop, name="noise-ec-codec-probe", daemon=True
        )
        _prober_thread.start()


def _probe_loop() -> None:
    while True:
        # Read the breaker on every pass: configure_codec_breaker may have
        # replaced the one this thread started for, and while this thread
        # lives ensure_codec_prober starts no other.
        br = codec_breaker()
        if br.closed:
            return
        remaining = br.open_remaining()
        if remaining > 0:
            time.sleep(min(remaining, 0.05))
            continue
        if not br.allow():  # another caller holds the half-open probe
            time.sleep(0.02)
            continue
        try:
            _probe_device()
        except Exception as exc:  # noqa: BLE001 — any failure keeps it open
            br.record_failure()
            log.warning("codec device probe failed: %s (breaker re-opened "
                        "for %.1fs)", exc, br.open_remaining())
        else:
            br.record_success()
            log.info("codec device probe succeeded; device route restored")
            from noise_ec_tpu.obs.events import event

            event("codec.restore", route="device")
            return


def _cpu_requested() -> bool:
    """True when the process asked for the CPU platform up front
    (``JAX_PLATFORMS=cpu``, as the tests and rehearsals do) — the one
    case where running without an accelerator is intended."""
    return (jax.config.jax_platforms or "").split(",")[0].strip() == "cpu"


def _resolve_kernel(kernel: str) -> str:
    """``"auto"`` -> the kernel family for the default backend: Pallas on
    TPU, XLA only on a CPU the process asked for. Any other platform
    (a TPU that failed to attach and left JAX on an unrequested CPU, a
    GPU) raises instead of quietly taking the device out of the path."""
    if kernel != "auto":
        return kernel
    backend = jax.default_backend()
    if backend == "tpu":
        return "pallas"
    if backend == "cpu" and _cpu_requested():
        return "xla"
    raise RuntimeError(
        f"kernel='auto' found JAX platform {backend!r} "
        f"(jax_platforms={jax.config.jax_platforms!r}): expected a TPU, or "
        "JAX_PLATFORMS=cpu for CPU runs"
    )


# --------------------------------------------------- device dispatch gate
#
# The device is one shared resource fed from many producer threads (the
# transport dispatcher's workers, the streaming encoder, repair drains,
# the object service). Unbounded, a burst of concurrent dispatches
# queues arbitrarily deep work onto the device while every producer
# keeps allocating host+device buffers for payloads that cannot run yet
# — the OOM shape the fleet lab exposes at scale. The gate is the
# bounded DEVICE QUEUE: at most ``capacity`` dispatches are in flight;
# further callers BLOCK (yield their thread) until a slot frees, which
# propagates backpressure up through the plugin encode/decode paths to
# whatever transport or service admitted the work. Waits are visible as
# the noise_ec_backpressure_* family (layer="device"); a wait past
# ``wait_timeout`` proceeds anyway — the gate is a governor, not a
# deadlock (same escape contract as TCPNetwork.wait_writable).
#
# QoS lanes (docs/object-service.md "QoS lanes"): a contended gate no
# longer drains FIFO. Waiters queue per (lane, tenant) — the lane and
# tenant come from the ambient ``qos_lane`` context the admitting layer
# set (ops/coalesce.py) — and freed slots are HANDED to a queued ticket
# in release() rather than raced for, so the pick order below is the
# actual service order:
#
# - live beats background: a repair/scrub/convert burst queued in the
#   background lane cannot delay an interactive GET behind it;
# - starvation floor: background still gets >= 1 of every
#   ``background_floor`` contended grants, so a saturating live tenant
#   cannot park repair forever (durability work must progress);
# - inside a lane, tenants share by smooth weighted round-robin on
#   their ``weight=`` policy token — a 10x-noisy tenant's queue drains
#   at its weight's share, not at its arrival rate.
#
# The governor escape is unchanged: a ticket that waits past
# ``wait_timeout`` abandons its queue slot and proceeds anyway.


class _Ticket:
    """One queued waiter; ``granted`` flips under the gate lock when
    release() hands it the freed slot (in_flight already charged)."""

    __slots__ = ("granted",)

    def __init__(self):
        self.granted = False


class _TenantQueue:
    """One tenant's FIFO inside a lane + its smooth-WRR credit."""

    __slots__ = ("weight", "current", "tickets")

    def __init__(self, weight: int):
        self.weight = max(1, int(weight))
        self.current = 0
        self.tickets: "deque[_Ticket]" = deque()


class DeviceGate:
    """Bounded admission to the device dispatch path (module comment).

    ``with gate:`` around a dispatch; reentrant nesting is NOT supported
    (DeviceCodec acquires only at its public entry points, which never
    nest). Tests shrink ``capacity`` to pin the blocking behavior and
    ``background_floor`` to pin the lane arbitration.
    """

    def __init__(
        self,
        capacity: int = 8,
        wait_timeout: float = 120.0,
        background_floor: int = 8,
    ):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        if background_floor < 2:
            raise ValueError(
                f"background_floor must be >= 2, got {background_floor}"
            )
        self.capacity = capacity
        self.wait_timeout = wait_timeout
        self.background_floor = background_floor
        self._cv = threading.Condition()
        self.in_flight = 0
        self.waiters = 0
        self.waits = 0  # local mirror of the counter (tests, reports)
        # lane -> tenant -> _TenantQueue; lanes fixed, tenant queues are
        # created on first wait and deleted when drained (bounds memory
        # and resets a departed tenant's WRR credit).
        self._queues: dict[str, dict[str, _TenantQueue]] = {
            lane: {} for lane in QOS_LANES
        }
        self._lane_waiters = {lane: 0 for lane in QOS_LANES}
        # Contended live grants since the last background grant (or
        # since background stopped waiting) — the starvation-floor odometer.
        self._live_streak = 0
        from noise_ec_tpu.obs.registry import default_registry

        reg = default_registry()
        self._waits_total = reg.counter(
            "noise_ec_backpressure_waits_total"
        ).labels(layer="device")
        self._wait_hist = reg.histogram(
            "noise_ec_backpressure_wait_seconds"
        ).labels(layer="device")
        reg.gauge("noise_ec_backpressure_queue_depth").set_callback(
            lambda: self.in_flight + self.waiters, layer="device"
        )
        depth_gauge = reg.gauge("noise_ec_lane_queue_depth")
        for lane in QOS_LANES:
            depth_gauge.set_callback(
                lambda lane=lane: self._lane_waiters[lane], lane=lane
            )
        self._grants = {
            lane: reg.counter("noise_ec_lane_grants_total").labels(lane=lane)
            for lane in QOS_LANES
        }
        default_tracer().declare("gate_wait")

    def acquire(self) -> None:
        lane, tenant, weight = current_qos()
        with self._cv:
            if self.in_flight < self.capacity and not self._queued():
                self.in_flight += 1
                self._grants[lane].add(1)
                return
            ticket = _Ticket()
            q = self._queues[lane].get(tenant)
            if q is None:
                q = self._queues[lane][tenant] = _TenantQueue(weight)
            else:
                q.weight = max(1, int(weight))  # latest policy wins
            q.tickets.append(ticket)
            self._lane_waiters[lane] += 1
            self.waits += 1
            self._waits_total.add(1)
            t0 = time.monotonic()
            deadline = t0 + self.wait_timeout
            self.waiters += 1
            try:
                with span("gate_wait", lane=lane):
                    while not ticket.granted:
                        remaining = deadline - time.monotonic()
                        if remaining <= 0:
                            break  # governor, not a deadlock: proceed
                        self._cv.wait(min(remaining, 0.5))
            finally:
                self.waiters -= 1
                if not ticket.granted:
                    # Governor escape: leave the queue and barge. The
                    # ungranted ticket is still queued (grants happen
                    # under this same lock), so discard is exact.
                    self._discard(lane, tenant, ticket)
                    self.in_flight += 1
                    self._grants[lane].add(1)
            self._wait_hist.observe(time.monotonic() - t0)

    def release(self) -> None:
        with self._cv:
            self.in_flight -= 1
            self._grant_free_slots()
            self._cv.notify_all()

    # ------------------------------------------------ queue internals

    def _queued(self) -> bool:
        return any(self._lane_waiters[lane] for lane in QOS_LANES)

    def _discard(self, lane: str, tenant: str, ticket: _Ticket) -> None:
        q = self._queues[lane].get(tenant)
        if q is None:
            return
        try:
            q.tickets.remove(ticket)
        except ValueError:
            return
        self._lane_waiters[lane] -= 1
        if not q.tickets:
            del self._queues[lane][tenant]

    def _grant_free_slots(self) -> None:
        """Hand every free slot to the next queued ticket (lock held)."""
        while self.in_flight < self.capacity:
            picked = self._pick()
            if picked is None:
                return
            lane, ticket = picked
            ticket.granted = True
            self.in_flight += 1
            self._grants[lane].add(1)
            if lane == "background":
                self._live_streak = 0
            elif self._lane_waiters["background"]:
                self._live_streak += 1
                from noise_ec_tpu.obs.events import event

                # Rate-limited by the event log's per-name bucket; the
                # streak odometer says how starved background is.
                event("qos.preempt", lane=lane, streak=self._live_streak,
                      background_waiting=self._lane_waiters["background"])
            else:
                self._live_streak = 0

    def _pick(self) -> Optional[tuple[str, _Ticket]]:
        live = self._queues["live"]
        background = self._queues["background"]
        if background and (
            not live or self._live_streak >= self.background_floor - 1
        ):
            lane = "background"
        elif live:
            lane = "live"
        else:
            return None
        queues = self._queues[lane]
        # Smooth weighted round-robin (each queue's credit grows by its
        # weight; the max-credit queue serves and repays the total), so
        # grants interleave proportionally instead of bursting.
        total = sum(q.weight for q in queues.values())
        best_name = best = None
        for name, q in queues.items():
            q.current += q.weight
            if best is None or q.current > best.current:
                best_name, best = name, q
        best.current -= total
        ticket = best.tickets.popleft()
        self._lane_waiters[lane] -= 1
        if not best.tickets:
            del queues[best_name]
        return lane, ticket

    def __enter__(self) -> "DeviceGate":
        self.acquire()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.release()
        return False


_device_gate: Optional[DeviceGate] = None
_device_gate_lock = threading.Lock()


def device_gate() -> DeviceGate:
    """The process-wide device dispatch gate (lazy singleton)."""
    global _device_gate
    with _device_gate_lock:
        if _device_gate is None:
            _device_gate = DeviceGate()
        return _device_gate


def configure_device_gate(**kwargs) -> DeviceGate:
    """Replace the process gate (tests shrink capacity; a fresh instance
    also resets occupancy). Returns the new gate."""
    global _device_gate
    with _device_gate_lock:
        _device_gate = DeviceGate(**kwargs)
        return _device_gate


# ------------------------------------------------- device buffer pool
#
# The host↔device data path used to allocate per dispatch: a fresh
# zeroed pad buffer on the host (a full memset of k * TWp bytes even
# when only the tail columns needed zeroing), a fresh device input
# buffer, and a fresh HBM output buffer. In steady state every one of
# those is the same shape call after call. The pool closes the loop:
#
# - HOST staging: ``acquire_padded`` hands back a recycled page of the
#   right (rows, cols) shape whose pad tail is ALREADY zero (only the
#   columns the previous lease dirtied are re-zeroed), so the per-call
#   cost is the payload memcpy alone. Leases are released only after
#   the dispatch's output has materialized — the buffer backs the H2D
#   transfer, so handing it to the next caller earlier would race an
#   in-flight copy.
# - DEVICE buffers: JAX arrays are immutable, so a device input cannot
#   be refilled in place — instead the stripe-matmul entry points are
#   jitted with ``donate_argnums`` (``_fused_words_fn(..., donate=True)``)
#   so XLA recycles the input's HBM for the output and steady-state
#   encode/decode never grows the allocation high-water mark. Donation
#   is only legal for arrays this module itself staged (callers of the
#   words entries keep ownership of theirs); the pool's ``donate``
#   bookkeeping enforces the invalidated-exactly-once rule.
#
# noise_ec_device_buffer_pool_{hits,misses}_total count the staging
# reuse rate; a miss rate that climbs under steady traffic means the
# shape working set outgrew max_per_key.


class BufferLease:
    """One checked-out staging buffer (see DeviceBufferPool)."""

    __slots__ = ("arr", "key", "payload_cols")

    def __init__(self, arr: np.ndarray, key: tuple, payload_cols: int):
        self.arr = arr
        self.key = key
        self.payload_cols = payload_cols


class DeviceBufferPool:
    """Reusable host staging buffers + device donation bookkeeping
    (module comment above)."""

    def __init__(self, max_per_key: int = 8):
        self.max_per_key = max_per_key
        self._lock = threading.Lock()
        self._free: dict[tuple, list[tuple[np.ndarray, int]]] = {}
        # id(arr) -> weakref (or the array itself when weakrefs are not
        # supported); presence means the buffer was already donated.
        self._donated: dict[int, object] = {}
        from noise_ec_tpu.obs.registry import default_registry

        reg = default_registry()
        self._hits = reg.counter(
            "noise_ec_device_buffer_pool_hits_total"
        ).labels()
        self._misses = reg.counter(
            "noise_ec_device_buffer_pool_misses_total"
        ).labels()

    def acquire_padded(self, rows: int, cols: int, payload_cols: int,
                       dtype=np.uint8) -> BufferLease:
        """A (rows, cols) staging buffer whose columns >= payload_cols
        are zero. Fill ``[:, :payload_cols]`` and release after the
        dispatch's output materializes."""
        key = (rows, cols, np.dtype(dtype).str)
        with self._lock:
            stack = self._free.get(key)
            entry = stack.pop() if stack else None
        if entry is not None:
            arr, prev_payload = entry
            if payload_cols < prev_payload:
                # Only the columns the previous lease dirtied: the rest
                # of the tail is still zero from its own zeroing.
                arr[:, payload_cols:prev_payload] = 0
            self._hits.add(1)
        else:
            arr = np.zeros((rows, cols), dtype=dtype)
            self._misses.add(1)
        return BufferLease(arr, key, payload_cols)

    def release(self, lease: BufferLease) -> None:
        with self._lock:
            stack = self._free.setdefault(lease.key, [])
            if len(stack) < self.max_per_key:
                stack.append((lease.arr, lease.payload_cols))

    def donate(self, arr) -> None:
        """Record that ``arr``'s device buffer is being donated to a
        jitted call. A buffer may be invalidated exactly once; a second
        donation is a use-after-free in waiting and raises."""
        key = id(arr)
        with self._lock:
            prior = self._donated.get(key)
            if prior is not None:
                held = prior() if isinstance(prior, weakref.ref) else prior
                if held is arr:
                    raise RuntimeError(
                        "device buffer donated twice (donation invalidates "
                        "the input exactly once)"
                    )
            try:
                self._donated[key] = weakref.ref(
                    arr, lambda _, k=key: self._donated.pop(k, None)
                )
            except TypeError:  # non-weakref-able: keep a bounded record
                self._donated[key] = arr
            while len(self._donated) > 4096:
                self._donated.pop(next(iter(self._donated)))

    def was_donated(self, arr) -> bool:
        with self._lock:
            prior = self._donated.get(id(arr))
        if prior is None:
            return False
        held = prior() if isinstance(prior, weakref.ref) else prior
        return held is arr


_buffer_pool: Optional[DeviceBufferPool] = None
_buffer_pool_lock = threading.Lock()


def buffer_pool() -> DeviceBufferPool:
    """The process-wide staging buffer pool (lazy singleton)."""
    global _buffer_pool
    with _buffer_pool_lock:
        if _buffer_pool is None:
            _buffer_pool = DeviceBufferPool()
        return _buffer_pool


def configure_buffer_pool(**kwargs) -> DeviceBufferPool:
    """Replace the process pool (tests shrink max_per_key; a fresh
    instance also drops all cached buffers)."""
    global _buffer_pool
    with _buffer_pool_lock:
        _buffer_pool = DeviceBufferPool(**kwargs)
        return _buffer_pool


def donation_supported() -> bool:
    """True when the backend honors donate_argnums (TPU/GPU; the CPU
    backend ignores donation and would warn per call)."""
    try:
        return jax.default_backend() in ("tpu", "gpu")
    # noise-ec: allow(event-on-swallow) — environment probe: no backend means no donation, not an incident
    except Exception:  # noqa: BLE001 — no backend, no donation
        return False


def _ready(out):
    """Wait for a dispatched program's output — the end of a
    ``device_wait`` span — with its copy to the host already started, so
    the transfer out overlaps the thread's return to Python. Waiting
    first and starting the copy only in ``np.array`` added about 0.8 ms
    to each 10 MiB encode dispatch on a TPU v5e host with four writer
    threads: the transfer waited for the interpreter lock."""
    out.copy_to_host_async()
    return jax.block_until_ready(out)


@functools.lru_cache(maxsize=256)
def _fused_xla_fn(degree: int, r: int, k: int, S: int):
    """Compiled (masks, shards) -> product stripes, shape-generic kernel."""

    def f(masks, shards):
        planes = pack_bitplanes_jax(shards, degree)
        out = gf2_matmul_jax(masks, planes)
        return unpack_bitplanes_jax(out, r, S, degree)

    return jax.jit(f)


def _fused_words_pipeline(r: int, m: int, bits_rows: tuple, interpret: bool):
    """Words -> parity-words encode: lane pack -> sparse matmul -> unpack.

    The device never touches sub-word symbol dtypes: XLA's 8-bit (32, 128)
    tiling makes u8<->u32 bitcasts a ~19 ms relayout on v5e, while
    host-side ``ndarray.view('<u4')`` is free and HBM holds the same bytes
    either way. TW must be a multiple of ``lane_quantum(m)`` = 1024*m
    (callers pad; symbols are positionwise so zero padding is inert).

    All three stages consume/produce each other's native layouts — the
    only reshapes are leading-dim merges (metadata-only). Replacing the
    sublane pack (whose (k, TW) -> (k, G8, 8, TL) input reshape was a
    physical relayout) took the RS(10,4) 8 MiB-shard encode from 1.06 ms
    to 0.33 ms on v5e (79 -> 258 GB/s data-in).

    Falls back to the sublane kernels when the lane tile cannot fit VMEM
    (rows > ~96 at m=8).
    """
    from noise_ec_tpu.ops.pallas_pack import (
        _lane_tl,
        pack_words_lanes,
        unpack_words_lanes,
    )

    def f(words):
        from noise_ec_tpu.ops.pallas_fused import (
            NoFusedPlanError,
            fused_encode_words_planned,
        )

        k, TW = words.shape
        W8 = TW // (8 * m)
        # Tier 1: fused kernel (pack -> matmul -> unpack in VMEM, no HBM
        # intermediates — 1.4D total traffic instead of 4.2D), through the
        # verified planner: candidates (single-phase, temp-capped
        # single-phase, manual-DMA split for wide codes) are ordered by
        # estimated VPU cost and compile-probed, so a Mosaic stack OOM
        # demotes to the next plan instead of failing the encode (see
        # pallas_fused "Verified planning"). Only the no-candidate signal
        # falls through to tier 2; a ValueError out of the chosen kernel's
        # build/run is a real bug and must surface.
        try:
            return fused_encode_words_planned(
                bits_rows, words, r, m, interpret=interpret
            )
        except NoFusedPlanError:
            pass
        # Tier 2: three-kernel lane pipeline (packed planes round-trip HBM).
        mr = max(k, r)  # ONE rows budget -> ONE TL for pack AND unpack
        try:
            _lane_tl(TW, m, mr)
        except ValueError:
            return _fused_words_sublane(r, m, interpret, words)
        tiled = pack_words_lanes(words, m, rows_budget=mr, interpret=interpret)
        out = gf2_matmul_pallas_sparse_rows(
            bits_rows, tiled.reshape(k * m, 8, W8), interpret=interpret
        )  # (r*m, 8, W8)
        return unpack_words_lanes(
            out.reshape(r, m, 8, W8), rows_budget=mr, interpret=interpret
        )

    def _fused_words_sublane(r, m, interpret, words):
        from noise_ec_tpu.ops.pallas_pack import (
            pack_words_pallas,
            pack_words16_pallas,
            unpack_words_pallas,
            unpack_words16_pallas,
        )

        pack = pack_words_pallas if m == 8 else pack_words16_pallas
        unpack = unpack_words_pallas if m == 8 else unpack_words16_pallas
        k, TW = words.shape
        planes = pack(words, interpret=interpret)  # (k, m, W)
        W = planes.shape[2]
        tiled = planes.reshape(k * m, 8, W // 8)
        out = gf2_matmul_pallas_sparse_rows(
            bits_rows, tiled, interpret=interpret
        )
        planes_out = tiled_to_planes(out, W).reshape(r, m, W)
        return unpack(planes_out, interpret=interpret)

    return f


def _jit_words(f, donate: bool):
    """jit a words pipeline, donating the input words' HBM into the
    output when asked AND the backend supports it (docs/design.md
    donation rules: only callers that staged the device array themselves
    may ask — the words entries' public contract keeps caller
    ownership)."""
    if donate and donation_supported():
        return jax.jit(f, donate_argnums=(0,))
    return jax.jit(f)


@functools.lru_cache(maxsize=256)
def _fused_words_fn(r: int, bits_rows: tuple, interpret: bool,
                    donate: bool = False):
    """GF(2^8) fused encode on uint32 WORDS: (k, TW) -> (r, TW)."""
    return _jit_words(_fused_words_pipeline(r, 8, bits_rows, interpret),
                      donate)


# Pad-to multiples for the words entry points: the lane-pack grouping unit
# (8*m*128 words — see pallas_pack lane_quantum).
WORD_QUANTUM = 8192  # uint32 words; 32 KiB per shard (GF(2^8))
WORD_QUANTUM16 = 16384  # uint32 words; 64 KiB per shard (GF(2^16))


def pad_words(TW: int) -> int:
    return -(-TW // WORD_QUANTUM) * WORD_QUANTUM


def pad_words16(TW: int) -> int:
    return -(-TW // WORD_QUANTUM16) * WORD_QUANTUM16


@functools.lru_cache(maxsize=256)
def _fused_words16_fn(r: int, bits_rows: tuple, interpret: bool,
                      donate: bool = False):
    """GF(2^16) fused encode on uint32 WORDS: (k, TW) -> (r, TW).

    Each word holds two little-endian uint16 symbols; the 16x16 delta-swap
    network packs 16 planes per shard.
    """
    return _jit_words(_fused_words_pipeline(r, 16, bits_rows, interpret),
                      donate)


# ----------------------------------------------------- panel words tier


def _panel_words_pipeline(r_rows: int, m: int, bits_rows: tuple,
                          plan: tuple, interpret: bool):
    """Wide-geometry words pipeline: row-blocked lane pack -> block-panel
    K-tiled matmul -> row-blocked unpack. Same layout contract as
    _fused_words_pipeline (pack and unpack share one TL by construction
    — pallas_pack.PACK_ROW_BLOCK), so the two tiers are byte-identical
    and interchangeable per matrix."""
    from noise_ec_tpu.ops.pallas_pack import (
        pack_words_lanes_blocked,
        unpack_words_lanes_blocked,
    )

    def f(words):
        k, TW = words.shape
        W8 = TW // (8 * m)
        tiled = pack_words_lanes_blocked(words, m, interpret=interpret)
        out = gf2_matmul_pallas_panel_rows(
            bits_rows, tiled.reshape(k * m, 8, W8), plan=plan,
            interpret=interpret,
        )
        return unpack_words_lanes_blocked(
            out.reshape(r_rows, m, 8, W8), interpret=interpret
        )

    return f


@functools.lru_cache(maxsize=128)
def _panel_words_fn(r_rows: int, m: int, bits_rows: tuple, plan: tuple,
                    interpret: bool, donate: bool = False):
    """Jitted panel-tier words entry: (k, TW) u32 -> (r_rows, TW) u32
    with the (KB, RB, TL) plan baked (the plan is part of the program,
    so a plan change builds a new one, and the dispatch that compiles
    it records as ``route="compile"`` — a visible recompile, not a
    silent one)."""
    return _jit_words(
        _panel_words_pipeline(r_rows, m, bits_rows, plan, interpret),
        donate,
    )


@functools.lru_cache(maxsize=256)
def _panel_probe_compiles(bits_rows: tuple, C: int, plan: tuple) -> bool:
    """AOT-compile two lane tiles of the panel matmul under ``plan``;
    True iff Mosaic accepts it (same two-tile rationale as the fused
    planner's probe: past two tiles VMEM pressure is grid-length
    independent). With G > 1 in the plan this compiles the whole
    sub-launch CHAIN — every one of the G programs — so a Mosaic
    program-size rejection of any slice fails the probe and
    panel_plan_for escalates G instead of demoting straight to MXU."""
    TL = plan[2]
    try:
        shape = jax.ShapeDtypeStruct((C, 8, 2 * TL), jnp.uint32)

        def f(planes):
            return gf2_matmul_pallas_panel_rows(
                bits_rows, planes, plan=plan
            )

        jax.jit(f).lower(shape).compile()
        return True
    except Exception:  # noqa: BLE001 — any compile failure escalates
        log.warning("panel plan %s failed the compile probe", plan)
        return False


def tile_label(plan: tuple) -> str:
    """The (KB, RB, TL) triple as the `tile` label value of the
    noise_ec_kernel_tile_* families (temp cap and sub-launch count
    excluded: both are derived from the network + triple and the label
    set must stay bounded)."""
    return f"kb{plan[0]}_rb{plan[1]}_tl{plan[2]}"


def plan_sublaunches(plan: tuple) -> int:
    """G of a panel plan (1 for legacy 4-tuple plans)."""
    return plan[4] if len(plan) > 4 else 1


_sublaunch_children: dict[str, object] = {}


def record_sublaunch_dispatch(entry: str, g: int) -> None:
    """Count a panel-routed dispatch's G sub-launches against
    ``noise_ec_kernel_sublaunch_dispatches_total{entry}`` — the
    execution-side view of the split (the program-side count lives in
    pallas_gf2mm._record_sublaunch_program)."""
    child = _sublaunch_children.get(entry)
    if child is None:
        from noise_ec_tpu.obs.registry import default_registry

        child = _sublaunch_children[entry] = default_registry().counter(
            "noise_ec_kernel_sublaunch_dispatches_total"
        ).labels(entry=entry)
    child.add(g)


# ------------------------------------------------ persistent compile cache
#
# The sub-launch split multiplies the panel program set (G programs per
# wide geometry instead of one) and the batch ladder multiplies it
# again — and every one of those programs would be re-compiled from
# scratch on every process restart, seconds each on real hardware. The
# persistent JAX compilation cache keeps the serialized executables on
# disk keyed by program fingerprint, so a restarted node replays the
# whole set as cache hits; the ladder pre-warm hook (prewarm_ladder)
# compiles the expected program set at startup so even the FIRST
# restart after a deploy pays the compile tax off the serving path.
#
# Placement belongs to whoever runs the process: JAX reads
# JAX_COMPILATION_CACHE_DIR itself, and default_compile_cache() then
# sets no directory. Without it the cache sits at one fixed path inside
# the checkout (DEFAULT_CACHE_DIR, .gitignored) — the path is part of
# the cache key, so a directory that moved between runs would never hit.

DEFAULT_CACHE_DIR = str(Path(__file__).resolve().parents[2] / ".jax_cache")

_cache_hits_child = None
_cache_listener_installed = False


def _note_cache_event(event: str) -> None:
    """jax.monitoring listener body: count persistent-compile-cache
    hits into noise_ec_compile_cache_hits_total (split out for tests —
    the monitoring hook itself cannot be fired on demand)."""
    global _cache_hits_child
    if not event.startswith("/jax/compilation_cache/cache_hits"):
        return
    if _cache_hits_child is None:
        from noise_ec_tpu.obs.registry import default_registry

        _cache_hits_child = default_registry().counter(
            "noise_ec_compile_cache_hits_total"
        ).labels()
    _cache_hits_child.add(1)


def default_compile_cache() -> str:
    """Arm the persistent JAX compilation cache (module comment above)
    and return its directory. Call before the first jit: JAX decides
    once per process whether the cache is used (reset_cache drops that
    memo, so a late call still takes effect for later compiles).
    Size/time floors are zeroed: the program set here is many SMALL
    kernels, exactly what the defaults would skip."""
    global _cache_listener_installed
    from jax.experimental.compilation_cache import compilation_cache as cc

    cache_dir = jax.config.jax_compilation_cache_dir
    if not cache_dir:
        cache_dir = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cc.reset_cache()
    if not _cache_listener_installed:
        from jax import monitoring

        def _listener(event, **kwargs):  # noqa: ANN001 — jax hook
            _note_cache_event(event)

        monitoring.register_event_listener(_listener)
        _cache_listener_installed = True
    log.info("persistent JAX compile cache at %s", cache_dir)
    return cache_dir


def prewarm_ladder(codec: "DeviceCodec", M: np.ndarray,
                   stripe_bytes: int = 4096, max_batch: int = 8) -> int:
    """The ladder pre-warm hook: compile (and, with the persistent
    cache armed, serialize) the power-of-two batch-ladder programs for
    matrix ``M`` before traffic arrives, so geometry churn after a
    restart replays them as compile-cache hits instead of paying the
    cold-compile tax per novel batch size. Returns the number of
    ladder rungs warmed."""
    M = np.asarray(M)
    k = M.shape[1]
    warmed = 0
    B = 1
    while B <= max_batch:
        Ds = [np.zeros((k, stripe_bytes), dtype=codec.gf.dtype)
              for _ in range(B)]
        codec.matmul_stripes_many(M, Ds)
        warmed += 1
        B *= 2
    return warmed


# Whole-plane baked XOR-network kernels scale with the generator's
# set-bit count: Mosaic program size is O(XORs) and Paar factoring is
# super-linear in terms, so geometries past this raw-XOR budget leave the
# whole-plane kernels. They used to fall straight to the dense MXU
# bit-plane kernel (ops/mxu_gf2.py: fixed 64*r int8 MACs per input byte —
# a ~110 GB/s roofline at r=56, under half the ROADMAP bar); the
# block-panel tier (pallas_gf2mm "panel tier") now sits between: Paar
# factoring runs PER PANEL (seconds, not the >9 min a whole RS(200,56)
# network costs) and VMEM per grid step is panel-sized, so the XOR
# network stays on the VPU up to PANEL_XOR_BUDGET raw XORs. RS(50,20)
# (~32k raw XORs, the widest code the single-step kernel wins) stays on
# the whole-plane route.
_BAKED_XOR_BUDGET = 60_000

# The panel tier's raw-XOR ceiling on the interpret kernel (CPU tests).
# Its OWN constant, deliberately NOT aliased to _BAKED_XOR_BUDGET even
# though the values coincide today: the two budgets answer different
# questions (_BAKED_XOR_BUDGET = "when does the whole-plane kernel stop
# winning", this = "how big a network can interpret-mode afford to
# trace at all"), so tuning the baked budget must never silently move
# interpret-mode panel routing with it. Rationale for the value:
# interpret mode exists for correctness coverage, and tracing +
# XLA:CPU-compiling a multi-hundred-k-op unrolled network takes minutes
# per geometry there (measured ~220 s for RS(200,56)) — the MXU route
# is bit-exact and cheap to build, so wide interpret runs use it. Tests
# that need the panel kernels at interpret force them via the explicit
# plan override.
_PANEL_XOR_BUDGET_INTERPRET = 60_000

# The baked pipeline's pack/unpack stages hold (rows, 8, 2*TL) u32 tiles in
# VMEM regardless of the XOR cost, so a matrix with many INPUT or OUTPUT
# rows OOMs even when its network is tiny (measured: a (3, 200)
# reconstruction matrix — 19k XORs — died in pallas_pack at 24.8M scoped
# vs the 16M VMEM limit). RS(50,20) (70 rows total) is measured-good; 96
# keeps ~2x VMEM margin on the pack tile model (96*8*1024*4 = 3.1 MiB).
_BAKED_MAX_ROWS = 96


def decode1_fold_matrix(gf: GF, A: np.ndarray, j: int) -> np.ndarray:
    """(r2, m) matrix folding the single-corrupt-row decode into ONE
    generator-shaped product (the device analogue of the host shim's
    rs_decode1_fused; same per-column guarantee as matrix/bw.py).

    With aug = [A | I] the parity check over the m received rows and
    p0 the first check row seeing basis column j:

    - row 0 = e_j ^ inv(A[p0,j]) * aug[p0]  — applied to the received
      rows this is rows[j] ^ inv(A[p0,j]) * s_p0, i.e. row j with the
      single-support correction applied (the e_j and aug terms cancel
      at column j, so the corrupted row is reconstructed from the
      others — correcting a fully-corrupt row IS reconstruction);
    - rows 1.. = aug[q] ^ (A[q,j]/A[p0,j]) * aug[p0] for q != p0 —
      each is s_q ^ c_q * s_p0, zero exactly where check row q is
      consistent with the hypothesis "only row j is in error". A
      column with ANY nonzero verify byte must be re-decoded by the
      general host path; columns that verify (including clean columns,
      where s_p0 = 0 makes the correction a no-op) are exact.

    Module-level so the parallel layer can build the fold for mesh-
    sharded decode steps without constructing a DeviceCodec.
    """
    A = np.asarray(A, dtype=gf.dtype)
    r2, k = A.shape
    if r2 < 2:
        # One parity row leaves NO consistency rows: the mask would
        # claim every column verified with zero verification behind
        # it. Matches the host kernel's e >= 1 requirement (a single
        # redundant share cannot correct anyway).
        raise ValueError(
            f"single-support decode needs >= 2 check rows, got {r2}"
        )
    if not 0 <= j < k:
        raise ValueError(f"j must index a basis row, got {j}")
    nz = np.flatnonzero(A[:, j])
    if nz.size == 0:
        raise ValueError(f"check column {j} is identically zero")
    p0 = int(nz[0])
    aug = np.concatenate([A, np.eye(r2, dtype=gf.dtype)], axis=1)
    inv_c = int(gf.inv(int(A[p0, j])))
    D = np.zeros((r2, k + r2), dtype=gf.dtype)
    D[0, j] = 1
    D[0] ^= gf.mul(inv_c, aug[p0].astype(np.int64)).astype(gf.dtype)
    out_i = 1
    for q in range(r2):
        if q == p0:
            continue
        c_q = int(gf.mul(int(A[q, j]), inv_c))
        D[out_i] = aug[q] ^ gf.mul(
            c_q, aug[p0].astype(np.int64)
        ).astype(gf.dtype)
        out_i += 1
    return D


class DeviceCodec:
    """Runs GF matrix x stripes products on the default JAX device.

    This one primitive is both reference hot loops: encode is
    parity_rows @ data (main.go:262), reconstruct is
    inverted_submatrix_rows @ survivors (main.go:77).
    """

    def __init__(self, field: str = "gf256", kernel: str = "auto"):
        if field not in _FIELDS:
            raise ValueError(f"unknown field {field!r}")
        self.field = field
        self.gf: GF = _FIELDS[field]()
        self.kernel = _resolve_kernel(kernel)
        if self.kernel not in ("pallas", "pallas_interpret", "xla"):
            raise ValueError(f"unknown kernel {self.kernel!r}")
        self._mask_dev_cache: dict[bytes, jnp.ndarray] = {}
        self._rows_cache: dict[bytes, tuple] = {}
        self._cost_cache: dict[bytes, int] = {}
        self._m2w_cache: dict = {}
        self._mxu = None

    def _key(self, M: np.ndarray) -> bytes:
        return M.tobytes() + M.shape[1].to_bytes(4, "little")

    def masks_for(self, M: np.ndarray) -> np.ndarray:
        """(r, k) GF matrix -> (m*r, m*k) uint32 select-mask matrix, cached."""
        return expand_generator_masks_cached(self.gf, M)

    def _panel_xor_budget(self) -> int:
        """The raw-XOR ceiling of the panel tier for THIS codec's kernel
        (module comment at _PANEL_XOR_BUDGET_INTERPRET: interpret mode
        cannot afford multi-hundred-k-op unrolled programs)."""
        if self.kernel == "pallas_interpret":
            return _PANEL_XOR_BUDGET_INTERPRET
        return PANEL_XOR_BUDGET

    def bits_rows_for(self, M: np.ndarray) -> tuple:
        """(r, k) GF matrix -> hashable per-row term tuples for the sparse
        kernel (cached).

        The shared choke point for EVERY baked-kernel entry (words,
        planes, byte-sliced, panel), so the PLANNING-TIME guard lives
        here: a network past THIS KERNEL'S panel budget must never reach
        Paar factoring (the panel tier factors per panel, but raw
        expansion/term-listing of a truly huge network is itself wasted
        work) or bake an unboundedly large program, through any path.
        Only the XOR-cost bound applies at this level — the row bound
        models the words entries' pack-stage VMEM, which the planes
        entry never runs, so it is enforced by route_for at the
        words/stripes routing decision instead (a (3, 200)
        reconstruction matrix stays legal here for matmul_planes).
        matmul_stripes/matmul_words route over-budget matrices to the
        MXU before ever calling this; direct callers get the clear error.
        """
        if self._xor_cost_for(M) > self._panel_xor_budget():
            raise NotImplementedError(
                "matrix exceeds the panel-tier XOR budget; use "
                "matmul_stripes/matmul_words (gf256) or the byte-sliced "
                "entries (gf65536) — the MXU route"
            )
        M = np.ascontiguousarray(np.asarray(M, dtype=self.gf.dtype))
        key = self._key(M)
        hit = self._rows_cache.get(key)
        if hit is None:
            hit = bits_to_rows(expand_generator_bits(self.gf, M))
            if len(self._rows_cache) > 4096:
                self._rows_cache.clear()
            self._rows_cache[key] = hit
        return hit

    def _xor_cost_for(self, M: np.ndarray) -> int:
        """Raw two-input XOR count of M's GF(2) bit-network (set bits
        minus output rows), cached — the route_for decision input."""
        M = np.ascontiguousarray(np.asarray(M, dtype=self.gf.dtype))
        key = self._key(M)
        hit = self._cost_cache.get(key)
        if hit is None:
            bits = expand_generator_bits(self.gf, M)
            hit = int(np.count_nonzero(bits)) - bits.shape[0]
            if len(self._cost_cache) > 4096:
                self._cost_cache.clear()
            self._cost_cache[key] = hit
        return hit

    def route_for(self, M: np.ndarray) -> str:
        """Which kernel family runs this matrix: "baked" (whole-plane
        XOR-network VPU kernels), "panel" (block-panel K-tiled VPU
        kernels — wide geometries), or "mxu" (dense int8 bit-plane
        matmul — past every XOR-network budget). Exposed so tests can
        pin the tier decision; NO supported geometry raises here — the
        old "must not even attempt" refusal became this routing.

        The row bound counts the rows the WHOLE-PLANE pipeline runs:
        symbol rows for gf256, 2x byte rows for the byte-sliced wide
        field — one bound (_BAKED_MAX_ROWS) for the one pack stage both
        share. Past the row bound OR the whole-plane XOR budget the
        matrix moves to the panel tier (row-blocked pack, K-tiled
        matmul — no whole-matrix VMEM residency, so no row bound), and
        past this kernel's panel XOR budget to the MXU.
        """
        r, k = np.asarray(M).shape
        rows = 2 * max(r, k) if self.gf.degree == 16 else max(r, k)
        cost = self._xor_cost_for(M)
        if cost > self._panel_xor_budget():
            return "mxu"
        if rows > _BAKED_MAX_ROWS or cost > _BAKED_XOR_BUDGET:
            return "panel"
        return "baked"

    def panel_plan_for(self, M: np.ndarray):
        """The verified (KB, RB, TL, temp_cap, G) panel plan for a
        panel-routed matrix, or None when no split compiles (the
        dispatch then falls back to the MXU route). Cached per matrix;
        the plan triple AND the sub-launch count G join the dispatch
        cache key, the triple labels the ``noise_ec_kernel_tile_*``
        telemetry.

        G starts at the program-size model's choice
        (``panel_plan`` / ``sublaunch_count``: estimated Mosaic op
        count per sub-launch vs PANEL_SUBLAUNCH_XOR_BUDGET) and the
        AOT probe confirms it. A Mosaic rejection ESCALATES G
        (doubling, capped at PK = one K-block per launch) and
        re-probes; only when even G = PK fails does the matrix demote
        to the MXU route — the split path replaced the old
        demote-on-first-rejection behavior."""
        bits_rows = self.bits_rows_for(M)
        m = self.gf.degree
        C = (2 * M.shape[1] * 8) if m == 16 else (M.shape[1] * 8)
        plan = panel_plan(bits_rows, C)
        if self.kernel == "pallas_interpret":
            return plan  # no scoped-vmem limit to probe against
        PK = max(1, -(-C // plan[0]))
        while True:
            if _panel_probe_compiles(bits_rows, C, plan):
                return plan
            G = plan[4]
            if G >= PK:
                log.warning(
                    "panel plan %s rejected even at G = K-blocks; "
                    "demoting matrix to the MXU route", plan,
                )
                return None
            plan = plan[:4] + (min(PK, G * 2),)
            log.info(
                "panel probe escalating to %d sub-launches for a "
                "%d-col network", plan[4], C,
            )

    def _route_plan(self, M: np.ndarray):
        """(route, plan): the tier decision plus, for the panel tier,
        the verified tile plan. A panel-routed matrix whose plan fails
        the compile probe demotes to ("mxu", None) here — the one place
        the demotion can happen, so every entry point agrees."""
        route = self.route_for(M)
        if route != "panel":
            return route, None
        plan = self.panel_plan_for(M)
        return ("panel", plan) if plan is not None else ("mxu", None)

    def _m2_for_wide(self, M: np.ndarray):
        """Cached (16r, 16k) int8 bit expansion of a gf65536 matrix for
        the byte-sliced MXU route (shared implementation — see
        mxu_gf2.cached_bit_expansion for the key scheme, bound, and
        tracer-leak guard)."""
        from noise_ec_tpu.ops.mxu_gf2 import cached_bit_expansion

        return cached_bit_expansion(self._m2w_cache, self.gf, M, bound=64)

    def _mxu_for(self):
        if self._mxu is None:
            from noise_ec_tpu.ops.mxu_gf2 import MxuCodec

            self._mxu = MxuCodec(
                self.gf, interpret=self.kernel == "pallas_interpret"
            )
        return self._mxu

    def supports_matrix(self, M: np.ndarray) -> bool:
        """Cheap predicate: does a device kernel exist for ``M``?

        Always True since the wide-field MXU route landed — every matrix
        has a device route on the stripes/byte-sliced entries (baked
        network or dense MXU). Kept as an API so decode dispatch code
        written against the predicate keeps working, and as the hook if a
        future backend ever reintroduces an unsupported region.
        """
        del M
        return True

    def supports_syndrome(self, A: np.ndarray) -> bool:
        """supports_matrix for the syndrome route (see supports_matrix)."""
        del A
        return True

    def matmul_stripes(self, M: np.ndarray, D) -> np.ndarray:
        """(r, k) GF matrix x (k, S) stripes -> (r, S), computed on device.

        Device-telemetry wrapper: every dispatch lands in
        ``noise_ec_device_op_seconds{kernel,route}`` — as
        ``route="compile"`` when JAX compiled inside it (feeding the
        recompile counter), else ``route="execute"``. This entry
        materializes the result on host, so the timing covers the device
        round trip, not just the async submit (obs/device.py); inside
        it, ``device_wait`` spans the program call through
        ``block_until_ready`` and ``readback`` the copy to the host.
        """
        M = np.asarray(M)
        D = np.asarray(D, dtype=self.gf.dtype)
        r, k = M.shape
        if D.shape[0] != k:
            raise ValueError(f"matrix cols {k} != stripe rows {D.shape[0]}")
        entry = f"matmul_stripes_{self.kernel}"
        record_kernel(entry, D.nbytes)
        # Bounded device queue: admission BEFORE the telemetry window so
        # a gated wait reads as backpressure, not kernel latency.
        with device_gate(), device_op(entry, nbytes=D.nbytes) as dt:
            return self._matmul_stripes_dispatch(M, D, dt)

    def _matmul_stripes_dispatch(self, M: np.ndarray, D: np.ndarray,
                                 dt) -> np.ndarray:
        r, k = M.shape
        S = D.shape[1]
        m = self.gf.degree
        if self.kernel == "xla":
            fn = _fused_xla_fn(m, r, k, S)
            with span("device_wait"):
                masks_dev = jnp.asarray(self.masks_for(M))
                D_dev = jnp.asarray(D)
                out = _ready(fn(masks_dev, D_dev))
            if dt.compiled():
                # Roofline: cost_analysis of the freshly cached program
                # (rate-limited per entry — the AOT walk is not free and
                # must not ride a geometry-churn storm).
                maybe_analyze_program(dt.entry, fn, masks_dev, D_dev)
            # np.array (copy) so callers get an ordinary writable ndarray,
            # not a read-only view of the device buffer.
            with span("readback"):
                return np.array(out)
        if m == 16:
            # PACKED BYTE-SLICED GF(2^16): each u16 symbol splits into
            # ADJACENT (lo, hi) byte rows (the packed (2k, S) panel —
            # pallas_pack.pack_u16_bytesliced), and the device runs the
            # GF(2^8)-shaped m=8 pipeline — the expanded bit matrix needs
            # NO permutation because the flat plane index is identical:
            # 16*j + b == (2*j + b//8)*8 + b%8. This trades two host
            # relayout passes for the 3-round delta-swap transpose
            # (vs 4 rounds for 16-plane groups) and the m=8 lane quantum.
            from noise_ec_tpu.ops.pallas_pack import (
                pack_u16_bytesliced,
                unpack_u16_bytesliced,
            )

            out_b = self._bytesliced_words(
                M, pack_u16_bytesliced(D), 2 * r, dt
            )
            return unpack_u16_bytesliced(out_b)
        route, plan = self._route_plan(M)
        if route == "mxu":
            # Past every XOR-network budget (_BAKED_XOR_BUDGET /
            # PANEL_XOR_BUDGET, or a panel plan the probe demoted):
            # dense MXU bit-plane product.
            # Already charged to matmul_stripes_{kernel} above; a second
            # record here would double-count the traffic.
            return self._mxu_for().encode_stripes(M, D)
        TWp = pad_words(-(-S // 4))
        lease = None
        if 4 * TWp != S:
            # Pooled staging page with a pre-zeroed pad tail: the per-call
            # cost is the payload memcpy, not an allocation + full memset.
            lease = buffer_pool().acquire_padded(
                k, 4 * TWp, S, dtype=self.gf.dtype
            )
            buf = lease.arr
            buf[:, :S] = D
        else:
            buf = np.ascontiguousarray(D)
        words = buf.view("<u4")
        # This entry stages its own device array (device_put below), so
        # the input HBM is donated into the output: steady-state encode /
        # reconstruct reuses one allocation instead of growing two.
        if route == "panel":
            dt.tile = tile_label(plan)
            record_sublaunch_dispatch(dt.entry, plan_sublaunches(plan))
            fn = _panel_words_fn(
                r, 8, self.bits_rows_for(M), plan,
                self.kernel == "pallas_interpret", True,
            )
        else:
            fn = _fused_words_fn(
                r, self.bits_rows_for(M),
                self.kernel == "pallas_interpret", True,
            )
        # device_wait: transfer in, queue and kernel; readback below is
        # the rest of the transfer out and the host copies.
        with span("device_wait"):
            words_dev = jax.device_put(words)
            if donation_supported():
                buffer_pool().donate(words_dev)
            out = _ready(fn(words_dev))
        if lease is not None:
            # Output ready => the H2D copy is long done; the staging page
            # is safe to hand to the next dispatch.
            buffer_pool().release(lease)
        if dt.compiled():
            # ShapeDtypeStruct, not the live array: the input was donated
            # and must not be touched again.
            maybe_analyze_program(
                dt.entry, fn, jax.ShapeDtypeStruct(words.shape, words.dtype)
            )
        # np.array: writable copy (np.asarray of a jax array is read-only
        # and callers are promised an ordinary ndarray).
        with span("readback"):
            out_w = np.array(out)
            return np.ascontiguousarray(out_w.view(self.gf.dtype)[:, :S])

    def matmul_stripes_many(self, M: np.ndarray, Ds: list) -> list:
        """B same-shape stripes products through ONE gated dispatch.

        The CoalescingDispatcher's batch entry: concurrent live requests
        sharing (matrix, stripe shape) stack into a single
        ``matmul_words_batch``-class device call (vmap over the batch
        axis) on the baked GF(2^8) routes, or a stripe-axis concatenation
        (symbols are positionwise, so ``M @ [D1|D2|..]`` is exact) on the
        XLA kernel and the byte-sliced wide field. Results are
        byte-identical to B separate :meth:`matmul_stripes` calls; one
        DeviceGate slot and one telemetry window cover the whole batch.
        """
        Ds = [np.asarray(D, dtype=self.gf.dtype) for D in Ds]
        if not Ds:
            return []
        if len(Ds) == 1:
            return [self.matmul_stripes(M, Ds[0])]
        M = np.asarray(M)
        r, k = M.shape
        S = Ds[0].shape[1]
        for D in Ds:
            if D.shape != (k, S):
                raise ValueError(
                    "matmul_stripes_many requires same-shape stripes "
                    f"(got {D.shape} vs {(k, S)})"
                )
        # Batch-size LADDER: runtime batch sizes are whatever concurrency
        # produced (3 today, 7 the next call), but every distinct batched
        # shape is its own jitted program — unquantized, a traffic wave
        # would compile once per novel size (seconds each on the
        # chip). Rounding B up to the next power of two bounds the
        # program set to log2(max_batch) variants; the pad members are
        # DISCARDED rows, so they need no zeroing — whatever bytes the
        # pooled staging page already holds are valid GF symbols.
        B = len(Ds)
        B_pad = 1 << (B - 1).bit_length()
        entry = f"matmul_stripes_{self.kernel}"
        nbytes = sum(D.nbytes for D in Ds)
        record_kernel(entry, nbytes)
        with device_gate(), device_op(entry, nbytes=nbytes) as dt:
            if self.kernel != "xla" and self.gf.degree == 8:
                return self._stripes_many_words(M, Ds, B_pad, dt)
            # Mesh dispatch tier (parallel/mesh.py, docs/design.md §13):
            # the batch dimension shards over the "stripes" axis of all
            # visible chips — the XLA kernel on the pjit tier, the baked
            # wide field on the byte-sliced words tier. Same gate slot,
            # telemetry window and breaker wrapping as the single-device
            # routes (a mesh fault fans out through the callers' own
            # fallback arms like any other dispatch error).
            from noise_ec_tpu.parallel.mesh import mesh_router

            router = mesh_router()
            if router.should_shard(B_pad):
                if self.kernel == "xla":
                    return router.matmul_sym_many(self, M, Ds, B_pad)
                if self.gf.degree == 16 and self._route_plan(M)[0] != "mxu":
                    return router.matmul_bytesliced_many(self, M, Ds, B_pad)
            pad = (
                [np.empty((k, (B_pad - B) * S), dtype=self.gf.dtype)]
                if B_pad != B else []
            )
            out = self._matmul_stripes_dispatch(
                M, np.concatenate(Ds + pad, axis=1), dt
            )
            return [
                np.ascontiguousarray(out[:, b * S : (b + 1) * S])
                for b in range(B)
            ]

    def _stripes_many_words(self, M: np.ndarray, Ds: list, B_pad: int,
                            dt) -> list:
        """GF(2^8) batch route: stack into (B_pad, k, TWp) pooled staging
        words and run the one vmapped fused dispatch."""
        B = len(Ds)
        k, S = Ds[0].shape
        TWp = pad_words(-(-S // 4))
        lease = buffer_pool().acquire_padded(B_pad * k, 4 * TWp, S)
        buf = lease.arr
        for b, D in enumerate(Ds):
            buf[b * k : (b + 1) * k, :S] = D
        words = buf.view("<u4").reshape(B_pad, k, TWp)
        with span("device_wait"):
            out = _ready(self._matmul_words_batch_dispatch(M, words, dt))
        buffer_pool().release(lease)
        with span("readback"):
            res = np.array(out).view(self.gf.dtype)  # (B_pad, r, 4*TWp)
            return [np.ascontiguousarray(res[b, :, :S]) for b in range(B)]

    def syndrome_stripes(
        self, A: np.ndarray, rows: np.ndarray
    ) -> tuple[np.ndarray, np.ndarray]:
        """Decode syndrome on device: s = A @ rows[:k] ^ rows[k:].

        ``A`` is the (m-k, k) basis-prediction matrix from the
        error-correcting decode (matrix/bw.py); ``rows`` the full (m, S)
        received stripes. Because XOR is addition in the field, the fused
        form is ONE generator-shaped device matmul with the augmented
        matrix [A | I] over all m rows — the same kernel as encode, so the
        decode guarantee (infectious Decode, /root/reference/main.go:77)
        rides the 400 GB/s path when stripes are device-resident. Returns
        (s, per-column nonzero-row counts); the count reduction is host-side
        (O(S) bytes, negligible next to the matmul).
        """
        A = np.asarray(A, dtype=self.gf.dtype)
        r2, k = A.shape
        rows = np.asarray(rows, dtype=self.gf.dtype)
        if rows.shape[0] != k + r2:
            raise ValueError(f"expected {k + r2} rows, got {rows.shape[0]}")
        aug = np.concatenate(
            [A, np.eye(r2, dtype=self.gf.dtype)], axis=1
        )
        s = self.matmul_stripes(aug, rows)
        return s, np.count_nonzero(s, axis=0)

    def decode1_matrix(self, A: np.ndarray, j: int) -> np.ndarray:
        """See :func:`decode1_fold_matrix` (instance sugar over self.gf)."""
        return decode1_fold_matrix(self.gf, A, j)

    def decode1_words(
        self, A: np.ndarray, j: int, rows_words
    ) -> tuple:
        """Device-resident single-corrupt-row decode step.

        ``rows_words``: (m, TW) uint32 device words of all m received
        stripes. Returns (corrected_row_j_words (TW,), verify_or (TW,))
        — verify_or is the OR-fold of the consistency rows; a byte of it
        nonzero means that byte column defeated the single-support
        hypothesis and must go through the general path. One fused
        generator-shaped matmul (same kernel and rate class as encode)
        plus an elementwise OR — jit-composable for chained timing.
        """
        D = self.decode1_matrix(A, j)  # raises for r2 < 2 (no verify rows)
        out = self.matmul_words(D, rows_words)
        corrected = out[0]
        bad = out[1]
        for q in range(2, out.shape[0]):
            bad = bad | out[q]
        return corrected, bad

    def _bytesliced_words(self, M: np.ndarray, Db: np.ndarray,
                          r2: int, dt=None) -> np.ndarray:
        """(2k, S) uint8 packed byte rows x the gf65536 matrix ->
        (2r, S) uint8.

        Runs the m=8 words pipeline over byte rows with the UNPERMUTED
        expanded GF(2^16) bits (see matmul_stripes).
        """
        k2, S = Db.shape
        TWp = pad_words(-(-S // 4))
        if 4 * TWp != S:
            buf = np.zeros((k2, 4 * TWp), dtype=np.uint8)
            buf[:, :S] = Db
        else:
            buf = np.ascontiguousarray(Db)
        route, plan = self._route_plan(M)
        if route == "mxu":
            # Over-budget wide-field matrices run the dense MXU kernel
            # directly on the byte rows: the kernel is pure GF(2) and
            # the UNPERMUTED (16r, 16k) expansion over 2k byte rows IS
            # an (8R, 8K) bit matrix with R = 2r, K = 2k. Same route
            # gate as gf256 (route_for), closing the round-5 refusal gap.
            from noise_ec_tpu.ops.mxu_gf2 import mxu_encode_words_bits

            out_w = np.array(mxu_encode_words_bits(
                self._m2_for_wide(M), buf.view("<u4"),
                r=r2, k=k2,
                interpret=self.kernel == "pallas_interpret",
            ))
            return out_w.view(np.uint8)[:, :S]
        if route == "panel":
            if dt is not None:
                dt.tile = tile_label(plan)
            record_sublaunch_dispatch(
                dt.entry if dt is not None else "matmul_words_bytesliced",
                plan_sublaunches(plan),
            )
            fn = _panel_words_fn(
                r2, 8, self.bits_rows_for(M), plan,
                self.kernel == "pallas_interpret",
            )
        else:
            fn = _fused_words_fn(
                r2, self.bits_rows_for(M),
                self.kernel == "pallas_interpret",
            )
        out_w = np.array(fn(jnp.asarray(buf.view("<u4"))))
        return out_w.view(np.uint8)[:, :S]

    def matmul_words_bytesliced(self, M: np.ndarray,
                                words: jnp.ndarray) -> jnp.ndarray:
        """Device-resident BYTE-SLICED gf65536 words entry.

        ``words`` is (2k, TW8) uint32 over byte rows (shard j's lo-byte
        row at 2j, hi-byte row at 2j+1 — the framework's device-resident
        GF(2^16) layout); returns (2r, TW8) parity byte-row words. This
        is the fast path the bench times; ``matmul_words`` keeps the
        interleaved-u16 contract on the 16-plane kernels for callers
        holding that layout.
        """
        if self.gf.degree != 16:
            raise ValueError("matmul_words_bytesliced is gf65536-only")
        r2 = 2 * M.shape[0]
        TW = words.shape[1]
        TWp = pad_words(TW)
        route, plan = self._route_plan(M)
        if route == "mxu":
            # Over-budget wide-field matrices: the dense MXU kernel
            # over the same byte rows (see _bytesliced_words).
            from noise_ec_tpu.ops.mxu_gf2 import mxu_encode_words_bits

            fn = functools.partial(
                mxu_encode_words_bits,
                self._m2_for_wide(M),
                r=r2,
                k=2 * M.shape[1],
                interpret=self.kernel == "pallas_interpret",
            )
        elif route == "panel":
            record_sublaunch_dispatch(
                "matmul_words_bytesliced", plan_sublaunches(plan)
            )
            fn = _panel_words_fn(
                r2, 8, self.bits_rows_for(M), plan,
                self.kernel == "pallas_interpret",
            )
        else:
            fn = _fused_words_fn(
                r2, self.bits_rows_for(M), self.kernel == "pallas_interpret"
            )
        if TWp != TW:
            return fn(jnp.pad(words, ((0, 0), (0, TWp - TW))))[:, :TW]
        return fn(words)

    def decode1_words_bytesliced(
        self, A: np.ndarray, j: int, rows_words: jnp.ndarray
    ) -> tuple:
        """Device-resident single-corrupt-row decode on the PACKED
        byte-sliced GF(2^16) layout (the wide-field analogue of
        :meth:`decode1_words`).

        ``rows_words``: (2m, TW8) uint32 packed byte-sliced words of
        all m received stripes (share i's lo-byte row at 2i, hi at
        2i+1 — pallas_pack.words16_to_bytesliced). Returns
        (corrected_lo_hi (2, TW8), verify_or (TW8,)): the corrected row
        j as its two byte rows, and the OR-fold of every consistency
        BYTE row — a nonzero byte defeats the single-support hypothesis
        for that column exactly as in the gf256 entry (a u16 column is
        bad iff either of its byte columns is). One generator-shaped
        byte-sliced matmul, so GF(2^16) decode rides the same m=8
        kernel tier (and panel route, when wide) as GF(2^8) instead of
        the 4-round 16-plane expansion that doubled its round count.
        """
        D = self.decode1_matrix(A, j)  # raises for r2 < 2
        out = self.matmul_words_bytesliced(D, rows_words)  # (2*r2, TW8)
        corrected = out[:2]
        bad = out[2]
        for q in range(3, out.shape[0]):
            bad = bad | out[q]
        return corrected, bad

    def matmul_words(self, M: np.ndarray, words: jnp.ndarray) -> jnp.ndarray:
        """Device-resident words entry: (k, TW) uint32 -> (r, TW) uint32.

        The words ARE the shard bytes (little-endian u32 view; 4 GF(2^8) or
        2 GF(2^16) symbols per word). Any TW is accepted: non-quantum sizes
        are zero-padded on device and the product sliced back (symbols are
        positionwise, so padding is inert; under an enclosing jit the
        pad/slice fuse into the program). This is the zero-relayout hot
        path used by bench and the parallel layer.
        """
        return self.matmul_words_batch(M, words[None])[0]

    def matmul_words_batch(self, M: np.ndarray, words: jnp.ndarray, *,
                           donate: bool = False) -> jnp.ndarray:
        """Batched words entry: (B, k, TW) uint32 -> (B, r, TW) uint32.

        vmap of the fused lane pipeline per object (the same kernels the
        single-object path runs; vmap adds a grid dimension).
        ``matmul_words`` delegates here with B=1; the streaming encoder
        uses it directly for many same-geometry device-resident objects.

        ``donate=True`` is an explicit caller opt-in that the input device
        array will never be touched again: on TPU/GPU the B=1 baked route
        then donates the words' HBM into the output (the streaming
        encoder's steady-state no-realloc contract). The default keeps
        caller ownership — bench's chained loops reuse their input.
        """
        if self.kernel == "xla":
            raise ValueError(
                "matmul_words/matmul_words_batch require a pallas kernel; "
                "use matmul_stripes (or BatchCodec.encode_batch) on the XLA path"
            )
        M = np.asarray(M)
        nbytes = 4 * int(np.prod(words.shape))
        record_kernel("matmul_words", nbytes)
        # Async-entry caveat: this path returns a device array without
        # materializing, so the execute-route timing is the submit cost;
        # the compile route still times the synchronous trace+compile.
        # Same bounded-queue admission as matmul_stripes (device gate).
        with device_gate(), device_op("matmul_words", nbytes=nbytes) as dt:
            return self._matmul_words_batch_dispatch(
                M, words, dt, donate=donate
            )

    def _matmul_words_batch_dispatch(self, M: np.ndarray, words: jnp.ndarray,
                                     dt, donate: bool = False) -> jnp.ndarray:
        # Mesh dispatch tier (parallel/mesh.py, docs/design.md §13): a
        # real batch on the baked GF(2^8) route shards its batch axis
        # over the "stripes" mesh axis — ONE shard_map program of the
        # same vmapped fused pipeline, donate_argnums preserved
        # per-shard. The router's compile helper quantizes to the
        # power-of-two ladder, so program count stays bounded. Roofline
        # analysis is skipped here (the mesh families carry their own
        # dispatch/shard-bytes telemetry).
        if words.shape[0] > 1 and self.gf.degree == 8 and (
            self._route_plan(M)[0] != "mxu"
        ):
            from noise_ec_tpu.parallel.mesh import mesh_router

            router = mesh_router()
            if router.should_shard(words.shape[0]):
                return router.matmul_words_batch(
                    self, M, words, donate=donate
                )
        TW = words.shape[2]
        TWp = pad_words(TW) if self.gf.degree == 8 else pad_words16(TW)
        route, plan = self._route_plan(M)
        if self.gf.degree == 8 and route == "mxu":
            # Past every XOR-network budget (see _BAKED_XOR_BUDGET /
            # PANEL_XOR_BUDGET): the dense MXU product, same words
            # contract. WORD_QUANTUM is a multiple of the MXU lane
            # tile, so the padding below fits both kernel families.
            mx = self._mxu_for()
            fn = functools.partial(mx.encode_words, M)
        else:
            if self.gf.degree == 16 and route == "mxu":
                # The MXU route consumes BYTE rows; this entry's
                # interleaved-u16 layout has no kernel at this size.
                raise NotImplementedError(
                    "over-budget GF(2^16) matrices run the MXU route "
                    "on the byte-sliced entries (matmul_words_bytesliced "
                    "/ matmul_stripes), not the interleaved words entry"
                )
            # Donation only on the single-object baked route: vmap wraps
            # the jit (donation would not thread through), and a padded
            # input is a fresh on-device copy anyway.
            donate = donate and words.shape[0] == 1 and TWp == TW
            if route == "panel":
                # Panel tier — the interleaved entry rides the m=16
                # blocked pack; the packed byte-sliced entries stay the
                # wide-field fast path (3 rounds, m=8 quantum).
                dt.tile = tile_label(plan)
                record_sublaunch_dispatch(
                    dt.entry, plan_sublaunches(plan)
                )
                fn = _panel_words_fn(
                    M.shape[0], self.gf.degree, self.bits_rows_for(M),
                    plan, self.kernel == "pallas_interpret", donate,
                )
            else:
                mk = (_fused_words_fn if self.gf.degree == 8
                      else _fused_words16_fn)
                fn = mk(
                    M.shape[0], self.bits_rows_for(M),
                    self.kernel == "pallas_interpret", donate,
                )
        if TWp != TW:
            words = jnp.pad(words, ((0, 0), (0, 0), (0, TWp - TW)))
        if words.shape[0] == 1:
            # Single object: skip the vmap wrapper (its extra grid
            # dimension measurably slows wide codes — RS(50,20) 243 vs
            # 201 GB/s on v5e).
            shape0 = jax.ShapeDtypeStruct(words.shape[1:], words.dtype)
            out = fn(words[0])[None]
        else:
            shape0 = jax.ShapeDtypeStruct(words.shape[1:], words.dtype)
            out = jax.vmap(fn)(words)
        if dt.compiled():
            # Best-effort: the MXU partial has no .lower and a traced
            # call passes tracers; the analysis degrades to None. Shape
            # struct, not the live array — it may have been donated.
            maybe_analyze_program("matmul_words", fn, shape0)
        return out[:, :, :TW] if TWp != TW else out

    def matmul_planes(self, M: np.ndarray, planes: jnp.ndarray) -> jnp.ndarray:
        """Device-level entry on packed (C, W) planes (HBM-resident path).

        Returns (m*r, W) planes on device; used by benches and the parallel
        layer to avoid host round-trips.
        """
        W = planes.shape[1]
        if self.kernel == "xla":
            M = np.ascontiguousarray(np.asarray(M, dtype=self.gf.dtype))
            key = self._key(M)
            dev = self._mask_dev_cache.get(key)
            if dev is None:
                dev = jnp.asarray(self.masks_for(M))
                if len(self._mask_dev_cache) > 1024:
                    self._mask_dev_cache.clear()
                self._mask_dev_cache[key] = dev
            return _gf2_matmul_jax_jit(dev, planes)
        M = np.asarray(M)
        route, plan = self._route_plan(M)
        if route == "mxu":
            raise NotImplementedError(
                "over-budget matrices have no planes-level XOR-network "
                "kernel; use matmul_stripes/matmul_words (the MXU route)"
            )
        if route == "panel":
            record_sublaunch_dispatch(
                "matmul_planes", plan_sublaunches(plan)
            )
            out = gf2_matmul_pallas_panel_rows(
                self.bits_rows_for(M),
                planes_to_tiled(planes),
                plan=plan,
                interpret=self.kernel == "pallas_interpret",
            )
        else:
            out = gf2_matmul_pallas_sparse_rows(
                self.bits_rows_for(M),
                planes_to_tiled(planes),
                interpret=self.kernel == "pallas_interpret",
            )
        return tiled_to_planes(out, W)
