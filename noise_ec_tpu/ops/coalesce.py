"""Live-path coalescing: batch concurrent same-shape matmul requests.

The store's repair engine proved the shape (PR 2): same-geometry stripes
folded into ONE batched device reconstruct turn B dispatch round trips
into one. But that trick lived behind the repair queue only — the LIVE
paths (plugin encode/decode, the object service, the fleet lab) still
dispatched one device call per request, so heavy concurrent traffic paid
per-call dispatch overhead B times. ``CoalescingDispatcher`` generalizes
the trick to every codec matmul: concurrent requests for the same
(backend, field, matrix, stripe-shape) bucket are batched into a single
batched dispatch (``DeviceCodec.matmul_stripes_many`` →
``matmul_words_batch`` on the device route) and the results fanned back
out to the waiting callers.

Flush policy (admission and batching share one queue):

- a lone request on an idle dispatcher flushes IMMEDIATELY — coalescing
  must never tax the uncontended path;
- when other coalesced work is already in flight (or another thread
  submitted within the hot window), the bucket leader lingers up to
  ``max(linger_seconds, linger_seconds * device-gate depth)`` — a
  bounded latency budget that grows only when the device queue is
  already deep (the request would have waited at the
  :class:`~noise_ec_tpu.ops.dispatch.DeviceGate` anyway, so the linger
  is free) — collecting followers before dispatching;
- a full bucket (``max_batch``) flushes at once;
- explicit batches (:meth:`submit_many` — the repair engine's group
  dispatch) merge into any open bucket for their key and flush without
  linger: they already ARE a batch;
- idempotent reads (:meth:`submit_shared` — the object service's
  per-(address, stripe) decoded-stripe fetch) ride a SINGLE-FLIGHT
  tier: same-key callers share one in-flight call's result (followers
  join even mid-call), flushed as ``reason="shared"``.

The batch function runs on the leader's thread; an exception propagates
to every member (each caller then applies its own fallback — e.g. the
codec breaker's golden-host degradation, so a breaker trip mid-batch
still returns correct bytes to all members through their own ``_mul``
fallback arm).

Metrics: ``noise_ec_coalesce_batches_total``,
``noise_ec_coalesce_flush_reason_total{reason}`` and
``noise_ec_coalesce_batch_size`` (one observation PER MEMBER request —
the distribution answers "what batch size did a request ride", so a p50
above 1 means most requests were amortized). The time a request spends
waiting here is the ``coalesce_wait`` span: a leader's linger
(``role="leader"``) and a follower's wait for its batch
(``role="follower"``).
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from typing import Callable, Optional, Sequence

from noise_ec_tpu.obs.trace import default_tracer, span

__all__ = [
    "CoalescingDispatcher",
    "QOS_LANES",
    "coalescer",
    "configure_coalescer",
    "current_qos",
    "qos_lane",
]

# A follower must never wait forever on a leader that died violently
# (thread killed between append and flush); after this many seconds it
# raises instead of hanging the receive path.
_FOLLOWER_TIMEOUT_S = 120.0

# ------------------------------------------------------------ QoS lanes
#
# The device gate and this dispatcher are SHARED by every producer in
# the process: live GET decodes, repair drains, scrub verifies, archival
# conversions. Without classification, one tenant's decode storm (or a
# background repair burst) queues ahead of everyone at the gate — the
# noisy-neighbor tail the ISSUE's DRF-style fairness addresses. The QoS
# context is a thread-local (lane, tenant, weight) tag set by the layer
# that KNOWS the traffic class (the object service tags per-tenant live
# work from the Tenant policy grammar; repair/scrub/convert/rebalance
# loops tag themselves background) and read by the admission points
# (DeviceGate.acquire's weighted lane queues, this dispatcher's linger
# budget). Thread-local — not a call argument — because the tag must
# survive the codec call stack without threading a parameter through
# every matmul signature. A coalesced batch runs on its leader's thread
# and therefore rides the leader's lane; members of one bucket share a
# (backend, field, matrix, shape) key, so cross-lane mixing inside one
# batch is bounded by the linger window and costs at most one batch.

QOS_LANES = ("live", "background")

_qos_local = threading.local()


def current_qos() -> tuple[str, str, int]:
    """The calling thread's ``(lane, tenant, weight)`` QoS tag —
    ``("live", "", 1)`` outside any :func:`qos_lane` scope."""
    return getattr(_qos_local, "ctx", ("live", "", 1))


@contextmanager
def qos_lane(lane: str, tenant: str = "", weight: int = 1):
    """Tag the calling thread's device-gate/coalescer admissions with a
    QoS class for the duration of the scope (module comment). Nests:
    the previous tag is restored on exit."""
    if lane not in QOS_LANES:
        raise ValueError(
            f"unknown QoS lane {lane!r} (lanes: {', '.join(QOS_LANES)})"
        )
    prev = getattr(_qos_local, "ctx", None)
    _qos_local.ctx = (lane, tenant, max(1, int(weight)))
    try:
        yield
    finally:
        if prev is None:
            del _qos_local.ctx
        else:
            _qos_local.ctx = prev


class _Bucket:
    __slots__ = ("key", "fn", "payloads", "results", "error", "done",
                 "closed")

    def __init__(self, key, fn):
        self.key = key
        self.fn = fn
        self.payloads: list = []
        self.results: Optional[list] = None
        self.error: Optional[BaseException] = None
        self.done = threading.Event()
        self.closed = False


class _Flight:
    __slots__ = ("done", "result", "error", "members")

    def __init__(self):
        self.done = threading.Event()
        self.result = None
        self.error: Optional[BaseException] = None
        self.members = 1


class CoalescingDispatcher:
    """Batches concurrent same-key requests into single dispatches
    (module docstring). One process-wide instance fronts every codec
    ``_mul``; tests build their own with shrunk knobs."""

    def __init__(self, *, linger_seconds: float = 0.0005,
                 max_batch: int = 32, hot_window_seconds: float = 0.005,
                 background_linger_x: float = 4.0):
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        if background_linger_x < 1.0:
            raise ValueError(
                f"background_linger_x must be >= 1, got {background_linger_x}"
            )
        self.linger_seconds = linger_seconds
        self.max_batch = max_batch
        self.hot_window_seconds = hot_window_seconds
        self.background_linger_x = background_linger_x
        self._lock = threading.Lock()
        self._buckets: dict = {}
        self._flights: dict = {}  # single-flight tier (submit_shared)
        self._inflight = 0  # batch dispatches currently running
        self._last_submit_t = 0.0
        self._last_submit_thread: Optional[int] = None
        from noise_ec_tpu.obs.registry import default_registry

        reg = default_registry()
        self._batches = reg.counter("noise_ec_coalesce_batches_total").labels()
        self._size_hist = reg.histogram("noise_ec_coalesce_batch_size").labels()
        self._flush_children = {
            reason: reg.counter(
                "noise_ec_coalesce_flush_reason_total"
            ).labels(reason=reason)
            for reason in ("solo", "linger", "full", "bulk", "shared")
        }
        default_tracer().declare("coalesce_wait")

    # ------------------------------------------------------------- submit

    def submit(self, key, batch_fn: Callable[[list], list], payload):
        """One request: returns its result once a batch containing it has
        dispatched. ``batch_fn(payloads) -> results`` must be equivalent
        for every caller sharing ``key`` (it runs on the leader's
        thread)."""
        now = time.monotonic()
        me = threading.get_ident()
        with self._lock:
            hot = (
                self._inflight > 0
                or (
                    now - self._last_submit_t < self.hot_window_seconds
                    and self._last_submit_thread != me
                )
            )
            self._last_submit_t = now
            self._last_submit_thread = me
            bucket = self._buckets.get(key)
            if bucket is not None and not bucket.closed and len(
                bucket.payloads
            ) < self.max_batch:
                idx = len(bucket.payloads)
                bucket.payloads.append(payload)
                follower = True
            else:
                bucket = _Bucket(key, batch_fn)
                bucket.payloads.append(payload)
                self._buckets[key] = bucket
                idx = 0
                follower = False
        if follower:
            return self._await(bucket, idx)
        self._lead(bucket, linger=self._linger_budget() if hot else 0.0)
        return self._result(bucket, idx)

    def submit_shared(self, key, fn: Callable[[], object]):
        """Single-flight tier: concurrent same-``key`` callers share ONE
        ``fn()`` call and all receive its result. Unlike :meth:`submit`,
        followers may join while the call is already RUNNING — the
        result is *broadcast*, not batched — which is the shape of
        idempotent reads: the object service routes each cold
        ``(address, stripe)`` decode through here, so a zipfian stampede
        on a cold object costs exactly one dispatch
        (docs/object-service.md "Read path").

        Returns ``(result, shared)`` — ``shared`` is True when this
        caller rode another caller's in-flight call. An exception from
        ``fn`` propagates to every member. Flights record the coalesce
        metrics under ``flush_reason="shared"`` (one batch-size
        observation per member, same contract as batched flushes).

        Tracing: ``fn`` runs on the LEADER's thread, so any spans it
        opens land in the leader's request trace — a follower's trace
        would otherwise lose the decode work entirely. The object
        service threads the leader's trace id through the shared result
        so followers can record a ``joined`` span pointing at the
        leader's trace (docs/observability.md "Request tracing")."""
        with self._lock:
            flight = self._flights.get(key)
            if flight is not None:
                flight.members += 1
                follower = True
            else:
                flight = self._flights[key] = _Flight()
                follower = False
        if follower:
            if not flight.done.wait(_FOLLOWER_TIMEOUT_S):
                raise RuntimeError(
                    "shared dispatch never completed (leader lost)"
                )
            if flight.error is not None:
                raise flight.error
            return flight.result, True
        try:
            flight.result = fn()
        # noise-ec: allow(event-on-swallow) — error is re-delivered to every waiter via flight.error
        except BaseException as exc:  # noqa: BLE001 — fan the error out
            flight.error = exc
        finally:
            with self._lock:
                del self._flights[key]
                members = flight.members
            self._batches.add(1)
            self._flush_children["shared"].add(1)
            for _ in range(members):
                self._size_hist.observe(members)
            flight.done.set()
        if flight.error is not None:
            raise flight.error
        return flight.result, False

    def submit_many(self, key, batch_fn: Callable[[list], list],
                    payloads: Sequence) -> list:
        """Explicit batch (the repair engine's group dispatch): joins any
        open bucket for ``key`` and flushes without linger — the batch
        already exists, so admission and batching share the one queue
        with live singleton traffic."""
        payloads = list(payloads)
        if not payloads:
            return []
        with self._lock:
            bucket = self._buckets.get(key)
            if bucket is not None and not bucket.closed:
                base = len(bucket.payloads)
                bucket.payloads.extend(payloads)
                follower = True
            else:
                bucket = _Bucket(key, batch_fn)
                bucket.payloads.extend(payloads)
                self._buckets[key] = bucket
                base = 0
                follower = False
        if follower:
            self._await(bucket, base)  # leader flushes; wait for results
            return [self._result(bucket, base + i)
                    for i in range(len(payloads))]
        self._lead(bucket, linger=0.0, reason="bulk")
        return [self._result(bucket, base + i) for i in range(len(payloads))]

    # -------------------------------------------------------------- flush

    def _linger_budget(self) -> float:
        """The bounded latency budget: the base linger, scaled by the
        device-gate queue depth (a deep gate queue means the batch would
        block at admission anyway, so a longer linger costs nothing).
        Background-lane leaders under pressure linger
        ``background_linger_x`` longer still — repair/scrub batches
        YIELD the contended gate to live GETs (collecting bigger
        batches while they wait), the coalescer half of the QoS-lane
        story (the gate's weighted queues are the other half)."""
        if self.linger_seconds <= 0:
            return 0.0
        depth = 0
        try:
            from noise_ec_tpu.ops.dispatch import device_gate

            gate = device_gate()
            depth = gate.in_flight + gate.waiters
        # noise-ec: allow(event-on-swallow) — linger sizing probe — host regime without jax
        except Exception:  # noqa: BLE001 — linger must not require jax
            pass
        budget = max(self.linger_seconds, self.linger_seconds * depth)
        if depth > 0 and current_qos()[0] == "background":
            budget *= self.background_linger_x
            from noise_ec_tpu.obs.events import event

            event("qos.linger", lane="background", depth=depth,
                  budget_ms=round(budget * 1e3, 3))
        return budget

    def _lead(self, bucket: _Bucket, linger: float,
              reason: Optional[str] = None) -> None:
        if linger > 0:
            with span("coalesce_wait", role="leader"):
                deadline = time.monotonic() + linger
                while time.monotonic() < deadline:
                    with self._lock:
                        if len(bucket.payloads) >= self.max_batch:
                            break
                    time.sleep(min(0.0002, linger))
        with self._lock:
            bucket.closed = True
            if self._buckets.get(bucket.key) is bucket:
                del self._buckets[bucket.key]
            size = len(bucket.payloads)
            self._inflight += 1
        if reason is None:
            reason = (
                "full" if size >= self.max_batch
                else ("linger" if linger > 0 else "solo")
            )
        try:
            results = bucket.fn(list(bucket.payloads))
            if len(results) != size:
                raise RuntimeError(
                    f"coalesced batch_fn returned {len(results)} results "
                    f"for {size} payloads"
                )
            bucket.results = list(results)
        # noise-ec: allow(event-on-swallow) — error is re-delivered to every waiter via bucket.error
        except BaseException as exc:  # noqa: BLE001 — fan the error out
            bucket.error = exc
        finally:
            with self._lock:
                self._inflight -= 1
            self._batches.add(1)
            self._flush_children[reason].add(1)
            for _ in range(size):
                self._size_hist.observe(size)
            bucket.done.set()
        if bucket.error is not None:
            raise bucket.error

    def _await(self, bucket: _Bucket, idx: int):
        with span("coalesce_wait", role="follower"):
            done = bucket.done.wait(_FOLLOWER_TIMEOUT_S)
        if not done:
            raise RuntimeError(
                "coalesced dispatch never completed (leader lost)"
            )
        return self._result(bucket, idx)

    def _result(self, bucket: _Bucket, idx: int):
        if bucket.error is not None:
            raise bucket.error
        return bucket.results[idx]


# Implicit-coalescing payload cutoff: batching amortizes PER-DISPATCH
# overhead, so it pays exactly while that overhead dominates — always on
# an RPC-fronted accelerator link (~100 ms fixed cost per call), only
# for small payloads on the in-process CPU backend (measured on the
# single-core rig: 8x 1 KiB-stripe requests ran 3x faster batched, 8x
# 64 KiB ran 0.56x — the wide program is compute-bound and the batch
# adds a concat). Requests above the cutoff dispatch directly; explicit
# submit_many batches (the repair engine) are caller-opted and always
# batch.
_cutoff_override: Optional[int] = None


def set_coalesce_cutoff(nbytes: Optional[int]) -> None:
    """Pin the implicit-coalescing payload cutoff (None restores the
    per-backend default; tests use this to force either regime)."""
    global _cutoff_override
    _cutoff_override = nbytes


def coalesce_cutoff_bytes() -> int:
    if _cutoff_override is not None:
        return _cutoff_override
    try:
        import jax

        if jax.default_backend() in ("tpu", "gpu"):
            base = 8 << 20
            # Mesh dispatch tier (parallel/mesh.py): with N chips the
            # batch SHARDS, so per-chip payload is nbytes/N — batching
            # keeps amortizing N× further up the payload scale before a
            # member becomes compute-bound on its own chip.
            from noise_ec_tpu.parallel.mesh import mesh_router

            router = mesh_router()
            if router.enabled:
                base *= router.n_pow2
            return base
    # noise-ec: allow(event-on-swallow) — device-count probe — host regime without jax
    except Exception:  # noqa: BLE001 — no jax, host regime
        pass
    return 128 << 10


_coalescer: Optional[CoalescingDispatcher] = None
_coalescer_lock = threading.Lock()


def coalescer() -> CoalescingDispatcher:
    """The process-wide coalescing dispatcher (lazy singleton)."""
    global _coalescer
    with _coalescer_lock:
        if _coalescer is None:
            _coalescer = CoalescingDispatcher()
        return _coalescer


def configure_coalescer(**kwargs) -> CoalescingDispatcher:
    """Replace the process dispatcher (tests shrink/grow the linger; a
    fresh instance also drops any open buckets). Returns the new one."""
    global _coalescer
    with _coalescer_lock:
        _coalescer = CoalescingDispatcher(**kwargs)
        return _coalescer
