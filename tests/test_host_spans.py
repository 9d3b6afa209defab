"""Program spans on the profiler's clock, the dispatch split, the
reconstruct span, the three wait spans, and the compile route read from
JAX's own events (docs/observability.md "Spans on the device trace")."""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from noise_ec_tpu.obs.device import device_op, install_compile_listener
from noise_ec_tpu.obs.registry import Registry, default_registry
from noise_ec_tpu.obs.trace import default_tracer, span


def _spans(name: str) -> list[dict]:
    return [d for d in default_tracer().dump() if d["name"] == name]


def _stage(name: str):
    return default_registry().histogram("noise_ec_stage_seconds").labels(
        stage=name
    )


def _op_count(entry: str, route: str) -> int:
    return default_registry().histogram("noise_ec_device_op_seconds").labels(
        kernel=entry, route=route
    ).count


def _matrix(rng, r: int = 2, k: int = 4) -> np.ndarray:
    from noise_ec_tpu.gf.field import GF256
    from noise_ec_tpu.matrix.generators import generator_matrix

    M = np.asarray(
        generator_matrix(GF256(), k, k + r, "cauchy")[k:], dtype=np.uint8
    ).copy()
    M ^= rng.integers(1, 255, size=M.shape, dtype=np.uint8)
    return M


# -------------------------------------------------- the profiler's clock


def test_span_tracemes_nest_on_the_profiler_clock(tmp_path):
    """Under a jax.profiler session the host plane holds each span's
    TraceMe (and the dispatch's), nested as the spans are, on the same
    clock as a bench.-style annotation around them."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.put"):
            time.sleep(0.002)
            with span("encode"):
                time.sleep(0.002)
                with span("sign"):
                    time.sleep(0.002)
                with device_op("spans_test", registry=Registry()):
                    time.sleep(0.002)
                time.sleep(0.002)
            time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    xplane = sorted(tmp_path.glob("**/*.xplane.pb"))[-1]
    pd = jax.profiler.ProfileData.from_file(str(xplane))
    found: dict = {}
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name in ("bench.put", "encode", "sign", "dispatch"):
                    found[ev.name] = (ev.start_ns,
                                      ev.start_ns + ev.duration_ns)
    assert set(found) == {"bench.put", "encode", "sign", "dispatch"}

    def inside(child, parent):
        return (found[parent][0] <= found[child][0]
                and found[child][1] <= found[parent][1])

    assert inside("encode", "bench.put")
    assert inside("sign", "encode")
    assert inside("dispatch", "encode")
    assert found["sign"][1] <= found["dispatch"][0]


# -------------------------------------------------------- the three waits


def test_gate_wait_span_times_the_contended_admission():
    from noise_ec_tpu.ops.dispatch import DeviceGate

    gate = DeviceGate(capacity=1)
    gate.acquire()  # free slot: no span
    second = threading.Thread(target=lambda: (gate.acquire(), gate.release()))
    second.start()
    deadline = time.monotonic() + 10
    while gate.waiters == 0 and time.monotonic() < deadline:
        time.sleep(0.001)
    time.sleep(0.05)
    gate.release()
    second.join(10)
    waits = _spans("gate_wait")
    assert len(waits) == 1
    assert waits[0]["seconds"] >= 0.04
    assert waits[0]["attrs"]["lane"] == "live"


def test_coalesce_wait_spans_the_leader_linger_and_the_follower():
    from noise_ec_tpu.ops.coalesce import CoalescingDispatcher

    disp = CoalescingDispatcher(
        linger_seconds=0.5, max_batch=2, hot_window_seconds=60.0
    )
    double = lambda ps: [p * 2 for p in ps]  # noqa: E731
    assert disp.submit("solo", double, 0) == 0  # idle: flushes at once
    results = {}

    def lead():  # another thread inside the hot window: lingers
        results["leader"] = disp.submit("k", double, 1)

    leader = threading.Thread(target=lead)
    leader.start()
    deadline = time.monotonic() + 10
    while not disp._buckets and time.monotonic() < deadline:
        time.sleep(0.001)
    results["follower"] = disp.submit("k", double, 2)
    leader.join(10)
    assert results == {"leader": 2, "follower": 4}
    roles = sorted(d["attrs"]["role"] for d in _spans("coalesce_wait"))
    assert roles == ["follower", "leader"]


@pytest.mark.parametrize("contended", [True, False])
def test_store_lock_wait_span_only_when_the_lock_is_held(contended):
    from noise_ec_tpu.store import StripeStore

    store = StripeStore()
    holder = None
    if contended:
        held = threading.Event()

        def hold():
            with store._lock:
                held.set()
                time.sleep(0.05)

        holder = threading.Thread(target=hold)
        holder.start()
        held.wait(10)
    assert len(store) == 0  # takes the lock
    if holder is not None:
        holder.join(10)
    waits = _spans("store_lock_wait")
    # Declared at construction: the stage reads 0 s until a wait happens.
    assert _stage("store_lock_wait").count == len(waits)
    if contended:
        assert len(waits) == 1 and waits[0]["seconds"] >= 0.02
    else:
        assert waits == []


def test_store_lock_stays_exclusive_under_contention():
    """Eight threads, a 10 µs switch interval, 2,000 read-modify-writes
    each under the store's lock: no update is lost, and contended
    acquires were timed as store_lock_wait spans."""
    import sys

    from noise_ec_tpu.store import StripeStore

    store = StripeStore()
    box = [0]

    def work():
        for _ in range(2000):
            with store._lock:
                v = box[0]
                box[0] = v + 1

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert box[0] == 8 * 2000
    assert _stage("store_lock_wait").count > 0


@pytest.mark.parametrize("lost", [(0,), ()])
def test_store_read_spans_the_reconstruct_only_when_degraded(rng, lost):
    from noise_ec_tpu.store import StripeStore

    store = StripeStore()
    blob = bytes(rng.integers(0, 256, size=1000, dtype=np.uint8))
    key = store.put_object(
        bytes(rng.integers(0, 256, size=64, dtype=np.uint8)), blob, 4, 6
    )
    for slot in lost:
        store.drop_shard(key, slot)
    with span("stripe_decode"):
        assert store.read(key) == blob
    spans = _spans("reconstruct")
    assert len(spans) == len(lost)
    if lost:
        assert spans[0]["parent"] == "stripe_decode"


# ------------------------------------------------- the dispatch itself


@pytest.mark.parametrize("many", [False, True])
def test_dispatch_splits_into_device_wait_and_readback(rng, many):
    """Inside the dispatch window: device_wait (program call to ready
    output), then readback (copy to host); both nest in the window."""
    from noise_ec_tpu.ops.dispatch import DeviceCodec

    dev = DeviceCodec(field="gf256", kernel="pallas_interpret")
    M = _matrix(rng)
    Ds = [rng.integers(0, 256, size=(4, 512)).astype(np.uint8)
          for _ in range(2 if many else 1)]
    hist = default_registry().histogram("noise_ec_device_op_seconds")
    entry = "matmul_stripes_pallas_interpret"

    def op_seconds():
        return sum(hist.labels(kernel=entry, route=route).sum
                   for route in ("compile", "execute"))

    if many:
        dev.matmul_stripes_many(M, Ds)  # compile outside the measurement
    else:
        dev.matmul_stripes(M, Ds[0])
    default_tracer().clear()
    before = op_seconds()
    if many:
        out = dev.matmul_stripes_many(M, Ds)
    else:
        out = [dev.matmul_stripes(M, Ds[0])]
    window = op_seconds() - before
    from noise_ec_tpu.codec.rs import host_matvec
    from noise_ec_tpu.gf.field import GF256

    np.testing.assert_array_equal(host_matvec(GF256(), M, Ds[-1]), out[-1])
    (wait,) = _spans("device_wait")
    (back,) = _spans("readback")
    assert wait["start"] + wait["seconds"] <= back["start"] + 1e-6
    assert 0 < wait["seconds"] + back["seconds"] <= window


@pytest.mark.parametrize("route", ["compile", "execute"])
def test_dispatch_route_is_what_jax_did_inside_it(rng, route):
    """compile: JAX compiled inside the dispatch. execute: JAX already
    held the program — here a first dispatch of a matrix never seen
    before, whose program another matrix of its shape compiled."""
    from noise_ec_tpu.ops.dispatch import DeviceCodec

    dev = DeviceCodec(field="gf256", kernel="xla")
    entry = "matmul_stripes_xla"
    width = 263 if route == "compile" else 271  # widths no other test uses
    D = rng.integers(0, 256, size=(4, width)).astype(np.uint8)
    if route == "execute":
        dev.matmul_stripes(_matrix(rng), D)
    default_tracer().clear()
    before = {r: _op_count(entry, r) for r in ("compile", "execute")}
    dev.matmul_stripes(_matrix(rng), D)
    after = {r: _op_count(entry, r) for r in ("compile", "execute")}
    assert {r: after[r] - before[r] for r in after} == {
        "compile": int(route == "compile"),
        "execute": int(route == "execute"),
    }
    compiled = _spans("backend_compile")
    assert bool(compiled) == (route == "compile")


def test_jax_events_land_as_finished_spans_on_the_calling_thread():
    install_compile_listener()
    fn = jax.jit(lambda x: x * 5 - 2)  # a fresh function: traced, compiled
    with span("encode"):
        np.asarray(fn(jnp.arange(5)))
    for name in ("jax_trace", "backend_compile"):
        got = [d for d in _spans(name) if "lambda" in d["attrs"]["fun"]]
        assert len(got) == 1, (name, _spans(name))
        assert got[0]["parent"] == "encode"
        assert got[0]["seconds"] > 0
