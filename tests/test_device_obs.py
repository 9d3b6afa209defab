"""Device-telemetry tests: dispatch latency with the compile/execute
split, the recompile counter under geometry churn, roofline cost
analysis, HBM gauges, the sampling profiler + /profile endpoint, and the
bench regression gate — the ISSUE 5 acceptance bars."""

import json
import sys
import time
import urllib.error
import urllib.request
from pathlib import Path

import numpy as np
import pytest

from noise_ec_tpu.obs.device import (
    analyze_program,
    device_op,
    hbm_snapshot,
    peak_hbm_gbps,
)
from noise_ec_tpu.obs.export import render_prometheus
from noise_ec_tpu.obs.metrics import DEVICE_LATENCY_BUCKETS, LATENCY_BUCKETS
from noise_ec_tpu.obs.registry import Registry, default_registry
from noise_ec_tpu.obs.sampler import StackSampler
from noise_ec_tpu.obs.server import StatsServer


def _get(url: str) -> tuple[int, bytes]:
    with urllib.request.urlopen(url, timeout=10) as resp:
        return resp.status, resp.read()


def _child_value(family, **labels) -> float:
    return family.labels(**labels).value


# -- device-scale buckets ---------------------------------------------------


def test_device_buckets_are_us_range_and_finer_than_host():
    assert DEVICE_LATENCY_BUCKETS[0] == pytest.approx(1e-6)
    assert all(
        b2 > b1
        for b1, b2 in zip(DEVICE_LATENCY_BUCKETS, DEVICE_LATENCY_BUCKETS[1:])
    )
    # Twice the resolution of the host buckets below 0.1 ms: a 14 us and
    # a 20 us reconstruct land in DIFFERENT buckets here (the host x2
    # set put both in (16, 32] us).
    sub01 = [b for b in DEVICE_LATENCY_BUCKETS if b <= 1e-4]
    host_sub01 = [b for b in LATENCY_BUCKETS if b <= 1e-4]
    assert len(sub01) >= 2 * len(host_sub01) - 1
    from bisect import bisect_left

    assert bisect_left(DEVICE_LATENCY_BUCKETS, 14e-6) != bisect_left(
        DEVICE_LATENCY_BUCKETS, 20e-6
    )
    # Top bucket still catches a stray seconds-scale compile.
    assert DEVICE_LATENCY_BUCKETS[-1] >= 0.5


# -- compile/execute split + recompile counter ------------------------------


def _fresh_geometries(rng, n, k=4, r=2):
    """n distinct (matrix, stripe) geometries whose programs no other
    test compiles: random GF matrices over odd stripe widths (the XLA
    kernel takes the matrix as an argument, so the width is what makes
    each one a new program)."""
    from noise_ec_tpu.matrix.generators import generator_matrix
    from noise_ec_tpu.gf.field import GF256

    gf = GF256()
    out = []
    for i in range(n):
        M = np.asarray(
            generator_matrix(gf, k, k + r, "cauchy")[k:], dtype=np.uint8
        ).copy()
        # Random XOR salt keeps the matrix bytes unique per call while
        # staying a valid GF(2^8) linear map for encode purposes.
        M ^= rng.integers(1, 255, size=M.shape, dtype=np.uint8)
        D = rng.integers(0, 256, size=(k, 229 + 6 * i)).astype(np.uint8)
        out.append((M, D))
    return out


def test_geometry_churn_advances_compile_counter_exactly_once_per_key(rng):
    """The acceptance bar: N distinct geometries -> the recompile counter
    advances exactly N (JAX compiled inside each first dispatch); repeat
    dispatches advance it zero times while the execute-route histogram
    keeps observing."""
    from noise_ec_tpu.ops.dispatch import DeviceCodec

    dev = DeviceCodec(field="gf256", kernel="xla")
    reg = default_registry()
    compiles = reg.counter("noise_ec_jit_compiles_total")
    ops = reg.histogram("noise_ec_device_op_seconds")
    entry = "matmul_stripes_xla"
    before = _child_value(compiles, kernel=entry)
    exec_before = ops.labels(kernel=entry, route="execute").count

    N = 3
    geoms = _fresh_geometries(rng, N)
    for M, D in geoms:
        dev.matmul_stripes(M, D)
    assert _child_value(compiles, kernel=entry) - before == N

    for M, D in geoms:  # same geometries again: zero new compiles
        dev.matmul_stripes(M, D)
        dev.matmul_stripes(M, D)
    assert _child_value(compiles, kernel=entry) - before == N
    assert ops.labels(kernel=entry, route="execute").count - exec_before == 2 * N
    # The compile route observed each first call too.
    assert ops.labels(kernel=entry, route="compile").count >= N


def test_failed_dispatch_does_not_consume_the_compile_slot():
    """A dispatch that raises records nothing: the NEXT call is the one
    that compiles, and the split must say so."""
    import jax
    import jax.numpy as jnp

    fn = jax.jit(lambda x: x * 3 + 1)  # a fresh function: a new program
    x = jnp.arange(7)
    reg = Registry()
    ops = reg.histogram("noise_ec_device_op_seconds")
    with pytest.raises(RuntimeError):
        with device_op("testfail", nbytes=1, registry=reg):
            raise RuntimeError("boom")
    assert sum(child.count for _, child in ops.children()) == 0
    with device_op("testfail", nbytes=1, registry=reg) as dt:
        np.asarray(fn(x))
    assert dt.route == "compile"
    with device_op("testfail", nbytes=1, registry=reg) as dt:
        np.asarray(fn(x))
    assert dt.route == "execute"


def test_device_roundtrip_serves_op_seconds_on_metrics(rng):
    """Acceptance: a loopback round trip on the device backend leaves
    nonzero noise_ec_device_op_seconds observations with a
    compile/execute split on /metrics, and repeat same-geometry traffic
    keeps noise_ec_jit_compiles_total flat."""
    from noise_ec_tpu.host.plugin import ShardPlugin
    from noise_ec_tpu.host.transport import LoopbackHub, LoopbackNetwork

    hub = LoopbackHub()
    a = LoopbackNetwork(hub, "tcp://dev-obs-a:1")
    b = LoopbackNetwork(hub, "tcp://dev-obs-b:1")
    pa, pb = ShardPlugin(backend="device"), ShardPlugin(backend="device")
    a.add_plugin(pa)
    b.add_plugin(pb)
    # An object size no other test dispatches: its encode program is new
    # to the process, so the first dispatch compiles.
    payload = bytes(rng.integers(0, 256, size=4452, dtype=np.uint8))
    pa.shard_and_broadcast(a, payload)
    assert pb.counters.get("verified") == 1

    reg = default_registry()
    ops = reg.histogram("noise_ec_device_op_seconds")
    compiles = reg.counter("noise_ec_jit_compiles_total")
    flat_before = {key: c.value for key, c in compiles.children()}

    # Same geometry + same payload size (distinct bytes: replay
    # protection dedups identical payloads) -> zero new compiles.
    payload2 = bytes(rng.integers(0, 256, size=4452, dtype=np.uint8))
    pa.shard_and_broadcast(a, payload2)
    assert pb.counters.get("verified") == 2
    assert {key: c.value for key, c in compiles.children()} == flat_before
    routes = {key[1] for key, child in ops.children() if child.count > 0}
    assert {"compile", "execute"} <= routes

    srv = StatsServer(port=0, registry=reg)
    try:
        _, body = _get(srv.url + "/metrics")
        text = body.decode()
        count_lines = [
            ln for ln in text.splitlines()
            if ln.startswith("noise_ec_device_op_seconds_count")
            and not ln.endswith(" 0")
        ]
        assert count_lines, "no nonzero device op observations on /metrics"
        assert any('route="compile"' in ln for ln in count_lines)
        assert any('route="execute"' in ln for ln in count_lines)
        assert "noise_ec_jit_compiles_total" in text
    finally:
        srv.close()


# -- kernel counter registry families ---------------------------------------


def test_record_kernel_feeds_registry_families():
    from noise_ec_tpu.obs.profiling import kernel_counters, record_kernel

    reg = default_registry()
    calls = reg.counter("noise_ec_kernel_calls_total")
    nbytes = reg.counter("noise_ec_kernel_bytes_total")
    c0 = _child_value(calls, entry="regkern")
    b0 = _child_value(nbytes, entry="regkern")
    bag0 = kernel_counters.get("regkern_bytes")
    record_kernel("regkern", 1024)
    record_kernel("regkern", 512)
    assert _child_value(calls, entry="regkern") - c0 == 2
    assert _child_value(nbytes, entry="regkern") - b0 == 1536
    # The plain bag still accumulates (timed_window / kernel_gbps).
    assert kernel_counters.get("regkern_bytes") - bag0 == 1536
    text = render_prometheus(reg)
    assert 'noise_ec_kernel_calls_total{entry="regkern"}' in text


# -- roofline ---------------------------------------------------------------


def test_analyze_program_exports_cost_gauges():
    import jax
    import jax.numpy as jnp

    fn = jax.jit(lambda a, b: a @ b)
    x = jnp.ones((64, 64), dtype=jnp.float32)
    np.asarray(fn(x, x))  # populate the jit cache first (the cheap path)
    reg = Registry()
    out = analyze_program("testmm", fn, x, x, registry=reg)
    if out is None:
        pytest.skip("backend offers no cost_analysis")
    assert out["flops"] > 0
    assert out["bytes"] > 0
    assert out["intensity"] == pytest.approx(out["flops"] / out["bytes"])
    text = render_prometheus(reg)
    assert 'noise_ec_device_program_flops{kernel="testmm"}' in text
    assert 'noise_ec_roofline_intensity{kernel="testmm"}' in text


def test_analyze_program_degrades_to_none():
    # No .lower on a plain lambda: telemetry returns None, never raises.
    assert analyze_program("nope", lambda x: x, 1, registry=Registry()) is None


def test_maybe_analyze_is_rate_limited_per_entry():
    """Geometry churn must pay recompiles, not a cost analysis per fresh
    geometry: the dispatch-path entry analyzes once per window."""
    import jax
    import jax.numpy as jnp

    from noise_ec_tpu.obs.device import (
        maybe_analyze_program,
        set_analysis_interval,
    )

    fn = jax.jit(lambda a: a + 1)
    x = jnp.ones((8,))
    np.asarray(fn(x))
    reg = Registry()
    set_analysis_interval(3600.0)
    try:
        first = maybe_analyze_program("ratelim", fn, x, registry=reg)
        second = maybe_analyze_program("ratelim", fn, x, registry=reg)
    finally:
        set_analysis_interval(60.0)
    assert second is None
    # Distinct entries have independent windows.
    assert first is None or isinstance(first, dict)


def test_peak_hbm_override():
    """Peaks key on device_kind: the CPU test backend is not in the
    table, so it has no roofline until a denominator is pinned."""
    from noise_ec_tpu.obs.device import DEVICE_PEAKS, set_peak_hbm_gbps

    assert DEVICE_PEAKS["TPU v5 lite"] == {"hbm_gbps": 819.0,
                                           "int8_tops": 393.0}
    assert peak_hbm_gbps() is None
    set_peak_hbm_gbps(1228.0)
    try:
        assert peak_hbm_gbps() == 1228.0
    finally:
        set_peak_hbm_gbps(None)
    assert peak_hbm_gbps() is None


# -- HBM accounting ---------------------------------------------------------


def test_hbm_snapshot_counts_live_arrays_and_serves_gauges():
    import jax.numpy as jnp

    pin = jnp.ones((1024,), dtype=jnp.uint8)  # noqa: F841 — held live
    snap = hbm_snapshot()
    assert snap["live_bytes"] >= 1024
    assert snap["peak_bytes"] >= snap["live_bytes"] or "bytes_in_use" in snap
    srv = StatsServer(port=0, registry=default_registry())
    try:
        _, body = _get(srv.url + "/metrics")
        text = body.decode()
        live = [
            ln for ln in text.splitlines()
            if ln.startswith("noise_ec_hbm_live_bytes ")
        ]
        assert live and float(live[0].split()[-1]) >= 1024
    finally:
        srv.close()
    del pin


def test_healthz_details_carry_hbm():
    srv = StatsServer(port=0, registry=Registry())
    try:
        _, body = _get(srv.url + "/healthz?verbose=1")
        doc = json.loads(body)
        assert doc["healthy"] is True
        assert "hbm" in doc.get("details", {})
        assert doc["details"]["hbm"]["live_bytes"] >= 0
    finally:
        srv.close()


# -- sampling profiler ------------------------------------------------------


def test_sampler_collapses_stacks():
    reg = Registry()
    s = StackSampler(hz=200.0, window_seconds=30.0, registry=reg).start()
    try:
        deadline = time.time() + 5
        while not s.counts() and time.time() < deadline:
            time.sleep(0.01)
        text = s.collapsed()
        assert text, "sampler collected nothing"
        lines = text.splitlines()
        # Collapsed format: 'thread;frame;frame count', heaviest first.
        stack, n = lines[0].rsplit(" ", 1)
        assert int(n) >= 1
        assert ";" in stack
        # This (main) thread shows up with this module on its stack.
        assert any("test_device_obs" in ln for ln in lines)
        counts = [int(ln.rsplit(" ", 1)[1]) for ln in lines]
        assert counts == sorted(counts, reverse=True)
    finally:
        s.close()
    assert not s.running
    assert reg.counter("noise_ec_profile_samples_total").labels().value > 0


def test_profile_endpoint_serves_collapsed_stacks():
    """Acceptance: /profile?seconds=1 returns non-empty collapsed text."""
    srv = StatsServer(port=0, registry=Registry())
    try:
        status, body = _get(srv.url + "/profile?seconds=1")
        assert status == 200
        text = body.decode()
        assert text.strip(), "/profile returned empty collapsed stacks"
        for ln in text.strip().splitlines():
            stack, n = ln.rsplit(" ", 1)
            assert int(n) >= 1 and ";" in stack
    finally:
        srv.close()
        # The endpoint starts the process-wide sampler; stop it so the
        # rest of the suite is not sampled (a later /profile restarts it).
        from noise_ec_tpu.obs.sampler import default_sampler

        default_sampler(start=False).close()


def test_profile_endpoint_rejects_bad_seconds():
    srv = StatsServer(port=0, registry=Registry())
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(srv.url + "/profile?seconds=nope")
        assert ei.value.code == 400
    finally:
        srv.close()


# -- xprof capture ----------------------------------------------------------


def test_xprof_endpoint_captures_into_dir(tmp_path):
    logdir = tmp_path / "xprof"
    srv = StatsServer(port=0, registry=Registry(), xprof_dir=str(logdir))
    try:
        status, body = _get(srv.url + "/xprof?seconds=0.2")
        assert status == 200
        doc = json.loads(body)
        assert doc["capturing"] is True
        deadline = time.time() + 15
        while time.time() < deadline:
            if logdir.exists() and any(logdir.rglob("*")):
                break
            time.sleep(0.1)
        assert logdir.exists() and any(logdir.rglob("*"))
    finally:
        srv.close()


def test_xprof_endpoint_404_without_dir():
    srv = StatsServer(port=0, registry=Registry())
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(srv.url + "/xprof?seconds=1")
        assert ei.value.code == 404
    finally:
        srv.close()


# -- bench regression gate --------------------------------------------------


# Synthetic BENCH_r*/MULTICHIP_r* records (not chip numbers) the gate
# replays in place of a recorded chip trajectory.
GATE_FIXTURES = Path(__file__).resolve().parent / "data" / "bench_gate"


def _bench_gate():
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "tools"))
    try:
        import bench_gate
    finally:
        sys.path.pop(0)
    return bench_gate


def test_bench_gate_directions_and_tolerances():
    bg = _bench_gate()
    assert bg.metric_direction("rs200_56_encode_gbps") == "up"
    assert bg.metric_direction("reconstruct3_1mib_p50_ms") == "down"
    assert bg.metric_direction("backend") is None
    assert bg.metric_direction("rs200_56_error") is None
    # The device tier of the large-object stream: direction up, TIGHT
    # device tolerance even though the host_ prefix would otherwise
    # grant the load-tail one.
    large = "host_node_large_object_device_mb_per_s"
    assert bg.metric_direction(large) == "up"
    assert bg.metric_tolerance(large) == bg.DEFAULT_TOLERANCE
    assert bg.metric_direction("device_matmul_words_achieved_gbps") is None
    assert bg.metric_tolerance("rs17_3_encode_gbps") < bg.metric_tolerance(
        "host_node_roundtrip_mb_per_s"
    )


def test_bench_gate_flags_synthetic_20pct_regression():
    """Acceptance: a 20% throughput cut exits nonzero; the recorded
    r01->r02 fixture series exits zero."""
    bg = _bench_gate()
    series = dict(bg.recorded_series(GATE_FIXTURES))
    r02 = series["BENCH_r02.json"]
    cut = dict(r02)
    cut["rs200_56_encode_gbps"] = r02["rs200_56_encode_gbps"] * 0.8
    problems, findings = bg.gate(r02, cut)
    assert any("rs200_56_encode_gbps" in p for p in problems)
    regressed = [f for f in findings if f["regressed"]]
    assert [f["metric"] for f in regressed] == ["rs200_56_encode_gbps"]

    problems, _ = bg.gate(series["BENCH_r01.json"], r02)
    assert problems == []


def test_bench_gate_check_mode_passes():
    """The --check self-test (the tier-1 CI hook) replays the recorded
    series clean."""
    bg = _bench_gate()
    assert bg.self_check(verbose=False, repo=GATE_FIXTURES) == []
    assert bg.main(["--check", "--repo", str(GATE_FIXTURES)]) == 0


def test_bench_gate_cli_on_recorded_rounds():
    bg = _bench_gate()
    root = GATE_FIXTURES
    assert bg.main([
        "--current", f"{root}/BENCH_r02.json",
        "--against", f"{root}/BENCH_r01.json",
    ]) == 0
    assert bg.main([
        "--current", f"{root}/BENCH_r01.json",
        "--against", f"{root}/BENCH_r02.json",
    ]) == 1  # the reversed diff is a genuine regression


def test_bench_gate_wire_rig_bars():
    """ISSUE-11: the wire hot-loop rig bars (>= 50k msgs/s, roundtrip
    MB/s within 4x of the large-object host path) bite on rigs with a
    recorded MULTICHIP round — the fixture series records one — and pass once
    the loop clears them; dev-box-shaped numbers are flagged with the
    ROADMAP pointer."""
    bg = _bench_gate()
    assert bg.newest_multichip_devices(GATE_FIXTURES) > 1  # the recorded rig
    slow = {
        "host_node_roundtrip_msgs_per_s": 216.3,
        "host_node_roundtrip_mb_per_s": 14.2,
        "host_node_large_object_mb_per_s": 229.8,
    }
    problems = bg.wire_rig_check(slow, GATE_FIXTURES)
    assert any("50000" in p for p in problems)
    assert any("4x" in p for p in problems)
    fast = {
        "host_node_roundtrip_msgs_per_s": 61000.0,
        "host_node_roundtrip_mb_per_s": 80.0,
        "host_node_large_object_mb_per_s": 229.8,
    }
    assert bg.wire_rig_check(fast, GATE_FIXTURES) == []
    # wire_ stats ride the host tolerance; the info keys carry no
    # direction (they describe amortization, not a perf contract).
    assert bg.metric_tolerance("wire_verify_batch_size_p50") == bg.HOST_TOLERANCE
    assert bg.metric_direction("wire_verify_batch_size_p50") is None
    assert bg.metric_direction("wire_frames_per_syscall") is None


def test_bench_gate_cache_hot_bars():
    """ISSUE-12: the tiered read-path bars — hot cached GETs >= 10x the
    degraded decode path at >= 90% hit rate — flag a cache that stopped
    amortizing, pass a healthy run, and skip rounds without the keys
    (recorded rounds predate the cache)."""
    bg = _bench_gate()
    healthy = {
        "object_get_hot_mb_per_s": 112000.0,
        "object_get_degraded_mb_per_s": 860.0,
        "object_get_hit_rate": 0.99,
    }
    assert bg.cache_hot_check(healthy) == []
    slow = dict(healthy, object_get_hot_mb_per_s=4000.0)
    assert any("10x" in p for p in bg.cache_hot_check(slow))
    cold = dict(healthy, object_get_hit_rate=0.4)
    assert any("hit_rate" in p for p in bg.cache_hot_check(cold))
    assert bg.cache_hot_check({"object_put_mb_per_s": 50.0}) == []
    # The hot stat rides host tolerance; the hit rate carries no
    # direction (cache_hot_check owns its bar).
    assert bg.metric_tolerance("object_get_hot_mb_per_s") == bg.HOST_TOLERANCE
    assert bg.metric_direction("object_get_hit_rate") is None


def test_bench_gate_north_star():
    bg = _bench_gate()
    base = {"rs17_3_encode_gbps": 500.0}
    ok = {"rs17_3_encode_gbps": 505.0, "headline_rs10_4_encode_gbps": 400.0}
    bad = {"rs17_3_encode_gbps": 505.0, "headline_rs10_4_encode_gbps": 12.0}
    problems, _ = bg.gate(base, ok)
    assert problems == []
    problems, _ = bg.gate(base, bad)
    assert any("north star" in p for p in problems)
