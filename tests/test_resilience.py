"""Resilience tests: chaos proxy determinism, circuit breakers, NACK
shard repair, codec graceful degradation, and the chaos-soak acceptance
path (docs/resilience.md)."""

import threading
import time

import numpy as np
import pytest

from noise_ec_tpu.host.plugin import ShardPlugin
from noise_ec_tpu.host.transport import (
    FaultInjector,
    LoopbackHub,
    LoopbackNetwork,
    TCPNetwork,
    format_address,
)
from noise_ec_tpu.obs.health import SLOEvaluator
from noise_ec_tpu.obs.registry import default_registry
from noise_ec_tpu.resilience import (
    ChaosLink,
    ChaosProfile,
    ChaosProxy,
    CircuitBreaker,
)
from noise_ec_tpu.store import RepairEngine, StripeStore


def counter_value(name: str, **labels) -> float:
    return default_registry().counter(name).labels(**labels).value


# ------------------------------------------------------------ chaos model


def test_chaos_profile_parse_grammar():
    p = ChaosProfile.parse(
        "drop=0.05, corrupt=0.01,delay=0.002,jitter=0.001,"
        "bandwidth=1048576,partition@2:2:a2b,partition@9:1,"
        "reset@5,kill@7:1.5"
    )
    assert p.drop == 0.05 and p.corrupt == 0.01
    assert p.delay == 0.002 and p.jitter == 0.001
    assert p.bandwidth == 1048576
    assert p.partitions == ((2.0, 2.0, "a2b"), (9.0, 1.0, "both"))
    assert p.resets == (5.0,)
    assert p.kills == ((7.0, 1.5),)
    # Partition windows: severed inside, healed at start + duration.
    assert p.partitioned("a2b", 2.5)
    assert not p.partitioned("b2a", 2.5)
    assert not p.partitioned("a2b", 4.0)  # healed
    assert p.partitioned("a2b", 9.5) and p.partitioned("b2a", 9.5)
    # Kills sever both directions too.
    assert p.partitioned("a2b", 7.5) and p.killed(7.5)
    for bad in ("drop", "partition@1", "kill@3", "frobnicate=1", "x@1",
                "churn@1", "churn@1:0:1", "churn@1:2:0", "churn@1:2:1:-1"):
        with pytest.raises(ValueError):
            ChaosProfile.parse(bad)
    # churn@ parses into the recurring-cycle primitive (jitter optional).
    c = ChaosProfile.parse("churn@2:4:0.5,churn@0:1:0.2:0.3")
    assert c.churns == ((2.0, 4.0, 0.5, 0.0), (0.0, 1.0, 0.2, 0.3))


def test_chaos_churn_windows_seeded_reproducibility():
    """The churn primitive's expansion is part of the seeded-
    reproducibility contract: same (seed, stream, profile) ⇒ identical
    kill/restart windows; a different seed or stream diverges. The
    fleet lab leans on the stream axis for per-peer staggering."""
    p = ChaosProfile.parse("churn@1:3:0.5:0.8")
    w1 = p.churn_windows(7, horizon=60.0, stream=3)
    w2 = p.churn_windows(7, horizon=60.0, stream=3)
    assert w1 == w2 and len(w1) == 20  # one cycle per interval
    # Windows are sorted, jittered around the nominal schedule, and
    # each carries the configured downtime.
    assert list(w1) == sorted(w1)
    for i, (start, down) in enumerate(w1):
        assert down == 0.5
        assert abs(start - (1.0 + 3.0 * i)) <= 0.8 + 1e-9
    assert p.churn_windows(8, horizon=60.0, stream=3) != w1
    assert p.churn_windows(7, horizon=60.0, stream=4) != w1
    # Zero jitter is exact; no-churn profiles expand to nothing.
    exact = ChaosProfile.parse("churn@0:10:1").churn_windows(1, 25.0)
    assert exact == ((0.0, 1.0), (10.0, 1.0), (20.0, 1.0))
    assert ChaosProfile().churn_windows(1, 100.0) == ()


def test_chaos_link_seeded_reproducibility():
    """Same seed + profile + frame sequence ⇒ identical fault stats AND
    an identical delivery trace (frames, order, delays) — the
    reproducibility contract every chaos run leans on."""
    profile = ChaosProfile.parse(
        "drop=0.1,duplicate=0.05,corrupt=0.05,reorder=0.1,"
        "delay=0.001,jitter=0.002,bandwidth=65536,partition@1:0.5:a2b"
    )
    rng = np.random.default_rng(42)
    frames = [rng.bytes(int(rng.integers(8, 200))) for _ in range(400)]
    times = np.cumsum(rng.uniform(0.001, 0.01, size=len(frames)))

    def run():
        link = ChaosLink(profile, seed=7, conn_id=3, direction="a2b")
        trace = []
        for frame, now in zip(frames, times):
            trace.append(link.admit(frame, float(now)))
        tail = link.flush()
        return trace, tail, link.stats()

    trace1, tail1, stats1 = run()
    trace2, tail2, stats2 = run()
    assert trace1 == trace2
    assert tail1 == tail2
    assert stats1 == stats2
    # The run is not trivially fault-free, and every fault class armed in
    # the profile actually fired.
    for key in ("dropped", "corrupted", "duplicated", "reordered",
                "partitioned"):
        assert stats1[key] > 0, (key, stats1)
    # A different seed diverges (the stats depend on the seed at all).
    link3 = ChaosLink(profile, seed=8, conn_id=3, direction="a2b")
    for frame, now in zip(frames, times):
        link3.admit(frame, float(now))
    link3.flush()
    assert link3.stats() != stats1


def test_fault_injector_duplicate_reorder_accounting():
    """Stats accounting under duplicate + reorder interaction on ONE
    shared link: every input is accounted for exactly once —
    delivered + dropped + pending == inputs + duplicated — and flush
    releases the held slot into delivered."""
    inj = FaultInjector(seed=5, drop=0.1, duplicate=0.4, reorder=0.4)
    rng = np.random.default_rng(1)
    inputs = 0
    out_count = 0
    for _ in range(50):  # stateful across calls, same link
        batch = [rng.bytes(16) for _ in range(int(rng.integers(1, 6)))]
        inputs += len(batch)
        out_count += len(inj.apply(batch, link="shared"))
    s = inj.stats
    assert s["duplicated"] > 0 and s["reordered"] > 0  # interaction armed
    assert out_count == s["delivered"]
    assert inj.pending in (0, 1)  # one delay-line slot per link
    assert (
        s["delivered"] + s["dropped"] + inj.pending
        == inputs + s["duplicated"]
    )
    held = inj.flush("shared")
    if held is not None:
        out_count += 1
    assert inj.pending == 0
    assert inj.flush("shared") is None
    assert (
        inj.stats["delivered"] + inj.stats["dropped"]
        == inputs + inj.stats["duplicated"]
    )


# -------------------------------------------------------- circuit breaker


def test_circuit_breaker_full_cycle():
    """closed → open → half-open → (failed probe: open, doubled timeout)
    → half-open → (successful probe) → closed, against a fake clock."""
    t = [0.0]
    br = CircuitBreaker(
        failure_threshold=2, reset_timeout=1.0, max_reset_timeout=4.0,
        clock=lambda: t[0], seed=0,
    )
    assert br.state() == "closed" and br.allow() and br.closed
    br.record_failure()
    assert br.state() == "closed"  # below threshold
    br.record_failure()
    assert br.state() == "open"
    assert not br.allow()
    assert br.open_remaining() == pytest.approx(1.0)
    t[0] = 0.5
    assert not br.allow()
    t[0] = 1.01
    assert br.state() == "half_open"
    assert br.allow()          # the single probe slot
    assert not br.allow()      # second caller must wait for the verdict
    br.record_failure()        # failed probe: re-open, timeout doubled
    assert br.state() == "open"
    assert br.open_remaining() == pytest.approx(2.0)
    t[0] = 3.02
    assert br.state() == "half_open" and br.allow()
    br.record_success()
    assert br.state() == "closed" and br.closed
    # Re-closing resets the timeout to the base value.
    br.record_failure()
    br.record_failure()
    assert br.open_remaining() == pytest.approx(1.0)


def test_circuit_breaker_backoff_full_jitter_bounds():
    br = CircuitBreaker(backoff_base=0.25, backoff_cap=4.0, seed=3)
    for attempt in range(12):
        ceiling = min(4.0, 0.25 * 2**attempt)
        for _ in range(20):
            d = br.backoff_delay(attempt)
            assert 0.0 <= d <= ceiling
    # Seeded: two breakers with the same seed draw identical schedules.
    a = CircuitBreaker(seed=11)
    b = CircuitBreaker(seed=11)
    assert [a.backoff_delay(i) for i in range(8)] == [
        b.backoff_delay(i) for i in range(8)
    ]


# --------------------------------------------------- codec degradation


def test_codec_breaker_degradation_and_half_open_probe(monkeypatch):
    """An injected device-dispatch failure retries once, trips the codec
    breaker, and every encode/reconstruct degrades to the golden host
    codec with NO wrong bytes; once the injected fault clears, the
    background half-open probe re-closes the breaker."""
    from noise_ec_tpu.codec.fec import FEC
    from noise_ec_tpu.ops import dispatch

    br = dispatch.configure_codec_breaker(
        reset_timeout=0.2, max_reset_timeout=1.0
    )
    fec = FEC(4, 6, backend="device")
    golden = FEC(4, 6, backend="numpy")
    data = bytes(range(64))
    calls = {"n": 0}

    def boom(self, M, D):
        calls["n"] += 1
        raise RuntimeError("injected device fault")

    err0 = counter_value("noise_ec_codec_fallback_total", reason="error")
    open0 = counter_value("noise_ec_codec_fallback_total", reason="open")
    with monkeypatch.context() as mp:
        mp.setattr(dispatch.DeviceCodec, "matmul_stripes", boom)
        shares = fec.encode_shares(data)
        # Bit-exact with the golden codec: degradation costs throughput,
        # never bytes.
        assert [
            (s.number, bytes(s.data)) for s in shares
        ] == [(s.number, bytes(s.data)) for s in golden.encode_shares(data)]
        assert calls["n"] == 2  # first failure retried once in-call
        assert br.state() == "open"
        assert counter_value(
            "noise_ec_codec_fallback_total", reason="error"
        ) == err0 + 1
        # While open: device not even attempted, "open" short-circuit.
        fec.encode_shares(data)
        assert calls["n"] == 2
        assert counter_value(
            "noise_ec_codec_fallback_total", reason="open"
        ) >= open0 + 1
        # Reconstruct degrades identically (the repair-engine path).
        full = fec._rs.reconstruct(
            [bytes(s.data) for s in shares[:4]] + [None, None]
        )
        assert [bytes(r) for r in full[4:]] == [
            bytes(s.data) for s in shares[4:]
        ]
    # Fault cleared (monkeypatch undone): the background prober runs a
    # canary matmul on the widening half-open schedule and closes.
    deadline = time.time() + 30
    while time.time() < deadline and not br.closed:
        time.sleep(0.05)
    assert br.closed, br.snapshot()
    # Device route restored: encodes run on the device again.
    assert fec.encode_shares(data)[5].data == shares[5].data


def test_codec_prober_follows_a_replaced_breaker(monkeypatch):
    """A prober still waiting out a replaced breaker's long timeout does
    not strand the breaker that replaced it: a trip of the new breaker
    heals once the fault clears, though no second prober may start while
    the first lives."""
    from noise_ec_tpu.codec.rs import ReedSolomon
    from noise_ec_tpu.ops import dispatch

    def boom(self, M, Ds):
        raise RuntimeError("injected device fault")

    D = np.arange(4 * 16, dtype=np.uint8).reshape(4, 16)
    old = dispatch.configure_codec_breaker(
        reset_timeout=60.0, max_reset_timeout=120.0
    )
    try:
        with monkeypatch.context() as mp:
            mp.setattr(dispatch.DeviceCodec, "matmul_stripes_many", boom)
            rs = ReedSolomon(4, 2)
            rs.matmul_many(rs.G[4:], [D, D])
        assert old.state() == "open"  # its prober now waits 60 s
        br = dispatch.configure_codec_breaker(
            reset_timeout=0.2, max_reset_timeout=1.0
        )
        with monkeypatch.context() as mp:
            mp.setattr(dispatch.DeviceCodec, "matmul_stripes_many", boom)
            rs = ReedSolomon(4, 2)
            rs.matmul_many(rs.G[4:], [D, D])
        assert br.state() == "open"
        deadline = time.time() + 20
        while time.time() < deadline and not br.closed:
            time.sleep(0.05)
        assert br.closed, br.snapshot()
    finally:
        dispatch.configure_codec_breaker()  # fresh, closed, for later tests


# ------------------------------------------------------- NACK shard repair


def make_tcp_pair(**b_kwargs):
    """A listening pair (a accepts, b dials) with numpy plugins."""
    inbox_a, inbox_b = [], []
    a = TCPNetwork(host="127.0.0.1", port=0, discovery=False)
    a.add_plugin(ShardPlugin(backend="numpy",
                             on_message=lambda m, s: inbox_a.append(m)))
    a.listen()
    b = TCPNetwork(host="127.0.0.1", port=0, discovery=False, **b_kwargs)
    b.add_plugin(ShardPlugin(backend="numpy",
                             on_message=lambda m, s: inbox_b.append(m)))
    b.listen()
    return a, b, inbox_a, inbox_b


def test_nack_repairs_partial_pool_on_loopback():
    """A pool stuck below k NACKs its held shards; the sender's store
    recognizes the interest and responds with the full stripe; the
    receiver completes and delivers."""
    hub = LoopbackHub()
    node_a = LoopbackNetwork(hub, format_address("tcp", "localhost", 3200))
    node_b = LoopbackNetwork(hub, format_address("tcp", "localhost", 3201))
    store_a = StripeStore()
    engine_a = RepairEngine(
        store_a, network=node_a, respond_interval_seconds=0.05,
        linger_seconds=0.0,
    )
    engine_a.start()
    plugin_a = ShardPlugin(backend="numpy", store=store_a)
    node_a.add_plugin(plugin_a)
    inbox_b = []
    plugin_b = ShardPlugin(
        backend="numpy", on_message=lambda m, s: inbox_b.append(m)
    )
    plugin_b.nack_grace_seconds = 0.15
    plugin_b.nack_backoff_base = 0.15
    node_b.add_plugin(plugin_b)

    req0 = counter_value("noise_ec_nack_requests_total")
    rep0 = counter_value("noise_ec_nack_repaired_total")
    payload = b"nack repairs me!"  # 16 bytes, k=4
    shards = plugin_a.prepare_shards(node_a.id, node_a.keys, payload)
    store_a.put_object(
        shards[0].file_signature, payload, 4, 6,
        sender_address=node_a.id.address,
        sender_public_key=bytes(node_a.keys.public_key),
    )
    # Deliver only 3 of 6 shards: the pool sticks below k = 4.
    for shard in shards[:3]:
        node_b.deliver(shard.marshal(), node_a.id)
    assert inbox_b == []
    deadline = time.time() + 15
    while time.time() < deadline and not inbox_b:
        time.sleep(0.02)
    try:
        assert inbox_b == [payload], (node_a.errors, node_b.errors)
        assert counter_value("noise_ec_nack_requests_total") > req0
        assert counter_value("noise_ec_nack_repaired_total") > rep0
    finally:
        engine_a.close()


def test_nack_giveup_records_incomplete():
    """With nobody able to answer, the NACK budget exhausts and records
    an outcome=incomplete e2e event (the SLO burn signal)."""
    hub = LoopbackHub()  # single node: broadcasts reach no one
    node = LoopbackNetwork(hub, format_address("tcp", "localhost", 3300))
    slo = SLOEvaluator(window_seconds=30.0, min_events=1)
    plugin = ShardPlugin(backend="numpy", slo=slo)
    plugin.nack_grace_seconds = 0.1
    plugin.nack_backoff_base = 0.05
    plugin.nack_max_retries = 1
    node.add_plugin(plugin)

    sender = LoopbackNetwork(hub, format_address("tcp", "localhost", 3301))
    giv0 = counter_value("noise_ec_nack_giveups_total")
    hist0 = default_registry().histogram(
        "noise_ec_e2e_latency_seconds"
    ).labels(outcome="incomplete").count
    payload = b"never completes!"  # 16 bytes, k=4
    shards = ShardPlugin(backend="numpy").prepare_shards(
        sender.id, sender.keys, payload
    )
    node.deliver(shards[0].marshal(), sender.id)
    deadline = time.time() + 15
    while (
        time.time() < deadline
        and counter_value("noise_ec_nack_giveups_total") == giv0
    ):
        time.sleep(0.02)
    assert counter_value("noise_ec_nack_giveups_total") == giv0 + 1
    assert default_registry().histogram(
        "noise_ec_e2e_latency_seconds"
    ).labels(outcome="incomplete").count == hist0 + 1
    verdict = slo.verdict()
    assert verdict["events"] >= 1 and verdict["success_rate"] == 0.0


# --------------------------------------------------------- reconnect


def test_tcp_reconnect_after_forced_reset():
    """A chaos reset kills the established connection; the supervisor
    re-dials the PROXY address (the address it originally dialed) and
    the pair re-registers without any new bootstrap call."""
    a, b, inbox_a, _ = make_tcp_pair()
    proxy = ChaosProxy(
        "127.0.0.1", a.port, profile=ChaosProfile(resets=(0.6,)), seed=1
    ).start()
    ok0 = counter_value("noise_ec_reconnect_total", result="ok")
    try:
        b.bootstrap([proxy.address])
        deadline = time.time() + 10
        while time.time() < deadline and (not b.peers or not a.peers):
            time.sleep(0.02)
        assert b.peers and a.peers
        # Schedule the reset relative to registration (deflake: see the
        # chaos-soak test's rebase_clock note).
        proxy.rebase_clock()
        # Wait for the scheduled reset to drop the connection...
        deadline = time.time() + 10
        while time.time() < deadline and proxy.reset_count == 0:
            time.sleep(0.02)
        assert proxy.reset_count == 1
        # ...and the supervisor to re-establish it.
        deadline = time.time() + 20
        while time.time() < deadline and (
            counter_value("noise_ec_reconnect_total", result="ok") == ok0
            or not b.peers or not a.peers
        ):
            time.sleep(0.05)
        assert counter_value("noise_ec_reconnect_total", result="ok") > ok0
        assert b.peers and a.peers
        assert b.supervisor.health_summary()["reconnects_ok"] >= 1
        # The healed link still carries verified traffic end to end.
        b.plugins[0].shard_and_broadcast(b, b"post reset send!")
        deadline = time.time() + 10
        while time.time() < deadline and not inbox_a:
            time.sleep(0.02)
        assert inbox_a == [b"post reset send!"]
    finally:
        proxy.close()
        a.close()
        b.close()


def test_wait_writable_is_noop_on_event_loop_thread():
    """wait_writable called ON the event-loop thread must return
    immediately (the drain it waits for runs on that very thread), even
    with a peer far over the soft cap."""
    import asyncio

    from noise_ec_tpu.host.crypto import PeerID
    from noise_ec_tpu.host.transport import _Peer

    net = TCPNetwork(host="127.0.0.1", port=0, discovery=False)
    net.listen()

    class _Stalled:
        class transport:
            @staticmethod
            def get_write_buffer_size():
                return 1 << 40  # absurdly over any cap

    try:
        net.peers[b"k" * 32] = _Peer(
            PeerID.create("tcp://x:1", b"k" * 32), _Stalled()
        )

        async def on_loop():
            t0 = time.monotonic()
            net.wait_writable(timeout=3.0)
            return time.monotonic() - t0

        elapsed = asyncio.run_coroutine_threadsafe(
            on_loop(), net._loop
        ).result(timeout=10)
        assert elapsed < 0.25  # guard short-circuits, no 3 s stall
        # Off the loop thread the same state DOES block until timeout.
        t0 = time.monotonic()
        net.wait_writable(timeout=0.3)
        assert time.monotonic() - t0 >= 0.29
    finally:
        net.peers.clear()
        net.close()


# ------------------------------------------------------ acceptance soak


def test_chaos_soak_eventual_delivery_and_health_flip(lockgraph, tmp_path):
    """The acceptance soak (ISSUE 4): two TCP nodes through the chaos
    proxy — 5% drop, 1% corrupt, one scheduled 2 s directional
    partition, one forced connection reset — deliver 100% of a
    200-message broadcast via reconnect + NACK repair + announce, accept
    zero wrong objects, and /healthz flips 503 → 200 as the partition
    heals and the SLO window slides. The flight recorder rides the whole
    soak: the flip auto-captures exactly ONE incident bundle (rate limit
    holds against re-flips), the delta ring stays under its byte cap,
    and the recorder's self-measured tick cost stays under 1% of wall
    time (the "always-on" claim, docs/observability.md)."""
    import json

    from noise_ec_tpu.obs.recorder import FlightRecorder
    from noise_ec_tpu.obs.server import StatsServer
    from urllib.request import urlopen

    # Sender A: stores its broadcasts, answers NACK interest, announces
    # recent stripes (the silent-loss recovery path).
    a = TCPNetwork(host="127.0.0.1", port=0, discovery=False)
    store_a = StripeStore()
    engine_a = RepairEngine(
        store_a, network=a, respond_interval_seconds=0.2,
        linger_seconds=0.0, announce_interval_seconds=0.25,
        announce_window_seconds=120.0, announce_max_stripes=256,
    )
    engine_a.start()
    plugin_a = ShardPlugin(
        backend="numpy", store=store_a,
        # k=5 n=6: one parity shard, so a single dropped frame leaves
        # the pool below k — the NACK path carries real weight.
        minimum_needed_shards=5, total_shards=6,
    )
    a.add_plugin(plugin_a)
    a.listen()

    # The chaos link B dials through. Directions are relative to the
    # DIALER (B): a2b = B->A (NACKs, interest), b2a = A->B (payloads).
    # The partition severs B->A: stuck pools' NACK rounds go unanswered
    # and give up during it (incomplete events burn the SLO); payloads
    # keep flowing so the window has plenty of events.
    profile = ChaosProfile.parse(
        "drop=0.05,corrupt=0.01,reset@0.6,partition@1.2:2:a2b"
    )
    chaos_seed = 1234
    proxy = ChaosProxy(
        "127.0.0.1", a.port, profile=profile, seed=chaos_seed
    ).start()

    inbox_b = []
    slo = SLOEvaluator(window_seconds=5.0, min_events=10)
    b = TCPNetwork(
        host="127.0.0.1", port=0, discovery=False, connection_timeout=2.0
    )
    store_b = StripeStore()
    engine_b = RepairEngine(
        store_b, network=b, respond_interval_seconds=0.2, linger_seconds=0.0
    )
    engine_b.start()
    plugin_b = ShardPlugin(
        backend="numpy", store=store_b, slo=slo,
        on_message=lambda m, s: inbox_b.append(m),
    )
    plugin_b.nack_grace_seconds = 0.3
    plugin_b.nack_backoff_base = 0.3
    plugin_b.nack_max_retries = 2
    b.add_plugin(plugin_b)
    b.listen()
    server = StatsServer(
        slo=slo, health_details=b.supervisor.health_summary
    )
    # The always-on flight recorder: subscribed to the soak's SLO, so
    # the partition's healthy -> degraded flip freezes the ring into a
    # bundle with no poller in the loop.
    recorder = FlightRecorder(
        slo=slo, incident_dir=str(tmp_path), max_bytes=256 * 1024,
        min_bundle_interval=300.0, interval=0.5,
    )
    # The diagnosis engine rides the same SLO + recorder (ISSUE 20):
    # the flip bundle must embed the event window and a verdict.
    from noise_ec_tpu.obs.diagnose import VERDICTS, DiagnosisEngine

    DiagnosisEngine(slo=slo, recorder=recorder)
    recorder.start()
    t_wall0 = time.perf_counter()

    def healthz() -> int:
        try:
            with urlopen(f"{server.url}/healthz", timeout=2) as resp:
                return resp.status
        except Exception as exc:  # noqa: BLE001 — 503 raises HTTPError
            return getattr(exc, "code", 0)

    saw_503 = [False]
    stop_poll = threading.Event()

    def poll_health():
        while not stop_poll.wait(0.1):
            if healthz() == 503:
                saw_503[0] = True

    poller = threading.Thread(target=poll_health, daemon=True)
    poller.start()

    from noise_ec_tpu.obs.trace import request as trace_request
    probe_tids: list[str] = []
    stop_probe = threading.Event()

    sent = []
    try:
        b.bootstrap([proxy.address])
        deadline = time.time() + 10
        while time.time() < deadline and (not b.peers or not a.peers):
            time.sleep(0.02)
        assert b.peers and a.peers, (a.errors, b.errors)
        # Anchor the chaos schedule on REGISTRATION, not proxy start: on
        # a loaded box registration can outlast reset@0.6, which then
        # aborts zero connections and the soak never exercises the
        # reconnect it asserts on (the transport-timing flake).
        proxy.rebase_clock()

        # Failed GET probes throughout the soak: their kept_error
        # request traces must ride the flip bundle (ISSUE 18 — incident
        # bundles embed the degraded window's sampled traces, not just
        # loose spans). Probing repeatedly keeps a fresh trace in the
        # span ring however the flip lands against the soak's span
        # stampede.
        def probe_requests():
            while not stop_probe.wait(0.2):
                try:
                    with trace_request("get", tenant="soak") as rscope:
                        raise RuntimeError("degraded-window probe")
                except RuntimeError:
                    if rscope.decision == "kept_error":
                        probe_tids.append(rscope.trace_id)

        prober = threading.Thread(target=probe_requests, daemon=True)
        prober.start()

        for i in range(200):
            payload = f"chaos soak msg {i:04d}!".encode()  # 20 B: k=5 stripes
            assert len(payload) % 5 == 0, len(payload)
            sent.append(payload)
            plugin_a.shard_and_broadcast(a, payload)
            time.sleep(0.015)  # the 3 s send window straddles the chaos

        # 100% eventual delivery via reconnect + NACK + announce.
        deadline = time.time() + 90
        while time.time() < deadline and len(inbox_b) < len(sent):
            time.sleep(0.2)
        assert sorted(inbox_b) == sorted(sent), (
            f"delivered {len(inbox_b)}/{len(sent)}",
            proxy.stats(),
            plugin_b.counters.snapshot(),
        )
        # Exactly once each, and nothing wrongly accepted: every
        # delivered object verified against the sender's signature
        # (corrupted frames died at the transport signature check).
        assert len(inbox_b) == len(sent)
        assert plugin_b.counters.snapshot().get("verify_failures", 0) == 0
        # The chaos actually happened.
        stats = proxy.stats()
        assert stats["resets"] == 1
        assert stats["dropped"] > 0 and stats["corrupted"] > 0
        assert stats["partitioned"] > 0
        # The reset forced at least one supervised reconnect.
        assert b.supervisor.health_summary()["reconnects_ok"] >= 1
        # Health: the partition burned the SLO window (503 observed
        # while it was severed)...
        assert saw_503[0], slo.verdict()
        # ...and /healthz recovered to 200 once the window slid past it.
        deadline = time.time() + 30
        status = healthz()
        while time.time() < deadline and status != 200:
            time.sleep(0.25)
            status = healthz()
        assert status == 200, slo.verdict()

        # --- flight recorder rode the soak (ISSUE 16): exactly one
        # bundle on the flip (re-flips rate-limited), ring bounded,
        # overhead within the 1% always-on budget.
        wall = time.perf_counter() - t_wall0
        recorder.close()
        bundles = sorted(tmp_path.glob("incident-*-flip.json"))
        assert len(bundles) == 1, [p.name for p in tmp_path.iterdir()]
        assert counter_value(
            "noise_ec_incident_bundles_total", trigger="flip"
        ) >= 1
        doc = json.loads(bundles[0].read_text())
        assert doc["trigger"] == "flip"
        assert doc["verdict"]["healthy"] is False
        assert doc["timeline"], "the pre-flip ring must ride the bundle"
        # A sampled request trace from the degraded window rode the
        # bundle whole (root span included), grouped under its req- id.
        stop_probe.set()
        carried = [t for t in probe_tids if t in doc["traces"]]
        assert carried, (sorted(doc["traces"]), len(probe_tids))
        assert "request" in {
            s["name"] for s in doc["traces"][carried[0]]
        }
        # The bundle loads in the offline reporter.
        import sys as _sys
        from pathlib import Path as _Path

        _sys.path.insert(
            0, str(_Path(__file__).resolve().parent.parent / "tools")
        )
        try:
            import trace_report
        finally:
            _sys.path.pop(0)
        report = trace_report.render_incident(doc)
        assert "healthy->degraded flip(s) in window" in report

        # --- the bundle carries the "why" layer (ISSUE 20): the wide-
        # event window rode along, and it holds the connection-
        # lifecycle / repair evidence the injected reset + partition
        # left behind.
        assert doc.get("events"), "flip bundle must embed the event window"
        ev_names = {e["name"] for e in doc["events"]}
        assert any(
            n.startswith(("peer.", "conn.", "repair.")) for n in ev_names
        ), ev_names
        # The embedded verdict is consistent with the injected fault:
        # the reset + severed dial land >= 2 peer.down/peer.drop events
        # in the window, so domain-loss must rank among the verdicts —
        # and every verdict stays inside the closed vocabulary with
        # evidence seqs that resolve against the embedded window.
        diagnosis = doc.get("diagnosis")
        assert diagnosis and "verdicts" in diagnosis, diagnosis
        names = [v["verdict"] for v in diagnosis["verdicts"]]
        assert set(names) <= set(VERDICTS), names
        assert "domain-loss" in names, (names, sorted(ev_names))
        embedded_seqs = {e["seq"] for e in doc["events"]}
        for v in diagnosis["verdicts"]:
            if v["verdict"] == "domain-loss":
                assert v["evidence"]["event_ids"], v
                assert set(v["evidence"]["event_ids"]) <= embedded_seqs, v

        stats_rec = recorder.stats()
        assert stats_rec["ring_bytes"] <= 256 * 1024
        assert stats_rec["tick_seconds"] <= 0.01 * wall, (
            stats_rec, wall,
        )
    except Exception:
        # Flake forensics (ISSUE 20): a failed soak prints the chaos
        # seed (the run is reproducible — the proxy's schedule and rng
        # derive from it) and the wide-event ring tail, so the decision
        # trail that led into the failure is in the test log instead of
        # gone with the process.
        from noise_ec_tpu.obs.events import default_event_log

        print(f"\n--- chaos-soak forensics: seed={chaos_seed} ---")
        try:
            print("proxy:", proxy.stats())
        except Exception:  # noqa: BLE001 — proxy may already be closed
            pass
        for rec in default_event_log().dump()[-40:]:
            print(
                f"  ev#{rec['seq']} t={rec['ts']:.3f} {rec['name']} "
                f"[{rec['severity']}] {rec['attrs']}"
            )
        raise
    finally:
        stop_poll.set()
        stop_probe.set()
        recorder.close()
        server.close()
        proxy.close()
        a.close()
        b.close()
        engine_a.close()
        engine_b.close()
