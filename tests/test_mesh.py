"""Mesh dispatch tier tests (docs/design.md §13) on the 8-virtual-CPU
device mesh the conftest forces: byte-identity of the sharded encode /
repair / decode routes vs the single-device golden paths, uneven tail
batches, the zero-reshard chained encode→decode contract, and the
mid-batch device-fault fan-out through the codec breaker."""

from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from noise_ec_tpu.gf.field import GF256, GF65536
from noise_ec_tpu.matrix.generators import generator_matrix
from noise_ec_tpu.matrix.hostmath import host_matvec
from noise_ec_tpu.matrix.linalg import reconstruction_matrix
from noise_ec_tpu.obs.registry import default_registry
from noise_ec_tpu.ops.dispatch import DeviceCodec
from noise_ec_tpu.parallel.mesh import (
    MeshRouter,
    configure_mesh_router,
    ladder_pad,
    mesh_router,
    reset_mesh_router,
)

_FIELDS = {"gf256": GF256, "gf65536": GF65536}


def counter_value(name: str, **labels) -> float:
    return default_registry().counter(name).labels(**labels).value


@pytest.fixture
def mesh8():
    """Force the router on over the 8 virtual CPU devices, restore the
    (CPU-disabled) default afterwards so later test modules see the
    single-device tier."""
    router = configure_mesh_router(enable=True)
    assert router.enabled and router.n_pow2 == 8
    yield router
    reset_mesh_router()


def test_ladder_and_device_planning(mesh8):
    assert ladder_pad(1) == 1 and ladder_pad(5) == 8 and ladder_pad(8) == 8
    assert mesh8.n_dev_for(2) == 2  # never wider than the padded batch
    assert mesh8.n_dev_for(5) == 8
    assert mesh8.n_dev_for(64) == 8
    assert mesh8.should_shard(2) and not mesh8.should_shard(1)
    # Default construction on this CPU rig: present but disabled.
    reset_mesh_router()
    assert not mesh_router().should_shard(64)


# ------------------------------------------------ byte identity, 3 tiers


@pytest.mark.parametrize("field,k,r", [
    ("gf256", 4, 2),
    ("gf256", 10, 4),
    ("gf65536", 3, 2),
])
def test_mesh_sym_tier_byte_identity_uneven_tail(mesh8, rng, field, k, r):
    """XLA-kernel batches ride the pjit tier: B=5 (not divisible by the
    8-device mesh — ladder pad carries garbage members) must be
    byte-identical to the single-device host truth for every geometry,
    GF(2^16) included."""
    gf = _FIELDS[field]()
    G = generator_matrix(gf, k, k + r, "cauchy")
    dev = DeviceCodec(field=field, kernel="xla")
    before = counter_value(
        "noise_ec_mesh_sharded_dispatches_total", mode="pjit"
    )
    Ds = [
        rng.integers(0, gf.order, size=(k, 96)).astype(gf.dtype)
        for _ in range(5)
    ]
    got = dev.matmul_stripes_many(G[k:], Ds)
    for D, g in zip(Ds, got):
        np.testing.assert_array_equal(g, host_matvec(gf, G[k:], D))
        assert g.flags.writeable  # the matmul_stripes contract
    assert counter_value(
        "noise_ec_mesh_sharded_dispatches_total", mode="pjit"
    ) > before


def test_mesh_words_tier_byte_identity(mesh8, rng):
    """The baked GF(2^8) route (the TPU hot path, interpret kernel on
    CPU) shards the staged words batch over shard_map."""
    gf = GF256()
    k, r = 10, 4
    G = generator_matrix(gf, k, k + r, "cauchy")
    dev = DeviceCodec(field="gf256", kernel="pallas_interpret")
    before = counter_value(
        "noise_ec_mesh_sharded_dispatches_total", mode="shard_map"
    )
    Ds = [
        rng.integers(0, 256, size=(k, 512)).astype(np.uint8)
        for _ in range(5)
    ]
    got = dev.matmul_stripes_many(G[k:], Ds)
    for D, g in zip(Ds, got):
        np.testing.assert_array_equal(g, host_matvec(gf, G[k:], D))
    assert counter_value(
        "noise_ec_mesh_sharded_dispatches_total", mode="shard_map"
    ) > before


def test_mesh_bytesliced_tier_byte_identity(mesh8, rng):
    """GF(2^16) on a Pallas kernel: the batch splits into byte rows and
    rides the m=8 words tier (unpermuted expansion), byte-identical to
    the wide-field host truth."""
    gf = GF65536()
    k, r = 3, 2
    G = generator_matrix(gf, k, k + r, "cauchy")
    dev = DeviceCodec(field="gf65536", kernel="pallas_interpret")
    Ds = [
        rng.integers(0, 1 << 16, size=(k, 128)).astype(np.uint16)
        for _ in range(4)
    ]
    got = dev.matmul_stripes_many(G[k:], Ds)
    for D, g in zip(Ds, got):
        np.testing.assert_array_equal(g, host_matvec(gf, G[k:], D))


def test_batchcodec_rides_the_mesh(mesh8, rng):
    """BatchCodec.encode_batch / reconstruct_batch (the parallel-layer
    batch entries) route matmul_batch through the pjit tier."""
    from noise_ec_tpu.golden.codec import GoldenCodec
    from noise_ec_tpu.parallel.batch import BatchCodec

    for field in ("gf256", "gf65536"):
        gf = _FIELDS[field]()
        bc = BatchCodec(4, 2, field=field)
        g = GoldenCodec(4, 6, field=field)
        batch = rng.integers(0, gf.order, size=(5, 4, 50)).astype(gf.dtype)
        full = np.asarray(bc.encode_batch(jnp.asarray(batch)))
        for b in range(5):
            np.testing.assert_array_equal(
                full[b, 4:], np.asarray(g.encode(batch[b]))
            )
        present = [1, 2, 4, 5]  # shards 0 and 3 erased
        rebuilt = np.asarray(
            bc.reconstruct_batch(jnp.asarray(full[:, present]), present)
        )
        np.testing.assert_array_equal(rebuilt, full)


# ------------------------------------------------------- repair storms


def test_repair_storm_rides_sharded_entry(mesh8, rng):
    """The repair engine's group reconstruct (store/repair.py →
    rs.matmul_many → coalescer → matmul_stripes_many) lands on the mesh
    tier and heals byte-identically."""
    from noise_ec_tpu.store import RepairEngine, Scrubber, StripeStore

    k, n = 4, 6
    store = StripeStore(backend="device")
    engine = RepairEngine(store, batch_min=2, linger_seconds=0.0)
    assert engine.max_batch == 512  # mesh-scaled drain width (8 devices)
    scrub = Scrubber(store, engine, interval_seconds=3600.0)
    payloads = {}
    for i in range(6):
        sig = i.to_bytes(8, "little") + bytes(56)
        blob = rng.integers(0, 256, size=k * 256, dtype=np.uint8).tobytes()
        payloads[store.put_object(sig, blob, k, n)] = blob
    before = counter_value(
        "noise_ec_mesh_sharded_dispatches_total", mode="pjit"
    )
    for skey in payloads:
        store.drop_shard(skey, 0)
        store.drop_shard(skey, 1)
    scrub.run_cycle()
    assert engine.drain_once() == len(payloads)
    for skey, blob in payloads.items():
        assert store.read(skey) == blob
    assert counter_value(
        "noise_ec_mesh_sharded_dispatches_total", mode="pjit"
    ) > before


# -------------------------------------------------- fault fan-out path


def test_mesh_fault_fans_out_through_breaker_to_host(mesh8, monkeypatch):
    """A device fault mid-mesh-batch degrades every member through the
    codec breaker to golden host bytes — the PR-4 graceful-degradation
    contract holds on the sharded route too."""
    from noise_ec_tpu.codec.rs import ReedSolomon
    from noise_ec_tpu.ops.dispatch import configure_codec_breaker

    configure_codec_breaker(reset_timeout=60.0)
    try:
        rs = ReedSolomon(4, 2)
        rng = np.random.default_rng(7)
        Ds = [
            rng.integers(0, 256, size=(4, 64)).astype(np.uint8)
            for _ in range(6)
        ]
        want = [host_matvec(rs.gf, rs.G[4:], D) for D in Ds]

        def boom(self, codec, M, Ds, B_pad):
            raise RuntimeError("injected mesh device fault")

        monkeypatch.setattr(MeshRouter, "matmul_sym_many", boom)
        fallbacks0 = counter_value(
            "noise_ec_codec_fallback_total", reason="error"
        )
        got = rs.matmul_many(rs.G[4:], Ds)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)
        assert counter_value(
            "noise_ec_codec_fallback_total", reason="error"
        ) > fallbacks0
        assert not rs._breaker.closed
    finally:
        configure_codec_breaker()  # fresh, closed breaker for later tests


# --------------------------------------------- chained decode, 0 reshard


def test_chained_encode_decode_zero_reshard(mesh8, rng):
    """The e2e acceptance: mesh encode → on-device corruption → mesh
    fused decode1, with every stage's out_shardings matching the next
    stage's in_shardings. noise_ec_mesh_reshard_total must not move, the
    corrected row must equal the pre-corruption truth, and the verify
    rows must be all-zero (single-support hypothesis holds)."""
    gf = GF256()
    k, r = 10, 4
    G = generator_matrix(gf, k, k + r, "cauchy")
    dev = DeviceCodec(field="gf256", kernel="pallas_interpret")
    B, TW = 8, 8192  # one lane quantum: no pad, donation-eligible shape
    words = rng.integers(
        0, 1 << 32, size=(B, k, TW), dtype=np.uint64
    ).astype(np.uint32)
    router = mesh8
    n_dev = router.n_dev_for(B)
    parity = router.matmul_words_batch(dev, G[k:], words)
    data_dev = jax.device_put(words, router.sharding_for(n_dev))
    assemble = jax.jit(
        lambda d, p: jnp.concatenate([d, p], axis=1).at[:, 5, :].set(
            jnp.concatenate([d, p], axis=1)[:, 5, :] ^ np.uint32(0xA5A5A5A5)
        ),
        out_shardings=router.sharding_for(n_dev),
    )
    full = assemble(data_dev, parity)
    reshard0 = counter_value("noise_ec_mesh_reshard_total")
    corrected, bad = router.decode1_words_batch(dev, G[k:], 5, full)
    assert counter_value("noise_ec_mesh_reshard_total") == reshard0, (
        "chained encode→decode resharded"
    )
    assert not np.asarray(bad).any()
    np.testing.assert_array_equal(np.asarray(corrected), words[:, 5, :])
    # Negative control: a replicated input IS a reshard and must count.
    from jax.sharding import NamedSharding, PartitionSpec as P

    repl = jax.device_put(
        np.asarray(full),
        NamedSharding(router.mesh_for(n_dev), P(None, None, None)),
    )
    corrected2, _ = router.decode1_words_batch(dev, G[k:], 5, repl)
    assert counter_value("noise_ec_mesh_reshard_total") == reshard0 + 1
    np.testing.assert_array_equal(np.asarray(corrected2), words[:, 5, :])


def test_bw_device_route_speculates_fused_decode1(mesh8, monkeypatch):
    """The Berlekamp-Welch device route's whole-share speculation runs
    the decode1 fold as ONE device matmul (matrix/bw.py device arm) and
    still recurses defeated columns to the exact per-column path."""
    from noise_ec_tpu.matrix import bw

    monkeypatch.setattr(bw, "_SPECULATE_MIN_S", 1 << 10)  # arm at 1 KiB
    gf = GF256()
    k, n = 4, 8
    G = generator_matrix(gf, k, n, "cauchy")
    dev = DeviceCodec(field="gf256", kernel="xla")
    rng = np.random.default_rng(0xB3)
    data = rng.integers(0, 256, size=(k, 4096)).astype(np.uint8)
    full = host_matvec(gf, G, data)
    rows = [np.ascontiguousarray(full[i]) for i in range(n)]
    rows[2] = rows[2] ^ 0x5A  # whole-share corruption of basis row 2
    res = bw.syndrome_decode_rows(
        gf, "cauchy", k, n, list(range(n)), rows, device=dev
    )
    assert res is not None
    data_rows, _, corrected = res
    np.testing.assert_array_equal(np.stack(data_rows), data)
    assert corrected


# ----------------------------------------------- bench_gate rig guard


def _bench_gate():
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "tools"))
    try:
        import bench_gate
    finally:
        sys.path.pop(0)
    return bench_gate


def test_bench_gate_flags_mesh_devices_regression():
    """batch_mesh_devices falling back to 1 on a rig whose MULTICHIP
    rounds prove an 8-device mesh must flag on fresh runs; a healthy
    mesh (or a genuinely single-device rig) must not."""
    bg = _bench_gate()
    rig = Path(__file__).resolve().parent / "data" / "bench_gate"
    assert bg.newest_multichip_devices(rig) == 8  # the fixture rig
    assert bg.mesh_rig_check({"batch_mesh_devices": 8}, rig) == []
    problems = bg.mesh_rig_check({"batch_mesh_devices": 1}, rig)
    assert problems and "mesh dispatch tier regressed" in problems[0]
    assert bg.mesh_rig_check({}, rig) != []  # sweep vanished entirely
    # Tolerance classes: sweep keys ride the device gate, staged mesh
    # stats the host one.
    assert bg.metric_tolerance("batch_mesh_encode_gbps_8chip") == \
        bg.DEFAULT_TOLERANCE
    assert bg.metric_tolerance("mesh_repair_gbps") == bg.HOST_TOLERANCE
    assert bg.metric_direction("batch_mesh_scaling_x") is None
    assert bg.metric_direction("batch_mesh_devices") is None  # identity
