#!/usr/bin/env python
"""Chip smoke: drive the served path once on a TPU, checked byte for byte.

    python chip_smoke.py             # one chip: the phases below, in order
    python chip_smoke.py --chips 4   # only the mesh dispatch tier over 4 chips

One process, one chip. Phases (each prints one ``phase ...`` line):

1. device  — platform, ``device_kind``, count, resolved ``DeviceCodec.kernel``;
   anything but a TPU exits non-zero (never continues on the CPU).
2. kernel  — RS(10,4) fused encode of HBM-resident uint32 words at 8 MiB
   per shard (80 MiB data, 32 MiB parity), bit-exact against the host
   codec; the plan ``verified_fused_plan`` chose must be a fused plan.
3. node    — the reference's main.go flow: two ``ShardPlugin`` nodes on
   the loopback transport, ``backend="device"``: chat lines sharded,
   signed, broadcast, reassembled and verified, then one 64 MiB stream.
4. objects — ``ObjectStore(k=10, n=14, stripe_bytes=10 MiB)`` (1 MiB
   cells, HDFS-EC's RS-10-4-1024k layout) over those two nodes: PUT
   4 x 64 MiB, GET each back, one range-GET, a degraded GET with 4 of 14
   shards gone from every stripe, and one corrupted-share decode through
   ``FEC(10, 14, bw_route="device")``.
5. audit   — zero codec fallbacks, a closed breaker, and device-op
   dispatches during the encode and the reconstruct phases.

Wall times printed on the way are smoke wall-times, not benchmark
numbers. Any failure raises (non-zero exit, no result line). The last
line of a passing run is exactly the JSON object
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

K, R = 10, 4
N = K + R
MIB = 1 << 20


class SmokeFailure(RuntimeError):
    """A phase's check failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(line: str) -> None:
    print(line, flush=True)


def _family_total(kind: str, name: str) -> float:
    """Sum of a counter family's children, or of a histogram family's
    observation counts, in the default registry."""
    from noise_ec_tpu.obs.registry import default_registry

    fam = getattr(default_registry(), kind)(name)
    if kind == "histogram":
        return sum(c.count for _, c in fam.children())
    return sum(c.value for _, c in fam.children())


def device_ops() -> float:
    """Device dispatches recorded so far (compile + execute routes; a
    host fallback never reaches this family)."""
    return _family_total("histogram", "noise_ec_device_op_seconds")


def _wait(pred, what: str, timeout: float = 300.0) -> None:
    deadline = time.monotonic() + timeout
    while not pred():
        check(time.monotonic() < deadline, f"timed out waiting for {what}")
        time.sleep(0.01)


# --------------------------------------------------------------- phases


def phase_device() -> dict:
    import jax

    from noise_ec_tpu.ops.dispatch import DeviceCodec

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    check(info["platform"] == "tpu",
          f"JAX platform is {info['platform']!r}, not a TPU")
    kernel = DeviceCodec().kernel
    say(f"phase device: platform={info['platform']} kind={info['kind']} "
        f"count={info['count']} kernel={kernel}")
    check(kernel == "pallas", f"DeviceCodec resolved kernel {kernel!r}")
    return info


def phase_kernel(shard_bytes: int = 8 * MIB, kernel: str = "pallas",
                 seed: int = 0) -> tuple:
    """RS(10,4) fused words encode at the benchmark's batch; returns the
    fused plan."""
    import jax
    import jax.numpy as jnp

    from noise_ec_tpu.gf.field import GF256
    from noise_ec_tpu.matrix.generators import generator_matrix
    from noise_ec_tpu.matrix.hostmath import host_matvec
    from noise_ec_tpu.ops.dispatch import DeviceCodec, pad_words
    from noise_ec_tpu.ops.pallas_fused import verified_fused_plan

    gf = GF256()
    G = generator_matrix(gf, K, N, "cauchy")
    dev = DeviceCodec(field="gf256", kernel=kernel)
    TW = shard_bytes // 4
    words = jax.random.bits(jax.random.key(seed), (K, TW), dtype=jnp.uint32)
    t0 = time.perf_counter()
    parity = dev.matmul_words(G[K:], words).block_until_ready()
    t_first = time.perf_counter() - t0
    t0 = time.perf_counter()
    parity = dev.matmul_words(G[K:], words).block_until_ready()
    t_warm = time.perf_counter() - t0
    plan = verified_fused_plan(dev.bits_rows_for(G[K:]), K, R, pad_words(TW),
                               8, kernel == "pallas_interpret")
    check(plan is not None, "no fused plan compiles for RS(10,4); the "
          "encode fell back to the three-kernel tier")
    data = np.asarray(words).view(np.uint8).reshape(K, shard_bytes)
    got = np.asarray(parity).view(np.uint8).reshape(R, shard_bytes)
    check(np.array_equal(got, host_matvec(gf, G[K:], data)),
          "fused RS(10,4) encode != host codec")
    say(f"phase kernel: ok RS(10,4) {shard_bytes // MIB} MiB/shard "
        f"({K * shard_bytes // MIB} MiB data, {R * shard_bytes // MIB} MiB "
        f"parity on device) plan={plan} smoke wall-time first={t_first:.3f}s "
        f"warm={t_warm:.4f}s")
    return plan


def _node_pair(ports: tuple) -> list:
    from noise_ec_tpu.host.transport import (
        LoopbackHub,
        LoopbackNetwork,
        format_address,
    )

    hub = LoopbackHub()
    return [LoopbackNetwork(hub, format_address("tcp", "localhost", p))
            for p in ports]


def phase_node(object_bytes: int = 64 * MIB, chunk_bytes: int = 4 * MIB,
               seed: int = 1) -> None:
    from noise_ec_tpu.host.plugin import ShardPlugin

    a, b = _node_pair((3001, 3002))
    lines, objects = [], []
    a.add_plugin(ShardPlugin(backend="device", minimum_needed_shards=K,
                             total_shards=N))
    b.add_plugin(ShardPlugin(
        backend="device", minimum_needed_shards=K, total_shards=N,
        on_message=lambda m, s: lines.append(bytes(m)),
        on_object=lambda m, s: objects.append(m),
    ))
    chat = [b"hello from node a", b"erasure-coded on the chip",
            bytes(range(256)) * 3]
    t0 = time.perf_counter()
    for line in chat:
        a.plugins[0].shard_and_broadcast(a, line)
    _wait(lambda: len(lines) >= len(chat), "chat lines at node b")
    check(sorted(lines) == sorted(chat), "chat lines differ after reassembly")
    t_chat = time.perf_counter() - t0

    payload = np.random.default_rng(seed).integers(
        0, 256, size=object_bytes, dtype=np.uint8).tobytes()
    t0 = time.perf_counter()
    a.plugins[0].stream_and_broadcast(a, payload, chunk_bytes=chunk_bytes)
    _wait(lambda: objects, "the streamed object at node b")
    t_stream = time.perf_counter() - t0
    check(len(objects) == 1 and objects[0] == payload,
          "streamed object differs at node b")
    say(f"phase node: ok {len(chat)} chat lines, one {object_bytes // MIB} "
        f"MiB stream ({chunk_bytes // MIB} MiB chunks) byte-identical; "
        f"smoke wall-time chat={t_chat:.3f}s stream={t_stream:.3f}s")


def phase_objects(n_objects: int = 4, object_bytes: int = 64 * MIB,
                  stripe_bytes: int = 10 * MIB, fec_bytes: int = 10 * MIB,
                  seed: int = 2) -> dict:
    """Object service over two device nodes; returns the device-op deltas
    of the encode (PUT) and reconstruct (degraded GET) steps."""
    from noise_ec_tpu.codec.fec import FEC, Share
    from noise_ec_tpu.host.plugin import ShardPlugin
    from noise_ec_tpu.service.objects import ObjectStore
    from noise_ec_tpu.store import StripeStore

    services = []
    for net in _node_pair((3101, 3102)):
        store = StripeStore(backend="device")
        plugin = ShardPlugin(backend="device", store=store)
        net.add_plugin(plugin)
        services.append(ObjectStore(store, plugin, net, k=K, n=N,
                                    stripe_bytes=stripe_bytes))
    a, b = services
    rng = np.random.default_rng(seed)
    payloads = {
        f"obj{i}": rng.integers(0, 256, size=object_bytes,
                                dtype=np.uint8).tobytes()
        for i in range(n_objects)
    }

    ops0 = device_ops()
    t0 = time.perf_counter()
    for name, data in payloads.items():
        a.put("smoke", name, data)
    t_put = time.perf_counter() - t0
    encode_ops = device_ops() - ops0

    def replicated() -> bool:
        try:
            docs = [b.resolve("smoke", name) for name in payloads]
        except KeyError:
            return False
        return all(len(b.store.status(key)["present"]) == N
                   for doc in docs for key in doc["stripes"])

    _wait(replicated, "node b to hold every stripe of every object")
    t0 = time.perf_counter()
    for name, data in payloads.items():
        check(b.read("smoke", name) == data, f"GET {name} differs")
    t_get = time.perf_counter() - t0
    start, length = stripe_bytes - 12345, stripe_bytes + 777
    _, total, chunks = b.get_range("smoke", "obj0", start, length)
    check(total == length and b"".join(chunks)
          == payloads["obj0"][start:start + length], "range GET differs")

    victim = f"obj{n_objects - 1}"
    doc = b.resolve("smoke", victim)
    stripes = sorted(set(doc["stripes"]))
    for key in stripes:
        for slot in (0, 3, 6, 9):  # 4 of 14, all data slots
            check(b.store.drop_shard(key, slot), f"drop {key}/{slot}")
    degraded0 = _family_total("counter", "noise_ec_store_degraded_reads_total")
    ops0 = device_ops()
    t0 = time.perf_counter()
    check(b.read("smoke", victim) == payloads[victim], "degraded GET differs")
    t_degraded = time.perf_counter() - t0
    reconstruct_ops = device_ops() - ops0
    check(_family_total("counter", "noise_ec_store_degraded_reads_total")
          - degraded0 == len(stripes), "degraded GET skipped reconstruct")

    fec = FEC(K, N, bw_route="device")
    data = rng.integers(0, 256, size=fec_bytes, dtype=np.uint8).tobytes()
    shares = fec.encode_shares(data)
    bad = bytearray(shares[3].data)
    bad[::7] = bytes(b ^ 0x5A for b in bad[::7])
    shares[3] = Share(3, bytes(bad))
    check(fec.decode(shares) == data, "corrupted-share decode differs")
    check(fec.stats["bw_decodes"] == 1, f"decode took {fec.stats}")

    say(f"phase objects: ok PUT {n_objects} x {object_bytes // MIB} MiB "
        f"(stripe {stripe_bytes // MIB} MiB, RS({K},{N - K})), GET and range "
        f"GET byte-identical, degraded GET over {len(stripes)} stripes with "
        f"4 of {N} shards gone, corrupted-share decode (bw_route=device) ok; "
        f"smoke wall-time put={t_put:.3f}s get={t_get:.3f}s "
        f"degraded={t_degraded:.3f}s")
    return {"encode": encode_ops, "reconstruct": reconstruct_ops}


def phase_audit(ops: dict) -> None:
    from noise_ec_tpu.ops.dispatch import codec_breaker

    fallbacks = _family_total("counter", "noise_ec_codec_fallback_total")
    breaker = codec_breaker()
    hits = _family_total("counter", "noise_ec_compile_cache_hits_total")
    say(f"phase audit: codec_fallbacks={fallbacks:g} "
        f"breaker={breaker.state()} device_ops_encode={ops['encode']:g} "
        f"device_ops_reconstruct={ops['reconstruct']:g} "
        f"compile_cache_hits={hits:g}")
    check(fallbacks == 0, f"{fallbacks:g} codec fallbacks to the host")
    check(breaker.closed, f"codec breaker is {breaker.state()}")
    check(ops["encode"] > 0, "no device dispatch during the PUTs")
    check(ops["reconstruct"] > 0, "no device dispatch during the degraded GET")


def phase_mesh(n_chips: int = 4, batch: int = 8, shard_bytes: int = MIB,
               kernel: str = "pallas", seed: int = 3) -> None:
    """Mesh dispatch tier over ``n_chips`` against the same batch with
    the router off and the host codec, plus the DP x TP encoder."""
    import jax
    import jax.numpy as jnp

    from noise_ec_tpu.gf.field import GF256
    from noise_ec_tpu.matrix.generators import generator_matrix
    from noise_ec_tpu.matrix.hostmath import host_matvec
    from noise_ec_tpu.matrix.linalg import reconstruction_matrix
    from noise_ec_tpu.ops.dispatch import DeviceCodec
    from noise_ec_tpu.parallel.batch import BatchCodec
    from noise_ec_tpu.parallel.mesh import (
        configure_mesh_router,
        default_2d_mesh,
        reset_mesh_router,
    )

    devs = jax.devices()
    check(len(devs) >= n_chips, f"{len(devs)} devices, need {n_chips}")
    gf = GF256()
    G = generator_matrix(gf, K, N, "cauchy")
    dev = DeviceCodec(field="gf256", kernel=kernel)
    TW = shard_bytes // 4
    x = np.random.default_rng(seed).integers(
        0, 1 << 32, size=(batch, K, TW), dtype=np.uint64).astype(np.uint32)
    data = x.view(np.uint8).reshape(batch, K, shard_bytes)
    want = np.stack([host_matvec(gf, G[K:], d) for d in data])
    erased = [0, 3, 6, 9]
    present = [i for i in range(N) if i not in erased]
    Rm = reconstruction_matrix(gf, G, present, erased)
    surv = np.concatenate(
        [x[:, [i for i in present if i < K]],
         want.view("<u4").reshape(batch, R, TW)], axis=1)

    def run(enable: bool) -> tuple:
        configure_mesh_router(devices=devs[:n_chips], enable=enable)
        par = np.asarray(dev.matmul_words_batch(G[K:], jnp.asarray(x)))
        rec = np.asarray(dev.matmul_words_batch(Rm, jnp.asarray(surv)))
        return par.view(np.uint8).reshape(batch, R, shard_bytes), rec

    sharded = "noise_ec_mesh_sharded_dispatches_total"
    try:
        before = _family_total("counter", sharded)
        par_mesh, rec_mesh = run(True)
        n_sharded = _family_total("counter", sharded) - before
        par_one, rec_one = run(False)
        check(_family_total("counter", sharded) - before == n_sharded,
              "router-off comparison dispatched through the mesh")
    finally:
        reset_mesh_router()
    check(n_sharded >= 2, f"{n_sharded:g} sharded dispatches, expected 2")
    check(np.array_equal(par_mesh, want), "mesh encode != host codec")
    check(np.array_equal(par_one, want), "single-device encode != host codec")
    check(np.array_equal(rec_mesh, rec_one), "mesh reconstruct != unsharded")
    check(np.array_equal(rec_mesh, x[:, erased]),
          "mesh reconstruct != the erased data rows")

    mesh = default_2d_mesh(devs[:n_chips])
    enc = BatchCodec(K, R).make_sharded_encoder(mesh, row_axis="row")
    par_dp = np.asarray(enc(jnp.asarray(data)))
    check(np.array_equal(par_dp, want), "DP x TP sharded encoder != host codec")
    say(f"phase mesh: ok {n_chips} chips, batch {batch} x RS({K},{R}) "
        f"{shard_bytes // MIB} MiB/shard: encode + 4-erasure reconstruct "
        f"through MeshRouter == MeshRouter(enable=False) == host codec; "
        f"DP x TP encoder mesh {dict(mesh.shape)} == host codec; "
        f"{sharded}={n_sharded:g}")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the mesh dispatch tier over 4 chips")
    args = ap.parse_args(argv)

    from noise_ec_tpu.ops.dispatch import default_compile_cache

    t0 = time.perf_counter()
    info = phase_device()  # no jit yet: the cache is armed right after
    check(info["count"] >= args.chips,
          f"{info['count']} devices visible, --chips {args.chips}")
    say(f"compile cache: {default_compile_cache()}")
    if args.chips == 4:
        phase_mesh(n_chips=4)
    else:
        phase_kernel()
        phase_node()
        phase_audit(phase_objects())
    say(f"smoke wall-time total={time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
