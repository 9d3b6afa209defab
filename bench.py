"""Headline benchmark: RS(10,4) encode GB/s on one chip (BASELINE config 1).

Measures the fused shard-bytes -> parity-bytes encode path (delta-swap pack
-> bitsliced GF(2) matmul -> unpack, all Pallas) on HBM-resident shards —
the same bytes-to-parity contract klauspost/reedsolomon's Encode() measures.
Shard buffers live on device as uint32 words (same bytes; the u8 view is
host-side metadata — see ops/dispatch.py on the u8 relayout cost).

Timing: host-side dispatch adds jitter to short device calls, so each
sample runs N dependent encodes
inside one jitted fori_loop (data-chained so they serialize) and the
per-encode time is the slope between a small-N and a payload-size-adaptive
large-N run (window sized to ~TARGET_WINDOW_S = 40 ms so jitter cannot
flip the slope).

Prints ONE JSON line:
  {"metric": ..., "value": ..., "unit": "GB/s", "vs_baseline": ...}
vs_baseline is against the BASELINE.json north-star bar of 40 GB/s
(klauspost AVX2-class; the reference itself publishes no numbers).
Secondary stats (reconstruct latency, per-config rates) go to stderr.
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np

NORTH_STAR_GBPS = 40.0
# Adaptive timing window per large-N sample (seconds); see module docstring.
TARGET_WINDOW_S = 0.040


class SmokeMismatch(RuntimeError):
    """A pre-timing golden-codec smoke failed: the kernel miscompiled.

    A distinct type (not bare ``assert``) so the checks survive ``python
    -O`` — a deterministic correctness failure must fail the bench run.
    """


def check_smoke(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeMismatch(what)


def chained_seconds_per_iter(make_encode, x, n_lo=10, n_hi=None, reps=7):
    """Median slope timing of one fused encode, chained inside fori_loop.

    The chain XORs 128 words of the output back into the input: iteration
    i+1's input depends on iteration i's output, so the encodes serialize
    (the pallas program is opaque — XLA must run it fully), while the
    chain itself adds negligible traffic. This measures encode alone, the
    same contract klauspost's Encode() benchmarks time.

    n_hi is sized so the measured window is ~40 ms assuming ~600 GB/s
    (the fused+factored kernel's ballpark) — dispatch jitter otherwise
    swamps fast configs (small payloads ran "negative" slopes with a
    fixed n_hi).
    """
    import jax
    from jax import lax

    if n_hi is None:
        n_hi = n_lo + max(
            50, min(4000, int(TARGET_WINDOW_S * 600e9 / max(x.nbytes, 1)))
        )

    def mk(N):
        @jax.jit
        def run(s):
            def body(i, s):
                p = make_encode(s).reshape(-1)[:128]
                idx = (0,) * (s.ndim - 1) + (slice(0, 128),)
                return s.at[idx].set(s[idx] ^ p)
            return lax.fori_loop(0, N, body, s).sum()
        return run

    lo, hi = mk(n_lo), mk(n_hi)
    np.asarray(lo(x)), np.asarray(hi(x))  # compile + warm
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter(); np.asarray(lo(x)); a = time.perf_counter() - t0
        t0 = time.perf_counter(); np.asarray(hi(x)); b = time.perf_counter() - t0
        ts.append((b - a) / (n_hi - n_lo))
    return float(np.median(ts))


def mesh_sweep_stats(rng=None) -> dict:
    """Sweep `batch_mesh_encode_gbps_{N}chip` over pow2 device subsets.

    Runs the mesh dispatch tier's OWN programs (parallel/mesh.py): the
    shard_map words tier on a Pallas backend, the pjit symbol tier on
    XLA — the same programs live batched traffic rides — with the batch
    axis over N devices, data-chained slope timing (no transfer in the
    window). `batch_mesh_devices` is the widest mesh exercised. Runs
    inline in main() over the devices this process sees: on one chip
    that is the 1-chip point only (no CPU stand-in for the others).
    """
    import jax
    import jax.numpy as jnp

    from noise_ec_tpu.gf.field import GF256
    from noise_ec_tpu.matrix.generators import generator_matrix
    from noise_ec_tpu.matrix.hostmath import host_matvec
    from noise_ec_tpu.ops.dispatch import DeviceCodec
    from noise_ec_tpu.parallel.mesh import (
        configure_mesh_router,
        reset_mesh_router,
    )

    if rng is None:
        rng = np.random.default_rng(5)
    backend = jax.default_backend()
    on_tpu = backend == "tpu"
    devs = jax.devices()
    n_avail = 1 << (len(devs).bit_length() - 1)
    sweep = [n for n in (1, 2, 4, 8) if n <= n_avail]
    out: dict = {"batch_mesh_devices": sweep[-1]}
    k, r = 10, 4
    gf = GF256()
    G = generator_matrix(gf, k, k + r, "cauchy")
    dev = DeviceCodec(field="gf256", kernel="pallas" if on_tpu else "xla")
    max_n = sweep[-1]
    if on_tpu:
        B, TW = 8 * max_n, (1 << 20) // 4  # 1 MiB shards, word layout
        x_host = rng.integers(
            0, 1 << 32, size=(B, k, TW), dtype=np.uint64
        ).astype(np.uint32)
        per_encode_bytes = B * k * TW * 4
    else:
        B, S = 2 * max_n, 32 << 10  # 32 KiB shards, symbol layout
        x_host = rng.integers(0, 256, size=(B, k, S)).astype(np.uint8)
        per_encode_bytes = B * k * S
    try:
        for N in sweep:
            router = configure_mesh_router(
                devices=devs[:N], enable=True, min_shard_batch=1
            )
            if on_tpu:
                fn = router.encode_words_program(dev, G[k:], N)
            else:
                fn = router.encode_sym_program(dev, G[k:], N)
            x = jax.device_put(x_host, router.sharding_for(N))
            got0 = np.asarray(fn(x))[0]
            if on_tpu:
                want0 = np.asarray(dev.matmul_words(
                    G[k:], jnp.asarray(x_host[0])
                ))
            else:
                want0 = host_matvec(gf, G[k:], x_host[0])
            check_smoke(np.array_equal(got0, want0),
                        f"mesh sweep N={N} encode != single-device truth")
            kwargs = {} if on_tpu else {"n_lo": 2, "n_hi": 12, "reps": 5}
            t = chained_seconds_per_iter(fn, x, **kwargs)
            out[f"batch_mesh_encode_gbps_{N}chip"] = round(
                per_encode_bytes / t / 1e9, 2
            )
        if len(sweep) > 1:
            out["batch_mesh_scaling_x"] = round(
                out[f"batch_mesh_encode_gbps_{max_n}chip"]
                / out["batch_mesh_encode_gbps_1chip"], 2
            )
    finally:
        reset_mesh_router()
    return out


def main() -> None:
    import jax
    import jax.numpy as jnp

    from noise_ec_tpu.gf.field import GF256
    from noise_ec_tpu.matrix.generators import generator_matrix
    from noise_ec_tpu.matrix.linalg import reconstruction_matrix
    from noise_ec_tpu.ops.dispatch import (
        DeviceCodec,
        default_compile_cache,
        plan_sublaunches,
    )

    default_compile_cache()
    backend = jax.default_backend()
    on_tpu = backend == "tpu"
    k, r = 10, 4
    # 8 x 1 MiB per shard folded into the stripe axis (HBM-resident batch,
    # BASELINE config 5; positionwise layout makes this identical to 8
    # independent 1 MiB-shard objects).
    S = (8 if on_tpu else 1) * (1 << 20)
    TW = S // 4
    gf = GF256()
    G = generator_matrix(gf, k, k + r, "cauchy")
    dev = DeviceCodec(field="gf256", kernel="pallas" if on_tpu else "xla")
    rng = np.random.default_rng(0)
    data_bytes = k * S

    stats = {"backend": backend, "kernel": dev.kernel, "data_bytes": data_bytes}

    # Host-path sections run FIRST, before the TPU kernel sections:
    # device-era host activity added ~10-40% load tails to host timing
    # on an earlier rig (identical code read 6.3 ms before TPU work and
    # 10.5 ms after on one run).
    # --- config D: decode under corruption (the infectious Decode
    # guarantee, SURVEY.md §2.3 D1 — error CORRECTION, not just erasure
    # fill). 1 MiB shards, all n shares present, RS(10,4):
    # (a) whole-share: one share entirely wrong (the BW decoder's
    #     vectorized fast path — one interpolation + re-encode);
    # (b) scattered: corrupt bytes sprinkled across two shares
    #     (per-column Berlekamp-Welch on the affected columns).
    try:
        from noise_ec_tpu.codec.fec import FEC, Share

        # bw_route="host" (the default): shares arrive as host bytes, so
        # the syndrome decode's matmuls run on the native shim rather
        # than re-shipping 14 MiB to the device per decode. bw_route="device"
        # exists for device-resident stripes (ops/dispatch.py
        # syndrome_stripes) and is covered by tests + hwcheck.
        fec = FEC(k, k + r, backend="numpy")
        S1 = 1 << 20
        stripes = rng.integers(0, 256, size=(k, S1)).astype(np.uint8)
        shares = fec.encode_shares(stripes.tobytes())
        cases: dict[str, tuple] = {}
        for name in ("whole_share", "scattered"):
            bad = [Share(s.number, s.data) for s in shares]
            if name == "whole_share":
                flip = np.frombuffer(bad[1].data, np.uint8) ^ 0xA5
                bad[1] = Share(1, flip.tobytes())
            else:
                for j, pos_seed in ((1, 11), (2, 13)):
                    arr = np.frombuffer(bad[j].data, np.uint8).copy()
                    pos = np.random.default_rng(pos_seed).integers(0, S1, 32)
                    arr[pos] ^= 0x5A
                    bad[j] = Share(j, arr.tobytes())
            got = fec.decode(bad)  # warm + correctness
            check_smoke(got == stripes.tobytes(),
                        f"corrupted-decode ({name}) wrong bytes")
            cases[name] = (fec, bad)
        # Wide-field variant (round 5: the shim's GF(2^16) tier — nibble-
        # shuffle mul_add over 0x1100B; was 12-16x slower on pure NumPy).
        fec16 = FEC(k, k + r, field="gf65536", backend="numpy")
        shares16 = fec16.encode_shares(stripes.tobytes())
        bad16 = [Share(s.number, s.data) for s in shares16]
        bad16[1] = Share(
            1, (np.frombuffer(bad16[1].data, np.uint8) ^ 0xA5).tobytes()
        )
        check_smoke(fec16.decode(bad16) == stripes.tobytes(),
                    "corrupted-decode (gf65536) wrong bytes")
        cases["gf65536_whole_share"] = (fec16, bad16)
        # INTERLEAVED timing: the single-core box has load epochs lasting
        # seconds; alternating the two cases inside one loop exposes both
        # to the same epochs (their p50 DIFFERENCE reflects code cost,
        # not which one ran during a busy second), and the short sleeps
        # stretch the 9 rounds across ~2 s so the p50 spans epochs
        # instead of living entirely inside one.
        samples: dict[str, list] = {name: [] for name in cases}
        order = list(cases.items())
        for round_i in range(9):
            # Rotate the case order per round: whichever case runs first
            # after the sleep takes the cold-cache hit, and a FIXED order
            # hands that penalty to the same case every round (measured:
            # it flattens a ~0.3 ms structural gap into a coin flip).
            for name, (fec_c, bad) in (
                order[round_i % len(order):] + order[: round_i % len(order)]
            ):
                t0 = time.perf_counter()
                fec_c.decode(bad)
                samples[name].append(time.perf_counter() - t0)
            if round_i < 8:
                time.sleep(0.25)
        for name, ts in samples.items():
            stats[f"decode_corrupt_{name}_p50_ms"] = round(
                sorted(ts)[4] * 1e3, 2
            )
            # min = the code's cost; p50 additionally carries whatever
            # the box was doing that second.
            stats[f"decode_corrupt_{name}_best_ms"] = round(
                min(ts) * 1e3, 2
            )
    except SmokeMismatch:
        raise  # deterministic correctness failure: fail the run
    except Exception as exc:  # noqa: BLE001 — secondary stat only
        stats["decode_corrupt_error"] = str(exc)[:80]

    # --- host-runtime story: full node round trip over REAL TCP sockets
    # (sign -> shard -> SHARD_BATCH frame -> recv ring -> batched frame
    # verify -> dispatch -> reassemble -> Ed25519 verify), driving the
    # wire hot loop (docs/design.md §15) the way production traffic
    # does: several senders with a pipelined in-flight window feeding
    # one receiver node. Pre-§15 this block timed a 2-node loopback
    # (1809.3 msgs/s at r05 with OpenSSL crypto; 143.5 on the pure-
    # Python dev box) with per-call blocking sends — the multi-sender
    # windowed shape is what the batch-verify and sendmsg coalescing
    # tiers exist to serve, so the stat drives them.
    try:
        import threading as _threading

        from noise_ec_tpu.host.plugin import ShardPlugin
        from noise_ec_tpu.host.transport import TCPNetwork

        # numpy codec backend: this stat isolates the HOST runtime
        # (signing, proto, ring parse, batched verify, dispatch); the
        # device throughput stats above cover the codec.
        n_senders = 4
        n_msgs = 24  # per sender
        payload_bytes = 64 << 10
        delivered = []
        done = _threading.Event()
        recv_kwargs = {}
        # recv_shards exists from ISSUE 11 on; the getattr guard lets the
        # same bench file measure the pre-§15 loop for the trajectory.
        if "recv_shards" in TCPNetwork.__init__.__code__.co_varnames:
            recv_kwargs["recv_shards"] = 2
        recv_net = TCPNetwork(host="127.0.0.1", port=0, discovery=False,
                              **recv_kwargs)
        recv_net.add_plugin(ShardPlugin(
            backend="numpy",
            on_message=lambda m, s: (
                delivered.append(len(m)),
                done.set() if len(delivered) >= n_senders * n_msgs else None,
            ),
        ))
        recv_net.listen()
        senders = []
        for i in range(n_senders):
            net = TCPNetwork(host="127.0.0.1", port=0, discovery=False)
            net.add_plugin(ShardPlugin(backend="numpy"))
            net.listen()
            net.bootstrap([recv_net.id.address])
            senders.append(net)
        deadline = time.time() + 30
        while time.time() < deadline and len(recv_net.peers) < n_senders:
            time.sleep(0.01)
        if len(recv_net.peers) < n_senders:
            raise SmokeMismatch(
                f"roundtrip bench: {len(recv_net.peers)}/{n_senders} "
                f"senders registered ({list(recv_net.errors)[:2]})"
            )
        base = rng.integers(0, 256, size=payload_bytes).astype(np.uint8)

        def _payload(sender_i: int, msg_i: int) -> bytes:
            # Distinct payloads: identical bytes share a file signature
            # and the receiver's replay protection would (correctly)
            # drop the repeats.
            b = base.copy()
            b[:8] = np.frombuffer(
                (sender_i << 32 | msg_i).to_bytes(8, "little"), np.uint8
            )
            return bytes(b)

        def _send(sender_i: int, count: int, first: int) -> None:
            plugin = senders[sender_i].plugins[0]
            for m in range(count):
                # Pipelined window: broadcasts return once frames are
                # posted (coalesce + flush ride the connection's loop),
                # so each sender keeps its peer's window full instead of
                # blocking per message; wait_writable is the bound.
                plugin.shard_and_broadcast(
                    senders[sender_i], _payload(sender_i, first + m)
                )

        # Warm (jit, codec caches, key tables, frame path) — one message
        # per sender, delivered before timing starts.
        for i in range(n_senders):
            _send(i, 1, 0)
        deadline = time.time() + 30
        while time.time() < deadline and len(delivered) < n_senders:
            time.sleep(0.01)
        delivered.clear()
        done.clear()
        t0 = time.perf_counter()
        threads = [
            _threading.Thread(target=_send, args=(i, n_msgs, 1))
            for i in range(n_senders)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        done.wait(timeout=120)
        t_host = time.perf_counter() - t0
        if len(delivered) != n_senders * n_msgs:
            # Deterministic correctness failure: fail the bench run like
            # the kernel smokes (not a stat, not retried).
            raise SmokeMismatch(
                f"host roundtrip lost messages: {len(delivered)}/"
                f"{n_senders * n_msgs}"
            )
        total = n_senders * n_msgs
        stats["host_node_roundtrip_msgs_per_s"] = round(total / t_host, 1)
        stats["host_node_roundtrip_mb_per_s"] = round(
            total * payload_bytes / t_host / 1e6, 1
        )
        # Tail latency from the receive path's own e2e histogram
        # (noise_ec_e2e_latency_seconds{outcome="ok"}): the deliveries
        # above are this process's only ok-outcome events, so the p99
        # here is the round trip's tail, not just its mean.
        from noise_ec_tpu.obs.registry import default_registry

        e2e_hist = default_registry().histogram(
            "noise_ec_e2e_latency_seconds"
        ).labels(outcome="ok")
        if e2e_hist.count:
            stats["host_node_roundtrip_p99_ms"] = round(
                e2e_hist.p99 * 1e3, 3
            )
        # Wire hot-loop amortization evidence (docs/design.md §15): how
        # many frames shared one Ed25519 batch verify, and how many
        # frames shared one send syscall, over this process's run.
        try:
            vb = default_registry().histogram(
                "noise_ec_wire_verify_batch_size"
            ).labels()
            if vb.count:
                stats["wire_verify_batch_size_p50"] = round(vb.p50, 2)
            fs = default_registry().histogram(
                "noise_ec_wire_frames_per_syscall"
            ).labels()
            if fs.count:
                stats["wire_frames_per_syscall"] = round(
                    fs.sum / fs.count, 2
                )
        except KeyError:
            pass  # pre-§15 registry (trajectory replays)
        for net in senders:
            net.close()
        recv_net.close()

        # --- large-object streaming: one 64 MiB object node-to-node as
        # 4 MiB erasure-coded chunks (sign once -> chunked encode ->
        # per-shard wire messages -> per-chunk reassembly -> one verify),
        # the round-3 end-to-end fast path. Two backends: the host-only
        # tier (numpy plugin + native C++ shim encode) and, on TPU, the
        # device codec through the pipelined StreamingEncoder. In-process
        # loopback (not TCP): this stat isolates the sign/encode/
        # reassemble pipeline; the TCP loop above owns the socket story.
        from noise_ec_tpu.host.transport import (
            LoopbackHub,
            LoopbackNetwork,
            format_address,
        )

        big = bytes(rng.integers(0, 256, size=64 << 20, dtype=np.uint8))
        for backend in ("numpy",) + (("device",) if on_tpu else ()):
            got = []
            # Fresh hub: exactly two nodes see the stream (the small-message
            # nodes above must not multiply the fan-out).
            hub2 = LoopbackHub()
            node_a = LoopbackNetwork(hub2, format_address("tcp", "localhost", 3100))
            node_b = LoopbackNetwork(hub2, format_address("tcp", "localhost", 3101))
            node_a.add_plugin(ShardPlugin(
                backend=backend, minimum_needed_shards=10, total_shards=14,
            ))
            node_b.add_plugin(ShardPlugin(
                backend=backend, minimum_needed_shards=10, total_shards=14,
                # Zero-copy delivery (ownership of the reassembly buffer
                # transfers) — the Go reference hands its decode []byte to
                # the consumer without a copy too (main.go:92).
                on_object=lambda m, s: got.append(len(m)),
            ))
            send_plugin = node_a.plugins[0]
            # Warm with a FULL-SIZE pass (shim/kernels/pools and the
            # allocator's high-water mark), then the timed trials below;
            # payloads are distinct because identical bytes dedup by
            # signature.
            send_plugin.stream_and_broadcast(node_a, big[2:] + b"\x00\x00",
                                             chunk_bytes=4 << 20)
            t_big = float("inf")
            # Best of 3 (distinct payloads — identical bytes dedup by
            # signature): single-core host timing has ~10% load tails and
            # this stat carries a hard round target.
            for trial in range(3):
                payload = big if trial == 0 else big[trial:] + bytes([trial]) * trial
                got.clear()
                t0 = time.perf_counter()
                send_plugin.stream_and_broadcast(node_a, payload,
                                                 chunk_bytes=4 << 20)
                t_big = min(t_big, time.perf_counter() - t0)
                if got != [len(payload)]:
                    raise SmokeMismatch(f"stream bench lost the object: {got}")
            # The device tier's figure gets its own key: it carries the
            # host<->device transfers the host tier never makes.
            suffix = "" if backend == "numpy" else "_device"
            stats[f"host_node_large_object{suffix}_mb_per_s"] = round(
                len(big) / t_big / 1e6, 1
            )
    except SmokeMismatch:
        raise  # deterministic correctness failure: fail the run
    except Exception as exc:  # noqa: BLE001 — secondary stat only
        stats["host_node_error"] = str(exc)[:80]


    # --- store repair: end-to-end background-repair throughput (scrub
    # flags the erasures -> repair queue coalesces same-shape stripes ->
    # ONE batched device reconstruct -> write-back), the always-on
    # production workload the stripe store turns the kernels into
    # (docs/store.md). Same-geometry RS(10,4) stripes with an identical
    # 2-shard erasure pattern, so the whole fleet folds into a single
    # BatchCodec dispatch per drain.
    try:
        from noise_ec_tpu.store import RepairEngine, Scrubber, StripeStore

        kr, nr = k, k + r
        B_rep = 16 if on_tpu else 8
        shard_rep = (1 << 20) if on_tpu else (64 << 10)
        obj_bytes = kr * shard_rep
        store = StripeStore(backend="device" if on_tpu else "numpy")
        engine = RepairEngine(store, batch_min=2, max_batch=2 * B_rep)
        scrub = Scrubber(store, engine, interval_seconds=3600.0)
        payloads = {}
        for i in range(B_rep):
            sig = i.to_bytes(8, "little") + bytes(56)
            blob = rng.integers(0, 256, size=obj_bytes, dtype=np.uint8
                                ).tobytes()
            payloads[store.put_object(sig, blob, kr, nr)] = blob

        def break_and_repair() -> float:
            for skey in payloads:
                store.drop_shard(skey, 0)
                store.drop_shard(skey, 1)
            t0 = time.perf_counter()
            scrub.run_cycle()
            repaired = engine.drain_once()
            t = time.perf_counter() - t0
            check_smoke(repaired == B_rep,
                        f"store repair healed {repaired}/{B_rep} stripes")
            return t

        break_and_repair()  # warm (jit compile, codec caches)
        for skey, blob in payloads.items():  # correctness before timing
            check_smoke(store.read(skey) == blob,
                        "store repair produced wrong bytes")
        t_rep = min(break_and_repair() for _ in range(3))
        stats["store_repair_gbps"] = round(
            B_rep * obj_bytes / t_rep / 1e9, 3
        )
        stats["store_repair_stripes_per_batch"] = B_rep
    except SmokeMismatch:
        raise  # deterministic correctness failure: fail the run
    except Exception as exc:  # noqa: BLE001 — secondary stat only
        stats["store_repair_error"] = str(exc)[:80]

    # --- LRC repair storm: shard-fetch amplification at equal storage
    # overhead (docs/lrc.md). Same single-loss storm run twice — once on
    # RS(40,16) (n=56) and once on LRC(40, 8 local, 8 global) (n=56) —
    # through scrub -> repair engine; repair_fetch_amplification is
    # (LRC shards read per heal) / (RS shards read per heal) off the
    # engine's noise_ec_store_repair_shards_read_total counters. The
    # ISSUE-13 bar (>= 5x fewer fetches, i.e. <= 0.2) gates fresh runs
    # in tools/bench_gate.py (lrc_repair_check); counts are exact, so
    # the stat is deterministic round over round (0.125 here: a local
    # heal reads its 5-member group cell instead of the full k=40).
    try:
        from noise_ec_tpu.obs.registry import default_registry as _lreg
        from noise_ec_tpu.store import (
            RepairEngine as _LRE,
            Scrubber as _LSC,
            StripeStore as _LSS,
        )

        k_l, g_l, n_l = 40, 8, 56
        B_l, shard_l = 8, 8 << 10
        reads_fam = _lreg().counter(
            "noise_ec_store_repair_shards_read_total"
        )
        per_heal = {}
        for code_label, code_str in (("rs", "rs"), ("lrc", f"lrc:{g_l}")):
            store_l = _LSS(backend="numpy")
            eng_l = _LRE(store_l, linger_seconds=0.0, max_batch=2 * B_l)
            scr_l = _LSC(store_l, eng_l, interval_seconds=3600.0)
            blobs_l = {}
            for i in range(B_l):
                sig = (0x4C52 + i).to_bytes(4, "little") + code_str.encode()
                blob = rng.integers(
                    0, 256, size=k_l * shard_l, dtype=np.uint8
                ).tobytes()
                blobs_l[store_l.put_object(
                    sig, blob, k_l, n_l, code=code_str
                )] = blob
            child = reads_fam.labels(code=code_label)
            r0 = child.value
            for skey in blobs_l:
                store_l.drop_shard(skey, 3)  # ONE data loss per stripe
            scr_l.run_cycle()
            healed = eng_l.drain_once()
            check_smoke(healed == B_l,
                        f"{code_label} storm healed {healed}/{B_l}")
            for skey, blob in blobs_l.items():
                check_smoke(store_l.read(skey) == blob,
                            f"{code_label} repair produced wrong bytes")
            per_heal[code_label] = (child.value - r0) / healed
        stats["repair_fetch_amplification"] = round(
            per_heal["lrc"] / per_heal["rs"], 4
        )
    except SmokeMismatch:
        raise  # deterministic correctness failure: fail the run
    except Exception as exc:  # noqa: BLE001 — secondary stat only
        stats["lrc_repair_error"] = str(exc)[:80]

    # --- hot->archival conversion throughput (docs/lrc.md): one cold
    # 16 MiB object in hot RS(10,4) stripes merged into wide archival
    # LRC(40/8+8) stripes through the conversion engine (decode-free
    # gather + device-side re-encode + atomic manifest swap), then a
    # byte-identity check across the boundary INCLUDING a degraded read
    # with one data loss per archival stripe (local-tier heals).
    try:
        from noise_ec_tpu.host.plugin import ShardPlugin as _CSP
        from noise_ec_tpu.host.transport import (
            LoopbackHub as _CHub,
            LoopbackNetwork as _CNet,
            format_address as _cfmt,
        )
        from noise_ec_tpu.service import (
            ObjectStore as _COS,
            TenantRegistry as _CTR,
        )
        from noise_ec_tpu.store import (
            ConversionEngine as _CCE,
            RepairEngine as _CRE,
            StripeStore as _CSS,
        )

        c_backend = "device" if on_tpu else "numpy"
        c_hub = _CHub()
        c_node = _CNet(c_hub, _cfmt("tcp", "localhost", 4000))
        c_store = _CSS(backend=c_backend)
        c_engine = _CRE(c_store, network=c_node, linger_seconds=0.0)
        c_plugin = _CSP(backend=c_backend, store=c_store)
        c_node.add_plugin(c_plugin)
        c_tenants = _CTR()
        c_tenants.configure(
            "cold", policy="archive=lrc:40/8+8,age=0,stripe_bytes="
            f"{4 << 20}"
        )
        c_objects = _COS(
            c_store, c_plugin, c_node, tenants=c_tenants,
            engine=c_engine, stripe_bytes=1 << 20, k=10, n=14,
        )
        conv_bytes = (32 if on_tpu else 16) << 20
        cold_obj = rng.integers(
            0, 256, size=conv_bytes, dtype=np.uint8
        ).tobytes()
        c_objects.put("cold", "glacier", cold_obj)
        conv = _CCE(c_store, c_tenants, repair=c_engine)
        t0 = time.perf_counter()
        c_stats = conv.run_cycle()
        t_conv = time.perf_counter() - t0
        check_smoke(c_stats["converted"] == 1,
                    f"conversion cycle converted {c_stats['converted']}/1")
        c_doc = c_objects.resolve("cold", "glacier")
        check_smoke(c_doc.get("code") == "lrc:8",
                    f"archival manifest carries {c_doc.get('code')}")
        check_smoke(c_objects.read("cold", "glacier") == cold_obj,
                    "conversion changed object bytes")
        for skey in c_doc["stripes"]:
            c_store.drop_shard(skey, 1)
        check_smoke(c_objects.read("cold", "glacier") == cold_obj,
                    "degraded archival read returned wrong bytes")
        stats["convert_mb_per_s"] = round(conv_bytes / t_conv / 1e6, 1)
    except SmokeMismatch:
        raise  # deterministic correctness failure: fail the run
    except Exception as exc:  # noqa: BLE001 — secondary stat only
        stats["convert_error"] = str(exc)[:80]

    # --- object service: PUT and degraded range-GET throughput through
    # the object layer (service/objects.py — chunk -> per-stripe sign +
    # erasure encode -> store + broadcast -> manifest; read = ranged
    # degraded decode from any k of n with n-k shards dropped). This is
    # the user-facing surface (docs/object-service.md); both stats ride
    # the tools/bench_gate.py regression gate under the host tolerance
    # (the put path is dominated by per-stripe signing on this box).
    try:
        from noise_ec_tpu.host.plugin import ShardPlugin as _OSP
        from noise_ec_tpu.host.transport import (
            LoopbackHub as _OHub,
            LoopbackNetwork as _ONet,
            format_address as _ofmt,
        )
        from noise_ec_tpu.service import ObjectStore as _OS
        from noise_ec_tpu.store import RepairEngine as _ORE
        from noise_ec_tpu.store import StripeStore as _OSS

        o_backend = "device" if on_tpu else "numpy"
        o_hub = _OHub()  # single node: broadcast is a no-op fan-out
        o_node = _ONet(o_hub, _ofmt("tcp", "localhost", 3800))
        o_store = _OSS(backend=o_backend)
        o_engine = _ORE(o_store, network=o_node, linger_seconds=0.0)
        o_plugin = _OSP(backend=o_backend, store=o_store)
        o_node.add_plugin(o_plugin)
        ko, no = 10, 14
        objects = _OS(
            o_store, o_plugin, o_node, engine=o_engine,
            stripe_bytes=1 << 20, k=ko, n=no,
        )
        obj_bytes = (32 if on_tpu else 16) << 20
        base_obj = rng.integers(
            0, 256, size=obj_bytes, dtype=np.uint8
        ).tobytes()
        objects.put("bench", "warm", base_obj)  # warm codecs/caches
        t_put = float("inf")
        last_name = None
        for trial in range(3):
            # Distinct content per trial: identical bytes share stripe
            # signatures and the second put would time cache hits.
            payload_t = base_obj[trial + 1:] + bytes([trial]) * (trial + 1)
            last_name = f"obj{trial}"
            t0 = time.perf_counter()
            objects.put("bench", last_name, payload_t)
            t_put = min(t_put, time.perf_counter() - t0)
            check_smoke(
                objects.read("bench", last_name) == payload_t,
                "object put/get returned wrong bytes",
            )
        stats["object_put_mb_per_s"] = round(obj_bytes / t_put / 1e6, 1)
        # Degrade every stripe of the last object below its data shards
        # (n-k erasures including data slots) and time the ranged read
        # that reconstructs through the codec backend.
        m = objects.resolve("bench", last_name)
        for skey in set(m["stripes"]):
            for shard_no in range(no - ko):
                o_store.drop_shard(skey, shard_no)
        expect = base_obj[3:] + bytes([2]) * 3
        t_get = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            got = objects.read("bench", last_name)
            t_get = min(t_get, time.perf_counter() - t0)
            check_smoke(got == expect,
                        "object degraded read returned wrong bytes")
        stats["object_get_degraded_mb_per_s"] = round(
            obj_bytes / t_get / 1e6, 1
        )

        # --- hot-read tier: zipfian GET mix over the decoded-object
        # cache (docs/object-service.md "Read path"). A fresh service
        # with the cache tier wired, a cold-start segment that decodes
        # and populates (zipfian draws + one warm sweep), then the
        # timed hot segment: the ISSUE-12 bars — object_get_hot_mb_per_s
        # >= 10x object_get_degraded_mb_per_s at >= 90% hit rate — ride
        # tools/bench_gate.py cache_hot_check on fresh runs.
        import hashlib as _hl

        from noise_ec_tpu.obs.registry import default_registry as _reg
        from noise_ec_tpu.service import DecodedObjectCache as _DC

        h_hub = _OHub()
        h_node = _ONet(h_hub, _ofmt("tcp", "localhost", 3900))
        h_store = _OSS(backend=o_backend)
        h_engine = _ORE(h_store, network=h_node, linger_seconds=0.0)
        h_plugin = _OSP(backend=o_backend, store=h_store)
        h_node.add_plugin(h_plugin)
        h_cache = _DC(max_bytes=512 << 20)
        hot_objects = _OS(
            h_store, h_plugin, h_node, engine=h_engine,
            stripe_bytes=1 << 20, k=ko, n=no, cache=h_cache,
        )
        n_obj = 12
        each = (4 if on_tpu else 2) << 20
        digests = {}
        for i in range(n_obj):
            payload_i = rng.integers(
                0, 256, size=each, dtype=np.uint8
            ).tobytes()
            hot_objects.put("bench", f"hot{i}", payload_i)
            digests[f"hot{i}"] = _hl.blake2b(
                payload_i, digest_size=16
            ).digest()
        # Cold-start segment: drop the PUT write-through warmth so the
        # first pass decodes through the store, then warm every object.
        h_cache.clear()
        zipf_draws = rng.zipf(1.1, size=32 + 96)
        for z in zipf_draws[:32]:
            hot_objects.read("bench", f"hot{(int(z) - 1) % n_obj}")
        for i in range(n_obj):
            hot_objects.read("bench", f"hot{i}")
        hits_fam = _reg().counter(
            "noise_ec_object_cache_hits_total"
        ).labels()
        miss_fam = _reg().counter(
            "noise_ec_object_cache_misses_total"
        ).labels()
        hits0, miss0 = hits_fam.value, miss_fam.value
        # Timed hot segment: consume the chunk iterator the way the
        # HTTP layer does (cached stripes stream zero-copy); identity
        # is verified OUTSIDE the window — hashing 2 MiB per GET costs
        # more than serving it and would time blake2b, not the cache.
        served = 0
        reads: dict[str, list] = {}
        t0 = time.perf_counter()
        for z in zipf_draws[32:]:
            name_z = f"hot{(int(z) - 1) % n_obj}"
            _, total_z, chunks_z = hot_objects.get_range("bench", name_z)
            blobs = list(chunks_z)
            served += total_z
            reads[name_z] = blobs
        t_hot = time.perf_counter() - t0
        for name_z, blobs in reads.items():
            check_smoke(
                _hl.blake2b(
                    b"".join(blobs), digest_size=16
                ).digest() == digests[name_z],
                "hot cached read returned wrong bytes",
            )
        d_hits = hits_fam.value - hits0
        d_miss = miss_fam.value - miss0
        stats["object_get_hot_mb_per_s"] = round(served / t_hot / 1e6, 1)
        stats["object_get_hit_rate"] = round(
            d_hits / max(1.0, d_hits + d_miss), 4
        )

        # --- request-tracing overhead: the same hot zipfian GET mix
        # with the tail sampler ARMED (default sample_n) vs tracing
        # disabled entirely, alternated so cache state is identical for
        # both legs. trace_overhead_pct rides tools/bench_gate.py
        # trace_overhead_check (<= 3%) on fresh runs; trace_keep_rate
        # is the armed legs' kept share off the
        # noise_ec_trace_requests_total{decision} deltas (clean-path
        # requests sample 1-in-sample_n, so this sits near 1/sample_n
        # plus the slow/error tail).
        from noise_ec_tpu.obs.trace import default_tracer as _dt

        tracer = _dt()
        req_fam = _reg().counter("noise_ec_trace_requests_total")

        def _trace_decisions() -> dict[str, float]:
            return {
                values[0]: float(child.value)
                for values, child in req_fam.children()
            }

        def _hot_pass() -> float:
            t0 = time.perf_counter()
            for z in zipf_draws[32:]:
                name_z = f"hot{(int(z) - 1) % n_obj}"
                _, _, chunks_z = hot_objects.get_range("bench", name_z)
                for _ in chunks_z:
                    pass
            return time.perf_counter() - t0

        was_enabled = tracer.enabled
        t_off = t_armed = float("inf")
        before_d = _trace_decisions()
        for _ in range(3):
            tracer.enabled = False
            t_off = min(t_off, _hot_pass())
            tracer.enabled = True
            t_armed = min(t_armed, _hot_pass())
        after_d = _trace_decisions()
        tracer.enabled = was_enabled
        stats["trace_overhead_pct"] = round(
            max(0.0, (t_armed - t_off) / t_off * 100.0), 2
        )
        req_total = sum(
            after_d.get(k, 0.0) - before_d.get(k, 0.0) for k in after_d
        )
        req_kept = sum(
            after_d.get(k, 0.0) - before_d.get(k, 0.0)
            for k in after_d if k.startswith("kept")
        )
        stats["trace_keep_rate"] = (
            round(req_kept / req_total, 4) if req_total else 0.0
        )

        # --- wide-event log overhead: the identical hot zipfian GET
        # mix with the event log ARMED vs disabled, alternated min-of-N
        # exactly like trace_overhead_pct above (more legs here — the
        # true delta is ~zero, so the measurement is noise-bound and
        # the min needs more draws to converge on a loaded box).
        # Events only fire at decision points (that is the design), so
        # the hot cache-hit path should pay ~nothing;
        # event_log_overhead_pct rides tools/bench_gate.py
        # event_overhead_check (<= 1%) on fresh runs to keep it that
        # way.
        from noise_ec_tpu.obs.events import default_event_log as _del

        elog = _del()
        ev_was = elog.enabled
        ev_off = ev_armed = float("inf")
        for _ in range(9):
            elog.enabled = False
            ev_off = min(ev_off, _hot_pass())
            elog.enabled = True
            ev_armed = min(ev_armed, _hot_pass())
        elog.enabled = ev_was
        stats["event_log_overhead_pct"] = round(
            max(0.0, (ev_armed - ev_off) / ev_off * 100.0), 2
        )

        # --- diagnosis latency: one full rule-table run over the
        # registry/event/trace state this bench just built (a busier
        # join than most real incidents). Min-of-5 wall time, in ms.
        from noise_ec_tpu.obs.diagnose import DiagnosisEngine as _DE

        engine = _DE()
        t_diag = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            engine.diagnose("request")
            t_diag = min(t_diag, time.perf_counter() - t0)
        stats["diagnose_verdict_ms"] = round(t_diag * 1e3, 3)

        # --- tenant isolation: per-tenant GET p99 attribution off the
        # labeled noise_ec_object_op_seconds{tenant,op,route} histogram
        # (docs/object-service.md "Tenant attribution"). Two phases on
        # the cached service above: a solo quiet tenant establishes the
        # baseline p99, then the same quiet workload repeats while an
        # unthrottled "talker" tenant hammers its own objects from
        # another thread (the quiet side paces itself, so the talker
        # takes ~10x the request share — a first-cut noisy-neighbor
        # mix). Both p99s come from bucket-delta interpolation over the
        # tenant-labeled series — the bench reads the same series an
        # operator would — and tenant_isolation_p99_ratio =
        # contended / solo rides the gate with lower-better semantics.
        import threading as _th

        op_fam = _reg().histogram("noise_ec_object_op_seconds")

        def _tenant_get_counts(tenant: str):
            """Summed (bounds, counts incl. +Inf) across routes for
            one tenant's GETs."""
            agg = None
            bounds = None
            for values, child in op_fam.children():
                lbl = dict(zip(op_fam.label_names, values))
                if lbl.get("tenant") != tenant or lbl.get("op") != "get":
                    continue
                snap = child.snapshot()
                bounds = snap["bounds"]
                counts = list(snap["counts"])
                agg = (
                    counts if agg is None
                    else [a + c for a, c in zip(agg, counts)]
                )
            return bounds, agg

        def _delta_p99(bounds, before, after, q=0.99):
            """q-quantile of the observations BETWEEN two snapshots,
            linearly interpolated inside the containing bucket (+Inf
            clamps to the top finite bound, like Histogram.percentile)."""
            if after is None:
                return 0.0
            deltas = (
                [b - a for a, b in zip(before, after)]
                if before is not None else list(after)
            )
            total = sum(deltas)
            if total <= 0:
                return 0.0
            target = q * total
            cum = 0.0
            for i, c in enumerate(deltas):
                if c <= 0:
                    continue
                if cum + c >= target:
                    lo = bounds[i - 1] if i > 0 else 0.0
                    hi = bounds[i] if i < len(bounds) else bounds[-1]
                    return lo + (hi - lo) * (target - cum) / c
                cum += c
            return bounds[-1]

        t_each = 1 << 20
        for i in range(6):
            for who in ("quiet", "talker"):
                payload_i = rng.integers(
                    0, 256, size=t_each, dtype=np.uint8
                ).tobytes()
                hot_objects.put(who, f"{who}{i}", payload_i)
        t_draws = rng.zipf(1.1, size=400)

        def _quiet_pass() -> None:
            # A paced quiet tenant: the 1 ms think time is what hands
            # the unthrottled talker its ~10x request share in phase 2.
            for z in t_draws[:200]:
                hot_objects.read("quiet", f"quiet{(int(z) - 1) % 6}")
                time.sleep(0.001)

        _, before1 = _tenant_get_counts("quiet")
        _quiet_pass()
        bounds_q, after1 = _tenant_get_counts("quiet")
        p99_solo = _delta_p99(bounds_q, before1, after1)

        stop_talker = _th.Event()

        def _talk() -> None:
            j = 0
            while not stop_talker.is_set():
                hot_objects.read("talker", f"talker{j % 6}")
                j += 1

        talker = _th.Thread(target=_talk, daemon=True)
        talker.start()
        try:
            _quiet_pass()
        finally:
            stop_talker.set()
            talker.join(timeout=10)
        bounds_q, after2 = _tenant_get_counts("quiet")
        p99_mixed = _delta_p99(bounds_q, after1, after2)
        check_smoke(
            after2 is not None and sum(after2) - sum(after1) >= 200,
            "tenant-labeled histogram missed quiet GETs",
        )
        stats["object_get_p99_ms"] = round(p99_mixed * 1e3, 3)
        stats["tenant_isolation_p99_ratio"] = round(
            p99_mixed / max(p99_solo, 1e-9), 3
        )
    except SmokeMismatch:
        raise  # deterministic correctness failure: fail the run
    except Exception as exc:  # noqa: BLE001 — secondary stat only
        stats["object_service_error"] = str(exc)[:80]

    # --- chaos recovery: partition-heal -> first successful delivery
    # latency through the REAL transport behind the chaos proxy
    # (docs/resilience.md). Three scheduled 1 s directional partitions
    # sever the payload direction while the sender keeps broadcasting;
    # partition_recovery_p50_ms is the median time from each heal to the
    # first outcome=ok delivery after it — the end-to-end cost of the
    # reconnect/NACK/announce healing loop, not of any one kernel.
    try:
        from noise_ec_tpu.host.plugin import ShardPlugin as _SP
        from noise_ec_tpu.host.transport import TCPNetwork
        from noise_ec_tpu.resilience.chaos import ChaosProfile, ChaosProxy
        from noise_ec_tpu.store import RepairEngine as _RE
        from noise_ec_tpu.store import StripeStore as _SS

        heals = [1.5, 3.5, 5.5]
        profile = ChaosProfile.parse(",".join(
            f"partition@{h - 1.0}:1.0:b2a" for h in heals  # b2a = payloads
        ))
        a_net = TCPNetwork(host="127.0.0.1", port=0, discovery=False)
        a_store = _SS()
        a_engine = _RE(
            a_store, network=a_net, respond_interval_seconds=0.2,
            linger_seconds=0.0, announce_interval_seconds=0.2,
            announce_window_seconds=30.0, announce_max_stripes=256,
        )
        a_engine.start()
        a_plug = _SP(backend="numpy", store=a_store)
        a_net.add_plugin(a_plug)
        a_net.listen()
        proxy = ChaosProxy(
            "127.0.0.1", a_net.port, profile=profile, seed=99
        ).start()
        deliveries: list[float] = []
        b_net = TCPNetwork(host="127.0.0.1", port=0, discovery=False)
        b_plug = _SP(
            backend="numpy",
            on_message=lambda m, s: deliveries.append(proxy.now()),
        )
        b_plug.nack_grace_seconds = 0.2
        b_plug.nack_backoff_base = 0.2
        b_net.add_plugin(b_plug)
        b_net.listen()
        b_net.bootstrap([proxy.address])
        t_end = time.time() + 20
        while time.time() < t_end and (not a_net.peers or not b_net.peers):
            time.sleep(0.02)
        check_smoke(bool(a_net.peers and b_net.peers),
                    "chaos bench peers never registered")
        seq = 0
        while proxy.now() < heals[-1] + 1.5:
            a_plug.shard_and_broadcast(
                a_net, f"chaos bench payload {seq:06d}!".encode()  # 25 B
            )
            seq += 1
            time.sleep(0.025)
        t_end = time.time() + 20
        recoveries = None
        while time.time() < t_end:
            after = [
                min((t for t in list(deliveries) if t >= h), default=None)
                for h in heals
            ]
            if all(x is not None for x in after):
                recoveries = [x - h for x, h in zip(after, heals)]
                break
            time.sleep(0.1)
        check_smoke(recoveries is not None,
                    "no post-heal delivery within the window")
        stats["partition_recovery_p50_ms"] = round(
            float(np.median(recoveries)) * 1e3, 1
        )
        proxy.close()
        a_net.close()
        b_net.close()
        a_engine.close()
    except SmokeMismatch:
        raise
    except Exception as exc:  # noqa: BLE001 — secondary stat only
        stats["chaos_recovery_error"] = str(exc)[:80]

    # --- fleet lab: tier-1-sized in-process fleet throughput
    # (docs/fleet.md). A 24-peer bounded-degree overlay drives a
    # chat-only mix through the full per-peer plugin stack (sign ->
    # shard -> per-link dispatch -> pool -> decode -> Ed25519 verify)
    # on the shared fair dispatcher; the stat is traffic submissions
    # per second with a 99.9% delivery smoke gate — the host-runtime
    # cost of fleet-scale fan-out, not any one kernel.
    try:
        from noise_ec_tpu.fleet import FleetLab, FleetProfile

        f_prof = FleetProfile.parse(
            "peers=24,fanout=4,msgs=160,chat=1,chat_bytes=64,chaos=clean"
        )
        f_lab = FleetLab(f_prof, seed=7)
        f_lab.start()
        f_report = f_lab.run()
        f_lab.close()
        check_smoke(
            f_report["delivery"]["rate"] >= 0.999,
            f"fleet bench delivery {f_report['delivery']}",
        )
        stats["fleet_msgs_per_s"] = f_report["msgs_per_s"]
    except SmokeMismatch:
        raise  # deterministic correctness failure: fail the run
    except Exception as exc:  # noqa: BLE001 — secondary stat only
        stats["fleet_error"] = str(exc)[:80]

    # --- placement ring: targeted-delivery fanout vs broadcast, and
    # the churn-rebalance amplification drill (docs/placement.md). The
    # same 24-peer object-only run twice — broadcast baseline, then
    # domains@8 targeted — shares the manifest-broadcast component, so
    # the per-put wire-send difference isolates the DATA-shard fanout:
    # placement_fanout_ratio = targeted data sends per put over the
    # n-shards ideal (the peers-to-n contract; gate bar 1.5x). Then a
    # whole-domain kill on the targeted fleet: rebalance_amplification
    # = bytes the rebalancers moved over the exact ownership-delta
    # bytes the ring reports (ring.moved) — ~1.0 means the rebalancer
    # moved only the delta. Both gated lower-better by bench_gate.
    try:
        from noise_ec_tpu.fleet import FleetLab, FleetProfile

        p_base = (
            "peers=24,fanout=4,msgs=40,object=1,object_bytes=8192,"
            "stripe_bytes=4096,k=4,n=8,chaos=clean"
        )
        pb_lab = FleetLab(FleetProfile.parse(p_base), seed=7)
        pb_lab.start()
        pb_report = pb_lab.run()
        pb_lab.close()
        pt_prof = FleetProfile.parse(p_base + ",domains@8")
        pt_lab = FleetLab(pt_prof, seed=7)
        pt_lab.start()
        try:
            pt_report = pt_lab.run()
            check_smoke(
                pb_report["delivery"]["rate"] >= 0.999
                and pt_report["delivery"]["rate"] >= 0.999,
                f"placement bench delivery broadcast="
                f"{pb_report['delivery']} targeted={pt_report['delivery']}",
            )
            stripes_per_put = 2  # 8192-byte objects over 4096 stripes
            n_sh, fan = pt_prof.n, pt_prof.fanout
            per_put_b = pb_report["wire_sends"] / max(
                1, pb_report["objects"]["puts"]
            )
            per_put_t = pt_report["wire_sends"] / max(
                1, pt_report["objects"]["puts"]
            )
            data_t = per_put_t - per_put_b + stripes_per_put * n_sh * fan
            stats["placement_fanout_ratio"] = round(
                max(data_t, 0.0) / (stripes_per_put * n_sh), 3
            )
            # Churn drill: settle steady-state deltas first so the
            # measured bytes are the kill's delta alone.
            pt_lab.rebalance_until_converged()
            alive_before = {
                f"fleet://{p.idx}" for p in pt_lab.peers if p.up
            }
            pt_lab.kill_domain("d7")
            alive_after = {
                f"fleet://{p.idx}" for p in pt_lab.peers if p.up
            }
            metas: dict = {}
            for p in pt_lab.peers:
                if p.store is None:
                    continue
                for s_key in p.store.keys():
                    if s_key in metas:
                        continue
                    try:
                        metas[s_key] = p.store.snapshot(s_key)[0]
                    except Exception:  # noqa: BLE001 — evicted mid-walk
                        continue
            ideal_bytes = 0
            for s_key, s_meta in metas.items():
                moved_slots = pt_lab.ring.moved(
                    s_key, s_meta.n, alive_before, alive_after,
                    k=s_meta.k, code=s_meta.code,
                )
                ideal_bytes += len(moved_slots) * s_meta.shard_len
            moved_before = sum(
                rb.bytes_moved for rb in pt_lab.rebalancers.values()
            )
            rb_stats = pt_lab.rebalance_until_converged()
            moved_bytes = rb_stats["bytes_moved"] - moved_before
            if ideal_bytes > 0:
                stats["rebalance_amplification"] = round(
                    moved_bytes / ideal_bytes, 3
                )
        finally:
            pt_lab.close()
    except SmokeMismatch:
        raise  # deterministic correctness failure: fail the run
    except Exception as exc:  # noqa: BLE001 — secondary stat only
        stats["placement_error"] = str(exc)[:80]

    # --- hedged k-of-n GETs under one straggler (docs/object-service.md
    # "Read path"). A targeted-placement fleet with a slow@ peer (every
    # link touching peer 2 pays 120 ms) drives a GET-heavy mix; reads
    # whose k-set lands on the straggler stall unhedged, while the
    # hedged engine races a spare source at the clamped per-peer p95
    # and cancels the loser. The stat is the hedged run's fleet-tenant
    # GET p99 (ms, lower-better) — the straggler-bounded tail the
    # ISSUE-19 acceptance names — smoke-gated on the hedge counters
    # actually moving (requests fanned, at least one spare won).
    try:
        from noise_ec_tpu.fleet import FleetLab, FleetProfile
        from noise_ec_tpu.obs.registry import default_registry as _hreg

        h_base = (
            "peers=24,fanout=4,msgs=64,object=1,get=2,object_bytes=8192,"
            "stripe_bytes=4096,k=4,n=8,chaos=clean,domains@8,slow@2:120"
        )

        def _hedge_counts() -> dict:
            reg = _hreg()
            return {
                key: float(
                    reg.counter(f"noise_ec_hedge_{key}_total")
                    .labels().value
                )
                for key in ("requests", "wins", "cancelled")
            }

        def _hedge_run(profile_s: str) -> dict:
            lab = FleetLab(FleetProfile.parse(profile_s), seed=7)
            lab.start()
            try:
                return lab.run()
            finally:
                lab.close()

        # The registry is process-global and earlier sections may have
        # hedged; delta the counters around the hedge=1 run alone.
        h_before = _hedge_counts()
        h_on = _hedge_run(h_base + ",hedge=1")
        h_delta = {
            key: val - h_before[key]
            for key, val in _hedge_counts().items()
        }
        check_smoke(
            h_on["delivery"]["rate"] >= 0.999,
            f"hedge bench delivery {h_on['delivery']}",
        )
        check_smoke(
            h_delta["requests"] > 0 and h_delta["wins"] > 0,
            f"hedge bench: straggler run moved no hedge counters "
            f"({h_delta})",
        )
        p99_hedged = h_on["tenant_get_p99_ms"].get("fleet", 0.0)
        check_smoke(
            p99_hedged > 0.0, "hedge bench: no fleet-tenant GET samples"
        )
        stats["object_get_p99_hedged_ms"] = round(p99_hedged, 3)
    except SmokeMismatch:
        raise  # deterministic correctness failure: fail the run
    except Exception as exc:  # noqa: BLE001 — secondary stat only
        stats["hedge_error"] = str(exc)[:80]

    # --- live-path coalescing: N concurrent senders whose same-geometry
    # encodes ride one node's CoalescingDispatcher (ops/coalesce.py) vs
    # the same N dispatches issued sequentially, one device call each.
    # The coalesced number carries the ISSUE-8 acceptance bar (>= 2x the
    # sequential baseline at 8 senders): per-dispatch overhead (jit
    # dispatch, transfer setup, gate admission) amortizes across the batch.
    # Registered under the bench_gate device tolerance (the _gbps suffix
    # outside HOST_PREFIXES).
    try:
        import threading

        from noise_ec_tpu.codec.rs import ReedSolomon
        from noise_ec_tpu.ops.coalesce import configure_coalescer

        # Payload per sender sits inside the implicit-coalescing cutoff
        # for the backend (ops/coalesce.py): dispatch-overhead-bound on
        # both tiers, so the stat measures amortization, not compute.
        N_SEND, ROUNDS = 8, 4
        S_CO = (64 << 10) if on_tpu else (4 << 10)
        rs_co = ReedSolomon(k, r)  # device backend, the plugin's codec
        P_CO = rs_co.G[k:]
        stripes_co = [
            rng.integers(0, 256, size=(k, S_CO)).astype(np.uint8)
            for _ in range(N_SEND)
        ]
        co_bytes = N_SEND * ROUNDS * k * S_CO
        dev_co = rs_co._dev
        dev_co.matmul_stripes(P_CO, stripes_co[0])  # warm (compile)
        for n_w in (2, 3, 5, 8):  # warm the batch-size ladder (1,2,4,8)
            dev_co.matmul_stripes_many(P_CO, stripes_co[:n_w])
        want_co = [np.asarray(dev_co.matmul_stripes(P_CO, s))
                   for s in stripes_co]

        def seq_once() -> float:
            t0 = time.perf_counter()
            for _ in range(ROUNDS):
                for s in stripes_co:
                    dev_co.matmul_stripes(P_CO, s)
            return time.perf_counter() - t0

        def coalesced_once() -> float:
            start = threading.Barrier(N_SEND + 1)
            outs: list = [None] * N_SEND

            def sender(i: int) -> None:
                start.wait()
                for _ in range(ROUNDS):
                    outs[i] = rs_co._mul(P_CO, stripes_co[i])

            threads = [
                threading.Thread(target=sender, args=(i,), daemon=True)
                for i in range(N_SEND)
            ]
            for t in threads:
                t.start()
            start.wait()
            t0 = time.perf_counter()
            for t in threads:
                t.join()
            elapsed = time.perf_counter() - t0
            for i in range(N_SEND):
                check_smoke(np.array_equal(outs[i], want_co[i]),
                            "coalesced encode produced wrong bytes")
            return elapsed

        configure_coalescer()  # fresh buckets, default linger
        t_seq = min(seq_once() for _ in range(3))
        t_co = min(coalesced_once() for _ in range(3))
        stats["live_coalesce_encode_gbps"] = round(co_bytes / t_co / 1e9, 3)
        stats["live_coalesce_sequential_gbps_ref"] = round(
            co_bytes / t_seq / 1e9, 3
        )
        stats["live_coalesce_speedup_x"] = round(t_seq / t_co, 2)
    except SmokeMismatch:
        raise  # deterministic correctness failure: fail the run
    except Exception as exc:  # noqa: BLE001 — secondary stat only
        stats["live_coalesce_error"] = str(exc)[:80]

    # --- mesh dispatch tier (docs/design.md §13): batched encode sharded
    # over the "stripes" mesh axis, swept over pow2 subsets of the
    # devices this process sees (one chip: the 1-chip point only).
    try:
        stats.update(mesh_sweep_stats(rng))
    except SmokeMismatch:
        raise  # deterministic correctness failure: fail the run
    except Exception as exc:  # noqa: BLE001 — secondary stat only
        stats["batch_mesh_error"] = str(exc)[:80]

    # --- mesh repair + corrupted decode: the OTHER two hot loops on the
    # sharded entry. Repair: a storm of same-pattern stripe rebuilds
    # through rs.matmul_many — the repair engine's exact group dispatch,
    # host-staged bytes in, so the stat carries staging like production
    # repair does (host tolerance via the mesh_ prefix in bench_gate).
    # Decode: B received codewords with one whole-share corruption each,
    # batch-decoded via the decode1 fold (corrected row + consistency
    # rows, matrix/bw.py contract) through matmul_stripes_many.
    try:
        from noise_ec_tpu.codec.rs import ReedSolomon as _MRS
        from noise_ec_tpu.matrix.hostmath import host_matvec as _hmv
        from noise_ec_tpu.ops.dispatch import decode1_fold_matrix as _d1f
        from noise_ec_tpu.parallel.mesh import (
            configure_mesh_router as _mesh_cfg,
            reset_mesh_router as _mesh_reset,
        )

        _mesh_cfg(enable=len(jax.devices()) > 1)
        rs_m = _MRS(k, r)  # device backend: the plugin/store codec
        B_m = 16
        S_m = (1 << 20) if on_tpu else (32 << 10)  # bytes per shard
        present_m = list(range(2, k + 2))  # data shards 0,1 erased
        R_m = reconstruction_matrix(gf, G, present_m, [0, 1])
        stacks_m = [
            rng.integers(0, 256, size=(k, S_m)).astype(np.uint8)
            for _ in range(B_m)
        ]
        warm_m = rs_m.matmul_many(R_m, stacks_m)
        check_smoke(
            np.array_equal(warm_m[0], _hmv(gf, R_m, stacks_m[0])),
            "mesh repair reconstruct != host truth",
        )
        t_mr = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            rs_m.matmul_many(R_m, stacks_m)
            t_mr = min(t_mr, time.perf_counter() - t0)
        stats["mesh_repair_gbps"] = round(B_m * k * S_m / t_mr / 1e9, 3)

        B_d = 8
        S_d = (256 << 10) if on_tpu else (32 << 10)
        D1 = _d1f(gf, G[k:], 1)  # systematic: A IS the parity matrix
        cws = []
        for _ in range(B_d):
            data_d = rng.integers(0, 256, size=(k, S_d)).astype(np.uint8)
            parity_d = np.asarray(rs_m._dev.matmul_stripes(G[k:], data_d))
            cw = np.concatenate([data_d, parity_d], axis=0)
            cw[1] ^= 0xA5  # whole-share corruption of data share 1
            cws.append((cw, data_d[1]))
        outs = rs_m._dev.matmul_stripes_many(D1, [c for c, _ in cws])
        check_smoke(
            np.array_equal(outs[0][0], cws[0][1])
            and not outs[0][1:].any(),
            "mesh decode1 != corrupted row truth",
        )
        ts_d = []
        for _ in range(9):
            t0 = time.perf_counter()
            rs_m._dev.matmul_stripes_many(D1, [c for c, _ in cws])
            ts_d.append(time.perf_counter() - t0)
        stats["mesh_decode_corrupt_p50_ms"] = round(
            sorted(ts_d)[4] * 1e3, 3
        )
        _mesh_reset()
    except SmokeMismatch:
        raise  # deterministic correctness failure: fail the run
    except Exception as exc:  # noqa: BLE001 — secondary stat only
        stats["mesh_error"] = str(exc)[:80]

    if dev.kernel == "pallas":
        # Correctness smoke BEFORE any timing: the bench must not be the
        # first time a shape runs on real hardware — one small fused encode
        # checked bit-exactly against the NumPy golden codec catches
        # miscompiles that interpret-mode CI cannot.
        from noise_ec_tpu.golden.codec import GoldenCodec

        smoke = rng.integers(0, 256, size=(k, 8192)).astype(np.uint8)
        got = dev.matmul_stripes(G[k:], smoke)
        want = np.asarray(GoldenCodec(k, k + r).encode(smoke))
        check_smoke(np.array_equal(got, want), "TPU fused encode != golden codec")
        stats["tpu_smoke"] = "ok"

        words = jnp.asarray(
            rng.integers(0, 1 << 32, size=(k, TW), dtype=np.uint64).astype(np.uint32)
        )
        t_enc = chained_seconds_per_iter(
            lambda s: dev.matmul_words(G[k:], s), words
        )
        gbps = data_bytes / t_enc / 1e9

        # --- config 2: Reconstruct() p50, 1-4 data-shard erasures, 1 MiB
        # shards (matrix changes per erasure count; kernel is the same
        # fused bitsliced matmul the decode hot loop runs, main.go:77).
        surv = jnp.asarray(
            rng.integers(0, 1 << 32, size=(k, (1 << 20) // 4), dtype=np.uint64).astype(np.uint32)
        )
        for e in (1, 2, 3, 4):
            erased = list(range(e))
            present = [i for i in range(k + r) if i not in erased][:k]
            R = reconstruction_matrix(gf, G, present, erased)
            t_rec = chained_seconds_per_iter(
                lambda s, R=R: dev.matmul_words(R, s), surv
            )
            stats[f"reconstruct{e}_1mib_p50_ms"] = round(t_rec * 1e3, 3)

        # --- config D, device route: the decode-under-corruption hot loop
        # (infectious Decode, main.go:77) on DEVICE-RESIDENT stripes — the
        # natural state in the batch/mesh story. The single-corrupt-row
        # correction folds into ONE generator-shaped fused matmul
        # (DeviceCodec.decode1_words: corrected row + consistency rows),
        # so the decode rides the same kernel class as encode. Host-route
        # numbers for the same contract are the decode_corrupt_* stats
        # above (shares arriving as host bytes).
        try:
            from noise_ec_tpu.matrix.linalg import gf_inv as _gf_inv

            data14 = rng.integers(0, 256, size=(k, 1 << 20)).astype(np.uint8)
            cw14 = np.asarray(GoldenCodec(k, k + r).encode_all(data14))
            cw14[1] ^= 0xA5  # whole-share corruption of data share 1
            A14 = gf.matmul(
                G[k:].astype(np.int64),
                _gf_inv(gf, G[:k]).astype(np.int64),
            ).astype(np.uint8)
            w14 = jnp.asarray(np.ascontiguousarray(cw14).view("<u4"))
            got_c, got_bad = dev.decode1_words(A14, 1, w14)
            check_smoke(
                np.array_equal(
                    np.asarray(got_c)[None].view(np.uint8)[0], data14[1]
                )
                and not np.asarray(got_bad).any(),
                "device decode1 != corrupted row truth",
            )
            t_d1 = chained_seconds_per_iter(
                lambda s: (lambda c, b: c[:128] ^ b[:128])(
                    *dev.decode1_words(A14, 1, s)
                ),
                w14,
            )
            stats["decode_corrupt_device_ms"] = round(t_d1 * 1e3, 3)
        except SmokeMismatch:
            raise
        except Exception as exc:  # noqa: BLE001 — secondary stat only
            t_d1 = None
            stats["decode_corrupt_device_error"] = str(exc)[:80]

        # --- config 3: high-rate RS(17,3), wide RS(50,20) and
        # archival-grade RS(100,30) streaming encode (HBM-resident
        # chunked stream, stripe axis folded). Each geometry gets its
        # own correctness smoke: wide codes exercise different kernel
        # tile brackets than RS(10,4) (a pack/unpack tile mismatch once
        # corrupted exactly these shapes). RS(100,30) rides the
        # block-panel K-tiled tier (ops/pallas_gf2mm "panel tier") —
        # its XOR network is past the whole-plane budget — so this key
        # is the wide-geometry sweep's mid point between RS(50,20)
        # (whole-plane) and RS(200,56) (the widest panel geometry).
        for (k3, r3) in ((17, 3), (50, 20), (100, 30)):
            G3 = generator_matrix(gf, k3, k3 + r3, "cauchy")
            # The route key rides next to every wide-sweep metric so a
            # probe demotion (panel -> mxu) is visible in the recorded
            # round, not just as a throughput cliff; panel routes also
            # record the program-size model's sub-launch count G.
            route3, plan3 = dev._route_plan(G3[k3:])
            stats[f"rs{k3}_{r3}_route"] = route3
            if route3 == "panel":
                stats[f"rs{k3}_{r3}_sublaunches"] = plan_sublaunches(plan3)
            sm3 = rng.integers(0, 256, size=(k3, 8192)).astype(np.uint8)
            check_smoke(
                np.array_equal(
                    dev.matmul_stripes(G3[k3:], sm3),
                    np.asarray(GoldenCodec(k3, k3 + r3).encode(sm3)),
                ),
                f"TPU RS({k3},{r3}) encode != golden codec",
            )
            # ~8 MiB object with shards aligned to the TL=512 lane-tile
            # quantum (8*8*512 = 32768 words): the planner can only use
            # the TL >= 256 tile brackets (pairwise delta-swap transpose)
            # when W8 divides by the tile, and the streaming chunk size is
            # the framework's own knob — RS(17,3) measured 513 GB/s at an
            # aligned shape vs 395 at the old WORD_QUANTUM-only alignment
            # (which landed on W8 = 1920, divisible by neither 512 nor
            # 256, silently forcing TL=128).
            TILE_Q = 8 * 8 * 512
            S3 = max(TILE_Q, ((8 << 20) // k3 // 4 // TILE_Q) * TILE_Q)
            w3 = jnp.asarray(
                rng.integers(0, 1 << 32, size=(k3, S3), dtype=np.uint64).astype(np.uint32)
            )
            t3 = chained_seconds_per_iter(
                lambda s, M=G3[k3:]: dev.matmul_words(M, s), w3
            )
            stats[f"rs{k3}_{r3}_encode_gbps"] = round(k3 * S3 * 4 / t3 / 1e9, 2)

        # --- config 3b (round 5, re-tiered in round 6): near-field-limit
        # RS(200,56) — the block-panel K-tiled VPU tier (its ~361k-XOR
        # network could not plan on the whole-plane kernels and the MXU's
        # int8 roofline at r=56 is only ~110 GB/s; panels Paar-factor in
        # seconds to ~132k ops and VMEM per grid step is panel-sized).
        # dispatch.route_for routes it; a Mosaic compile-probe failure
        # demotes back to the MXU route, so the stat degrades instead of
        # erroring. The per-tile attribution is in the
        # noise_ec_kernel_tile_* families / the device_tile_* summary.
        try:
            kN, rN = 200, 56
            GN = generator_matrix(gf, kN, kN + rN, "cauchy")
            routeN, planN = dev._route_plan(GN[kN:])
            stats["rs200_56_route"] = routeN
            # The ROADMAP bar's named lever: G > 1 here means the
            # program-size model split the ~361k-XOR network across
            # K-grid sub-launches instead of demoting to the MXU.
            stats["rs200_56_sublaunches"] = (
                plan_sublaunches(planN) if routeN == "panel" else 0
            )
            smN = rng.integers(0, 256, size=(kN, 4096)).astype(np.uint8)
            check_smoke(
                np.array_equal(
                    dev.matmul_stripes(GN[kN:], smN),
                    np.asarray(GoldenCodec(kN, kN + rN).encode(smN)),
                ),
                "TPU RS(200,56) encode != golden codec",
            )
            SN = 64 << 10  # words/shard: 256 KiB -> 50 MiB object
            wN = jnp.asarray(
                rng.integers(0, 1 << 32, size=(kN, SN), dtype=np.uint64).astype(np.uint32)
            )
            tN = chained_seconds_per_iter(
                lambda s: dev.matmul_words(GN[kN:], s), wN, n_hi=60
            )
            stats["rs200_56_encode_gbps"] = round(kN * SN * 4 / tN / 1e9, 2)

            # Corrupted-share decode at the same geometry: the decode1
            # fold (corrected row + consistency rows as ONE (56, 256)
            # generator-shaped matmul — matrix/bw.py contract) whose
            # expanded network also rides the panel tier. p50 of 9
            # wall-clock rounds on a 16 MiB device-resident codeword,
            # one whole data share corrupted.
            from noise_ec_tpu.matrix.linalg import gf_inv as _gfiN

            AN = gf.matmul(
                GN[kN:].astype(np.int64),
                _gfiN(gf, GN[:kN]).astype(np.int64),
            ).astype(np.uint8)
            SNd = 64 << 10  # bytes/shard: 256 rows -> 16 MiB codeword
            dataN = rng.integers(0, 256, size=(kN, SNd)).astype(np.uint8)
            parityN = np.asarray(dev.matmul_stripes(GN[kN:], dataN))
            cwN = np.concatenate([dataN, parityN], axis=0)
            cwN[1] ^= 0xA5  # whole-share corruption of data share 1
            wNd = jnp.asarray(np.ascontiguousarray(cwN).view("<u4"))
            cN, bN = dev.decode1_words(AN, 1, wNd)
            check_smoke(
                np.array_equal(
                    np.asarray(cN)[None].view(np.uint8)[0], dataN[1]
                )
                and not np.asarray(bN).any(),
                "RS(200,56) decode1 != corrupted row truth",
            )
            tsN = []
            for _ in range(9):
                t0 = time.perf_counter()
                cN, bN = dev.decode1_words(AN, 1, wNd)
                np.asarray(cN), np.asarray(bN)
                tsN.append(time.perf_counter() - t0)
            stats["rs200_56_decode_corrupt_p50_ms"] = round(
                sorted(tsN)[4] * 1e3, 3
            )
        except SmokeMismatch:
            raise
        except Exception as exc:  # noqa: BLE001 — secondary stat only
            stats["rs200_56_error"] = str(exc)[:80]

        # --- config 4a: Cauchy vs PAR1-Vandermonde generator, RS(10,4).
        Gp = generator_matrix(gf, k, k + r, "par1")
        tp = chained_seconds_per_iter(
            lambda s: dev.matmul_words(Gp[k:], s), words
        )
        stats["rs10_4_par1_encode_gbps"] = round(data_bytes / tp / 1e9, 2)

        # --- config 4b: GF(2^16) field variant on the BYTE-SLICED m=8
        # pipeline: each u16 symbol splits into (lo, hi) byte rows and the
        # device runs the GF(2^8)-shaped kernels over the unpermuted
        # expanded bit matrix (flat plane index 16j+b == (2j+b//8)*8+b%8)
        # — 3-round transpose and the TL=512 tile, vs the 16-plane
        # kernels' 4 rounds and TL<=256 (267 -> ~385 GB/s on v5e).
        try:
            from noise_ec_tpu.gf.field import GF65536

            gf16 = GF65536()
            G16 = generator_matrix(gf16, k, k + r, "cauchy")
            dev16 = DeviceCodec(field="gf65536", kernel="pallas")
            smoke16 = rng.integers(0, 1 << 16, size=(k, 4096)).astype(np.uint16)
            check_smoke(
                np.array_equal(
                    dev16.matmul_stripes(G16[k:], smoke16),
                    np.asarray(
                        GoldenCodec(k, k + r, field="gf65536").encode(smoke16)
                    ),
                ),
                "TPU GF(2^16) fused encode != golden codec",
            )
            TW8 = (1 << 20) // 4 * 8  # 8 MiB per shard = 2 byte rows x 4 MiB
            w16 = jnp.asarray(
                rng.integers(
                    0, 1 << 32, size=(2 * k, TW8), dtype=np.uint64
                ).astype(np.uint32)
            )
            t16 = chained_seconds_per_iter(
                lambda s: dev16.matmul_words_bytesliced(G16[k:], s), w16
            )
            stats["rs10_4_gf65536_encode_gbps"] = round(
                2 * k * TW8 * 4 / t16 / 1e9, 2
            )

            # --- wide-field decode parity: GF(2^16) corrupted-share
            # decode on the PACKED byte-sliced layout
            # (decode1_words_bytesliced — both byte planes of a symbol
            # adjacent in one (2m, TW8) panel, so the decode rides the
            # same 3-round m=8 kernel tier as GF(2^8) instead of the
            # 4-round 16-plane expansion) vs the GF(2^8) device decode
            # above, SAME data volume (14 MiB codeword, 1 MiB shards).
            # The ratio is the bench-gated contract (downward-only:
            # lower is better, 1.0 = field-blind decode).
            from noise_ec_tpu.matrix.linalg import gf_inv as _gfi16
            from noise_ec_tpu.ops.pallas_pack import (
                pack_u16_bytesliced as _p16,
            )

            data16 = rng.integers(
                0, 1 << 16, size=(k, (1 << 20) // 2)
            ).astype(np.uint16)  # 1 MiB shards
            cw16 = np.asarray(
                GoldenCodec(k, k + r, field="gf65536").encode_all(data16)
            )
            cw16[1] ^= 0xA5A5  # whole-share corruption of data share 1
            A16 = gf16.matmul(
                G16[k:].astype(np.int64),
                _gfi16(gf16, G16[:k]).astype(np.int64),
            ).astype(np.uint16)
            # Route + sub-launch count of the wide-field decode fold —
            # the other geometry the ROADMAP bar names (a GF(2^16)
            # RS(100,30)-class fold is RS(200,56)-sized in byte rows).
            routeD16, planD16 = dev16._route_plan(
                dev16.decode1_matrix(A16, 1)
            )
            stats["gf65536_decode_route"] = routeD16
            if routeD16 == "panel":
                stats["gf65536_decode_sublaunches"] = plan_sublaunches(
                    planD16
                )
            w16d = jnp.asarray(
                np.ascontiguousarray(_p16(cw16)).view("<u4")
            )  # (2m, TW8) packed byte-sliced words
            c16, b16 = dev16.decode1_words_bytesliced(A16, 1, w16d)
            got16 = np.ascontiguousarray(
                np.asarray(c16).view(np.uint8).reshape(2, -1)
                .transpose(1, 0)
            ).view("<u2").reshape(-1)
            check_smoke(
                np.array_equal(got16, data16[1])
                and not np.asarray(b16).any(),
                "GF(2^16) byte-sliced decode1 != corrupted row truth",
            )
            t16d = chained_seconds_per_iter(
                lambda s: (lambda c, b: c[0][:128] ^ b[:128])(
                    *dev16.decode1_words_bytesliced(A16, 1, s)
                ),
                w16d,
            )
            stats["decode_corrupt_device_gf65536_ms"] = round(
                t16d * 1e3, 3
            )
            if t_d1:
                stats["gf65536_vs_gf256_decode_ratio"] = round(
                    t16d / t_d1, 3
                )
        except Exception as exc:  # noqa: BLE001 — secondary stat only
            stats["rs10_4_gf65536_error"] = str(exc)[:80]

        # --- comparison bar: the native CPU shim (klauspost-class path).
        try:
            from noise_ec_tpu.shim import CppReedSolomon

            cpp = CppReedSolomon(k, r)
            buf = np.zeros((k + r, 1 << 20), dtype=np.uint8)
            buf[:k] = rng.integers(0, 256, size=(k, 1 << 20)).astype(np.uint8)
            cpp.encode_into(buf)
            t0 = time.perf_counter()
            for _ in range(5):
                cpp.encode_into(buf)
            tc = (time.perf_counter() - t0) / 5
            stats["cpu_shim_encode_gbps"] = round(k * (1 << 20) / tc / 1e9, 2)
        except Exception as exc:  # noqa: BLE001
            stats["cpu_shim_error"] = str(exc)[:80]
    else:
        # Portability fallback (CPU CI): host-path timing, not the headline.
        shards = rng.integers(0, 256, size=(k, S)).astype(np.uint8)
        dev.matmul_stripes(G[k:], shards)  # compile
        t0 = time.perf_counter()
        for _ in range(3):
            dev.matmul_stripes(G[k:], shards)
        t_enc = (time.perf_counter() - t0) / 3
        gbps = data_bytes / t_enc / 1e9

    # Device telemetry summary (obs/device.py): per-kernel achieved GB/s
    # and roofline utilization from the execute-route dispatch stats, the
    # HBM snapshot, and the recompile count the run accumulated — the
    # same series a live node serves on /metrics, folded into the bench
    # artifact so the recorded trajectory carries them too (bench_gate
    # skips them: they describe the run, not the perf contract).
    try:
        from noise_ec_tpu.obs.device import roofline_summary, tile_summary
        from noise_ec_tpu.obs.registry import default_registry

        stats.update(roofline_summary())
        stats.update(tile_summary())
        compiles = default_registry().counter("noise_ec_jit_compiles_total")
        total_compiles = sum(c.value for _, c in compiles.children())
        if total_compiles:
            stats["device_jit_compiles"] = int(total_compiles)
        # Sub-launch telemetry (design.md §14 "Sub-launch splitting"):
        # how many K-grid sub-launches the panel dispatches executed and
        # how many distinct sub-launch programs the run built — the
        # program-set size the persistent compile cache amortizes.
        sub_d = default_registry().counter(
            "noise_ec_kernel_sublaunch_dispatches_total"
        )
        total_sub = sum(c.value for _, c in sub_d.children())
        if total_sub:
            stats["device_sublaunch_dispatches"] = int(total_sub)
        sub_p = default_registry().counter(
            "noise_ec_kernel_sublaunch_programs_total"
        )
        total_prog = sum(c.value for _, c in sub_p.children())
        if total_prog:
            stats["device_sublaunch_programs"] = int(total_prog)
    except Exception as exc:  # noqa: BLE001 — telemetry must not fail bench
        stats["device_obs_error"] = str(exc)[:80]

    stats["encode_s"] = t_enc
    print(
        json.dumps(
            {
                "metric": "rs10_4_encode_throughput",
                "value": round(gbps, 3),
                "unit": "GB/s",
                "vs_baseline": round(gbps / NORTH_STAR_GBPS, 4),
            }
        )
    )
    print(json.dumps(stats), file=sys.stderr)


if __name__ == "__main__":
    main()
