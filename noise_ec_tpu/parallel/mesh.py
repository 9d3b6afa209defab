"""Device-mesh helpers + the mesh dispatch tier for the codec hot loops.

Two layers live here (docs/design.md §13):

- **Mesh constructors** (:func:`make_mesh`, :func:`default_2d_mesh`) — the
  framework's parallel axes (the EC analogue of dp/tp/sp — SURVEY.md
  §2.4): ``"batch"`` data parallelism over independent objects, ``"row"``
  tensor parallelism over generator parity rows (ICI all-gather
  assembly), with the stripe-length axis tiled *inside* the Pallas grid.
  ``parallel/batch.py``'s explicit ``make_sharded_*`` builders consume
  these directly.

- **The :class:`MeshRouter` dispatch tier** — the production path that
  puts every *batched* codec dispatch on all visible chips without the
  caller knowing a mesh exists. ``DeviceCodec.matmul_stripes_many`` /
  ``matmul_words_batch`` (and through them the live-path
  ``CoalescingDispatcher``, the repair engine's ``rs.matmul_many``
  group reconstructs, and ``BatchCodec``'s batch entries) consult the
  process router: when >= 2 devices are usable and the batch clears
  ``min_shard_batch``, the batch dimension is sharded over a 1-D
  ``"stripes"`` mesh axis (matrix replicated, zero collectives — GF
  symbols are positionwise) and the whole batch runs as ONE sharded
  program. The compile helper picks the tier per kernel (SNIPPETS [2]
  Titanax-style):

  ========================  =========================================
  kernel                    tier
  ========================  =========================================
  pallas / pallas_interpret ``shard_map`` (manual SPMD — GSPMD cannot
                            partition through a ``pallas_call``; the
                            vmapped fused words pipeline runs per
                            device shard)
  xla                       ``pjit`` — ``jax.jit`` with explicit
                            ``in_shardings`` / ``out_shardings``
                            (pure lax ops; GSPMD partitions the
                            vmapped planes pipeline automatically)
  < 2 devices or tiny B     single-device (the PR-8 paths unchanged)
  ========================  =========================================

  Batch sizes are quantized to the PR-8 power-of-two ladder
  (:func:`ladder_pad`) before program lookup, so the jitted-program set
  stays bounded AND the device count always divides the padded batch;
  pad members are discarded garbage rows. Every program pins matched
  boundary shardings — a stage's ``out_shardings`` equal the next
  stage's ``in_shardings`` — so chained encode→decode never reshards;
  ``noise_ec_mesh_reshard_total`` counts committed inputs arriving with
  a DIFFERENT sharding (it must stay 0 on chained paths, asserted in
  tests). ``donate_argnums`` is preserved on the sharded words programs
  (donation-on-mesh rules: docs/design.md §13), so PR 8's HBM recycling
  holds per-shard.

  Default: enabled on TPU/GPU with >= 2 devices; DISABLED on CPU even
  with ``--xla_force_host_platform_device_count`` virtual devices (on a
  shared-core host, sharding is pure overhead) — tests and the bench
  sweep opt in with :func:`configure_mesh_router`.
"""

from __future__ import annotations

import functools
import hashlib
import math
import threading
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


__all__ = [
    "MeshRouter",
    "configure_mesh_router",
    "default_2d_mesh",
    "ladder_pad",
    "make_mesh",
    "mesh_router",
    "reset_mesh_router",
]

# The 1-D mesh axis the dispatch tier shards batches over: independent
# stripes (objects / coalesced requests), the degenerate-DP axis.
STRIPES_AXIS = "stripes"


def _shard_map(f, mesh, in_specs, out_specs):
    """jax.shard_map with the varying-axes check off (the local bodies
    run opaque Pallas kernels the checker cannot type)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def make_mesh(
    axis_names: Sequence[str] = ("batch",),
    axis_sizes: Optional[Sequence[int]] = None,
    devices: Optional[Sequence] = None,
) -> Mesh:
    """Build a Mesh over ``devices`` (default: all visible JAX devices).

    If ``axis_sizes`` is omitted, all devices go to the first axis and the
    rest get size 1.
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if axis_sizes is None:
        axis_sizes = [n] + [1] * (len(axis_names) - 1)
    if math.prod(axis_sizes) != n:
        raise ValueError(f"axis sizes {axis_sizes} != device count {n}")
    arr = np.asarray(devices).reshape(tuple(axis_sizes))
    return Mesh(arr, tuple(axis_names))


def default_2d_mesh(devices: Optional[Sequence] = None) -> Mesh:
    """("batch", "row") mesh: widest batch axis, row axis of 2 when even.

    Used by the multi-chip dry run; real deployments choose explicitly.
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    row = 2 if n % 2 == 0 and n >= 2 else 1
    return make_mesh(("batch", "row"), (n // row, row), devices)


def ladder_pad(B: int) -> int:
    """The PR-8 power-of-two batch ladder: next power of two >= B."""
    return 1 << (max(1, B) - 1).bit_length()


class MeshRouter:
    """Routes batched codec dispatches over a device mesh (module doc).

    One process-wide instance (:func:`mesh_router`) fronts the
    ``DeviceCodec`` batch entries; tests and bench build their own over
    device subsets with :func:`configure_mesh_router`.
    """

    def __init__(self, devices: Optional[Sequence] = None, *,
                 min_shard_batch: int = 2, enable: Optional[bool] = None):
        self.devices = list(devices if devices is not None else jax.devices())
        n = max(1, len(self.devices))
        # Power-of-two floor: the widest axis that always divides a
        # ladder-padded batch (both are powers of two).
        self.n_pow2 = 1 << (n.bit_length() - 1)
        self.min_shard_batch = min_shard_batch
        if enable is None:
            try:
                backend = jax.default_backend()
            except Exception:  # noqa: BLE001 — no backend, no mesh
                backend = "cpu"
            enable = self.n_pow2 >= 2 and backend in ("tpu", "gpu")
        self.enabled = bool(enable) and self.n_pow2 >= 2
        # RLock: program builders run under the lock and re-enter it for
        # the mesh cache (mesh_for).
        self._lock = threading.RLock()
        self._meshes: dict[int, Mesh] = {}
        self._programs: dict[tuple, object] = {}
        from noise_ec_tpu.obs.registry import default_registry

        reg = default_registry()
        self._dispatch_children = {
            mode: reg.counter(
                "noise_ec_mesh_sharded_dispatches_total"
            ).labels(mode=mode)
            for mode in ("shard_map", "pjit")
        }
        self._shard_bytes = reg.histogram("noise_ec_mesh_shard_bytes").labels()
        self._reshard = reg.counter("noise_ec_mesh_reshard_total").labels()
        reg.gauge("noise_ec_mesh_devices").set_callback(_mesh_devices_gauge)

    # ------------------------------------------------------------ planning

    def should_shard(self, B: int) -> bool:
        """The routing predicate the dispatch entries consult."""
        return self.enabled and B >= max(2, self.min_shard_batch)

    def n_dev_for(self, B_pad: int) -> int:
        """Devices used for a ladder-padded batch (divides it exactly)."""
        return min(self.n_pow2, ladder_pad(B_pad))

    def mesh_for(self, n_dev: int) -> Mesh:
        with self._lock:
            mesh = self._meshes.get(n_dev)
            if mesh is None:
                mesh = Mesh(
                    np.asarray(self.devices[:n_dev]), (STRIPES_AXIS,)
                )
                self._meshes[n_dev] = mesh
            return mesh

    def sharding_for(self, n_dev: int, ndim: int = 3) -> NamedSharding:
        """The boundary sharding every program in the tier pins: batch
        axis over ``"stripes"``, everything else replicated. A stage's
        out_shardings ARE the next stage's in_shardings."""
        return NamedSharding(
            self.mesh_for(n_dev), P(STRIPES_AXIS, *(None,) * (ndim - 1))
        )

    # ------------------------------------------------------------- metrics

    def _record(self, mode: str, nbytes: int, n_dev: int) -> None:
        self._dispatch_children[mode].add(1)
        self._shard_bytes.observe(max(1, nbytes // max(1, n_dev)))

    def _note_input(self, arr, expected: NamedSharding) -> None:
        """Count a committed device input arriving with a different
        sharding than the program pins — the resharding transfer the
        matched in/out_shardings contract exists to prevent."""
        try:
            if isinstance(arr, jax.Array) and not arr.sharding.is_equivalent_to(
                expected, arr.ndim
            ):
                self._reshard.add(1)
        except Exception:  # noqa: BLE001 — diagnostics must not raise
            pass

    # ------------------------------------------------------------ programs

    def _program(self, key: tuple, build):
        with self._lock:
            fn = self._programs.get(key)
            if fn is None:
                if len(self._programs) > 256:
                    self._programs.clear()
                fn = self._programs[key] = build()
            return fn

    def _words_program(self, kernel: str, r_out: int, bits_rows: tuple,
                       n_dev: int, donate: bool, plan: tuple = None):
        """shard_map tier: the vmapped words pipeline per device shard,
        (B, k, TWp) u32 -> (B, r_out, TWp) u32. ``plan`` selects the
        block-panel pipeline for wide geometries (the (KB, RB, TL,
        temp_cap) tile plan — GSPMD cannot partition a pallas_call, so
        the panel kernels shard exactly like the fused ones: manual
        SPMD, one vmapped pipeline per shard) and joins the program
        cache key, mirroring the single-device dispatch key."""
        from noise_ec_tpu.ops.dispatch import (
            _fused_words_pipeline,
            _panel_words_pipeline,
            donation_supported,
        )

        interpret = kernel == "pallas_interpret"
        donate = donate and donation_supported()
        # The plan carries the sub-launch count G (design.md §14): the
        # per-shard panel pipeline chains its G K-grid sub-launches
        # INSIDE the shard_map body, so the split never crosses the
        # mesh boundary — shardings in and out are the same one program
        # and the zero-reshard contract holds across sub-launches.
        key = ("words", kernel, r_out, bits_rows, n_dev, donate, plan)

        def build():
            if plan is not None:
                single = _panel_words_pipeline(
                    r_out, 8, bits_rows, plan, interpret
                )
            else:
                single = _fused_words_pipeline(r_out, 8, bits_rows, interpret)

            def local(words_local):
                return jax.vmap(single)(words_local)

            spec = P(STRIPES_AXIS, None, None)
            f = _shard_map(
                local, self.mesh_for(n_dev), in_specs=(spec,), out_specs=spec
            )
            if donate:
                return jax.jit(f, donate_argnums=(0,))
            return jax.jit(f)

        return self._program(key, build)

    def _decode1_program(self, kernel: str, r2: int, bits_rows: tuple,
                         n_dev: int, plan: tuple = None):
        """shard_map tier, fused corrupted-share decode: one generator-
        shaped matmul per object (the decode1 fold — corrected row +
        consistency rows) with the verify-OR folded INSIDE the program,
        so chained encode→decode has no intermediate host hop. Wide
        fold matrices ride the block-panel pipeline (``plan``), same as
        the encode tier. Returns (corrected (B, TWp), verify_or
        (B, TWp))."""
        from noise_ec_tpu.ops.dispatch import (
            _fused_words_pipeline,
            _panel_words_pipeline,
        )

        interpret = kernel == "pallas_interpret"
        key = ("decode1", kernel, r2, bits_rows, n_dev, plan)

        def build():
            if plan is not None:
                single = _panel_words_pipeline(
                    r2, 8, bits_rows, plan, interpret
                )
            else:
                single = _fused_words_pipeline(r2, 8, bits_rows, interpret)

            def one(w):
                out = single(w)  # (r2, TWp)
                bad = out[1]
                for q in range(2, r2):
                    bad = bad | out[q]
                return out[0], bad

            def local(words_local):
                return jax.vmap(one)(words_local)

            in_spec = P(STRIPES_AXIS, None, None)
            out_spec = P(STRIPES_AXIS, None)
            f = _shard_map(
                local, self.mesh_for(n_dev),
                in_specs=(in_spec,), out_specs=(out_spec, out_spec),
            )
            return jax.jit(f)

        return self._program(key, build)

    def _sym_program(self, degree: int, out_rows: int, masks: np.ndarray,
                     n_dev: int):
        """pjit tier (XLA kernel): vmapped planes pipeline with explicit
        in/out_shardings — masks replicated, batch axis sharded. Returns
        (fn, masks_dev)."""
        from noise_ec_tpu.ops.bitops import (
            pack_bitplanes_jax,
            unpack_bitplanes_jax,
        )
        from noise_ec_tpu.ops.gf2mm import gf2_matmul_jax

        masks = np.ascontiguousarray(masks)
        digest = hashlib.blake2b(masks.tobytes(), digest_size=12).digest()
        key = ("sym", degree, out_rows, masks.shape, digest, n_dev)

        def build():
            mesh = self.mesh_for(n_dev)
            repl = NamedSharding(mesh, P(None, None))
            shard = self.sharding_for(n_dev)

            def body(masks_g, batch):
                def one(sh):
                    planes = pack_bitplanes_jax(sh, degree)
                    out = gf2_matmul_jax(masks_g, planes)
                    return unpack_bitplanes_jax(
                        out, out_rows, sh.shape[1], degree
                    )

                return jax.vmap(one)(batch)

            fn = jax.jit(
                body, in_shardings=(repl, shard), out_shardings=shard
            )
            return fn, jax.device_put(masks, repl)

        return self._program(key, build)

    # --------------------------------------------------- words batch entry

    def _words_dispatch(self, kernel: str, r_out: int, bits_rows: tuple,
                        words, *, donate: bool, plan: tuple = None):
        """Shared body for the words-tier entries: ladder-pad the batch,
        quantum-pad the words, place (or reshard-count) the input, run
        the sharded program. ``words``: (B, k, TW) u32, np or jax.
        Returns the (B_pad, r_out, TWp) device output plus (B, TW)."""
        from noise_ec_tpu.ops.dispatch import (
            buffer_pool,
            donation_supported,
            pad_words,
        )

        B, k, TW = words.shape
        TWp = pad_words(TW)
        B_pad = ladder_pad(B)
        n_dev = self.n_dev_for(B_pad)
        padded = TWp != TW or B_pad != B
        is_np = isinstance(words, np.ndarray)
        # Donation-on-mesh rules (docs/design.md §13): a host-staged or
        # freshly padded input is an array THIS tier created — always
        # donatable; a caller's device array needs the explicit opt-in.
        donate = donation_supported() and (is_np or padded or donate)
        fn = self._words_program(kernel, r_out, bits_rows, n_dev, donate,
                                 plan)
        expected = self.sharding_for(n_dev)
        if is_np:
            if padded:
                buf = np.zeros((B_pad, k, TWp), dtype=np.uint32)
                buf[:B, :, :TW] = words
            else:
                buf = np.ascontiguousarray(words)
            arr = jax.device_put(buf, expected)
            if donate:
                buffer_pool().donate(arr)
        else:
            arr = words
            if padded:
                arr = jnp.pad(
                    arr, ((0, B_pad - B), (0, 0), (0, TWp - TW))
                )
            else:
                self._note_input(arr, expected)
        out = fn(arr)
        self._record("shard_map", 4 * B * k * TW, n_dev)
        if plan is not None:
            from noise_ec_tpu.ops.dispatch import (
                plan_sublaunches,
                record_sublaunch_dispatch,
            )

            record_sublaunch_dispatch(
                "mesh_words", plan_sublaunches(plan)
            )
        return out, B, TW

    def matmul_words_batch(self, codec, M: np.ndarray, words, *,
                           donate: bool = False):
        """Mesh-sharded GF(2^8) batched words encode/reconstruct:
        (B, k, TW) u32 -> (B, r, TW) u32, batch axis over the mesh.

        The hook ``DeviceCodec._matmul_words_batch_dispatch`` routes
        through (so the gate, breaker, and telemetry wrappers above it
        are unchanged). Byte-identical to the single-device vmap route.
        Panel-routed (wide) matrices ride the same shard_map tier with
        the block-panel pipeline per shard (``_words_program``).
        """
        M = np.asarray(M)
        route, plan = codec._route_plan(M)
        out, B, TW = self._words_dispatch(
            codec.kernel, M.shape[0], codec.bits_rows_for(M), words,
            donate=donate, plan=plan if route == "panel" else None,
        )
        return out[:B, :, :TW]

    def decode1_words_batch(self, codec, A: np.ndarray, j: int, words):
        """Mesh-sharded fused corrupted-share decode (the device
        Berlekamp-Welch single-support route, matrix/bw.py contract):
        (B, m, TW) u32 received codewords -> (corrected_row_j (B, TW),
        verify_or (B, TW)). in_shardings match the encode tier's
        out_shardings, so a chained encode→decode never reshards.
        """
        from noise_ec_tpu.ops.dispatch import decode1_fold_matrix, pad_words

        if codec.gf.degree != 8:
            raise NotImplementedError(
                "mesh decode1 runs the GF(2^8) words tier; wide-field "
                "batches ride the byte-sliced stripes entry"
            )
        D = decode1_fold_matrix(codec.gf, np.asarray(A), j)
        B, m, TW = words.shape
        B_pad = ladder_pad(B)
        n_dev = self.n_dev_for(B_pad)
        bits_rows = codec.bits_rows_for(D)
        route, plan = codec._route_plan(D)
        fn = self._decode1_program(
            codec.kernel, D.shape[0], bits_rows, n_dev,
            plan if route == "panel" else None,
        )
        TWp = pad_words(TW)
        expected = self.sharding_for(n_dev)
        arr = words
        if isinstance(arr, np.ndarray):
            if TWp != TW or B_pad != B:
                buf = np.zeros((B_pad, m, TWp), dtype=np.uint32)
                buf[:B, :, :TW] = arr
                arr = buf
            arr = jax.device_put(np.ascontiguousarray(arr), expected)
        elif TWp != TW or B_pad != B:
            arr = jnp.pad(arr, ((0, B_pad - B), (0, 0), (0, TWp - TW)))
        else:
            self._note_input(arr, expected)
        corrected, bad = fn(arr)
        self._record("shard_map", 4 * B * m * TW, n_dev)
        if route == "panel":
            from noise_ec_tpu.ops.dispatch import (
                plan_sublaunches,
                record_sublaunch_dispatch,
            )

            record_sublaunch_dispatch(
                "mesh_decode1", plan_sublaunches(plan)
            )
        return corrected[:B, :TW], bad[:B, :TW]

    # ----------------------------------------------------- sym batch entry

    def matmul_sym_batch(self, degree: int, out_rows: int,
                         masks: np.ndarray, batch):
        """pjit tier: (B, k, S) symbol batch x replicated mask matrix ->
        (B, out_rows, S), batch axis sharded. Serves the XLA kernel's
        ``matmul_stripes_many`` route AND ``BatchCodec.matmul_batch``.
        """
        B = int(batch.shape[0])
        B_pad = ladder_pad(B)
        n_dev = self.n_dev_for(B_pad)
        fn, masks_dev = self._sym_program(degree, out_rows, masks, n_dev)
        expected = self.sharding_for(n_dev)
        if B_pad != B:
            if isinstance(batch, np.ndarray):
                buf = np.empty(
                    (B_pad,) + batch.shape[1:], dtype=batch.dtype
                )
                buf[:B] = batch  # pad members: discarded garbage rows
                batch = buf
            else:
                batch = jnp.pad(batch, ((0, B_pad - B), (0, 0), (0, 0)))
        if not isinstance(batch, np.ndarray):
            self._note_input(batch, expected)
        nbytes = int(np.prod(batch.shape[1:])) * batch.dtype.itemsize * B
        out = fn(masks_dev, batch)
        self._record("pjit", nbytes, n_dev)
        return out[:B]

    # --------------------------------------------- bench/test program API

    def encode_words_program(self, codec, M: np.ndarray, n_dev: int):
        """Compiled sharded words encode for bench/tests: (B, k, TWp)
        u32 -> (B, r, TWp), batch axis over ``n_dev`` mesh devices (no
        donation — chained timing loops reuse their input). Wide
        matrices get their panel plan, like the dispatch entries."""
        M = np.asarray(M)
        route, plan = codec._route_plan(M)
        return self._words_program(
            codec.kernel, M.shape[0], codec.bits_rows_for(M), n_dev, False,
            plan if route == "panel" else None,
        )

    def encode_sym_program(self, codec, M: np.ndarray, n_dev: int):
        """Compiled pjit-tier symbol encode for bench/tests: a callable
        (B, k, S) -> (B, r, S) with the replicated mask operand bound."""
        M = np.asarray(M)
        fn, masks_dev = self._sym_program(
            codec.gf.degree, M.shape[0], codec.masks_for(M), n_dev
        )
        return functools.partial(fn, masks_dev)

    # --------------------------------------- DeviceCodec list-entry shims

    def matmul_sym_many(self, codec, M: np.ndarray, Ds: list,
                        B_pad: int) -> list:
        """XLA-kernel ``matmul_stripes_many`` route: stack the B stripe
        payloads (garbage ladder pad) and run the pjit tier. Returns B
        ordinary writable ndarrays, byte-identical to B single calls."""
        M = np.asarray(M)
        k, S = Ds[0].shape
        batch = np.empty((B_pad, k, S), dtype=codec.gf.dtype)
        for b, D in enumerate(Ds):
            batch[b] = D
        out = np.asarray(self.matmul_sym_batch(
            codec.gf.degree, M.shape[0], codec.masks_for(M), batch
        ))
        return [np.array(out[b]) for b in range(len(Ds))]

    def matmul_bytesliced_many(self, codec, M: np.ndarray, Ds: list,
                               B_pad: int) -> list:
        """GF(2^16) baked-route batch: each u16 member splits into
        (lo, hi) byte rows (the unpermuted expansion — see
        ``DeviceCodec.matmul_stripes``) and the batch runs the m=8
        words tier with 2k/2r rows. Returns B (r, S) u16 arrays."""
        from noise_ec_tpu.ops.dispatch import buffer_pool, pad_words

        M = np.asarray(M)
        r, k = M.shape
        r2, k2 = 2 * r, 2 * k
        S = Ds[0].shape[1]  # symbols per shard == bytes per byte-row
        TWp = pad_words(-(-S // 4))
        lease = buffer_pool().acquire_padded(B_pad * k2, 4 * TWp, S)
        buf = lease.arr
        for b, D in enumerate(Ds):
            buf[b * k2:(b + 1) * k2, :S] = (
                np.ascontiguousarray(D)
                .view(np.uint8)
                .reshape(k, S, 2)
                .transpose(0, 2, 1)
                .reshape(k2, S)
            )
        words = buf.view("<u4").reshape(B_pad, k2, TWp)
        route, plan = codec._route_plan(M)
        out, _, _ = self._words_dispatch(
            codec.kernel, r2, codec.bits_rows_for(M), words, donate=True,
            plan=plan if route == "panel" else None,
        )
        out_w = np.asarray(out)  # (B_pad, r2, TWp)
        buffer_pool().release(lease)
        res = []
        for b in range(len(Ds)):
            ob = np.ascontiguousarray(out_w[b]).view(np.uint8)[:, :S]
            res.append(np.ascontiguousarray(
                ob.reshape(r, 2, S).transpose(0, 2, 1)
            ).view("<u2").reshape(r, S))
        return res


def _mesh_devices_gauge() -> int:
    """Devices the active codec mesh spans (1 = single-device tier)."""
    r = _router
    return r.n_pow2 if r is not None and r.enabled else 1


_router: Optional[MeshRouter] = None
_router_lock = threading.Lock()


def mesh_router() -> MeshRouter:
    """The process-wide mesh dispatch router (lazy singleton)."""
    global _router
    with _router_lock:
        if _router is None:
            _router = MeshRouter()
        return _router


def configure_mesh_router(**kwargs) -> MeshRouter:
    """Replace the process router (tests/bench force ``enable`` or pin a
    device subset; a fresh instance also drops compiled programs)."""
    global _router
    with _router_lock:
        _router = MeshRouter(**kwargs)
        return _router


def reset_mesh_router() -> None:
    """Drop the router so the next use rebuilds over the CURRENT device
    list — ``multihost.initialize`` calls this after joining the
    distributed runtime (the global device list replaces the local one).
    """
    global _router
    with _router_lock:
        _router = None
