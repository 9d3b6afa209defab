"""Chunked streaming encode/decode for wide / high-rate codes (BASELINE
config 3) — the double-buffered host↔device data path.

The reference encodes whole messages in one call (main.go:262); for long
objects (RS(17,3), RS(50,20) streaming configs) the TPU build chunks the
byte stream on the host and keeps THREE stages of the data path busy at
once: while chunk i computes on device, chunk i+1's H2D staging is
already submitted (``jax.device_put`` is asynchronous) and chunk i−1's
parity is flowing D2H (``copy_to_host_async`` + an explicit readiness
handle, never a per-chunk ``block_until_ready``). The consumer blocks
only when the in-flight window is full AND the oldest chunk is still
computing.

Two transfer-volume rules keep the host<->device link the only bound:

- **parity-only fetch**: the device computes and returns ONLY the r
  parity rows. The k data rows already live on the host (they are the
  caller's bytes) — shipping them down just to ship them back was
  ~(n−k+n)/r times the necessary D2H volume (RS(10,4): 3.5x).
- **donated staging**: the words staged for a chunk are device-put and
  their HBM donated into the parity output
  (``matmul_words_batch(donate=True)``), so steady-state encode never
  grows the device allocation high-water mark (ops/dispatch.py pool
  rules).

Each chunk is an independent codeword batch, so a lost chunk only costs
that chunk's shards — the same per-message isolation the reference's
mempool gives (main.go:55).
"""

from __future__ import annotations

import time
from collections import deque
from typing import Iterable, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from noise_ec_tpu.obs.registry import default_registry
from noise_ec_tpu.parallel.batch import BatchCodec

__all__ = [
    "StreamChunk",
    "StreamingDecoder",
    "StreamingEncoder",
    "decode_stream",
]


def _is_ready(arr) -> bool:
    """Non-blocking readiness probe of a device array (the explicit
    handle the double-buffer window polls instead of blocking)."""
    probe = getattr(arr, "is_ready", None)
    if probe is None:
        return True  # plain ndarray: nothing in flight
    try:
        return bool(probe())
    except Exception:  # noqa: BLE001 — a deleted/odd array counts ready
        return True


class StreamChunk:
    """Encoded shards for one chunk of the stream.

    Constructed either from separate ``data`` (k, stride) / ``parity``
    (r, stride) uint8 rows — the parity-only-fetch fast path, where the
    data rows are zero-copy views of the caller's bytes — or from a full
    ``shards`` (n, stride) array (tests and legacy callers). ``shards``
    is assembled (one concat copy) only if someone asks for it.
    """

    __slots__ = ("index", "data_len", "_shards", "data", "parity")

    def __init__(self, index: int, shards: Optional[np.ndarray] = None,
                 data_len: int = 0, *, data: Optional[np.ndarray] = None,
                 parity: Optional[np.ndarray] = None):
        self.index = index
        self.data_len = data_len
        self._shards = shards
        self.data = data
        self.parity = parity
        if shards is None and (data is None or parity is None):
            raise ValueError("StreamChunk needs shards or data+parity")

    @property
    def shards(self) -> np.ndarray:
        """(n, stride) codeword rows (assembled and cached on demand)."""
        if self._shards is None:
            self._shards = np.concatenate([self.data, self.parity], axis=0)
        return self._shards

    def rows(self) -> list:
        """Per-row buffers for wire marshal — zero-copy when the chunk
        carries split data/parity (no (n, stride) assembly)."""
        if self._shards is not None:
            return [self._shards[i] for i in range(self._shards.shape[0])]
        return (
            [self.data[i] for i in range(self.data.shape[0])]
            + [self.parity[i] for i in range(self.parity.shape[0])]
        )


class _Pending:
    """One in-flight chunk of the double-buffered window."""

    __slots__ = ("index", "data_len", "data", "parity_dev", "t0")

    def __init__(self, index, data_len, data, parity_dev, t0):
        self.index = index
        self.data_len = data_len
        self.data = data
        self.parity_dev = parity_dev
        self.t0 = t0


class StreamingEncoder:
    """Encode an arbitrary byte stream as a sequence of RS codewords.

    ``chunk_bytes`` is the payload per codeword; it is split into k equal
    stripes (zero-padded tail chunk) and parity is computed on device
    through the double-buffered window (module docstring): H2D of chunk
    i+1 overlaps compute of chunk i and the D2H of chunk i−1.
    """

    def __init__(self, data_shards: int, parity_shards: int, *,
                 chunk_bytes: int = 1 << 20, field: str = "gf256",
                 matrix: str = "cauchy", kernel: str = "auto"):
        self.codec = BatchCodec(data_shards, parity_shards, field=field,
                                matrix=matrix)
        self.k = data_shards
        self.r = parity_shards
        self.n = data_shards + parity_shards
        sym = self.codec.gf.degree // 8
        from noise_ec_tpu.ops.dispatch import _resolve_kernel

        self._kernel = kernel
        # Words branch iff a Pallas kernel will actually run it; an explicit
        # kernel="xla" (even on TPU) keeps the async symbol path.
        self._use_words = _resolve_kernel(kernel) != "xla"
        # Round the chunk so each stripe is whole symbols — the caller-visible
        # contract, identical on every backend. The TPU words path needs
        # whole uint32 words per stripe; rather than shrink chunk_bytes
        # (which would reject caller-prechunked streams that were valid on
        # other backends), each chunk is zero-padded up to _padded_bytes
        # before striping. Padding sits at the tail of the flat buffer, so
        # decode_stream's reshape(-1)[:data_len] slice drops it for free.
        quantum = data_shards * sym
        self.chunk_bytes = max(quantum, chunk_bytes - chunk_bytes % quantum)
        wq = data_shards * max(sym, 4)
        self._padded_bytes = (
            -(-self.chunk_bytes // wq) * wq if self._use_words else self.chunk_bytes
        )
        # Per-chunk dispatch-to-fetch latency (includes pipeline queueing:
        # a growing p99 here means the consumer or the fetch link, not the
        # kernels, is the bottleneck). One observe per chunk — nothing on
        # the per-kernel path.
        self._chunk_hist = default_registry().histogram(
            "noise_ec_stream_chunk_seconds"
        ).labels()

    def _stage(self, chunk) -> np.ndarray:
        """(k, stride) uint8 data rows. Full chunks are zero-copy views
        of the caller's bytes (the caller holds them for the call — the
        same retention contract as the host shim path); short tail
        chunks get their own padded buffer, since the rows escape to the
        consumer inside the yielded StreamChunk."""
        buf = np.frombuffer(chunk, dtype=np.uint8)
        if buf.size < self._padded_bytes:
            pad = np.zeros(self._padded_bytes, dtype=np.uint8)
            pad[: buf.size] = buf
            buf = pad
        return buf.reshape(self.k, self._padded_bytes // self.k)

    def _dispatch_chunk(self, idx: int, chunk, t0: float) -> _Pending:
        """Submit one chunk's H2D + parity compute; returns the pending
        handle without waiting on anything."""
        data = self._stage(chunk)
        if self._use_words:
            from noise_ec_tpu.ops.dispatch import (
                buffer_pool,
                donation_supported,
            )

            # (1, k, TW) from the start so the device_put result is the
            # ONLY reference to the staged buffer — donation then truly
            # recycles its HBM into the parity output.
            words = np.ascontiguousarray(data).view("<u4")[None]
            words_dev = jax.device_put(words)
            donate = donation_supported()
            if donate:
                buffer_pool().donate(words_dev)
            dev = self.codec.device_codec(self._kernel)
            parity_dev = dev.matmul_words_batch(
                self.codec.parity_matrix, words_dev, donate=donate
            )[0]
        else:
            sym = data.view("<u2") if self.codec.gf.degree == 16 else data
            parity_dev = self.codec.matmul_batch(
                self.codec.parity_matrix, jnp.asarray(sym)[None]
            )[0]
        # Start the D2H now (explicit readiness handle; the window polls
        # is_ready and blocks only when full).
        try:
            parity_dev.copy_to_host_async()
        except Exception:  # noqa: BLE001 — backends without the hint
            pass
        return _Pending(idx, len(chunk), data, parity_dev, t0)

    def _finish(self, pend: _Pending) -> StreamChunk:
        arr = np.asarray(pend.parity_dev)  # blocks only if not ready yet
        if arr.dtype != np.uint8:
            arr = arr.view(np.uint8)
        self._chunk_hist.observe(time.perf_counter() - pend.t0)
        return StreamChunk(
            index=pend.index, data_len=pend.data_len,
            data=pend.data, parity=arr,
        )

    def _drain(self, window: deque, depth: int) -> Iterator[StreamChunk]:
        """Yield leading chunks in index order: ready heads always flow
        (free progress while the device works); a still-computing head
        blocks the consumer only once the window exceeds ``depth``."""
        while window and (
            len(window) > depth or _is_ready(window[0].parity_dev)
        ):
            yield self._finish(window.popleft())

    def encode_stream(self, chunks: Iterable[bytes],
                      depth: int = 4) -> Iterator[StreamChunk]:
        """Yield encoded StreamChunks; keeps up to ``depth`` in flight
        (the double-buffered window — module docstring)."""
        window: deque = deque()
        idx = 0
        for chunk in chunks:
            if len(chunk) > self.chunk_bytes:
                raise ValueError(
                    f"chunk {idx} is {len(chunk)} bytes > chunk_bytes "
                    f"{self.chunk_bytes}"
                )
            t0 = time.perf_counter()
            window.append(self._dispatch_chunk(idx, chunk, t0))
            idx += 1
            yield from self._drain(window, depth)
        yield from self._drain(window, 0)

    def encode_bytes(self, data: bytes, depth: int = 4) -> Iterator[StreamChunk]:
        """Convenience: chunk a contiguous buffer and encode_stream it."""
        def gen():
            for off in range(0, len(data), self.chunk_bytes):
                yield data[off: off + self.chunk_bytes]
        if len(data) == 0:
            return iter(())
        return self.encode_stream(gen(), depth=depth)


class StreamingDecoder:
    """Pipelined degraded-chunk rebuild: the decode path's half of the
    double-buffered window. Chunks whose shards share one erasure
    pattern ride ``BatchCodec.reconstruct_batch_words`` with the same
    H2D / compute / D2H overlap as the encoder — H2D of chunk i+1
    overlaps the reconstruct of chunk i and the fetch of chunk i−1."""

    def __init__(self, data_shards: int, parity_shards: int, *,
                 field: str = "gf256", matrix: str = "cauchy",
                 kernel: str = "auto"):
        self.codec = BatchCodec(data_shards, parity_shards, field=field,
                                matrix=matrix)
        self.k = data_shards
        self.n = data_shards + parity_shards
        self._kernel = kernel

    def reconstruct_stream(self, chunks: Iterable[tuple],
                           present: list[int],
                           depth: int = 4) -> Iterator[tuple]:
        """``chunks``: iterable of (index, rows) with ``rows`` a
        (len(present), stride_bytes) uint8 array of the surviving shards
        in ``present`` index order. Yields (index, full (n, stride)
        uint8 codeword rows) in input order, pipelined ``depth`` deep."""
        window: deque = deque()

        def finish(entry):
            idx, dev_rows = entry
            out = np.asarray(dev_rows)
            if out.dtype != np.uint8:
                out = (
                    np.ascontiguousarray(out).view(np.uint8)
                    .reshape(self.n, -1)
                )
            return idx, out

        for idx, rows in chunks:
            rows = np.asarray(rows)
            if rows.dtype != np.uint8:
                rows = rows.view(np.uint8)
            words = np.ascontiguousarray(rows).view("<u4")
            dev_rows = self.codec.reconstruct_batch_words(
                jnp.asarray(words)[None], present, kernel=self._kernel
            )[0]
            try:
                dev_rows.copy_to_host_async()
            except Exception:  # noqa: BLE001
                pass
            window.append((idx, dev_rows))
            while window and (
                len(window) > depth or _is_ready(window[0][1])
            ):
                yield finish(window.popleft())
        while window:
            yield finish(window.popleft())


def decode_stream(chunks: Iterable[StreamChunk], data_shards: int,
                  total_len: Optional[int] = None) -> bytes:
    """Reassemble the byte stream from (in-order, complete) StreamChunks."""
    parts = []
    for c in chunks:
        arr = (
            np.asarray(c.data) if c.data is not None
            else np.asarray(c.shards[:data_shards])
        )
        if arr.dtype != np.uint8:  # rebuilt gf65536 chunks arrive as uint16
            arr = arr.view(np.uint8)
        data = arr.reshape(-1)[: c.data_len]
        parts.append(data.tobytes())
    out = b"".join(parts)
    return out[:total_len] if total_len is not None else out
