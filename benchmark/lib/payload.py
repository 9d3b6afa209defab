"""Seeded object bytes: the same seed and PUT index give the same bytes.

One random base buffer is drawn from the seed in set-up. Object ``index``
is a window of it at an offset drawn from (seed, index), with the first
16 bytes of every stripe replaced by a tag of (seed, index, stripe), so
no two stripes of a run share content (the store would otherwise dedup
identical stripes to one key)."""

from __future__ import annotations

import hashlib
import struct

import numpy as np

TAG_BYTES = 16
SEED_MASK = (1 << 64) - 1


class Payloads:
    def __init__(self, seed: int, object_bytes: int, capacity: int):
        self.seed = int(seed)
        self.object_bytes = int(object_bytes)
        self.capacity = int(capacity)
        self.spread = 1 << 20
        rng = np.random.default_rng(self.seed & SEED_MASK)
        self.base = rng.bytes(self.object_bytes + self.spread)
        self._seed_tag = hashlib.blake2b(
            str(self.seed).encode(), digest_size=8).digest()

    def _offset(self, index: int) -> int:
        h = hashlib.blake2b(f"{self.seed}/{index}".encode(), digest_size=8)
        return int.from_bytes(h.digest(), "little") % self.spread

    def tag(self, index: int, stripe: int) -> bytes:
        return self._seed_tag + struct.pack("<II", index, stripe)

    def _tags(self, index: int, lo: int, hi: int):
        """(position, tag bytes) of the tags that fall in [lo, hi)."""
        for s in range(lo // self.capacity, -(-hi // self.capacity)):
            pos = s * self.capacity
            end = min(pos + TAG_BYTES, self.object_bytes)
            if end > lo and pos < hi:
                yield pos, self.tag(index, s)[:end - pos]

    def make(self, index: int) -> bytearray:
        """Object ``index`` whole, as the PUT sends it."""
        off = self._offset(index)
        buf = bytearray(memoryview(self.base)[off:off + self.object_bytes])
        for pos, tag in self._tags(index, 0, self.object_bytes):
            buf[pos:pos + len(tag)] = tag
        return buf

    def view(self, index: int, start: int, length: int) -> np.ndarray:
        """``length`` bytes of object ``index`` at ``start``, built without
        the rest of the object."""
        off = self._offset(index)
        end = min(start + length, self.object_bytes)
        out = np.frombuffer(self.base, dtype=np.uint8)[
            off + start:off + end].copy()
        for pos, tag in self._tags(index, start, end):
            lo, hi = max(pos, start), min(pos + len(tag), end)
            out[lo - start:hi - start] = np.frombuffer(
                tag[lo - pos:hi - pos], dtype=np.uint8)
        return out

    def mismatch_bytes(self, index: int, start: int, length: int,
                       data) -> int:
        """Bytes of ``data`` that differ from ``length`` bytes of object
        ``index`` at ``start``; a short or long answer counts the bytes
        it lacks or adds."""
        want = self.view(index, start, length)
        got = np.frombuffer(data, dtype=np.uint8)
        n = min(len(got), len(want))
        return (int(np.count_nonzero(got[:n] != want[:n]))
                + abs(len(got) - len(want)))
