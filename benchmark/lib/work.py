"""The HBM bytes the window's requests needed, whatever implements them.

- Encoding a stripe whose payload is L bytes under RS(k, n): read k data
  shards and write r = n - k parity shards of ceil(L / k) bytes each,
  (k + r) * shard bytes (the object service pads a stripe to a multiple
  of k, so shard bytes = padded length / k).
- Reconstructing ``erased`` lost data shards of a stripe: read k surviving
  shards and write the erased ones, (k + erased) * shard bytes.

A request needs its stripes once. Manifest stripes (hundreds of bytes)
are left out. Reads of one stripe whose request intervals overlap may
share one reconstruct (the object service single-flights them), so they
count once: the count can fall short of the work done, never exceed it.
"""

from __future__ import annotations


def shard_bytes(payload_bytes: int, k: int) -> int:
    return -(-payload_bytes // k)


def stripe_payloads(object_bytes: int, capacity: int) -> list[int]:
    full, tail = divmod(object_bytes, capacity)
    return [capacity] * full + ([tail] if tail else [])


def encode_bytes(payload_bytes: int, k: int, n: int) -> int:
    return n * shard_bytes(payload_bytes, k)


def reconstruct_bytes(payload_bytes: int, k: int, erased: int) -> int:
    return (k + erased) * shard_bytes(payload_bytes, k)


def put_bytes_needed(object_sizes: list, capacity: int, k: int,
                     n: int) -> int:
    return sum(encode_bytes(p, k, n) for size in object_sizes
               for p in stripe_payloads(size, capacity))


def degraded_read_bytes_needed(reads: list, k: int, erased: int) -> int:
    """``reads``: (stripe identity, t0, t1, payload bytes) of every
    degraded stripe read; overlapping reads of one stripe count once."""
    total = 0
    by_stripe: dict = {}
    for ident, t0, t1, payload in reads:
        by_stripe.setdefault(ident, []).append((t0, t1, payload))
    for spans in by_stripe.values():
        spans.sort()
        end = float("-inf")
        for t0, t1, payload in spans:
            if t0 > end:
                total += reconstruct_bytes(payload, k, erased)
            end = max(end, t1)
    return total
