"""How ``correct`` is decided: every number here is compared with its limit
once the window has closed. All comparisons are exact, so every limit is 0.

- Every acknowledged PUT (set-up's included) is read back from node b's
  store, every stripe of it: its data shards against the seeded payload,
  its parity shards against ``reference.encode`` (the plain codec in this
  directory), stripes spread over ``CHECK_THREADS`` threads. A slot
  b dropped on purpose (the traffic's ``lose_slots``), or a stripe the
  program evicted because a later PUT replaced its object, is read from
  what b's store held when it stored the stripe (``Cluster.stored_on_b``).
- Every GET and stripe read is byte-compared with the seeded payload.
- Every STAT must name the address its object's PUT was acknowledged with.
- An operation that raised is an answer that never came.

A cell compares the numbers its operations can move (``compared``).
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from . import reference

LIMITS = {
    "missing_on_b": 0,
    "data_mismatch_bytes": 0,
    "parity_mismatch_bytes": 0,
    "read_mismatch_bytes": 0,
    "stat_mismatch": 0,
    "ops_failed": 0,
}
# Numbers only a cell whose deck has these operations can move.
NEEDS = {"read_mismatch_bytes": {"get", "read_stripe"},
         "stat_mismatch": {"stat"}}


def compared(deck) -> list[str]:
    """The numbers a cell with this deck compares."""
    return [name for name in LIMITS
            if name not in NEEDS or NEEDS[name] & set(deck)]


def _as_u8(blob) -> np.ndarray:
    return np.frombuffer(blob, dtype=np.uint8)


def _mismatch(got: np.ndarray, want: np.ndarray) -> int:
    n = min(len(got), len(want))
    return int(np.count_nonzero(got[:n] != want[:n])) + abs(
        len(got) - len(want))


# The reference's table lookups and XORs release the GIL, so the check
# runs on a few threads once the window has closed (~50 ms per 10 MiB
# stripe on one thread).
CHECK_THREADS = 4


def _stripe_numbers(shards: list, stripe: np.ndarray, k: int,
                    r: int) -> tuple[int, int]:
    """(data, parity) mismatch bytes of one stripe b holds."""
    if len(stripe) % k:
        stripe = np.concatenate(
            [stripe, np.zeros((-len(stripe)) % k, np.uint8)])
    data = list(stripe.reshape(k, -1))
    parity = reference.encode(data, r)
    return (sum(_mismatch(_as_u8(shards[j]), data[j]) for j in range(k)),
            sum(_mismatch(_as_u8(shards[k + j]), parity[j])
                for j in range(r)))


def check_puts(cluster, traffic, config: dict) -> dict:
    k, n = int(config["k"]), int(config["n"])
    r = n - k
    cap = traffic.payloads.capacity
    b = cluster["b"]
    out = {"missing_on_b": 0, "data_mismatch_bytes": 0,
           "parity_mismatch_bytes": 0}
    last_of_name: dict = {}
    for i, put in enumerate(traffic.puts):
        last_of_name[put.name] = i
    jobs = []  # (shards b holds, put, stripe position)
    for i, put in enumerate(traffic.puts):
        doc = put.doc
        live = b.store.get_manifest(doc["address"]) is not None
        if not live and last_of_name[put.name] == i:
            out["missing_on_b"] += 1  # b never indexed the newest object
        for s, key in enumerate(doc["stripes"]):
            try:
                _, current, _ = b.store.snapshot(key)
            except KeyError:
                current = None
            captured = cluster.stored_on_b.get(key)
            lost = traffic.lost.get(key, ())
            shards = []
            for slot in range(n):
                blob = current[slot] if current is not None else None
                if blob is None and (slot in lost or not live) and captured:
                    blob = captured[slot]
                shards.append(blob)
            if any(x is None for x in shards):
                out["missing_on_b"] += sum(x is None for x in shards)
                continue
            jobs.append((shards, put, s))

    def one(job) -> tuple[int, int]:
        shards, put, s = job
        lo = s * cap
        stripe = traffic.payloads.view(put.index, lo,
                                       min(cap, put.nbytes - lo))
        return _stripe_numbers(shards, stripe, k, r)

    with ThreadPoolExecutor(CHECK_THREADS) as pool:
        for data_bad, parity_bad in pool.map(one, jobs):
            out["data_mismatch_bytes"] += data_bad
            out["parity_mismatch_bytes"] += parity_bad
    return out


def check_ops(ops: list, payloads) -> dict:
    out = {"read_mismatch_bytes": 0, "stat_mismatch": 0, "ops_failed": 0}
    for op in ops:
        if not op.ok:
            out["ops_failed"] += 1
        elif op.kind in ("get", "read_stripe"):
            out["read_mismatch_bytes"] += payloads.mismatch_bytes(
                op.index, op.start, op.length, op.data)
            op.data = None
        elif op.kind == "stat":
            out["stat_mismatch"] += int(op.address != op.doc["want"]
                                        or op.doc["got_size"]
                                        != op.doc["size"])
    return out


def check(cluster, traffic, ops: list, config: dict) -> dict:
    """name -> [value, limit] of the numbers the cell compares."""
    found = check_ops(ops, traffic.payloads)
    found.update(check_puts(cluster, traffic, config))
    return {name: [found[name], LIMITS[name]]
            for name in compared(traffic.t["deck"])}


def passed(numbers: dict) -> bool:
    return all(value <= limit for value, limit in numbers.values())
