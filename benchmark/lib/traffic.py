"""The one traffic generator. A traffic file (``benchmark/traffic/*.json``)
names the clients, the object size, the set-up it needs (objects loaded
first, shard slots lost on a node) and a deck of operations; this module
runs any such file against the cluster as closed-loop clients.

Each client draws its operations from its own copy of the deck, shuffled
anew by (seed, client, round) each time it runs out, so every seed sends
the same mix in another order. An object's bytes come from the seed and
the PUT's index; client c's j-th PUT has index preload + c + clients * j.

Operations: ``put``, ``get`` (whole object), ``stat`` (resolve),
``delete``, ``read_stripe`` (one whole stripe-aligned range). GET, STAT
and DELETE pick a seeded random live object; a DELETE takes one no other
client is reading, so no operation fails by design."""

from __future__ import annotations

import contextlib
import random
import threading
import time
from dataclasses import dataclass
from typing import Callable, Optional

WARM_INDEX = 1 << 30  # PUT indices of warm-up objects


class AllBusy(Exception):
    """Every live object is being read: this DELETE is not sent."""


@dataclass
class Op:
    kind: str
    client: int
    t0: float = 0.0
    t1: float = 0.0
    ok: bool = False
    error: Optional[str] = None
    nbytes: int = 0  # payload bytes the operation moved
    name: Optional[str] = None
    index: Optional[int] = None  # PUT index of the object touched
    address: Optional[str] = None  # manifest address (put ack, stat answer)
    start: int = 0  # read range
    length: int = 0
    stripe: Optional[int] = None
    data: Optional[bytes] = None  # the read's answer, kept for the check
    doc: Optional[dict] = None  # the PUT's acknowledged manifest


class LiveSet:
    """Objects that exist, with readers counted so a DELETE never takes
    an object a GET is reading."""

    def __init__(self):
        self.lock = threading.Lock()
        self.objects: dict[str, tuple] = {}  # name -> (index, address, size)
        self.readers: dict[str, int] = {}

    def add(self, name: str, index: int, address: str, size: int) -> None:
        with self.lock:
            self.objects[name] = (index, address, size)

    def checkout(self, rng: random.Random):
        with self.lock:
            if not self.objects:
                return None
            name = rng.choice(sorted(self.objects))
            self.readers[name] = self.readers.get(name, 0) + 1
            return (name,) + self.objects[name]

    def checkin(self, name: str) -> None:
        with self.lock:
            self.readers[name] -= 1
            if not self.readers[name]:
                del self.readers[name]

    def take(self, rng: random.Random):
        with self.lock:
            free = sorted(n for n in self.objects if n not in self.readers)
            if not free:
                return None
            name = rng.choice(free)
            return (name,) + self.objects.pop(name)


class Traffic:
    def __init__(self, traffic: dict, config: dict, cluster, payloads,
                 seed: int):
        self.t = traffic
        self.cluster = cluster
        self.payloads = payloads
        self.seed = int(seed)
        self.tenant = config["tenant"]
        self.clients = int(traffic["clients"])
        self.preload_objects = int(traffic.get("preload_objects", 0))
        self.live = LiveSet()
        self.puts: list[Op] = []  # every acknowledged PUT, set-up included
        self._puts_lock = threading.Lock()
        self.lost: dict = {}  # node b stripe key -> slots dropped
        self.skipped = 0
        self.setup_failures: list[str] = []
        self._run = {
            "put": self._put,
            "get": self._get,
            "stat": self._stat,
            "delete": self._delete,
            "read_stripe": self._read_stripe,
        }
        unknown = set(traffic["deck"]) - set(self._run)
        if unknown:
            raise ValueError(f"unknown operations in the deck: {unknown}")

    # ------------------------------------------------------------ set-up

    def _service(self, kind: str):
        return self.cluster[self.t.get(kind, {}).get("node", "a")].service

    def _do_put(self, op: Op, name: str, index: int,
                live: bool = True) -> None:
        data = self.payloads.make(index)
        op.name, op.index, op.nbytes = name, index, len(data)
        doc = self._service("put").put(self.tenant, name, data)
        op.doc, op.address = doc, doc["address"]
        with self._puts_lock:
            self.puts.append(op)
        if live:
            self.live.add(name, index, doc["address"], len(data))

    def preload(self) -> None:
        """PUT the traffic's objects first, from as many writers as it has
        clients (at most 8): object i is ``pre-<i>`` with PUT index i."""
        writers = max(1, min(8, self.clients, self.preload_objects))

        def writer(w: int) -> None:
            for i in range(w, self.preload_objects, writers):
                self._guarded("preload", self._do_put, Op("preload", -1),
                              f"pre-{i}", i)

        threads = [threading.Thread(target=writer, args=(w,), daemon=True)
                   for w in range(writers)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()

    def _guarded(self, what: str, fn, *args) -> None:
        """Set-up work whose failure the window and the check will show:
        noted on stderr, not raised."""
        try:
            fn(*args)
        except Exception as exc:  # noqa: BLE001 — reported, then measured
            self.setup_failures.append(f"{what}: {exc!r}")

    def lose_slots(self) -> int:
        """Drop the traffic's lost slots from every stripe of every
        loaded object on the named node. Returns the slots dropped."""
        spec = self.t.get("lose_slots")
        if not spec:
            return 0
        node = self.cluster[spec["node"]]
        dropped = 0
        for op in list(self.puts):
            for key in op.doc["stripes"]:
                for slot in spec["slots"]:
                    if node.store.drop_shard(key, slot):
                        dropped += 1
                self.lost[key] = list(spec["slots"])
        return dropped

    def warm(self) -> None:
        """One operation of every kind in the deck, through the real
        path, on warm-up objects of their own."""
        rng = random.Random(f"{self.seed}/warm")
        for i, kind in enumerate(sorted(self.t["deck"])):
            op = Op(kind, -1)
            if kind == "put":
                self._guarded("warm put", self._do_put, op, f"warm-{i}",
                              WARM_INDEX + i)
            elif kind == "delete":
                self._guarded("warm delete", self._warm_delete, i)
            else:
                self._guarded(f"warm {kind}", self._run[kind], op, rng)

    def _warm_delete(self, i: int) -> None:
        self._do_put(Op("warm", -1), f"warm-{i}", WARM_INDEX + i, live=False)
        self._service("delete").delete(self.tenant, f"warm-{i}")

    # ------------------------------------------------------------ window

    def _put(self, op: Op, rng: random.Random, client: int = 0,
             j: int = 0) -> None:
        spec = self.t["put"]
        index = self.preload_objects + client + self.clients * j
        if spec["names"] == "ring":
            name = f"w{client}-{j % int(spec['ring'])}"
        else:
            name = f"c{client}-{j}"
        self._do_put(op, name, index)
        op.ok = True

    def _get(self, op: Op, rng: random.Random, **_) -> None:
        pick = self.live.checkout(rng)
        if pick is None:
            raise LookupError("no live object")
        name, index, _, size = pick
        op.name, op.index, op.start, op.length = name, index, 0, size
        try:
            _, _, chunks = self._service("get").get_range(self.tenant, name)
            op.data = b"".join(chunks)
        finally:
            self.live.checkin(name)
        op.nbytes = len(op.data)
        op.ok = True

    def _stat(self, op: Op, rng: random.Random, **_) -> None:
        pick = self.live.checkout(rng)
        if pick is None:
            raise LookupError("no live object")
        name, index, address, size = pick
        op.name, op.index = name, index
        try:
            doc = self._service("stat").resolve(self.tenant, name)
        finally:
            self.live.checkin(name)
        op.doc = {"want": address, "size": size, "got_size": doc["size"]}
        op.address = doc["address"]
        op.ok = True

    def _delete(self, op: Op, rng: random.Random, **_) -> None:
        pick = self.live.take(rng)
        if pick is None:
            raise AllBusy()
        name, index, _, _ = pick
        op.name, op.index = name, index
        self._service("delete").delete(self.tenant, name)
        op.ok = True

    def _read_stripe(self, op: Op, rng: random.Random, **_) -> None:
        pick = self.live.checkout(rng)
        if pick is None:
            raise LookupError("no live object")
        name, index, _, size = pick
        cap = self.payloads.capacity
        n_stripes = size // cap if self.t["read_stripe"].get(
            "full_stripes_only") else -(-size // cap)
        s = rng.randrange(n_stripes)
        op.name, op.index, op.stripe = name, index, s
        op.start, op.length = s * cap, min(cap, size - s * cap)
        try:
            _, _, chunks = self._service("read_stripe").get_range(
                self.tenant, name, op.start, op.length)
            op.data = b"".join(chunks)
        finally:
            self.live.checkin(name)
        op.nbytes = len(op.data)
        op.ok = True

    def _client(self, client: int, t_start: float, t_end: float, out: list,
                annotate: Callable, barrier: threading.Barrier) -> None:
        rng = random.Random(f"{self.seed}/client/{client}")
        deck = [k for k, n in sorted(self.t["deck"].items())
                for _ in range(int(n))]
        hand: list = []
        rounds = 0
        puts = 0
        barrier.wait()
        time.sleep(max(0.0, t_start - time.perf_counter()))
        while time.perf_counter() < t_end:
            if not hand:
                hand = list(deck)
                random.Random(f"{self.seed}/deck/{client}/{rounds}").shuffle(
                    hand)
                rounds += 1
            kind = hand.pop()
            op = Op(kind, client)
            with annotate(kind):
                op.t0 = time.perf_counter()
                try:
                    if kind == "put":
                        self._put(op, rng, client=client, j=puts)
                        puts += 1
                    else:
                        self._run[kind](op, rng)
                except AllBusy:
                    with self._puts_lock:
                        self.skipped += 1
                    continue
                except Exception as exc:  # noqa: BLE001 — a failed op is data
                    op.ok = False
                    op.error = repr(exc)
                op.t1 = time.perf_counter()
            out.append(op)

    def run(self, seconds: float, annotate: Optional[Callable] = None):
        """Closed-loop clients for ``seconds``: no operation starts after
        the window ends, and every one started in it runs to completion.
        Returns (ops, window start)."""
        annotate = annotate or (lambda kind: contextlib.nullcontext())
        barrier = threading.Barrier(self.clients + 1)
        outs: list[list] = [[] for _ in range(self.clients)]
        t_start = time.perf_counter() + 0.1
        t_end = t_start + seconds
        threads = [
            threading.Thread(target=self._client, name=f"client-{c}",
                             args=(c, t_start, t_end, outs[c], annotate,
                                   barrier),
                             daemon=True)
            for c in range(self.clients)
        ]
        for th in threads:
            th.start()
        barrier.wait()
        for th in threads:
            th.join()
        return [op for ops in outs for op in ops], t_start
