"""Faults planted under a run, and the control. Each takes the built
cluster and ``patch(obj, attr, value)`` (pytest's monkeypatch.setattr, or
plain setattr in a process of its own) and breaks the timed path."""

from __future__ import annotations

import numpy as np

from lib import reference


def control(cluster, patch) -> None:
    """The plain reference put in the codec's place, with the PAR1 layout
    P[i][c] = (c+1)^i: not MDS, so it breaks the configurations' "any
    n-k shards may be lost" guarantee."""
    from noise_ec_tpu.codec.rs import ReedSolomon

    def encode(self, shards):
        data = [np.frombuffer(bytes(memoryview(s).cast("B")), np.uint8)
                for s in shards[:self.k]]
        return data + reference.encode(data, self.r,
                                       parity=reference.par1_parity)

    patch(ReedSolomon, "encode", encode)


def state_unchanged(cluster, patch) -> None:
    """Node b acknowledges a stripe and leaves its store unchanged."""
    from noise_ec_tpu.obs.trace import trace_key

    patch(cluster["b"].store, "put_object",
          lambda sig, data, k, n, **kw: trace_key(sig))


def half_batch(cluster, patch) -> None:
    """The second half of every batched product is left out (zeros); a
    single product loses the second half of its columns."""
    from noise_ec_tpu.ops.dispatch import DeviceCodec

    original = DeviceCodec.matmul_stripes_many

    def many(self, M, Ds):
        out = [np.array(o) for o in original(self, M, Ds)]
        if len(out) > 1:
            for i in range(len(out) // 2, len(out)):
                out[i][:] = 0
        else:
            out[0][:, out[0].shape[1] // 2:] = 0
        return out

    patch(DeviceCodec, "matmul_stripes_many", many)


def exchange_left_out(cluster, patch) -> None:
    """Node a's broadcasts never reach node b."""
    patch(cluster["a"].network.hub, "fan_out", lambda sender, wire: None)


def product_altered(cluster, patch) -> None:
    """One byte of every device product flipped where it is produced."""
    from noise_ec_tpu.ops.dispatch import DeviceCodec

    original = DeviceCodec.matmul_stripes_many

    def many(self, M, Ds):
        out = [np.array(o) for o in original(self, M, Ds)]
        for o in out:
            o.reshape(-1)[0] ^= 0x5A
        return out

    patch(DeviceCodec, "matmul_stripes_many", many)


def read_altered(cluster, patch) -> None:
    """One byte of every stripe a read serves flipped where it is served."""
    from noise_ec_tpu.service.objects import ObjectStore

    original = ObjectStore._read_stripe_tiered

    def tiered(self, *args, **kw):
        blob = bytearray(original(self, *args, **kw))
        blob[0] ^= 0x5A
        return bytes(blob)

    patch(ObjectStore, "_read_stripe_tiered", tiered)


def stored_altered(cluster, patch) -> None:
    """Node b stores every object with one byte flipped."""
    store = cluster["b"].store
    original = store.put_object

    def put_object(sig, data, k, n, **kw):
        data = bytearray(data)
        data[0] ^= 0x5A
        return original(sig, bytes(data), k, n, **kw)

    patch(store, "put_object", put_object)


def stat_altered(cluster, patch) -> None:
    """Node a answers every STAT with another address."""
    service = cluster["a"].service
    original = service.resolve

    def resolve(tenant, name):
        doc = dict(original(tenant, name))
        doc["address"] = doc["address"][::-1]
        return doc

    patch(service, "resolve", resolve)


def shed_all(cluster, patch) -> None:
    """Both nodes shed every request, as under device-memory pressure."""
    for node in cluster.nodes.values():
        patch(node.service, "shed_reason", lambda: "hbm")


FAULTS = {
    "state_unchanged": state_unchanged,
    "half_batch": half_batch,
    "exchange_left_out": exchange_left_out,
    "product_altered": product_altered,
    "stored_altered": stored_altered,
    "read_altered": read_altered,
    "stat_altered": stat_altered,
    "shed_all": shed_all,
}
NEEDS = {"read_altered": {"get", "read_stripe"}, "stat_altered": {"stat"}}


def faults_for(traffic: dict) -> list[str]:
    """The faults a cell can have: a read fault needs reads, a STAT fault
    STATs."""
    deck = set(traffic["deck"])
    return [f for f in FAULTS if f not in NEEDS or NEEDS[f] & deck]


def tiny(cell) -> dict:
    """Overrides that shrink a cell to a CPU test's size: 1 KiB shards,
    objects of 6 1/3 stripes, at most 6 clients and 8 loaded objects."""
    k = int(cell.config["k"])
    capacity = 1024 * k
    return {
        "config": {"stripe_bytes": capacity},
        "traffic": {
            "object_bytes": 6 * capacity + capacity // 3,
            "clients": min(6, int(cell.traffic["clients"])),
            "preload_objects": min(8, int(cell.traffic.get(
                "preload_objects", 0))),
        },
    }
