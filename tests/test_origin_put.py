"""The origin's copy of a PUT stripe: ``ShardPlugin.shard_and_broadcast``
stores the shares it encoded for the wire through
``StripeStore.put_encoded`` instead of encoding the object a second time
(docs/store.md "put_object"). The stored stripe is byte-identical to
``put_object``'s; receives still encode their own; the store entry checks
shape and never encodes."""

import numpy as np
import pytest

from noise_ec_tpu.host.plugin import ShardPlugin
from noise_ec_tpu.host.transport import (
    LoopbackHub,
    LoopbackNetwork,
    format_address,
)
from noise_ec_tpu.obs.registry import default_registry
from noise_ec_tpu.service import ObjectStore
from noise_ec_tpu.store import RepairEngine, Scrubber, StripeStore

# MinIO's shard for 1 MiB blocks over 12 data drives: ceil(2**20 / 12).
SHARD_LEN = 87_382


def _puts(encode: str) -> float:
    return default_registry().counter("noise_ec_store_puts_total").labels(
        encode=encode
    ).value


def _dispatches() -> int:
    hist = default_registry().histogram("noise_ec_device_op_seconds")
    return sum(child.count for _, child in hist.children())


def _node(hub, port: int, backend: str):
    net = LoopbackNetwork(hub, format_address("tcp", "localhost", port))
    store = StripeStore(backend=backend)
    plugin = ShardPlugin(backend=backend, store=store)
    net.add_plugin(plugin)
    return net, plugin, store


# ------------------------------------------------------ byte identity


@pytest.mark.parametrize("backend", ["numpy", "device"])
@pytest.mark.parametrize("k,n", [(4, 6), (10, 14), (12, 16)])
def test_origin_stripe_is_put_objects_stripe(rng, k, n, backend):
    """The origin's stored stripe equals put_object's encode of the same
    payload shard for shard; it scrubs clean and reads back with n - k
    shards gone."""
    net, plugin, store = _node(LoopbackHub(), 4700, backend)
    payload = bytes(rng.integers(0, 256, size=k * SHARD_LEN, dtype=np.uint8))
    reused0, computed0 = _puts("reused"), _puts("computed")
    shards = plugin.shard_and_broadcast(net, payload, geometry=(k, n))
    key = store.keys()[0]
    meta, stored, unverified = store.snapshot(key)
    assert (_puts("reused") - reused0, _puts("computed") - computed0) == (1, 0)
    assert all(s is shard.shard_data for s, shard in zip(stored, shards))
    assert unverified == set()

    plain = StripeStore(backend=backend)
    want_key = plain.put_object(shards[0].file_signature, payload, k, n)
    want_meta, want, _ = plain.snapshot(want_key)
    assert key == want_key
    assert (meta.k, meta.n, meta.shard_len, meta.object_len) == (
        want_meta.k, want_meta.n, want_meta.shard_len, want_meta.object_len
    ) == (k, n, SHARD_LEN, len(payload))
    for slot in range(n):
        assert stored[slot] == want[slot], slot

    stats = Scrubber(store, RepairEngine(store)).run_cycle()
    assert stats == {"scrubbed": 1, "flagged_missing": 0, "flagged_corrupt": 0}
    for slot in range(n - k):  # data slots: the read must reconstruct
        assert store.drop_shard(key, slot)
    assert store.read(key) == payload


# ------------------------------------------------- the loopback pair


def test_object_put_encodes_each_stripe_twice_across_the_pair(rng):
    """One multi-stripe ObjectStore.put from a to b: each stripe (data
    and manifest) costs the pair two device dispatches — a's wire encode
    and b's encode of the verified object — and the store puts split
    evenly between reused (a) and computed (b). a still indexes the
    manifest through its put listener."""
    k, n, stripe_bytes = 4, 6, 4096
    hub = LoopbackHub()
    services = {}
    for name, port in (("a", 4710), ("b", 4711)):
        net, plugin, store = _node(hub, port, "device")
        services[name] = ObjectStore(
            store, plugin, net, k=k, n=n, stripe_bytes=stripe_bytes
        )
    a, b = services["a"], services["b"]
    payload = bytes(rng.integers(0, 256, size=3 * stripe_bytes + 100,
                                 dtype=np.uint8))
    dispatches0 = _dispatches()
    reused0, computed0 = _puts("reused"), _puts("computed")
    doc = a.put("acme", "blob.bin", payload)
    stripes = len(doc["stripes"]) + 1  # the data stripes and the manifest
    assert len(doc["stripes"]) == 4
    assert _dispatches() - dispatches0 == 2 * stripes
    assert _puts("reused") - reused0 == stripes
    assert _puts("computed") - computed0 == stripes

    assert a.store.get_manifest(doc["address"])["stripes"] == doc["stripes"]
    assert b.store.get_manifest(doc["address"])["stripes"] == doc["stripes"]
    assert a.read("acme", "blob.bin") == payload
    for key in doc["stripes"]:
        assert a.store.snapshot(key)[1] == b.store.snapshot(key)[1]


# ------------------------------------------------ the entry's checks


def _encoded(rng, k: int, n: int, shard_len: int, code: str = "rs"):
    rs = StripeStore().codec(k, n, code=code)
    data = bytes(rng.integers(0, 256, size=k * (shard_len - 1) + 1,
                              dtype=np.uint8))
    shards = [
        np.ascontiguousarray(s).tobytes() for s in rs.encode(rs.split(data))
    ]
    return data, shards


class _NoCodec(StripeStore):
    def codec(self, *args, **kwargs):
        raise AssertionError("put_encoded must not encode")


@pytest.mark.parametrize(
    "fault,match",
    [
        ("shard_count", "expected 6 shards"),
        ("unequal_lengths", "one non-zero length"),
        ("empty_shards", "one non-zero length"),
        ("data_over_capacity", "outside"),
        ("empty_data", "outside"),
        ("geometry", "invalid geometry"),
        ("code", "unknown codec code"),
    ],
)
def test_put_encoded_rejects_bad_shapes(rng, fault, match):
    store = _NoCodec()
    data, shards = _encoded(rng, 4, 6, 64)
    k, kw = 4, {}
    if fault == "shard_count":
        shards = shards[:-1]
    elif fault == "unequal_lengths":
        shards[3] = shards[3][:-1]
    elif fault == "empty_shards":
        shards = [b""] * 6
    elif fault == "data_over_capacity":
        data = bytes(4 * 64 + 1)
    elif fault == "empty_data":
        data = b""
    elif fault == "geometry":
        k = 7
    elif fault == "code":
        kw = {"code": "xor"}
    with pytest.raises(ValueError, match=match):
        store.put_encoded(b"\x01" * 64, data, shards, k, 6, **kw)
    assert len(store) == 0


@pytest.mark.parametrize("code,n", [("rs", 6), ("lrc:2", 8)])
def test_put_encoded_keeps_the_shards_and_never_encodes(rng, code, n):
    """The stripe holds the very bytes objects it was given, under the
    code it was given; a store whose codec raises if called serves the
    object, and the listeners see it."""
    store = _NoCodec()
    data, shards = _encoded(rng, 4, n, 64, code)
    seen = []
    store.add_put_listener(lambda key, blob, meta: seen.append(blob))
    reused0, computed0 = _puts("reused"), _puts("computed")
    key = store.put_encoded(b"\x02" * 64, data, shards, 4, n, code=code)
    meta, stored, unverified = store.snapshot(key)
    assert all(s is given for s, given in zip(stored, shards))
    assert unverified == set()
    assert (meta.n, meta.code, meta.shard_len, meta.object_len) == (
        n, code, 64, len(data)
    )
    assert seen == [data]
    assert store.read(key) == data  # a join: no codec needed
    assert (_puts("reused") - reused0, _puts("computed") - computed0) == (1, 0)
    with pytest.raises(AssertionError, match="must not encode"):
        store.put_object(b"\x03" * 64, data, 4, n, code=code)
