"""The system under test: two in-process nodes on the loopback transport,
built as chip_smoke.py builds them (a copy, so the yardstick does not move
with the program). Node ``a`` is the gateway the clients call, node ``b``
the replica. Each node is ``ObjectStore`` -> ``ShardPlugin`` ->
``StripeStore`` with ``backend="device"``."""

from __future__ import annotations

import threading
from dataclasses import dataclass, field


@dataclass
class Node:
    name: str
    network: object
    plugin: object
    store: object
    service: object


@dataclass
class Cluster:
    nodes: dict
    # What node b's store held when it stored each stripe: key -> the
    # shard list of StripeStore.snapshot, taken by a put listener (a list
    # of references, no copy). The check reads a stripe that a later
    # overwrite evicted from here.
    stored_on_b: dict = field(default_factory=dict)
    lock: threading.Lock = field(default_factory=threading.Lock)

    def __getitem__(self, name: str) -> Node:
        return self.nodes[name]


def build(config: dict, ports: tuple = (3101, 3102)) -> Cluster:
    from noise_ec_tpu.host.plugin import ShardPlugin
    from noise_ec_tpu.host.transport import (
        LoopbackHub,
        LoopbackNetwork,
        format_address,
    )
    from noise_ec_tpu.service.objects import ObjectStore
    from noise_ec_tpu.store import StripeStore

    hub = LoopbackHub()
    nodes = {}
    for name, port in zip(("a", "b"), ports):
        net = LoopbackNetwork(hub, format_address("tcp", "localhost", port))
        store = StripeStore(backend="device")
        plugin = ShardPlugin(backend="device", store=store)
        net.add_plugin(plugin)
        service = ObjectStore(store, plugin, net, k=int(config["k"]),
                              n=int(config["n"]),
                              stripe_bytes=int(config["stripe_bytes"]))
        nodes[name] = Node(name, net, plugin, store, service)
    cluster = Cluster(nodes)
    b_store = nodes["b"].store

    def capture(key, data, meta) -> None:
        _, shards, _ = b_store.snapshot(key)
        with cluster.lock:
            cluster.stored_on_b[key] = shards

    b_store.add_put_listener(capture)
    return cluster
