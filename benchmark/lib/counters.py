"""The program's own spans and counters, read as deltas over the window.

Sources (the program's default registry, ``noise_ec_tpu.obs.registry``):
- ``noise_ec_stage_seconds{stage}``: one observation per finished span,
  request-traced or not, so its sums are exact;
- ``noise_ec_device_op_seconds{kernel,route}``: one observation per
  device dispatch, timed by the host around the round trip."""

from __future__ import annotations

from collections import defaultdict


def _histogram(name: str) -> dict:
    from noise_ec_tpu.obs.registry import default_registry

    fam = default_registry().histogram(name)
    return {labels: (child.sum, child.count) for labels, child in
            fam.children()}


def snapshot() -> dict:
    return {
        "stage": _histogram("noise_ec_stage_seconds"),
        "device_op": _histogram("noise_ec_device_op_seconds"),
    }


class Delta:
    """``after - before`` of two snapshots."""

    def __init__(self, before: dict, after: dict):
        self.stage_s: dict = defaultdict(float)
        for labels, (s, _) in after["stage"].items():
            self.stage_s[labels[0]] += s - before["stage"].get(
                labels, (0.0, 0))[0]
        self.device_op_s = 0.0
        self.device_op_n = 0
        self.device_op_by_route: dict = defaultdict(int)
        for labels, (s, n) in after["device_op"].items():
            s0, n0 = before["device_op"].get(labels, (0.0, 0))
            self.device_op_s += s - s0
            self.device_op_n += n - n0
            self.device_op_by_route[labels[1]] += n - n0

    def stage_seconds(self, *stages: str) -> float:
        return sum(self.stage_s.get(s, 0.0) for s in stages)
