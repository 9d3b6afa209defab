"""Arithmetic the metric readers share (``benchmark/metrics/*.py``). Each
returns None where it finds nothing to read; a reader never reports 0 in
place of a reading it could not make."""

from __future__ import annotations

import math

from . import work


def rate(ctx, amount: float):
    """``amount`` over the window: its start to the last completion of an
    operation started in it."""
    if ctx.elapsed_s <= 0 or amount <= 0:
        return None
    return amount / ctx.elapsed_s


def mb_per_s(ctx, *kinds: str):
    moved = sum(op.nbytes for op in ctx.ok(*kinds))
    value = rate(ctx, moved)
    return None if value is None else value / 1e6


def percentile_ms(ctx, kind: str, q: float):
    """Nearest-rank percentile of every ``kind`` operation's latency; one
    that failed counts as slower than any that completed."""
    lat = sorted(op.t1 - op.t0 if op.ok else math.inf
                 for op in ctx.ops if op.kind == kind)
    if not lat:
        return None
    value = lat[max(0, math.ceil(q * len(lat)) - 1)]
    return None if math.isinf(value) else value * 1e3


def span_s_per_gb(ctx, stages: tuple, *kinds: str):
    gb = ctx.gb(*kinds)
    if gb <= 0:
        return None
    return ctx.delta.stage_seconds(*stages) / gb


def device_op_s_per_gb(ctx, *kinds: str):
    gb = ctx.gb(*kinds)
    if gb <= 0 or not ctx.delta.device_op_n:
        return None
    return ctx.delta.device_op_s / gb


def idle_pct(ctx):
    t = ctx.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)


def roofline_pct(ctx, needed_bytes: int):
    """Needed HBM bytes at the chip's peak bandwidth over kernel time."""
    t = ctx.trace
    bw = ctx.peaks.get("hbm_bytes_per_s")
    if t is None or not bw or t.kernel_s <= 0 or needed_bytes <= 0:
        return None
    return 100.0 * needed_bytes / bw / t.kernel_s


def put_bytes_needed(ctx) -> int:
    cfg = ctx.config
    k, n = int(cfg["k"]), int(cfg["n"])
    stripe = int(cfg["stripe_bytes"])
    return work.put_bytes_needed([op.nbytes for op in ctx.ok("put")],
                                 max(k, stripe - stripe % k), k, n)


def degraded_read_bytes_needed(ctx) -> int:
    k = int(ctx.config["k"])
    lost = ctx.lost
    erased = max((sum(1 for s in slots if s < k)
                  for slots in lost.values()), default=0)
    if not erased:
        return 0
    reads = [((op.name, op.stripe), op.t0, op.t1, op.length)
             for op in ctx.ok("read_stripe")]
    return work.degraded_read_bytes_needed(reads, k, erased)
