"""Single-kernel fused encode: pack -> GF(2) matmul -> unpack, one launch.

The three-kernel words pipeline (ops/dispatch.py) round-trips both packed
operands through HBM: for RS(10,4) on D data bytes it moves D (pack read)
+ D (pack write) + D (matmul read) + 0.4D (matmul write) + 0.4D (unpack
read) + 0.4D (unpack write) = 4.2D of HBM traffic to produce 0.4D of
parity. This kernel keeps the packed planes in VMEM scratch and moves
exactly D + 0.4D: per grid step it

1. packs the (k, 8*m*TL) input slab with the lane-axis delta-swap
   (pallas_pack.lane_delta_swap — same bijection as the standalone
   kernels),
2. runs the geometry-baked XOR chains of the sparse matmul on the
   scratch-resident (k*m, 8, TL) plane tiles,
3. applies the inverse delta-swap (an involution) to the (r, m, 8, TL)
   parity planes and writes parity WORDS straight to the output block.

The layout contract is identical to the three-kernel path (the hot-path
unit tests compare both against the golden codec), so DeviceCodec can pick
whichever fits VMEM: the fused kernel needs in + out blocks (double-
buffered) plus both plane scratches resident at once, so very wide codes
fall back to the pipeline — and geometries past the whole-plane budgets
leave this module entirely for the block-panel K-tiled tier
(ops/pallas_gf2mm "panel tier", docs/design.md §14; dispatch.route_for
owns the decision). Reference hot loop: /root/reference/main.go:262.
"""

from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from noise_ec_tpu.ops.pallas_pack import (
    _ROUNDS,
    _ROUNDS16,
    _pack_lanes_kernel,
    _unpack_lanes_kernel,
    _use_pairwise,
    lane_delta_swap,
    transpose_windows,
)
from noise_ec_tpu.ops.xor_factor import eval_bits_rows

log = logging.getLogger("noise_ec_tpu.ops")

# 1 MiB tighter than pallas_gf2mm's VMEM_BUDGET_BYTES: the fused kernel
# additionally keeps delta-swap pack/unpack temporaries on the Mosaic stack,
# which the shared Paar-temp estimate does not cover. Calibration anchors
# (valid for WHOLE-PLANE kernels only — the panel tier counts its capped
# per-panel temps at full size instead, pallas_gf2mm
# PANEL_TEMP_ALIVE_FRACTION): GF(2^16) RS(10,4) at TL=512 OOMed at 17.97M
# scoped and must be REJECTED (accounted 14.44M > 13M); GF(2^8) RS(50,20)
# at TL=128 compiled and must be ACCEPTED (accounted 12.75M <= 13M).
_FUSED_VMEM_BUDGET = 13 << 20


def fused_lane_tl(TW: int, m: int, k: int, r: int, bits_rows: tuple) -> int:
    """Largest TL in {512, 256, 128} whose fused working set fits VMEM
    WITH the fully-factored network (no temp cap).

    Conservative by design: callers that guard on this (parallel/batch.py
    tier selection) do not run the compile probe, and temp-capped plans
    are exactly the ones whose real Mosaic stack usage the static model
    cannot predict — those are only reachable through the verified
    planner (fused_encode_words_planned). Raises ValueError when no
    uncapped tile fits.
    """
    from noise_ec_tpu.ops.pallas_gf2mm import xor_temp_bytes_per_lane

    W8 = TW // (8 * m)
    per_lane = 4 * 8 * m * (2 * k + 2 * r + k + r) + xor_temp_bytes_per_lane(
        bits_rows, k * m
    )
    for TL in (512, 256, 128):
        if W8 % TL == 0 and per_lane * TL <= _FUSED_VMEM_BUDGET:
            return TL
    raise ValueError(
        f"no uncapped fused tile for TW={TW}, m={m}, k={k}, r={r}"
    )


# A temp cap is accepted only while the refactored network stays within
# this factor of the fully-factored XOR cost — beyond it, the extra VPU
# work outweighs the larger lane tile it buys.
_CAP_COST_RATIO = 1.25


def single_fused_plan(TW: int, m: int, k: int, r: int,
                      bits_rows: tuple) -> tuple:
    """(TL, temp_cap) for the single-phase fused kernel.

    For each candidate TL (largest first), the Paar temporaries either fit
    outright (temp_cap = None) or are re-factored under the cap the VMEM
    headroom allows — accepted when the capped network costs at most
    _CAP_COST_RATIO of the full factoring (GF(2^16) RS(10,4): cap 400
    costs +9% XORs but lifts TL 256 -> 512). Raises ValueError when no
    tile fits.
    """
    from noise_ec_tpu.ops.pallas_gf2mm import (
        TEMP_ALIVE_FRACTION,
        xor_temp_bytes_per_lane,
    )
    from noise_ec_tpu.ops.xor_factor import factored_cost, paar_factor

    W8 = TW // (8 * m)
    blocks_per_lane = 4 * 8 * m * (2 * k + 2 * r + k + r)
    temps_full = xor_temp_bytes_per_lane(bits_rows, k * m)
    bytes_per_temp = 8 * 4 * TEMP_ALIVE_FRACTION
    # Pass 1 — any UNCAPPED tile, largest first: an uncapped smaller tile
    # beats a capped larger one here, because this planner's callers
    # (fused_encode_words, via parallel/batch.py) compile WITHOUT the
    # probe, and capped plans are exactly the ones whose real Mosaic
    # stack usage the static model cannot predict. The probing planner
    # (fused_plan_candidates) makes its own capped-vs-uncapped ordering.
    for TL in (512, 256, 128):
        if W8 % TL:
            continue
        headroom = _FUSED_VMEM_BUDGET // TL - blocks_per_lane
        if headroom >= temps_full:
            return (TL, None)
    # Pass 2 — capped fallback (last resort; only reached when nothing
    # fits uncapped at any tile).
    full_cost = None
    for TL in (512, 256, 128):
        if W8 % TL:
            continue
        headroom = _FUSED_VMEM_BUDGET // TL - blocks_per_lane
        cap = int(headroom // bytes_per_temp) if headroom > 0 else 0
        if cap < 1:
            continue
        if full_cost is None:
            ops, rows = paar_factor(bits_rows, k * m)
            full_cost = factored_cost(ops, rows)
        ops_c, rows_c = paar_factor(bits_rows, k * m, max_temps=cap)
        if factored_cost(ops_c, rows_c) <= _CAP_COST_RATIO * full_cost:
            return (TL, cap)
    raise ValueError(
        f"no fused tile for TW={TW}, m={m}, k={k}, r={r} "
        f"(need TW % {1024 * m} == 0 and a tile within VMEM)"
    )


def _fused_kernel(m, TL, rounds, bits_rows, temp_cap, in_ref, out_ref,
                  pk_ref, po_ref):
    k = in_ref.shape[0]
    # 1. pack into VMEM scratch — the standalone lane-pack kernel body,
    # pointed at the scratch ref instead of an HBM-backed output block.
    _pack_lanes_kernel(m, TL, rounds, in_ref, pk_ref)
    # 2. geometry-baked sparse GF(2) matmul on (8, TL) plane tiles, with
    # Paar common-subexpression factoring (~2-3x fewer XORs), optionally
    # temp-capped to fit a larger lane tile (single_fused_plan).
    outs = eval_bits_rows(
        bits_rows, k * m,
        lambda c: pk_ref[c // m, c % m, :, :],
        lambda: jnp.zeros((8, TL), dtype=jnp.uint32),
        max_temps=temp_cap if temp_cap is not None else 100_000,
    )
    for row, val in enumerate(outs):
        po_ref[row // m, row % m, :, :] = val
    # 3. unpack scratch parity planes -> output words (same sharing).
    _unpack_lanes_kernel(m, TL, rounds, po_ref, out_ref)


@functools.lru_cache(maxsize=512)
def _fused_call(bits_rows: tuple, k: int, r: int, TW: int, m: int,
                interpret: bool):
    TL, temp_cap = single_fused_plan(TW, m, k, r, bits_rows)
    rounds = _ROUNDS if m == 8 else _ROUNDS16
    return pl.pallas_call(
        functools.partial(_fused_kernel, m, TL, rounds, bits_rows, temp_cap),
        grid=(TW // (8 * m * TL),),
        in_specs=[
            pl.BlockSpec((k, 8 * m * TL), lambda c: (0, c),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((r, 8 * m * TL), lambda c: (0, c),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((r, TW), jnp.uint32),
        scratch_shapes=[
            pltpu.VMEM((k, m, 8, TL), jnp.uint32),
            pltpu.VMEM((r, m, 8, TL), jnp.uint32),
        ],
        interpret=interpret,
    )


def fused_encode_words(
    bits_rows: tuple,  # STATIC (r*m)-row term tuples over k*m plane rows
    words: jnp.ndarray,  # (k, TW) uint32
    r: int,
    m: int = 8,
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    """(k, TW) uint32 data words -> (r, TW) uint32 parity words, one launch.

    TW must be a multiple of ``lane_quantum(m)`` = 1024*m (callers pad).
    Raises ValueError when no tile fits VMEM — callers fall back to the
    three-kernel pipeline.
    """
    k, TW = words.shape
    return _fused_call(bits_rows, k, r, TW, m, interpret)(words)


# ---------------------------------------------------------------------------
# Split-phase fused encode: wide codes at full lane tiles.
#
# The single-launch fused kernel's VMEM working set scales with k (input
# block + packed scratch) AND with the Paar network's temporaries, so wide
# codes (RS(50,20): 400 input planes, ~3.8k temps) are forced down to
# TL=128 — below the TL>=256 bracket where the pairwise delta-swap
# transpose (2.8x fewer vector ops than the full-slab form) applies, and
# the kernel runs ~VPU-bound at half the flagship rate.
#
# The split formulation processes the input in P contiguous K-SLICES:
# phase p packs only its slice into a slice-sized scratch and evaluates
# only the sub-network over that slice's plane columns, XOR-accumulating
# into the parity-plane scratch; the last phase applies the inverse
# transpose and writes parity words. A Pallas-pipelined (lanes x phases)
# grid version re-fetched the revisited input block from HBM every phase
# step (measured: throughput ~ 1/P) and was removed; the kernel below
# keeps the input in HBM (memory_space=ANY) and hand-rolls the slice DMA
# with double buffering, so input bytes move exactly once.
#
# Reference hot loop: /root/reference/main.go:262 (contract accepts any
# k <= n <= 256, so wide geometries are first-class).


def split_bits_rows_ksl(bits_rows: tuple, k: int, m: int, ksl: int) -> tuple:
    """Partition the (r*m)-row network into ceil(k/ksl) sub-networks by
    contiguous ksl-row input slices; sub-network p's column ids are
    re-indexed to its local [0, ksl*m) plane range (a padded final slice
    simply has columns no term references)."""
    P = -(-k // ksl)
    out = []
    for p in range(P):
        lo, hi = p * ksl * m, min((p + 1) * ksl * m, k * m)
        out.append(
            tuple(
                tuple(c - lo for c in row if lo <= c < hi) for row in bits_rows
            )
        )
    return tuple(out)


def _pack_rows_kernel(m, TL, rounds, in_ref, out_ref, row_lo, rows):
    """_pack_lanes_kernel on a static row slice of the input block."""
    for sigma in range(8):
        if _use_pairwise(TL):
            ws = transpose_windows(
                [
                    in_ref[row_lo : row_lo + rows,
                           (sigma * m + i) * TL : (sigma * m + i + 1) * TL]
                    for i in range(m)
                ],
                rounds,
            )
        else:
            V = lane_delta_swap(
                in_ref[row_lo : row_lo + rows,
                       sigma * m * TL : (sigma + 1) * m * TL],
                TL, rounds,
            )
            ws = [V[:, i * TL : (i + 1) * TL] for i in range(m)]
        for i in range(m):
            out_ref[:rows, i, sigma, :] = ws[i]


# ---------------------------------------------------------------------------
# Manual-DMA split kernel: the production wide-code formulation.
#
# The Pallas-pipelined split kernel above re-fetches its (revisited) input
# block from HBM on EVERY phase step — measured: RS(10,4) P=2 drops from
# 421 to 299 GB/s and P=5 to 193, i.e. throughput ~ 1/P, the signature of
# P-fold input traffic. This variant keeps the input in HBM
# (memory_space=ANY) and hand-rolls the slice movement: one grid step per
# lane tile runs ALL phases, DMA-ing each phase's ceil(k/P)-row slice into
# a double-buffered VMEM scratch (phase p+1's copy overlaps phase p's
# pack + XOR network). Input bytes move exactly once; VMEM holds only two
# slices, one slice's packed planes, the parity planes, and one phase's
# Paar temporaries — which is what buys TL >= 256 (pairwise transpose)
# for codes whose single-phase working set forces TL=128.


def _dma_split_kernel(m, TL, rounds, nets, ksl,
                      in_ref, out_ref, buf_ref, pk_ref, po_ref, sems):
    # The input array is padded to P*ksl rows with ksl a multiple of 8:
    # Mosaic requires HBM row slices aligned to the (8, 128) tiling, and
    # full slices keep every DMA identical. Padded rows are zero and no
    # sub-network references their plane columns.
    P = len(nets)
    L = 8 * m * TL
    c = pl.program_id(0)

    def copy(ph, slot):
        return pltpu.make_async_copy(
            in_ref.at[pl.ds(ph * ksl, ksl), pl.ds(c * L, L)],
            buf_ref.at[slot],
            sems.at[slot],
        )

    copy(0, 0).start()
    for ph, net in enumerate(nets):
        slot = ph % 2
        copy(ph, slot).wait()
        if ph + 1 < P:
            copy(ph + 1, 1 - slot).start()
        _pack_rows_kernel(m, TL, rounds, buf_ref.at[slot], pk_ref, 0, ksl)
        outs = eval_bits_rows(
            net, ksl * m,
            lambda col: pk_ref[col // m, col % m, :, :],
            lambda: jnp.zeros((8, TL), dtype=jnp.uint32),
        )
        for row, val in enumerate(outs):
            if ph == 0:
                po_ref[row // m, row % m, :, :] = val
            else:
                po_ref[row // m, row % m, :, :] ^= val
    _unpack_lanes_kernel(m, TL, rounds, po_ref, out_ref)


@functools.lru_cache(maxsize=512)
def _dma_split_call(nets: tuple, r: int, TW: int, m: int, ksl: int,
                    TL: int, interpret: bool):
    P = len(nets)
    rounds = _ROUNDS if m == 8 else _ROUNDS16
    return pl.pallas_call(
        functools.partial(_dma_split_kernel, m, TL, rounds, nets, ksl),
        grid=(TW // (8 * m * TL),),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],  # stays in HBM
        out_specs=pl.BlockSpec((r, 8 * m * TL), lambda c: (0, c),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((r, TW), jnp.uint32),
        scratch_shapes=[
            pltpu.VMEM((2, ksl, 8 * m * TL), jnp.uint32),  # slice buffers
            pltpu.VMEM((ksl, m, 8, TL), jnp.uint32),
            pltpu.VMEM((r, m, 8, TL), jnp.uint32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=interpret,
    )


# ---------------------------------------------------------------------------
# Verified planning: candidates ordered by estimated cost, compile-probed.
#
# The static VMEM models above are PRE-FILTERS, not guarantees: Mosaic's
# stack allocator overlaps XOR-network temporaries by a geometry-dependent
# fraction (measured 0.4 for RS(10,4)'s 193 temps, ~0.9 for capped
# GF(2^16) networks), so a plan that fits the model can still OOM the 16M
# scoped-vmem limit at compile time — and the model must stay conservative
# enough that it rejects plans a different geometry would have run fine.
# Rather than tightening the model until every geometry loses headroom,
# the planner AOT-compiles one lane tile of each candidate (cheap, cached
# per geometry — VMEM usage is TW-independent) and picks the first that
# actually compiles.


def _pack_w(TL: int) -> int:
    # VPU bytes per packed word: pairwise delta-swap (7.5 ops x 4 B) at
    # TL >= 256, full-slab rolls (~21 ops x 4 B) at TL = 128.
    return 30 if TL >= 256 else 84


_PROBE_BUDGET = 15_750_000  # loose pre-filter; the probe is the real gate
# Calibrations shared by the candidate scan (single source of truth):
# - split-kernel temporaries don't overlap across traced phase bodies the
#   way the single-phase calibration assumes (observed: RS(50,20) P=5
#   hit 16.25M scoped vs 12.76M accounted) -> scale the shared estimate.
# - scan-time estimates for sub-network factoring yield and temp count
#   (conservative fits to measured matrices: RS(50,20) 0.32/0.12,
#   GF(2^16) RS(10,4) 0.34/0.13).
_SPLIT_TEMP_SCALE = 2.5
_FACTOR_RATIO = 0.35
_TEMP_RATIO = 0.15


def _temp_bytes_per_op() -> float:
    from noise_ec_tpu.ops.pallas_gf2mm import TEMP_ALIVE_FRACTION

    return 8 * 4 * TEMP_ALIVE_FRACTION


def fused_plan_candidates(TW: int, m: int, k: int, r: int,
                          bits_rows: tuple) -> list:
    """Ordered candidate plans: ("single", TL, cap) and ("dma", TL, ksl).

    Scored by estimated VPU bytes per input byte (XOR network + transpose
    work, including split accumulates and row padding); ascending score =
    descending predicted throughput.
    """
    from noise_ec_tpu.ops.pallas_gf2mm import xor_temp_bytes_per_lane
    from noise_ec_tpu.ops.xor_factor import (
        factored_cost,
        paar_factor,
        xor_cost,
    )

    W8 = TW // (8 * m)
    out = []
    blocks_single = 4 * 8 * m * (2 * k + 2 * r + k + r)
    temps_full = xor_temp_bytes_per_lane(bits_rows, k * m)
    ops_f, rows_f = paar_factor(bits_rows, k * m)
    full_cost = factored_cost(ops_f, rows_f)
    # Mild preference for wider lane tiles beyond what the op counts
    # capture (fewer grid steps, better vectorization; RS(10,4) measured
    # +16% at 512 vs 256).
    tl_factor = {512: 1.0, 256: 1.08, 128: 1.15}

    def single_score(TL, cost):
        return tl_factor[TL] * (32 * cost + _pack_w(TL) * 8 * m * (k + r))

    for TL in (512, 256, 128):
        if W8 % TL:
            continue
        headroom = _PROBE_BUDGET // TL - blocks_single
        if headroom >= temps_full:
            out.append((single_score(TL, full_cost), ("single", TL, 0)))
        # Capped variants whenever the STRICT model would demand a cap at
        # this TL — emitted alongside the uncapped candidate (the probe
        # decides which actually compiles), at the model cap and a
        # tighter 0.6x fallback for geometries whose temporaries Mosaic
        # overlaps poorly.
        strict_headroom = _FUSED_VMEM_BUDGET // TL - blocks_single
        if strict_headroom > 0 and temps_full > strict_headroom:
            cap_model = int(strict_headroom // _temp_bytes_per_op())
            for cap in (cap_model, max(1, int(cap_model * 0.6))):
                if cap < 1 or cap * _temp_bytes_per_op() >= temps_full:
                    continue
                ops_c, rows_c = paar_factor(bits_rows, k * m, max_temps=cap)
                cost_c = factored_cost(ops_c, rows_c)
                if cost_c <= _CAP_COST_RATIO * full_cost:
                    out.append((single_score(TL, cost_c), ("single", TL, cap)))
    # DMA-split candidates (ksl multiple of 8 — Mosaic HBM row slices must
    # align to the (8, 128) tiling; the runner zero-pads the input rows).
    max_ksl = -(-k // 8) * 8
    for TL in (512, 256):
        if W8 % TL:
            continue
        for ksl in range(8, max_ksl + 1, 8):
            P = -(-k // ksl)
            if P < 2:
                continue
            nets = split_bits_rows_ksl(bits_rows, k, m, ksl)
            raw_max = max(xor_cost(net) for net in nets)
            est_temps = raw_max * _TEMP_RATIO * _temp_bytes_per_op()
            per_lane_est = (
                4 * 8 * m * (3 * ksl + 3 * r)
                + est_temps * _SPLIT_TEMP_SCALE
            )
            if per_lane_est * TL > _PROBE_BUDGET:
                continue
            sumf_est = sum(xor_cost(net) for net in nets) * _FACTOR_RATIO
            # 1.5x: measured overhead of the manual-DMA formulation beyond
            # the op counts (per-phase parity-plane accumulate traffic,
            # first-phase DMA bubbles, slice-pad pack) — RS(50,20) measured
            # 178.8 GB/s dma(TL=256) vs 243.6 single(TL=128) on v5e, so
            # the score must not prefer dma on op counts alone.
            score = 1.5 * tl_factor[TL] * (
                32 * (sumf_est + (P - 1) * r * m)
                + _pack_w(TL) * 8 * m * (P * ksl + r)
            )
            out.append((score, ("dma", TL, ksl)))
    out.sort(key=lambda t: t[0])
    # Bound probe work: a handful of best candidates is always enough.
    return [cand for _, cand in out[:8]]


def _build_planned_call(bits_rows: tuple, k: int, r: int, TW: int, m: int,
                        cand: tuple, interpret: bool):
    """(callable, padded_k) for a candidate plan at the given TW."""
    kind, TL = cand[0], cand[1]
    if kind == "single":
        cap = cand[2] or None
        rounds = _ROUNDS if m == 8 else _ROUNDS16
        call = pl.pallas_call(
            functools.partial(_fused_kernel, m, TL, rounds, bits_rows, cap),
            grid=(TW // (8 * m * TL),),
            in_specs=[
                pl.BlockSpec((k, 8 * m * TL), lambda c: (0, c),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((r, 8 * m * TL), lambda c: (0, c),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((r, TW), jnp.uint32),
            scratch_shapes=[
                pltpu.VMEM((k, m, 8, TL), jnp.uint32),
                pltpu.VMEM((r, m, 8, TL), jnp.uint32),
            ],
            interpret=interpret,
        )
        return call, k
    ksl = cand[2]
    nets = split_bits_rows_ksl(bits_rows, k, m, ksl)
    return _dma_split_call(nets, r, TW, m, ksl, TL, interpret), len(nets) * ksl


@functools.lru_cache(maxsize=1024)
def _probe_compiles(bits_rows: tuple, k: int, r: int, m: int,
                    cand: tuple) -> bool:
    """AOT-compile TWO lane tiles of the candidate; True iff it compiles.

    Past two tiles VMEM pressure is independent of the grid length (the
    pipeline double-buffers at grid >= 2 — a ONE-tile probe skips the
    second buffer and falsely passed plans that OOM on real grids), so a
    two-tile probe validates any TW with the same TL.
    """
    TW = 2 * 8 * m * cand[1]
    try:
        call, k_pad = _build_planned_call(bits_rows, k, r, TW, m, cand, False)
        shape = jax.ShapeDtypeStruct((k_pad, TW), jnp.uint32)
        jax.jit(call).lower(shape).compile()
        return True
    except Exception as exc:  # noqa: BLE001 — any compile failure disqualifies
        # Logged, not silent: an API break would otherwise demote every
        # fused encode to the three-kernel or sublane tier unnoticed.
        log.warning("fused plan %s (k=%d, r=%d, m=%d) failed to compile: "
                    "%s: %s", cand, k, r, m, type(exc).__name__, exc)
        return False


@functools.lru_cache(maxsize=512)
def verified_fused_plan(bits_rows: tuple, k: int, r: int, TW: int, m: int,
                        interpret: bool):
    """Best candidate that actually compiles, or None.

    Interpret mode (CPU tests) has no scoped-vmem limit: the first
    candidate wins without probing.
    """
    cands = fused_plan_candidates(TW, m, k, r, bits_rows)
    if interpret:
        return cands[0] if cands else None
    for cand in cands:
        if _probe_compiles(bits_rows, k, r, m, cand):
            return cand
    return None


class NoFusedPlanError(ValueError):
    """No fused-kernel candidate compiles for this geometry — the caller
    should fall back to the three-kernel pipeline. A distinct type so the
    dispatch fallback cannot swallow a genuine ValueError raised while
    building or running a chosen kernel (that is a bug and must surface)."""


def fused_encode_words_planned(
    bits_rows: tuple,
    words: jnp.ndarray,  # (k, TW) uint32
    r: int,
    m: int = 8,
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    """Fused encode through the verified planner (single or DMA-split).

    Raises :class:`NoFusedPlanError` when no candidate compiles — callers
    fall back to the three-kernel pipeline.
    """
    k, TW = words.shape
    cand = verified_fused_plan(bits_rows, k, r, TW, m, interpret)
    if cand is None:
        raise NoFusedPlanError(
            f"no fused plan compiles for k={k}, r={r}, m={m}"
        )
    call, k_pad = _build_planned_call(bits_rows, k, r, TW, m, cand, interpret)
    if k_pad != k:
        words = jnp.pad(words, ((0, k_pad - k), (0, 0)))
    return call(words)
