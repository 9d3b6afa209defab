"""GF(2) generator matmul on the MXU: int8 bit-planes, mod-2 accumulators.

The fused VPU kernels (ops/pallas_fused.py) compute the bitsliced encode as
a Paar-factored XOR network on u32 lanes; for wide codes the XOR count is
the wall (RS(50,20): ~10.1k XORs — BASELINE.md config 3). This module is
the alternative formulation VERDICT r3 asked to measure before conceding
that bound: treat the (8r, 8k) GF(2) generator bit-matrix as an int8
operand, the data bits as an int8 (8k, S) matrix of 0/1, and run the whole
product on the 128x128 systolic array —

    acc (8r, S) = M2 (8r, 8k) @ bits (8k, S)   in int8 x int8 -> int32
    parity_bit  = acc & 1                       (popcount parity == mod 2)

Everything (u32 -> byte -> bit unpack, the dot, bit -> byte -> u32 repack)
lives inside ONE Pallas kernel so the 8x bit-plane blowup and the 32-bit
accumulators never touch HBM: per grid step the kernel reads a (k, TWt)
u32 block and writes the (r, TWt) parity block, HBM traffic identical to
the VPU kernels. Arithmetic cost is fixed at 64*r*k MACs per data byte —
on a v5e (394 INT8 TOPS) the roofline for RS(50,20) is ~308 GB/s, which is
why this only makes sense for wide codes; RS(10,4)'s XOR network is far
below its MXU MAC count.

Reference contract: the same encode hot loop as ops/pallas_fused.py
(/root/reference/main.go:262 via infectious Encode).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from noise_ec_tpu.gf.bitmatrix import expand_generator_bits
from noise_ec_tpu.gf.field import GF

# Lane-tile width in u32 words per grid step. 512 words = 2048 byte
# columns; the in-kernel int8 bit matrix is (8k, 2048) = 16k * k bytes —
# ~800 KiB at k=50, comfortably VMEM-resident beside the i32 accumulator.
MXU_TILE_WORDS = 512


def _mxu_kernel(r: int, k: int, kernel_tw: int, m2_ref, w_ref, o_ref):
    # Mosaic cannot reshape across the minor (lane) dim, so the u32 words
    # are never byte-deinterleaved: all 32 bits unpack along a NEW sublane
    # axis (lane dim untouched), and the four byte lanes of each word run
    # as four MXU dots sharing one (8r, 8k) bit-matrix — bit i of byte
    # lane c is u32 bit 8c+i, so slice [8c:8c+8] of the bit axis is
    # exactly byte lane c's plane group.
    w = w_ref[...]  # (k, TWt) uint32
    bit32 = jnp.arange(32, dtype=jnp.uint32)
    bits = ((w[:, None, :] >> bit32[None, :, None]) & 1).astype(jnp.int8)
    m2 = m2_ref[...]
    out = None
    for c in range(4):
        xc = bits[:, 8 * c : 8 * c + 8, :].reshape(8 * k, kernel_tw)
        acc = jax.lax.dot_general(
            m2,
            xc,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )  # (8r, TWt) int32
        pbits = (acc & 1).astype(jnp.uint32).reshape(r, 8, kernel_tw)
        # OR-fold (shifted bits are disjoint; Mosaic has no unsigned
        # reductions) straight into the output u32: byte c bit bo is u32
        # bit 8c+bo.
        for bo in range(8):
            term = pbits[:, bo, :] << (8 * c + bo)
            out = term if out is None else out | term
    o_ref[...] = out


@functools.partial(
    jax.jit, static_argnames=("r", "k", "tile_words", "interpret")
)
def _mxu_encode_words_jit(m2, words, *, r, k, tile_words, interpret):
    from jax.experimental import pallas as pl

    kt = tile_words
    tw = words.shape[1]
    grid = (tw // kt,)
    return pl.pallas_call(
        functools.partial(_mxu_kernel, r, k, kt),
        grid=grid,
        in_specs=[
            pl.BlockSpec((8 * r, 8 * k), lambda i: (0, 0)),
            pl.BlockSpec((k, kt), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((r, kt), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((r, tw), jnp.uint32),
        interpret=interpret,
    )(m2, words)


def cached_bit_expansion(cache: dict, gf: GF, M: np.ndarray,
                         *, bound: int = 256):
    """Cached int8 GF(2) bit expansion of ``M`` with device promotion.

    One implementation for every MXU caller (MxuCodec and the dispatch
    wide-field route) so the cache-key scheme (full shape + bytes — the
    r4 collision fix), the size bound, and the tracer-leak guard cannot
    diverge: the promotion to a device-resident jnp array happens ONLY
    outside an active trace (jnp.asarray under tracing returns a tracer,
    and caching that leaks it into later calls).
    """
    M = np.ascontiguousarray(np.asarray(M, dtype=gf.dtype))
    key = (M.shape, M.tobytes())
    hit = cache.get(key)
    if hit is None:
        hit = expand_generator_bits(gf, M).astype(np.int8)
        if len(cache) > bound:
            cache.clear()
        cache[key] = hit
    if isinstance(hit, np.ndarray):
        dev = jnp.asarray(hit)
        if isinstance(dev, jax.core.Tracer):
            return dev  # under a trace: use it, never cache it
        hit = cache[key] = dev
    return hit


def mxu_encode_words_bits(
    m2: np.ndarray,
    words,
    *,
    r: int,
    k: int,
    interpret: bool = False,
):
    """Low-level MXU entry on a PRE-EXPANDED GF(2) bit matrix.

    ``m2``: (8r, 8k) 0/1 int8 bit matrix over k byte rows; ``words``:
    (k, TW) uint32 with TW a multiple of the chosen lane tile. The field
    is irrelevant here — the kernel is pure GF(2) — which is what lets
    the BYTE-SLICED GF(2^16) path run on the MXU: its expanded (16r, 16k)
    bit matrix over 2k byte rows IS an (8R, 8K) matrix with R = 2r,
    K = 2k (design.md: the flat plane index needs no permutation).
    The lane tile narrows for many-byte-row geometries so the in-kernel
    bit tensor (k * 32 * tile bytes) stays VMEM-resident.
    """
    tile = MXU_TILE_WORDS if k <= 256 else MXU_TILE_WORDS // 2
    words = jnp.asarray(words)
    if words.shape[1] % tile:
        raise ValueError(
            f"TW {words.shape[1]} not a multiple of tile {tile}"
        )
    if isinstance(m2, np.ndarray):
        # Callers that cache a device-resident operand pass it through
        # untouched; only host ndarrays get staged here.
        m2 = jnp.asarray(np.ascontiguousarray(m2, dtype=np.int8))
    return _mxu_encode_words_jit(
        m2,
        words,
        r=r,
        k=k,
        tile_words=tile,
        interpret=interpret,
    )


class MxuCodec:
    """Experimental MXU-route encoder over u32 word stripes.

    Same contract as DeviceCodec.matmul_words (parity rows only); kept
    separate so the verified planner can measure it against the XOR
    network per geometry instead of hardwiring either.
    """

    def __init__(self, gf: GF, tile_words: int = MXU_TILE_WORDS,
                 interpret: bool = False):
        if gf.degree != 8:
            raise ValueError("MXU route currently GF(2^8) only")
        self.gf = gf
        self.tile_words = tile_words
        self.interpret = interpret
        self._m2_cache: dict = {}

    def _m2_for(self, M: np.ndarray):
        return cached_bit_expansion(self._m2_cache, self.gf, M)

    def encode_words(self, M: np.ndarray, words) -> jnp.ndarray:
        """(r, k) GF matrix x (k, TW) u32 words -> (r, TW) parity words.

        TW must be a multiple of ``tile_words`` (callers pad, exactly as
        for the fused VPU kernels)."""
        r, k = np.asarray(M).shape
        words = jnp.asarray(words)
        if words.shape[0] != k:
            raise ValueError(f"matrix cols {k} != word rows {words.shape[0]}")
        if words.shape[1] % self.tile_words:
            raise ValueError(
                f"TW {words.shape[1]} not a multiple of tile {self.tile_words}"
            )
        return _mxu_encode_words_jit(
            self._m2_for(M),
            words,
            r=r,
            k=k,
            tile_words=self.tile_words,
            interpret=self.interpret,
        )

    def encode_stripes(self, M: np.ndarray, D: np.ndarray) -> np.ndarray:
        """Byte-stripe convenience wrapper (pads to the word tile)."""
        D = np.ascontiguousarray(np.asarray(D, dtype=np.uint8))
        r, k = np.asarray(M).shape
        S = D.shape[1]
        quantum = 4 * self.tile_words
        Sp = -(-S // quantum) * quantum
        if Sp != S:
            buf = np.zeros((k, Sp), dtype=np.uint8)
            buf[:, :S] = D
        else:
            buf = D
        out = np.array(self.encode_words(M, buf.view("<u4")))
        return out.view(np.uint8)[:, :S]
