"""AOT compiles of the served path's kernels for a described v5e chip.

No chip is attached here: ``jax.experimental.topologies`` describes a
``v5e:2x2`` topology and each kernel is lowered and compiled for one of
its devices, so the TPU compiler (Mosaic) refuses here what it would
refuse on the chip — at no chip time. Nothing runs, so these say nothing
about results or speed.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library, and every
xdist worker imports this file. Keep these tests in this one file.
"""

import numpy as np
import pytest

K, R = 10, 4
SHARD_WORDS = (8 << 20) // 4  # 8 MiB per shard, the benchmark's batch


@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip can be written to the persistent
    # cache but never read back without one: keep the cache out of it.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


def _words(one_chip, rows: int, TW: int = SHARD_WORDS):
    import jax
    import jax.numpy as jnp

    return jax.ShapeDtypeStruct((rows, TW), jnp.uint32, sharding=one_chip)


def _bits_rows(M: np.ndarray) -> tuple:
    from noise_ec_tpu.gf.bitmatrix import expand_generator_bits
    from noise_ec_tpu.gf.field import GF256
    from noise_ec_tpu.ops.pallas_gf2mm import bits_to_rows

    return bits_to_rows(expand_generator_bits(GF256(), M))


def _generator() -> np.ndarray:
    from noise_ec_tpu.gf.field import GF256
    from noise_ec_tpu.matrix.generators import generator_matrix

    return generator_matrix(GF256(), K, K + R, "cauchy")


def _compile_fused(one_chip, M: np.ndarray) -> str:
    """HLO text of the first fused-plan candidate for ``M`` at 8 MiB per
    shard, compiled for the described chip."""
    import jax

    from noise_ec_tpu.ops.pallas_fused import (
        _build_planned_call,
        fused_plan_candidates,
    )

    r, k = M.shape
    bits_rows = _bits_rows(M)
    cands = fused_plan_candidates(SHARD_WORDS, 8, k, r, bits_rows)
    assert cands, "no fused candidate for the geometry"
    call, k_pad = _build_planned_call(bits_rows, k, r, SHARD_WORDS, 8,
                                      cands[0], False)
    return jax.jit(call).lower(_words(one_chip, k_pad)).compile().as_text()


def test_fused_rs10_4_encode_compiles(one_chip):
    text = _compile_fused(one_chip, _generator()[K:])
    assert "tpu_custom_call" in text


def test_fused_four_erasure_reconstruct_compiles(one_chip):
    from noise_ec_tpu.gf.field import GF256
    from noise_ec_tpu.matrix.linalg import reconstruction_matrix

    erased = [0, 3, 6, 9]
    present = [i for i in range(K + R) if i not in erased]
    Rm = reconstruction_matrix(GF256(), _generator(), present, erased)
    assert Rm.shape == (4, K)
    assert "tpu_custom_call" in _compile_fused(one_chip, Rm)


def test_three_kernel_lane_pipeline_compiles(one_chip):
    """The tier-2 fallback: lane pack -> sparse matmul -> lane unpack."""
    import jax

    from noise_ec_tpu.ops.pallas_gf2mm import gf2_matmul_pallas_sparse_rows
    from noise_ec_tpu.ops.pallas_pack import (
        pack_words_lanes,
        unpack_words_lanes,
    )

    bits_rows = _bits_rows(_generator()[K:])
    W8 = SHARD_WORDS // 64

    def pipeline(words):
        tiled = pack_words_lanes(words, 8, rows_budget=K)
        out = gf2_matmul_pallas_sparse_rows(
            bits_rows, tiled.reshape(K * 8, 8, W8)
        )
        return unpack_words_lanes(out.reshape(R, 8, 8, W8), rows_budget=K)

    text = jax.jit(pipeline).lower(_words(one_chip, K)).compile().as_text()
    assert text.count("tpu_custom_call") >= 3


def test_mxu_encode_compiles(one_chip):
    import jax
    import jax.numpy as jnp

    from noise_ec_tpu.gf.bitmatrix import expand_generator_bits
    from noise_ec_tpu.gf.field import GF256
    from noise_ec_tpu.ops.mxu_gf2 import MXU_TILE_WORDS, _mxu_encode_words_jit

    m2 = expand_generator_bits(GF256(), _generator()[K:]).astype(np.int8)
    m2_shape = jax.ShapeDtypeStruct(m2.shape, jnp.int8, sharding=one_chip)
    text = _mxu_encode_words_jit.lower(
        m2_shape, _words(one_chip, K), r=R, k=K,
        tile_words=MXU_TILE_WORDS, interpret=False,
    ).compile().as_text()
    assert "tpu_custom_call" in text
