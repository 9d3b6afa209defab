"""Worker process for tests/test_multihost.py — NOT collected by pytest.

Joins a 2-process JAX distributed runtime over a localhost coordinator,
builds a global ("batch", "row") mesh whose ROW axis spans both processes,
encodes a words batch with the parity rows sharded across the hosts
(cross-host all-gather assembles the codeword), and checks the result
bit-exactly against the golden codec. Prints one MULTIHOST-OK line.
"""

import os
import sys

port, proc_id, nprocs = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=4"
    ).strip()

import jax  # noqa: E402

# Pin the CONFIG as well as the env var, exactly as tests/conftest.py
# does, before any backend initializes.
jax.config.update("jax_platforms", "cpu")

from noise_ec_tpu.parallel import multihost  # noqa: E402

multihost.initialize(f"127.0.0.1:{port}", nprocs, proc_id)

import numpy as np  # noqa: E402

assert jax.device_count() == 4 * nprocs, jax.device_count()

from noise_ec_tpu.golden.codec import GoldenCodec  # noqa: E402
from noise_ec_tpu.parallel.batch import BatchCodec  # noqa: E402

k, r = 10, 8  # r divisible by the 8-way row axis -> one parity row per device
bc = BatchCodec(k, r)
# Row axis size 8 over 2 processes x 4 devices: devices 0-3 live on process
# 0 and 4-7 on process 1, so parity rows 4-7 are computed on the OTHER host
# and the tiled all_gather that assembles them crosses the process boundary.
mesh = multihost.global_mesh(("batch", "row"), (1, 8))
enc = bc.make_sharded_encoder_words(mesh, row_axis="row")

rng = np.random.default_rng(0xD15)  # same seed on both hosts
B, TW = 2, 2560
words = rng.integers(0, 1 << 32, size=(B, k, TW), dtype=np.uint64).astype(np.uint32)
gwords = multihost.replicate_to_global(words, mesh)
parity = multihost.fetch_to_every_host(enc(gwords))

g = GoldenCodec(k, k + r)
for b in range(B):
    want = np.asarray(g.encode(np.ascontiguousarray(words[b]).view(np.uint8)))
    got = np.ascontiguousarray(parity[b]).view(np.uint8)
    np.testing.assert_array_equal(got, want)

# Decode side across the SAME cross-host mesh (round 4): the
# error-correcting decode's bad-column scan is one augmented
# [G_parity | I] matmul (matrix/bw.py); shard the received codewords over
# the global batch axis, corrupt one share of one object, and the nonzero
# syndrome must localize to it on every host.
data_u8 = np.stack(
    [np.ascontiguousarray(words[b]).view(np.uint8) for b in range(B)]
)
full = np.concatenate(
    [data_u8, np.ascontiguousarray(parity).view(np.uint8).reshape(B, r, -1)],
    axis=1,
)
full[1, 2] ^= 0x5A  # object 1, data share 2, every column
aug = np.concatenate([bc.G[k:], np.eye(r, dtype=bc.G.dtype)], axis=1)
mesh2 = multihost.global_mesh(("batch", "row"), (8, 1))
syn = bc.make_sharded_matmul(mesh2, aug)
gfull = multihost.replicate_to_global(
    np.concatenate([full] * 4, axis=0), mesh2  # 8 objects: one per device
)
s_out = multihost.fetch_to_every_host(syn(gfull))
bad_objects = np.nonzero(s_out.any(axis=(1, 2)))[0]
np.testing.assert_array_equal(bad_objects, [1, 3, 5, 7])  # the corrupt copies
assert not s_out[0].any() and s_out[1].all(axis=0).any()

# Round 5: the single-corrupt-row decode FOLD across the cross-host mesh —
# corrected row + rank-1 consistency rows as one generator-shaped matmul
# (BatchCodec.make_sharded_decode1). The corrupted copies' share 2 must
# come back equal to the true data row with zero consistency rows
# everywhere (clean objects correct to a no-op), on every host.
dec1 = bc.make_sharded_decode1(mesh2, 2)
d_out = multihost.fetch_to_every_host(dec1(gfull))
data8 = np.concatenate([data_u8] * 4, axis=0)
np.testing.assert_array_equal(d_out[:, 0], data8[:, 2])
assert not d_out[:, 1:].any()

print(f"MULTIHOST-OK proc={proc_id} checksum={int(parity.sum())}", flush=True)
