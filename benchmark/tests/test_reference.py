"""The plain reference codec: GF(2^8) arithmetic by hand, and agreement
with the program's codec at a tiny size for both geometries."""

import itertools

import numpy as np
import pytest

from lib import reference


def test_field_by_hand():
    assert reference.mul(2, 0x80) == 0x1D  # x * x^7 = x^8 = x^4+x^3+x^2+1
    assert reference.mul(3, 7) == 9  # (x+1)(x^2+x+1) = x^3+1
    assert reference.inv(2) == 0x8E  # 2 * 0x8e = 0x11c ^ 0x11d = 1
    for a in range(1, 256):
        assert reference.mul(a, reference.inv(a)) == 1
    assert reference.power(2, 8) == 0x1D


def test_cauchy_entries_by_hand():
    P = reference.cauchy_parity(10, 4)
    assert P[0, 0] == reference.inv(10)  # 1/((10+0) xor 0)
    assert P[3, 5] == reference.inv(13 ^ 5)


@pytest.mark.parametrize("k,n", [(10, 14), (12, 16)])
def test_reference_matches_program(k, n):
    from noise_ec_tpu.codec.rs import ReedSolomon

    r = n - k
    rng = np.random.default_rng(k)
    data = [rng.integers(0, 256, 257, dtype=np.uint8) for _ in range(k)]
    rs = ReedSolomon(k, r, backend="numpy")
    program = [np.asarray(s) for s in rs.encode(data)]
    ours = data + reference.encode(data, r)
    for a, b in zip(program, ours):
        assert np.array_equal(a, b)
    lost = [0, 3, 6, 9][:r]
    shards = [None if i in lost else ours[i] for i in range(n)]
    rebuilt = reference.reconstruct(shards, k, r)
    for i in range(k):
        assert np.array_equal(rebuilt[i], data[i])
    par1 = reference.encode(data, r, parity=reference.par1_parity)
    assert any(not np.array_equal(a, b) for a, b in zip(par1, ours[k:]))


def _unrecoverable(k, r, parity):
    """Patterns of lost data shards, repaired from a subset of the parity
    rows of the same size, whose decode system is singular."""
    G = reference.generator(k, r, parity)
    bad = 0
    for e in range(1, r + 1):
        for lost in itertools.combinations(range(k), e):
            for rows in itertools.combinations(range(r), e):
                keep = [i for i in range(k) if i not in lost]
                try:
                    reference.invert(G[keep + [k + p for p in rows]])
                except ValueError:
                    bad += 1
    return bad


@pytest.mark.parametrize("k,r,bad", [(10, 4, 12), (12, 4, 20)])
def test_par1_is_not_mds(k, r, bad):
    """The control's layout breaks "any n-k shards may be lost"; the
    Cauchy code keeps it."""
    assert _unrecoverable(k, r, reference.par1_parity) == bad
    assert _unrecoverable(k, r, reference.cauchy_parity) == 0
