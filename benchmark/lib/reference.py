"""Plain Reed-Solomon reference over GF(2^8), written from the published
construction and nothing of the program.

Field: GF(2^8) with the primitive polynomial x^8 + x^4 + x^3 + x^2 + 1
(0x11D), generator 2, as in klauspost/reedsolomon and ISA-L. Code:
systematic, generator [I; P] with the Cauchy parity block
P[i][j] = 1 / ((k + i) xor j)  (i < r, j < k). Shard s of a stripe of
k * L bytes is bytes [s*L, (s+1)*L); parity row i is the GF sum of
P[i][j] * data[j]. Multiplication is by table lookup, one 256-byte row of
a 256 x 256 product table per coefficient.

``par1_parity`` is the PAR1 layout P[i][c] = (c + 1)^i, which is not MDS:
the control puts it in the program's place (PERF.md, "correct").
"""

from __future__ import annotations

import numpy as np

POLY = 0x11D


def _tables() -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    exp = np.zeros(512, dtype=np.int64)
    log = np.zeros(256, dtype=np.int64)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= POLY
    exp[255:510] = exp[:255]
    a = np.arange(256)[:, None]
    b = np.arange(256)[None, :]
    prod = exp[(log[a] + log[b]) % 255]
    prod[(a == 0) | (b == 0)] = 0
    return exp, log, prod.astype(np.uint8)


EXP, LOG, MUL = _tables()


def mul(a: int, b: int) -> int:
    return int(MUL[a, b])


def inv(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("0 has no inverse in GF(2^8)")
    return int(EXP[255 - LOG[a]])


def power(a: int, e: int) -> int:
    if e == 0:
        return 1
    if a == 0:
        return 0
    return int(EXP[(LOG[a] * e) % 255])


def cauchy_parity(k: int, r: int) -> np.ndarray:
    return np.array([[inv((k + i) ^ j) for j in range(k)] for i in range(r)],
                    dtype=np.uint8)


def par1_parity(k: int, r: int) -> np.ndarray:
    return np.array([[power(c + 1, i) for c in range(k)] for i in range(r)],
                    dtype=np.uint8)


def generator(k: int, r: int, parity=cauchy_parity) -> np.ndarray:
    return np.concatenate([np.eye(k, dtype=np.uint8), parity(k, r)])


def matmul(M: np.ndarray, rows: list) -> list[np.ndarray]:
    """GF product of an (a, b) coefficient matrix with b byte rows."""
    out = []
    for coeffs in M:
        acc = np.zeros(len(rows[0]), dtype=np.uint8)
        for c, row in zip(coeffs, rows):
            if c:
                acc ^= np.take(MUL[c], row)
        out.append(acc)
    return out


def encode(data: list, r: int, parity=cauchy_parity) -> list[np.ndarray]:
    """The r parity shards of k data shards."""
    return matmul(parity(len(data), r), data)


def invert(A: np.ndarray) -> np.ndarray:
    """Inverse of a square GF(2^8) matrix by Gauss-Jordan elimination."""
    n = len(A)
    M = np.concatenate([A.astype(np.uint8), np.eye(n, dtype=np.uint8)], 1)
    for col in range(n):
        pivot = next((r for r in range(col, n) if M[r, col]), None)
        if pivot is None:
            raise ValueError("singular matrix")
        M[[col, pivot]] = M[[pivot, col]]
        M[col] = MUL[inv(int(M[col, col]))][M[col]]
        for r in range(n):
            if r != col and M[r, col]:
                M[r] ^= MUL[int(M[r, col])][M[col]]
    return M[:, n:]


def reconstruct(shards: list, k: int, r: int,
                parity=cauchy_parity) -> list[np.ndarray]:
    """The k data shards from any k present shards of n = k + r (None
    marks a lost shard)."""
    present = [i for i, s in enumerate(shards) if s is not None][:k]
    if len(present) < k:
        raise ValueError(f"{len(present)} shards present, need {k}")
    G = generator(k, r, parity)
    decode = invert(G[present])
    return matmul(decode, [np.frombuffer(shards[i], dtype=np.uint8)
                           if not isinstance(shards[i], np.ndarray)
                           else shards[i] for i in present])
