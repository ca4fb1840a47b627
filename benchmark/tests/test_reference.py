"""The plain reference code: systematic and MDS, and its layout."""

import itertools

import numpy as np
import pytest

from harness import reference

POLY = 0x11D


def _rank(M):
    mul = reference.mul_table(POLY)
    M = M.copy()
    rank = 0
    for c in range(M.shape[1]):
        piv = next((r for r in range(rank, M.shape[0]) if M[r, c]), None)
        if piv is None:
            continue
        M[[rank, piv]] = M[[piv, rank]]
        inv = int(np.nonzero(mul[M[rank, c]] == 1)[0][0])
        M[rank] = mul[inv, M[rank]]
        for r in range(M.shape[0]):
            if r != rank and M[r, c]:
                M[r] ^= mul[M[r, c], M[rank]]
        rank += 1
    return rank


@pytest.mark.parametrize("k,m", [(4, 2), (8, 4)])
def test_generator_systematic_and_mds(k, m):
    G = reference.generator(k, m, POLY)
    assert np.array_equal(G[:k], np.eye(k, dtype=np.uint8))
    for rows in itertools.combinations(range(k + m), k):
        assert _rank(G[list(rows)]) == k


def test_field():
    mul = reference.mul_table(POLY)
    assert mul[2, 0x80] == 0x1D          # x * x^7 = x^8 = poly - x^8
    assert all(mul[a, 1] == a for a in range(256))
    assert (mul[1:, 1:] != 0).all()


def test_encode_layout():
    k, m, unit = 2, 1, 4
    data = bytes(range(1, 11))           # 10 B: two stripes of 2 x 4 B
    shards = reference.encode(data, k, m, unit, POLY)
    assert shards[0] == bytes([1, 2, 3, 4, 9, 10, 0, 0])
    assert shards[1] == bytes([5, 6, 7, 8, 0, 0, 0, 0])
    G = reference.generator(k, m, POLY)
    mul = reference.mul_table(POLY)
    a, b = np.frombuffer(shards[0], np.uint8), np.frombuffer(shards[1],
                                                              np.uint8)
    assert shards[2] == (mul[G[2, 0]][a] ^ mul[G[2, 1]][b]).tobytes()
