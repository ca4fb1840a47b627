"""Plain reference of the pool's erasure code: GF(2^8) with numpy only.

Written from the configuration's statement of the code, never from the
program's tables: the field is GF(2^8) over the polynomial the config
names, the generator is the systematic Vandermonde matrix the config
describes (V[i][j] = i**j for the k+m shard rows, columns reduced so the
first k rows are the identity), and an object is cut the ECUtil way:
zero-padded to whole stripes of k chunks of ``stripe_unit`` bytes, and
shard i is chunk i of every stripe, in stripe order.
"""

from __future__ import annotations

import functools

import numpy as np


@functools.lru_cache(maxsize=4)
def mul_table(poly: int) -> np.ndarray:
    """256 x 256 product table of GF(2^8) modulo ``poly``."""
    exp = np.zeros(510, np.int64)
    log = np.zeros(256, np.int64)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= poly
    exp[255:] = exp[:255]
    a = np.arange(256)
    prod = exp[(log[a][:, None] + log[a][None, :]) % 255]
    prod[0, :] = 0
    prod[:, 0] = 0
    return prod.astype(np.uint8)


def _pow(mul: np.ndarray, a: int, n: int) -> int:
    out = 1
    for _ in range(n):
        out = int(mul[out, a])
    return out


def _inv(mul: np.ndarray, a: int) -> int:
    return int(np.nonzero(mul[a] == 1)[0][0])


@functools.lru_cache(maxsize=8)
def generator(k: int, m: int, poly: int) -> np.ndarray:
    """(k+m, k) systematic Vandermonde generator."""
    mul = mul_table(poly)
    V = np.array([[_pow(mul, i, j) for j in range(k)] for i in range(k + m)],
                 np.uint8)
    for i in range(k):
        if V[i, i] == 0:
            j = next(j for j in range(i + 1, k) if V[i, j])
            V[:, [i, j]] = V[:, [j, i]]
        V[:, i] = mul[_inv(mul, int(V[i, i])), V[:, i]]
        for j in range(k):
            if j != i and V[i, j]:
                V[:, j] ^= mul[int(V[i, j]), V[:, i]]
    return V


def encode(data: bytes, k: int, m: int, stripe_unit: int,
           poly: int) -> list[bytes]:
    """The k+m shard streams of one object."""
    width = k * stripe_unit
    stripes = -(-len(data) // width) or 1
    buf = np.zeros(stripes * width, np.uint8)
    buf[:len(data)] = np.frombuffer(data, np.uint8)
    chunks = buf.reshape(stripes, k, stripe_unit).transpose(1, 0, 2)
    rows = chunks.reshape(k, stripes * stripe_unit)
    mul = mul_table(poly)
    G = generator(k, m, poly)
    out = [rows[i].tobytes() for i in range(k)]
    for r in range(k, k + m):
        acc = np.zeros(rows.shape[1], np.uint8)
        for j in range(k):
            acc ^= mul[G[r, j]][rows[j]]
        out.append(acc.tobytes())
    return out
