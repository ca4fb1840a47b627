"""Plain reference of a CLAY pool's code: GF(2^8) with numpy only.

Written from the configuration's ``code`` statement, never from the
program's tables or schedules: the field and the systematic Vandermonde
generators are ``harness/reference.py``'s; the q x t node grid, the
planes' digit vectors, the pairwise coupling and the inner MDS code are
the constraints every codeword meets, and the generator of the whole
code (chunk-major, then plane) is solved from them by Gaussian
elimination.  An object is cut the ECUtil way, as for the RS pools:
zero-padded to whole stripes of k chunks of ``stripe_unit`` bytes, and
shard i is chunk i of every stripe, in stripe order.
"""

from __future__ import annotations

import functools

import numpy as np

from harness.reference import generator, mul_table


def gf_matmul(A: np.ndarray, X: np.ndarray, poly: int) -> np.ndarray:
    """A (r x n) times X (n x c) over GF(2^8), one row op per nonzero."""
    mul = mul_table(poly)
    out = np.zeros((A.shape[0], X.shape[1]), np.uint8)
    for i, j in zip(*np.nonzero(A)):
        out[i] ^= mul[A[i, j]][X[j]]
    return out


def solve_square(A: np.ndarray, poly: int) -> np.ndarray:
    """The inverse of a square GF(2^8) matrix, by Gauss-Jordan."""
    mul = mul_table(poly)
    n = A.shape[0]
    M = np.concatenate([A.astype(np.uint8), np.eye(n, dtype=np.uint8)], 1)
    for col in range(n):
        hit = np.nonzero(M[col:, col])[0]
        if not len(hit):
            raise ValueError("the constraints do not determine the parity")
        p = col + int(hit[0])
        M[[col, p]] = M[[p, col]]
        inv = int(np.nonzero(mul[M[col, col]] == 1)[0][0])
        M[col] = mul[inv][M[col]]
        f = M[:, col].copy()
        f[col] = 0
        rows = np.nonzero(f)[0]
        M[rows] ^= mul[f[rows, None], M[col][None, :]]
    return M[:, n:]


@functools.lru_cache(maxsize=4)
def code_generator(k: int, m: int, d: int, poly: int) -> np.ndarray:
    """((k+m)*S, k*S) generator of CLAY (k, m, d): row chunk*S + z gives
    plane z of a chunk over the k data chunks' planes."""
    mul = mul_table(poly)
    q = d - k + 1
    nu = (q - (k + m) % q) % q
    t = (k + m + nu) // q
    S, N = q ** t, q * t
    mds = generator(k + nu, m, poly)[k + nu:]
    P = generator(2, 2, poly)[2:]

    def digit(z, y):
        return (z // q ** (t - 1 - y)) % q

    # U[node*S + z] over the coupled symbols C (same indexing)
    U = np.zeros((N * S, N * S), np.uint8)
    for node in range(N):
        y, x = divmod(node, q)
        for z in range(S):
            me = node * S + z
            zy = digit(z, y)
            if zy == x:
                U[me, me] = 1
                continue
            other = (y * q + zy) * S + z + (x - zy) * q ** (t - 1 - y)
            if x > zy:          # this member is hi
                U[me, me] ^= P[0, 0]
                U[me, other] ^= P[0, 1]
            else:
                U[me, other] ^= P[1, 0]
                U[me, me] ^= P[1, 1]
    checks = []
    for z in range(S):          # inner MDS at every plane
        for r in range(m):
            row = U[(k + nu + r) * S + z].copy()
            for j in range(k + nu):
                row ^= mul[mds[r, j]][U[j * S + z]]
            checks.append(row)
    for v in range(k, k + nu):  # shortened nodes hold zeros
        for z in range(S):
            row = np.zeros(N * S, np.uint8)
            row[v * S + z] = 1
            checks.append(row)
    H = np.stack(checks)
    rest = solve_square(H[:, k * S:], poly)
    parity = gf_matmul(rest, H[:, :k * S], poly)[nu * S:]
    return np.concatenate([np.eye(k * S, dtype=np.uint8), parity])


def encode(data: bytes, k: int, m: int, d: int, stripe_unit: int,
           poly: int) -> list[bytes]:
    """The k+m shard streams of one object."""
    G = code_generator(k, m, d, poly)
    S = G.shape[1] // k
    sc = stripe_unit // S
    width = k * stripe_unit
    stripes = -(-len(data) // width) or 1
    buf = np.zeros(stripes * width, np.uint8)
    buf[:len(data)] = np.frombuffer(data, np.uint8)
    # (stripe, chunk, plane, byte) -> rows (chunk, plane), columns
    # (stripe, byte)
    x = buf.reshape(stripes, k, S, sc).transpose(1, 2, 0, 3)
    out = gf_matmul(G, x.reshape(k * S, stripes * sc), poly)
    out = out.reshape(k + m, S, stripes, sc).transpose(0, 2, 1, 3)
    return [out[i].tobytes() for i in range(k + m)]
