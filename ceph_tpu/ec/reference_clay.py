"""Plain reference of the CLAY code (coupled-layer MSR), numpy only.

Written from the code's definition, independent of the plugin
(``ceph_tpu.ec.plugins.clay``) and of the engine, for the tests to hold
the plugin's host schedule and its device programs to:

- GF(2^8) modulo the polynomial (0x11D), its product table built here.
- k data and m parity chunks; q = d - k + 1; nu = (q - (k+m) % q) % q
  shortened (always-zero) nodes; t = (k+m+nu) / q.  The N = q*t nodes sit
  on a q x t grid, node = y*q + x; chunk i is node i (i < k) or node i+nu.
- Each chunk holds S = q**t sub-chunks ("planes"); plane z has the base-q
  digit vector (z_0 .. z_{t-1}), z_0 most significant.
- Coupling: at plane z, node (y, x) with z_y == x is a dot: U = C.  Else
  it pairs with node (y, z_y) at plane z' (z with digit y set to x); of
  the pair, the member whose own x is larger is "hi", and
  (U_hi, U_lo) = P @ (C_hi, C_lo), P the parity block of the (k=2, m=2)
  systematic Vandermonde code.
- Code: at every plane the uncoupled values U of the N nodes form a
  codeword of the inner systematic Vandermonde MDS code (k+nu data
  nodes, m parity nodes), and the shortened nodes hold zeros.

The generator G ((k+m)*S x k*S, rows chunk-major then plane) is solved
from those constraints by Gaussian elimination.  Decode and repair of
any pattern solve G restricted to the sub-chunks given; whatever is
wanted must be determined by them.
"""

from __future__ import annotations

import functools

import numpy as np

POLY = 0x11D


@functools.lru_cache(maxsize=4)
def mul_table(poly: int = POLY) -> np.ndarray:
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


def _inv(mul: np.ndarray, a: int) -> int:
    return int(np.nonzero(mul[a] == 1)[0][0])


def vandermonde(k: int, m: int, poly: int = POLY) -> np.ndarray:
    """(k+m, k) systematic Vandermonde generator: V[i][j] = i**j, columns
    reduced so the top k rows are the identity."""
    mul = mul_table(poly)
    V = np.zeros((k + m, k), np.uint8)
    for i in range(k + m):
        p = 1
        for j in range(k):
            V[i, j] = p
            p = int(mul[p, i])
    for i in range(k):
        if V[i, i] == 0:
            j = next(j for j in range(i + 1, k) if V[i, j])
            V[:, [i, j]] = V[:, [j, i]]
        V[:, i] = mul[_inv(mul, int(V[i, i])), V[:, i]]
        for j in range(k):
            if j != i and V[i, j]:
                V[:, j] ^= mul[int(V[i, j]), V[:, i]]
    return V


def gf_matmul(A: np.ndarray, X: np.ndarray, poly: int = POLY) -> np.ndarray:
    """A (r x n) times X (n x c) over GF(2^8), one row op per nonzero."""
    mul = mul_table(poly)
    out = np.zeros((A.shape[0], X.shape[1]), np.uint8)
    for i, j in zip(*np.nonzero(A)):
        out[i] ^= mul[A[i, j]][X[j]]
    return out


def eliminate(A: np.ndarray, poly: int = POLY):
    """Gauss-Jordan elimination of A (r x n): returns (E, pivots) with
    E (n x r) such that x = E @ y solves A @ x = y whenever y is in A's
    column space (free unknowns set to zero), and the pivot columns."""
    mul = mul_table(poly)
    r, n = A.shape
    M = np.concatenate([A.astype(np.uint8), np.eye(r, dtype=np.uint8)], 1)
    pivots = []
    row = 0
    for col in range(n):
        hit = np.nonzero(M[row:, col])[0]
        if not len(hit):
            continue
        p = row + int(hit[0])
        M[[row, p]] = M[[p, row]]
        M[row] = mul[_inv(mul, int(M[row, col]))][M[row]]
        f = M[:, col].copy()
        f[row] = 0
        rows = np.nonzero(f)[0]
        M[rows] ^= mul[f[rows, None], M[row][None, :]]
        pivots.append(col)
        row += 1
        if row == r:
            break
    E = np.zeros((n, r), np.uint8)
    for i, col in enumerate(pivots):
        E[col] = M[i, n:]
    return E, pivots


class Clay:
    """CLAY (k, m, d) over GF(2^8) mod ``poly``: its generator and its
    decode from any sub-chunks that determine what is wanted."""

    def __init__(self, k: int, m: int, d: int, poly: int = POLY):
        if not k <= d <= k + m - 1:
            raise ValueError(f"d={d} outside [{k}, {k + m - 1}]")
        self.k, self.m, self.d, self.poly = k, m, d, poly
        self.q = d - k + 1
        self.nu = (self.q - (k + m) % self.q) % self.q
        self.t = (k + m + self.nu) // self.q
        self.S = self.q ** self.t
        self.N = self.q * self.t
        self.mds = vandermonde(k + self.nu, m, poly)[k + self.nu:]
        self.P = vandermonde(2, 2, poly)[2:]
        self.G = self._generator()

    def node(self, chunk: int) -> int:
        return chunk if chunk < self.k else chunk + self.nu

    def digits(self, z: int) -> list[int]:
        return [(z // self.q ** (self.t - 1 - y)) % self.q
                for y in range(self.t)]

    def repair_planes(self, lost: int) -> list[int]:
        """The planes a single-chunk repair reads from each helper: those
        at which the lost node is a dot."""
        y, x = divmod(self.node(lost), self.q)
        return [z for z in range(self.S) if self.digits(z)[y] == x]

    def _uncoupled(self) -> np.ndarray:
        """(N*S x N*S): row node*S + z gives U(node, z) over the coupled
        symbols C, column node*S + z."""
        q, S = self.q, self.S
        U = np.zeros((self.N * S, self.N * S), np.uint8)
        for node in range(self.N):
            y, x = divmod(node, q)
            for z in range(S):
                vec = self.digits(z)
                me = node * S + z
                if vec[y] == x:
                    U[me, me] = 1
                    continue
                partner = y * q + vec[y]
                z2 = z + (x - vec[y]) * q ** (self.t - 1 - y)
                other = partner * S + z2
                hi = x > vec[y]
                row = self.P[0] if hi else self.P[1]
                # row @ (C_hi, C_lo)
                U[me, me if hi else other] ^= row[0]
                U[me, other if hi else me] ^= row[1]
        return U

    def _generator(self) -> np.ndarray:
        mul = mul_table(self.poly)
        k, m, nu, S, N = self.k, self.m, self.nu, self.S, self.N
        U = self._uncoupled()
        checks = []
        for z in range(S):
            for r in range(m):
                row = np.zeros(N * S, np.uint8)
                for j in range(k + nu):
                    if self.mds[r, j]:
                        row ^= mul[self.mds[r, j]][U[j * S + z]]
                row ^= U[(k + nu + r) * S + z]
                checks.append(row)
        for v in range(k, k + nu):
            for z in range(S):
                row = np.zeros(N * S, np.uint8)
                row[v * S + z] = 1
                checks.append(row)
        H = np.stack(checks)
        data = np.arange(k * S)
        rest = np.arange(k * S, N * S)
        E, pivots = eliminate(H[:, rest], self.poly)
        if len(pivots) != len(rest):
            raise ValueError("CLAY constraints do not determine parity")
        # H_rest C_rest = H_data C_data (characteristic 2)
        C_rest = gf_matmul(E, H[:, data], self.poly)
        G = np.zeros(((k + m) * S, k * S), np.uint8)
        G[:k * S] = np.eye(k * S, dtype=np.uint8)
        G[k * S:] = C_rest[nu * S:]
        return G

    def rows(self, chunk: int, planes=None) -> list[int]:
        planes = range(self.S) if planes is None else planes
        return [chunk * self.S + z for z in planes]

    def encode_chunks(self, data: np.ndarray) -> np.ndarray:
        """(k, C) data chunks of one stripe -> (k+m, C) chunks."""
        sc = data.shape[1] // self.S
        x = data.reshape(self.k * self.S, sc)
        return gf_matmul(self.G, x, self.poly).reshape(self.k + self.m, -1)

    def solve(self, given: dict, want: list[int]) -> dict:
        """``given`` maps (chunk, plane) -> sub-chunk bytes; returns the
        ``want`` chunks (whole), from the generator restricted to the
        given sub-chunks.  Raises when they do not determine them."""
        keys = sorted(given)
        A = self.G[[c * self.S + z for c, z in keys]]
        E, _ = eliminate(A, self.poly)
        W = self.G[[r for w in want for r in self.rows(w)]]
        M = gf_matmul(W, E, self.poly)
        if not np.array_equal(gf_matmul(M, A, self.poly), W):
            raise ValueError(f"sub-chunks {keys} do not determine {want}")
        Y = np.stack([np.frombuffer(bytes(given[key]), np.uint8)
                      for key in keys])
        out = gf_matmul(M, Y, self.poly)
        return {w: out[i * self.S:(i + 1) * self.S].reshape(-1)
                for i, w in enumerate(want)}


@functools.lru_cache(maxsize=8)
def code(k: int, m: int, d: int, poly: int = POLY) -> Clay:
    return Clay(k, m, d, poly)
