"""The one traffic generator: every mix under ``traffic/`` is data for it.

Everything derives from the seed.  Each worker of a closed loop draws its
ops from its own stream, so a worker's n-th op (kind, key, whether its
answer is kept for the check) is the same in every run of one seed; how
the workers interleave is the measurement.  Payloads carry
``(seed, key, version)`` in a header and a body cut from a seeded pool of
random bytes, so a stale version or another key's bytes never pass for
the right ones.
"""

from __future__ import annotations

import struct

import numpy as np

HEADER = struct.Struct("<8sQQQ")
MAGIC = b"ctpubnch"
MASK64 = (1 << 64) - 1

# stream ids: the set-up's warm-up traffic and the measured window draw
# from disjoint streams, so the window's ops do not depend on how many
# ops the warm-up got through
WARMUP, WINDOW = 1, 2


def _mix64(*vals: int) -> int:
    x = 0x9E3779B97F4A7C15
    for v in vals:
        x = ((x ^ (v & MASK64)) * 0xBF58476D1CE4E5B9) & MASK64
        x ^= x >> 31
    return x


class Payloads:
    """Object bytes of (key, version) for one seed and one object size."""

    def __init__(self, seed: int, size: int):
        if size < HEADER.size:
            raise ValueError(f"objects of {size} B cannot hold the "
                             f"{HEADER.size} B header")
        self.seed = seed & MASK64
        self.size = size
        self._body = size - HEADER.size
        self._pool = np.random.default_rng([self.seed, 1]).bytes(
            2 * self._body + 4096)

    def make(self, key: int, version: int) -> bytes:
        off = _mix64(self.seed, key, version) % (
            len(self._pool) - self._body + 1)
        return (HEADER.pack(MAGIC, self.seed, key, version)
                + self._pool[off:off + self._body])

    def version_of(self, key: int, data: bytes) -> int | None:
        """The version whose payload ``data`` is, or None when it is no
        payload of ``key`` in this run."""
        if len(data) != self.size:
            return None
        magic, seed, k, version = HEADER.unpack_from(data)
        if magic != MAGIC or seed != self.seed or k != key:
            return None
        return version if data == self.make(key, version) else None


def zipf_cdf(n_keys: int, s: float) -> np.ndarray:
    """Cumulative zipf(s) over ranks 1..n_keys (YCSB's zipfian)."""
    w = 1.0 / np.arange(1, n_keys + 1, dtype=np.float64) ** s
    cdf = np.cumsum(w / w.sum())
    cdf[-1] = 1.0
    return cdf


class Plan:
    """Seeded op streams of one mix.

    ``mix["ops"]`` maps op kind ("write_full", "read") to its share;
    ``mix["keys"]["distribution"]`` is "fresh" (every write a new
    object), "uniform" or "zipfian" (with "constant", and "scrambled" to
    spread the hot ranks over the key space as YCSB's
    ScrambledZipfianGenerator does) over ``n_keys`` keys;
    ``mix["check_fraction"]`` is the share of ops whose answer is kept
    for the check.
    """

    def __init__(self, seed: int, mix: dict, n_keys: int):
        self.seed = seed & MASK64
        self.n_keys = int(n_keys)
        self.kinds = list(mix["ops"])
        self._cum = np.cumsum([float(mix["ops"][k]) for k in self.kinds])
        self._cum /= self._cum[-1]
        keys = mix["keys"]
        self.dist = keys["distribution"]
        if self.dist not in ("fresh", "uniform", "zipfian"):
            raise ValueError(f"unknown key distribution {self.dist!r}")
        self._cdf = (zipf_cdf(self.n_keys, float(keys["constant"]))
                     if self.dist == "zipfian" else None)
        self._perm = (np.random.default_rng([self.seed, 2]).permutation(
            self.n_keys) if keys.get("scrambled") else None)
        self.check_fraction = float(mix.get("check_fraction", 1.0))

    def stream(self, kind: int, worker: int):
        """Endless (op, key, keep) tuples of one worker."""
        rng = np.random.default_rng([self.seed, 3, kind, worker])
        seq = 0
        while True:
            u_op, u_key, u_keep = rng.random(3)
            op = self.kinds[int(np.searchsorted(self._cum, u_op,
                                                side="right"))]
            if self.dist == "fresh":
                key = (kind << 48) | ((worker + 1) << 24) | seq
            elif self.dist == "uniform":
                key = int(u_key * self.n_keys)
            else:
                key = min(int(np.searchsorted(self._cdf, u_key)),
                          self.n_keys - 1)
            if self._perm is not None and self.dist != "fresh":
                key = int(self._perm[key])
            yield op, int(key), bool(u_keep < self.check_fraction)
            seq += 1
