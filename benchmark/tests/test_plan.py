"""The traffic generator: same seed, same plan; payloads name their
version."""

import itertools

import numpy as np
import pytest

from harness import spec
from harness.traffic import WINDOW, Payloads, Plan, zipf_cdf

MIXES = ["write", "ycsb_a", "degraded_read"]
SEED = 2**33 + 12345          # seeds go past 32 signed bits


def _ops(mix, seed, worker, n=300):
    plan = Plan(seed, mix, 1000)
    return list(itertools.islice(plan.stream(WINDOW, worker), n))


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_plan(name):
    mix = spec.traffic(name)
    for w in range(3):
        assert _ops(mix, SEED, w) == _ops(mix, SEED, w)
    assert _ops(mix, SEED, 0) != _ops(mix, SEED + 1, 0)
    assert _ops(mix, SEED, 0) != _ops(mix, SEED, 1)


def test_mix_shares_and_keys():
    mix = spec.traffic("ycsb_a")
    ops = _ops(mix, SEED, 0, 20000)
    reads = sum(op == "read" for op, _, _ in ops) / len(ops)
    assert 0.48 < reads < 0.52
    keys = [k for _, k, _ in ops]
    assert 0 <= min(keys) and max(keys) < 1000
    # zipfian 0.99: the hottest key draws ~1/H(1000, 0.99) of the ops
    top = max(np.bincount(keys)) / len(keys)
    assert abs(top - 1 / np.sum(1.0 / np.arange(1, 1001) ** 0.99)) < 0.02


def test_fresh_keys_never_repeat():
    mix = spec.traffic("write")
    keys = [k for w in range(4) for _, k, _ in _ops(mix, SEED, w, 500)]
    assert len(set(keys)) == len(keys)


def test_zipf_cdf():
    cdf = zipf_cdf(4, 1.0)
    assert np.allclose(cdf, np.cumsum([1, 1 / 2, 1 / 3, 1 / 4]) / (25 / 12))


@pytest.mark.parametrize("size", [1000, 4 << 20])
def test_payload_names_its_version(size):
    p = Payloads(SEED, size)
    a, b = p.make(7, 3), p.make(7, 4)
    assert len(a) == size and a != b
    assert p.version_of(7, a) == 3 and p.version_of(7, b) == 4
    assert p.version_of(8, a) is None                 # another key's bytes
    assert Payloads(SEED + 1, size).version_of(7, a) is None
    bent = bytearray(a)
    bent[-1] ^= 1
    assert p.version_of(7, bytes(bent)) is None       # one byte altered
    assert p.version_of(7, a[:-1]) is None
    assert Payloads(SEED, size).make(7, 3) == a       # same seed, same bytes
