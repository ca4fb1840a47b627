"""The benchmark's plain CLAY reference against the program's encode, at
small sizes, and against the geometry the configuration states."""

import numpy as np
import pytest

from harness import reference_clay, spec

POLY = 0x11D


@pytest.mark.parametrize("k,m,d,unit,size", [
    (8, 4, 11, 4096, 3 * 8 * 4096 + 777),   # the config's profile
    (4, 2, 5, 512, 5000),                   # q = 2
    (5, 2, 6, 256, 3333),                   # nu = 1 shortened node
], ids=["k8m4d11", "k4m2d5", "k5m2d6"])
def test_reference_matches_the_program(k, m, d, unit, size):
    from ceph_tpu.ec.registry import ErasureCodePluginRegistry

    ec = ErasureCodePluginRegistry().factory(
        "clay", {"k": str(k), "m": str(m), "d": str(d)})
    data = np.random.default_rng(k + d).integers(0, 256, size, np.uint8)
    shards = reference_clay.encode(data.tobytes(), k, m, d, unit, POLY)
    width = k * unit
    stripes = -(-size // width)
    buf = np.zeros(stripes * width, np.uint8)
    buf[:size] = data
    chunks = ec.encode_chunks_batch(buf.reshape(stripes, k, unit))
    assert shards == [chunks[:, i].tobytes() for i in range(k + m)]


def test_reference_is_systematic_and_follows_the_config():
    cfg = spec.load(f"{spec.BENCH_DIR}/configs/rados_bench.clay84d11.4m.json")
    p = cfg["pool"]["profile"]
    k, m, d = int(p["k"]), int(p["m"]), int(p["d"])
    G = reference_clay.code_generator(k, m, d, int(cfg["code"]["polynomial"]))
    # q = 4, t = 3: 64 planes of 64 B in each 4 KiB chunk
    assert G.shape == ((k + m) * 64, k * 64)
    assert int(p["stripe_unit"]) // 64 == 64
    assert np.array_equal(G[:k * 64], np.eye(k * 64, dtype=np.uint8))
