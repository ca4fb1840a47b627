"""The check catches a broken timed path.  A run without the chip check,
on a small cluster on the CPU, with a fault planted in the program under
the window: each must make ``correct`` false.

- state unchanged: ``write_full`` is acknowledged but never applied;
- fewer acknowledgements: one shard position of every written object is
  never committed to its OSD's store, and the write is acknowledged;
- an answer altered where it is produced: the GF engine's output (the
  device encode of every write, the device decode of a degraded read)
  has one byte flipped.

The cells have no batch to halve and no exchange between chips.
"""

import asyncio
import os
import time

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from harness import cell as cellmod  # noqa: E402
from harness import spec  # noqa: E402
from harness.sut import ClusterSUT, warm_codec  # noqa: E402


def _small(traffic: str):
    cfg = spec.load(f"{spec.BENCH_DIR}/configs/rados_bench.ec84.4m.json")
    cfg.update(osds=3, object_bytes=64 << 10, objects=16)
    cfg["pool"].update(pg_num=8)
    cfg["pool"]["profile"].update(k=2, m=1)
    mix = spec.traffic(traffic)
    mix.update(concurrency=4, warmup_s=0.3, check_fraction=1.0)
    return cfg, mix


def _run(traffic: str, seed: int = 3000000021):
    cfg, mix = _small(traffic)
    warm_codec(cfg, mix)
    out = asyncio.run(cellmod.run(cfg, mix, seed, 1.0, False,
                                  ClusterSUT(cfg), time.perf_counter(),
                                  None))
    checks = {c["name"]: c for c in out["checks"]}
    return all(cellmod.passed(c) for c in checks.values()), checks


def _noop_writes(monkeypatch):
    from ceph_tpu.client import rados

    async def write_full(self, oid, data):
        return None
    monkeypatch.setattr(rados.IoCtx, "write_full", write_full)


def _drop_last_shard(monkeypatch):
    from ceph_tpu.store import memstore

    apply = memstore.MemStore._apply

    def bent(self, op):
        oid = op[2] if len(op) > 2 else None
        if (getattr(oid, "shard", None) == 2
                and str(getattr(oid, "name", "")).startswith("bench-")):
            return None
        return apply(self, op)
    monkeypatch.setattr(memstore.MemStore, "_apply", bent)


def _flip_gf_output(monkeypatch):
    from ceph_tpu.ec import engine

    apply = engine.BitplaneEngine.apply

    def bent(self, coeff, data):
        out = apply(self, coeff, data)
        return out.at[(0,) * out.ndim].set(out[(0,) * out.ndim] ^ 1)
    monkeypatch.setattr(engine.BitplaneEngine, "apply", bent)


@pytest.mark.parametrize("traffic", ["write", "degraded_read"])
def test_sound_program_is_correct(traffic):
    correct, checks = _run(traffic)
    assert correct, checks


@pytest.mark.parametrize("traffic,fault,caught_by", [
    ("write", _noop_writes, "objects_wrong"),
    ("write", _flip_gf_output, "objects_wrong"),
    ("write", _drop_last_shard, "shards_missing"),
    ("degraded_read", _noop_writes, "setup_ops_failed"),
    ("degraded_read", _flip_gf_output, "reads_wrong"),
])
def test_fault_is_caught(monkeypatch, traffic, fault, caught_by):
    fault(monkeypatch)
    correct, checks = _run(traffic)
    assert not correct, checks
    assert not cellmod.passed(checks[caught_by]), checks
