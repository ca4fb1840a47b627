"""Batched locality-aware repair engine tests.

The strategy-selector table (LRC reads only the lost chunk's local
group, CLAY reads only the d helpers' repair sub-chunks, multi-failure
falls back to plain RS), plan memoization, launch-count reduction vs
the per-object path, exact read-byte accounting, mClock batch-cost
pacing, and the RepairScheduler drain/demotion contract."""

import asyncio

import numpy as np
import pytest

from ceph_tpu.common.perf import PerfCounters
from ceph_tpu.ec.registry import ErasureCodePluginRegistry
from ceph_tpu.osd.ec_backend import ECBackend, LocalShard
from ceph_tpu.osd.repair import (
    RepairPlan,
    RepairScheduler,
    clear_plan_cache,
    minimum_to_decode_cached,
    plan_repair,
    register_repair_counters,
    repair_codec_sig,
)
from ceph_tpu.store import CollectionId, GHObject, MemStore, Transaction


def _run(coro):
    return asyncio.run(coro)


class CountingShard(LocalShard):
    """ShardIO wrapper accounting every store read (calls + bytes)."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.read_calls = 0
        self.read_bytes = 0

    async def read_shard(self, oid, offset=0, length=None):
        raw = await super().read_shard(oid, offset, length)
        self.read_calls += 1
        self.read_bytes += len(raw)
        return raw


def make_backend(plugin, profile, stripe_unit=None, counting=False):
    codec = ErasureCodePluginRegistry().factory(plugin, profile)
    n = codec.get_chunk_count()
    cls = CountingShard if counting else LocalShard
    stores, shards = {}, {}
    for i in range(n):
        store = MemStore()
        cid = CollectionId(1, 0, shard=i)
        _run(store.queue_transactions(
            Transaction().create_collection(cid)
        ))
        stores[i] = (store, cid)
        shards[i] = cls(store, cid, pool=1, shard=i)
    be = ECBackend(codec, shards, stripe_unit=stripe_unit)
    be._test_stores = stores
    return be


def _payload(size, seed=0):
    return np.random.default_rng(seed).integers(
        0, 256, size, np.uint8
    ).tobytes()


def _seed_degraded(be, lost, nobj=6, size=4096, seed=11):
    """Write nobj objects, snapshot the lost shards' true bytes, then
    delete those shard objects.  Returns {name: data}, {(name, s): raw}."""
    originals, true_shards = {}, {}
    for i in range(nobj):
        data = _payload(size, seed + i)
        originals[f"o{i}"] = data
        _run(be.write(f"o{i}", data))
    for name in originals:
        for s in lost:
            true_shards[(name, s)] = _run(be.shards[s].read_shard(name))
    for name in originals:
        for s in lost:
            store, cid = be._test_stores[s]
            _run(store.queue_transactions(
                Transaction().remove(cid, GHObject(1, name, shard=s))
            ))
    return originals, true_shards


def _assert_shards_identical(be, originals, true_shards, lost):
    for name in originals:
        for s in lost:
            got = _run(be.shards[s].read_shard(name))
            assert got == true_shards[(name, s)], f"{name} shard {s}"


# -- strategy selector table --------------------------------------------


def test_plan_lrc_single_loss_is_group_local():
    clear_plan_cache()
    ec = ErasureCodePluginRegistry().factory(
        "lrc", {"k": "12", "m": "4", "l": "4"}
    )
    n = ec.get_chunk_count()
    lost = 3
    plan = plan_repair(ec, [lost], [s for s in range(n) if s != lost])
    assert plan.strategy == "lrc"
    # every read lands inside the lost chunk's local group: the l+1
    # group members are contiguous under the kml mapping
    group = len(plan.read_set) + 1           # group size includes lost
    g0 = (lost // group) * group
    assert all(g0 <= s < g0 + group for s in plan.read_set)
    assert lost not in plan.read_set
    assert plan.read_fraction(ec.get_data_chunk_count()) < 1.0


def test_plan_clay_single_loss_reads_helper_subchunks():
    clear_plan_cache()
    ec = ErasureCodePluginRegistry().factory(
        "clay", {"k": "8", "m": "4", "d": "11"}
    )
    n = ec.get_chunk_count()
    plan = plan_repair(ec, [3], [s for s in range(n) if s != 3])
    assert plan.strategy == "clay"
    assert len(plan.read_set) == 11          # exactly d helpers
    assert 3 not in plan.read_set
    # 1/q of each helper's sub-chunks
    assert len(plan.planes) == ec.sub_chunk_no // ec.q
    assert plan.sub_chunk_no == ec.sub_chunk_no
    # bandwidth below the k-whole-chunk baseline: d/q sub-chunk reads
    frac = plan.read_fraction(ec.get_data_chunk_count())
    assert frac == pytest.approx(
        11 / ec.q / ec.get_data_chunk_count() * ec.q
    ) or frac < 1.0


def test_plan_multi_failure_falls_back_to_rs():
    clear_plan_cache()
    for plugin, profile in (
        ("lrc", {"k": "12", "m": "4", "l": "4"}),
        ("clay", {"k": "8", "m": "4", "d": "11"}),
    ):
        ec = ErasureCodePluginRegistry().factory(plugin, profile)
        n = ec.get_chunk_count()
        lost = [3, 7]
        plan = plan_repair(ec, lost, [s for s in range(n) if s not in lost])
        assert plan.strategy == "rs", plugin
        assert set(plan.read_set) == set(
            ec.minimum_to_decode(lost, [s for s in range(n)
                                        if s not in lost])
        )


def test_plan_clay_helper_unavailable_falls_back():
    clear_plan_cache()
    ec = ErasureCodePluginRegistry().factory(
        "clay", {"k": "8", "m": "4", "d": "11"}
    )
    n = ec.get_chunk_count()
    single = plan_repair(ec, [3], [s for s in range(n) if s != 3])
    gone = single.read_set[0]                # kill one helper too
    avail = [s for s in range(n) if s not in (3, gone)]
    plan = plan_repair(ec, [3], avail)
    assert plan.strategy == "rs"
    assert gone not in plan.read_set


# -- plan memoization ---------------------------------------------------


def test_plan_repair_memoizes_per_signature():
    clear_plan_cache()
    perf = PerfCounters("t")
    register_repair_counters(perf)
    reg = ErasureCodePluginRegistry()
    prof = {"k": "12", "m": "4", "l": "4"}
    ec1 = reg.factory("lrc", prof)
    ec2 = reg.factory("lrc", prof)           # distinct instance, same sig
    assert repair_codec_sig(ec1) == repair_codec_sig(ec2)
    n = ec1.get_chunk_count()
    avail = [s for s in range(n) if s != 3]
    p1 = plan_repair(ec1, [3], avail, perf=perf)
    p2 = plan_repair(ec2, [3], avail, perf=perf)
    assert p1 is p2                           # served from the memo
    assert perf.value("ec_repair_plan_misses") == 1
    assert perf.value("ec_repair_plan_hits") == 1
    # a different avail set is a NEW key (retry-on-dead-read-set loop)
    plan_repair(ec1, [3], avail[:-1], perf=perf)
    assert perf.value("ec_repair_plan_misses") == 2


def test_minimum_to_decode_cached_matches_plugin():
    clear_plan_cache()
    perf = PerfCounters("t")
    register_repair_counters(perf)
    ec = ErasureCodePluginRegistry().factory(
        "jax_rs", {"k": "4", "m": "2", "technique": "cauchy_good"}
    )
    lost, avail = [1], [0, 2, 3, 4, 5]
    want = ec.minimum_to_decode(lost, avail)
    assert minimum_to_decode_cached(ec, lost, avail, perf=perf) == want
    assert minimum_to_decode_cached(ec, lost, avail, perf=perf) == want
    assert perf.value("ec_repair_plan_misses") == 1
    assert perf.value("ec_repair_plan_hits") == 1


# -- batched rebuild: correctness + accounting --------------------------


def test_recover_batch_rs_bit_identical_and_fewer_launches():
    clear_plan_cache()
    be = make_backend(
        "jax_rs", {"k": "4", "m": "2", "technique": "cauchy_good"},
        stripe_unit=128,
    )
    lost = [1, 4]
    originals, true_shards = _seed_degraded(be, lost, nobj=8)
    base = be.perf.value("ec_device_launches")
    res = _run(be.recover_batch(list(originals), lost, {}))
    launches = be.perf.value("ec_device_launches") - base
    assert set(res["recovered"]) == set(originals)
    assert res["strategy"] == "rs"
    _assert_shards_identical(be, originals, true_shards, lost)
    for name, data in originals.items():
        assert _run(be.read(name)) == data
    # one decode launch for the whole batch vs one per object
    assert launches < len(originals)
    assert be.perf.value("ec_repair_objects") == len(originals)
    assert be.perf.value("ec_repair_batches") >= 1


def test_recover_batch_lrc_reads_only_local_group():
    clear_plan_cache()
    be = make_backend(
        "lrc", {"k": "12", "m": "4", "l": "4"}, counting=True
    )
    lost = [3]
    originals, true_shards = _seed_degraded(be, lost, nobj=6)
    for sh in be.shards.values():             # count only repair reads
        sh.read_calls = sh.read_bytes = 0
    res = _run(be.recover_batch(list(originals), lost, {}))
    # snapshot read accounting BEFORE any verification reads
    touched = {s for s, sh in be.shards.items() if sh.read_calls}
    read = sum(sh.read_bytes for sh in be.shards.values())
    assert res["strategy"] == "lrc"
    assert set(res["recovered"]) == set(originals)
    _assert_shards_identical(be, originals, true_shards, lost)
    plan = plan_repair(
        be.ec, lost,
        [s for s in range(be.ec.get_chunk_count()) if s not in lost],
    )
    assert touched == set(plan.read_set)      # ONLY the local group
    # exact accounting: counters equal the bytes the wrappers saw
    assert be.perf.value("ec_repair_read_bytes") == read
    k = be.ec.get_data_chunk_count()
    shard_len = read // (len(plan.read_set) * len(originals))
    saved = (k - len(plan.read_set)) * shard_len * len(originals)
    assert be.perf.value("ec_repair_read_bytes_saved") == saved


def test_recover_batch_clay_reads_only_helper_subchunks():
    clear_plan_cache()
    be = make_backend(
        "clay", {"k": "8", "m": "4", "d": "11"}, counting=True
    )
    lost = [3]
    originals, true_shards = _seed_degraded(be, lost, nobj=4, size=8192)
    for sh in be.shards.values():
        sh.read_calls = sh.read_bytes = 0
    res = _run(be.recover_batch(list(originals), lost, {}))
    # snapshot read accounting BEFORE any verification reads
    touched = {s for s, sh in be.shards.items() if sh.read_bytes}
    total = sum(sh.read_bytes for sh in be.shards.values())
    assert res["strategy"] == "clay"
    assert set(res["recovered"]) == set(originals)
    _assert_shards_identical(be, originals, true_shards, lost)
    for name, data in originals.items():
        assert _run(be.read(name)) == data
    plan = plan_repair(
        be.ec, lost,
        [s for s in range(be.ec.get_chunk_count()) if s not in lost],
    )
    assert touched == set(plan.read_set)      # ONLY the d helpers
    # each helper contributes 1/q of its bytes: the sub-chunk planes
    sub, q = be.ec.sub_chunk_no, be.ec.q
    whole = sum(
        len(true_shards[(n_, 3)]) for n_ in originals
    ) * len(plan.read_set)
    assert total * q == whole                 # exactly 1/q of whole reads
    assert len(plan.planes) == sub // q
    assert be.perf.value("ec_repair_read_bytes") == total


class RangedCountingShard(CountingShard):
    """CountingShard that also counts ranged sub-ops, per object."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.ranged: dict[str, int] = {}

    async def read_shard_ranges(self, oid, ranges, version=None):
        self.ranged[oid] = self.ranged.get(oid, 0) + 1
        return await super().read_shard_ranges(oid, ranges, version)


def test_recover_batch_clay_one_ranged_subop_per_helper():
    """Each helper serves ONE ranged sub-op per object, whatever the
    object's stripe count: every stripe's repair planes ride it."""
    clear_plan_cache()
    codec = ErasureCodePluginRegistry().factory(
        "clay", {"k": "8", "m": "4", "d": "11"})
    shards, stores = {}, {}
    for i in range(codec.get_chunk_count()):
        store = MemStore()
        cid = CollectionId(1, 0, shard=i)
        _run(store.queue_transactions(Transaction().create_collection(cid)))
        stores[i] = (store, cid)
        shards[i] = RangedCountingShard(store, cid, pool=1, shard=i)
    be = ECBackend(codec, shards, stripe_unit=1024)
    be._test_stores = stores
    lost = [4]                     # node 4: four runs of planes per stripe
    originals, true_shards = _seed_degraded(be, lost, nobj=3,
                                            size=5 * 8 * 1024)
    res = _run(be.recover_batch(list(originals), lost, {}))
    assert set(res["recovered"]) == set(originals)
    _assert_shards_identical(be, originals, true_shards, lost)
    for s, sh in be.shards.items():
        want = {} if s in lost else {n: 1 for n in originals}
        assert sh.ranged == want, s


def test_recover_batch_multi_failure_lrc_falls_back_to_rs():
    clear_plan_cache()
    be = make_backend("lrc", {"k": "12", "m": "4", "l": "4"})
    lost = [3, 7]
    originals, true_shards = _seed_degraded(be, lost, nobj=4)
    res = _run(be.recover_batch(list(originals), lost, {}))
    assert res["strategy"] == "rs"
    assert set(res["recovered"]) == set(originals)
    _assert_shards_identical(be, originals, true_shards, lost)


def test_recover_batch_demotes_missing_objects():
    clear_plan_cache()
    be = make_backend(
        "jax_rs", {"k": "4", "m": "2", "technique": "cauchy_good"},
        stripe_unit=128,
    )
    lost = [1]
    originals, true_shards = _seed_degraded(be, lost, nobj=3)
    names = list(originals) + ["ghost"]       # never written
    res = _run(be.recover_batch(names, lost, {}))
    assert set(res["recovered"]) == set(originals)
    assert "ghost" not in res["recovered"]
    _assert_shards_identical(be, originals, true_shards, lost)


# -- RepairScheduler drain ----------------------------------------------


class _FakeBackend:
    """Records recover_batch calls; optionally fails some objects."""

    def __init__(self, fail=()):
        self.calls = []
        self.fail = set(fail)

    async def recover_batch(self, names, lost, versions=None):
        self.calls.append((tuple(names), tuple(lost)))
        done = [n for n in names if n not in self.fail]
        return {"recovered": done, "strategy": "rs", "batches": 1}


def test_drain_groups_by_lost_pattern_and_chunks():
    perf = PerfCounters("t")
    sched = RepairScheduler(perf, max_batch_objects=4,
                            min_batch_objects=2)
    rebuild = {f"a{i}": [1] for i in range(6)}
    rebuild.update({f"b{i}": [2, 5] for i in range(3)})
    rebuild["solo"] = [3]                     # group of 1: classic path
    fb = _FakeBackend()
    done = _run(sched.drain(fb, rebuild))
    assert done == {f"a{i}" for i in range(6)} | {
        f"b{i}" for i in range(3)}
    assert "solo" not in done
    # pattern [1] chunks at max_batch_objects=4: 4 + 2, pattern [2,5]: 3
    sizes = sorted(len(ns) for ns, _ in fb.calls)
    assert sizes == [2, 3, 4]
    patterns = {lost for _, lost in fb.calls}
    assert patterns == {(1,), (2, 5)}
    assert sched.objects == 9 and sched.batches == 3


def test_drain_demotes_failed_objects():
    perf = PerfCounters("t")
    sched = RepairScheduler(perf, min_batch_objects=2)
    fb = _FakeBackend(fail={"x1"})
    done = _run(sched.drain(fb, {"x0": [1], "x1": [1], "x2": [1]}))
    assert done == {"x0", "x2"}
    assert sched.demoted == 1
    assert perf.value("ec_repair_demoted") == 1
    stats = sched.stats()
    assert stats["by_strategy"] == {"rs": 2}


def test_drain_paces_through_mclock_recovery_at_batch_cost():
    from ceph_tpu.osd.scheduler import MClockScheduler

    class SpyScheduler:
        def __init__(self):
            self.acquires = []

        async def acquire(self, clazz, cost=1):
            self.acquires.append((clazz, cost))

    perf = PerfCounters("t")
    spy = SpyScheduler()
    sched = RepairScheduler(perf, op_scheduler=spy, use_mclock=True,
                            max_batch_objects=4, min_batch_objects=2)
    fb = _FakeBackend()
    _run(sched.drain(fb, {f"o{i}": [1] for i in range(6)}))
    assert spy.acquires == [("recovery", 4), ("recovery", 2)]

    # the real scheduler accepts vector cost and accounts it
    async def real():
        ms = MClockScheduler()
        await ms.acquire("recovery", cost=5)
        return ms._dispatched.get("recovery", 0)

    assert _run(real()) == 5


# -- device cache vectored install --------------------------------------


def test_device_cache_install_batch():
    from ceph_tpu.store.device_cache import DeviceShardCache

    cache = DeviceShardCache(max_bytes=1 << 20)
    entries = [
        ("o1", 0, np.zeros(64, np.uint8), 3),
        ("o1", 1, np.ones(64, np.uint8), 3),
        ("o2", 0, np.full(32, 7, np.uint8), 1),
    ]
    assert cache.install_batch("ns", entries) == 3
    ent = cache.get("ns", "o1", 1)
    assert ent is not None and ent.version == 3
    assert np.asarray(ent.arr)[0] == 1
    assert cache.get("ns", "o2", 0).nbytes == 32


# -- full-host failure drill --------------------------------------------


def test_host_failure_drill_batched_rebuild():
    """Kill every OSD on one CRUSH host under seeded load: degraded
    writes and mid-rebuild reads must complete (mClock recovery pacing,
    no starvation), the missing sets must drain through the batched
    engine, and every object must read back bit-identical."""
    from ceph_tpu.msg import reset_local_namespace
    from ceph_tpu.testing import run_host_failure_drill

    reset_local_namespace()
    try:
        out = asyncio.run(run_host_failure_drill(seed=5))
    finally:
        reset_local_namespace()
    assert out["repair_batches"] > 0
    assert out["repair_objects"] > 0
    assert out["verified"] == 48
    assert out["mid_rebuild_reads"] == 8
    assert len(out["killed_osds"]) == 2       # both of host1's OSDs


# -- plan dataclass -----------------------------------------------------


def test_repair_plan_read_fraction():
    rs = RepairPlan("rs", (0, 2, 3, 5))
    assert rs.read_fraction(4) == 1.0
    lrc = RepairPlan("lrc", (0, 1, 2, 4))
    assert lrc.read_fraction(12) == pytest.approx(4 / 12)
    clay = RepairPlan("clay", tuple(range(11)),
                      tuple(range(16)), None, 64)
    assert clay.read_fraction(8) == pytest.approx(11 * 16 / 64 / 8)
