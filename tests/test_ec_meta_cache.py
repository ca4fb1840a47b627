"""The EC primary's per-object metadata cache (``ECBackend.meta_cache``).

Within an interval the primary answers ``_read_meta`` from what its own
mutations left, so client ops stop fanning a version getattr out to
every shard.  Over MemStore shards wrapped to count ``get_attr`` calls:
hits send no metadata getattr; a mutation that did not settle leaves no
entry and the next read fans in for the max version; remove and
set_attr update the entry; a fill that overlaps a settled mutation is
dropped; the LRU holds 64 objects; a rebuilt backend and a PG going
active start empty.
"""

import asyncio
import json
from types import SimpleNamespace

import pytest

from ceph_tpu.ec.registry import ErasureCodePluginRegistry
from ceph_tpu.msg import reset_local_namespace
from ceph_tpu.osd.daemon import OSDDaemon
from ceph_tpu.osd.ec_backend import (
    META_CACHE_OBJECTS,
    MISS,
    VERSION_ATTR,
    ECBackend,
    ECObjectMeta,
    ECWriteDegraded,
    LocalShard,
    ShardReadError,
)
from ceph_tpu.osd.pg import PGId, object_to_ps
from ceph_tpu.store import CollectionId, MemStore, Transaction
from ceph_tpu.vstart import DevCluster

K, M = 4, 2
N = K + M


class CountingShard(LocalShard):
    """A LocalShard that counts version getattrs, and can hold them
    (``gate``) or fail its writes (``fail_writes``)."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.version_getattrs = 0
        self.gate: asyncio.Event | None = None
        self.fail_writes = False
        self.block_writes: asyncio.Event | None = None

    async def get_attr(self, oid, name):
        if name == VERSION_ATTR:
            self.version_getattrs += 1
        try:
            value = await super().get_attr(oid, name)
        except KeyError as e:
            value = e
        if self.gate is not None:
            gate, self.gate = self.gate, None
            await gate.wait()
        if isinstance(value, KeyError):
            raise value
        return value

    async def write_shard(self, oid, offset, data, attrs, log=None):
        if self.fail_writes:
            raise IOError(f"shard {self.shard}: injected write failure")
        if self.block_writes is not None:
            await self.block_writes.wait()
        return await super().write_shard(oid, offset, data, attrs, log)


def _backend(strict: bool = False, resident: bool = False) -> ECBackend:
    codec = ErasureCodePluginRegistry().factory(
        "jax_rs", {"k": str(K), "m": str(M), "technique": "cauchy_good"})
    shards = {}
    for i in range(N):
        store = MemStore()
        cid = CollectionId(1, 0, shard=i)
        asyncio.run(store.queue_transactions(
            Transaction().create_collection(cid)))
        shards[i] = CountingShard(store, cid, pool=1, shard=i)
    return ECBackend(codec, shards, stripe_unit=128,
                     log_hook=(lambda *a: None) if strict else None,
                     resident=resident)


def _getattrs(be: ECBackend) -> int:
    return sum(s.version_getattrs for s in be.shards.values())


def _counts(be: ECBackend) -> tuple[int, int, int]:
    return (_getattrs(be), int(be.perf.value("ec_meta_cache_hit")),
            int(be.perf.value("ec_meta_cache_miss")))


def _stored_max_version(be: ECBackend, oid: str) -> int | None:
    """The max version any shard's store holds, read behind the
    backend's back."""
    best = None
    for s in be.shards.values():
        try:
            raw = s.store.getattr(s.cid, s._oid(oid), VERSION_ATTR)
        except KeyError:
            continue
        v = int(json.loads(raw)["version"])
        best = v if best is None else max(best, v)
    return best


@pytest.mark.parametrize("resident", [False, True],
                         ids=["store_reads", "resident_reads"])
def test_a_write_then_a_read_send_no_metadata_getattr(resident):
    be = _backend(resident=resident)

    async def run():
        await be.write("o", b"a" * 3000)          # fresh: one fan-in
        before = _counts(be)
        await be.write("o", b"b" * 3000)
        data = await be.read("o")
        return before, _counts(be), data

    (g0, h0, m0), (g1, h1, m1), data = asyncio.run(run())
    assert data == b"b" * 3000
    assert (h1 - h0, m1 - m0) == (2, 0)
    # a shard read from the store still checks its own version: the k
    # data shards' getattrs, and no metadata round
    assert g1 - g0 == (0 if resident else K)


@pytest.mark.parametrize("kind", ["writefull", "append", "create"])
def test_a_fresh_object_op_vector_fans_in_once(kind):
    be = _backend(strict=True)
    op = {"op": kind}
    if kind != "create":
        op["data"] = b"x" * 1000
    osd = SimpleNamespace(_stopped=False)
    pg = SimpleNamespace(backend=be, pgid="1.0", epoch=1)

    async def run():
        rc, _, version = await OSDDaemon._do_ops_ec(osd, pg, "fresh",
                                                     [op], "r1")
        return rc, version, _counts(be)

    rc, version, (getattrs, hits, misses) = asyncio.run(run())
    assert (rc, version) == (0, 1)
    assert getattrs == N            # one round, not two
    assert (hits, misses) == (1, 1)
    assert be.meta_cache.get("fresh") == ECObjectMeta(
        0 if kind == "create" else 1000, 1)


@pytest.mark.parametrize("failure", ["beyond_m", "cancelled", "degraded"])
def test_an_unsettled_write_leaves_no_entry(failure):
    be = _backend(strict=failure == "degraded")

    async def run():
        await be.write("o", b"1" * 2000)
        assert be.meta_cache.get("o") == ECObjectMeta(2000, 1)
        if failure == "beyond_m":
            for i in range(M + 1):
                be.shards[i].fail_writes = True
            with pytest.raises(ShardReadError):
                await be.write("o", b"2" * 2000)
        elif failure == "degraded":
            # a live shard misses the commit and its heal fails too
            be.shards[1].fail_writes = True
            with pytest.raises(ECWriteDegraded):
                await be.write("o", b"2" * 2000)
            # the background heal would fan in beside the read below
            for task in be._repair_tasks:
                task.cancel()
        else:
            hold = asyncio.Event()
            be.shards[N - 1].block_writes = hold
            task = asyncio.ensure_future(be.write("o", b"2" * 2000))
            while _stored_max_version(be, "o") != 2:
                await asyncio.sleep(0)
            task.cancel()
            with pytest.raises(asyncio.CancelledError):
                await task
            be.shards[N - 1].block_writes = None
        assert be.meta_cache.get("o") is MISS
        for s in be.shards.values():
            s.fail_writes = False
        before = _counts(be)
        meta = await be._read_meta("o")
        return before, _counts(be), meta

    (g0, h0, m0), (g1, h1, m1), meta = asyncio.run(run())
    assert (g1 - g0, h1 - h0, m1 - m0) == (N, 0, 1)
    assert meta.version == _stored_max_version(be, "o") == 2


@pytest.mark.parametrize("kind", ["remove", "set_attr"])
def test_remove_and_set_attr_update_the_entry(kind):
    be = _backend(strict=True)

    async def run():
        await be.write("o", b"z" * 1500)
        if kind == "remove":
            await be.remove("o")
        else:
            await be.set_attr("o", "_u_color", b"red")
        g0 = _getattrs(be)
        cached = await be._read_meta("o")
        assert _getattrs(be) == g0          # answered from the entry
        be.meta_cache.clear()
        return cached, await be._read_meta("o")

    cached, fanned_in = asyncio.run(run())
    assert cached == fanned_in
    assert cached == (None if kind == "remove"
                      else ECObjectMeta(1500, 2))


@pytest.mark.parametrize("mutation", ["write", "remove", "set_attr",
                                      "clear"])
def test_a_fill_overlapping_a_mutation_is_dropped(mutation):
    """Scrub and recovery read metadata outside the object lock: a
    fan-in that read the shards before a mutation settled must not
    overwrite the entry the mutation left."""
    be = _backend()

    async def run():
        await be.write("o", b"v" * 1000)
        be.meta_cache.clear()
        gate = asyncio.Event()
        for s in be.shards.values():
            s.gate = gate           # holds this fan-in's answers only
        late = asyncio.ensure_future(be._read_meta("o"))
        while any(s.gate is not None for s in be.shards.values()):
            await asyncio.sleep(0)
        if mutation == "write":
            await be.write("o", b"w" * 1000)
        elif mutation == "remove":
            await be.remove("o")
        elif mutation == "set_attr":
            await be.set_attr("o", "_u_k", b"v")
        else:
            be.meta_cache.clear()
        gate.set()
        return await late

    stale = asyncio.run(run())
    assert stale == ECObjectMeta(1000, 1)       # what the shards said
    want = {"write": ECObjectMeta(1000, 2), "remove": None,
            "set_attr": ECObjectMeta(1000, 2), "clear": MISS}[mutation]
    assert be.meta_cache.get("o") == want
    assert not be.meta_cache._fills


@pytest.mark.parametrize("objects", [META_CACHE_OBJECTS,
                                     META_CACHE_OBJECTS + 1])
def test_the_lru_holds_64_objects(objects):
    assert META_CACHE_OBJECTS == 64
    be = _backend()

    async def run():
        for i in range(objects):
            await be.write(f"o{i}", b"q" * 200)
        g0 = _getattrs(be)
        await be._read_meta(f"o{objects - 1}")
        hit_newest = _getattrs(be) - g0
        await be._read_meta("o0")
        return hit_newest, _getattrs(be) - g0

    newest, oldest = asyncio.run(run())
    assert len(be.meta_cache) == META_CACHE_OBJECTS
    assert newest == 0
    # the 65th object evicted the least recently used, o0
    assert oldest == (N if objects > META_CACHE_OBJECTS else 0)


@pytest.mark.parametrize("event", ["rebuilt", "activated"])
def test_a_rebuilt_backend_and_an_activated_pg_start_empty(event):
    async def run():
        reset_local_namespace()
        cluster = DevCluster(n_mons=1, n_osds=N)
        await cluster.start()
        try:
            rados = await cluster.client()
            r = await rados.mon_command(
                "osd erasure-code-profile set", name="mc",
                profile={"plugin": "jax_rs", "k": str(K), "m": str(M),
                         "crush-failure-domain": "osd"})
            assert r["rc"] == 0, r
            await rados.pool_create("mc", pg_num=4, pool_type="erasure",
                                    erasure_code_profile="mc")
            await cluster.wait_health_ok(timeout=120)
            io = await rados.open_ioctx("mc")
            await io.write_full("obj", b"m" * 5000)
            m = rados.monc.osdmap
            pool = next(p.pool_id for p in m.pools.values()
                        if p.name == "mc")
            ps = object_to_ps("obj", m.pools[pool].pg_num)
            osd = cluster.osds[m.pg_to_up_acting(pool, ps)[3]]
            pg = osd.pgs[PGId(pool, ps)]
            old = pg.backend
            assert old.meta_cache.get("obj") == ECObjectMeta(5000, 1)
            if event == "rebuilt":
                osd._make_backend(pg)       # what an interval change runs
                assert pg.backend is not old
            else:
                merge = osd._merge_log

                async def merge_after_a_fill(pg_, d):
                    # an answer learned during peering, before the
                    # log merge that may rewind it
                    pg_.backend.meta_cache.put("obj", ECObjectMeta(1, 99))
                    return await merge(pg_, d)

                osd._merge_log = merge_after_a_fill
                await osd._peer(pg)
                assert pg.state == "active" and pg.backend is old
            assert len(pg.backend.meta_cache) == 0
            misses = int(osd.perf.value("ec_meta_cache_miss"))
            assert await io.read("obj") == b"m" * 5000
            assert int(osd.perf.value("ec_meta_cache_miss")) == misses + 1
            await rados.shutdown()
        finally:
            await cluster.stop()
            reset_local_namespace()

    asyncio.run(run())
