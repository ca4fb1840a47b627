"""Per-object read/write ordering on an EC primary (ObjectContext::RWState).

The reader/writer state alone: shared reads, exclusive writes, FIFO
grants, re-entry, cancel cleanup and the uncontended fast path.  Then a
k=4 m=2 pool of 1,000 B objects takes zipfian reads and ``write_full``s
from 32 tasks of one client over a few hot keys.  A RadosModel-style
oracle keeps, per key, the newest version issued and the newest
acknowledged: no op may fail, every read's payload version lies between
the newest acknowledged at its issue and the newest issued at its
completion, and after the run every key's k+m stored shards equal the
benchmark's numpy reference encode of its newest acknowledged version.
"""

import asyncio
import importlib.util
import os
import struct

import numpy as np
import pytest

from ceph_tpu.common.perf import PerfCounters
from ceph_tpu.msg import reset_local_namespace
from ceph_tpu.osd.object_state import READ, WRITE, ObjectStates
from ceph_tpu.osd.pg import object_to_ps
from ceph_tpu.store.types import CollectionId, GHObject
from ceph_tpu.vstart import DevCluster

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference():
    path = os.path.join(ROOT, "benchmark", "harness", "reference.py")
    spec = importlib.util.spec_from_file_location("bench_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()
K, M, UNIT, POLY = 4, 2, 4096, 285
SIZE = 1000
HEADER = struct.Struct("<8sQQ")


@pytest.fixture(autouse=True)
def _clean_local():
    reset_local_namespace()
    yield
    reset_local_namespace()


def payload(key: int, version: int) -> bytes:
    body = np.random.default_rng([key, version]).bytes(SIZE - HEADER.size)
    return HEADER.pack(b"rwstate!", key, version) + body


def version_of(key: int, data: bytes):
    if len(data) != SIZE:
        return None
    magic, k, v = HEADER.unpack_from(data)
    if magic != b"rwstate!" or k != key or data != payload(key, v):
        return None
    return v


class Oracle:
    """Per key: the newest version issued and the newest acknowledged."""

    def __init__(self):
        self.issued: dict[int, int] = {}
        self.acked: dict[int, int] = {}
        self.failed: list[str] = []
        self.wrong: list[str] = []
        self.reads = 0

    async def write(self, io, key: int):
        v = self.issued.get(key, -1) + 1
        self.issued[key] = v
        try:
            await io.write_full(f"user{key}", payload(key, v))
        except Exception as e:
            self.failed.append(f"write user{key} v{v}: {e!r}")
            return
        self.acked[key] = max(self.acked.get(key, -1), v)

    async def read(self, io, key: int):
        lo = self.acked.get(key, -1)
        try:
            data = await io.read(f"user{key}")
        except Exception as e:
            self.failed.append(f"read user{key}: {e!r}")
            return
        hi = self.issued.get(key, -1)
        v = version_of(key, data)
        self.reads += 1
        if v is None or not lo <= v <= hi:
            self.wrong.append(f"read user{key}: v{v}, expected {lo}..{hi}")


async def _ec42_cluster(pg_num: int = 8):
    cluster = DevCluster(n_mons=1, n_osds=6,
                         overrides={"osd_ec_resident": True})
    await cluster.start()
    rados = await cluster.client()
    r = await rados.mon_command(
        "osd erasure-code-profile set", name="ycsb",
        profile={"plugin": "jax_rs", "technique": "reed_sol_van",
                 "k": str(K), "m": str(M), "stripe_unit": str(UNIT),
                 "crush-failure-domain": "osd"})
    assert r["rc"] == 0, r
    await rados.pool_create("ycsb", pg_num=pg_num, pool_type="erasure",
                            erasure_code_profile="ycsb")
    await cluster.wait_health_ok(timeout=120)
    io = await rados.open_ioctx("ycsb")
    pool = next(p.pool_id for p in rados.monc.osdmap.pools.values()
                if p.name == "ycsb")
    return cluster, rados, io, pool


def _stored_shards(cluster, rados, pool: int, name: str) -> dict:
    m = rados.monc.osdmap
    ps = object_to_ps(name, m.pools[pool].pg_num)
    out = {}
    for shard, osd in enumerate(m.pg_to_up_acting(pool, ps)[2]):
        try:
            out[shard] = cluster.osds[osd].store.read(
                CollectionId(pool, ps, shard),
                GHObject(pool, name, shard=shard))
        except (KeyError, FileNotFoundError):
            out[shard] = None
    return out


def test_zipfian_reads_and_updates_on_hot_keys_are_ordered():
    keys, tasks, per_task = 8, 32, 12
    w = 1.0 / np.arange(1, keys + 1) ** 0.99
    cdf = np.cumsum(w / w.sum())

    async def run():
        cluster, rados, io, pool = await _ec42_cluster()
        oracle = Oracle()
        try:
            await asyncio.gather(*(oracle.write(io, k)
                                   for k in range(keys)))
            assert not oracle.failed, oracle.failed

            async def worker(t: int):
                rng = np.random.default_rng([2500, t])
                for _ in range(per_task):
                    u_op, u_key = rng.random(2)
                    key = min(int(np.searchsorted(cdf, u_key)), keys - 1)
                    if u_op < 0.5:
                        await oracle.read(io, key)
                    else:
                        await oracle.write(io, key)

            await asyncio.gather(*(worker(t) for t in range(tasks)))
            shards = {k: _stored_shards(cluster, rados, pool, f"user{k}")
                      for k in range(keys)}
            await rados.shutdown()
        finally:
            await cluster.stop()
        return oracle, shards

    oracle, shards = asyncio.run(run())
    assert not oracle.failed, oracle.failed[:5]
    assert oracle.reads > 0 and not oracle.wrong, oracle.wrong[:5]
    for key, got in shards.items():
        want = REF.encode(payload(key, oracle.acked[key]), K, M, UNIT, POLY)
        assert [got[i] for i in range(K + M)] == want, (
            f"user{key}: stored shards are not v{oracle.acked[key]}")


# -- the reader/writer state alone ----------------------------------------

def _states():
    perf = PerfCounters("osd")
    return ObjectStates(perf), perf


def test_readers_share_the_state():
    async def body():
        table, perf = _states()
        gate = asyncio.Event()
        inside = []

        async def reader(i):
            async with table.lock("x", READ):
                inside.append(i)
                await gate.wait()

        tasks = [asyncio.ensure_future(reader(i)) for i in range(3)]
        await asyncio.sleep(0)
        assert inside == [0, 1, 2]      # all three hold it at once
        assert perf.value("obj_rw_acquires") == 3
        assert perf.value("obj_rw_waits") == 0
        gate.set()
        await asyncio.gather(*tasks)
        assert len(table) == 0

    asyncio.run(body())


@pytest.mark.parametrize("first", [READ, WRITE])
def test_a_writer_excludes_readers_and_writers(first):
    async def body():
        table, perf = _states()
        order = []

        async def hold(mode, tag, gate=None):
            async with table.lock("x", mode):
                order.append(f"{tag}+")
                if gate is not None:
                    await gate.wait()
                else:
                    await asyncio.sleep(0)
                order.append(f"{tag}-")

        gate = asyncio.Event()
        t1 = asyncio.ensure_future(hold(first, "a", gate))
        await asyncio.sleep(0)
        t2 = asyncio.ensure_future(hold(WRITE, "w"))
        t3 = asyncio.ensure_future(hold(READ, "r"))
        for _ in range(5):
            await asyncio.sleep(0)
        assert order == ["a+"]          # the writer waits on a, r on w
        gate.set()
        await asyncio.gather(t1, t2, t3)
        assert order == ["a+", "a-", "w+", "w-", "r+", "r-"]
        assert perf.value("obj_rw_acquires") == 3
        assert perf.value("obj_rw_waits") == 2
        assert len(table) == 0

    asyncio.run(body())


def test_a_queued_writer_goes_before_later_readers():
    async def body():
        table, _ = _states()
        order = []
        gate = asyncio.Event()

        async def hold(mode, tag, wait=False):
            async with table.lock("x", mode):
                order.append(tag)
                if wait:
                    await gate.wait()

        readers = [asyncio.ensure_future(hold(READ, f"r{i}", True))
                   for i in range(2)]
        await asyncio.sleep(0)
        writer = asyncio.ensure_future(hold(WRITE, "w"))
        await asyncio.sleep(0)
        late = [asyncio.ensure_future(hold(READ, f"late{i}"))
                for i in range(2)]
        for _ in range(5):
            await asyncio.sleep(0)
        assert order == ["r0", "r1"]    # late readers queue behind w
        gate.set()
        await asyncio.gather(*readers, writer, *late)
        assert order == ["r0", "r1", "w", "late0", "late1"]

    asyncio.run(body())


def test_a_waiter_cancelled_while_waiting_leaves_no_entry():
    async def body():
        table, _ = _states()
        gate = asyncio.Event()

        async def hold():
            async with table.lock("x", WRITE):
                await gate.wait()

        async def want(mode):
            async with table.lock("x", mode):
                pass

        holder = asyncio.ensure_future(hold())
        await asyncio.sleep(0)
        waiters = [asyncio.ensure_future(want(m)) for m in (WRITE, READ)]
        await asyncio.sleep(0)
        waiters[0].cancel()             # the head of the queue goes
        await asyncio.sleep(0)
        gate.set()
        await holder
        await waiters[1]                # the reader behind it still runs
        assert waiters[0].cancelled()
        assert len(table) == 0
        # a lone waiter cancelled: the table forgets the object
        holder = asyncio.ensure_future(hold())
        gate.clear()
        await asyncio.sleep(0)
        lone = asyncio.ensure_future(want(READ))
        await asyncio.sleep(0)
        lone.cancel()
        await asyncio.gather(lone, return_exceptions=True)
        gate.set()
        await holder
        assert len(table) == 0

    asyncio.run(body())


def test_an_uncontended_acquisition_does_not_suspend():
    async def body():
        table, perf = _states()
        guard = table.lock("x", WRITE)
        enter = guard.__aenter__()
        with pytest.raises(StopIteration):
            enter.send(None)            # ran to its end in one step
        assert table.held("x") == WRITE
        leave = guard.__aexit__(None, None, None)
        with pytest.raises(StopIteration):
            leave.send(None)
        assert len(table) == 0 and perf.value("obj_rw_waits") == 0

    asyncio.run(body())


def test_the_holding_task_re_enters_its_own_grant():
    async def body():
        table, perf = _states()
        async with table.lock("x", WRITE):
            # nested writes and reads of the same object in this task
            # re-enter the one grant
            async with table.lock("x", WRITE), table.lock("x", READ):
                assert table.held("x") == WRITE
        assert perf.value("obj_rw_acquires") == 1
        async with table.lock("x", READ):
            with pytest.raises(RuntimeError):
                async with table.lock("x", WRITE):
                    pass
        # a task started under the grant queues like any other
        order = []

        async def other():
            async with table.lock("x", READ):
                order.append("other")

        async with table.lock("x", WRITE):
            task = asyncio.ensure_future(other())
            for _ in range(3):
                await asyncio.sleep(0)
            order.append("holder")
        await task
        assert order == ["holder", "other"]
        assert len(table) == 0

    asyncio.run(body())


def test_a_shrinking_writefull_is_never_seen_as_enoent():
    """writefull of a shorter object is remove + write inside one op
    vector: a concurrent read sees the old or the new object, never
    none."""
    big, small = b"B" * 40_000, b"s" * SIZE

    async def run():
        cluster, rados, io, _ = await _ec42_cluster(pg_num=4)
        seen, errors = [], []
        try:
            await io.write_full("shrink", big)

            async def writer():
                for i in range(12):
                    await io.write_full("shrink", small if i % 2 == 0
                                        else big)

            async def reader():
                for _ in range(12):
                    try:
                        seen.append(await io.read("shrink"))
                    except Exception as e:
                        errors.append(repr(e))

            await asyncio.gather(writer(), writer(),
                                 *(reader() for _ in range(6)))
            await rados.shutdown()
        finally:
            await cluster.stop()
        return seen, errors

    seen, errors = asyncio.run(run())
    assert not errors, errors[:5]
    assert seen and all(d in (big, small) for d in seen)
