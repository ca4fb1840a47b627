"""Batched deep scrub (ECBackend.scrub_batch + osd/scrub.ScrubEngine).

The contract pinned here: the batched path's per-object verdicts are
BIT-EXACT with the per-object scrub oracle across codec families
(including mapped LRC, whose chunk_mapping interleaves parity between
data groups), a warm resident cache serves deep scrub with ZERO
host->device bytes, sweeps resume from the persisted cursor after a
mid-sweep restart, the SLO gate parks a sweep between batches, and the
``store.corrupt_shard`` failpoint injects deterministic at-rest rot."""

import asyncio

import numpy as np
import pytest

from ceph_tpu.common import failpoint as fp
from ceph_tpu.common.perf import PerfCounters
from ceph_tpu.ec.registry import ErasureCodePluginRegistry
from ceph_tpu.osd import pg_log
from ceph_tpu.osd.ec_backend import ECBackend, LocalShard
from ceph_tpu.osd.repair import RepairScheduler
from ceph_tpu.osd.scrub import SCRUB_COUNTERS, ScrubEngine, cursor_load
from ceph_tpu.store import CollectionId, GHObject, MemStore, Transaction

RS = {"k": "4", "m": "2", "technique": "reed_sol_van"}

CODECS = [
    ("jax_rs", RS),
    ("jax_rs", {"k": "3", "m": "2", "technique": "cauchy_good"}),
    ("clay", {"k": "4", "m": "2"}),
    # mapped layout: chunk_mapping DD__DD__ puts parity BETWEEN the
    # data groups, so storage order != codec order
    ("lrc", {"k": "4", "m": "2", "l": "3"}),
]


def _run(coro):
    return asyncio.run(coro)


@pytest.fixture(autouse=True)
def _clean_failpoints():
    fp.fp_clear()
    yield
    fp.fp_clear()


async def _backend(plugin="jax_rs", profile=RS, unit=128, **kw):
    codec = ErasureCodePluginRegistry().factory(plugin, dict(profile))
    align = getattr(codec, "get_alignment", lambda: 1)()
    unit = -(-unit // align) * align
    store = MemStore()
    shards = {}
    for i in range(codec.get_chunk_count()):
        cid = CollectionId(1, 0, shard=i)
        await store.queue_transactions(
            Transaction().create_collection(cid))
        shards[i] = LocalShard(store, cid, pool=1, shard=i)
    be = ECBackend(codec, shards, stripe_unit=unit, **kw)
    be._test_store = store
    return be


def _rot(be, name, shard, offset=5, mask=0x10):
    """One silent at-rest bit flip through the failpoint-gated store
    hook (the same injection surface the chaos drill uses)."""
    fp.fp_set("store.corrupt_shard", "error", count=1)
    flip = be._test_store.corrupt_shard(
        CollectionId(1, 0, shard=shard),
        GHObject(1, name, shard=shard),
        offset=offset, mask=mask)
    assert flip is not None, f"injection refused on {name}/{shard}"
    return flip


async def _write_corpus(be, nobj=6, seed=5):
    rng = np.random.default_rng(seed)
    datas = {}
    for i in range(nobj):
        size = 4096 if i % 2 else 2048      # two shard-length groups
        datas[f"o{i}"] = rng.integers(0, 256, size, np.uint8).tobytes()
        await be.write(f"o{i}", datas[f"o{i}"])
    return datas


# -- batched verdicts == per-object oracle ---------------------------------


@pytest.mark.parametrize("plugin,profile", CODECS)
def test_batched_reports_bit_exact_with_oracle(plugin, profile):
    """Every batched per-object report must EQUAL the per-object
    scrub's report — clean objects, a rotted data shard, and a rotted
    parity shard alike — across codec families."""

    async def run():
        be = await _backend(plugin, profile)
        datas = await _write_corpus(be)
        dshard = be.data_shards[0]
        pshard = next(i for i in range(be.n)
                      if i not in be.data_shards)
        _rot(be, "o1", dshard)
        _rot(be, "o2", pshard)
        names = sorted(datas)
        out = await be.scrub_batch(names)
        assert out["groups"] == 2           # two length buckets
        batched = out["reports"]
        for name in names:
            oracle = await be.scrub(name)
            assert batched[name] == oracle, (
                plugin, name, batched[name], oracle)
        assert batched["o0"]["clean"] and batched["o3"]["clean"]
        assert not batched["o1"]["clean"]
        assert dshard in batched["o1"]["crc_mismatch"]
        assert not batched["o2"]["clean"]
        assert pshard in (batched["o2"]["crc_mismatch"]
                          + batched["o2"]["parity_inconsistent"])

    _run(run())


def test_batched_launch_accounting_vs_oracle():
    """The whole point of batching: a uniform group verifies in 2
    launches (one coalesced re-encode + one fused verify) where the
    per-object oracle pays one launch per object."""

    async def run():
        be = await _backend()
        rng = np.random.default_rng(1)
        names = []
        for i in range(16):
            names.append(f"u{i}")
            await be.write(f"u{i}", rng.integers(
                0, 256, 4096, np.uint8).tobytes())
        l0 = be.perf.value("ec_scrub_launches")
        out = await be.scrub_batch(sorted(names))
        batched_launches = be.perf.value("ec_scrub_launches") - l0
        assert out["groups"] == 1
        assert batched_launches == 2
        l0 = be.perf.value("ec_scrub_launches")
        for n in names:
            await be.scrub(n)
        assert be.perf.value("ec_scrub_launches") - l0 == len(names)

    _run(run())


def test_batched_missing_shard_reported_not_stale():
    """A shard object deleted outright must surface as missing_shards
    (routed to repair), never conflated into stale_version."""

    async def run():
        be = await _backend()
        datas = await _write_corpus(be, nobj=2)
        store = be._test_store
        await store.queue_transactions(Transaction().remove(
            CollectionId(1, 0, shard=3), GHObject(1, "o1", shard=3)))
        rep = (await be.scrub_batch(sorted(datas)))["reports"]["o1"]
        assert rep["missing_shards"] == [3]
        assert rep["stale_version"] == []
        assert not rep["clean"]
        oracle = await be.scrub("o1")
        assert oracle["missing_shards"] == [3]
        assert oracle["stale_version"] == []

    _run(run())


# -- warm resident cache: deep scrub with zero H2D -------------------------


def test_warm_resident_scrub_zero_h2d():
    """Satellite 1: clean resident entries serve deep scrub version-
    matched — a warm scrub verifies the device copies with ZERO
    host->device bytes."""

    async def run():
        be = await _backend(resident=True)
        assert be.resident is not None
        datas = await _write_corpus(be, nobj=4)
        h2d0 = be.perf.value("ec_resident_h2d_bytes")
        reports = (await be.scrub_batch(sorted(datas)))["reports"]
        assert all(r["clean"] for r in reports.values())
        assert be.perf.value("ec_resident_h2d_bytes") - h2d0 == 0
        # evicted entries fall back to store reads — still clean, but
        # the cold path pays the transfer again
        be.resident.evict(target=0)
        reports = (await be.scrub_batch(sorted(datas)))["reports"]
        assert all(r["clean"] for r in reports.values())
        assert be.perf.value("ec_resident_h2d_bytes") > h2d0

    _run(run())


# -- ScrubEngine: conviction, sweep, repair --------------------------------


def test_convict_attribution_table():
    assert ScrubEngine.convict(
        {"crc_mismatch": [2], "parity_inconsistent": [4, 5]}) \
        == ([2], None)
    assert ScrubEngine.convict(
        {"stale_version": [1], "missing_shards": [3]}) == ([1, 3], None)
    # parity-only disagreement with hinfo: data shards crc-verified
    # clean, so the parity is the rot
    assert ScrubEngine.convict(
        {"parity_inconsistent": [5], "hinfo": True}) == ([5], None)
    # without hinfo an unattributable mismatch is REFUSED (repairing
    # would launder the corruption into fresh parity)
    shards, err = ScrubEngine.convict(
        {"parity_inconsistent": [4, 5], "hinfo": False})
    assert shards == [] and "unattributable" in err
    assert ScrubEngine.convict({"clean": True}) == ([], None)


def test_sweep_convicts_and_repairs_bit_identical():
    async def run():
        be = await _backend()
        datas = await _write_corpus(be, nobj=6)
        true_shards = {
            (o, s): await be.shards[s].read_shard(o)
            for o in datas for s in range(be.n)}
        _rot(be, "o1", 0)
        _rot(be, "o4", 5)
        perf = PerfCounters("t")
        # min_batch_objects=1: each chunk convicts a single object,
        # and the daemon's per-object fallback is not wired here
        engine = ScrubEngine(RepairScheduler(perf,
                                             min_batch_objects=1),
                             perf)
        res = await engine.sweep_pg(be, sorted(datas),
                                    batch_objects=3)
        assert res["objects"] == 6
        assert res["errors"] == 2
        assert res["repaired"] == 2
        flagged = {d["object"] for d in res["inconsistent"]}
        assert flagged == {"o1", "o4"}
        assert all(d["repaired"] for d in res["inconsistent"])
        # bit-identical repair: every shard stream byte-equal to the
        # pre-rot snapshot, and a second sweep is spotless
        for (o, s), raw in true_shards.items():
            assert await be.shards[s].read_shard(o) == raw, (o, s)
        res2 = await engine.sweep_pg(be, sorted(datas))
        assert res2["errors"] == 0
        assert engine.stats()["sweeps"] == 2
        assert perf.value("ec_scrub_repaired") == 2

    _run(run())


def test_sweep_pauses_while_slo_burning():
    """Satellite 3: the sweep parks between batches while the SLO gate
    is raised and resumes where it left off — one preempt counted per
    pause episode."""

    async def run():
        be = await _backend()
        datas = await _write_corpus(be, nobj=4)
        perf = PerfCounters("t")
        engine = ScrubEngine(RepairScheduler(perf), perf)
        engine.pause("slo")
        task = asyncio.ensure_future(
            engine.sweep_pg(be, sorted(datas), batch_objects=2))
        await asyncio.sleep(0.1)
        assert not task.done()
        assert engine.preempts == 1
        assert perf.value("ec_scrub_preempts") == 1
        engine.resume("slo")
        res = await asyncio.wait_for(task, 20)
        assert res["objects"] == 4 and res["errors"] == 0

    _run(run())


def test_sweep_cursor_resumes_after_restart():
    """Satellite 4: a sweep killed mid-flight leaves its cursor on the
    PG meta object; a fresh engine (the restarted OSD) resumes after
    the last verified chunk instead of rescanning, and a finished
    sweep clears the cursor."""

    class FlakyBackend:
        def __init__(self, be, fail_after):
            self.be = be
            self.calls = 0
            self.fail_after = fail_after

        async def scrub_batch(self, names):
            self.calls += 1
            if self.calls > self.fail_after:
                raise RuntimeError("osd died mid-sweep")
            return await self.be.scrub_batch(names)

    async def run():
        be = await _backend()
        store = be._test_store
        await store.queue_transactions(
            Transaction().create_collection(pg_log.meta_cid(1, 0)))
        datas = await _write_corpus(be, nobj=6)
        names = sorted(datas)
        perf = PerfCounters("t")
        engine = ScrubEngine(RepairScheduler(perf), perf, store=store)
        flaky = FlakyBackend(be, fail_after=1)
        with pytest.raises(RuntimeError):
            await engine.sweep_pg(flaky, names, epoch=3, pool=1,
                                  batch_objects=2)
        cur = cursor_load(store, 1, 0)
        assert cur == {"epoch": 3, "pos": names[1], "scanned": 2}

        # the restarted OSD: fresh engine, same store, same epoch
        engine2 = ScrubEngine(RepairScheduler(perf), perf, store=store)
        res = await engine2.sweep_pg(be, names, epoch=3, pool=1,
                                     batch_objects=2)
        assert engine2.resumes == 1
        assert res["objects"] == 6          # 2 carried + 4 rescanned
        assert res["errors"] == 0
        assert cursor_load(store, 1, 0) is None   # cleared when done

        # a NEW epoch invalidates a stale cursor: full rescan
        from ceph_tpu.osd.scrub import cursor_save
        await cursor_save(store, 1, 0, epoch=3, pos=names[3],
                          scanned=4)
        res = await engine2.sweep_pg(be, names, epoch=4, pool=1,
                                     batch_objects=2)
        assert engine2.resumes == 1         # did not resume
        assert res["objects"] == 6

    _run(run())


def test_scrub_counters_registered():
    be = _run(_backend())
    dump = be.perf.dump()
    for key in SCRUB_COUNTERS:
        assert key in dump, key


# -- the store failpoint ---------------------------------------------------


def test_corrupt_shard_failpoint_gating_and_determinism():
    async def run():
        be = await _backend()
        await _write_corpus(be, nobj=1)
        cid = CollectionId(1, 0, shard=0)
        oid = GHObject(1, "o0", shard=0)
        store = be._test_store
        before = store.read(cid, oid)

        # unarmed: inert, bytes untouched
        assert store.corrupt_shard(cid, oid) is None
        assert store.read(cid, oid) == before

        # armed with count: injects exactly that many times
        def flips(seed):
            fp.fp_clear()
            fp.set_seed(seed)
            fp.fp_set("store.corrupt_shard", "error", count=2)
            out = []
            for _ in range(3):
                out.append(store.corrupt_shard(cid, oid))
            return out

        got = flips(42)
        assert got[0] is not None and got[1] is not None
        assert got[2] is None               # count exhausted
        # un-rot (each flip is a single xor) and replay: the seeded
        # rng draws the SAME offsets and masks
        mutated = bytearray(store.read(cid, oid))
        for f in (got[1], got[0]):
            mutated[f["offset"]] ^= f["mask"]
        assert bytes(mutated) == before
        await store.queue_transactions(
            Transaction().write(cid, oid, 0, bytes(before)))
        replay = flips(42)
        assert [(f["offset"], f["mask"]) for f in got[:2]] == \
            [(f["offset"], f["mask"]) for f in replay[:2]]

    _run(run())
