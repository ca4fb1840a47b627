"""EC cross-op coalescing: the CoalescedLauncher micro-batcher.

Concurrent in-flight ops must share device launches (the cfg6 perf
lever) WITHOUT observable semantic change: bit-identity with the
uncoalesced path over the corpus profiles, failure isolation (a poisoned
batchmate fails alone; shard-write failpoint injection mid-gather leaves
batchmates committed), cancelled-waiter cleanup, and the pow2 shape
bucketing keeping the applier/program cache bounded.
"""

import asyncio
import math

import numpy as np
import pytest

from ceph_tpu.common import failpoint as fp
from ceph_tpu.ec.registry import ErasureCodePluginRegistry
from ceph_tpu.osd.ec_backend import ECBackend, LocalShard
from ceph_tpu.store.memstore import MemStore
from ceph_tpu.store.object_store import Transaction
from ceph_tpu.store.types import CollectionId

# dense jax_rs profiles representative of the corpus matrix (PROFILES
# in ceph_tpu/ec/corpus.py); the wide-symbol + bit-schedule techniques
# ride the same engine entry points
COALESCE_PROFILES = [
    {"k": "4", "m": "2", "technique": "reed_sol_van"},
    {"k": "8", "m": "4", "technique": "reed_sol_van"},
    {"k": "8", "m": "3", "technique": "isa_vandermonde"},
    {"k": "10", "m": "4", "technique": "cauchy_good"},
    {"k": "5", "m": "2", "technique": "liberation", "w": "7"},
]


async def _backend(profile=None, unit=128, **kw):
    profile = profile or {"k": "4", "m": "2",
                          "technique": "reed_sol_van"}
    codec = ErasureCodePluginRegistry().factory("jax_rs", profile)
    align = getattr(codec, "get_alignment", lambda: 1)()
    unit = -(-unit // align) * align      # bit-schedule codecs need k*w
    store = MemStore()
    shards = {}
    for i in range(codec.get_chunk_count()):
        cid = CollectionId(1, 0, shard=i)
        await store.queue_transactions(
            Transaction().create_collection(cid)
        )
        shards[i] = LocalShard(store, cid, pool=1, shard=i)
    return ECBackend(codec, shards, stripe_unit=unit, **kw)


@pytest.fixture(autouse=True)
def _clean_failpoints():
    fp.fp_clear()
    yield
    fp.fp_clear()


@pytest.mark.parametrize(
    "profile", COALESCE_PROFILES,
    ids=lambda p: f"k{p['k']}m{p['m']}_{p['technique']}")
def test_coalesced_encode_decode_bit_identical(profile):
    """Concurrent ops through the coalescer produce byte-for-byte the
    results of direct per-op _encode_batch/_decode_batch calls."""
    async def run():
        be = await _backend(profile)
        rng = np.random.default_rng(11)
        k, chunk = be.k, be.sinfo.chunk_size
        batches = [
            np.asarray(rng.integers(0, 256, (b, k, chunk)), np.uint8)
            for b in (1, 3, 8, 5, 2, 16, 7, 1)
        ]
        # inflate inflight so the flusher genuinely parks + batches
        be._inflight_ops = len(batches) + 1
        try:
            coalesced = await asyncio.gather(*(
                be._coalesced_encode(s) for s in batches
            ))
        finally:
            be._inflight_ops = 0
        st = be.coalescer.stats()
        assert st["ops"] == len(batches)
        assert st["launches"] < len(batches), st  # genuinely coalesced
        for s, got in zip(batches, coalesced):
            want = await be._encode_batch(s)
            assert np.array_equal(np.asarray(got), np.asarray(want))

        # decode: batchmates share a launch only with the SAME
        # (survivors, todo) failure pattern
        full = [np.asarray(await be._encode_batch(s)) for s in batches]
        missing = [0, be.k]                  # one data + one parity
        avails = [
            {i: c[:, i] for i in range(be.n) if i not in missing}
            for c in full
        ]
        be._inflight_ops = len(avails) + 1
        try:
            decs = await asyncio.gather(*(
                be._coalesced_decode(a, missing) for a in avails
            ))
        finally:
            be._inflight_ops = 0
        for c, got in zip(full, decs):
            for w in missing:
                assert np.array_equal(np.asarray(got[w]), c[:, w])

    asyncio.run(run())


def test_64_concurrent_writes_share_launches():
    """The cfg6 claim, counter-verified: 64 concurrent 4 KiB writes to
    distinct objects run >= 8x fewer device launches than ops, and read
    back bit-identically."""
    async def run():
        be = await _backend()
        datas = {f"o{i}": bytes([i]) * 4096 for i in range(64)}
        await asyncio.gather(*(
            be.write(o, d) for o, d in datas.items()
        ))
        for o, d in datas.items():
            assert await be.read(o) == d
        dump = be.perf.dump()
        launches = dump["ec_coalesce_launches"]
        ops = dump["ec_coalesce_ops"]
        assert ops == 64
        assert launches <= ops / 8, (launches, ops)
        assert dump["ec_device_launches"] <= ops / 8
        # occupancy + wait instrumentation actually populated
        occ = dump["ec_coalesce_occupancy"]
        assert occ["avgcount"] == launches
        assert occ["sum"] == ops
        assert dump["ec_coalesce_wait_hist_us"]["count"] == 64

    asyncio.run(run())


def test_serial_writes_flush_immediately():
    """A solo writer never pays the micro-window: with one op in
    flight the launcher flushes at once (idle fast path)."""
    async def run():
        be = await _backend(coalesce_window_us=200_000.0)
        import time
        t0 = time.perf_counter()
        for i in range(5):
            await be.write("solo", bytes([i]) * 512)
        elapsed = time.perf_counter() - t0
        # 5 serial writes with a 200 ms window would take > 1s if the
        # idle fast path were broken
        assert elapsed < 1.0, elapsed
        assert be.coalescer.stats()["launches"] == 5

    asyncio.run(run())


def test_failpoint_shard_write_failure_mid_gather():
    """Failpoint-injected shard-write failures mid-gather must not leak
    across batchmates: every unaffected write commits and reads back
    bit-identically (an affected op may fail individually, never the
    batch)."""
    async def run():
        be = await _backend()
        fp.set_seed(5)
        fp.fp_set("ec.shard_write", "error", count=3)
        datas = {f"o{i}": bytes([i + 1]) * 4096 for i in range(32)}
        results = await asyncio.gather(*(
            be.write(o, d) for o, d in datas.items()
        ), return_exceptions=True)
        fp.fp_clear()
        failed = {o for o, r in zip(datas, results)
                  if isinstance(r, BaseException)}
        # injection hit at most 3 ops' gathers; lenient mode tolerates
        # up to m per-op failures, so usually zero ops fail outright
        assert len(failed) <= 3, failed
        for o, d in datas.items():
            if o in failed:
                continue
            assert await be.read(o) == d, o
        assert len(datas) - len(failed) >= 29

    asyncio.run(run())


def test_poisoned_batchmate_fails_alone():
    """A payload that poisons the batched launch (wrong row count) must
    fail only its own op — batchmates transparently solo-retry."""
    async def run():
        be = await _backend()
        rng = np.random.default_rng(3)
        chunk = be.sinfo.chunk_size
        good = np.asarray(
            rng.integers(0, 256, (4, be.k, chunk)), np.uint8)
        bad = np.asarray(
            rng.integers(0, 256, (2, be.k + 1, chunk)), np.uint8)
        be._inflight_ops = 3
        try:
            res = await asyncio.gather(
                be.coalescer.submit(("enc",), good, 4),
                be.coalescer.submit(("enc",), bad, 2),
                return_exceptions=True,
            )
        finally:
            be._inflight_ops = 0
        assert not isinstance(res[0], BaseException)
        want = await be._encode_batch(good)
        assert np.array_equal(np.asarray(res[0]), np.asarray(want))
        assert isinstance(res[1], BaseException), res[1]
        st = be.coalescer.stats()
        assert st["solo_retries"] == 2
        assert st["failed_ops"] == 1
        assert st["pending_ops"] == 0

    asyncio.run(run())


def test_cancelled_waiter_cleanup():
    """Cancelling a parked op drops it from the batch without failing
    batchmates, and leaves no pending state behind."""
    async def run():
        be = await _backend(coalesce_window_us=100_000.0)
        rng = np.random.default_rng(4)
        chunk = be.sinfo.chunk_size
        s1 = np.asarray(rng.integers(0, 256, (2, be.k, chunk)), np.uint8)
        s2 = np.asarray(rng.integers(0, 256, (3, be.k, chunk)), np.uint8)
        # hold the flush open: pretend more ops are in flight than are
        # parked, so only the (long) window could flush
        be._inflight_ops = 5
        t1 = asyncio.ensure_future(be._coalesced_encode(s1))
        t2 = asyncio.ensure_future(be._coalesced_encode(s2))
        await asyncio.sleep(0.05)
        assert not t1.done() and not t2.done()
        t2.cancel()
        with pytest.raises(asyncio.CancelledError):
            await t2
        # release the idle condition: parked == inflight -> flush now
        be._inflight_ops = 1
        be.coalescer.notify()
        out = await t1
        want = await be._encode_batch(s1)
        assert np.array_equal(np.asarray(out), np.asarray(want))
        st = be.coalescer.stats()
        assert st["cancelled_waiters"] == 1
        assert st["ops"] == 1               # the cancelled op never ran
        assert st["pending_ops"] == 0 and st["pending_stripes"] == 0
        be._inflight_ops = 0

    asyncio.run(run())


@pytest.mark.parametrize(
    "profile", COALESCE_PROFILES,
    ids=lambda p: f"k{p['k']}m{p['m']}_{p['technique']}")
def test_coalesced_write_batchmates_match_solo(profile, monkeypatch):
    """Device stripe batches of concurrent resident writes share one
    launch, and one jitted split call per batchmate hands it the shard
    streams and per-shard rows a solo launch gives it — the chunks of
    the host encode, shard by shard."""
    import jax.numpy as jnp

    from ceph_tpu.osd import ec_backend

    monkeypatch.setattr(ec_backend, "_SPLIT_SHAPES", set())

    async def run():
        be = await _backend(profile, resident=True)
        rng = np.random.default_rng(13)
        k, chunk, n = be.k, be.sinfo.chunk_size, be.n
        host = [np.asarray(rng.integers(0, 256, (b, k, chunk)), np.uint8)
                for b in (1, 3, 8, 2)]
        dev = [jnp.asarray(h) for h in host]
        be._inflight_ops = len(dev) + 1
        try:
            outs = await asyncio.gather(*(
                be._coalesced_encode_write(d) for d in dev))
        finally:
            be._inflight_ops = 0
        st = be.coalescer.stats()
        assert st["ops"] == len(dev) and st["launches"] == 1, st
        # one split program per batchmate size (solo splits come below)
        assert be.perf.value("ec_write_glue_compiles") == len(dev)
        for h, d, (streams, shards) in zip(host, dev, outs):
            chunks = np.asarray(await be._encode_batch(h))
            want = chunks.transpose(1, 0, 2).reshape(n, -1)
            solo_streams, solo_shards = (await be._encode_write([d]))[0]
            assert np.array_equal(np.asarray(streams), want)
            assert np.array_equal(np.asarray(solo_streams), want)
            assert len(shards) == len(solo_shards) == n
            for i in range(n):
                assert np.array_equal(np.asarray(shards[i]), want[i])
                assert np.array_equal(np.asarray(solo_shards[i]), want[i])

    asyncio.run(run())


def test_shape_buckets_bounded():
    """pow2 batch-dim bucketing: any mix of stripe counts up to max B
    compiles at most ceil(log2(max B)) + 1 encode shapes per codec
    (mesh_stats tracks the DISTINCT padded batch dims launched)."""
    async def run():
        be = await _backend(coalesce=False)
        rng = np.random.default_rng(9)
        chunk = be.sinfo.chunk_size
        max_b = 100
        for b in list(range(1, 33)) + [47, 63, 64, 65, 99, max_b]:
            s = np.asarray(
                rng.integers(0, 256, (b, be.k, chunk)), np.uint8)
            out = await be._encode_batch(s)
            assert out.shape == (b, be.n, chunk)   # sliced back
        buckets = be.mesh_stats["encode_buckets"]
        assert len(buckets) <= math.ceil(math.log2(max_b)) + 1, buckets
        assert all(bk & (bk - 1) == 0 for bk in buckets), buckets
        assert be.perf.dump()["ec_coalesce_pad_waste"] > 0

    asyncio.run(run())


def test_decode_grouping_by_failure_pattern():
    """Decode batchmates with DIFFERENT missing sets never share a
    launch (different decode matrices); same sets do."""
    async def run():
        be = await _backend()
        rng = np.random.default_rng(13)
        chunk = be.sinfo.chunk_size
        full = [
            np.asarray(await be._encode_batch(np.asarray(
                rng.integers(0, 256, (4, be.k, chunk)), np.uint8)))
            for _ in range(4)
        ]
        miss_a, miss_b = [0], [1]
        jobs = []
        for i, c in enumerate(full):
            missing = miss_a if i % 2 == 0 else miss_b
            avail = {j: c[:, j] for j in range(be.n)
                     if j not in missing}
            jobs.append((missing, c,
                         be._coalesced_decode(avail, missing)))
        base = be.coalescer.stats()["launches"]
        be._inflight_ops = len(jobs) + 1
        try:
            outs = await asyncio.gather(*(j[2] for j in jobs))
        finally:
            be._inflight_ops = 0
        launches = be.coalescer.stats()["launches"] - base
        assert launches == 2, launches      # one per failure pattern
        for (missing, c, _), got in zip(jobs, outs):
            for w in missing:
                assert np.array_equal(np.asarray(got[w]), c[:, w])

    asyncio.run(run())


def test_chaos_ec_pool_with_coalescing():
    """Seeded chaos over an ERASURE-CODED pool (coalescing on by
    default): the RadosModel oracle must verify with failpoint churn
    (msgr delay + recovery delay) interleaving with coalesced launches.

    Seed 3's plan arms failpoints without OSD kills: EC recovery of
    stray copies after kill/revive is a pre-existing vstart limitation
    (positions not re-announced) independent of coalescing — verified
    by running a kill seed with osd_ec_coalesce=false, which fails
    identically."""
    from ceph_tpu.msg import reset_local_namespace
    from ceph_tpu.testing import run_chaos

    reset_local_namespace()
    try:
        r = asyncio.run(run_chaos(seed=3, ec=True, n_batches=6))
    finally:
        reset_local_namespace()
    assert r["verified"]
    assert r["ops_done"] > 0 and r["checks"] > 0
    assert any(ev == "fp_set" for _, ev, _a in r["schedule"])
