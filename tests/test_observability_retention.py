"""Retention-layer observability: known answers and e2e wiring.

Unit layer: tsdb downsample tier math (sum/count/min/max carried so
merges are EXACT — pinned against hand-computed buckets), ring
eviction at capacity, tier selection; the delta-collect wire protocol
(resync on ack mismatch, removed keys, stale-delta rejection, byte
accounting through the one payload meter); per-class multiwindow
burn-pair hysteresis on a synthetic clock; and the device-kernel
profiler whose attribution totals must reconcile EXACTLY with the
perf counters the launch paths already increment.

Cluster layer: a 3-OSD vstart under classed load — ``mgr.ts_query``
series are monotone, class-labeled histograms reach the dumps, the
delta collect ships fewer bytes than its own full resync, and the
``ts status`` digest rollup reaches the mon.
"""

import asyncio
import json

import numpy as np
import pytest

from ceph_tpu.common.perf import CounterType, PerfCounters
from ceph_tpu.common.perf_collect import (
    DeltaCollectDecoder,
    DeltaCollectEncoder,
    payload_bytes,
)
from ceph_tpu.common.slo import (
    MultiWindowBurn,
    class_burn,
    make_target,
)
from ceph_tpu.common.tsdb import TSDB, agg_merge, Series
from ceph_tpu.ec.profiler import KernelProfiler, profiler_for, roofline_text
from ceph_tpu.msg import reset_local_namespace
from ceph_tpu.vstart import DevCluster


@pytest.fixture(autouse=True)
def _clean():
    reset_local_namespace()
    yield
    reset_local_namespace()


# -- tsdb tier math ------------------------------------------------------
def test_minute_tier_known_answer():
    s = Series("x", raw_points=100, m1_points=100, h1_points=10,
               tier1_s=60.0, tier2_s=3600.0)
    # two samples in minute [0,60), three in [60,120)
    for t, v in ((0.0, 4.0), (30.0, 2.0),
                 (60.0, 10.0), (70.0, 1.0), (110.0, 7.0),
                 (120.0, 0.0)):                   # rolls [60,120) closed
        s.observe(t, v)
    pts = s.tier_points("1m")
    # closed buckets carry exact (start, sum, count, min, max)
    assert pts[0] == (0.0, 6.0, 2, 2.0, 4.0)
    assert pts[1] == (60.0, 18.0, 3, 1.0, 10.0)
    # the open bucket is queryable without waiting for the boundary
    assert pts[2] == (120.0, 0.0, 1, 0.0, 0.0)


def test_agg_merge_is_exact_and_associative():
    a = (0.0, 6.0, 2, 2.0, 4.0)
    b = (60.0, 18.0, 3, 1.0, 10.0)
    c = (120.0, 5.0, 1, 5.0, 5.0)
    m = agg_merge(agg_merge(a, b), c)
    assert m == agg_merge(a, agg_merge(b, c))
    assert m == (0.0, 29.0, 6, 1.0, 10.0)
    # mean derived from sum/count, never stored: exact at any tier
    assert m[1] / m[2] == pytest.approx(29.0 / 6.0)


def test_hour_tier_merges_closed_minutes():
    s = Series("x", raw_points=10000, m1_points=100, h1_points=10,
               tier1_s=60.0, tier2_s=3600.0)
    # one sample per minute for 61 minutes: 60 closed minute buckets
    # fold into hour bucket 0, the 61st opens hour bucket 3600
    for i in range(62):
        s.observe(i * 60.0, float(i))
    h = s.tier_points("1h")
    assert h[0] == (0.0, sum(range(60)), 60, 0.0, 59.0)
    # bucket [3600, 7200) holds the closed minute 60 so far
    assert h[1] == (3600.0, 60.0, 1, 60.0, 60.0)


def test_raw_ring_evicts_at_capacity():
    s = Series("x", raw_points=4, m1_points=4, h1_points=4,
               tier1_s=60.0, tier2_s=3600.0)
    for i in range(10):
        s.observe(float(i), float(i))
    assert len(s.raw) == 4
    assert s.raw[0] == (6.0, 6.0)      # oldest retained
    assert s.evictions == 6


def test_window_start_mid_bucket_keeps_overlapping_buckets():
    # A store younger than the query window must still answer it: the
    # forensic lead-up asks for now-600s on clusters seconds old.
    db = TSDB(raw_points=720, m1_points=100, h1_points=10,
              tier1_s=60.0, tier2_s=3600.0)
    now = 3600.0 + 700.0               # 700s past an hour boundary
    for i in range(70):                # 700s of 10s feeds
        db.observe(3600.0 + i * 10.0, "s", float(i))
    # raw never wrapped -> it IS the full history; don't fall to a
    # coarser tier that would blur (or lose) the same data
    q = db.query("s", start=now - 600.0)
    assert q["tier"] == "raw"
    assert len(q["points"]) == 60      # the 600s window at 10s/feed
    # an explicit aggregate tier keeps the open bucket even though its
    # START (the hour boundary, now-700) predates the window start
    qh = db.query("s", start=now - 600.0, tier="1h")
    assert len(qh["points"]) == 1
    assert qh["points"][0][0] == 3600.0


def test_tier_selection_and_query_slicing():
    db = TSDB(raw_points=4, m1_points=100, h1_points=10,
              tier1_s=60.0, tier2_s=3600.0)
    for i in range(100):
        db.observe(i * 30.0, "s", float(i))
    # raw retains only the last 4 points; an old start falls to 1m
    q = db.query("s", start=0.0)
    assert q["tier"] == "1m"
    assert q["points"][0][0] == 0.0
    # a recent start stays raw
    q2 = db.query("s", start=99 * 30.0 - 1)
    assert q2["tier"] == "raw"
    # explicit tier + end slicing
    q3 = db.query("s", end=59.0, tier="1m")
    assert [p[0] for p in q3["points"]] == [0.0]
    # unknown series: empty, not KeyError
    assert db.query("nope")["points"] == []


def test_max_series_drops_and_counts():
    db = TSDB(max_series=2)
    db.observe(0.0, "a", 1.0)
    db.observe(0.0, "b", 1.0)
    db.observe(0.0, "c", 1.0)          # over the catalog bound
    assert db.names() == ["a", "b"]
    assert db.stats()["dropped_series"] == 1
    # non-numeric values are ignored, not crashed on
    db.observe(0.0, "a", "not-a-number")
    assert len(db.query("a")["points"]) == 1


# -- delta-encoded collect -----------------------------------------------
def test_delta_collect_roundtrip_and_resync_on_ack_mismatch():
    enc, dec = DeltaCollectEncoder(), DeltaCollectDecoder()
    d1 = {"op": 1, "idle": 5, "h": {"buckets": [1, 0], "sum": 2.0,
                                    "count": 1}}
    p1 = enc.encode(d1, dec.epoch)
    assert p1["full"] and dec.decode(p1) == d1

    d2 = dict(d1, op=2)
    p2 = enc.encode(d2, dec.epoch)
    assert not p2["full"] and list(p2["changed"]) == ["op"]
    assert dec.decode(p2) == d2
    # delta payload is smaller than the full it replaces
    assert payload_bytes(p2) < payload_bytes(p1)

    # mgr restart: a fresh decoder acks 0 -> encoder must full-resync
    dec2 = DeltaCollectDecoder()
    d3 = dict(d2, op=3)
    p3 = enc.encode(d3, dec2.epoch)
    assert p3["full"] and dec2.decode(p3) == d3
    assert enc.full_sends == 2 and enc.delta_sends == 1

    # removed keys propagate
    d4 = {k: v for k, v in d3.items() if k != "idle"}
    p4 = enc.encode(d4, dec2.epoch)
    assert p4["removed"] == ["idle"] and dec2.decode(p4) == d4


def test_delta_collect_drops_stale_out_of_order_delta():
    enc, dec = DeltaCollectEncoder(), DeltaCollectDecoder()
    dec.decode(enc.encode({"op": 1}, dec.epoch))
    p_delta = enc.encode({"op": 2}, dec.epoch)
    dec.decode(p_delta)
    # replaying the old delta after state moved on must be a no-op
    # (concurrent collects can reorder decode), and the unchanged ack
    # then forces a resync instead of silent corruption
    assert dec.decode(p_delta) == {"op": 2}
    assert dec.stale_drops == 1
    p_next = enc.encode({"op": 3}, 999)        # mismatched ack
    assert p_next["full"] and dec.decode(p_next) == {"op": 3}


# -- per-class multiwindow burn ------------------------------------------
def test_class_burn_known_answer():
    # threshold ON a log2 edge: 3 of 4 samples above 50ms, p99 target
    # => frac_above/allowed = 0.75/0.01 = 75, capped at 1000
    p = PerfCounters("t")
    p.add("h", CounterType.HISTOGRAM)
    for us in (1000.0, 100000.0, 100000.0, 100000.0):
        p.hinc("h", us)
    hist = p.dump()["h"]
    tgt = make_target("put_p99_ms", 50.0)
    assert class_burn(hist, [tgt]) == pytest.approx(75.0)
    # empty hist: zero burn, not a divide
    assert class_burn({"buckets": [], "count": 0}, [tgt]) == 0.0
    # worst latency objective wins
    t2 = make_target("op_p50_ms", 50.0)        # allowed=0.5 -> 1.5
    assert class_burn(hist, [tgt, t2]) == pytest.approx(75.0)


def test_multiwindow_burn_pair_hysteresis():
    mw = MultiWindowBurn(fast_s=300.0, slow_s=3600.0,
                         raise_evals=2, clear_evals=2)
    # one hot sample inside 5m but a cold hour: fast>1, slow<=1 -> no
    # violation (a brief spike cannot page)
    for i in range(11):
        mw.observe(i * 300.0, "gold", 0.0)
    mw.observe(3600.0, "gold", 12.0)
    rec = mw.evaluate(3600.0)["gold"]
    assert rec["fast_burn"] > 1.0 and rec["slow_burn"] <= 1.0
    assert not rec["burning"] and not rec["violating"]

    # sustained burn: both windows over 1.0, but the FIRST bad eval
    # must not raise (raise_evals=2)
    t = 3600.0
    for i in range(12):
        t += 300.0
        mw.observe(t, "gold", 5.0)
    r1 = mw.evaluate(t)["gold"]
    assert r1["burning"] and not r1["violating"]
    t += 300.0
    mw.observe(t, "gold", 5.0)
    r2 = mw.evaluate(t)["gold"]
    assert r2["violating"] and mw.worst() == "gold"

    # recovery: one good eval must not clear (clear_evals=2)
    t += 3600.0                    # slow window slides fully past
    mw.observe(t, "gold", 0.0)
    r3 = mw.evaluate(t)["gold"]
    assert not r3["burning"] and r3["violating"]
    t += 300.0
    mw.observe(t, "gold", 0.0)
    assert not mw.evaluate(t)["gold"]["violating"]
    assert mw.worst() is None


def test_multiwindow_burn_long_ago_incident_cannot_page():
    mw = MultiWindowBurn(fast_s=300.0, slow_s=3600.0, raise_evals=1)
    # heavy burn 50 min ago, quiet since: slow avg still >1 but the
    # fast window has recovered -> not burning
    for i in range(6):
        mw.observe(i * 100.0, "bronze", 30.0)
    for i in range(6, 36):
        mw.observe(i * 100.0, "bronze", 0.0)
    rec = mw.evaluate(3500.0)["bronze"]
    assert rec["slow_burn"] > 1.0 and rec["fast_burn"] <= 1.0
    assert not rec["burning"]


# -- device-kernel profiler ----------------------------------------------
def test_kernel_profiler_totals_and_registry():
    p = PerfCounters("osd.0")
    prof = profiler_for(p)
    assert profiler_for(p) is prof          # one profiler per counters
    prof.record("jaxrs-k4-m2:enc", 100.0, stripes=8, hbm_bytes=4096)
    prof.record("jaxrs-k4-m2:enc", 50.0, stripes=4, hbm_bytes=2048)
    prof.record("jaxrs-k4-m2:dec", 25.0, stripes=1, hbm_bytes=512)
    t = prof.totals()
    assert t == {"launches": 3, "stripes": 13, "wall_us": 175.0,
                 "hbm_bytes": 6656}
    d = prof.dump(peak_gibps=100.0)
    enc = d["jaxrs-k4-m2:enc"]
    assert enc["launches"] == 2 and enc["hbm_bytes"] == 6144
    assert enc["gibps"] > 0 and enc["roofline_pct"] > 0
    # a launch timed to its enqueue is counted, but no bandwidth is
    # derived for its signature: the table reads "not measured"
    prof.record("jaxrs-k4-m2:dec", 5.0, stripes=1, hbm_bytes=512,
                enqueue_only=True)
    prof.record("jaxrs-k4-m2:enc-dev", 7.0, stripes=2, hbm_bytes=1024,
                enqueue_only=True)
    assert prof.totals() == {"launches": 5, "stripes": 16,
                             "wall_us": 187.0, "hbm_bytes": 8192}
    d = prof.dump(peak_gibps=100.0)
    for sig in ("jaxrs-k4-m2:dec", "jaxrs-k4-m2:enc-dev"):
        assert d[sig]["enqueue_only"] >= 1
        assert "gibps" not in d[sig] and "roofline_pct" not in d[sig]
        assert roofline_text(d[sig].get("roofline_pct")) == "not measured"
    assert d["jaxrs-k4-m2:enc"]["enqueue_only"] == 0
    prof.reset()
    assert prof.totals()["launches"] == 0


@pytest.mark.parametrize("platform,kind,want", [
    ("cpu", "cpu", None),
    ("tpu", "TPU v5 lite", 819e9 / (1 << 30)),
    ("tpu", "TPU v99", KeyError),
])
def test_hbm_peak_by_device_kind(monkeypatch, platform, kind, want):
    """The roofline peak comes from the device kind: none off a TPU
    (share not measured), the published value for a v5e, and an error
    for a TPU the table does not know."""
    import types

    import jax

    from ceph_tpu.ec.profiler import hbm_peak_gibps, roofline_text

    dev = types.SimpleNamespace(platform=platform, device_kind=kind)
    monkeypatch.setattr(jax, "devices", lambda: [dev])
    if want is KeyError:
        with pytest.raises(KeyError, match="no published HBM peak"):
            hbm_peak_gibps()
        return
    assert hbm_peak_gibps() == want
    assert roofline_text(None) == "not measured"


def test_profiler_attribution_matches_launch_counters():
    """The acceptance reconciliation: drive a real ECBackend and the
    profiler's per-signature totals must equal the byte counter
    EXACTLY and account for the encode/decode launch wall time."""
    async def run():
        from ceph_tpu.ec.registry import ErasureCodePluginRegistry
        from ceph_tpu.osd.ec_backend import ECBackend, LocalShard
        from ceph_tpu.store.memstore import MemStore
        from ceph_tpu.store.object_store import Transaction
        from ceph_tpu.store.types import CollectionId

        codec = ErasureCodePluginRegistry().factory(
            "jax_rs", {"k": "2", "m": "1",
                       "technique": "reed_sol_van"})
        store = MemStore()
        shards = {}
        for i in range(3):
            cid = CollectionId(1, 0, shard=i)
            await store.queue_transactions(
                Transaction().create_collection(cid))
            shards[i] = LocalShard(store, cid, pool=1, shard=i)
        be = ECBackend(codec, shards, stripe_unit=128)
        rng = np.random.default_rng(0)
        datas = {}
        for i in range(8):
            datas[f"o{i}"] = rng.integers(
                0, 256, 1024, np.uint8).tobytes()
            await be.write(f"o{i}", datas[f"o{i}"])
        for name, want in datas.items():
            assert await be.read(name) == want

        prof = be.profiler
        d = prof.dump()
        assert d, "no kernel launches attributed"
        # every signature carries this backend's codec identity
        for sig in d:
            assert sig.startswith(be.codec_sig + ":"), sig
        # HBM bytes reconcile EXACTLY with the launch byte counter
        # (the profiler records the same increments at the same sites)
        assert prof.totals()["hbm_bytes"] == \
            be.perf.value("ec_launch_bytes")
        # wall time accounts for >=90% of the timed launch histograms
        dump = be.perf.dump()
        hist_wall = sum(
            dump[k]["sum"] for k in
            ("ec_encode_launch_us", "ec_decode_launch_us")
            if isinstance(dump.get(k), dict))
        assert hist_wall > 0
        assert prof.totals()["wall_us"] >= 0.9 * hist_wall
        # ec_kernels section shape (what daemon dumps ship)
        ek = prof.dump(peak_gibps=100.0)
        for rec in ek.values():
            assert {"launches", "stripes", "wall_us",
                    "hbm_bytes", "gibps",
                    "roofline_pct"} <= set(rec)

        # the device-resident write path times its launch to the
        # enqueue: still attributed, byte-exact, but not a bandwidth
        rbe = ECBackend(codec, shards, stripe_unit=128, resident=True)
        for i in range(4):
            await rbe.write(f"r{i}", datas[f"o{i}"])
        rk = rbe.profiler.dump(peak_gibps=100.0)
        enc = rk[rbe.codec_sig + ":enc"]
        assert enc["launches"] >= 1
        assert enc["enqueue_only"] == enc["launches"]
        assert "gibps" not in enc and "roofline_pct" not in enc
        assert rbe.profiler.totals()["hbm_bytes"] == \
            rbe.perf.value("ec_launch_bytes")

    asyncio.run(run())


# -- cluster e2e ---------------------------------------------------------
TS_OVERRIDES = {
    "slo_put_p99_ms": 50.0,
    "slo_window": 1.5,
    "slo_raise_evals": 1,
    "slo_clear_evals": 1,
    "osd_heartbeat_interval": 0.1,
    "slo_burn_fast_s": 1.0,
    "slo_burn_slow_s": 2.0,
}


def test_tsdb_e2e_classed_load_and_ts_query():
    async def run():
        cluster = DevCluster(n_mons=1, n_osds=3,
                             overrides=dict(TS_OVERRIDES))
        await cluster.start()
        try:
            mgr = await cluster.start_mgr(report_interval=0.1)
            rados = await cluster.client()
            await rados.pool_create("tsp", pg_num=4, size=3)
            ioctx = await rados.open_ioctx("tsp")
            from ceph_tpu.client.rados import op_class

            for i in range(15):
                with op_class("gold"):
                    await ioctx.write_full(f"g{i}", b"x" * 512)
                with op_class("bronze"):
                    await ioctx.write_full(f"b{i}", b"y" * 512)
            await asyncio.sleep(0.6)        # several report cycles

            # class-labeled histograms reached the daemon dumps
            snap = await mgr.collect()
            gold = sum(
                (c.get("op_class_gold_latency_us") or {})
                .get("count", 0)
                for c in snap["osd_perf"].values())
            assert gold > 0
            # ...and were recorded as tsdb series
            q = mgr.ts_query(name="class.gold.ops")
            vals = [p[1] for p in q["points"]]
            assert vals and max(vals) > 0
            # cumulative counters render as monotone series
            rq = mgr.ts_query(name="collect.resyncs")
            rvals = [p[1] for p in rq["points"]]
            assert rvals and rvals == sorted(rvals)
            # burn series exist for every declared objective
            assert mgr.ts_query(
                name="slo.put_p99_ms.burn")["points"]
            # delta collect: enabled, and a delta cycle ships fewer
            # bytes than the bootstrap full-resync cycle
            st = mgr.collect_stats
            assert st["delta"] and st["resyncs"] >= 3
            assert 0 < st["last_payload_bytes"] < \
                st["payload_bytes"]
            # catalog query + prefix query
            names = mgr.ts_query()["names"]
            assert any(n.startswith("util.") for n in names)
            pq = mgr.ts_query(prefix="collect.")
            assert "collect.payload_bytes" in pq["series"]

            # the digest rollup reaches the mon for `ceph-tpu top`
            r = await rados.mon_command("ts status")
            assert r["rc"] == 0
            ts = r["data"]["tsdb"]
            assert ts["stats"]["series"] > 0
            assert "tails" in ts and ts["tails"]
            # forensic capture attaches the lead-up series
            entry = await mgr.forensics_capture("manual-test")
            bundle = mgr.forensics_bundle(entry["id"])
            series = bundle["modules"]["ts"]["series"]
            assert any(n.startswith("slo.") for n in series)
        finally:
            await cluster.stop()

    asyncio.run(run())


def test_class_violation_names_tenant_class_in_health():
    async def run():
        from ceph_tpu.common import failpoint as fp

        cluster = DevCluster(n_mons=1, n_osds=3,
                             overrides=dict(TS_OVERRIDES))
        await cluster.start()
        try:
            await cluster.start_mgr(report_interval=0.1)
            rados = await cluster.client()
            await rados.pool_create("clsp", pg_num=4, size=3)
            ioctx = await rados.open_ioctx("clsp")
            from ceph_tpu.client.rados import op_class

            fp.fp_set("osd.sub_op", "delay", delay=0.3)
            try:
                deadline = asyncio.get_running_loop().time() + 20.0
                i = 0
                while True:
                    with op_class("gold"):
                        await ioctx.write_full(f"s{i}", b"x" * 512)
                    i += 1
                    r = await rados.mon_command("health detail")
                    c = r["data"]["checks"].get("SLO_VIOLATION")
                    if c and "tenant class gold" in c["message"]:
                        break
                    assert asyncio.get_running_loop().time() \
                        < deadline, c
                    await asyncio.sleep(0.05)
                assert any("tenant class gold" in ln
                           for ln in c["detail"])
            finally:
                fp.fp_clear("osd.sub_op")
        finally:
            await cluster.stop()

    asyncio.run(run())
