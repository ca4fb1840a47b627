"""SLO engine + serving observability: known answers and e2e health.

Unit layer: exact burn-rate math on log2 bucket edges (thresholds are
placed ON an edge so frac_above is exact, not interpolated), sliding-
window snapshot eviction, raise/clear hysteresis, the error-rate and
rebuild-floor objective kinds, and the histogram guards the window
math depends on (mismatched-length merge, empty-quantile None,
clamped delta).

Exposition layer: label-value escaping per the Prometheus text format
and HELP/TYPE dedupe when several daemons export the same series.

Cluster layer: an ``osd.sub_op`` delay failpoint drags real write
latency over a declared ``put_p99_ms`` target — SLO_VIOLATION must
raise through mgr -> mon health naming the objective, then clear once
the window slides past the slow ops — and the burn-rate + utilization
gauges must ride the mgr's Prometheus scrape.
"""

import asyncio
import time

import pytest

from ceph_tpu.common import failpoint as fp
from ceph_tpu.common.perf import (
    HIST_BUCKETS,
    CounterType,
    PerfCounters,
    hist_delta,
    hist_frac_above,
    hist_merge,
    hist_quantile,
)
from ceph_tpu.common.slo import (
    SLOEngine,
    make_target,
    parse_slo_targets,
)
from ceph_tpu.msg import reset_local_namespace
from ceph_tpu.vstart import DevCluster


@pytest.fixture(autouse=True)
def _clean():
    reset_local_namespace()
    fp.fp_clear()
    fp.set_seed(0)
    yield
    fp.fp_clear()
    fp.set_seed(0)
    reset_local_namespace()


def _hist(samples):
    p = PerfCounters("t")
    p.add("h", CounterType.HISTOGRAM)
    for s in samples:
        p.hinc("h", float(s))
    return p.dump()["h"]


# -- target parsing ------------------------------------------------------
def test_make_target_parses_objective_families():
    t = make_target("put_p99_ms", 50.0)
    assert (t.kind, t.quantile, t.source) == \
        ("latency", 0.99, "op_w_latency_us")
    t = make_target("get_p999_ms", 200.0)
    assert (t.kind, t.quantile, t.source) == \
        ("latency", 0.999, "op_r_latency_us")
    t = make_target("op_p50_ms", 5.0)
    assert (t.kind, t.quantile, t.source) == \
        ("latency", 0.5, "op_latency_us")
    assert make_target("error_rate", 0.01).kind == "error_rate"
    assert make_target("rebuild_floor_gibs", 0.5).kind == "rebuild_floor"
    with pytest.raises(ValueError):
        make_target("bogus_objective", 1.0)

    ts = parse_slo_targets("put_p99_ms=50, get_p999_ms=200\nerror_rate=0.01")
    assert [t.objective for t in ts] == \
        ["put_p99_ms", "get_p999_ms", "error_rate"]
    assert parse_slo_targets("") == []


# -- histogram guards (the window math's foundations) --------------------
def test_hist_merge_tolerates_mismatched_bucket_counts():
    short = {"buckets": [1, 2], "sum": 3.0, "count": 3}
    full = _hist([4.0, 4.0])
    m = hist_merge(short, full)
    assert len(m["buckets"]) == HIST_BUCKETS
    assert m["count"] == 5
    assert m["buckets"][0] == 1 and m["buckets"][1] == 2
    assert m["buckets"][2] == 2          # both 4.0 samples, le=4


def test_hist_quantile_empty_is_none():
    assert hist_quantile({"buckets": [], "count": 0}, 0.5) is None
    assert hist_quantile({"buckets": [0] * HIST_BUCKETS, "count": 0},
                         0.99) is None
    # live-counter convenience wrapper still reports 0.0
    p = PerfCounters("x")
    p.add("h", CounterType.HISTOGRAM)
    assert p.quantile("h", 0.5) == 0.0


def test_hist_delta_is_clamped_elementwise_difference():
    prev = _hist([2.0, 500.0])
    cur = hist_merge(prev, _hist([2.0, 3000.0]))
    d = hist_delta(cur, prev)
    assert d["count"] == 2
    assert d["buckets"][1] == 1          # the new 2.0 sample
    assert sum(d["buckets"]) == 2
    # a counter reset (cur below prev) clamps to zero, never negative
    z = hist_delta(prev, cur)
    assert z["count"] == 0 and min(z["buckets"]) == 0


def test_hist_frac_above_exact_at_bucket_edges():
    # 90 samples in le=512, 10 in le=2048; 1024 is an empty edge bucket
    h = _hist([512.0] * 90 + [2048.0] * 10)
    assert hist_frac_above(h, 1024.0) == pytest.approx(0.1)
    assert hist_frac_above(h, 2048.0) == 0.0
    assert hist_frac_above(h, 0.5) == 1.0
    assert hist_frac_above({"buckets": [], "count": 0}, 10.0) == 0.0


# -- burn rate known answer ----------------------------------------------
def _observe_pair(eng, dumps0, dumps1, t0=0.0, t1=10.0):
    eng.observe(t0, dumps0)
    eng.observe(t1, dumps1)


def test_latency_burn_rate_known_answer():
    # target p99 <= 1.024ms; 10% of window samples above 1024us
    # => burn = 0.10 / (1 - 0.99) = exactly 10.0
    eng = SLOEngine([make_target("put_p99_ms", 1.024)],
                    raise_evals=1, clear_evals=1)
    bad = _hist([512.0] * 90 + [2048.0] * 10)
    _observe_pair(eng, {"osd.0": {"op_w_latency_us": _hist([])}},
                  {"osd.0": {"op_w_latency_us": bad}})
    (rec,) = eng.evaluate()
    assert rec["burn_rate"] == pytest.approx(10.0)
    assert rec["ok"] is False and rec["violating"] is True
    assert rec["worst_daemon"] == "osd.0"
    assert rec["samples"] == 100
    hc = eng.health_checks()["SLO_VIOLATION"]
    assert hc["severity"] == "HEALTH_WARN"
    assert "put_p99_ms" in hc["message"] and "osd.0" in hc["message"]
    assert any("put_p99_ms" in ln for ln in hc["detail"])
    g = eng.gauges()["put_p99_ms"]
    assert g["burn_rate"] == pytest.approx(10.0) and g["ok"] == 0.0


def test_latency_within_target_does_not_burn():
    eng = SLOEngine([make_target("put_p99_ms", 10.0)],
                    raise_evals=1, clear_evals=1)
    _observe_pair(eng, {"osd.0": {"op_w_latency_us": _hist([])}},
                  {"osd.0": {"op_w_latency_us": _hist([512.0] * 100)}})
    (rec,) = eng.evaluate()
    assert rec["ok"] is True and rec["burn_rate"] == 0.0
    assert eng.health_checks() == {}


# -- sliding window ------------------------------------------------------
def test_sliding_window_keeps_delta_base_at_trailing_edge():
    eng = SLOEngine([], window=10.0)
    for t in (0.0, 5.0, 12.0, 20.0):
        eng.observe(t, {"osd.0": {"op": t}})
    # 0.0 evicted (5.0 is still <= 20-10 so it becomes the base)
    assert [t for t, _ in eng._snaps] == [5.0, 12.0, 20.0]
    assert eng.window_span() == 15.0
    total, per = eng._window_scalar("op")
    assert total == 15.0 and per == {"osd.0": 15.0}


def test_hysteresis_raise_and_clear_eval_counts():
    eng = SLOEngine([make_target("put_p99_ms", 1.024)],
                    window=10.0, raise_evals=2, clear_evals=2)
    bad = _hist([2048.0] * 100)
    _observe_pair(eng, {"osd.0": {"op_w_latency_us": _hist([])}},
                  {"osd.0": {"op_w_latency_us": bad}})
    (r1,) = eng.evaluate()
    assert r1["ok"] is False and r1["violating"] is False   # 1 bad eval
    (r2,) = eng.evaluate()
    assert r2["violating"] is True                          # raised at 2
    assert "SLO_VIOLATION" in eng.health_checks()
    # window slides past the bad ops: zero-delta snapshots are good
    _observe_pair(eng, {"osd.0": {"op_w_latency_us": bad}},
                  {"osd.0": {"op_w_latency_us": bad}}, 30.0, 40.0)
    (g1,) = eng.evaluate()
    assert g1["ok"] is True and g1["violating"] is True     # 1 good eval
    (g2,) = eng.evaluate()
    assert g2["violating"] is False                         # cleared at 2
    assert eng.health_checks() == {}


# -- error rate + rebuild floor ------------------------------------------
def test_error_rate_objective():
    eng = SLOEngine([make_target("error_rate", 0.01)],
                    raise_evals=1, clear_evals=1)
    _observe_pair(eng, {"osd.0": {"op": 100, "op_error": 0}},
                  {"osd.0": {"op": 200, "op_error": 2}})
    (rec,) = eng.evaluate()
    assert rec["value"] == pytest.approx(0.02)
    assert rec["burn_rate"] == pytest.approx(2.0)
    assert rec["ok"] is False and rec["worst_daemon"] == "osd.0"


def test_rebuild_floor_objective_gated_on_recovery():
    eng = SLOEngine([make_target("rebuild_floor_gibs", 1.0)],
                    raise_evals=1, clear_evals=1)
    # 1 GiB rebuilt over a 2s window = 0.5 GiB/s, under the 1.0 floor
    _observe_pair(eng, {"osd.0": {"ec_repair_rebuild_bytes": 0}},
                  {"osd.0": {"ec_repair_rebuild_bytes": 1 << 30}},
                  0.0, 2.0)
    (idle,) = eng.evaluate(recovery_active=False)
    assert idle["ok"] is True and idle.get("idle") is True
    (rec,) = eng.evaluate(recovery_active=True)
    assert rec["value"] == pytest.approx(0.5)
    assert rec["burn_rate"] == pytest.approx(2.0)
    assert rec["ok"] is False and rec["worst_daemon"] == "osd.0"


# -- prometheus exposition ------------------------------------------------
def test_prom_escape_and_label():
    from ceph_tpu.services.mgr import prom_escape, prom_label

    assert prom_escape('a"b\nc\\d') == 'a\\"b\\nc\\\\d'
    assert prom_label(ceph_daemon="osd.0") == '{ceph_daemon="osd.0"}'
    assert prom_label(name='x"y\nz') == '{name="x\\"y\\nz"}'


def test_prometheus_text_dedupes_help_and_escapes_labels():
    from ceph_tpu.services.mgr import Mgr

    h = _hist([512.0, 2048.0])
    snapshot = {
        "status": {
            "health": {"status": "HEALTH_OK"},
            "osdmap": {"num_osds": 2, "num_up_osds": 2,
                       "num_in_osds": 2, "num_pools": 1},
            "mon": {"quorum": ["a"]},
        },
        "osds": {0: {"up": True, "in": True},
                 1: {"up": True, "in": True}},
        "osd_perf": {
            0: {"op": 10.0, "op_latency_us": h},
            1: {"op": 20.0, "op_latency_us": h},
        },
    }
    extra = {"ceph_slo_burn_rate": {
        "help": "burn",
        "samples": [('{objective="put_p99_ms"}', 10.0)],
    }}
    text = Mgr.prometheus_text(snapshot, extra)
    # every described metric appears once, even with 2 daemons
    for name in ("ceph_osd_op", "ceph_osd_op_latency_us",
                 "ceph_slo_burn_rate"):
        assert text.count(f"# HELP {name} ") == 1, name
        assert text.count(f"# TYPE {name} ") == 1, name
    # both daemons' series survive the dedupe
    assert 'ceph_osd_op{ceph_daemon="osd.0"} 10' in text
    assert 'ceph_osd_op{ceph_daemon="osd.1"} 20' in text
    assert text.count("_bucket{ceph_daemon=") == 2 * HIST_BUCKETS
    assert 'ceph_slo_burn_rate{objective="put_p99_ms"} 10' in text


# -- cluster e2e ---------------------------------------------------------
SLO_OVERRIDES = {
    "slo_put_p99_ms": 50.0,
    "slo_window": 1.5,
    "slo_raise_evals": 1,
    "slo_clear_evals": 1,
    "osd_heartbeat_interval": 0.1,
}


def test_slo_violation_health_raise_and_clear():
    async def run():
        cluster = DevCluster(n_mons=1, n_osds=3,
                             overrides=dict(SLO_OVERRIDES))
        await cluster.start()
        try:
            await cluster.start_mgr(report_interval=0.1)
            rados = await cluster.client()
            await rados.pool_create("slop", pg_num=4, size=3)
            ioctx = await rados.open_ioctx("slop")

            async def checks():
                r = await rados.mon_command("health detail")
                assert r["rc"] == 0, r
                return r["data"]["checks"]

            # healthy traffic: well under the 50ms target
            for i in range(10):
                await ioctx.write_full(f"ok{i}", b"x" * 512)
            await asyncio.sleep(0.3)
            assert "SLO_VIOLATION" not in await checks()

            # stall replica sub-ops: every write's p99 blows the target
            fp.fp_set("osd.sub_op", "delay", delay=0.3)
            deadline = asyncio.get_running_loop().time() + 15.0
            i = 0
            while True:
                await ioctx.write_full(f"slow{i}", b"y" * 512)
                i += 1
                c = await checks()
                if "SLO_VIOLATION" in c:
                    break
                assert asyncio.get_running_loop().time() < deadline, c
                await asyncio.sleep(0.05)
            v = c["SLO_VIOLATION"]
            assert v["severity"] == "HEALTH_WARN"
            assert "put_p99_ms" in v["message"]
            assert "burning" in v["message"]
            assert any("worst daemon" in ln for ln in v["detail"])

            # failpoint cleared: once the window slides past the slow
            # ops the objective goes good and the check clears
            fp.fp_clear("osd.sub_op")
            deadline = asyncio.get_running_loop().time() + 15.0
            while True:
                await ioctx.write_full("fast", b"z" * 512)
                if "SLO_VIOLATION" not in await checks():
                    break
                assert asyncio.get_running_loop().time() < deadline
                await asyncio.sleep(0.1)
        finally:
            await cluster.stop()

    asyncio.run(run())


def test_slo_and_utilization_gauges_in_scrape():
    async def run():
        cluster = DevCluster(n_mons=1, n_osds=3,
                             overrides=dict(SLO_OVERRIDES))
        await cluster.start()
        try:
            mgr = await cluster.start_mgr(report_interval=0.1)
            rados = await cluster.client()
            await rados.pool_create("gaug", pg_num=4, size=3)
            ioctx = await rados.open_ioctx("gaug")
            for i in range(20):
                await ioctx.write_full(f"o{i}", b"x" * 4096)
                await ioctx.read(f"o{i}")
            await asyncio.sleep(0.5)     # two report cycles: window live

            snap = await mgr.collect()
            text = mgr.prometheus_text(snap, mgr.prometheus_extra())
            assert 'ceph_slo_burn_rate{objective="put_p99_ms"}' in text
            assert 'ceph_slo_ok{objective="put_p99_ms"} 1' in text
            # CPU backend: no published HBM peak, share not measured
            assert "ceph_util_roofline_pct" not in text
            assert "ceph_util_device_gibps" in text
            assert "ceph_util_rebuild_gibps" in text
            assert "ceph_util_client_p99_ms" in text
            # per-daemon histogram series feed the same scrape
            assert "ceph_osd_op_w_latency_us_bucket" in text

            # digest surfaces the same objectives for /api/slo
            digest = mgr.last_digest or {}
            objs = {o["objective"]
                    for o in digest.get("slo", {}).get("objectives", [])}
            assert "put_p99_ms" in objs
            util = digest.get("utilization", {})
            assert util.get("client_p99_ms", 0.0) > 0.0
        finally:
            await cluster.stop()

    asyncio.run(run())
