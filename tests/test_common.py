"""Config registry, perf counters, logging, crc32c tests."""

import io

import pytest

from ceph_tpu.common import crc32c as crcmod
from ceph_tpu.common import log as logmod
from ceph_tpu.common.config import ConfigProxy, Level, Option
from ceph_tpu.common.perf import (
    CounterType,
    PerfCountersCollection,
)


# -- config --------------------------------------------------------------

def test_config_defaults_and_set():
    cfg = ConfigProxy()
    assert cfg.get("osd_pool_default_size") == 3
    cfg.set("osd_pool_default_size", "5")
    assert cfg.get("osd_pool_default_size") == 5


def test_config_validation():
    cfg = ConfigProxy()
    with pytest.raises(ValueError):
        cfg.set("osd_pool_default_size", "zero")
    with pytest.raises(ValueError):
        cfg.set("osd_pool_default_size", 0)  # min=1
    with pytest.raises(KeyError):
        cfg.set("no_such_option", 1)


def test_config_observers():
    cfg = ConfigProxy()
    seen = []
    cfg.observe("osd_heartbeat_grace", lambda n, v: seen.append((n, v)))
    cfg.set("osd_heartbeat_grace", 7.5)
    assert seen == [("osd_heartbeat_grace", 7.5)]


def test_config_sources_precedence(tmp_path, monkeypatch):
    conf = tmp_path / "conf.json"
    conf.write_text('{"cluster": "from-file", "osd_pool_default_size": 4}')
    monkeypatch.setenv("CEPH_TPU_CLUSTER", "from-env")
    cfg = ConfigProxy(conf_file=str(conf))
    assert cfg.get("cluster") == "from-env"  # env beats file
    assert cfg.get("osd_pool_default_size") == 4
    cfg.apply_central({
        "cluster": "from-mon",
        "osd_pool_default_size": 6,
        "unknown_is_skipped": 1,
    })
    # env outranks the central config db; file does not
    assert cfg.get("cluster") == "from-env"
    assert cfg.get("osd_pool_default_size") == 6
    show = cfg.show()
    assert show["cluster"]["source"] == "env"
    assert show["osd_pool_default_size"]["source"] == "mon"
    assert show["osd_heartbeat_grace"]["source"] == "default"


def test_config_register_subsystem_options():
    cfg = ConfigProxy()
    cfg.register([Option("my_opt", int, 9, "custom", Level.DEV)])
    assert cfg.get("my_opt") == 9


def test_config_resident_writeback_accepts_only_false():
    """Residency always writes through: osd_ec_resident_writeback loads
    as false and refuses true."""
    cfg = ConfigProxy(overrides={"osd_ec_resident_writeback": False})
    assert cfg.get("osd_ec_resident_writeback") is False
    with pytest.raises(ValueError):
        cfg.set("osd_ec_resident_writeback", True)
    with pytest.raises(ValueError):
        ConfigProxy(overrides={"osd_ec_resident_writeback": "true"})


def test_config_bool_parse():
    cfg = ConfigProxy()
    cfg.set("osd_ec_coalesce", "false")
    assert cfg.get("osd_ec_coalesce") is False
    cfg.set("osd_ec_coalesce", "yes")
    assert cfg.get("osd_ec_coalesce") is True


# -- perf ----------------------------------------------------------------

def test_perf_counters():
    coll = PerfCountersCollection()
    perf = coll.create("osd")
    perf.add("ops")
    perf.add("op_latency", CounterType.LONGRUNAVG)
    perf.inc("ops")
    perf.inc("ops", 4)
    perf.tinc("op_latency", 0.25)
    perf.tinc("op_latency", 0.75)
    d = coll.dump()["osd"]
    assert d["ops"] == 5
    assert d["op_latency"] == {"sum": 1.0, "avgcount": 2}


def test_perf_timer_and_histogram():
    coll = PerfCountersCollection()
    perf = coll.create("ec")
    perf.add("encode_lat", CounterType.LONGRUNAVG)
    with perf.time("encode_lat"):
        pass
    assert coll.dump()["ec"]["encode_lat"]["avgcount"] == 1
    h = coll.create_histogram("op_size", [64, 4096, 1 << 20])
    for v in (10, 100, 5000, 1 << 22):
        h.sample(v)
    assert coll.dump()["op_size_histogram"]["counts"] == [1, 1, 1, 1]


# -- log -----------------------------------------------------------------

def test_log_ring_and_gating():
    log = logmod.Dout("osd")
    logmod.set_level("osd", 1, gather=10)
    log.dout(5, "gathered but not emitted %d", 42)
    log.derr("boom")
    buf = io.StringIO()
    lines = logmod.dump_recent(file=buf)
    assert any("gathered but not emitted 42" in l for l in lines)
    assert any("boom" in l for l in lines)
    with pytest.raises(ValueError):
        logmod.Dout("nope")


# -- crc32c --------------------------------------------------------------

def test_crc32c_vector_and_chaining():
    assert crcmod.crc32c(0, b"123456789") == 0xE3069283
    a, b = b"foo", b"barbaz"
    assert crcmod.crc32c(crcmod.crc32c(0, a), b) == crcmod.crc32c(0, a + b)


def test_crc32c_python_fallback_matches_native():
    data = bytes(range(256)) * 7 + b"tail"
    native = crcmod._load_native()
    want = crcmod.crc32c(123, data)
    crcmod._native = False
    try:
        assert crcmod.crc32c(123, data) == want
    finally:
        crcmod._native = native


# -- FIFOCache -----------------------------------------------------------

def test_fifo_cache_eviction_and_overwrite():
    from ceph_tpu.common.cache import FIFOCache
    c = FIFOCache(max_entries=2)
    c.put("a", 1)
    c.put("b", 2)
    c.put("a", 10)          # overwrite must NOT evict "b"
    assert c.get("a") == 10 and c.get("b") == 2 and len(c) == 2
    c.put("c", 3)           # full: evicts oldest ("a")
    assert c.get("a") is None and c.get("b") == 2 and c.get("c") == 3
