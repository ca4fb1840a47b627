"""Metric arithmetic on synthetic windows, every reader found by name."""

import pytest

from harness import spec
from harness.cell import Op, Window
from harness.trace import TraceSummary

CFG = {"pool": {"profile": {"k": 8, "m": 4, "stripe_unit": 4096}}}
MIB = 1 << 20


def _op(kind, t0, t1, nbytes, key=0, ok=True):
    o = Op(kind, key, False)
    o.t0, o.t1, o.nbytes, o.ok = t0, t1, nbytes, ok
    return o


def _window(ops, c0=None, c1=None, **kw):
    return Window(cfg=CFG, mix={"prefix": "obj-"}, setup_s=42.5,
                  t_start=100.0, t_end=110.0, t_drained=111.0, ops=ops,
                  counters0=c0 or {}, counters1=c1 or {}, **kw)


def read(name, w):
    return spec.reader(name)(w)


def test_every_declared_metric_has_a_reader():
    bench = spec.benchmark()
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            assert callable(spec.reader(m["name"]))


def test_end_to_end():
    ops = [_op("write_full", 100 + i, 100.5 + i, 4 * MIB) for i in range(10)]
    ops.append(_op("write_full", 109.9, 110.4, 4 * MIB))  # acked after close
    ops.append(_op("write_full", 105, 105.1, 4 * MIB, ok=False))
    w = _window(ops)
    # 11 acknowledged writes of 4 MiB; the last op completed at 110.4
    assert read("client_MiBps", w) == pytest.approx(11 * 4 / 10.4)
    # 12 samples: nearest rank ceil(0.95 * 12) = 12th, the slowest
    assert read("op_p95_ms", w) == pytest.approx(500.0)
    assert read("setup_s", w) == 42.5


def test_client_rate_is_not_stepped_by_the_close():
    # 16 writes in flight complete together just after the close: the
    # rate takes them and their time, it does not drop them
    ops = [_op("write_full", 109.0, 110.2, 4 * MIB, key=i)
           for i in range(16)]
    assert read("client_MiBps", _window(ops)) == pytest.approx(
        16 * 4 / 10.2)
    assert read("client_MiBps", _window([])) is None


def test_p95_nearest_rank():
    ops = [_op("read", 100, 100 + i / 1000, 1) for i in range(1, 101)]
    assert read("op_p95_ms", _window(ops)) == pytest.approx(95.0)


def test_counter_metrics():
    c0 = {"op_latency": (10.0, 100), "ec_coalesce_ops": 50,
          "ec_coalesce_launches": 40, "ec_resident_h2d_bytes": 1000,
          "ec_resident_d2h_bytes": 0}
    c1 = {"op_latency": (14.0, 300), "ec_coalesce_ops": 80,
          "ec_coalesce_launches": 60, "ec_resident_h2d_bytes": 5000,
          "ec_resident_d2h_bytes": 6000}
    w = _window([_op("write_full", 100, 101, 4000)], c0, c1)
    assert read("osd_op_mean_ms", w) == pytest.approx(20.0)
    assert read("coalesce_ops_per_launch", w) == pytest.approx(1.5)
    assert read("hostdev_bytes_per_client_byte", w) == pytest.approx(2.5)
    # nothing to read: nothing returned
    assert read("coalesce_ops_per_launch", _window([], c0, c0)) is None
    assert read("osd_op_mean_ms", _window([], c0, c0)) is None


def test_loop_lag():
    w = _window([], lag=[i / 1000 for i in range(1, 201)])
    assert read("loop_lag_p99_ms", w) == pytest.approx(198.0)
    assert read("loop_lag_p99_ms", _window([])) is None


def _summary(events, w0=0, w1=10_000_000_000):
    s = TraceSummary(window_ns=(w0, w1), devices=1, events=events)
    s.busy_ns = sum(d for _, _, d in events)
    return s


def test_device_idle_and_roofline():
    peaks = {"hbm_bytes_per_s": 819e9}
    # 2 writes of 4 MiB: (8 + 4) rows of 512 KiB each = 12 MiB
    ops = [_op("write_full", 100, 101, 4 * MIB, key=i) for i in range(2)]
    # one degraded read whose lost shard is data (k + 1 rows), one parity
    ops += [_op("read", 100, 101, 4 * MIB, key=7),
            _op("read", 100, 101, 4 * MIB, key=8)]
    kernel = ('%_pallas_apply_words.1 = s32[4,131072] custom-call(s8[128,256]'
              ' %bm32.1, s32[8,131072] %words.1), '
              'custom_call_target="tpu_custom_call"')
    events = [(kernel, 0, 100_000), ("%fusion.3 = u8[524288] fusion()",
                                     200_000, 300_000)]
    w = _window(ops, trace=_summary(events), peaks=peaks,
                lost_shard={"obj-7": 3, "obj-8": 9})
    need = 2 * 12 * 512 * 1024 + 9 * 512 * 1024
    assert read("gf_kernel_roofline", w) == pytest.approx(
        100 * need / 819e9 / 100e-6)
    assert read("device_idle_pct", w) == pytest.approx(
        100 * (1 - 400_000 / 10e9))
    # 0.4 ms busy over 4 ops of 4 MiB
    assert read("ec_device_ms_per_MiB", w) == pytest.approx(0.4 / 16)
    # no kernel in the trace: the roofline says nothing, never 0
    w.trace = _summary([("fusion.3", 0, 10)])
    assert read("gf_kernel_roofline", w) is None
    w.trace = None
    assert read("device_idle_pct", w) is None
    assert read("ec_device_ms_per_MiB", w) is None


def test_unknown_device_kind_is_an_error():
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        spec.peaks("TPU v99")
