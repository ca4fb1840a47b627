"""The span readers (harness/spans.py and the seven program-span metrics)
on synthetic spans and on a small CPU trace of the write cell's harness
path (record_span_trace.py), and the existing metrics unchanged on the
recorded v5e trace."""

import json
import os
import shutil

import jax
import pytest

from harness import spans, spec, trace
from harness.cell import Op, Window
from harness.trace import WINDOW_SPAN, TraceSummary

DATA = os.path.join(os.path.dirname(__file__), "data")
CPU = os.path.join(DATA, "cpu_spans.xplane.pb")
V5E = os.path.join(DATA, "v5e_window.xplane.pb")
MIB = 1 << 20
US = 1000
NEW = ("osd_queue_wait_ms", "subop_fanout_wait_ms", "ec_coalesce_wait_ms",
       "msgr_loop_ms_per_MiB", "ec_host_loop_ms_per_MiB",
       "store_loop_ms_per_MiB", "loop_span_busy_pct")


def _ops(n, nbytes, failed=0):
    out = []
    for i in range(n + failed):
        o = Op("write_full", i, False)
        o.ok, o.nbytes = i < n, nbytes
        out.append(o)
    return out


def _window(s, ops):
    return Window(cfg={}, mix={}, setup_s=0, t_start=0, t_end=1,
                  t_drained=1, ops=ops, counters0={}, counters1={},
                  trace=s)


def _summary(host, window=(100, 200)):
    s = TraceSummary(window_ns=window, devices=1)
    s.host_spans = [(1, WINDOW_SPAN, window[0], window[1])] + host
    return s


# -- arithmetic on synthetic spans ---------------------------------------
def test_loop_thread_is_the_line_holding_the_window():
    s = _summary([(0, "store:apply", 110, 120),     # another thread
                  (1, "store:apply", 130, 135)])
    assert spans.loop_line(s) == 1
    assert spans.loop_cpu_spans(s) == [("store:apply", 130, 135)]


def test_spans_are_clipped_to_the_window():
    s = _summary([(1, "msgr:frame_in", 90, 110),     # 10 inside
                  (1, "msgr:encode", 195, 230),      # 5 inside
                  (1, "msgr:encode", 250, 260),      # outside
                  (1, "osd:queue", 90, 150),         # starts before
                  (1, "osd:queue", 150, 170)])
    w = _window(s, _ops(1, MIB))
    assert spec.reader("msgr_loop_ms_per_MiB")(w) == pytest.approx(15e-6)
    assert spec.reader("loop_span_busy_pct")(w) == pytest.approx(15.0)
    # a wait mean takes the spans that start in the window, whole
    assert spec.reader("osd_queue_wait_ms")(w) == pytest.approx(20e-6)


def test_self_time_subtracts_nested_cpu_spans():
    # ec:prep 100-160 holds ec:d2h 110-130 and a store apply 140-150
    # (a nested layer), then msgr frames back to back
    s = _summary([(1, "ec:prep", 100, 160), (1, "ec:d2h", 110, 130),
                  (1, "store:apply", 140, 150),
                  (1, "msgr:frame_in", 160, 170),
                  (1, "msgr:frame_out", 170, 175)])
    cpu = spans.loop_cpu_spans(s)
    assert spans.self_ns(cpu) == {"ec:prep": 30, "ec:d2h": 20,
                                  "store:apply": 10, "msgr:frame_in": 10,
                                  "msgr:frame_out": 5}
    assert spans.layer_self_ns(cpu) == {"ec": 50, "store": 10, "msgr": 15}
    assert spans.union_ns(cpu) == 75
    w = _window(s, _ops(2, MIB))
    assert spec.reader("ec_host_loop_ms_per_MiB")(w) == pytest.approx(
        50 / 1e6 / 2)
    assert spec.reader("store_loop_ms_per_MiB")(w) == pytest.approx(
        10 / 1e6 / 2)


def test_client_mib_base_counts_acknowledged_bytes():
    s = _summary([(1, "store:apply", 100, 140)])
    # 3 acknowledged 1 MiB writes; 2 failed ones do not count
    w = _window(s, _ops(3, MIB, failed=2))
    assert spans.client_mib(w) == 3
    assert spec.reader("store_loop_ms_per_MiB")(w) == pytest.approx(
        40 / 1e6 / 3)
    assert spec.reader("store_loop_ms_per_MiB")(_window(s, [])) is None


def test_gap_label_names_layers_unspanned_and_launch_thread():
    s = _summary([(1, "store:apply", 100, 140), (1, "msgr:encode", 150, 160),
                  (1, "ec:prep", 170, 200), (1, "ec:d2h", 180, 190),
                  (2, "ec:launch", 120, 145), (3, "ec:launch", 130, 150)])
    label = spans.GapLabeller(s)
    # gap 100-200: store 40, ec 30, msgr 10, unspanned 20; launch
    # threads busy 120-150 = 30
    assert label(100, 200) == ("; loop: store 40%, ec 30%, msgr 10%, "
                               "unspanned 20%; launch thread 30%")
    # a gap inside the ec:prep span: all of it is ec
    assert label(180, 190) == ("; loop: ec 100%, unspanned 0%; "
                               "launch thread 0%")


def test_a_program_without_spans_reads_none(tmp_path, monkeypatch):
    monkeypatch.setattr(spans, "TRACE_DIR", str(tmp_path))   # no capture
    s = _summary([])
    w = _window(s, _ops(4, MIB))
    for name in NEW:
        assert spec.reader(name)(w) is None, name
    assert spans.GapLabeller(s)(100, 200) == ""
    bare = TraceSummary(window_ns=(0, 10), devices=1)   # no host spans
    for name in NEW:
        assert spec.reader(name)(_window(bare, _ops(1, MIB))) is None
        assert spec.reader(name)(_window(None, _ops(1, MIB))) is None


# -- the recorded CPU trace ------------------------------------------------
def _raw_loop():
    """Window and the loop line's events straight from the planes: the
    line (counted over all host planes, as trace.summarize numbers
    them) that holds the window span."""
    data = jax.profiler.ProfileData.from_file(CPU)
    lines = [line for plane in data.planes
             if plane.name.startswith("/host:") for line in plane.lines]
    for i, line in enumerate(lines):
        for ev in line.events:
            if ev.name == WINDOW_SPAN:
                return (ev.start_ns, ev.end_ns), i, lines
    raise AssertionError("no window span")


def _timeline(events, w0, w1):
    """A 1 us bitmap of the window covered by the (start, end) events."""
    busy = bytearray(int((w1 - w0) // US) + 1)
    for a, b in events:
        lo, hi = max(a, w0), min(b, w1)
        for t in range(int((lo - w0) // US), int((hi - w0) // US)):
            busy[t] = 1
    return busy


def _recorded(path):
    s = trace.summarize(path)
    spans.load(s, path)
    return s


def test_host_spans_are_read_from_the_runs_capture(tmp_path, monkeypatch):
    # the readers find the capture where run.py has the profiler write it
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    shutil.copy(CPU, d / "host.xplane.pb")
    monkeypatch.setattr(spans, "TRACE_DIR", str(tmp_path))
    s = trace.summarize(CPU)
    assert spans.host_spans(s) == _recorded(CPU).host_spans
    assert spans.has_spans(s)
    # a capture of another window gives no spans
    other = TraceSummary(window_ns=(s.window_ns[0], s.window_ns[1] + 1),
                         devices=0)
    assert spans.host_spans(other) == []


def test_recorded_trace_readers_match_a_microsecond_timeline():
    s = _recorded(CPU)
    (w0, w1), loop, lines = _raw_loop()
    assert s.window_ns == (w0, w1) and spans.loop_line(s) == loop
    ops = _ops(80, 64 << 10)
    w = _window(s, ops)
    mib = 80 * 64 / 1024
    evs = [(ev.name, ev.start_ns, ev.end_ns) for ev in lines[loop].events]
    n_ev = sum(1 for n, _, _ in evs if n in spans.CPU_LAYER)
    tol = 2 * US * n_ev
    # cpu spans nest only within their own layer on this path, so a
    # layer's self time is the time its spans cover
    for name, layer in (("msgr_loop_ms_per_MiB", "msgr"),
                        ("ec_host_loop_ms_per_MiB", "ec"),
                        ("store_loop_ms_per_MiB", "store")):
        bits = _timeline([(a, b) for n, a, b in evs
                          if spans.CPU_LAYER.get(n) == layer], w0, w1)
        got = spec.reader(name)(w)
        assert got == pytest.approx(sum(bits) * US / 1e6 / mib,
                                    abs=tol / 1e6 / mib), name
        assert got > 0, name
    bits = _timeline([(a, b) for n, a, b in evs if n in spans.CPU_LAYER],
                     w0, w1)
    busy = spec.reader("loop_span_busy_pct")(w)
    assert busy == pytest.approx(100 * sum(bits) * US / (w1 - w0),
                                 abs=100 * tol / (w1 - w0))
    assert 0 < busy < 100
    for name, span in (("osd_queue_wait_ms", "osd:queue"),
                       ("subop_fanout_wait_ms", "osd:fanout"),
                       ("ec_coalesce_wait_ms", "ec:coalesce_wait")):
        durs = [ev.duration_ns for line in lines for ev in line.events
                if ev.name == span and w0 <= ev.start_ns < w1]
        assert len(durs) >= 10, span
        assert spec.reader(name)(w) == pytest.approx(
            sum(durs) / len(durs) / 1e6)
    # the codec call ran on worker threads, never the loop
    launch = {ln for ln, n, _, _ in s.host_spans if n == spans.LAUNCH}
    assert launch and loop not in launch


def test_recorded_trace_gap_label_adds_up():
    s = _recorded(CPU)
    w0, w1 = s.window_ns
    text = spans.GapLabeller(s)(w0, w1)
    assert text.startswith("; loop: ") and "unspanned" in text
    loop = text.split("; loop: ")[1].split("; launch thread")[0]
    shares = [int(p.rsplit(" ", 1)[1].rstrip("%"))
              for p in loop.split(", ")]
    assert abs(sum(shares) - 100) <= len(shares)
    busy = spec.reader("loop_span_busy_pct")(_window(s, _ops(1, MIB)))
    unspanned = int(loop.rsplit("unspanned ", 1)[1].rstrip("%"))
    assert unspanned == pytest.approx(100 - busy, abs=1)


# -- the v5e trace: the existing metrics as they were ----------------------
PARENT = {       # read by the parent commit's harness on this window
    "client_MiBps": 6.857142857142857, "op_p95_ms": 500.0,
    "setup_s": 12.5, "loop_lag_p99_ms": 197.0, "osd_op_mean_ms": 125.0,
    "coalesce_ops_per_launch": 1.5, "hostdev_bytes_per_client_byte": 1.25,
    "gf_kernel_roofline": 22.162665769374495,
    "ec_device_ms_per_MiB": 1.0349718333333333,
    "device_idle_pct": 81.38514911594551,
}


def test_existing_metrics_read_as_at_the_parent():
    s = _recorded(V5E)
    ops = []
    for i in range(3):
        for kind in ("write_full", "read"):
            o = Op(kind, i, False)
            o.ok, o.nbytes, o.t0, o.t1 = True, 4 << 20, 1.0 + i, 1.5 + i
            ops.append(o)
    w = Window(
        cfg={"pool": {"profile": {"k": 8, "m": 4, "stripe_unit": 4096}}},
        mix={"prefix": "obj-"}, setup_s=12.5, t_start=0, t_end=4,
        t_drained=4, ops=ops,
        counters0={"op_latency": (1.0, 10), "ec_coalesce_ops": 5,
                   "ec_coalesce_launches": 2, "ec_resident_h2d_bytes": 0,
                   "ec_resident_d2h_bytes": 0},
        counters1={"op_latency": (3.5, 30), "ec_coalesce_ops": 29,
                   "ec_coalesce_launches": 18,
                   "ec_resident_h2d_bytes": 12 << 20,
                   "ec_resident_d2h_bytes": 18 << 20},
        lag=[0.001 * i for i in range(200)], trace=s,
        peaks=spec.peaks("TPU v5 lite"),
        lost_shard={f"obj-{i}": 0 for i in range(3)})
    for name, want in PARENT.items():
        assert spec.reader(name)(w) == want, name
    assert (s.busy_ns, len(s.gaps), s.devices, len(s.events)) == (
        24839324.0, 400, 1, 448)
    # no program spans in that recording: the new readers stay silent
    assert [n for _, n, _, _ in s.host_spans] == [WINDOW_SPAN]
    for name in NEW:
        assert spec.reader(name)(w) is None


def test_gap_listing_prints_the_ten_longest_gaps(capsys):
    assert spans.main([V5E]) == 0
    rows = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    s = trace.summarize(V5E)
    want = trace.breakdown(s, lambda a, b: "")["idle_gaps"]
    # no program spans in that recording: each gap is listed, unlabelled
    assert rows == want and len(rows) == 10
