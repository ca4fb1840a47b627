"""The object-ordering readers (obj_wait_ms, obj_wait_pct) on synthetic
spans and on a small CPU trace of the YCSB-A cell's harness path
(record_obj_wait_trace.py), whose OSD counters over the window are
recorded beside it."""

import json
import os
import shutil

import jax
import pytest

from harness import spans, spec, trace
from harness.cell import Op, Window
from harness.trace import WINDOW_SPAN, TraceSummary

DATA = os.path.join(os.path.dirname(__file__), "data")
CPU = os.path.join(DATA, "cpu_obj_wait.xplane.pb")
COUNTED = os.path.join(DATA, "cpu_obj_wait.json")
NAMES = ("obj_wait_ms", "obj_wait_pct")


def _window(s):
    o = Op("write_full", 1, False)
    o.t0, o.t1, o.nbytes, o.ok = 100.0, 100.5, 1000, True
    return Window(cfg={}, mix={}, setup_s=1.0, t_start=100.0, t_end=101.0,
                  t_drained=101.0, ops=[o], counters0={}, counters1={},
                  trace=s)


def _capture_dir(tmp_path, monkeypatch, src=CPU):
    """The recorded capture where run.py has the profiler write it."""
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    shutil.copy(src, d / "host.xplane.pb")
    monkeypatch.setattr(spans, "TRACE_DIR", str(tmp_path))


def _raw_spans():
    """(start, end, waited) of every osd:obj_wait span in the window,
    straight from the planes."""
    data = jax.profiler.ProfileData.from_file(CPU)
    events = [ev for plane in data.planes if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events]
    w = next(ev for ev in events if ev.name == WINDOW_SPAN)
    return [(ev.start_ns, ev.end_ns, dict(ev.stats)) for ev in events
            if ev.name == "osd:obj_wait"
            and w.start_ns <= ev.start_ns < w.end_ns]


def test_mean_wait_of_synthetic_spans():
    s = TraceSummary(window_ns=(100, 200), devices=0)
    s.host_spans = [(1, WINDOW_SPAN, 100, 200),
                    (1, "osd:obj_wait", 110, 110),        # uncontended
                    (1, "osd:obj_wait", 120, 4_000_120),  # waited 4 ms
                    (1, "osd:obj_wait", 90, 2_000_090)]   # started before
    assert spec.reader("obj_wait_ms")(_window(s)) == pytest.approx(2.0)


def test_recorded_trace_reads_the_counters_of_its_window(tmp_path,
                                                         monkeypatch):
    _capture_dir(tmp_path, monkeypatch)
    with open(COUNTED) as f:
        counted = json.load(f)
    s = trace.summarize(CPU)
    raw = _raw_spans()
    # one span per counted acquisition, waited=1 on each counted wait
    assert len(raw) == counted["obj_rw_acquires"] > 0
    assert sum(st["waited"] for _, _, st in raw) == counted["obj_rw_waits"]
    assert {st["mode"] for _, _, st in raw} <= {"r", "w"}
    assert all(st.get("reqid") and st.get("oid") for _, _, st in raw)
    w = _window(s)
    assert spec.reader("obj_wait_pct")(w) == pytest.approx(
        100.0 * counted["obj_rw_waits"] / counted["obj_rw_acquires"])
    assert spec.reader("obj_wait_ms")(w) == pytest.approx(
        sum(b - a for a, b, _ in raw) / len(raw) / 1e6)
    # an uncontended acquisition's span ends where it starts
    free = [b - a for a, b, st in raw if not st["waited"]]
    held = [b - a for a, b, st in raw if st["waited"]]
    assert max(free) < min(held)


def test_a_program_without_the_spans_reads_none(tmp_path, monkeypatch):
    # the parent's capture has no osd:obj_wait spans: both read nothing
    _capture_dir(tmp_path, monkeypatch,
                 os.path.join(DATA, "cpu_spans.xplane.pb"))
    s = trace.summarize(os.path.join(DATA, "cpu_spans.xplane.pb"))
    for name in NAMES:
        assert spec.reader(name)(_window(s)) is None, name
        assert spec.reader(name)(_window(None)) is None, name
    # no capture at all
    monkeypatch.setattr(spans, "TRACE_DIR", str(tmp_path / "none"))
    s = TraceSummary(window_ns=(0, 10), devices=0)
    for name in NAMES:
        assert spec.reader(name)(_window(s)) is None, name
    # a capture of another window
    _capture_dir(tmp_path / "other", monkeypatch)
    s = trace.summarize(CPU)
    s.window_ns = (s.window_ns[0], s.window_ns[1] + 1)
    assert spec.reader("obj_wait_pct")(_window(s)) is None
