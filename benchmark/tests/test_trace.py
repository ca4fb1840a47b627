"""The trace reduction on a small recorded v5e trace (record_trace.py):
busy time, idle gaps and the GF kernel time agree with a direct count of
the raw events, and the roofline of the recorded work stays within the
chip's peak."""

import os

import jax
import pytest

from harness import spec, trace
from harness.cell import Op, Window

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "v5e_window.xplane.pb")
US = 1000


def _raw():
    """(window, device-op events) straight from the planes."""
    data = jax.profiler.ProfileData.from_file(DATA)
    window, events = None, []
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                a, d = int(ev.start_ns), int(ev.duration_ns)
                if ev.name == trace.WINDOW_SPAN:
                    window = (a, a + d)
                elif (plane.name.startswith("/device:")
                      and line.name == trace.OPS_LINE):
                    events.append((ev.name, a, d))
    return window, events


def test_busy_and_gaps_match_a_microsecond_timeline():
    s = trace.summarize(DATA)
    (w0, w1), events = _raw()
    assert s.window_ns == (w0, w1) and s.devices == 1
    busy = bytearray((w1 - w0) // US + 1)
    for _, a, d in events:
        lo, hi = max(a, w0), min(a + d, w1)
        for t in range((lo - w0) // US, (hi - w0) // US):
            busy[t] = 1
    assert s.busy_ns == pytest.approx(sum(busy) * US, abs=2 * US * len(
        events))
    gap_ns = sum(b - a for a, b in s.gaps)
    assert gap_ns + s.busy_ns == pytest.approx(w1 - w0, rel=1e-9)
    # the recording sleeps 20 ms between steps: the idle gaps show it
    assert max(b - a for a, b in s.gaps) > 15e6


def test_gf_kernel_roofline_of_the_recorded_work():
    s = trace.summarize(DATA)
    roofline = spec.reader("gf_kernel_roofline")
    is_gf = roofline.__globals__["is_gf_kernel"]
    kernels = [(n, d) for n, _, d in s.events if is_gf(n)]
    # three encodes and three decodes, one kernel launch each
    assert len(kernels) == 6, sorted({n for n, _, _ in s.events})
    ops = []
    for i in range(3):
        for kind in ("write_full", "read"):
            o = Op(kind, i, False)
            o.ok, o.nbytes = True, 4 << 20
            ops.append(o)
    w = Window(cfg={"pool": {"profile": {"k": 8, "m": 4,
                                          "stripe_unit": 4096}}},
               mix={"prefix": "obj-"}, setup_s=0, t_start=0, t_end=1,
               t_drained=1, ops=ops, counters0={}, counters1={}, trace=s,
               peaks=spec.peaks("TPU v5 lite"),
               lost_shard={f"obj-{i}": 0 for i in range(3)})
    need = 3 * (12 + 9) * (512 << 10)
    want = 100 * need / 819e9 / (sum(d for _, d in kernels) / 1e9)
    got = roofline(w)
    assert got == pytest.approx(want)
    assert 0 < got <= 100
    # the whole device cost per client MiB sees the kernels and the
    # conversion glue around them: at least the kernels' own time
    per_mib = spec.reader("ec_device_ms_per_MiB")(w)
    assert per_mib == pytest.approx(s.busy_ns / 1e6 / 24)
    assert per_mib >= sum(d for _, d in kernels) / 1e6 / 24
