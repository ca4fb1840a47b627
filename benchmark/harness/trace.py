"""Reduce a JAX profiler trace of the measured window to what the metrics
read: device-op intervals clipped to the window, their union (busy time),
and the idle gaps between them.

The window is the benchmark's own ``TraceAnnotation`` (``WINDOW_SPAN``)
on the host plane; device planes are those named ``/device:<kind>:<n>``
and their op line is ``XLA Ops``.  ``summarize`` takes the ``.xplane.pb``
path, so a small recorded trace checks the arithmetic on the CPU.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

WINDOW_SPAN = "ceph_tpu_bench:window"
OPS_LINE = "XLA Ops"


@dataclass
class TraceSummary:
    window_ns: tuple[int, int]
    devices: int
    # (name, start_ns, duration_ns) of every device op inside the window,
    # clipped to it, over all device planes
    events: list = field(default_factory=list)
    busy_ns: float = 0.0           # union of op intervals, mean per device
    gaps: list = field(default_factory=list)   # (start_ns, end_ns)

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) / 1e9

    @property
    def busy_s(self) -> float:
        return self.busy_ns / 1e9


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def _union(intervals: list[tuple[float, float]]):
    """Merged, sorted intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def summarize(xplane_path: str) -> TraceSummary:
    import jax

    data = jax.profiler.ProfileData.from_file(xplane_path)
    window = None
    device_lines = []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW_SPAN:
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
        elif plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_lines.append(line)
    if window is None:
        raise ValueError(f"{xplane_path}: no {WINDOW_SPAN!r} span")
    w0, w1 = window
    s = TraceSummary(window_ns=(w0, w1), devices=len(device_lines))
    merged_all = []
    for line in device_lines:
        spans = []
        for ev in line.events:
            a, b = max(ev.start_ns, w0), min(ev.start_ns + ev.duration_ns,
                                             w1)
            if b > a:
                s.events.append((ev.name, a, b - a))
                spans.append((a, b))
        merged = _union(spans)
        merged_all.append(merged)
        s.busy_ns += sum(e - a for a, e in merged)
    if device_lines:
        s.busy_ns /= len(device_lines)
    if len(merged_all) == 1:
        edges = [w0] + [x for iv in merged_all[0] for x in iv] + [w1]
        s.gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                  if edges[i + 1] > edges[i]]
    return s


def short_name(hlo: str) -> str:
    """An op's HLO text without layouts, cut to 120 characters."""
    return re.sub(r"\{[^{}]*\}", "", hlo)[:120]


def breakdown(s: TraceSummary, label_gap, top: int = 10) -> dict:
    """The device ops that took the most time and the longest idle gaps,
    each gap named by ``label_gap(start_ns, end_ns)``."""
    per_op: dict[str, float] = {}
    for name, _, dur in s.events:
        name = short_name(name)
        per_op[name] = per_op.get(name, 0.0) + dur / 1e9
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(s.gaps, key=lambda g: g[0] - g[1])[:top]
    return {"device_ops": [[n, v] for n, v in ops],
            "idle_gaps": [[label_gap(a, b), (b - a) / 1e9] for a, b in gaps]}
