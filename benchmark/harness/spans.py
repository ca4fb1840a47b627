"""The op path's own spans in the traced window: where the host's time
went while the chip waited.

The program (``ceph_tpu/common/tracing.py``) emits its spans into the
profiler capture the traced run takes, so they share the device trace's
clock.  ``host_spans`` reads those that overlap the window from the
same ``.xplane.pb`` that ``trace.summarize`` reduced (the newest under
``run.py``'s trace directory), once per summary.  The event loop that
runs the client, the messenger, the mon and every OSD is the host line
(thread) that holds the benchmark's window span.

A *cpu* span encloses a synchronous section, so on the loop's line it is
what the loop was doing; cpu spans nest, and a span's *self time* is its
duration less that of the cpu spans inside it, so each instant of the
loop counts once, for the innermost layer.  Wait spans enclose awaits
and overlap freely; they are read as means.  Every interval is clipped
to the window the device metrics use.  Against a program without these
spans every reader returns None and the gap label adds nothing.

    cd benchmark && python3 -m harness.spans [.trace | <file.xplane.pb>]

prints the traced window's ten longest device idle gaps, each with what
the host did in it (``GapLabeller``), one JSON pair per line.
"""

from __future__ import annotations

import bisect
import os

from harness.trace import WINDOW_SPAN, _union, find_xplane

# where run.py has the profiler write the traced run's capture
TRACE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".trace")
# the op path's span layers (ceph_tpu/common/tracing.py)
SPAN_PREFIXES = ("msgr:", "ec:", "store:", "osd:", "objecter:")

# cpu span -> its layer (the first word of the gap label's attribution)
CPU_LAYER = {
    "msgr:encode": "msgr", "msgr:frame_out": "msgr",
    "msgr:frame_in": "msgr",
    "ec:prep": "ec", "ec:h2d": "ec", "ec:d2h": "ec", "ec:hinfo": "ec",
    "store:apply": "store", "store:read": "store",
}
LAUNCH = "ec:launch"        # the codec call, on a worker thread


def read_host_spans(xplane_path: str, window_ns) -> list:
    """(line, name, start_ns, end_ns) of the window span and of every
    program span on a host plane that overlaps ``window_ns``, unclipped;
    ``line`` numbers the host lines (threads) of all host planes.  Empty
    when the file's window span is not ``window_ns``."""
    import jax

    data = jax.profiler.ProfileData.from_file(xplane_path)
    w0, w1 = window_ns
    out, window = [], None
    n = 0
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                a = ev.start_ns
                b = a + ev.duration_ns
                if ev.name == WINDOW_SPAN:
                    window = (a, b)
                elif not (ev.name.startswith(SPAN_PREFIXES)
                          and b > w0 and a < w1):
                    continue
                out.append((n, ev.name, a, b))
            n += 1
    return out if window == (w0, w1) else []


def load(s, xplane_path: str) -> None:
    """Read the program spans of ``s``'s window from ``xplane_path``."""
    s.host_spans = read_host_spans(xplane_path, s.window_ns)


def host_spans(s) -> list:
    """``read_host_spans`` of the summary's window, read on first use
    from the newest capture under ``TRACE_DIR``; empty without one."""
    if s is None:
        return []
    if getattr(s, "host_spans", None) is None:
        try:
            load(s, find_xplane(TRACE_DIR))
        except FileNotFoundError:
            s.host_spans = []
    return s.host_spans


def loop_line(s) -> int | None:
    """The host line that holds the window span."""
    for line, name, _, _ in host_spans(s):
        if name == WINDOW_SPAN:
            return line
    return None


def has_spans(s) -> bool:
    """True when the program emitted any span into the window."""
    return any(name != WINDOW_SPAN for _, name, _, _ in host_spans(s))


def _clip(spans, lo: float, hi: float):
    for name, a, b in spans:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            yield name, a, b


def loop_cpu_spans(s) -> list[tuple[str, float, float]]:
    """(name, start, end) of the loop line's cpu spans, clipped to the
    window, by start (outer before inner at equal starts)."""
    line = loop_line(s)
    w0, w1 = s.window_ns
    raw = [(n, a, b) for ln, n, a, b in host_spans(s)
           if ln == line and n in CPU_LAYER]
    return sorted(_clip(raw, w0, w1), key=lambda x: (x[1], -x[2]))


def self_ns(spans) -> dict[str, float]:
    """Self time per span name of properly nested spans sorted as
    ``loop_cpu_spans`` sorts them."""
    out: dict[str, float] = {}
    stack: list[tuple[str, float]] = []         # (name, end)
    for name, a, b in spans:
        while stack and stack[-1][1] <= a:
            stack.pop()
        out[name] = out.get(name, 0.0) + (b - a)
        if stack:
            parent = stack[-1][0]
            out[parent] = out.get(parent, 0.0) - (b - a)
        stack.append((name, b))
    return out


def layer_self_ns(spans) -> dict[str, float]:
    out: dict[str, float] = {}
    for name, ns in self_ns(spans).items():
        layer = CPU_LAYER[name]
        out[layer] = out.get(layer, 0.0) + ns
    return out


def union_ns(spans) -> float:
    return sum(b - a for a, b in _union([(a, b) for _, a, b in spans]))


def mean_ms(s, name: str) -> float | None:
    """Mean duration of the ``name`` spans that start in the window."""
    if s is None:
        return None
    w0, w1 = s.window_ns
    durs = [b - a for _, n, a, b in host_spans(s)
            if n == name and w0 <= a < w1]
    if not durs:
        return None
    return sum(durs) / len(durs) / 1e6


def client_mib(w) -> float:
    """Client bytes acknowledged by the window's ops, in MiB: the base
    ``ec_device_ms_per_MiB`` divides by."""
    return sum(o.nbytes for o in w.ops if o.ok) / (1 << 20)


def layer_ms_per_mib(w, layer: str) -> float | None:
    """Loop-thread self time of one layer's cpu spans per client MiB."""
    s = w.trace
    mib = client_mib(w)
    if not has_spans(s) or not mib:
        return None
    spans = loop_cpu_spans(s)
    if not any(CPU_LAYER[n] == layer for n, _, _ in spans):
        return None
    return layer_self_ns(spans).get(layer, 0.0) / 1e6 / mib


class GapLabeller:
    """What the host did in an idle gap of the device: the loop's layers
    by the share of the gap their cpu spans' self time covers, the
    ``unspanned`` rest, and the share the codec call's worker threads
    were busy."""

    def __init__(self, s):
        self.line = loop_line(s)
        self.on = has_spans(s)
        spans = loop_cpu_spans(s) if self.on else []
        self.spans = spans
        self.starts = [a for _, a, _ in spans]
        self.longest = max((b - a for _, a, b in spans), default=0.0)
        self.launches = _union([(a, b) for ln, n, a, b in host_spans(s)
                                if n == LAUNCH and ln != self.line]) \
            if self.on else []

    def __call__(self, a: float, b: float) -> str:
        if not self.on or b <= a:
            return ""
        i = bisect.bisect_left(self.starts, a - self.longest)
        j = bisect.bisect_left(self.starts, b)
        inside = list(_clip(self.spans[i:j], a, b))
        gap = b - a
        layers = sorted(layer_self_ns(inside).items(),
                        key=lambda kv: -kv[1])
        parts = [f"{layer} {100 * ns / gap:.0f}%" for layer, ns in layers
                 if ns > 0]
        parts.append(f"unspanned {100 * (1 - union_ns(inside) / gap):.0f}%")
        launch = sum(max(0.0, min(e, b) - max(s, a))
                     for s, e in self.launches)
        return (f"; loop: {', '.join(parts)}; launch thread "
                f"{100 * launch / gap:.0f}%")


def main(argv=None) -> int:
    import argparse
    import json

    from harness import trace

    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("capture", nargs="?", default=TRACE_DIR,
                   help="a .xplane.pb, or a directory holding one")
    path = p.parse_args(argv).capture
    if os.path.isdir(path):
        path = find_xplane(path)
    s = trace.summarize(path)
    load(s, path)
    label = GapLabeller(s)
    for text, secs in trace.breakdown(s, lambda a, b: label(a, b)[2:])[
            "idle_gaps"]:
        print(json.dumps([text, secs]))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
