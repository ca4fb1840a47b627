"""obj_wait_pct (program span; layer: OSD op path: object ordering,
osd/object_state.py): the share, in %, of the acquisitions of an
object's reader/writer state in the traced window that had to wait.

Each acquisition counts the OSD's obj_rw_acquires and emits one
osd:obj_wait span into the capture; one that waits also counts
obj_rw_waits and tags its span ``waited=1``.  The benchmark's counter
snapshot (harness/sut.py) does not take these two counters, so this
reads the same events from the spans that start in the window, in the
capture ``harness/spans.py`` reads.  Moves op_p95_ms."""

from harness import spans
from harness.trace import WINDOW_SPAN, find_xplane

SPAN = "osd:obj_wait"


def counts(s) -> tuple[int, int] | None:
    """(acquisitions, waits) of ``s``'s window, or None without a
    capture of that window."""
    import jax

    try:
        path = find_xplane(spans.TRACE_DIR)
    except FileNotFoundError:
        return None
    w0, w1 = s.window_ns
    window, acquires, waits = None, 0, 0
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW_SPAN:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                elif ev.name == SPAN and w0 <= ev.start_ns < w1:
                    acquires += 1
                    waits += dict(ev.stats).get("waited") == 1
    return (acquires, waits) if window == (w0, w1) else None


def read(w):
    if w.trace is None:
        return None
    got = counts(w.trace)
    if not got or not got[0]:
        return None
    return 100.0 * got[1] / got[0]
