"""clay_helper_bytes_per_rebuilt_byte (program span; layer: EC backend:
CLAY sub-chunk rebuild, osd/ec_backend.py _reconstruct): the helper
bytes fed to the window's rebuilds over the bytes they rebuilt.

The EC primary emits one ec:repair span per rebuilt object, from its
plan to the rebuilt chunk, tagged ``helper_bytes`` (the bytes of the
chunks or sub-chunks the rebuild reads) and ``rebuilt_bytes``.  This
sums both tags over the spans that start in the traced window, in the
capture ``harness/spans.py`` reads.  CLAY k=8 m=4 d=11 reads d/q = 2.75
when every rebuild takes the helpers' repair sub-chunks, 8 when it
decodes from k whole chunks.  Moves client_MiBps."""

from harness import spans
from harness.trace import WINDOW_SPAN, find_xplane

SPAN = "ec:repair"


def totals(s) -> tuple[float, float] | None:
    """(helper bytes, rebuilt bytes) of ``s``'s window, or None without
    a capture of that window."""
    import jax

    try:
        path = find_xplane(spans.TRACE_DIR)
    except FileNotFoundError:
        return None
    w0, w1 = s.window_ns
    window, fed, rebuilt = None, 0.0, 0.0
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW_SPAN:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                elif ev.name == SPAN and w0 <= ev.start_ns < w1:
                    tags = dict(ev.stats)
                    fed += float(tags.get("helper_bytes", 0))
                    rebuilt += float(tags.get("rebuilt_bytes", 0))
    return (fed, rebuilt) if window == (w0, w1) else None


def read(w):
    if w.trace is None:
        return None
    got = totals(w.trace)
    if not got or not got[1]:
        return None
    return got[0] / got[1]
