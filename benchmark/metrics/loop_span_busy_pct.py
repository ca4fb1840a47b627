"""loop_span_busy_pct (program span; layer: host event loop): 100 x the
union of the cpu spans (msgr, ec host side, store) on the event loop's
thread over the traced window, the window running from its start until
every op issued in it has completed.  What the rest of the loop's time
went to, the spans do not say.  Moves op_p95_ms."""

from harness import spans


def read(w):
    s = w.trace
    if not spans.has_spans(s) or s.window_s <= 0:
        return None
    cpu = spans.loop_cpu_spans(s)
    if not cpu:
        return None
    return 100.0 * spans.union_ns(cpu) / (s.window_ns[1] - s.window_ns[0])
