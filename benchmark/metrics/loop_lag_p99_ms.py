"""loop_lag_p99_ms (host clock; layer: the host event loop that runs the
client, the messenger and every OSD): 99th percentile (nearest rank) of
how late a 1 ms sleep wakes, sampled back to back by the benchmark's own
probe task from window start until every op has completed.  Moves
op_p95_ms: a stalled loop delays every op in flight."""

import math


def read(w):
    if not w.lag:
        return None
    s = sorted(w.lag)
    return 1e3 * s[max(0, math.ceil(0.99 * len(s)) - 1)]
