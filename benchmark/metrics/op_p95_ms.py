"""op_p95_ms (host clock): 95th percentile (nearest rank) of the latency,
issue to completion, of every op issued in the window, those that
completed after it closed included.  Exact samples, no histogram."""

import math


def read(w):
    lat = sorted(o.t1 - o.t0 for o in w.ops)
    if not lat:
        return None
    return 1e3 * lat[max(0, math.ceil(0.95 * len(lat)) - 1)]
