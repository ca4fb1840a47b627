"""device_idle_pct (device trace; layer: device): 100 x (1 - union of the
device-op intervals / the traced window), the window running from its
start until every op issued in it has completed.  Moves op_p95_ms."""


def read(w):
    s = w.trace
    if s is None or s.devices == 0 or s.window_s <= 0:
        return None
    return 100.0 * (1.0 - s.busy_s / s.window_s)
