"""osd_op_mean_ms (program counter; layer: OSD op path, osd/daemon.py
do_op and the mClock scheduler): change of the OSDs' op_latency time sum
over the change of its count, summed over the live OSDs, across the
window.  Moves op_p95_ms."""


def read(w):
    d = w.delta("op_latency")
    if not d or d[1] <= 0:
        return None
    return 1e3 * d[0] / d[1]
