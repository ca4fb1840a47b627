"""osd_queue_wait_ms (program span; layer: OSD op path: admission,
osd/daemon.py _handle_osd_op): mean duration of the osd:queue spans that
start in the traced window, from an op's arrival at its primary to its
"dispatched" mark: the client throttle and the mClock acquire.  Moves
op_p95_ms."""

from harness import spans


def read(w):
    return spans.mean_ms(w.trace, "osd:queue")
