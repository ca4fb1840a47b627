"""subop_fanout_wait_ms (program span; layer: OSD op path: shard
fan-out, osd/ec_backend.py): mean duration of the osd:fanout spans that
start in the traced window.  On the primary, one per client op: from
the first shard sub-op sent to the last reply, the 12 shard commits of
a write, the shard reads of a read and the survivors a degraded read
fetches, before its decode.  Moves op_p95_ms."""

from harness import spans


def read(w):
    return spans.mean_ms(w.trace, "osd:fanout")
