"""ec_coalesce_wait_ms (program span; layer: EC backend coalescer,
osd/ec_backend.py CoalescedLauncher): mean duration of the
ec:coalesce_wait spans that start in the traced window, from an op
parking its stripes in the coalescer to the flush of its launch.
Moves op_p95_ms."""

from harness import spans


def read(w):
    return spans.mean_ms(w.trace, "ec:coalesce_wait")
