"""obj_wait_ms (program span; layer: OSD op path: object ordering,
osd/object_state.py as osd/daemon.py _do_ops_ec takes it): mean duration
of the osd:obj_wait spans that start in the traced window, from an op
vector's request for its object's reader/writer state to the grant; an
uncontended acquisition's span ends where it starts.  Moves op_p95_ms."""

from harness import spans


def read(w):
    return spans.mean_ms(w.trace, "osd:obj_wait")
