"""coalesce_ops_per_launch (program counter; layer: EC backend coalescer,
osd/ec_backend.py CoalescedLauncher): change of ec_coalesce_ops over the
change of ec_coalesce_launches, summed over the live OSDs, across the
window.  Moves op_p95_ms: each launch serves that many ops."""


def read(w):
    ops, launches = w.delta("ec_coalesce_ops"), w.delta(
        "ec_coalesce_launches")
    if not launches:
        return None
    return ops / launches
