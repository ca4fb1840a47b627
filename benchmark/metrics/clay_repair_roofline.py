"""clay_repair_roofline (device trace; layer: CLAY repair kernel,
ec/plugins/clay.py's repair program and the grouped kernel of
ec/pallas_kernels.py): the least time the chip needs for the window's
CLAY sub-chunk rebuilds over the time its Pallas GF kernels ran, in %.

The least time is the bytes the rebuild must move over the peak HBM
bandwidth of peaks.json.  The bytes come from the ops, never from launch
shapes: an object of S bytes is padded to whole stripes of k chunks of
stripe_unit bytes, a shard row of row = ceil(S / (k x stripe_unit)) x
stripe_unit bytes; a read whose lost shard (the killed OSD's position in
the object's acting set) is a data shard reads the d helpers' repair
sub-chunks, 1/q of a row each (q = d - k + 1), and writes the lost row:
(d/q + 1) x row bytes.  The kernel time is the summed device duration of
the trace's Pallas GF kernel events inside the window.  A pool without
``d`` in its profile (no CLAY) reads None.  Moves client_MiBps.
"""

import math

# the Pallas GF kernels of ec/pallas_kernels.py are the tpu_custom_call
# of its _pallas_apply_* wrappers in a v5e trace's HLO names
KERNEL_PREFIX = "%_pallas_apply"


def is_gf_kernel(name: str) -> bool:
    return name.startswith(KERNEL_PREFIX) and "tpu_custom_call" in name


def needed_bytes(w) -> float:
    p = w.profile
    k, unit, d = int(p["k"]), int(p["stripe_unit"]), int(p["d"])
    q = d - k + 1
    total = 0.0
    for o in w.ops:
        if not (o.ok and o.op == "read"):
            continue
        lost = w.lost_shard.get(f"{w.mix.get('prefix', 'obj-')}{o.key}")
        if lost is not None and lost < k:
            row = math.ceil(o.nbytes / (k * unit)) * unit
            total += (d / q + 1) * row
    return total


def read(w):
    s = w.trace
    if s is None or not w.peaks or "d" not in w.profile:
        return None
    kernel_ns = sum(dur for name, _, dur in s.events if is_gf_kernel(name))
    need = needed_bytes(w)
    if kernel_ns <= 0 or need <= 0:
        return None
    least_s = need / float(w.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (kernel_ns / 1e9)
