"""gf_kernel_roofline (device trace; layer: GF engine and kernels,
ec/engine.py and ec/pallas_kernels.py): the least time the chip needs
for the window's erasure-code work over the time its Pallas GF kernels
ran, in %.

The least time is the bytes the algorithm must move over the peak HBM
bandwidth of peaks.json (the work is bound by bandwidth: a byte meets k
coefficients at most).  The bytes come from the ops, never from launch
shapes, so the same work reads the same whatever implements it: an
object of S bytes is padded to whole stripes of k chunks of stripe_unit
bytes; a write reads its k data rows and writes m parity rows; a read
whose lost shard (the killed OSD's position in the object's acting set)
is a data shard reads k surviving rows and writes the lost one.  The
kernel time is the summed device duration of the trace's Pallas GF
kernel events inside the window.  Moves client_MiBps.
"""

import math

# A v5e trace names each op by its HLO text; the Pallas GF kernels of
# ec/pallas_kernels.py are the tpu_custom_call of its _pallas_apply_*
# wrappers: "%_pallas_apply_words.1 = s32[4,131072]... custom-call(...)"
KERNEL_PREFIX = "%_pallas_apply"


def is_gf_kernel(name: str) -> bool:
    return name.startswith(KERNEL_PREFIX) and "tpu_custom_call" in name


def needed_bytes(w) -> int:
    p = w.profile
    k, m, unit = int(p["k"]), int(p["m"]), int(p["stripe_unit"])
    total = 0
    for o in w.ops:
        if not o.ok:
            continue
        row = math.ceil(o.nbytes / (k * unit)) * unit
        if o.op == "write_full":
            total += (k + m) * row
        elif o.op == "read":
            lost = w.lost_shard.get(f"{w.mix.get('prefix', 'obj-')}{o.key}")
            if lost is not None and lost < k:
                total += (k + 1) * row
    return total


def read(w):
    s = w.trace
    if s is None or not w.peaks:
        return None
    kernel_ns = sum(d for name, _, d in s.events if is_gf_kernel(name))
    need = needed_bytes(w)
    if kernel_ns <= 0 or need <= 0:
        return None
    least_s = need / float(w.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (kernel_ns / 1e9)
