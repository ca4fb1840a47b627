"""ec_device_ms_per_MiB (device trace; layer: GF engine and kernels,
ec/engine.py and ec/pallas_kernels.py): device busy time (the union of
the device-op intervals in the traced window, mean over the chips) per
MiB of client bytes acknowledged by the window's ops.  The EC codec is
the only device work in these cells, so this is its whole device cost
per byte: the GF kernel and the byte/lane conversion glue, checksums and
copies around it, whatever the kernel's own roofline says.  Moves
client_MiBps."""


def read(w):
    s = w.trace
    client = sum(o.nbytes for o in w.ops if o.ok)
    if s is None or s.devices == 0 or s.busy_ns <= 0 or not client:
        return None
    return (s.busy_ns / 1e6) / (client / (1 << 20))
