"""hostdev_bytes_per_client_byte (program counter; layer: EC backend
host-device boundary, osd/ec_backend.py and store/device_cache.py):
change of ec_resident_h2d_bytes + ec_resident_d2h_bytes over the client
bytes of every op issued in the window.  Moves client_MiBps."""


def read(w):
    h2d, d2h = w.delta("ec_resident_h2d_bytes"), w.delta(
        "ec_resident_d2h_bytes")
    client = sum(o.nbytes for o in w.ops if o.ok)
    if h2d is None or d2h is None or not client:
        return None
    return (h2d + d2h) / client
