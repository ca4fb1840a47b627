"""ec_host_loop_ms_per_MiB (program span; layer: EC backend host side
and host-device boundary, osd/ec_backend.py): self time of the ec:prep
(stripe split, padding, concatenation, shard slicing), ec:h2d, ec:d2h
and ec:hinfo (the shards' crc32c) spans on the event loop's thread,
clipped to the traced window, per MiB of client bytes acknowledged by
the window's ops.  Moves client_MiBps."""

from harness import spans


def read(w):
    return spans.layer_ms_per_mib(w, "ec")
