"""store_loop_ms_per_MiB (program span; layer: object store,
store/memstore.py): self time of the store:apply (a shard transaction's
apply) and store:read (a shard read's copy) spans on the event loop's
thread, clipped to the traced window, per MiB of client bytes
acknowledged by the window's ops.  Moves client_MiBps."""

from harness import spans


def read(w):
    return spans.layer_ms_per_mib(w, "store")
