"""msgr_loop_ms_per_MiB (program span; layer: messenger,
msg/messenger.py): self time of the msgr:encode, msgr:frame_out and
msgr:frame_in spans on the event loop's thread, clipped to the traced
window, per MiB of client bytes acknowledged by the window's ops.
Moves client_MiBps."""

from harness import spans


def read(w):
    return spans.layer_ms_per_mib(w, "msgr")
