#!/usr/bin/env python3
"""Record the small v5e trace the trace self-test reads.

    python3 benchmark/tests/record_trace.py   # on one TPU v5e

Inside the benchmark's window span it encodes, then decodes one lost
data chunk of, 4 MiB objects of the jax_rs k=8 m=4 codec a few times,
with host pauses between, and copies the ``.xplane.pb`` to
``tests/data/v5e_window.xplane.pb``.
"""

import glob
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(1, os.path.dirname(os.path.dirname(HERE)))


def main() -> int:
    import jax
    import jax.numpy as jnp

    from ceph_tpu.ec.registry import ErasureCodePluginRegistry
    from harness.trace import WINDOW_SPAN

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_trace: needs a TPU")
    ec = ErasureCodePluginRegistry().factory(
        "jax_rs", {"k": "8", "m": "4", "technique": "reed_sol_van"})
    data = jax.random.bits(jax.random.key(0), (128, 8, 4096), jnp.uint8)

    def step():
        chunks = ec.encode_chunks_device(data)
        avail = {i: chunks[:, i] for i in range(1, 12)}
        jax.block_until_ready(ec.decode_chunks_device(avail, [0]))

    step()
    d = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    with jax.profiler.TraceAnnotation(WINDOW_SPAN):
        for _ in range(3):
            step()
            time.sleep(0.02)
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)[0]
    dst = os.path.join(HERE, "data", "v5e_window.xplane.pb")
    shutil.copy(src, dst)
    print(f"{dst}: {os.path.getsize(dst)} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
