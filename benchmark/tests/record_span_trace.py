#!/usr/bin/env python3
"""Record the small CPU trace the span readers' self-test reads.

    JAX_PLATFORMS=cpu python3 benchmark/tests/record_span_trace.py

Runs the write cell's harness path (``harness/cell.py``, traced) on a
small cluster on the CPU — 3 OSDs, jax_rs k=2 m=1, 64 KiB objects, 4 in
flight, a 0.4 s window — and copies the ``.xplane.pb`` of its window to
``tests/data/cpu_spans.xplane.pb``.  The program's op-path spans are in
it; device planes are not (the CPU has none).
"""

import asyncio
import glob
import os
import shutil
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(1, os.path.dirname(os.path.dirname(HERE)))

SEED = 3000000023


def small_write_cell():
    from harness import spec

    cfg = spec.load(f"{spec.BENCH_DIR}/configs/rados_bench.ec84.4m.json")
    cfg.update(osds=3, object_bytes=64 << 10, objects=16)
    cfg["pool"].update(pg_num=4)
    cfg["pool"]["profile"].update(k=2, m=1)
    mix = spec.traffic("write")
    mix.update(concurrency=4, warmup_s=0.3)
    return cfg, mix


def main() -> int:
    from harness import cell as cellmod
    from harness.sut import ClusterSUT, warm_codec

    cfg, mix = small_write_cell()
    warm_codec(cfg, mix)
    d = tempfile.mkdtemp()
    out = asyncio.run(cellmod.run(cfg, mix, SEED, 0.4, True,
                                  ClusterSUT(cfg), time.perf_counter(),
                                  None, trace_dir=d))
    src = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)[0]
    dst = os.path.join(HERE, "data", "cpu_spans.xplane.pb")
    shutil.copy(src, dst)
    ok = sum(o.ok for o in out["window"].ops)
    print(f"{dst}: {os.path.getsize(dst)} bytes, {ok} ops acknowledged")
    return 0


if __name__ == "__main__":
    sys.exit(main())
