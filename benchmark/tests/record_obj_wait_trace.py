#!/usr/bin/env python3
"""Record the small CPU trace the object-ordering readers' self-test
reads.

    JAX_PLATFORMS=cpu python3 benchmark/tests/record_obj_wait_trace.py

Runs the YCSB-A cell's harness path (``harness/cell.py``, traced) on a
small cluster on the CPU — 6 OSDs, jax_rs k=4 m=2, 1,000 B records, 24
keys, 32 threads, a 0.4 s window — and copies the ``.xplane.pb`` of its
window to ``tests/data/cpu_obj_wait.xplane.pb``.  Beside it,
``cpu_obj_wait.json`` holds the OSDs' ``obj_rw_acquires`` and
``obj_rw_waits`` over the same window, which the benchmark's counter
snapshot does not take: the numbers the spans must add up to.
"""

import asyncio
import glob
import json
import os
import shutil
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(1, os.path.dirname(os.path.dirname(HERE)))

SEED = 3000000025
COUNTERS = ("obj_rw_acquires", "obj_rw_waits")


def small_ycsb_cell():
    from harness import spec

    cfg = spec.load(f"{spec.BENCH_DIR}/configs/ycsb.ec42.1k.json")
    cfg.update(osds=6, objects=24)
    cfg["pool"].update(pg_num=8)
    mix = spec.traffic("ycsb_a")
    mix.update(warmup_s=0.3)
    return cfg, mix


def main() -> int:
    from harness import cell as cellmod
    from harness.sut import ClusterSUT, warm_codec

    class CountingSUT(ClusterSUT):
        def counters(self):
            out = super().counters()
            for key in COUNTERS:
                out[key] = sum(osd.perf.dump().get(key, 0)
                               for osd in self.cluster.osds.values())
            return out

    cfg, mix = small_ycsb_cell()
    warm_codec(cfg, mix)
    d = tempfile.mkdtemp()
    out = asyncio.run(cellmod.run(cfg, mix, SEED, 0.4, True,
                                  CountingSUT(cfg), time.perf_counter(),
                                  None, trace_dir=d))
    w = out["window"]
    bad = [c for c in out["checks"] if not cellmod.passed(c)]
    if bad:
        print(f"not correct: {bad}", file=sys.stderr)
        return 1
    src = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)[0]
    dst = os.path.join(HERE, "data", "cpu_obj_wait.xplane.pb")
    shutil.copy(src, dst)
    with open(os.path.join(HERE, "data", "cpu_obj_wait.json"), "w") as f:
        json.dump({key: w.delta(key) for key in COUNTERS}, f)
        f.write("\n")
    print(f"{dst}: {os.path.getsize(dst)} bytes, {len(w.ops)} ops, "
          + ", ".join(f"{k} {w.delta(k)}" for k in COUNTERS))
    return 0


if __name__ == "__main__":
    sys.exit(main())
