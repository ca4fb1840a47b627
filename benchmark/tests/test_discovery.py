"""A configuration, a mix, a metric and a cell added as new files and
entries are found by name, with no existing file edited."""

import asyncio
import json
import os
import shutil
import time

from harness import cell as cellmod
from harness import spec
from harness.control import ControlSUT


def test_new_pieces_are_found_by_name(tmp_path):
    root = tmp_path / "checkout"
    bench_dir = root / "benchmark"
    shutil.copytree(spec.BENCH_DIR, bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__", ".trace"))
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), root)
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}

    # the new pieces: a deployment, a mix, a per-layer metric, a cell
    cfg = json.loads((bench_dir / "configs" /
                      "rados_bench.ec84.4m.json").read_text())
    cfg.update(name="tiny.ec21", osds=3, object_bytes=8192, objects=16)
    cfg["pool"]["profile"].update(k=2, m=1)
    (bench_dir / "configs" / "tiny.ec21.json").write_text(json.dumps(cfg))
    mix = {"loop": "closed", "concurrency": 2, "ops": {"read": 0.5,
           "write_full": 0.5}, "keys": {"distribution": "uniform"},
           "populate": True, "failure": None, "warmup_s": 0.1,
           "check_fraction": 1.0, "control": "stale_read"}
    (bench_dir / "traffic" / "half_half.json").write_text(json.dumps(mix))
    (bench_dir / "metrics" / "reads_per_op.py").write_text(
        "def read(w):\n"
        "    return sum(o.op == 'read' for o in w.ops) / len(w.ops)\n")
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "tiny.ec21", "source": "test",
                         "file": "benchmark/configs/tiny.ec21.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": "half.tiny", "config": "tiny.ec21",
                           "traffic": "half_half", "chips": 1, "why": "t"})
    b["per_layer"].append({"name": "reads_per_op", "unit": "1",
                           "better": "higher", "source": "host_clock",
                           "layer": "client", "moves": "op_p95_ms",
                           "workloads": ["half.tiny"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))

    # nothing that was there changed but BENCHMARK.json
    changed = [p for p, data in before.items()
               if p.exists() and p.read_bytes() != data]
    assert changed == [root / "BENCHMARK.json"]

    bench = spec.benchmark(str(root))
    c = spec.cell(bench, "half.tiny")
    got_cfg = spec.config(bench, c["config"], str(root))
    got_mix = spec.traffic(c["traffic"], str(bench_dir))
    assert got_cfg == cfg and got_mix == mix
    layer = spec.metrics_for(bench, "half.tiny", "per_layer")
    assert [m["name"] for m in layer] == ["reads_per_op"]
    out = asyncio.run(cellmod.run(got_cfg, got_mix, 5, 0.3, False,
                                  ControlSUT(got_cfg, None),
                                  time.perf_counter(), None))
    value = spec.reader("reads_per_op", str(bench_dir))(out["window"])
    assert 0.0 < value < 1.0
    assert all(cellmod.passed(x) for x in out["checks"])
