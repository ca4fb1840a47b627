#!/usr/bin/env python3
"""Run a cell with the control in the cluster's place and print the
numbers the check compares.

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 \
        --seconds <s> [--mode none]

The control is the plain reference store with the one guarantee broken
that the cell's traffic mix names under ``control`` (see
``harness/control.py``); ``--mode none`` runs the sound reference store.
It runs at the cell's own sizes and load, on the host: the benchmark's
own runs never run it.  One JSON line per seed: the checks and whether
the run came out correct, which for a control has to be false.
"""

import argparse
import asyncio
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)

from harness import cell as cellmod  # noqa: E402
from harness import spec  # noqa: E402
from harness.control import ControlSUT  # noqa: E402


def run_control(cfg: dict, mix: dict, seed: int, seconds: float,
                mode: str | None) -> dict:
    out = asyncio.run(cellmod.run(cfg, mix, seed, seconds, False,
                                  ControlSUT(cfg, mode), time.perf_counter(),
                                  None))
    checks = out["checks"]
    return {"seed": seed, "mode": mode,
            "correct": all(cellmod.passed(c) for c in checks),
            "attempted": len(out["window"].ops),
            "checks": {c["name"]: c["value"] for c in checks}}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--mode", default=None)
    args = p.parse_args(argv)
    bench = spec.benchmark()
    cell = spec.cell(bench, args.workload)
    cfg = spec.config(bench, cell["config"])
    mix = spec.traffic(cell["traffic"])
    mode = args.mode or mix["control"]
    for seed in (int(s) for s in args.seeds.split(",")):
        line = run_control(cfg, mix, seed, args.seconds,
                           None if mode == "none" else mode)
        print(json.dumps({"workload": args.workload, **line}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
