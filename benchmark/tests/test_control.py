"""The control comes out not correct, and the sound reference correct,
for every cell's mix at a size a test run can hold.  On the chip the same
runs are made at each cell's own size with ``benchmark/control.py``."""

import asyncio
import time

import pytest

from harness import cell as cellmod
from harness import spec
from harness.control import ControlSUT

CELLS = [(w["config"], w["traffic"]) for w in spec.benchmark()["workloads"]]


def _small(config: str, traffic: str):
    cfg = spec.load(f"{spec.BENCH_DIR}/configs/{config}.json")
    cfg["object_bytes"] = min(int(cfg["object_bytes"]), 64 << 10)
    cfg["objects"] = min(int(cfg["objects"]), 24)
    mix = spec.traffic(traffic)
    mix["warmup_s"] = 0.1
    mix["check_fraction"] = 1.0
    return cfg, mix


def _run(cfg, mix, seed, mode):
    out = asyncio.run(cellmod.run(cfg, mix, seed, 0.4, False,
                                  ControlSUT(cfg, mode),
                                  time.perf_counter(), None))
    return all(cellmod.passed(c) for c in out["checks"]), out["checks"]


@pytest.mark.parametrize("config,traffic", CELLS)
@pytest.mark.parametrize("seed", [11, 2**32 + 7, 3000000019])
def test_control_is_not_correct(config, traffic, seed):
    cfg, mix = _small(config, traffic)
    correct, checks = _run(cfg, mix, seed, mix["control"])
    assert not correct, checks


@pytest.mark.parametrize("config,traffic", CELLS)
def test_sound_reference_is_correct(config, traffic):
    cfg, mix = _small(config, traffic)
    correct, checks = _run(cfg, mix, 11, None)
    assert correct, checks
