"""Find the benchmark's pieces by name.

``BENCHMARK.json`` names the cells; a cell names a configuration (its
``file`` in ``BENCHMARK.json``) and a traffic mix (``traffic/<mix>.json``);
each metric is read by ``metrics/<metric>.py``; the peaks are
``peaks.json``, keyed by ``device_kind``.  A new piece is a new file and
a new entry: nothing here changes.
"""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return load(os.path.join(root, "BENCHMARK.json"))


def cell(spec: dict, name: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(spec: dict, name: str, root: str = ROOT) -> dict:
    for c in spec["configs"]:
        if c["name"] == name:
            return load(os.path.join(root, c["file"]))
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def traffic(name: str, bench_dir: str = BENCH_DIR) -> dict:
    return load(os.path.join(bench_dir, "traffic", f"{name}.json"))


def metrics_for(spec: dict, cell_name: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` entries a cell reports."""
    return [m for m in spec[kind]
            if cell_name in m.get("workloads", [cell_name])]


def reader(name: str, bench_dir: str = BENCH_DIR):
    """``read(window)`` of ``metrics/<name>.py``."""
    path = os.path.join(bench_dir, "metrics", f"{name}.py")
    mod_spec = importlib.util.spec_from_file_location(
        f"benchmark_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def peaks(kind: str, bench_dir: str = BENCH_DIR) -> dict:
    """Published peaks of one device kind; an unknown kind is an error."""
    table = load(os.path.join(bench_dir, "peaks.json"))
    try:
        return table["kinds"][kind]
    except KeyError:
        raise KeyError(f"device kind {kind!r} is not in peaks.json "
                       f"(have {sorted(table['kinds'])})") from None
