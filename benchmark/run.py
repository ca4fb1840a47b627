#!/usr/bin/env python3
"""Run one benchmark cell on the accelerator and print one result line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell (an entry of ``BENCHMARK.json`` ``workloads``) names a
configuration and a traffic mix; see ``harness/spec.py`` for where each
piece lives.  The run refuses anything but a TPU with as many chips as
the cell asks for, keeps JAX's persistent compilation cache in the
checkout (or where ``JAX_COMPILATION_CACHE_DIR`` says), builds the
configuration's cluster, does the mix's set-up traffic, measures for
``--seconds``, checks what was stored and read against the plain
reference, and prints as the last line of stdout a JSON object with
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and, with
``--trace 1``, ``breakdown``), ending with ``checks``: every number
compared, beside its limit.  The same numbers end stderr.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, BENCH_DIR)
sys.path.insert(1, ROOT)

from harness import spec  # noqa: E402

TRACE_DIR = os.path.join(BENCH_DIR, ".trace")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def device_check(chips: int) -> dict:
    """The devices JAX sees, or exit non-zero with no result."""
    import jax

    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu" or len(devs) < chips:
        log(f"run: needs {chips} TPU chip(s); JAX found {len(devs)} "
            f"{d0.platform} device(s) ({d0.device_kind})")
        sys.exit(3)
    log(f"device: platform={d0.platform} device_kind={d0.device_kind} "
        f"count={len(devs)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


def compile_cache() -> str:
    """JAX's persistent cache: JAX_COMPILATION_CACHE_DIR, else a fixed
    directory in the checkout (the program's own default, which it then
    takes too)."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    os.makedirs(path, exist_ok=True)
    import jax

    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCount:
    """Backend compiles and persistent-cache misses, by phase."""

    def __init__(self):
        import jax

        self.compiles = self.misses = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._dur)

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _dur(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1


def peak_bytes(devices: int) -> int:
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:devices])


def result_line(cell: dict, bench: dict, out: dict, device: dict,
                traced: bool) -> dict:
    w = out["window"]
    kind = "per_layer" if traced else "end_to_end"
    metrics = {}
    for m in spec.metrics_for(bench, cell["name"], kind):
        value = spec.reader(m["name"])(w)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    checks = out["checks"]
    from harness.cell import passed

    correct = all(passed(c) for c in checks)
    wrong = sum(c["value"] for c in checks
                if c["name"] in ("ops_failed", "reads_wrong",
                                 "objects_wrong"))
    line = {"correct": correct, "attempted": len(w.ops),
            "failed": int(wrong), "metrics": metrics, "device": device}
    if traced:
        from harness import trace

        s = w.trace
        device["busy_s"] = s.busy_s
        device["window_s"] = s.window_s
        at = out["ops_in_flight"]
        offset = s.window_ns[0] / 1e9 - w.t_start

        def label(a, b):
            return at((a + b) / 2e9 - offset) + " in flight"

        line["breakdown"] = trace.breakdown(s, label)
    line["checks"] = {c["name"]: {"value": c["value"],
                                  "limit": f"{c['cmp']} {c['limit']}"}
                      for c in checks}
    return line


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    bench = spec.benchmark()
    cell = spec.cell(bench, args.workload)
    cfg = spec.config(bench, cell["config"])
    mix = spec.traffic(cell["traffic"])
    cache = compile_cache()
    device = device_check(int(cell["chips"]))
    peaks = spec.peaks(device["kind"])
    log(f"compile cache: {cache}")
    counts = CompileCount()

    from harness import cell as cellmod
    from harness import sut as sutmod
    from harness import trace

    t = time.perf_counter()
    warmed = sutmod.warm_codec(cfg, mix)
    log(f"set-up: jax init {t - T_PROCESS:.3f} s; codec warm {warmed} "
        f"shapes {time.perf_counter() - t:.3f} s, {counts.compiles} "
        f"compiles, {counts.misses} cache misses")
    traced = bool(args.trace)
    if traced:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        os.makedirs(TRACE_DIR)

    compiles = {}

    def at(when):
        def hook():
            compiles[when] = (counts.compiles, counts.misses)
            if when == "close":
                device["memory_peak_bytes"] = peak_bytes(int(cell["chips"]))
        return hook

    out = asyncio.run(cellmod.run(
        cfg, mix, args.seed, args.seconds, traced, sutmod.ClusterSUT(cfg),
        T_PROCESS, peaks, trace_dir=TRACE_DIR,
        hooks={"start": at("start"), "close": at("close")}))
    w = out["window"]
    if traced:
        w.trace = trace.summarize(trace.find_xplane(TRACE_DIR))
    line = result_line(cell, bench, out, device, traced)
    ph = ", ".join(f"{k} {v:.3f}" for k, v in out["phases"].items())
    log(f"set-up {w.setup_s:.3f} s ({ph}); window {len(w.ops)} ops, "
        f"drained {w.t_drained - w.t_end:.3f} s after the close; "
        f"compiles in the window "
        f"{compiles['close'][0] - compiles['start'][0]}, cache misses "
        f"{compiles['close'][1] - compiles['start'][1]}")
    for o in w.ops:
        if not o.ok:
            log(f"first failed op: {o.op} {o.key}: {o.err}")
            break
    for note in out["notes"][:8]:
        log(f"note: {note}")
    for name, c in line["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
