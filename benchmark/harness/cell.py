"""One run of one cell: set-up, the measured window, the check, metrics.

The window drives the client API with a closed loop of ``concurrency``
workers, each issuing its seeded ops back to back until the window
closes; ops in flight then are drained, and belong to the window.  Every
op is timed by the host clock from issue to completion.
"""

from __future__ import annotations

import asyncio
import gc
import time
from dataclasses import dataclass, field


from harness import reference, trace
from harness.traffic import WARMUP, WINDOW, Payloads, Plan

DRAIN_S = 120.0


class Op:
    __slots__ = ("op", "key", "keep", "version", "t0", "t1", "ok",
                 "nbytes", "lo", "hi", "data", "err")

    def __init__(self, op: str, key: int, keep: bool):
        self.op, self.key, self.keep = op, key, keep
        self.version = self.lo = self.hi = -1
        self.t0 = self.t1 = 0.0
        self.ok, self.nbytes, self.data, self.err = False, 0, None, ""


class Driver:
    """Issues a plan's ops against a system under test and keeps, per
    key, the newest version issued and the newest acknowledged."""

    def __init__(self, sut, plan: Plan, payloads: Payloads,
                 concurrency: int, prefix: str):
        self.sut, self.plan, self.payloads = sut, plan, payloads
        self.concurrency = int(concurrency)
        self.prefix = prefix
        self.issued: dict[int, int] = {}     # key -> newest version issued
        self.acked: dict[int, int] = {}      # key -> newest acknowledged

    def name(self, key: int) -> str:
        return f"{self.prefix}{key}"

    async def one(self, op: str, key: int, keep: bool,
                  out: list | None = None) -> Op:
        """Issue one op; its record goes into ``out`` before it is
        awaited, so an op that never completes is counted as failed."""
        rec = Op(op, key, keep)
        if out is not None:
            out.append(rec)
        name = self.name(key)
        if op == "write_full":
            rec.version = self.issued.get(key, -1) + 1
            self.issued[key] = rec.version
            data = self.payloads.make(key, rec.version)
            rec.nbytes = len(data)
            rec.t0 = time.perf_counter()
            try:
                await self.sut.write(name, data)
                rec.ok = True
            except Exception as e:     # an op that fails is counted
                rec.err = f"{type(e).__name__}: {e}"[:300]
            rec.t1 = time.perf_counter()
            if rec.ok and rec.version > self.acked.get(key, -1):
                self.acked[key] = rec.version
        elif op == "read":
            rec.lo = self.acked.get(key, -1)
            rec.t0 = time.perf_counter()
            try:
                data = await self.sut.read(name)
                rec.ok = True
            except Exception as e:
                rec.err = f"{type(e).__name__}: {e}"[:300]
                data = b""
            rec.t1 = time.perf_counter()
            rec.hi = self.issued.get(key, -1)
            rec.nbytes = len(data)
            if keep:
                rec.data = data
        else:
            raise ValueError(f"unknown op {op!r}")
        return rec

    async def populate(self, n_keys: int) -> list[Op]:
        sem = asyncio.Semaphore(self.concurrency)

        async def put(key):
            async with sem:
                return await self.one("write_full", key, False)

        return list(await asyncio.gather(*(put(k) for k in range(n_keys))))

    async def run(self, stream: int, seconds: float) -> tuple:
        """Closed loop for ``seconds``; returns (t_start, t_end, t_drained,
        ops).  Ops issued before the window closes are waited for."""
        ops: list[Op] = []
        t_start = time.perf_counter()
        t_end = t_start + seconds

        async def worker(w: int):
            it = self.plan.stream(stream, w)
            while time.perf_counter() < t_end:
                op, key, keep = next(it)
                await self.one(op, key, keep, ops)

        tasks = [asyncio.ensure_future(worker(w))
                 for w in range(self.concurrency)]
        done, pending = await asyncio.wait(
            tasks, timeout=seconds + DRAIN_S)
        for t in pending:
            t.cancel()
        await asyncio.gather(*pending, return_exceptions=True)
        for t in done:
            t.result()
        t_drained = time.perf_counter()
        for rec in ops:
            if not rec.t1:             # cancelled: it never came
                rec.t1, rec.err = t_drained, "no reply before the drain"
        return t_start, t_end, t_drained, ops


class LagProbe:
    """Oversleep of a 1 ms sleep on the event loop, sampled back to back."""

    def __init__(self):
        self.samples: list[tuple[float, float]] = []    # (t, lag s)
        self._task = None

    async def _run(self):
        while True:
            t = time.perf_counter()
            await asyncio.sleep(0.001)
            self.samples.append((t, time.perf_counter() - t - 0.001))

    def start(self):
        self._task = asyncio.ensure_future(self._run())

    async def stop(self):
        self._task.cancel()
        await asyncio.gather(self._task, return_exceptions=True)


@dataclass
class Window:
    """What a metric reader reads."""
    cfg: dict
    mix: dict
    setup_s: float
    t_start: float
    t_end: float
    t_drained: float
    ops: list
    counters0: dict
    counters1: dict
    lag: list = field(default_factory=list)
    trace: object = None
    peaks: dict | None = None
    lost_shard: dict = field(default_factory=dict)   # object -> shard

    def delta(self, key: str):
        a, b = self.counters0.get(key), self.counters1.get(key)
        if a is None or b is None:
            return None
        if isinstance(a, tuple):
            return (b[0] - a[0], b[1] - a[1])
        return b - a

    @property
    def profile(self) -> dict:
        return self.cfg["pool"]["profile"]


def _shard_note(name: str, shard: int, got, want: bytes) -> str:
    if got is None:
        return f"{name} shard {shard}: missing"
    diff = [i for i in range(min(len(got), len(want))) if got[i] != want[i]]
    where = (f"{len(diff)} bytes differ, first at {diff[0]}" if diff
             else "same prefix")
    return (f"{name} shard {shard}: {len(got)} B stored, {len(want)} B "
            f"expected, {where}")


def check(driver: Driver, ops: list, shards: dict, cfg: dict,
          decode_launches, notes: list) -> list[dict]:
    """The numbers compared, each with its limit.  Exact comparisons:
    limit 0 for wrong answers, at least one answer checked.  A written
    object is checked on all k+m shard positions: ``objects_wrong``
    counts objects with a shard absent or unlike the reference's,
    ``shards_missing`` the absent shards.  What was
    wrong goes into ``notes``."""
    p = cfg["pool"]["profile"]
    k, m = int(p["k"]), int(p["m"])
    unit, poly = int(p["stripe_unit"]), int(cfg["code"]["polynomial"])
    failed = sum(not o.ok for o in ops)
    out = [{"name": "ops_failed", "value": failed, "cmp": "<=", "limit": 0}]
    reads = [o for o in ops if o.op == "read" and o.ok and o.data is not None]
    if any(o.op == "read" for o in ops):
        wrong = 0
        for o in reads:
            v = driver.payloads.version_of(o.key, o.data)
            if v is None or not (o.lo <= v <= o.hi):
                wrong += 1
                notes.append(f"{driver.name(o.key)}: read {len(o.data)} B, "
                             f"version {v}, expected {o.lo}..{o.hi}")
        out += [{"name": "reads_wrong", "value": wrong, "cmp": "<=",
                 "limit": 0},
                {"name": "reads_checked", "value": len(reads), "cmp": ">=",
                 "limit": 1}]
    if any(o.op == "write_full" for o in ops):
        wrong = missing = 0
        for key, got in shards.items():
            want = reference.encode(
                driver.payloads.make(key, driver.acked[key]), k, m, unit,
                poly)
            absent = [i for i in range(k + m) if got.get(i) is None]
            bad = [i for i, g in sorted(got.items())
                   if g is not None and g != want[i]]
            wrong += bool(absent or bad)
            missing += len(absent)
            for i in (absent + bad)[:3]:
                notes.append(_shard_note(driver.name(key), i, got.get(i),
                                         want[i]))
        out += [{"name": "objects_wrong", "value": wrong, "cmp": "<=",
                 "limit": 0},
                {"name": "shards_missing", "value": missing, "cmp": "<=",
                 "limit": 0},
                {"name": "objects_checked", "value": len(shards),
                 "cmp": ">=", "limit": 1}]
    if decode_launches is not None:
        out.append({"name": "decode_launches", "value": decode_launches,
                    "cmp": ">=", "limit": 1})
    return out


def passed(c: dict) -> bool:
    return (c["value"] <= c["limit"] if c["cmp"] == "<="
            else c["value"] >= c["limit"])


def stored_shards(sut, driver: Driver, ops: list) -> dict:
    """key -> {shard: stored bytes or None}, for each object whose window
    write was drawn for the check (read once the window has closed)."""
    keys = sorted({o.key for o in ops
                   if o.op == "write_full" and o.keep and o.ok})
    return {key: sut.shards(driver.name(key)) for key in keys}


async def run(cfg: dict, mix: dict, seed: int, seconds: float,
              traced: bool, sut, t_process: float, peaks: dict | None,
              trace_dir: str | None = None, hooks: dict | None = None) -> dict:
    """Set up, measure, check.  Returns the pieces of the result line:
    the Window, the checks and set-up phases.  ``hooks["start"]`` and
    ``hooks["close"]`` are called as the window opens and once its ops
    have completed, before the check reads the stores."""
    hooks = hooks or {}
    phases = {}
    n_keys = int(cfg["objects"])
    payloads = Payloads(seed, int(cfg["object_bytes"]))
    plan = Plan(seed, mix, n_keys)
    driver = Driver(sut, plan, payloads, mix["concurrency"],
                    mix.get("prefix", "obj-"))
    lost_shard: dict[str, int] = {}
    try:
        t = time.perf_counter()
        await sut.start()
        phases["cluster_s"] = time.perf_counter() - t
        t = time.perf_counter()
        await sut.warm_launches(mix)
        phases["launch_warm_s"] = time.perf_counter() - t
        t = time.perf_counter()
        setup_ops = []
        if mix.get("populate"):
            setup_ops += await driver.populate(n_keys)
        phases["populate_s"] = time.perf_counter() - t
        failure = mix.get("failure")
        if failure:
            t = time.perf_counter()
            victim = int(failure["osd"])
            for key in range(n_keys):
                name = driver.name(key)
                acting = sut.acting(name)
                if victim in acting:
                    lost_shard[name] = acting.index(victim)
            await sut.kill(victim)
            phases["failure_s"] = time.perf_counter() - t
        t = time.perf_counter()
        setup_ops += (await driver.run(WARMUP,
                                       float(mix.get("warmup_s", 5))))[3]
        phases["warmup_s"] = time.perf_counter() - t

        gc.collect()        # not in the window: the set-up's garbage
        counters0 = sut.counters()
        probe = LagProbe() if traced else None
        if traced:
            import jax

            opts = jax.profiler.ProfileOptions()
            opts.host_tracer_level = 1
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            probe.start()
        if "start" in hooks:
            hooks["start"]()
        t_window = time.perf_counter()
        setup_s = t_window - t_process
        if traced:
            with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
                t_start, t_end, t_drained, ops = await driver.run(
                    WINDOW, seconds)
            await probe.stop()
            jax.profiler.stop_trace()
        else:
            t_start, t_end, t_drained, ops = await driver.run(WINDOW,
                                                              seconds)
        counters1 = sut.counters()
        if "close" in hooks:
            hooks["close"]()
        shards = stored_shards(sut, driver, ops)
    finally:
        await sut.stop()
    dec = None
    if mix.get("failure") and "ec_decode_launch_us" in counters1:
        a = counters0.get("ec_decode_launch_us", (0, 0))
        dec = counters1["ec_decode_launch_us"][1] - a[1]
    notes: list[str] = []
    checks = check(driver, ops, shards, cfg, dec, notes)
    checks.insert(0, {"name": "setup_ops_failed",
                      "value": sum(not o.ok for o in setup_ops),
                      "cmp": "<=", "limit": 0})
    w = Window(cfg=cfg, mix=mix, setup_s=setup_s, t_start=t_start,
               t_end=t_end, t_drained=t_drained, ops=ops,
               counters0=counters0, counters1=counters1,
               lag=[lag for ts, lag in (probe.samples if probe else [])
                    if t_start <= ts < t_drained],
               peaks=peaks, lost_shard=lost_shard)
    return {"window": w, "checks": checks, "phases": phases, "notes": notes,
            "ops_in_flight": _in_flight(ops)}


def _in_flight(ops: list):
    """label_gap's view of the host: which client ops were in flight."""
    spans = sorted((o.t0, o.t1, o.op) for o in ops)

    def at(t: float) -> str:
        kinds: dict[str, int] = {}
        for a, b, op in spans:
            if a > t:
                break
            if b >= t:
                kinds[op] = kinds.get(op, 0) + 1
        return ", ".join(f"{n} {op}" for op, n in sorted(kinds.items())) \
            or "no client op"
    return at
