#!/usr/bin/env python3
"""chip_smoke: run the EC store's main path once on the TPU and check it.

One process holds the chip and starts no child that touches JAX.

Phases (``python chip_smoke.py``, one chip):

1. device  — ``jax.devices()[0]`` must be a TPU, or the script exits
   non-zero.  It never falls back to the CPU.
2. kernels — device-resident encode and 4-erasure decode at the bench
   shapes (k=8 m=4 reed_sol_van, 16,384 stripes of 4 KiB; k=10 m=4
   cauchy_good, 1,024 stripes of 40 KiB), CLAY k=8 m=4 d=11 and LRC
   k=12 m=4 l=4 repair, and the corpus check.  Every result is compared
   bit for bit with the numpy reference (ceph_tpu.ec.reference / gf).
3. cluster — an in-process DevCluster (1 mon, 12 OSDs) with a jax_rs
   k=8 m=4 EC pool (pg_num 32, failure domain osd, 4 KiB stripe unit):
   256 objects of 4 MiB through IoCtx.write_full, read back, one OSD
   killed and marked down, degraded reads and overwrites, the OSD
   revived, the batched repair drained and its rebuilt shards compared
   with the reference encode.

``python chip_smoke.py --chips 4`` runs only the multi-chip path: the
distributed EC dry run on the four local chips, and the cluster writes
through the host mesh coalescer against the same writes on one device.

The last line of stdout is ``{"ok": true, "device": {...}}``.  Any
failure raises before it is printed and the exit code is non-zero.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time

MIB = 1 << 20

# one counter per JAX monitoring event we report: compile requests (each
# is a persistent-cache hit or a miss that compiled), and the hits and
# misses themselves (a second run of this script should mostly hit)
_EVENTS = {"compiles": 0, "cache_hits": 0, "cache_misses": 0}
_SLOW_COMPILE_S = 5.0
_slow_compiles: list[tuple[float, str]] = []


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def device_check(chips: int) -> dict:
    """Refuse anything but a TPU; print what JAX sees."""
    import importlib.metadata

    import jax

    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX found {d0.platform!r} ({d0.device_kind}), "
            "not a TPU; this script never falls back to the CPU")
    if len(devs) < chips:
        raise SystemExit(
            f"chip_smoke: --chips {chips} needs {chips} TPU devices, "
            f"JAX sees {len(devs)}")
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed as a package"
    log(f"device: platform={d0.platform} kind={d0.device_kind} "
        f"count={len(devs)} jax={jax.__version__} libtpu={libtpu}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


def start_compile_accounting() -> None:
    import jax

    from ceph_tpu.common.jaxutil import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            _EVENTS["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            _EVENTS["cache_misses"] += 1

    def on_duration(event, duration, fun_name="?", **_):
        if event == "/jax/core/compile/backend_compile_duration":
            _EVENTS["compiles"] += 1
            if duration > _SLOW_COMPILE_S:
                _slow_compiles.append((duration, fun_name))

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)


def _ref_apply(A, X, block: int = 1 << 20):
    """Reference GF(2^8) product A @ X, column-blocked to bound memory."""
    import numpy as np

    from ceph_tpu.ec.gf import gf_matmul

    out = np.empty((A.shape[0], X.shape[1]), np.uint8)
    for c in range(0, X.shape[1], block):
        out[:, c:c + block] = gf_matmul(A, X[:, c:c + block])
    return out


def _shard_layout(batch):
    """(B, k, C) stripe batch -> (k, B*C) shard streams (ECUtil layout)."""
    import numpy as np

    B, k, C = batch.shape
    return np.ascontiguousarray(np.transpose(batch, (1, 0, 2))
                                .reshape(k, B * C))


# -- kernel phase ---------------------------------------------------------

def _rs_case(reg, seed: int, technique: str, k: int, m: int,
             stripes: int, chunk: int) -> None:
    """Encode then decode 4 erasures on the device; both bit-identical
    to the reference on the full batch."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ceph_tpu.ec import reference

    ec = reg.factory("jax_rs", {"k": str(k), "m": str(m),
                                "technique": technique})
    n = k + m
    data = jax.random.bits(jax.random.key(seed), (stripes, k, chunk),
                           jnp.uint8)
    t0 = time.perf_counter()
    chunks = ec.encode_chunks_device(data)
    chunks.block_until_ready()
    t_enc = time.perf_counter() - t0
    lost = sorted(int(x) for x in np.random.default_rng(seed).choice(
        n, size=4, replace=False))
    avail = {i: chunks[:, i] for i in range(n) if i not in lost}
    t0 = time.perf_counter()
    rebuilt = ec.decode_chunks_device(avail, lost)
    rebuilt.block_until_ready()
    t_dec = time.perf_counter() - t0

    ref = reference.encode(ec.generator, _shard_layout(np.asarray(data)))
    check(np.array_equal(_shard_layout(np.asarray(chunks)), ref),
          f"{technique} k={k} m={m}: device encode differs from reference")
    check(np.array_equal(_shard_layout(np.asarray(rebuilt)), ref[lost]),
          f"{technique} k={k} m={m}: device decode of {lost} differs "
          "from reference")
    hlo = jax.jit(ec.encode_chunks_device).lower(data).compile().as_text()
    check("tpu_custom_call" in hlo,
          f"{technique} k={k} m={m}: encode compiled without a Pallas "
          "kernel")
    log(f"kernel {technique} k={k} m={m}: {stripes} stripes x "
        f"{k * chunk // 1024} KiB ({stripes * k * chunk / MIB:g} MiB), "
        f"encode + decode of {lost} bit-identical to reference, Pallas "
        f"kernel in the compiled encode (first-call wall incl. compile: "
        f"encode {t_enc:.3f} s, decode {t_dec:.3f} s)")


def _clay_case(reg, seed: int, stripes: int = 128, sc: int = 1024,
               lost: int = 3) -> None:
    """CLAY k=8 m=4 d=11 single-chunk repair as one device apply of the
    probed repair operator (bench.py's cfg4 shape), against the
    reference product; a small encoded batch checks it rebuilds the
    lost chunk itself."""
    import jax.numpy as jnp
    import numpy as np

    from ceph_tpu.ec.engine import default_engine
    from ceph_tpu.ec.pallas_kernels import bytes_to_words, words_to_bytes
    from ceph_tpu.ec.repair_operator import clay_repair_operator

    ec = reg.factory("clay", {"k": "8", "m": "4", "d": "11"})
    R, helpers, planes = clay_repair_operator(ec, lost)
    eng = default_engine()
    grouped = eng._grouped_applier(R) is not None

    def repair(flat):
        return np.asarray(words_to_bytes(
            eng.apply_words(R, bytes_to_words(jnp.asarray(flat)))))

    rows = len(helpers) * len(planes)
    flat = np.random.default_rng(seed).integers(
        0, 256, (rows, stripes * sc), np.uint8)
    check(np.array_equal(repair(flat), _ref_apply(R, flat)),
          "clay repair operator on the device differs from reference")

    # truth: 8 encoded stripes, repaired from the helpers' repair planes
    small = 8
    C = ec.sub_chunk_no * sc
    data = np.random.default_rng(seed + 1).integers(
        0, 256, (small, ec.k, C), np.uint8)
    full = np.asarray(ec.encode_chunks_batch(data))
    hflat = _shard_layout(np.concatenate([
        full[:, h].reshape(small, ec.sub_chunk_no, sc)[:, planes]
        for h in helpers], axis=1))
    want = _shard_layout(full[:, lost].reshape(small, ec.sub_chunk_no, sc))
    check(np.array_equal(repair(hflat), want),
          "clay repair did not rebuild the lost chunk")
    log(f"kernel clay k=8 m=4 d=11 repair of chunk {lost}: operator "
        f"{R.shape[0]}x{R.shape[1]} over {stripes} stripes x "
        f"{C // 1024} KiB chunks bit-identical to reference, lost chunk "
        f"rebuilt exactly ({'grouped fused' if grouped else 'shard'} "
        "kernel)")


def _lrc_case(reg, seed: int, stripes: int = 64, C: int = MIB,
              lost: int = 0) -> None:
    """LRC k=12 m=4 l=4 local-group repair (bench.py's cfg5 shape)
    against the reference product, and on a small encoded batch."""
    import jax.numpy as jnp
    import numpy as np

    from ceph_tpu.ec.engine import default_engine
    from ceph_tpu.ec.pallas_kernels import bytes_to_words, words_to_bytes
    from ceph_tpu.ec.repair_operator import lrc_repair_operator

    ec = reg.factory("lrc", {"k": "12", "m": "4", "l": "4"})
    coeffs, minimum = lrc_repair_operator(ec, lost)
    eng = default_engine()

    def repair(group):
        return np.asarray(words_to_bytes(
            eng.apply_words(coeffs, bytes_to_words(jnp.asarray(group)))))

    group = np.random.default_rng(seed).integers(
        0, 256, (len(minimum), stripes * C), np.uint8)
    check(np.array_equal(repair(group), _ref_apply(coeffs, group)),
          "lrc group repair on the device differs from reference")

    small, c_small = 8, 4096
    data = np.random.default_rng(seed + 1).integers(
        0, 256, (small, ec.get_data_chunk_count(), c_small), np.uint8)
    full = np.asarray(ec.encode_chunks_batch(data))
    got = repair(_shard_layout(full[:, minimum]))
    check(np.array_equal(got, _shard_layout(full[:, [lost]])),
          "lrc repair did not rebuild the lost chunk")
    log(f"kernel lrc k=12 m=4 l=4 repair of chunk {lost} from group "
        f"{minimum}: {stripes} stripes x {C // MIB} MiB bit-identical to "
        "reference, lost chunk rebuilt exactly")


def kernel_phase(seed: int, rs_stripes: int = 16384,
                 cauchy_stripes: int = 1024) -> None:
    from ceph_tpu.ec import corpus, pallas_kernels
    from ceph_tpu.ec.engine import default_engine
    from ceph_tpu.ec.registry import ErasureCodePluginRegistry

    pallas_kernels.set_encode_variant("auto")
    check(default_engine().use_pallas,
          "the default engine does not take the Pallas path")
    log(f"pallas: use_pallas=True, encode variant "
        f"{pallas_kernels.get_encode_variant() or 'production'!r}")
    reg = ErasureCodePluginRegistry()
    t0 = time.perf_counter()
    _rs_case(reg, seed, "reed_sol_van", 8, 4, rs_stripes, 512)
    _rs_case(reg, seed + 1, "cauchy_good", 10, 4, cauchy_stripes, 4096)
    _clay_case(reg, seed + 2)
    _lrc_case(reg, seed + 3)
    failures = corpus.check()
    check(not failures, f"corpus check failed: {failures}")
    n_cases = len(list(corpus.CORPUS_DIR.glob("*.json")))
    log(f"corpus check: {n_cases} archived cases bit-identical")
    log(f"kernel phase: {time.perf_counter() - t0:.1f} s, "
        f"{_EVENTS['compiles']} compile requests so far{_slow_text()}")


def _slow_text() -> str:
    """The compile requests over _SLOW_COMPILE_S since the last call."""
    if not _slow_compiles:
        return ""
    slow = ", ".join(f"{name} {sec:.1f} s"
                     for sec, name in sorted(_slow_compiles, reverse=True))
    _slow_compiles.clear()
    return f"; compiles over {_SLOW_COMPILE_S:g} s: {slow}"


# -- cluster phase --------------------------------------------------------

# Ceph's defaults: 4 MiB RADOS/RBD objects, 4 KiB EC stripe unit
# (osd_pool_erasure_code_stripe_unit), 16 ops in flight (rados bench).
PROFILE = {"plugin": "jax_rs", "k": "8", "m": "4",
           "technique": "reed_sol_van", "crush-failure-domain": "osd",
           "stripe_unit": "4096"}
POOL, PG_NUM, N_OSDS, IN_FLIGHT = "smoke", 32, 12, 16

# In-process cluster: one event loop serves every daemon, and each OSD
# compiles an op shape on first use in its launch thread.  _warm_codec
# compiles the codec's buckets before the OSDs boot; the heartbeat grace
# is raised so a stall the warm-up missed cannot get a healthy OSD
# marked down, and the client deadline so a cold compile cannot fail an
# op (this run checks results, not latency).  The killed OSD is marked
# down explicitly (`osd down`, the thrasher's way).
OVERRIDES = {"osd_heartbeat_grace": 20.0,
             "mon_osd_down_out_interval": 3600.0,
             "client_op_deadline": 600.0}


def object_data(seed: int, i: int, gen: int, size: int) -> bytes:
    import numpy as np

    return np.random.default_rng([seed, i, gen]).bytes(size)


def expected_shards(data: bytes):
    """Reference shard streams (k+m, size/k) of one object."""
    import numpy as np

    from ceph_tpu.ec import reference
    from ceph_tpu.ec.matrix import generator_matrix

    k, unit = int(PROFILE["k"]), int(PROFILE["stripe_unit"])
    G = generator_matrix(PROFILE["technique"], k, int(PROFILE["m"]))
    stripes = np.frombuffer(data, np.uint8).reshape(-1, k, unit)
    return reference.encode(G, _shard_layout(stripes))


async def _gather(n: int, fn) -> None:
    sem = asyncio.Semaphore(IN_FLIGHT)

    async def one(i):
        async with sem:
            await fn(i)

    await asyncio.gather(*(one(i) for i in range(n)))


def _warm_codec() -> None:
    """Compile the pool codec's device encode and single-erasure decode
    for every coalescer bucket before the OSDs boot, so the first ops
    do not all compile at once in the OSDs' launch threads."""
    import jax
    import jax.numpy as jnp

    from ceph_tpu.ec.registry import ErasureCodePluginRegistry

    ec = ErasureCodePluginRegistry().factory("jax_rs", PROFILE)
    k, unit = ec.get_data_chunk_count(), int(PROFILE["stripe_unit"])
    b = 4 * MIB // (k * unit)   # one object's stripes
    while b <= 4096:            # osd_ec_coalesce_max_stripes default
        chunks = ec.encode_chunks_device(jnp.zeros((b, k, unit), jnp.uint8))
        avail = {i: chunks[:, i] for i in range(1, ec.get_chunk_count())}
        jax.block_until_ready(ec.decode_chunks_device(avail, [0]))
        b *= 2


async def _make_cluster(overrides: dict):
    from ceph_tpu.vstart import DevCluster

    _warm_codec()

    cluster = DevCluster(n_mons=1, n_osds=N_OSDS,
                         overrides={**OVERRIDES, **overrides})
    await cluster.start()
    rados = await cluster.client()
    r = await rados.mon_command("osd erasure-code-profile set",
                                name=POOL, profile=PROFILE)
    check(r["rc"] in (0, -17), f"profile set: {r}")
    await rados.pool_create(POOL, pg_num=PG_NUM, pool_type="erasure",
                            erasure_code_profile=POOL)
    await cluster.wait_health_ok(timeout=120)
    io = await rados.open_ioctx(POOL)
    return cluster, rados, io


async def _write_all(io, seed: int, n: int, size: int, gen: int = 0,
                     start: int = 0) -> float:
    t0 = time.perf_counter()
    await _gather(n, lambda i: io.write_full(
        f"obj-{start + i}", object_data(seed, start + i, gen, size)))
    return time.perf_counter() - t0


async def _read_all(io, seed: int, idx: list[int], size: int,
                    gen_of) -> float:
    t0 = time.perf_counter()

    async def one(j):
        i = idx[j]
        got = await io.read(f"obj-{i}")
        check(got == object_data(seed, i, gen_of(i), size),
              f"obj-{i}: read-back differs from what was written")

    await _gather(len(idx), one)
    return time.perf_counter() - t0


def _pool_id(rados) -> int:
    return next(p.pool_id for p in rados.monc.osdmap.pools.values()
                if p.name == POOL)


def _kernel_totals(cluster) -> dict:
    """Launches per codec-signature kind summed over every OSD."""
    from ceph_tpu.ec.profiler import profiler_for

    out: dict[str, int] = {}
    for osd in cluster.osds.values():
        for sig, rec in profiler_for(osd.perf).kernels.items():
            kind = sig.split(":", 1)[1]
            out[kind] = out.get(kind, 0) + rec["launches"]
    return out


def _shard_of(store, pool: int, name: str):
    """(shard id, bytes) of the one shard of ``name`` this store holds."""
    from ceph_tpu.osd.pg import object_to_ps
    from ceph_tpu.store.types import GHObject

    ps = object_to_ps(name, PG_NUM)
    cid = next(c for c in store.list_collections()
               if c.pool == pool and c.pg == ps and c.shard >= 0)
    return cid.shard, store.read(cid, GHObject(pool, name,
                                               shard=cid.shard))


async def _wait_map(rados, pred, what: str, timeout: float = 60.0):
    deadline = time.monotonic() + timeout
    while not pred(rados.monc.osdmap):
        check(time.monotonic() < deadline, f"timed out: {what}")
        await asyncio.sleep(0.1)


def _peak_bytes() -> int:
    import jax

    stats = jax.devices()[0].memory_stats() or {}    # None on the CPU
    return int(stats.get("peak_bytes_in_use", 0))


async def cluster_phase(seed: int, objects: int, size: int,
                        degraded_reads: int = 32,
                        overwrites: int = 64) -> None:
    import numpy as np

    compiles0 = _EVENTS["compiles"]
    t_phase = time.perf_counter()
    cluster, rados, io = await _make_cluster({})
    try:
        log(f"cluster: 1 mon, {N_OSDS} OSDs, pool {POOL!r} jax_rs k=8 "
            f"m=4 stripe_unit 4096, pg_num {PG_NUM}, resident caches on")
        gib = objects * size / (1 << 30)
        dt = await _write_all(io, seed, objects, size)
        log(f"write: {objects} x {size / MIB:g} MiB ({gib:g} GiB) in "
            f"{dt:.2f} s = {objects / dt:.2f} objects/s")
        dt = await _read_all(io, seed, list(range(objects)), size,
                             lambda i: 0)
        log(f"read: {objects} objects bit-identical in {dt:.2f} s = "
            f"{objects / dt:.2f} objects/s")

        pool = _pool_id(rados)
        victim = int(np.random.default_rng(seed).integers(N_OSDS))
        t0 = time.perf_counter()
        await cluster.kill_osd(victim)
        r = await rados.mon_command("osd down", ids=[victim])
        check(r["rc"] == 0, f"osd down: {r}")
        await _wait_map(rados, lambda m: not m.is_up(victim),
                        f"osd.{victim} marked down")
        log(f"osd.{victim} killed and marked down in "
            f"{time.perf_counter() - t0:.2f} s")
        dec0 = _kernel_totals(cluster).get("dec", 0)
        idx = list(range(degraded_reads))
        dt = await _read_all(io, seed, idx, size, lambda i: 0)
        dec = _kernel_totals(cluster).get("dec", 0) - dec0
        check(dec > 0, "degraded reads launched no device decode")
        log(f"degraded reads: {degraded_reads} bit-identical in "
            f"{dt:.2f} s, {dec} device decode launches")

        start = objects - overwrites
        await _write_all(io, seed, overwrites, size, gen=1, start=start)
        log(f"degraded overwrites: {overwrites} objects rewritten while "
            f"osd.{victim} is down")

        await cluster.revive_osd(victim)
        store = cluster.osds[victim].store
        want = {i: expected_shards(object_data(seed, i, 1, size))
                for i in range(start, objects)}
        deadline = time.monotonic() + 300
        while True:
            batches = objects_rep = 0
            for osd_id in cluster.osds:
                eng = (await rados.osd_daemon_command(
                    osd_id, "ec_repair_stats")).get("engine", {})
                batches += eng.get("batches", 0)
                objects_rep += eng.get("objects", 0)
            stale = []
            for i in range(start, objects):
                try:
                    shard, got = _shard_of(store, pool, f"obj-{i}")
                except (KeyError, StopIteration):
                    stale.append(i)
                    continue
                if got != want[i][shard].tobytes():
                    stale.append(i)
            if batches > 0 and not stale:
                break
            check(time.monotonic() < deadline,
                  f"repair did not drain: batches={batches}, "
                  f"{len(stale)} shards on osd.{victim} not rebuilt")
            await asyncio.sleep(0.5)
        log(f"osd.{victim} revived: batched repair drained "
            f"({batches} batches, {objects_rep} objects), its "
            f"{overwrites} rebuilt shards bit-identical to the reference "
            "encode")
        await _read_all(io, seed, list(range(start, objects)), size,
                        lambda i: 1)
        log(f"read after repair: {overwrites} objects bit-identical")

        kernels = _kernel_totals(cluster)
        check(sum(kernels.values()) > 0,
              "the launch profiler counted no device launches")
        log(f"launch profiler: {kernels}")
        log(f"cluster phase: {time.perf_counter() - t_phase:.1f} s, "
            f"{_EVENTS['compiles'] - compiles0} compile requests, peak "
            f"device bytes in use {_peak_bytes()}{_slow_text()}")
    except BaseException:
        _dump_cluster_state(cluster)
        raise
    finally:
        await cluster.stop()


def _dump_cluster_state(cluster) -> None:
    """On a failure: the daemons' log ring, PG states, in-flight ops and
    slow compiles, which say where the cluster stuck."""
    from ceph_tpu.common.log import recent_lines

    print("\n".join(recent_lines(200)), file=sys.stderr)
    states: dict[str, int] = {}
    for osd in cluster.osds.values():
        for pg in osd.pgs.values():
            if pg.is_primary:
                states[str(pg.state)] = states.get(str(pg.state), 0) + 1
    print(f"cluster failed; primary PG states {states}{_slow_text()}",
          file=sys.stderr)
    for osd_id, osd in sorted(cluster.osds.items()):
        ops = osd.op_tracker.dump_ops_in_flight()
        if ops.get("num_ops"):
            print(f"osd.{osd_id} in flight: {json.dumps(ops)[:3000]}",
                  file=sys.stderr)


# -- four chips -----------------------------------------------------------

async def _pool_shards(seed: int, objects: int, size: int,
                       mesh: bool) -> tuple[dict, dict]:
    """Write the cluster phase's objects and return every stored shard
    ((name, shard) -> bytes) plus the host coalescer's stats."""
    from ceph_tpu.osd import mesh_coalesce

    mesh_coalesce.reset_host_coalescer()
    cluster, rados, io = await _make_cluster(
        {"osd_ec_mesh_coalesce": mesh})
    try:
        dt = await _write_all(io, seed, objects, size)
        pool = _pool_id(rados)
        shards = {}
        for osd in cluster.osds.values():
            for i in range(objects):
                shard, got = _shard_of(osd.store, pool, f"obj-{i}")
                shards[(i, shard)] = got
        stats = mesh_coalesce.host_coalescer().stats() if mesh else {}
        log(f"{'mesh' if mesh else 'one-device'} arm: {objects} x "
            f"{size / MIB:g} MiB written in {dt:.2f} s = "
            f"{objects / dt:.2f} objects/s{_slow_text()}")
        return shards, stats
    except BaseException:
        _dump_cluster_state(cluster)
        raise
    finally:
        await cluster.stop()


def multichip_phase(seed: int, objects: int, size: int) -> None:
    import __graft_entry__

    __graft_entry__._dryrun_body(4)
    one, _ = asyncio.run(_pool_shards(seed, objects, size, mesh=False))
    mesh, stats = asyncio.run(_pool_shards(seed, objects, size, mesh=True))
    check(one.keys() == mesh.keys() and len(one) == objects * 12,
          "the two arms stored different shard sets")
    diff = [key for key in one if one[key] != mesh[key]]
    check(not diff, f"mesh arm shards differ from one-device arm: "
          f"{diff[:4]}")
    per_dev = stats["per_device_stripes"]
    log(f"mesh coalescer: {stats['launches']} launches, "
        f"{stats['ops']} ops, per-device stripes {per_dev}")
    check(stats["devices"] == 4 and len(per_dev) == 4
          and all(v > 0 for v in per_dev.values()),
          f"the mesh launches did not split over all four devices: "
          f"{per_dev}")
    check(stats["failed_ops"] == 0, f"mesh coalescer failed ops: {stats}")
    log(f"mesh arm parity and data shards ({len(one)}) bit-identical to "
        "the one-device arm")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--objects", type=int, default=256,
                   help="cluster-phase object count (halve it only when "
                        "a run's time limit forces it)")
    args = p.parse_args(argv)

    device = device_check(args.chips)
    start_compile_accounting()
    if args.objects != 256:
        log(f"cut: {args.objects} objects instead of 256")
    size = 4 * MIB
    if args.chips == 4:
        multichip_phase(args.seed, args.objects, size)
    else:
        kernel_phase(args.seed)
        asyncio.run(cluster_phase(args.seed, args.objects, size))
    log(f"compile requests: {_EVENTS['compiles']}, persistent cache hits "
        f"{_EVENTS['cache_hits']}, misses {_EVENTS['cache_misses']}")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
