"""EC kernel and cluster benchmarks: k=8 m=4, 4KiB stripes, batched.

``python bench.py`` measures the kernel cells on a TPU and prints ONE
final JSON line {"metric", "value", "unit", "vs_baseline", "extra"}.
It refuses to run without a TPU: a number from the CPU backend is not
a device number.  ``--cfgN`` / ``--serve`` entries run their own
scenarios and print their own records.

Device timings use the serial-fori_loop protocol of
ceph_tpu.ec.benchmark.device_seconds_per_iter (iterations are data-
dependent; fixed costs cancel by differencing two iteration counts).
The headline value is the MEDIAN of HEADLINE_SAMPLES independent
measurements (min/max/samples reported in extra).

Baseline semantics: vs_baseline is device throughput over the in-repo
CPU reference (numpy GF, jerasure semantics) measured each run at the
same k/m and bytes-per-iteration (stripe subdivision is computation-
identical for a column-independent GF matrix code) — the same-harness
A/B the reference benchmark performs
(ceph_erasure_code_benchmark.cc:150-243).  The 5.0 GiB/s isa-l anchor
(qualitative "fast SIMD" per reference src/erasure-code/isa/README; no
absolute numbers are published) is kept as
extra.vs_isal_anchor_5gibps.

extra reports the BASELINE.md comparison configs:
  cfg1  reed_sol_van k=4 m=2, 1MiB object, CPU numpy reference (measured)
  cfg2  isa_vandermonde k=8 m=3, 4KiB stripes, device encode
  cfg3  cauchy_good k=10 m=4, 1024-stripe batch, device encode + decode
  headline config also reports decode and recovery (single-chunk repair)
  p50 per-op device latency.  cfg4 (CLAY mesh repair) and cfg5 (LRC group
  repair) are mesh collectives, exercised by dryrun_multichip and
  tests/test_sharding.py; their single-chip repair paths are reported here.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

from ceph_tpu.common.jaxutil import enable_compile_cache

ISA_L_BASELINE_GIBPS = 5.0
HEADLINE_SAMPLES = 5


def _require_tpu() -> None:
    """Device timings come only from a TPU: anything else raises."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"bench.py measures the TPU; JAX found {dev.platform!r} "
            f"({dev.device_kind}) — a CPU number is not a device number")


def _cpu_reference_encode_gibps(k: int = 4, m: int = 2,
                                nbytes: int = 1 << 20,
                                iters: int = 8, reps: int = 3) -> float:
    """In-repo CPU reference encode throughput (numpy GF, jerasure
    reed_sol_van semantics).  Defaults = BASELINE config #1
    (k=4 m=2, 1MiB); also run at the headline total size for the
    measured-vs-measured vs_baseline ratio.  GF matrix encode is
    column-independent, so one (k, N) call is byte-for-byte the same
    computation as N*k/stripe_width separate stripes — total bytes, not
    stripe subdivision, is what the CPU side must match.  Best-of-reps
    timing so a transiently loaded host doesn't inflate the ratio."""
    from ceph_tpu.ec import reference
    from ceph_tpu.ec.matrix import generator_matrix

    G = generator_matrix("reed_sol_van", k, m)
    data = np.random.default_rng(3).integers(
        0, 256, (k, nbytes // k), np.uint8
    )
    reference.encode(G, data)  # warm table construction
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(iters):
            reference.encode(G, data)
        best = min(best, time.perf_counter() - t0)
    return data.nbytes * iters / best / 2**30


def _recovery_latency_ms(ec, stripes: int = 1024) -> float:
    """Per-op device latency of a single-chunk repair (k survivors ->
    1 lost chunk) for a stripes x 4KiB-stripe batch.  Reuses run_decode's
    serial-loop protocol; the op is ~tens of us, so thousands of iterations
    spread the diff beyond host jitter."""
    from ceph_tpu.ec.benchmark import run_decode

    dec = run_decode(ec, size=stripes * 4096, iterations=3072,
                     stripes=stripes, erasures=1, erased=[3])
    return dec["seconds"] * 1e3


def _clay_repair_gibps(stripes: int = 128, sc: int = 1024) -> float:
    """cfg4 single-chip: CLAY k=8 m=4 d=11 repair as one device apply of
    the probed repair operator (recovered bytes per second; helper reads
    are d*sub/q = 11/4 of the recovered volume).  128 stripes x 64 KiB
    chunks is the whole-chunk-recovery shape — a 16-stripe batch (~3 MB
    per apply) measured launch overhead, not the kernel."""
    import jax.numpy as jnp

    from ceph_tpu.ec.benchmark import device_seconds_per_iter
    from ceph_tpu.ec.engine import default_engine
    from ceph_tpu.ec.registry import ErasureCodePluginRegistry
    from ceph_tpu.ec.repair_operator import clay_repair_operator

    from ceph_tpu.ec.pallas_kernels import bytes_to_words

    ec = ErasureCodePluginRegistry().factory(
        "clay", {"k": "8", "m": "4", "d": "11"}
    )
    C = ec.sub_chunk_no * sc
    data = np.random.default_rng(7).integers(
        0, 256, (stripes, ec.k, C), np.uint8
    )
    chunks = np.asarray(ec.encode_chunks_batch(data))
    lost = 3
    R, helpers, planes = clay_repair_operator(ec, lost)
    # shard layout: each (helper, repair-plane) stream is one
    # contiguous row — GF matrix application is column-independent,
    # so one (rows, stripes*sc) apply covers the whole stripe batch
    # with NO per-iteration relayout (the round-3 bench transposed
    # (B, rows, sc) inside the timed step)
    flat = np.ascontiguousarray(np.stack([
        chunks[:, h].reshape(stripes, ec.sub_chunk_no, sc)[:, planes]
        for h in helpers
    ], axis=1).reshape(stripes, len(helpers) * len(planes), sc)
        .transpose(1, 0, 2)
        .reshape(len(helpers) * len(planes), stripes * sc))
    eng = default_engine()
    words = bytes_to_words(jnp.asarray(flat))

    def step(i, x):
        rec = eng.apply_words(R, x)
        return x.at[0, 0].set(rec[0, 0] ^ i)

    sec = device_seconds_per_iter(step, words, lo=32, hi=160)
    return stripes * C / sec / 2**30


def _lrc_repair_gibps(stripes: int = 64, C: int = 1 << 20) -> float:
    """cfg5 single-chip: LRC k=12 m=4 local-group repair (one coefficient
    row over the l group members) — recovered bytes per second."""
    import jax.numpy as jnp

    from ceph_tpu.ec.benchmark import device_seconds_per_iter
    from ceph_tpu.ec.engine import default_engine
    from ceph_tpu.ec.registry import ErasureCodePluginRegistry
    from ceph_tpu.ec.repair_operator import lrc_repair_operator

    from ceph_tpu.ec.pallas_kernels import bytes_to_words

    ec = ErasureCodePluginRegistry().factory(
        "lrc", {"k": "12", "m": "4", "l": "4"}
    )
    lost = 0
    coeffs, minimum = lrc_repair_operator(ec, lost)
    # Shard layout: each group member's stream is one contiguous row.
    group = np.random.default_rng(9).integers(
        0, 256, (len(minimum), stripes * C), np.uint8
    )
    eng = default_engine()
    words = bytes_to_words(jnp.asarray(group))

    def step(i, x):
        rec = eng.apply_words(coeffs, x)
        return x.at[0, 0].set(rec[0, 0] ^ i)

    sec = device_seconds_per_iter(step, words, lo=32, hi=160)
    return stripes * C / sec / 2**30


def _cfg6_coalesce_ab(n_writes: int = 64, write_bytes: int = 4096) -> dict:
    """cfg6: cross-op EC coalescing A/B — n_writes concurrent 4 KiB
    small-writes through the full ECBackend write path (RMW, hinfo,
    shard fan-out) with the CoalescedLauncher on vs off.  The graded
    signal is the DEVICE LAUNCH COUNT (perf counter ec_device_launches,
    bumped once per _encode_batch/_decode_batch call), which is exact on
    any backend — CPU runs verify the launch count; the
    wall-clock ratio is reported alongside but only means something
    on-chip.  Read-back is verified bit-identical in both modes."""
    import asyncio

    from ceph_tpu.ec.registry import ErasureCodePluginRegistry
    from ceph_tpu.osd.ec_backend import ECBackend, LocalShard
    from ceph_tpu.store import CollectionId, MemStore, Transaction

    def make_backend(coalesce: bool) -> ECBackend:
        codec = ErasureCodePluginRegistry().factory(
            "jax_rs", {"k": "4", "m": "2", "technique": "reed_sol_van"}
        )
        shards = {}
        for i in range(6):
            store = MemStore()
            cid = CollectionId(1, 0, shard=i)
            asyncio.run(store.queue_transactions(
                Transaction().create_collection(cid)))
            shards[i] = LocalShard(store, cid, pool=1, shard=i)
        return ECBackend(codec, shards, stripe_unit=128,
                         coalesce=coalesce)

    async def run(be: ECBackend) -> float:
        datas = {f"obj-{i}": bytes([i % 256]) * write_bytes
                 for i in range(n_writes)}
        t0 = time.perf_counter()
        await asyncio.gather(*(
            be.write(o, d) for o, d in datas.items()
        ))
        dt = time.perf_counter() - t0
        for o, d in datas.items():
            got = await be.read(o)
            if got != d:
                raise AssertionError(f"cfg6 read-back mismatch on {o}")
        return dt

    out: dict = {"writes": n_writes, "write_bytes": write_bytes}
    for label, coalesce in (("on", True), ("off", False)):
        be = make_backend(coalesce)
        # one warm-up write outside the timed section absorbs the
        # first-launch compile, which would otherwise dominate either arm
        asyncio.run(run_warm(be))
        dump = be.perf.dump()
        warm_launches = float(dump.get("ec_device_launches", 0.0))
        dt = asyncio.run(run(be))
        dump = be.perf.dump()
        out[f"launches_{label}"] = (
            float(dump.get("ec_device_launches", 0.0)) - warm_launches
        )
        out[f"wall_s_{label}"] = round(dt, 4)
        if coalesce:
            st = be.coalescer.stats()
            out["occupancy"] = round(st["occupancy"], 2)
            out["pad_waste_stripes"] = float(
                dump.get("ec_coalesce_pad_waste", 0.0))
    out["launch_reduction"] = round(
        out["launches_off"] / max(out["launches_on"], 1.0), 1
    )
    return out


async def run_warm(be) -> None:
    await be.write("warmup", b"\x5a" * 512)


def _cfg6_main() -> None:
    """Standalone cfg6 entry (``python bench.py --cfg6``): CPU-sufficient
    — no TPU needed.  Appends its own metric record to
    BENCH_LOCAL.jsonl and prints it as the final JSON line."""
    cfg6 = _cfg6_coalesce_ab()
    record = {
        "metric": "ec_coalesce_64w_4KiB_launch_reduction",
        "value": cfg6["launch_reduction"],
        "unit": "x fewer device launches",
        "vs_baseline": cfg6["launch_reduction"],
        "extra": cfg6,
    }
    _append_local_record(record)
    print(json.dumps(record), flush=True)


def _cfg7_resident_ab(n_objects: int = 64, object_bytes: int = 16384,
                      sub_write_bytes: int = 512, rounds: int = 2) -> dict:
    """cfg7: device-resident EC data path A/B — the same workload (64
    objects fully written at 16 KiB, then ``rounds`` waves of 64
    concurrent 512 B sub-stripe overwrites) run once with the resident
    shard cache and once through the classic host path.  The graded
    signal is HOST<->DEVICE BYTES over the overwrite phase (perf
    counters ec_resident_h2d_bytes / ec_resident_d2h_bytes).  Both arms
    write through: each write uploads its RMW stripe and downloads all
    k+m encoded chunks for the store.  The >= 4x gate was set for the
    removed write-back mode, which uploaded only client bytes; the
    write-through arm does not reach it.  Both counters are exact
    logical-byte tallies, valid on CPU — no chip needed to verify the
    counter.  Read-back is verified bit-identical in both modes after a
    full eviction."""
    import asyncio

    from ceph_tpu.ec.registry import ErasureCodePluginRegistry
    from ceph_tpu.osd.ec_backend import ECBackend, LocalShard
    from ceph_tpu.store import CollectionId, MemStore, Transaction

    def make_backend(resident: bool) -> ECBackend:
        codec = ErasureCodePluginRegistry().factory(
            "jax_rs", {"k": "4", "m": "2", "technique": "cauchy_good"}
        )
        shards = {}
        for i in range(6):
            store = MemStore()
            cid = CollectionId(1, 0, shard=i)
            asyncio.run(store.queue_transactions(
                Transaction().create_collection(cid)))
            shards[i] = LocalShard(store, cid, pool=1, shard=i)
        # stripe_unit=1024, k=4 -> 4 KiB stripes (the ISSUE target size)
        return ECBackend(codec, shards, stripe_unit=1024,
                         resident=resident)

    async def populate(be: ECBackend) -> dict[str, bytearray]:
        datas = {f"obj-{i}": bytearray(bytes([i % 256]) * object_bytes)
                 for i in range(n_objects)}
        await asyncio.gather(*(
            be.write(o, bytes(d)) for o, d in datas.items()
        ))
        return datas

    async def overwrite_phase(be: ECBackend,
                              datas: dict[str, bytearray]) -> float:
        t0 = time.perf_counter()
        for r in range(rounds):
            off = 512 + r * 4096
            patch = bytes([0xA0 + r]) * sub_write_bytes
            await asyncio.gather(*(
                be.write(o, patch, offset=off) for o in datas
            ))
            for d in datas.values():
                d[off:off + sub_write_bytes] = patch
        return time.perf_counter() - t0

    async def verify(be: ECBackend, datas: dict[str, bytearray]) -> None:
        if be.resident is not None:
            be.resident.evict(target=0)
        for o, d in datas.items():
            got = await be.read(o)
            if got != bytes(d):
                raise AssertionError(f"cfg7 read-back mismatch on {o}")

    out: dict = {"objects": n_objects, "object_bytes": object_bytes,
                 "sub_write_bytes": sub_write_bytes, "rounds": rounds}
    for label, resident in (("resident", True), ("classic", False)):
        be = make_backend(resident)
        datas = asyncio.run(populate(be))
        h2d0 = be.perf.value("ec_resident_h2d_bytes")
        d2h0 = be.perf.value("ec_resident_d2h_bytes")
        dt = asyncio.run(overwrite_phase(be, datas))
        h2d = be.perf.value("ec_resident_h2d_bytes") - h2d0
        d2h = be.perf.value("ec_resident_d2h_bytes") - d2h0
        asyncio.run(verify(be, datas))
        out[f"h2d_bytes_{label}"] = h2d
        out[f"d2h_bytes_{label}"] = d2h
        out[f"xfer_bytes_{label}"] = h2d + d2h
        out[f"wall_s_{label}"] = round(dt, 4)
        if resident:
            out["resident_stats"] = be.resident_stats()
    out["xfer_reduction"] = round(
        out["xfer_bytes_classic"] / max(out["xfer_bytes_resident"], 1.0), 1
    )
    if out["xfer_reduction"] < 4.0:
        raise AssertionError(
            f"cfg7 transfer reduction {out['xfer_reduction']}x < 4x gate"
        )
    return out


def _cfg7_main() -> None:
    """Standalone cfg7 entry (``python bench.py --cfg7``): CPU-sufficient
    — the byte counters are exact on any backend.  Appends its own
    metric record to BENCH_LOCAL.jsonl and prints it as the final JSON
    line."""
    cfg7 = _cfg7_resident_ab()
    record = {
        "metric": "ec_resident_64w_512B_substripe_xfer_reduction",
        "value": cfg7["xfer_reduction"],
        "unit": "x fewer host<->device bytes",
        "vs_baseline": cfg7["xfer_reduction"],
        "extra": cfg7,
    }
    _append_local_record(record)
    print(json.dumps(record), flush=True)


def _cfg8_mesh_ab(n_writes: int = 32, write_bytes: int = 4096) -> dict:
    """cfg8: mesh-global EC coalescing A/B — the same concurrent
    small-write workload driven through TWO co-located ECBackends (two
    OSDs' worth of EC groups), once with both backends parked on ONE
    host-level MeshCoalescer (each flush is a single shard_map launch
    whose batch axis splits over every local device) and once with
    the per-backend single-device CoalescedLauncher of cfg6.  The graded
    signals are exact on any backend, so CPU runs verify them:

    - per-device batch counters (real addressable-shard row counts read
      off each placed launch) prove the batch axis split across ALL
      mesh devices, and cross_backend_launches proves ops from distinct
      backends rode one launch;
    - bit-identity for the corpus payloads: reed_sol_van through the
      full write/read path, SHEC through the sharded encode plane, and
      CLAY/LRC through the sharded sub-chunk repair plane;
    - CLAY/LRC degraded reads move >= 2x fewer inter-device bytes than
      whole-chunk recovery (ec_mesh_ici_bytes vs
      ec_mesh_ici_whole_bytes — hard-gated below)."""
    import asyncio

    import jax

    from ceph_tpu.ec.registry import ErasureCodePluginRegistry
    from ceph_tpu.osd.ec_backend import ECBackend, LocalShard
    from ceph_tpu.osd.mesh_coalesce import MeshCoalescer
    from ceph_tpu.store import CollectionId, MemStore, Transaction

    ndev = len(jax.devices())
    if ndev < 2:
        raise AssertionError(
            f"cfg8 needs a multi-device mesh, backend has {ndev}")

    def make_backend(profile: dict, plugin: str = "jax_rs",
                     unit: int = 128, **kw) -> ECBackend:
        codec = ErasureCodePluginRegistry().factory(plugin, profile)
        align = getattr(codec, "get_alignment", lambda: 1)()
        unit = -(-unit // align) * align
        shards = {}
        for i in range(codec.get_chunk_count()):
            store = MemStore()
            cid = CollectionId(1, 0, shard=i)
            asyncio.run(store.queue_transactions(
                Transaction().create_collection(cid)))
            shards[i] = LocalShard(store, cid, pool=1, shard=i)
        return ECBackend(codec, shards, stripe_unit=unit, **kw)

    RS = {"k": "4", "m": "2", "technique": "reed_sol_van"}

    async def run_pair(b1: ECBackend, b2: ECBackend) -> float:
        datas = {f"obj-{i}": bytes([i % 255 + 1]) * write_bytes
                 for i in range(n_writes)}
        t0 = time.perf_counter()
        await asyncio.gather(
            *(b1.write(o, d) for o, d in datas.items()),
            *(b2.write(o, d) for o, d in datas.items()),
        )
        dt = time.perf_counter() - t0
        for be in (b1, b2):
            for o, d in datas.items():
                got = await be.read(o)
                if got != d:
                    raise AssertionError(f"cfg8 read-back mismatch on {o}")
        return dt

    out: dict = {"writes_per_backend": n_writes, "backends": 2,
                 "write_bytes": write_bytes, "devices": ndev}

    # --- arm A: mesh-sharded (one host-level coalescer, two OSDs) ---
    co = MeshCoalescer()
    b1 = make_backend(RS, mesh_coalescer=co)
    b2 = make_backend(RS, mesh_coalescer=co)
    if b1.mesh_co is not co or b2.mesh_co is not co:
        raise AssertionError("cfg8: backends did not join the mesh plane")
    asyncio.run(run_warm(b1))
    asyncio.run(run_warm(b2))
    st0 = co.stats()
    warm_launches, warm_ops = st0["launches"], st0["ops"]
    out["wall_s_mesh"] = round(asyncio.run(run_pair(b1, b2)), 4)
    st = co.stats()
    out["launches_mesh"] = st["launches"] - warm_launches
    out["ops_mesh"] = st["ops"] - warm_ops
    out["cross_backend_launches"] = st["cross_backend_launches"]
    out["max_backends_in_launch"] = st["max_backends_in_launch"]
    out["occupancy_mesh"] = round(
        out["ops_mesh"] / max(out["launches_mesh"], 1), 2)
    # per-device scaling table: lifetime stripe rows per device, read off
    # the REAL addressable shards of each placed launch
    per_dev = dict(st["per_device_stripes"])
    out["per_device_stripes"] = {str(d): int(r)
                                 for d, r in sorted(per_dev.items())}
    out["last_per_device"] = {str(d): int(r) for d, r in
                              sorted(st["last_per_device"].items())}
    if len(per_dev) != ndev or any(r <= 0 for r in per_dev.values()):
        raise AssertionError(
            f"cfg8: batch axis did not split over all {ndev} devices: "
            f"{per_dev}"
        )
    if out["cross_backend_launches"] < 1:
        raise AssertionError(
            "cfg8: no launch carried ops from more than one backend"
        )

    # --- corpus bit-identity on the sharded planes ---
    import numpy as np
    rng = np.random.default_rng(8)

    # SHEC joins the mesh encode plane (generator, no decode_selection):
    # sharded encode must be bit-identical to the single-device launch.
    bs = make_backend({"k": "4", "m": "3", "c": "2"}, plugin="shec",
                      unit=1024, mesh_coalescer=co)
    if bs.mesh_co is not co:
        raise AssertionError("cfg8: shec backend did not join the mesh")
    batch = np.asarray(
        rng.integers(0, 256, (6, bs.k, bs.sinfo.chunk_size)), np.uint8)

    async def shec_check() -> None:
        mesh_out = np.asarray(await bs._coalesced_encode(batch))
        ref = np.asarray(await bs._encode_batch(batch))
        if not np.array_equal(mesh_out, ref):
            raise AssertionError("cfg8: shec sharded encode not "
                                 "bit-identical to single-device")

    asyncio.run(shec_check())
    out["shec_encode_bit_identical"] = True

    # CLAY / LRC ride the sharded sub-chunk repair plane on degraded
    # reads: bit-identity plus the >=2x ICI-byte gate.
    async def repair_check(be: ECBackend, lost: int) -> dict:
        data = np.asarray(
            rng.integers(0, 256, (4, be.k, be.sinfo.chunk_size)), np.uint8)
        full = np.asarray(await be._encode_batch(data))
        avail = {i: full[:, i] for i in range(be.n) if i != lost}
        got = await be._coalesced_decode(avail, [lost])
        if not np.array_equal(np.asarray(got[lost]), full[:, lost]):
            raise AssertionError("cfg8: sharded repair not bit-identical")
        d = be.perf.dump()
        moved = float(d.get("ec_mesh_ici_bytes", 0.0))
        whole = float(d.get("ec_mesh_ici_whole_bytes", 0.0))
        if be.mesh_stats["repairs"] < 1:
            raise AssertionError("cfg8: repair did not take the mesh plane")
        if not (moved > 0 and moved * 2 <= whole):
            raise AssertionError(
                f"cfg8: ICI gate failed — moved {moved} vs whole-chunk "
                f"{whole} (need moved*2 <= whole)"
            )
        return {"ici_bytes": moved, "whole_chunk_bytes": whole,
                "reduction": round(whole / moved, 2)}

    bc = make_backend({"k": "8", "m": "4", "d": "11"}, plugin="clay",
                      unit=1024, mesh_coalescer=co)
    out["clay_repair"] = asyncio.run(repair_check(bc, lost=3))
    bl = make_backend({"k": "12", "m": "4", "l": "4"}, plugin="lrc",
                      unit=1024, mesh_coalescer=co)
    out["lrc_repair"] = asyncio.run(repair_check(bl, lost=6))

    # --- arm B: per-backend single-device coalescer (cfg6 launcher) ---
    c1 = make_backend(RS, coalesce=True)
    c2 = make_backend(RS, coalesce=True)
    asyncio.run(run_warm(c1))
    asyncio.run(run_warm(c2))
    warm = sum(float(be.perf.dump().get("ec_device_launches", 0.0))
               for be in (c1, c2))
    out["wall_s_single"] = round(asyncio.run(run_pair(c1, c2)), 4)
    out["launches_single"] = sum(
        float(be.perf.dump().get("ec_device_launches", 0.0))
        for be in (c1, c2)) - warm

    out["launch_reduction"] = round(
        out["launches_single"] / max(out["launches_mesh"], 1), 1)
    out["devices_engaged_mesh"] = len(per_dev)
    out["devices_engaged_single"] = 1
    return out


def _cfg8_main() -> None:
    """Standalone cfg8 entry (``python bench.py --cfg8``): launch counts,
    per-device shard layouts, and ICI byte counters are exact on any
    backend.  Runs on the devices this process sees — the chips of a
    TPU host, or a virtual CPU mesh set up by the caller's XLA_FLAGS."""
    cfg8 = _cfg8_mesh_ab()
    record = {
        "metric": "ec_mesh_2osd_32w_4KiB_cross_osd_batch_split",
        "value": cfg8["devices_engaged_mesh"],
        "unit": "devices sharing each coalesced launch",
        "vs_baseline": round(
            cfg8["devices_engaged_mesh"]
            / cfg8["devices_engaged_single"], 1),
        "extra": cfg8,
    }
    _append_local_record(record)
    print(json.dumps(record), flush=True)


def _cfg9_repair_ab(n_objects: int = 256, object_bytes: int = 4096) -> dict:
    """cfg9: batched locality-aware repair A/B — the same degraded set
    (n_objects objects with a shared lost-shard pattern) drained once
    through the classic per-object ``recover_shard`` loop and once
    through the repair engine's ``recover_batch``.  Graded signals are
    exact on any backend:

    - DEVICE LAUNCH COUNT (perf counter ec_device_launches): the
      batched drain must issue >= 8x fewer launches than the
      per-object loop (gate);
    - SURVIVOR READ BYTES on locality codecs: LRC repairs from the
      lost chunk's local group and CLAY from the d helpers' repair
      sub-chunks, so (read + saved) / read — the whole-chunk
      counterfactual over the locality read — must be >= 1.5x on both
      (gate; the geometric ratios are k/l = 3.0 and qk/d ~ 2.9);
    - BIT-IDENTITY across four jax_rs techniques (reed_sol_van,
      cauchy_good, isa_vandermonde, liberation): rebuilt shard bytes
      must equal the pre-kill bytes and client read-back must round-
      trip (gate).
    """
    import asyncio

    from ceph_tpu.ec.registry import ErasureCodePluginRegistry
    from ceph_tpu.osd.ec_backend import ECBackend, LocalShard
    from ceph_tpu.osd.repair import clear_plan_cache
    from ceph_tpu.store import CollectionId, GHObject, MemStore, \
        Transaction

    def make_backend(plugin: str, profile: dict,
                     stripe_unit=None) -> ECBackend:
        codec = ErasureCodePluginRegistry().factory(plugin, profile)
        stores, shards = {}, {}
        for i in range(codec.get_chunk_count()):
            store = MemStore()
            cid = CollectionId(1, 0, shard=i)
            asyncio.run(store.queue_transactions(
                Transaction().create_collection(cid)))
            stores[i] = (store, cid)
            shards[i] = LocalShard(store, cid, pool=1, shard=i)
        be = ECBackend(codec, shards, stripe_unit=stripe_unit)
        be._bench_stores = stores
        return be

    async def seed(be: ECBackend, nobj: int, lost: list[int]):
        """Write nobj objects, snapshot the lost shards, delete them."""
        originals, true_shards = {}, {}
        for i in range(nobj):
            data = (i % 251).to_bytes(1, "big") * object_bytes
            originals[f"obj-{i}"] = data
            await be.write(f"obj-{i}", data)
        for name in originals:
            for s in lost:
                true_shards[(name, s)] = \
                    await be.shards[s].read_shard(name)
                store, cid = be._bench_stores[s]
                await store.queue_transactions(Transaction().remove(
                    cid, GHObject(1, name, shard=s)))
        return originals, true_shards

    async def verify(be, originals, true_shards, lost,
                     client_read: bool = True):
        for name, data in originals.items():
            for s in lost:
                got = await be.shards[s].read_shard(name)
                if got != true_shards[(name, s)]:
                    raise AssertionError(
                        f"cfg9 rebuilt shard mismatch {name} s{s}")
            # lrc's mapped layout has no ECBackend client-read path;
            # shard-level identity is the repair contract there
            if client_read and await be.read(name) != data:
                raise AssertionError(f"cfg9 read-back mismatch {name}")

    out: dict = {"objects": n_objects, "object_bytes": object_bytes}
    rs_prof = {"k": "4", "m": "2", "technique": "reed_sol_van"}
    lost = [1, 4]

    # -- A-arm: classic per-object recover_shard loop -------------------
    clear_plan_cache()
    be_a = make_backend("jax_rs", rs_prof, stripe_unit=128)

    async def run_a():
        originals, true_shards = await seed(be_a, n_objects, lost)
        base = be_a.perf.value("ec_device_launches")
        t0 = time.perf_counter()
        for name in originals:
            await be_a.recover_shard(name, lost)
        dt = time.perf_counter() - t0
        launches = be_a.perf.value("ec_device_launches") - base
        await verify(be_a, originals, true_shards, lost)
        return launches, dt

    out["launches_per_object"], out["wall_s_per_object"] = \
        asyncio.run(run_a())

    # -- B-arm: batched engine drain ------------------------------------
    clear_plan_cache()
    be_b = make_backend("jax_rs", rs_prof, stripe_unit=128)

    async def run_b():
        originals, true_shards = await seed(be_b, n_objects, lost)
        base = be_b.perf.value("ec_device_launches")
        t0 = time.perf_counter()
        res = await be_b.recover_batch(list(originals), lost, {})
        dt = time.perf_counter() - t0
        launches = be_b.perf.value("ec_device_launches") - base
        if set(res["recovered"]) != set(originals):
            raise AssertionError("cfg9 batched drain left objects behind")
        await verify(be_b, originals, true_shards, lost)
        return launches, dt

    out["launches_batched"], out["wall_s_batched"] = asyncio.run(run_b())
    out["launch_reduction"] = round(
        out["launches_per_object"] / max(out["launches_batched"], 1.0), 1
    )
    if out["launch_reduction"] < 8.0:
        raise AssertionError(
            f"cfg9 launch reduction {out['launch_reduction']}x < 8x gate")

    # -- locality read-byte gates: LRC group-local, CLAY sub-chunk ------
    for tag, plugin, profile, single in (
        ("lrc", "lrc", {"k": "12", "m": "4", "l": "4"}, 3),
        ("clay", "clay", {"k": "8", "m": "4", "d": "11"}, 3),
    ):
        clear_plan_cache()
        be = make_backend(plugin, profile)

        async def run_locality(be=be, single=single, tag=tag):
            originals, true_shards = await seed(be, 64, [single])
            res = await be.recover_batch(list(originals), [single], {})
            if res["strategy"] != tag:
                raise AssertionError(
                    f"cfg9 {tag}: strategy {res['strategy']}")
            await verify(be, originals, true_shards, [single],
                         client_read=(tag == "clay"))
            read = be.perf.value("ec_repair_read_bytes")
            saved = be.perf.value("ec_repair_read_bytes_saved")
            return read, saved

        read, saved = asyncio.run(run_locality())
        ratio = round((read + saved) / max(read, 1), 2)
        out[f"read_bytes_{tag}"] = read
        out[f"read_bytes_saved_{tag}"] = saved
        out[f"read_reduction_{tag}"] = ratio
        if ratio < 1.5:
            raise AssertionError(
                f"cfg9 {tag} read reduction {ratio}x < 1.5x gate")

    # -- bit-identity across the jax_rs technique matrix ----------------
    techniques = [
        ({"k": "4", "m": "2", "technique": "reed_sol_van"}, [1, 4]),
        ({"k": "4", "m": "2", "technique": "cauchy_good"}, [1, 4]),
        ({"k": "4", "m": "2", "technique": "isa_vandermonde"}, [1, 4]),
        # liberation is w-constrained: the corpus-pinned k=5 m=2 w=7
        ({"k": "5", "m": "2", "technique": "liberation", "w": "7"},
         [1, 5]),
    ]
    for profile, tlost in techniques:
        clear_plan_cache()
        # liberation's bit-matrix alignment (w=7 packets) rejects a
        # 128 B unit; the codec's own chunk size is always aligned
        unit = 128 if profile["technique"] != "liberation" else None
        be = make_backend("jax_rs", profile, stripe_unit=unit)

        async def run_tech(be=be, tlost=tlost):
            originals, true_shards = await seed(be, 16, tlost)
            res = await be.recover_batch(list(originals), tlost, {})
            if set(res["recovered"]) != set(originals):
                raise AssertionError(
                    f"cfg9 {profile['technique']}: incomplete batch")
            await verify(be, originals, true_shards, tlost)

        asyncio.run(run_tech())
    out["techniques_bit_identical"] = [
        p["technique"] for p, _ in techniques]
    return out


def _cfg9_main() -> None:
    """Standalone cfg9 entry (``python bench.py --cfg9``): CPU-sufficient
    — the launch-count and read-byte signals are exact perf counters on
    any backend.  Appends its own metric record to BENCH_LOCAL.jsonl and
    prints it as the final JSON line."""
    cfg9 = _cfg9_repair_ab()
    record = {
        "metric": "ec_repair_256obj_batched_launch_reduction",
        "value": cfg9["launch_reduction"],
        "unit": "x fewer device launches",
        "vs_baseline": cfg9["launch_reduction"],
        "extra": cfg9,
    }
    _append_local_record(record)
    print(json.dumps(record), flush=True)


def _cfg11_rescan_ab(n_osds: int = 200, pg_num: int = 8192) -> dict:
    """cfg11: whole-PG-space rescan A/B at 200 OSDs / 8k PGs — the
    epoch-cached OSDMapMapping table (one vectorized numpy pass, what
    every OSD now pays per map epoch) vs the legacy scalar per-PG CRUSH
    walk, on the same map with live upmap/pg_temp/primary_temp overlays
    and down OSDs.  Lookups are asserted bit-identical across the full
    PG space before any timing counts."""
    import time as _time

    from ceph_tpu.osd.osd_map import Incremental, NO_OSD, OSDMap, PoolInfo
    from ceph_tpu.placement.crush_map import CrushMap

    osds_per_host = 4
    crush = CrushMap()
    root = crush.add_bucket("default", "root")
    osd = 0
    for h in range(n_osds // osds_per_host):
        host = crush.add_bucket(f"host{h}", "host")
        for _ in range(osds_per_host):
            crush.add_item(host, osd, 1.0)
            osd += 1
        crush.add_item(root, host)
    crush.create_replicated_rule("replicated_rule", failure_domain="host")
    m = OSDMap(crush)
    inc = Incremental(1)
    for i in range(n_osds):
        inc.new_up[i] = f"osd.{i}:1{i:04d}"
    inc.new_pools.append(PoolInfo(
        1, "scale", "replicated", size=3, pg_num=pg_num))
    m.apply_incremental(inc)
    # overlays + failures so the cached path exercises its fixups, not
    # just the clean bulk pass
    inc = Incremental(2)
    inc.new_down = [7, 42, 133]
    for ps in range(0, pg_num, 257):
        inc.new_pg_upmap_items[(1, ps)] = [(ps % n_osds,
                                            (ps * 7 + 11) % n_osds)]
    for ps in range(1, pg_num, 511):
        inc.new_pg_temp[(1, ps)] = [(ps + j) % n_osds for j in range(3)]
    for ps in range(2, pg_num, 1023):
        inc.new_primary_temp[(1, ps)] = (ps * 13) % n_osds
    m.apply_incremental(inc)

    def scalar_row(ps):
        up = m.raw_row_to_up(1, ps, m._pg_to_raw_osds_scalar(1, ps))
        acting = list(m.pg_temp.get((1, ps), up)) or up
        primary = m.primary_temp.get((1, ps))
        up_primary = next((o for o in up if o != NO_OSD), NO_OSD)
        acting_primary = (
            primary if primary is not None
            else next((o for o in acting if o != NO_OSD), NO_OSD)
        )
        return up, up_primary, acting, acting_primary

    # A: the legacy rescan — one scalar CRUSH walk per PG
    t0 = _time.perf_counter()
    scalar = [scalar_row(ps) for ps in range(pg_num)]
    t_scalar = _time.perf_counter() - t0

    # cold build: includes the one-off bulk CRUSH pass (paid once per
    # crush/weight change, then carried across overlay-only epochs)
    mapping = m.mapping()
    mapping.invalidate()
    t0 = _time.perf_counter()
    tables = mapping.up_acting_tables(1)
    t_cold = _time.perf_counter() - t0

    for ps in range(pg_num):
        if tables.lookup(ps) != scalar[ps]:
            raise AssertionError(
                f"cfg11 table/scalar drift at pg {ps}: "
                f"{tables.lookup(ps)} != {scalar[ps]}")

    # B: the steady-state rescan an OSD pays per overlay epoch —
    # vectorized up/acting rebuild off the epoch-cached raw rows
    reps = 5
    t0 = _time.perf_counter()
    for _ in range(reps):
        tables = mapping.up_acting_tables(1)
    t_warm = (_time.perf_counter() - t0) / reps

    out = {
        "n_osds": n_osds, "pg_num": pg_num,
        "scalar_rescan_s": round(t_scalar, 4),
        "cached_cold_s": round(t_cold, 4),
        "cached_warm_s": round(t_warm, 5),
        "speedup_cold": round(t_scalar / t_cold, 1),
        "speedup_warm": round(t_scalar / t_warm, 1),
        "bit_identical_pgs": pg_num,
    }
    if out["speedup_warm"] < 20:
        raise AssertionError(
            f"cfg11 warm rescan speedup {out['speedup_warm']}x < 20x gate")
    return out


def _cfg11_main() -> None:
    """Standalone cfg11 entry (``python bench.py --cfg11``): pure
    control-plane numpy/CPU work, no device needed.  Appends its record
    to BENCH_LOCAL.jsonl and prints it as the final JSON line."""
    cfg11 = _cfg11_rescan_ab()
    record = {
        "metric": "osdmap_rescan_200osd_8kpg_cached_speedup",
        "value": cfg11["speedup_warm"],
        "unit": "x faster full PG-space rescan",
        "vs_baseline": cfg11["speedup_warm"],
        "extra": cfg11,
    }
    _append_local_record(record)
    print(json.dumps(record), flush=True)


def _cfg10_serve(seed: int = 0, ops_per_phase: int = 240,
                 clients: int = 4, defend: bool = False) -> dict:
    """cfg10: serving-load SLO scenario (``python bench.py --serve``).

    Three phases over one EC (jax_rs k=2 m=1) DevCluster with the mgr
    SLO module armed:

      baseline  closed-loop seeded load on a healthy cluster;
      recovery  kill one OSD, serve degraded, revive it mid-phase so
                the batched repair engine rebuilds its shards UNDER
                client load — the interference case the rebuild-floor
                objective and the utilization panel exist for;
      drain     open-loop (fixed-arrival) load on the re-healed
                cluster — the tapering-traffic regime.

    Each phase gets its own SLO verdict: a fresh SLOEngine is fed the
    per-OSD counter snapshots at the phase edges (window == phase), so
    every objective is judged on exactly that phase's traffic.  Op
    schedules derive from the seed alone (plan_sha256 in each phase
    record proves two runs issued identical streams); wall-clock
    numbers are the measurement, not the schedule.

    ``defend=True`` arms the PR-15 QoS defense plane (cfg12: same
    scenario, ``qos_enable`` on): the mgr QoS module backs the
    recovery mClock class off while client latency burns and pushes
    quantile-adaptive EC hedge timeouts — the A/B against defend=False
    is the storm-flip acceptance measurement."""
    import asyncio
    import hashlib

    async def run() -> dict:
        from ceph_tpu.common.slo import SLOEngine, make_target
        from ceph_tpu.testing.loadgen import LoadGen, RadosBackend
        from ceph_tpu.vstart import DevCluster

        overrides = {
            "mon_osd_down_out_interval": 300.0,  # we control revive
            "slo_put_p99_ms": 600.0, "slo_get_p999_ms": 400.0,
            "slo_error_rate": 0.01, "slo_rebuild_floor_gibs": 5e-5,
            "slo_window": 30.0,
            "slo_raise_evals": 1, "slo_clear_evals": 1,
            # class attribution: all serve load runs as tenant class
            # "gold"; burn-pair windows shrunk to the phase timescale
            # so the 5m/1h model raises/clears within the replay
            "slo_burn_fast_s": 2.0, "slo_burn_slow_s": 6.0,
        }
        if defend:
            # the defense plane reacts within one burning eval and
            # hedges off a short healthy-read quantile: the kill-phase
            # stragglers (sub-ops parked on the dead OSD) get
            # reconstructed instead of waited out
            overrides.update({
                "qos_enable": True,
                "qos_hedge_min_samples": 8,
                "qos_hedge_max_ms": 100.0,
            })
        cluster = DevCluster(n_mons=1, n_osds=4, overrides=overrides)
        await cluster.start()
        mgr = await cluster.start_mgr(report_interval=0.2)
        rados = await cluster.client()
        r = await rados.mon_command(
            "osd erasure-code-profile set", name="serve_ec",
            profile={"plugin": "jax_rs", "k": "2", "m": "1",
                     "crush-failure-domain": "osd"})
        assert r["rc"] in (0, -17), r
        await rados.pool_create("serve", pg_num=8, pool_type="erasure",
                                erasure_code_profile="serve_ec")
        io = await rados.open_ioctx("serve")
        await cluster.wait_health_ok()

        # calibrated so the healthy phases pass on a CPU-sim cluster
        # while the recovery storm HONESTLY violates the get tail —
        # the harness's job is to detect that, not hide it
        targets = [make_target("put_p99_ms", 600.0),
                   make_target("get_p999_ms", 400.0),
                   make_target("error_rate", 0.01),
                   make_target("rebuild_floor_gibs", 5e-5)]

        async def osd_dumps() -> dict:
            snap = await mgr.collect()
            return {f"osd.{o}": c
                    for o, c in snap["osd_perf"].items()}

        def rebuild_total(dumps: dict) -> float:
            return sum(float(d.get("ec_repair_rebuild_bytes", 0) or 0)
                       for d in dumps.values())

        def make_gen(phase_seed: int, mode: str, n_clients: int,
                     rate: float = 120.0) -> "LoadGen":
            return LoadGen(RadosBackend(io, prefix="serve"),
                           seed=phase_seed, mode=mode,
                           clients=n_clients, rate=rate,
                           total_ops=ops_per_phase, n_keys=48,
                           tenant_class="gold")

        phases: list[dict] = []

        async def run_phase(name: str, gen, recovery_active: bool,
                            mid_action=None) -> dict:
            # window >> phase so both edge snapshots stay in the deque
            eng = SLOEngine(targets, window=3600.0,
                            raise_evals=1, clear_evals=1)
            d0 = await osd_dumps()
            t0 = time.monotonic()
            eng.observe(t0, d0)
            if mid_action is None:
                res = await gen.run()
            else:
                res = await mid_action(gen)
            d1 = await osd_dumps()
            t1 = time.monotonic()
            eng.observe(t1, d1)
            evals = eng.evaluate(recovery_active=recovery_active)
            wall = max(t1 - t0, 1e-9)
            rebuild_b = max(0.0, rebuild_total(d1) - rebuild_total(d0))
            plan_sha = hashlib.sha256(
                json.dumps(gen.plan(), sort_keys=True).encode()
            ).hexdigest()[:16]
            rec = {
                "phase": name, "wall_s": round(wall, 3),
                "plan_sha256": plan_sha,
                "rebuild_gibs": round(rebuild_b / (1 << 30) / wall, 6),
                "client_p50_ms": res["p50_ms"],
                "client_p99_ms": res["p99_ms"],
                "client_p999_ms": res["p999_ms"],
                "loadgen": res,
                "slo": [{k: e.get(k) for k in
                         ("objective", "ok", "burn_rate", "value",
                          "worst_daemon", "samples")} for e in evals],
                "pass": all(e["ok"] for e in evals),
            }
            # the mgr's live tenant-class verdict at phase end: the
            # storm phase's SLO_VIOLATION must NAME the burning class
            # (all serve load is stamped "gold")
            slo_mod = mgr.modules.get("slo")
            rec["classes"] = dict(
                getattr(slo_mod, "class_eval", None) or {})
            chk = slo_mod.health_checks() if slo_mod else {}
            rec["tenant_class"] = (chk.get("SLO_VIOLATION")
                                   or {}).get("tenant_class", "")
            # flight-recorder: every phase verdict carries its forensic
            # bundle (id + on-disk path + worst daemon) into the
            # BENCH_LOCAL.jsonl record, so a failed phase can be
            # replayed offline with `ceph-tpu forensics show <id>`.
            # worst_daemon mirrors the SLO payload's choice: the worst
            # daemon of the hottest-burning failed objective.
            worst = ""
            bad = [e for e in evals if not e["ok"]]
            if bad:
                worst = max(bad, key=lambda e: e["burn_rate"]) \
                    .get("worst_daemon") or ""
            try:
                entry = await mgr.forensics_capture(
                    f"serve:{name}:"
                    + ("pass" if rec["pass"] else "fail"),
                    worst_daemon=worst,
                    detail={"phase": name, "seed": seed,
                            "pass": rec["pass"]})
                rec["forensics"] = {"id": entry["id"],
                                    "bundle": entry["path"],
                                    "worst_daemon":
                                        entry["worst_daemon"]}
            except (ConnectionError, TimeoutError):
                rec["forensics"] = None
            phases.append(rec)
            return rec

        try:
            # phase 1: baseline — populate once, then measure clean
            gen0 = make_gen(seed, "closed", clients)
            await gen0.populate()
            await run_phase("baseline", gen0, recovery_active=False)

            # phase 2: recovery storm — serve degraded, then serve
            # THROUGH the rebuild the revive triggers
            victim = cluster.n_osds - 1

            async def storm(gen):
                await cluster.kill_osd(victim)
                res = await gen.run()
                await cluster.revive_osd(victim)
                # let the repair engine drain inside the phase window;
                # health is the wrong signal (an active SLO_VIOLATION
                # holds it in WARN by design) and degraded-objects
                # alone races peering (briefly 0 right after revive) —
                # wait for rebuild QUIESCENCE: no degraded objects and
                # a flat rebuild counter for several samples
                await asyncio.sleep(1.0)
                deadline = time.monotonic() + 20.0
                stable, last = 0, -1.0
                while time.monotonic() < deadline and stable < 3:
                    digest = mgr.last_digest or {}
                    cur = rebuild_total(await osd_dumps())
                    if cur == last and \
                            int(digest.get("degraded_objects", 0)) == 0:
                        stable += 1
                    else:
                        stable = 0
                    last = cur
                    await asyncio.sleep(0.3)
                return res

            await run_phase("recovery", make_gen(seed + 1, "closed",
                                                 clients),
                            recovery_active=True, mid_action=storm)

            # phase 3: drain — open-loop fixed arrivals on the healed
            # cluster (coordinated-omission-free tail measurement).
            # 40/s leaves headroom on the CPU sim: open loop stacks
            # delay honestly, so a transient hiccup at a hotter rate
            # flips the healthy verdict on scheduler noise alone.
            await run_phase("drain", make_gen(seed + 2, "open",
                                              clients, rate=40.0),
                            recovery_active=False)

            # cross-check: the mgr's own windowed view of the same run
            digest = mgr.last_digest or {}
            mgr_view = {"slo": digest.get("slo", {}),
                        "utilization": digest.get("utilization", {})}
            # the defense plane's decision trail: every retune/hedge
            # push the controller made, in order (same seed => same
            # sequence — the replayability acceptance check)
            qos_events = [
                {"type": e["type"], **(e.get("fields") or {})}
                for e in mgr.journal.snapshot()
                if str(e["type"]).startswith("qos.")]
            qos_view = {"defend": defend,
                        "state": digest.get("qos", {}),
                        "events": qos_events}
        finally:
            await rados.shutdown()
            await cluster.stop()

        return {"seed": seed, "defend": defend, "phases": phases,
                "verdicts": {p["phase"]: p["pass"] for p in phases},
                "qos": qos_view, "mgr_view": mgr_view}

    return asyncio.run(run())


def _storm_burn(out: dict) -> float:
    """Worst get_p999 burn rate in the recovery (storm) phase."""
    for p in out["phases"]:
        if p["phase"] != "recovery":
            continue
        for e in p["slo"]:
            if e["objective"] == "get_p999_ms":
                return float(e.get("burn_rate") or 0.0)
    return 0.0


def _serve_main() -> None:
    """Standalone cfg10/cfg12 entry
    (``python bench.py --serve [--seed N] [--defend on|off|ab]``):
    CPU-sufficient — the SLO verdict machinery, loadgen determinism,
    and counter plumbing are exact on any backend; on-chip the same
    scenario measures real device rebuild interference.  Appends its
    record (per-phase verdicts in extra.phases) to BENCH_LOCAL.jsonl
    and prints it as the final JSON line.

    ``--defend`` selects the cfg12 QoS A/B: ``on``/``off`` run one arm
    with the defense plane armed/disarmed; ``ab`` runs both arms at
    the same seed and appends ONE paired record whose value is the
    storm-phase burn improvement (off/on)."""
    seed = 0
    argv = sys.argv[1:]
    if "--seed" in argv:
        seed = int(argv[argv.index("--seed") + 1])
    defend = ""
    if "--defend" in argv:
        defend = argv[argv.index("--defend") + 1]
        if defend not in ("on", "off", "ab"):
            raise SystemExit(f"--defend {defend!r}: want on|off|ab")

    if defend == "ab":
        off = _cfg10_serve(seed=seed, defend=False)
        on = _cfg10_serve(seed=seed, defend=True)
        burn_off, burn_on = _storm_burn(off), _storm_burn(on)
        record = {
            "metric": "serving_slo_qos_defense_ab",
            # a fully-defended arm burns 0: floor the denominator so
            # the ratio reads "at least this much better"
            "value": round(burn_off / max(burn_on, 0.01), 3),
            "unit": "x storm get_p999 burn reduction (off/on, >=)",
            # acceptance: defenses flip the storm verdict outright, or
            # cut the burn >= 3x while rebuild stays above the floor
            "vs_baseline": float(
                on["verdicts"].get("recovery", False)
                or burn_off >= 3.0 * burn_on),
            "extra": {"seed": seed,
                      "storm_burn_off": round(burn_off, 3),
                      "storm_burn_on": round(burn_on, 3),
                      "retunes_on": len([e for e in on["qos"]["events"]
                                         if e["type"] == "qos.retune"]),
                      "off": off, "on": on},
        }
    else:
        out = _cfg10_serve(seed=seed, defend=(defend == "on"))
        passed = sum(1 for p in out["phases"] if p["pass"])
        v = out["verdicts"]
        record = {
            "metric": ("serving_slo_three_phase" if not defend
                       else f"serving_slo_defend_{defend}"),
            "value": round(passed / max(len(out["phases"]), 1), 3),
            "unit": "phase pass fraction",
            # expectation: healthy phases meet SLO; the storm phase's
            # verdict is the detection signal, pass or fail
            "vs_baseline": float(v.get("baseline", False)
                                 and v.get("drain", False)),
            "extra": out,
        }
    _append_local_record(record)
    print(json.dumps(record), flush=True)


def _cfg13_expansion(seed: int = 0, defend: bool = False) -> dict:
    """cfg13 single arm: the seeded live-expansion drill from
    testing/chaos.py as a bench scenario.  The drill itself asserts
    the hard gates (moved objects/bytes EQUAL the PoolTables.diff
    prediction, batched launches ≪ objects, client p99 and
    time-to-balanced inside SLO) — a returned dict IS a passed arm.

    ``defend=True`` arms the PR-15 QoS defense plane, which paces the
    motion as the backfill mClock class (its own AIMD floor/ceiling,
    distinct from failure recovery)."""
    import asyncio

    async def run() -> dict:
        from ceph_tpu.testing.chaos import run_expansion_drill

        overrides = None
        if defend:
            overrides = {"qos_enable": True,
                         "qos_hedge_min_samples": 8,
                         "qos_hedge_max_ms": 100.0}
        return await run_expansion_drill(seed=seed, overrides=overrides)

    return asyncio.run(run())


def _cfg13_main() -> None:
    """Standalone cfg13 entry
    (``python bench.py --cfg13 [--seed N] [--defend on|off|ab]``):
    CPU-sufficient — placement diff, motion accounting, and SLO
    verdicts are exact on any backend; on-chip the same drill measures
    real decode-launch batching.  Default (and ``--defend ab``) runs
    the QoS off/on pair at one seed and appends ONE paired record:
    value is the defended arm's time-to-balanced, vs_baseline proves
    both arms moved exactly what PoolTables.diff predicted while the
    defended arm held the client p99 SLO with backfill still draining
    to completion (above its floor, or it would never have balanced)."""
    seed = 0
    argv = sys.argv[1:]
    if "--seed" in argv:
        seed = int(argv[argv.index("--seed") + 1])
    defend = "ab"
    if "--defend" in argv:
        defend = argv[argv.index("--defend") + 1]
        if defend not in ("on", "off", "ab"):
            raise SystemExit(f"--defend {defend!r}: want on|off|ab")

    if defend == "ab":
        off = _cfg13_expansion(seed=seed, defend=False)
        on = _cfg13_expansion(seed=seed, defend=True)
        ok = (off["moved"]["objects"] == off["predicted"]["objects"]
              and on["moved"]["objects"] == on["predicted"]["objects"]
              and off["moved"]["bytes"] == off["predicted"]["bytes"]
              and on["moved"]["bytes"] == on["predicted"]["bytes"]
              and on["slo"]["pass"])
        record = {
            "metric": "expansion_rebalance_slo_ab",
            "value": on["slo"]["time_to_balanced_s"],
            "unit": "s time-to-balanced (QoS armed)",
            "vs_baseline": float(ok),
            "extra": {"seed": seed, "off": off, "on": on},
        }
    else:
        out = _cfg13_expansion(seed=seed, defend=(defend == "on"))
        record = {
            "metric": f"expansion_rebalance_slo_defend_{defend}",
            "value": out["slo"]["time_to_balanced_s"],
            "unit": "s time-to-balanced",
            "vs_baseline": float(
                out["moved"]["bytes"] == out["predicted"]["bytes"]
                and out["slo"]["pass"]),
            "extra": out,
        }
    _append_local_record(record)
    print(json.dumps(record), flush=True)


def _cfg14_scrub(seed: int = 0, objects: int = 64,
                 obj_size: int = 4096) -> dict:
    """cfg14 single arm: scrub-launch reduction A/B on a standalone
    EC backend.  ``objects`` uniform ``obj_size`` writes land in ONE
    shard-length group, so the batched deep scrub is exactly two device
    launches (one coalesced re-encode + one fused parity/CRC verify)
    against one-launch-per-object for the sequential oracle.  The
    launch counter is exact on any backend (CPU included); on-chip the
    same ratio is what keeps an always-on scrubber off the dispatch
    path.  Verdict parity between the two arms is asserted object by
    object — the cheap sweep may not weaken detection."""
    import asyncio

    import numpy as np

    async def run() -> dict:
        from ceph_tpu.ec.registry import ErasureCodePluginRegistry
        from ceph_tpu.osd.ec_backend import ECBackend, LocalShard
        from ceph_tpu.store import CollectionId, MemStore, Transaction

        codec = ErasureCodePluginRegistry().factory(
            "jax_rs", {"k": "4", "m": "2", "technique": "reed_sol_van"})
        store = MemStore()
        shards = {}
        for i in range(codec.get_chunk_count()):
            cid = CollectionId(1, 0, shard=i)
            await store.queue_transactions(
                Transaction().create_collection(cid))
            shards[i] = LocalShard(store, cid, pool=1, shard=i)
        be = ECBackend(codec, shards, stripe_unit=128)

        rng = np.random.default_rng(seed)
        names = [f"s{i:03d}" for i in range(objects)]
        for name in names:
            await be.write(
                name, rng.integers(0, 256, obj_size, np.uint8).tobytes())

        t0 = time.perf_counter()
        before = be.perf.value("ec_scrub_launches")
        out = await be.scrub_batch(names)
        batched_launches = be.perf.value("ec_scrub_launches") - before
        batched_s = time.perf_counter() - t0

        t0 = time.perf_counter()
        before = be.perf.value("ec_scrub_launches")
        oracle = {name: await be.scrub(name) for name in names}
        oracle_launches = be.perf.value("ec_scrub_launches") - before
        oracle_s = time.perf_counter() - t0

        mismatched = [n for n in names if out["reports"][n] != oracle[n]]
        unclean = [n for n in names if not out["reports"][n]["clean"]]
        return {
            "objects": objects,
            "obj_size": obj_size,
            "groups": out["groups"],
            "batched_launches": batched_launches,
            "oracle_launches": oracle_launches,
            "reduction_x": oracle_launches / max(batched_launches, 1.0),
            "batched_s": round(batched_s, 4),
            "oracle_s": round(oracle_s, 4),
            "verdicts_match": not mismatched,
            "mismatched": mismatched,
            "unclean": unclean,
        }

    return asyncio.run(run())


def _cfg14_main() -> None:
    """Standalone cfg14 entry
    (``python bench.py --cfg14 [--seed N] [--objects N]``):
    CPU-valid — launch accounting and verdict parity are exact on any
    backend.  Hard gate: the batched sweep must cut scrub launches by
    at least 16x on a 64-object uniform group (measured 32x: 2 launches
    vs 64) with per-object verdicts EQUAL to the sequential oracle and
    a clean corpus staying clean."""
    seed = 0
    objects = 64
    argv = sys.argv[1:]
    if "--seed" in argv:
        seed = int(argv[argv.index("--seed") + 1])
    if "--objects" in argv:
        objects = int(argv[argv.index("--objects") + 1])

    out = _cfg14_scrub(seed=seed, objects=objects)
    ok = (out["verdicts_match"]
          and not out["unclean"]
          and out["groups"] == 1
          and out["reduction_x"] >= 16.0)
    if not ok:
        raise SystemExit(f"cfg14 gate failed: {json.dumps(out)}")
    record = {
        "metric": "scrub_launch_reduction_64obj",
        "value": round(out["reduction_x"], 2),
        "unit": "x fewer device launches (batched sweep vs per-object)",
        "vs_baseline": float(ok),
        "extra": {"seed": seed, **out},
    }
    _append_local_record(record)
    print(json.dumps(record), flush=True)


def _cfg15_resync(seed: int = 0, defend: bool = False,
                  n_objects: int = 240, obj_size: int = 1 << 17,
                  clients: int = 4, max_window_s: float = 150.0) -> dict:
    """cfg15 single arm: cold-zone resync as a QoS class (PR-18).

    Two-zone MultisiteRealm; zone B is partitioned while ``n_objects``
    seeded payloads land on master zone A, then B's gateway handle is
    re-spliced so a fresh sync agent full-syncs the whole backlog FROM
    A while a closed-loop client GET stream hits A.  The replication
    reads and the client reads share A's OSD queues (and the one event
    loop), so an unpaced resync burns the client get tail.

    ``defend=True`` arms ``qos_enable`` on zone A's mgr — the SOURCE
    zone owns the replication decision because its clients are the
    ones burning — and attaches B's orchestrator to A's multisite
    module, which pushes the controller's replication-class rate to
    the agent actually doing the pull (``qos.replication_push``
    journal entries are the actuation proof).  The class is floored,
    so the arm gate requires CONVERGENCE (lag drained to zero,
    bit-identical read-back on B), not just a quiet client tail."""
    import asyncio
    import random

    async def run() -> dict:
        from ceph_tpu.msg import reset_local_namespace
        from ceph_tpu.vstart import MultisiteRealm

        reset_local_namespace()
        overrides = {
            "rgw_datalog_shards": 4,
            "mon_osd_down_out_interval": 300.0,
            "slo_put_p99_ms": 600.0, "slo_get_p999_ms": 20.0,
            "slo_error_rate": 0.01, "slo_rebuild_floor_gibs": 5e-5,
            "slo_window": 30.0,
            "slo_raise_evals": 1, "slo_clear_evals": 1,
        }
        if defend:
            overrides.update({
                "qos_enable": True,
                "qos_replication_max_ops": 12.0,
                "qos_replication_min_ops": 4.0,
            })
        realm = MultisiteRealm(
            ("a", "b"), n_osds=3, overrides=overrides,
            agent_kwargs={"poll_interval": 0.05, "seed": seed})
        await realm.start()
        loop = asyncio.get_running_loop()
        try:
            gw_a = realm.zones["a"]["gw"]
            gw_b = realm.zones["b"]["gw"]
            orch_b = realm.zones["b"]["orch"]

            # partition B while the backlog lands on A (the cold-zone
            # premise: B must later pull EVERYTHING as one full sync).
            # The orchestrator plans its agent asynchronously — wait
            # for it, or the "partition" snapshots an empty dict and
            # the agent spawns live moments later
            while not orch_b.agents:
                await asyncio.sleep(0.02)
            parted = dict(orch_b.agents)
            orch_b.agents.clear()
            for agent in parted.values():
                await agent.stop()

            rng = random.Random(f"cfg15:{seed}")
            bucket = "bench"
            await gw_a.create_bucket(bucket)
            payloads: dict[str, bytes] = {}
            for i in range(n_objects):
                key = f"obj-{i:04d}"
                payloads[key] = rng.randbytes(obj_size)
                await gw_a.put_object(bucket, key, payloads[key])

            # mgr started AFTER seeding so the SLO window judges the
            # measurement phase, not the bulk load
            mgr_a = await realm.zones["a"]["cluster"].start_mgr(
                report_interval=0.2)
            mgr_a.modules["multisite"].attach(orch_b)

            keys = sorted(payloads)
            lats: list[float] = []
            stop = asyncio.Event()

            async def client(i: int) -> None:
                crng = random.Random(f"cfg15:{seed}:client:{i}")
                while not stop.is_set():
                    key = keys[crng.randrange(len(keys))]
                    t0 = loop.time()
                    await gw_a.get_object(bucket, key)
                    lats.append((loop.time() - t0) * 1e3)

            tasks = [asyncio.ensure_future(client(i))
                     for i in range(clients)]
            # rejoin: the handle splice forces a replan, the fresh
            # agent full-syncs the whole backlog under the client load
            t0 = loop.time()
            await orch_b.set_gateway("a", realm.zones["a"]["gw"])

            async def resynced() -> bool:
                ag = orch_b.agents.get(("a", "b"))
                if ag is None or ag.perf.value("sync_full_passes") < 1:
                    return False
                led = await ag.lag()
                return led["entries"] == 0 and led["bytes"] == 0

            while not await resynced():
                assert loop.time() - t0 < max_window_s, "resync stall"
                await asyncio.sleep(0.1)
            resync_s = loop.time() - t0
            stop.set()
            await asyncio.gather(*tasks)

            # convergence gate: B serves every byte A holds
            for key, want in payloads.items():
                got = (await gw_b.get_object(bucket, key))["data"]
                assert got == want, key

            lats.sort()

            def pct(q: float) -> float:
                return lats[int(q * (len(lats) - 1))] if lats else 0.0

            ag = orch_b.agents.get(("a", "b"))
            digest = mgr_a.last_digest or {}
            get_obj = next(
                (o for o in digest.get("slo", {}).get("objectives", [])
                 if o.get("objective") == "get_p999_ms"), {})
            events = [
                {"type": e["type"], **(e.get("fields") or {})}
                for e in mgr_a.journal.snapshot()
                if str(e["type"]) == "qos.replication_push"
                or (str(e["type"]) == "qos.retune"
                    and (e.get("fields") or {}).get("clazz")
                    == "replication")]
            return {
                "seed": seed, "defend": defend,
                "objects": n_objects, "obj_size": obj_size,
                "resync_s": round(resync_s, 3),
                "client_ops": len(lats),
                "get_p50_ms": round(pct(0.5), 3),
                "get_p99_ms": round(pct(0.99), 3),
                "get_p999_ms": round(pct(0.999), 3),
                # the mgr SLO engine's own windowed view of the same
                # interference (OSD-side, thousands of samples — the
                # stable A/B statistic; the client percentiles above
                # are top-of-tail and noisy run to run)
                "slo_get_p999": {
                    "value_ms": round(float(get_obj.get("value", 0.0)),
                                      3),
                    "burn": round(float(get_obj.get("burn_rate", 0.0)),
                                  3),
                    "ok": bool(get_obj.get("ok", False)),
                },
                "sync": {
                    "bytes": ag.perf.value("sync_bytes"),
                    "put_ops": ag.perf.value("sync_put_ops"),
                    "paced_waits": ag.perf.value("sync_paced_waits"),
                },
                "mgr": {"slo": digest.get("slo", {}),
                        "qos": digest.get("qos", {}),
                        "pushed_rate": digest.get(
                            "multisite", {}).get("pushed_rate"),
                        "events": events},
                "converged": True,
            }
        finally:
            await realm.stop()

    return asyncio.run(run())


def _cfg15_main() -> None:
    """Standalone cfg15 entry
    (``python bench.py --cfg15 [--seed N] [--defend on|off|ab]``):
    CPU-sufficient — pacing, lag accounting, and convergence are exact
    on any backend; on-chip the replicated payloads additionally flow
    through real device checksum launches.  Default (and ``--defend
    ab``) runs the QoS off/on pair at one seed and appends ONE paired
    record: value is the get_p999 SLO burn ratio (unpaced resync over
    paced resync, from the mgr's own windowed objective — the stable
    statistic; client-sampled percentiles ride along in extra),
    vs_baseline proves both arms converged to lag zero with
    bit-identical read-back while the defended arm actually actuated
    (at least one ``qos.replication_push``) and held the objective
    the unpaced arm burns."""
    seed = 0
    argv = sys.argv[1:]
    if "--seed" in argv:
        seed = int(argv[argv.index("--seed") + 1])
    defend = "ab"
    if "--defend" in argv:
        defend = argv[argv.index("--defend") + 1]
        if defend not in ("on", "off", "ab"):
            raise SystemExit(f"--defend {defend!r}: want on|off|ab")

    if defend == "ab":
        off = _cfg15_resync(seed=seed, defend=False)
        on = _cfg15_resync(seed=seed, defend=True)
        pushes = [e for e in on["mgr"]["events"]
                  if e["type"] == "qos.replication_push"]
        burn_off = off["slo_get_p999"]["burn"]
        burn_on = on["slo_get_p999"]["burn"]
        ok = (off["converged"] and on["converged"]
              and len(pushes) >= 1
              and burn_on < 1.0            # defended arm holds the SLO
              and burn_off > burn_on)      # ...which the unpaced burns
        record = {
            "metric": "multisite_resync_qos_ab",
            "value": round(burn_off / max(burn_on, 0.01), 3),
            "unit": "x get_p999 burn shed by pacing the resync "
                    "(defend off/on, both converged to lag 0)",
            "vs_baseline": float(ok),
            "extra": {"seed": seed, "off": off, "on": on},
        }
    else:
        out = _cfg15_resync(seed=seed, defend=(defend == "on"))
        record = {
            "metric": f"multisite_resync_qos_defend_{defend}",
            "value": out["get_p999_ms"],
            "unit": "ms client get p999 during cold-zone resync",
            "vs_baseline": float(out["converged"]),
            "extra": out,
        }
    _append_local_record(record)
    print(json.dumps(record), flush=True)


def _cfg16_collect_ab(n_osds: int = 200, cycles: int = 12,
                      seed: int = 0) -> dict:
    """cfg16: delta-encoded perf collect A/B at 200 simulated OSDs.

    Drives the pure wire codec (common/perf_collect.py) over
    deterministic per-OSD dump streams shaped like a real dump: ~60
    registered counters per OSD (scalars + LONGRUNAVG pairs + log2
    histograms) of which only the serving-path handful moves each
    cycle — the registered-but-idle majority is exactly what the
    delta protocol elides.  Accounting is counter-verified: both arms
    meter bytes through the ONE :func:`payload_bytes` function, and
    the decoded dumps (hence any digest/tsdb built from them) are
    asserted bit-identical to the originals.  A mgr restart is
    injected mid-run (decoders dropped) to prove resync-on-ack-
    mismatch recovers byte-exactly.  Pure CPU — no device work."""
    from ceph_tpu.common.perf_collect import (
        DeltaCollectDecoder,
        DeltaCollectEncoder,
        payload_bytes,
    )
    from ceph_tpu.common.tsdb import TSDB

    rng = np.random.default_rng(seed)
    # dump shape mirrors a real OSD's registration surface: a handful
    # of serving-path counters that move every cycle, plus the long
    # tail of registered-but-idle subsystem counters (bluestore /
    # recovery / scrub / qos stats), LONGRUNAVG pairs, and log2
    # histograms that only move when THEIR path runs (class hists
    # with no ops of that class, ec hists with no device work)
    idle_scalars = [f"bluestore_stat_{i}" for i in range(24)] \
        + [f"recovery_stat_{i}" for i in range(8)] \
        + [f"scrub_stat_{i}" for i in range(8)]
    pair_keys = [f"avg_{i}" for i in range(16)]
    hist_keys = ["op_latency_us", "op_w_latency_us",
                 "op_r_latency_us", "op_class_gold_latency_us",
                 "op_class_bronze_latency_us",
                 "ec_encode_launch_us", "ec_decode_launch_us",
                 "ec_mesh_launch_us", "ec_coalesce_wait_hist_us",
                 "ec_scrub_verify_us", "subop_latency_us",
                 "journal_latency_us"]

    def fresh_dump() -> dict:
        d = {"op": 0, "op_w": 0, "op_r": 0, "op_error": 0,
             "ec_launch_bytes": 0, "ec_resident_hits": 0,
             "ec_resident_misses": 0, "tracer_ring_evictions": 0,
             "tracer_orphan_spans": 0}
        for k in idle_scalars:
            d[k] = int(rng.integers(0, 1000))
        for k in pair_keys:
            d[k] = {"sum": float(rng.integers(0, 1000)),
                    "avgcount": int(rng.integers(1, 100))}
        for k in hist_keys:
            d[k] = {"buckets": [0] * 32, "sum": 0.0, "count": 0}
        return d

    def advance(d: dict) -> dict:
        # the serving-path handful moves; everything else is the
        # registered-but-idle majority a full dump re-ships anyway
        out = json.loads(json.dumps(d))   # deep copy, JSON types only
        ops = int(rng.integers(1, 50))
        out["op"] += ops
        out["op_w"] += ops // 2
        out["op_r"] += ops - ops // 2
        out["ec_launch_bytes"] += int(rng.integers(0, 1 << 20))
        for k in ("op_latency_us", "op_w_latency_us"):
            h = out[k]
            b = int(rng.integers(4, 12))
            h["buckets"][b] += ops
            h["sum"] += float(ops * (1 << b))
            h["count"] += ops
        return out

    dumps = {osd: fresh_dump() for osd in range(n_osds)}
    encs = {osd: DeltaCollectEncoder() for osd in range(n_osds)}
    decs = {osd: DeltaCollectDecoder() for osd in range(n_osds)}
    restart_at = cycles // 2
    full_total = delta_total = 0
    delta_by_cycle: list[int] = []
    resyncs = 0
    ts_full = TSDB(raw_points=64, m1_points=16, h1_points=8)
    ts_delta = TSDB(raw_points=64, m1_points=16, h1_points=8)
    for cyc in range(cycles):
        if cyc == restart_at:
            # mgr restart: decoders (and their acks) are gone; the
            # encoders must detect the mismatch and full-resync
            decs = {osd: DeltaCollectDecoder()
                    for osd in range(n_osds)}
        cyc_delta = 0
        for osd in range(n_osds):
            dumps[osd] = advance(dumps[osd])
            full_total += payload_bytes({"counters": dumps[osd]})
            payload = encs[osd].encode(dumps[osd], decs[osd].epoch)
            nb = payload_bytes(payload)
            delta_total += nb
            cyc_delta += nb
            if payload.get("full"):
                resyncs += 1
            decoded = decs[osd].decode(payload)
            if decoded != dumps[osd]:
                raise AssertionError(
                    f"cfg16 decode drift osd {osd} cycle {cyc}")
        delta_by_cycle.append(cyc_delta)
        # the retention layer sees identical contents either way —
        # fold the same derived series from both arms' dumps
        t = float(cyc * 5)
        cluster_ops_a = sum(d["op"] for d in dumps.values())
        cluster_ops_b = sum(decs[o]._state["op"]
                            for o in range(n_osds))
        ts_full.observe(t, "cluster.ops", cluster_ops_a)
        ts_delta.observe(t, "cluster.ops", cluster_ops_b)
    tsq_a = json.dumps(ts_full.query("cluster.ops"), sort_keys=True)
    tsq_b = json.dumps(ts_delta.query("cluster.ops"), sort_keys=True)
    if tsq_a != tsq_b:
        raise AssertionError("cfg16 tsdb contents differ between arms")
    # steady state excludes the two bootstrap/restart resync cycles:
    # the per-cycle claim is about the running regime
    steady = [b for i, b in enumerate(delta_by_cycle)
              if i not in (0, restart_at)]
    full_per_cycle = full_total / cycles
    steady_per_cycle = sum(steady) / max(1, len(steady))
    ratio = full_per_cycle / max(1.0, steady_per_cycle)
    out = {
        "n_osds": n_osds, "cycles": cycles,
        "full_bytes_per_cycle": int(full_per_cycle),
        "delta_bytes_per_cycle_steady": int(steady_per_cycle),
        "delta_bytes_total": delta_total,
        "full_bytes_total": full_total,
        "bytes_ratio": round(ratio, 2),
        "resyncs": resyncs,
        "expected_resyncs": 2 * n_osds,
        "decoded_bit_identical": True,
        "tsdb_bit_identical": True,
    }
    if resyncs != 2 * n_osds:
        raise AssertionError(
            f"cfg16 resync accounting off: {resyncs} != {2 * n_osds}")
    if ratio < 5.0:
        raise AssertionError(
            f"cfg16 delta-collect ratio {ratio:.2f}x < 5x gate")
    return out


def _cfg16_main() -> None:
    """Standalone cfg16 entry (``python bench.py --cfg16``): pure
    CPU byte accounting — the wire codec, the payload meter, and the
    bit-identity assertions are exact on any backend."""
    out = _cfg16_collect_ab()
    record = {
        "metric": "perf_collect_delta_bytes_ab_200osd",
        "value": out["bytes_ratio"],
        "unit": "x fewer bytes/cycle (delta vs full collect)",
        "vs_baseline": out["bytes_ratio"],
        "extra": out,
    }
    _append_local_record(record)
    print(json.dumps(record), flush=True)


def _append_local_record(record: dict) -> None:
    """Append a successful run to BENCH_LOCAL.jsonl (the auditable local
    trail; PERF.md explains the protocol)."""
    import datetime

    rec = dict(record)
    rec["ts"] = datetime.datetime.now(datetime.timezone.utc).strftime(
        "%Y-%m-%dT%H:%M:%SZ"
    )
    here = os.path.dirname(os.path.abspath(__file__))
    try:
        with open(os.path.join(here, "BENCH_LOCAL.jsonl"), "a") as f:
            f.write(json.dumps(rec) + "\n")
    except OSError:
        pass


def main() -> None:
    _require_tpu()
    from ceph_tpu.ec.benchmark import make_codec, run_encode, run_decode, \
        verify_all_erasures

    # Correctness gate first: exhaustive erasure sweep on a small profile.
    gate = make_codec("jax_rs", ["k=4", "m=2", "technique=reed_sol_van"])
    verify_all_erasures(gate, size=4096)

    extra: dict = {}
    extra["cfg1_cpu_numpy_encode_gibps"] = round(
        _cpu_reference_encode_gibps(), 3
    )
    # Headline CPU reference: same k/m and same bytes-per-iteration as
    # the device headline (stripe subdivision is a no-op for column-
    # independent GF matrix encode — see _cpu_reference_encode_gibps).
    cpu_headline = _cpu_reference_encode_gibps(
        k=8, m=4, nbytes=16384 * 4096, iters=2, reps=3)
    extra["headline_cpu_numpy_encode_gibps"] = round(cpu_headline, 3)

    # Headline: k=8 m=4, 4KiB stripes (512B chunks), big resident batch.
    # Median of HEADLINE_SAMPLES independent measurements.
    ec = make_codec("jax_rs", ["k=8", "m=4", "technique=reed_sol_van"])
    stripes = 16384
    samples = []
    for si in range(HEADLINE_SAMPLES):
        enc = run_encode(ec, size=stripes * 4096, iterations=256,
                         stripes=stripes)
        samples.append(enc["GiBps"])
    samples.sort()
    value = samples[len(samples) // 2]
    extra["headline_samples_gibps"] = [round(s, 3) for s in samples]
    extra["headline_min_gibps"] = round(samples[0], 3)
    extra["headline_max_gibps"] = round(samples[-1], 3)

    dec = run_decode(ec, size=stripes * 4096, iterations=256, stripes=stripes,
                     erasures=4)
    extra["headline_decode_gibps"] = round(dec["GiBps"], 3)
    extra["recovery_p50_device_ms"] = round(_recovery_latency_ms(ec), 4)

    # cfg2: isa-parity RS k=8 m=3, 4KiB stripe units.
    ec2 = make_codec("jax_rs", ["k=8", "m=3", "technique=isa_vandermonde"])
    enc2 = run_encode(ec2, size=16384 * 4096, iterations=128, stripes=16384)
    extra["cfg2_encode_gibps"] = round(enc2["GiBps"], 3)

    # cfg3: Cauchy k=10 m=4, 1024-stripe batch (exact BASELINE wording).
    ec3 = make_codec("jax_rs", ["k=10", "m=4", "technique=cauchy_good"])
    enc3 = run_encode(ec3, size=1024 * 40960, iterations=128, stripes=1024)
    dec3 = run_decode(ec3, size=1024 * 40960, iterations=128, stripes=1024,
                      erasures=4)
    extra["cfg3_encode_gibps"] = round(enc3["GiBps"], 3)
    extra["cfg3_decode_gibps"] = round(dec3["GiBps"], 3)

    # cfg4/cfg5 single-chip repair (mesh versions run in dryrun_multichip
    # and tests/test_sharding.py).
    extra["cfg4_clay_repair_gibps"] = round(_clay_repair_gibps(), 3)
    extra["cfg5_lrc_repair_gibps"] = round(_lrc_repair_gibps(), 3)

    # cfg6: cross-op coalescing A/B (launch-count signal is exact on any
    # backend; on-chip the wall-clock ratio becomes meaningful too).
    extra["cfg6_coalesce"] = _cfg6_coalesce_ab()

    # cfg7: device-resident A/B (byte-counter signal is exact on any
    # backend; on-chip it closes the HBM roofline gap at 4 KiB stripes).
    extra["cfg7_resident"] = _cfg7_resident_ab()

    # cfg8: mesh-global coalescing A/B needs several local devices
    import jax

    if len(jax.devices()) >= 2:
        extra["cfg8_mesh"] = _cfg8_mesh_ab()
    else:
        extra["cfg8_mesh"] = "skipped (one device)"

    extra["vs_isal_anchor_5gibps"] = round(value / ISA_L_BASELINE_GIBPS, 3)
    record = {
        "metric": "ec_encode_k8_m4_4KiB_stripes",
        "value": round(value, 3),
        "unit": "GiB/s",
        "vs_baseline": round(value / cpu_headline, 3),
        "extra": extra,
    }
    _append_local_record(record)
    print(json.dumps(record), flush=True)


if __name__ == "__main__":
    enable_compile_cache()   # before any jit lowering: reruns skip compiles
    if "--cfg6" in sys.argv[1:]:
        _cfg6_main()
        sys.exit(0)
    if "--cfg7" in sys.argv[1:]:
        _cfg7_main()
        sys.exit(0)
    if "--cfg8" in sys.argv[1:]:
        _cfg8_main()
        sys.exit(0)
    if "--cfg9" in sys.argv[1:]:
        _cfg9_main()
        sys.exit(0)
    if "--serve" in sys.argv[1:]:
        _serve_main()
        sys.exit(0)
    if "--cfg11" in sys.argv[1:]:
        _cfg11_main()
        sys.exit(0)
    if "--cfg13" in sys.argv[1:]:
        _cfg13_main()
        sys.exit(0)
    if "--cfg14" in sys.argv[1:]:
        _cfg14_main()
        sys.exit(0)
    if "--cfg15" in sys.argv[1:]:
        _cfg15_main()
        sys.exit(0)
    if "--cfg16" in sys.argv[1:]:
        _cfg16_main()
        sys.exit(0)
    main()
