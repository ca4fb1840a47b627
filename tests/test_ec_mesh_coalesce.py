"""Mesh-global EC coalescing: the host-level MeshCoalescer.

PR 7's tentpole promotes cross-op coalescing from per-backend to the
host: ops from ALL co-located OSDs' EC backends flush as ONE
shard_map-sharded launch whose stripe batch splits over every local
jax device (the 8-device virtual CPU mesh here, see conftest).  Gates:
multi-OSD ops genuinely share a launch (cross_backend_launches, real
per-device shard layouts), bit-identity with the single-chip path
across the dense GF(2^8) techniques, solo ops and 1-device meshes
degrade gracefully, device-resident payloads feed sharded launches
with no host round trip, and CLAY/LRC single-chunk degraded reads move
counter-verified >= 2x fewer interconnect bytes than whole-chunk
repair.  A running OSD cluster's PG write and shard recovery ride the
coalescer, and ShardedApplier (the compile-once shard_map apply it
launches through) matches the codec's encode.
"""

import asyncio

import numpy as np
import pytest

from ceph_tpu.ec.registry import ErasureCodePluginRegistry
from ceph_tpu.osd.ec_backend import ECBackend, LocalShard
from ceph_tpu.osd.mesh_coalesce import (MeshCoalescer, host_coalescer,
                                        reset_host_coalescer)
from ceph_tpu.parallel.ec_sharding import ShardedApplier, make_ec_mesh
from ceph_tpu.store.memstore import MemStore
from ceph_tpu.store.object_store import Transaction
from ceph_tpu.store.types import CollectionId

# the four dense GF(2^8) techniques of the corpus matrix (bit-schedule
# codes have generator=None and keep the per-backend launcher)
MESH_PROFILES = [
    {"k": "4", "m": "2", "technique": "reed_sol_van"},
    {"k": "8", "m": "3", "technique": "isa_vandermonde"},
    {"k": "10", "m": "4", "technique": "cauchy_good"},
    {"k": "6", "m": "3", "technique": "isa_cauchy"},
]


async def _backend(profile=None, plugin="jax_rs", unit=128, **kw):
    profile = profile or {"k": "4", "m": "2",
                          "technique": "reed_sol_van"}
    codec = ErasureCodePluginRegistry().factory(plugin, profile)
    align = getattr(codec, "get_alignment", lambda: 1)()
    unit = -(-unit // align) * align
    store = MemStore()
    shards = {}
    for i in range(codec.get_chunk_count()):
        cid = CollectionId(1, 0, shard=i)
        await store.queue_transactions(
            Transaction().create_collection(cid)
        )
        shards[i] = LocalShard(store, cid, pool=1, shard=i)
    return ECBackend(codec, shards, stripe_unit=unit, **kw)


def _ndev():
    import jax

    return len(jax.devices())


def test_cross_osd_ops_share_one_sharded_launch():
    """Concurrent encodes from TWO backends (distinct stores — the
    two-OSD analog) land in ONE launch whose batch axis splits over
    every mesh device; results match each backend's own single-chip
    path byte for byte."""
    async def run():
        co = MeshCoalescer()
        be1 = await _backend(mesh_coalescer=co)
        be2 = await _backend(mesh_coalescer=co)
        assert be1.mesh_co is co and be2.mesh_co is co
        rng = np.random.default_rng(7)
        k, chunk = be1.k, be1.sinfo.chunk_size
        b1 = np.asarray(rng.integers(0, 256, (5, k, chunk)), np.uint8)
        b2 = np.asarray(rng.integers(0, 256, (3, k, chunk)), np.uint8)
        be1._inflight_ops = be2._inflight_ops = 2
        try:
            o1, o2 = await asyncio.gather(
                be1._coalesced_encode(b1), be2._coalesced_encode(b2))
        finally:
            be1._inflight_ops = be2._inflight_ops = 0
        st = co.stats()
        assert st["launches"] == 1 and st["ops"] == 2, st
        assert st["cross_backend_launches"] == 1, st
        # the proof the batch really fans out: REAL addressable-shard
        # layouts, every device holding rows, summing to the bucket
        n = _ndev()
        assert len(st["last_per_device"]) == n, st
        assert all(r > 0 for r in st["last_per_device"].values())
        assert sum(st["last_per_device"].values()) == 8  # pow2(5+3)
        w1 = await be1._encode_batch(b1)
        w2 = await be2._encode_batch(b2)
        assert np.array_equal(np.asarray(o1), np.asarray(w1))
        assert np.array_equal(np.asarray(o2), np.asarray(w2))
        # launch-level perf counters landed on a participating backend
        mesh_launches = (be1.perf.value("ec_mesh_launches")
                         + be2.perf.value("ec_mesh_launches"))
        assert mesh_launches == 1
        assert (be1.perf.value("ec_mesh_ops")
                + be2.perf.value("ec_mesh_ops")) == 2

    asyncio.run(run())


@pytest.mark.parametrize(
    "profile", MESH_PROFILES,
    ids=lambda p: f"k{p['k']}m{p['m']}_{p['technique']}")
def test_sharded_bit_identity_all_techniques(profile):
    """Encode AND decode through the mesh coalescer equal the direct
    single-device batch path for every dense technique."""
    async def run():
        co = MeshCoalescer()
        be = await _backend(profile, mesh_coalescer=co)
        assert be.mesh_co is co and be._mesh_dec_ok
        rng = np.random.default_rng(11)
        k, chunk = be.k, be.sinfo.chunk_size
        batches = [
            np.asarray(rng.integers(0, 256, (b, k, chunk)), np.uint8)
            for b in (1, 3, 8, 5, 2, 16, 7, 1)
        ]
        be._inflight_ops = len(batches) + 1
        try:
            outs = await asyncio.gather(*(
                be._coalesced_encode(s) for s in batches))
        finally:
            be._inflight_ops = 0
        assert co.stats()["launches"] < len(batches)
        for s, got in zip(batches, outs):
            want = await be._encode_batch(s)
            assert np.array_equal(np.asarray(got), np.asarray(want))

        full = [np.asarray(await be._encode_batch(s)) for s in batches]
        missing = [0, be.k]
        avails = [
            {i: c[:, i] for i in range(be.n) if i not in missing}
            for c in full
        ]
        be._inflight_ops = len(avails) + 1
        try:
            decs = await asyncio.gather(*(
                be._coalesced_decode(a, missing) for a in avails))
        finally:
            be._inflight_ops = 0
        for c, got in zip(full, decs):
            for w in missing:
                assert np.array_equal(np.asarray(got[w]), c[:, w])

    asyncio.run(run())


def test_solo_op_flushes_alone():
    """A solo op still launches (occupancy 1) — the idle fast path of
    the host launcher, no window stall, correct bytes."""
    async def run():
        co = MeshCoalescer(window_us=200_000.0)
        be = await _backend(mesh_coalescer=co)
        import time

        rng = np.random.default_rng(3)
        s = np.asarray(
            rng.integers(0, 256, (4, be.k, be.sinfo.chunk_size)),
            np.uint8)
        t0 = time.perf_counter()
        out = await be._coalesced_encode(s)
        assert time.perf_counter() - t0 < 1.0
        want = await be._encode_batch(s)
        assert np.array_equal(np.asarray(out), np.asarray(want))
        st = co.stats()
        assert st["launches"] == 1 and st["ops"] == 1
        assert st["cross_backend_launches"] == 0

    asyncio.run(run())


def test_one_device_mesh_degrades_to_backend_launcher():
    """A 1-device pool refuses registration: the backend keeps its
    per-backend CoalescedLauncher and everything still works."""
    async def run():
        import jax

        co = MeshCoalescer(devices=jax.devices()[:1])
        be = await _backend(mesh_coalescer=co)
        assert be.mesh_co is None
        assert be.coalescer is not None
        await be.write("obj", b"x" * 4096)
        assert await be.read("obj") == b"x" * 4096
        assert co.stats()["launches"] == 0
        assert be.coalescer.stats()["launches"] > 0

    asyncio.run(run())


def test_codec_without_generator_keeps_backend_launcher():
    """clay has no dense generator: sharded launches are refused (the
    repair plane is separate), the per-backend launcher serves ops."""
    async def run():
        co = MeshCoalescer()
        be = await _backend({"k": "4", "m": "2", "d": "5"},
                            plugin="clay", unit=1024,
                            mesh_coalescer=co)
        assert be.mesh_co is None and be._mesh_host is co

    asyncio.run(run())


def test_resident_device_batch_feeds_sharded_launch_no_h2d():
    """A device-resident stripe batch rides the sharded launch with NO
    host round trip: the h2d counter stays flat and the result comes
    back as a device array."""
    async def run():
        import jax.numpy as jnp

        co = MeshCoalescer()
        be = await _backend({"k": "4", "m": "2",
                             "technique": "reed_sol_van"},
                            mesh_coalescer=co, resident=True)
        assert be.resident is not None and be.mesh_co is co
        rng = np.random.default_rng(5)
        host = np.asarray(
            rng.integers(0, 256, (8, be.k, be.sinfo.chunk_size)),
            np.uint8)
        dev = jnp.asarray(host)
        h2d0 = be.perf.value("ec_resident_h2d_bytes")
        d2h0 = be.perf.value("ec_resident_d2h_bytes")
        out = await be._coalesced_encode(dev)
        assert be._is_device(out)
        assert be.perf.value("ec_resident_h2d_bytes") == h2d0
        assert be.perf.value("ec_resident_d2h_bytes") == d2h0
        want = await be._encode_batch(host)
        assert np.array_equal(np.asarray(out), np.asarray(want))
        assert co.stats()["launches"] == 1

    asyncio.run(run())


def test_resident_write_through_mesh_coalescer():
    """A resident backend on the host mesh coalescer writes through the
    sharded launch and splits its shard streams with the write path's
    jitted program: each shard's resident entry holds the bytes its
    store got, and the object reads back."""
    async def run():
        co = MeshCoalescer()
        be = await _backend({"k": "4", "m": "2",
                             "technique": "reed_sol_van"},
                            mesh_coalescer=co, resident=True)
        assert be.mesh_co is co
        data = np.random.default_rng(6).integers(
            0, 256, 5 * be.sinfo.stripe_width + 9, np.uint8).tobytes()
        await be.write("obj", data)
        assert co.stats()["launches"] == 1
        assert be.perf.value("ec_write_glue_fused") == 1
        for i in range(be.n):
            ent = be.resident.get(be.resident_ns, "obj", i, count=False)
            assert np.asarray(ent.arr).tobytes() == \
                await be.shards[i].read_shard("obj")
        assert await be.read("obj") == data

    asyncio.run(run())


def test_mixed_host_device_batchmates():
    """One device op + one host op share a launch; each gets its own
    representation back and the host op's transfers are counted."""
    async def run():
        import jax.numpy as jnp

        co = MeshCoalescer()
        be1 = await _backend(mesh_coalescer=co, resident=True)
        be2 = await _backend(mesh_coalescer=co)
        rng = np.random.default_rng(9)
        k, chunk = be1.k, be1.sinfo.chunk_size
        h1 = np.asarray(rng.integers(0, 256, (4, k, chunk)), np.uint8)
        h2 = np.asarray(rng.integers(0, 256, (2, k, chunk)), np.uint8)
        be1._inflight_ops = be2._inflight_ops = 2
        try:
            o1, o2 = await asyncio.gather(
                be1._coalesced_encode(jnp.asarray(h1)),
                be2._coalesced_encode(h2))
        finally:
            be1._inflight_ops = be2._inflight_ops = 0
        assert co.stats()["launches"] == 1
        assert be1._is_device(o1)
        assert isinstance(o2, np.ndarray)
        assert np.array_equal(np.asarray(o1),
                              np.asarray(await be1._encode_batch(h1)))
        assert np.array_equal(o2,
                              np.asarray(await be2._encode_batch(h2)))
        assert be2.perf.value("ec_resident_h2d_bytes") > 0
        assert be2.perf.value("ec_resident_d2h_bytes") > 0

    asyncio.run(run())


def test_poisoned_batchmate_solo_retries():
    """A malformed payload poisons only itself; batchmates transparently
    retry through their own single-device path."""
    async def run():
        co = MeshCoalescer()
        be = await _backend(mesh_coalescer=co)
        rng = np.random.default_rng(13)
        chunk = be.sinfo.chunk_size
        good = np.asarray(
            rng.integers(0, 256, (4, be.k, chunk)), np.uint8)
        bad = np.asarray(
            rng.integers(0, 256, (2, be.k + 1, chunk)), np.uint8)
        be._inflight_ops = 3
        try:
            res = await asyncio.gather(
                co.submit(be, ("enc",), good, 4),
                co.submit(be, ("enc",), bad, 2),
                return_exceptions=True,
            )
        finally:
            be._inflight_ops = 0
        assert not isinstance(res[0], BaseException), res[0]
        want = await be._encode_batch(good)
        assert np.array_equal(np.asarray(res[0]), np.asarray(want))
        assert isinstance(res[1], BaseException)
        st = co.stats()
        assert st["solo_retries"] == 2
        assert st["failed_ops"] == 1
        assert st["pending_ops"] == 0

    asyncio.run(run())


@pytest.mark.parametrize("plugin,profile,lost,unit", [
    ("clay", {"k": "8", "m": "4", "d": "11"}, 3, 1024),
    ("lrc", {"k": "12", "m": "4", "l": "4"}, 6, 1024),
], ids=["clay_k8m4d11", "lrc_k12m4l4"])
def test_subchunk_repair_moves_less_ici(plugin, profile, lost, unit):
    """Single-chunk degraded reads on clay/lrc run the sharded
    sub-chunk repair: bit-identical bytes, and the modeled interconnect
    counters prove >= 2x fewer bytes moved than whole-chunk repair."""
    async def run():
        co = MeshCoalescer()
        be = await _backend(profile, plugin=plugin, unit=unit,
                            mesh_coalescer=co)
        rng = np.random.default_rng(17)
        data = np.asarray(
            rng.integers(0, 256, (4, be.k, be.sinfo.chunk_size)),
            np.uint8)
        full = np.asarray(await be._encode_batch(data))
        avail = {i: full[:, i] for i in range(be.n) if i != lost}
        out = await be._coalesced_decode(avail, [lost])
        assert np.array_equal(np.asarray(out[lost]), full[:, lost])
        assert be.mesh_stats["repairs"] == 1
        moved = be.perf.value("ec_mesh_ici_bytes")
        whole = be.perf.value("ec_mesh_ici_whole_bytes")
        assert moved > 0 and moved * 2 <= whole, (moved, whole)
        assert be.perf.dump()["ec_mesh_launch_us"]["count"] == 1
        # multi-chunk loss falls back to the classic decode path
        lost2 = [lost, (lost + 1) % be.n]
        avail2 = {i: full[:, i] for i in range(be.n)
                  if i not in lost2}
        out2 = await be._coalesced_decode(avail2, lost2)
        for w in lost2:
            assert np.array_equal(np.asarray(out2[w]), full[:, w])
        assert be.mesh_stats["repairs"] == 1   # unchanged

    asyncio.run(run())


def test_full_write_read_through_host_singleton():
    """End-to-end: two backends on the process-level host_coalescer()
    singleton write/read concurrently; ops coalesce across backends
    and every object reads back bit-identically."""
    async def run():
        reset_host_coalescer()
        co = host_coalescer()
        try:
            be1 = await _backend(mesh_coalescer=co)
            be2 = await _backend(mesh_coalescer=co)
            datas1 = {f"o{i}": bytes([i + 1]) * 4096 for i in range(16)}
            datas2 = {f"p{i}": bytes([i + 17]) * 4096 for i in range(16)}
            await asyncio.gather(
                *(be1.write(o, d) for o, d in datas1.items()),
                *(be2.write(o, d) for o, d in datas2.items()))
            for o, d in datas1.items():
                assert await be1.read(o) == d
            for o, d in datas2.items():
                assert await be2.read(o) == d
            st = co.stats()
            assert st["ops"] >= 32
            assert st["launches"] < st["ops"] / 4, st
            assert st["cross_backend_launches"] >= 1, st
            n = _ndev()
            assert len(st["per_device_stripes"]) == n
        finally:
            reset_host_coalescer()

    asyncio.run(run())


def test_sharded_applier_matches_codec():
    """ShardedApplier output == codec encode, any batch size (padding
    path included)."""
    k = 4
    codec = ErasureCodePluginRegistry().factory(
        "jax_rs", {"k": str(k), "m": "2", "technique": "cauchy_good"}
    )
    mesh = make_ec_mesh(cs=2)
    gen = np.asarray(codec.generator, np.uint8)
    ap = ShardedApplier(mesh, gen[k:])
    for batch in (1, 3, 8, 13):
        data = np.random.default_rng(batch).integers(
            0, 256, (batch, k, 64), np.uint8)
        want = np.asarray(codec.encode_chunks_batch(data))
        parity = ap(data)
        assert np.array_equal(parity, want[:, k:]), f"batch={batch}"


def test_cluster_pg_write_and_recovery_ride_the_mesh():
    """OSD-cluster proof on the 8-device virtual mesh: with
    osd_ec_mesh_coalesce an EC-pool PG write and a shard recovery run
    through the host mesh coalescer (mesh_stats move) and stay correct
    end to end."""
    from ceph_tpu.common.config import ConfigProxy
    from tests.test_osd_daemon import start_cluster, wait_active

    def conf():
        return ConfigProxy(overrides={
            "mon_lease": 0.4, "mon_lease_interval": 0.1,
            "mon_election_timeout": 0.3, "mon_tick_interval": 0.1,
            "mon_accept_timeout": 0.5,
            "osd_heartbeat_interval": 0.1,
            "osd_heartbeat_grace": 0.6,
            "mon_osd_down_out_interval": 30.0,
            "osd_ec_mesh_coalesce": True,
        })

    async def run():
        mon, osds, client = await start_cluster(6, conf_factory=conf,
                                                pools=[
            {"prefix": "osd erasure-code-profile set", "name": "p42",
             "profile": {"plugin": "jax_rs", "k": "4", "m": "2",
                         "crush-failure-domain": "osd"}},
            {"prefix": "osd pool create", "pool": "ecm", "pg_num": 4,
             "pool_type": "erasure", "erasure_code_profile": "p42"},
        ])
        pool_id = next(p.pool_id for p in mon.osd_monitor.osdmap
                       .pools.values() if p.name == "ecm")
        await wait_active(osds, pool_id)

        payload = bytes(range(256)) * 64      # 16 KiB
        r = await client.op("ecm", "big", [
            {"op": "write", "off": 0, "data": payload},
        ])
        assert r["rc"] == 0, r
        r = await client.op("ecm", "big", [{"op": "read", "off": 0}])
        assert r["results"][0]["data"] == payload

        backends = [pg.backend for osd in osds
                    for pg in osd.pgs.values()
                    if pg.pgid.pool == pool_id and pg.backend]
        assert backends, "no EC backends instantiated"
        assert all(b.mesh_co is host_coalescer() and b._mesh_dec_ok
                   for b in backends), \
            "mesh coalescer not registered on the PG backends"
        assert sum(b.mesh_stats["encodes"] for b in backends) >= 1, \
            "write did not ride the sharded plane"

        # recovery: rebuild a lost shard through the mesh decode on
        # the primary that served the write
        be = next(b for b in backends if b.mesh_stats["encodes"] >= 1)
        await be.shards[0].remove_shard("big")
        d0 = be.mesh_stats["decodes"]
        await be.recover_shard("big", [0])
        assert be.mesh_stats["decodes"] > d0, \
            "recovery did not ride the sharded plane"
        r = await client.op("ecm", "big", [{"op": "read", "off": 0}])
        assert r["results"][0]["data"] == payload

        await client.shutdown()
        for o in osds:
            await o.shutdown()
        await mon.shutdown()

    reset_host_coalescer()
    try:
        asyncio.run(run())
    finally:
        reset_host_coalescer()
