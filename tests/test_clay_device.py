"""CLAY on the device.

The probed encode / decode / repair programs of the clay plugin against
its host schedule and against the plain reference
(``ceph_tpu/ec/reference_clay.py``); the grouped kernel's run-time
operands in interpret mode; and degraded reads of a CLAY pool, which
rebuild a lost data chunk from the d helpers' repair sub-chunks (one
ranged sub-op per helper the read does not hold) or, where those are not
all up, decode it from k chunks.
"""

import asyncio
import collections
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ceph_tpu.ec import reference_clay
from ceph_tpu.ec.engine import BitplaneEngine, apply_operator
from ceph_tpu.ec.pallas_kernels import PallasGroupedApply
from ceph_tpu.ec.registry import ErasureCodePluginRegistry
from ceph_tpu.ec.repair_operator import clay_repair_operator
from ceph_tpu.osd.ec_backend import ECBackend, LocalShard, ShardReadError
from ceph_tpu.store import CollectionId, GHObject, MemStore, Transaction

# (k, m, d): q = 2, 4, 2; the last has nu = 1 shortened node
PROFILES = [(4, 2, 5), (8, 4, 11), (5, 2, 6)]
IDS = ["k4m2d5", "k8m4d11", "k5m2d6_nu1"]


def _codec(k, m, d):
    return ErasureCodePluginRegistry().factory(
        "clay", {"k": str(k), "m": str(m), "d": str(d)})


def _stripes(ec, n=3, sc=8, seed=0):
    """(n, k, C) data and its (n, k+m, C) chunks from the host schedule."""
    C = ec.sub_chunk_no * sc
    data = np.random.default_rng(seed).integers(
        0, 256, (n, ec.k, C), np.uint8)
    return data, ec._encode_batch(data)


def _patterns():
    """Every single erasure and two double erasures of each profile."""
    out = []
    for (k, m, d), name in zip(PROFILES, IDS):
        n = k + m
        for lost in [(i,) for i in range(n)] + [(0, n - 1), (1, k)]:
            out.append(pytest.param((k, m, d), lost,
                                    id=f"{name}-{'_'.join(map(str, lost))}"))
    return out


@pytest.mark.parametrize("profile", PROFILES, ids=IDS)
def test_reference_encode_matches_host_plugin(profile):
    ec = _codec(*profile)
    ref = reference_clay.code(*profile)
    assert ref.S == ec.sub_chunk_no and ref.nu == ec.nu
    data, chunks = _stripes(ec, seed=1)
    for s in range(len(data)):
        assert np.array_equal(ref.encode_chunks(data[s]), chunks[s])


@pytest.mark.parametrize("sc", [8, 6], ids=["sc8", "sc6"])
@pytest.mark.parametrize("profile", PROFILES, ids=IDS)
def test_device_encode_matches_host_and_reference(profile, sc):
    """sc = 6: sub-chunks that are not a whole number of 4-byte lanes
    pad to one on the device and trim back."""
    ec = _codec(*profile)
    data, chunks = _stripes(ec, sc=sc, seed=2)
    dev = ec.encode_chunks_device(jnp.asarray(data))
    assert isinstance(dev, jax.Array)
    assert np.array_equal(np.asarray(dev), chunks)
    # the batch entry point ECBackend calls: the same program, host out
    batch = ec.encode_chunks_batch(data)
    assert isinstance(batch, np.ndarray)
    assert np.array_equal(batch, chunks)
    ref = reference_clay.code(*profile)
    assert np.array_equal(np.asarray(dev[0]), ref.encode_chunks(data[0]))
    # one stripe, (k, C) in and (k+m, C) out, as jax_rs takes it
    assert np.array_equal(np.asarray(ec.encode_chunks_device(data[1])),
                          chunks[1])


@pytest.mark.parametrize("profile,lost", _patterns())
def test_device_decode_matches_host_and_reference(profile, lost):
    ec = _codec(*profile)
    ref = reference_clay.code(*profile)
    data, chunks = _stripes(ec, seed=3)
    n = ec.get_chunk_count()
    avail = {i: chunks[:, i] for i in range(n) if i not in lost}
    host = ec._decode_host(avail, list(lost))
    dev = ec.decode_chunks_device(
        {i: jnp.asarray(c) for i, c in avail.items()}, list(lost))
    assert dev.shape == (len(data), len(lost), chunks.shape[2])
    batch = ec.decode_chunks_batch(avail, list(lost))
    for w in lost:
        assert np.array_equal(batch[w], host[w])
    S = ec.sub_chunk_no
    given = {(c, z): chunks[0, c].reshape(S, -1)[z]
             for c in avail for z in range(S)}
    want = ref.solve(given, list(lost))
    for j, w in enumerate(lost):
        assert np.array_equal(np.asarray(dev[:, j]), host[w])
        assert np.array_equal(host[w], chunks[:, w])
        assert np.array_equal(want[w], chunks[0, w])


@pytest.mark.parametrize("profile", PROFILES, ids=IDS)
def test_device_repair_matches_reference(profile):
    """Every lost chunk rebuilt from its d helpers' repair planes: the
    device program, the plugin's host repair and the reference's
    elimination over exactly those sub-chunks agree."""
    ec = _codec(*profile)
    ref = reference_clay.code(*profile)
    _, chunks = _stripes(ec, n=2, sc=8, seed=4)
    S, n = ec.sub_chunk_no, ec.get_chunk_count()
    for lost in range(n):
        R, helpers, planes = clay_repair_operator(ec, lost)
        assert planes == ref.repair_planes(lost)
        per = np.stack([chunks[:, h].reshape(2, S, -1)[:, planes]
                        .reshape(2, -1) for h in helpers])
        got = np.asarray(ec.repair_chunks_device(lost, tuple(helpers), per))
        assert np.array_equal(got, chunks[:, lost]), lost
        given = {(h, z): chunks[0, h].reshape(S, -1)[z]
                 for h in helpers for z in planes}
        assert np.array_equal(ref.solve(given, [lost])[lost],
                              chunks[0, lost])


def test_reference_refuses_what_the_given_subchunks_do_not_determine():
    ref = reference_clay.code(4, 2, 5)
    chunks = ref.encode_chunks(np.arange(4 * ref.S * 2, dtype=np.uint8)
                               .reshape(4, -1))
    planes = ref.repair_planes(0)
    # four helpers' repair planes are short of d = 5
    given = {(h, z): chunks[h].reshape(ref.S, -1)[z]
             for h in (1, 2, 3, 4) for z in planes}
    with pytest.raises(ValueError):
        ref.solve(given, [0])


@pytest.mark.parametrize("which", ["repair", "decode", "encode"])
def test_grouped_kernel_operands_match_the_einsum(which):
    """The kernel the chip runs for each k=8 m=4 d=11 operator (the
    paired grouped kernel, columns and row order as run-time operands),
    in interpret mode, against the bitplane einsum."""
    ec = _codec(8, 4, 11)
    coeff = {"repair": lambda: clay_repair_operator(ec, 0)[0],
             "decode": lambda: ec._probe(tuple(range(1, 9)), (0,))[0],
             "encode": lambda: ec._probe(None, (8, 9, 10, 11))[0],
             }[which]()
    xla = BitplaneEngine(use_pallas=False).operator(coeff)
    assert xla[0] == ("xla",)
    # the engine's kernel choice on the chip, in interpret mode
    assert BitplaneEngine(use_pallas=True).operator(coeff)[0][0] == "grouped"
    grouped = PallasGroupedApply(coeff, interpret=True).operator()
    words = jnp.asarray(np.random.default_rng(5).integers(
        -2 ** 31, 2 ** 31 - 1, (coeff.shape[1], 200),
        np.int64).astype(np.int32))

    run = jax.jit(lambda ops, w, *, spec: apply_operator((spec, ops), w),
                  static_argnames="spec")
    assert np.array_equal(
        np.asarray(run(grouped[1], words, spec=grouped[0])),
        np.asarray(run(xla[1], words, spec=xla[0])))


# -- the EC backend ---------------------------------------------------------


class RangedShard(LocalShard):
    """Counts the ranged sub-ops each shard serves, per object; ``down``
    fails them."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.ranged = collections.Counter()
        self.down = False

    async def read_shard_ranges(self, oid, ranges, version=None):
        if self.down:
            raise ShardReadError("injected ranged read failure")
        self.ranged[oid] += 1
        return await super().read_shard_ranges(oid, ranges, version)


def _backend(profile, unit, resident=None):
    ec = _codec(*profile)
    stores, shards = {}, {}
    for i in range(ec.get_chunk_count()):
        store = MemStore()
        cid = CollectionId(1, 0, shard=i)
        asyncio.run(store.queue_transactions(
            Transaction().create_collection(cid)))
        stores[i] = (store, cid)
        shards[i] = RangedShard(store, cid, pool=1, shard=i)
    be = ECBackend(ec, shards, stripe_unit=unit, resident=resident)
    return be, stores


async def _lose(be, stores, names, shard):
    store, cid = stores[shard]
    for name in names:
        await store.queue_transactions(
            Transaction().remove(cid, GHObject(1, name, shard=shard)))
        if be.resident is not None:
            be.resident.drop(be.resident_ns, name, shard)


def _objects(n, unit, k):
    rng = np.random.default_rng(6)
    return {f"o{i}": rng.integers(0, 256, 3 * k * unit + 100 * i,
                                  np.uint8).tobytes() for i in range(n)}


@pytest.mark.parametrize("resident", [None, True], ids=["store", "resident"])
def test_degraded_read_rebuilds_from_helper_subchunks(resident):
    profile, unit = (8, 4, 11), 1024
    be, stores = _backend(profile, unit, resident)
    assert (be.resident is not None) == bool(resident)
    objs = _objects(5, unit, 8)
    lost = 2

    async def run():
        for name, data in objs.items():
            await be.write(name, data)
        await _lose(be, stores, objs, lost)
        for sh in be.shards.values():
            sh.ranged.clear()
        return await asyncio.gather(*(be.read(n) for n in objs))

    got = asyncio.run(run())
    assert got == list(objs.values())
    perf = be.perf.dump()
    assert perf["ec_clay_subchunk_repairs"] == len(objs)
    assert perf["ec_clay_layered_decodes"] == 0
    # the read holds the 7 surviving data chunks whole: ONE ranged
    # sub-op to each of the 4 parity helpers per object, none elsewhere;
    # with residency the parity streams the primary keeps serve them
    for s, sh in be.shards.items():
        want = {n: 1 for n in objs} if s >= 8 and not resident else {}
        assert sh.ranged == want, s
    ec = be.ec
    sc = unit // ec.sub_chunk_no
    planes = ec.sub_chunk_no // ec.q
    rows = sum(-(-len(d) // (8 * unit)) for d in objs.values())
    assert perf["ec_subchunk_read_bytes"] == 4 * rows * planes * sc
    # the five concurrent rebuilds shared one coalesced launch
    assert perf["ec_decode_launch_us"]["count"] == 1


def test_two_lost_chunks_decode_layered_from_k():
    profile, unit = (8, 4, 11), 1024
    be, stores = _backend(profile, unit)
    objs = _objects(3, unit, 8)

    async def run():
        for name, data in objs.items():
            await be.write(name, data)
        await _lose(be, stores, objs, 1)
        await _lose(be, stores, objs, 5)
        return [await be.read(n) for n in objs]

    assert asyncio.run(run()) == list(objs.values())
    perf = be.perf.dump()
    assert perf["ec_clay_layered_decodes"] == len(objs)
    assert perf["ec_clay_subchunk_repairs"] == 0
    assert perf["ec_subchunk_read_bytes"] == 0


def test_a_failed_helper_falls_back_to_the_layered_decode():
    """A parity helper whose ranged read fails leaves fewer than d
    helpers: the read decodes the lost chunk from k whole chunks."""
    profile, unit = (8, 4, 11), 1024
    be, stores = _backend(profile, unit)
    objs = _objects(2, unit, 8)

    async def run():
        for name, data in objs.items():
            await be.write(name, data)
        await _lose(be, stores, objs, 0)
        be.shards[10].down = True
        return [await be.read(n) for n in objs]

    assert asyncio.run(run()) == list(objs.values())
    perf = be.perf.dump()
    assert perf["ec_clay_layered_decodes"] == len(objs)
    assert perf["ec_clay_subchunk_repairs"] == 0


# -- a CLAY pool on a DevCluster -------------------------------------------


def test_clay_pool_degraded_reads_on_a_cluster(tmp_path, monkeypatch):
    """One OSD of a k=4 m=2 d=5 pool down: every read returns its bytes;
    each rebuild is counted, sends one ranged sub-op to each helper it
    does not hold, and its ec:repair span reads helper_bytes /
    rebuilt_bytes = d/q."""
    from ceph_tpu.msg import reset_local_namespace
    from ceph_tpu.osd import daemon as osd_daemon
    from ceph_tpu.osd.pg import object_to_ps
    from ceph_tpu.vstart import DevCluster

    sent = collections.Counter()
    net, local = osd_daemon.NetworkShard, LocalShard
    real_net, real_local = net.read_shard_ranges, local.read_shard_ranges

    async def net_ranges(self, oid, ranges, version=None):
        sent[(oid, self.osd)] += 1
        return await real_net(self, oid, ranges, version)

    async def local_ranges(self, oid, ranges, version=None):
        sent[(oid, "local", self.shard)] += 1
        return await real_local(self, oid, ranges, version)

    monkeypatch.setattr(net, "read_shard_ranges", net_ranges)
    monkeypatch.setattr(local, "read_shard_ranges", local_ranges)
    rng = np.random.default_rng(7)
    objs = {f"c{i}": rng.integers(0, 256, 9000 + 700 * i,
                                  np.uint8).tobytes() for i in range(6)}

    async def run():
        reset_local_namespace()
        cluster = DevCluster(n_mons=1, n_osds=6,
                             overrides={"osd_ec_resident": True})
        await cluster.start()
        try:
            rados = await cluster.client()
            r = await rados.mon_command(
                "osd erasure-code-profile set", name="cl",
                profile={"plugin": "clay", "k": "4", "m": "2", "d": "5",
                         "stripe_unit": "512",
                         "crush-failure-domain": "osd"})
            assert r["rc"] == 0, r
            await rados.pool_create("cl", pg_num=4, pool_type="erasure",
                                    erasure_code_profile="cl")
            await cluster.wait_health_ok(timeout=120)
            io = await rados.open_ioctx("cl")
            for name, data in objs.items():
                await io.write_full(name, data)
            m = rados.monc.osdmap
            pool = next(p.pool_id for p in m.pools.values()
                        if p.name == "cl")
            acting = {n: m.pg_to_up_acting(
                pool, object_to_ps(n, m.pools[pool].pg_num))[2]
                for n in objs}
            victim = acting["c0"][1]
            await cluster.kill_osd(victim)
            assert (await rados.mon_command("osd down",
                                            ids=[victim]))["rc"] == 0
            while rados.monc.osdmap.is_up(victim):
                await asyncio.sleep(0.05)
            lost = {n: a.index(victim) for n, a in acting.items()}
            opts = jax.profiler.ProfileOptions()
            opts.host_tracer_level = 1
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
            try:
                got = {n: await io.read(n) for n in objs}
            finally:
                jax.profiler.stop_trace()
            repairs = sum(o.perf.dump().get("ec_clay_subchunk_repairs", 0)
                          for o in cluster.osds.values())
            await rados.shutdown()
            return got, lost, repairs
        finally:
            await cluster.stop()
            reset_local_namespace()

    got, lost, repairs = asyncio.run(run())
    assert got == objs
    rebuilt = [n for n, pos in lost.items() if pos < 4]
    assert rebuilt and repairs == len(rebuilt)
    # one ranged sub-op per helper per rebuilt object: the two parity
    # helpers (the read holds the three surviving data chunks whole)
    per_obj = collections.Counter(key[0] for key in sent)
    assert set(per_obj) == set(rebuilt)
    assert all(v == 2 for v in per_obj.values())
    assert all(v == 1 for v in sent.values())
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[0]
    tags = [dict(ev.stats)
            for plane in jax.profiler.ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name == "ec:repair"]
    assert len(tags) == len(rebuilt)
    assert all(t["path"] == "subchunk" and t["helpers"] == 5 for t in tags)
    assert sum(t["helper_bytes"] for t in tags) == pytest.approx(
        5 / 2 * sum(t["rebuilt_bytes"] for t in tags))
