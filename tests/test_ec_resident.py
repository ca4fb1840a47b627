"""Device-resident EC data path: DeviceShardCache + resident backend.

The residency tier must be invisible to clients: corpus-profile
bit-identity through the device-resident write/read path, a full
write -> evict -> read-back cycle landing on the store copy, coalesced
launches with mixed resident/non-resident batchmates, and the cache's
LRU/watermark mechanics (every entry is a copy of store bytes, so
eviction only drops).
"""

import asyncio

import numpy as np
import pytest

from ceph_tpu.common import failpoint as fp
from ceph_tpu.ec.registry import ErasureCodePluginRegistry
from ceph_tpu.osd.ec_backend import ECBackend, LocalShard
from ceph_tpu.store.device_cache import DeviceShardCache
from ceph_tpu.store.memstore import MemStore
from ceph_tpu.store.object_store import Transaction
from ceph_tpu.store.types import CollectionId

# jax_rs slices of the corpus matrix (PROFILES in ceph_tpu/ec/corpus.py)
# spanning dense, bit-schedule, and wide-symbol techniques — all ride
# the same encode_chunks_device/decode_chunks_device entry points
RESIDENT_PROFILES = [
    {"k": "4", "m": "2", "technique": "reed_sol_van"},
    {"k": "10", "m": "4", "technique": "cauchy_good"},
    {"k": "5", "m": "2", "technique": "liberation", "w": "7"},
    {"k": "5", "m": "3", "technique": "reed_sol_van", "w": "16"},
]


async def _backend(profile=None, unit=128, **kw):
    profile = profile or {"k": "4", "m": "2",
                          "technique": "reed_sol_van"}
    codec = ErasureCodePluginRegistry().factory("jax_rs", profile)
    align = getattr(codec, "get_alignment", lambda: 1)()
    unit = -(-unit // align) * align      # bit-schedule codecs need k*w
    store = MemStore()
    shards = {}
    for i in range(codec.get_chunk_count()):
        cid = CollectionId(1, 0, shard=i)
        await store.queue_transactions(
            Transaction().create_collection(cid)
        )
        shards[i] = LocalShard(store, cid, pool=1, shard=i)
    return ECBackend(codec, shards, stripe_unit=unit, **kw)


@pytest.fixture(autouse=True)
def _clean_failpoints():
    fp.fp_clear()
    yield
    fp.fp_clear()


def _run(coro):
    return asyncio.run(coro)


# -- DeviceShardCache mechanics -------------------------------------------


def _arr(n, fill=0):
    return np.full(n, fill, np.uint8)


def test_cache_lru_watermark_eviction():
    """Budget crossings evict LRU-first down to the low watermark;
    get() refreshes recency."""
    cache = DeviceShardCache(max_bytes=1024, low_watermark=0.5)
    for i in range(4):
        cache.put("pg", f"o{i}", 0, _arr(256, i), version=1)
    assert cache.bytes == 1024 and not cache.over_high
    cache.get("pg", "o0", 0)              # o0 becomes most-recent
    cache.put("pg", "o4", 0, _arr(256, 4), version=1)
    assert cache.over_high
    cache.evict()
    assert cache.bytes <= 512
    assert cache.get("pg", "o0", 0) is not None   # refreshed, survived
    assert cache.get("pg", "o1", 0) is None       # LRU, evicted
    assert cache.evictions == 3
    st = cache.stats()
    assert st["entries"] == 2 and st["evictions"] == 3
    assert st["hits"] == 2 and st["misses"] == 1


def test_cache_drop_scopes_and_bump_version():
    cache = DeviceShardCache(max_bytes=4096)
    for ns in ("1.0", "1.1"):
        for shard in range(3):
            cache.put(ns, "obj", shard, _arr(64), version=1)
    cache.drop("1.0", "obj", 0)
    assert cache.stats(ns="1.0")["entries"] == 2
    cache.bump_version("1.1", "obj", 5)
    assert cache.get("1.1", "obj", 2, count=False).version == 5
    assert cache.get("1.0", "obj", 1, count=False).version == 1
    cache.drop_object("1.1", "obj")
    assert cache.stats(ns="1.1")["entries"] == 0
    cache.drop_ns("1.0")
    assert cache.bytes == 0


# -- resident backend: corpus bit-identity --------------------------------


@pytest.mark.parametrize(
    "profile", RESIDENT_PROFILES,
    ids=lambda p: f"k{p['k']}m{p['m']}_{p['technique']}")
def test_resident_corpus_payload_bit_identical(profile):
    """The corpus payload (deliberately unaligned) written through the
    device-resident path reads back bit-identical — both from the cache
    and, after a full eviction, from the persisted store copy."""
    from ceph_tpu.ec.corpus import _payload

    async def run():
        be = await _backend(profile, resident=True)
        assert be.resident is not None
        payload = _payload()
        await be.write("corpus", payload)
        assert await be.read("corpus") == payload      # cache-served
        be.resident.evict(target=0)
        assert await be.read("corpus") == payload      # store-served

    _run(run())


def test_resident_write_evict_readback_cycle():
    """write -> sub-stripe overwrite -> evict -> read-back; the
    overwrite uploads its one stripe (old bytes spliced with the
    client's on the host), once."""
    async def run():
        be = await _backend(resident=True)
        data = bytearray(bytes(range(256)) * 16)       # 4 KiB, 8 stripes
        await be.write("cyc", bytes(data))
        h2d0 = be.perf.value("ec_resident_h2d_bytes")
        patch = b"\xee" * 96
        await be.write("cyc", patch, offset=700)
        data[700:796] = patch
        # the RMW stripe batch [512, 1024) is the overwrite's one upload
        assert be.perf.value("ec_resident_h2d_bytes") - h2d0 == \
            be.sinfo.stripe_width == 512
        assert await be.read("cyc") == bytes(data)
        be.resident.evict(target=0)
        assert be.resident.stats()["entries"] == 0
        assert await be.read("cyc") == bytes(data)     # store copy
        st = be.resident_stats()
        assert st["enabled"] and st["evictions"] >= be.k

    _run(run())


def test_resident_remove_and_version_coherence():
    """remove() drops residency; a stale clean entry (version behind
    the object) is bypassed in favour of the store."""
    async def run():
        be = await _backend(resident=True)
        await be.write("gone", b"\x42" * 1024)
        await be.remove("gone")
        assert be.resident.stats()["entries"] == 0
        with pytest.raises(Exception):
            await be.read("gone")

        await be.write("attr", b"\x17" * 1024)
        await be.set_attr("attr", "user.x", b"y")      # bumps version
        assert await be.read("attr") == b"\x17" * 1024

    _run(run())


def test_resident_read_serves_only_version_matched():
    """A resident entry serves a shard read only at its own version: a
    raw read (version=None) and a read at another version fall through
    to the store (None), and a matched read returns the cached range."""
    async def run():
        be = await _backend(resident=True)
        data = bytes(range(256)) * 4
        meta = await be.write("v", data)
        clen = be.sinfo.logical_to_next_chunk_offset(len(data))
        stored = await be.shards[0].read_shard("v")
        hit = be._resident_read(0, "v", 0, clen, clen, meta.version)
        assert hit is not None
        assert np.asarray(hit).tobytes() == stored[:clen]
        assert be._resident_read(0, "v", 0, clen, clen, None) is None
        assert be._resident_read(
            0, "v", 0, clen, clen, meta.version + 1) is None
        # the raw read still returns the store bytes
        raw = await be._read_shard_range(0, "v", 0, clen, clen)
        assert raw.tobytes() == stored[:clen]

    _run(run())


# -- mixed resident / non-resident coalesced batches ----------------------


def test_coalesced_mixed_device_host_batchmates():
    """One coalesced launch fed a mix of device-resident and host
    (numpy) stripe batches returns each submitter bit-identical
    results in its own flavour (device in, device out; host in, host
    out)."""
    import jax.numpy as jnp

    async def run():
        be = await _backend(resident=True)
        rng = np.random.default_rng(23)
        k, chunk = be.k, be.sinfo.chunk_size
        host_batches = [
            np.asarray(rng.integers(0, 256, (b, k, chunk)), np.uint8)
            for b in (2, 1, 4)
        ]
        dev_batches = [jnp.asarray(h) for h in host_batches[::-1]]
        batches = [x for pair in zip(host_batches, dev_batches)
                   for x in pair]
        be._inflight_ops = len(batches) + 1
        try:
            outs = await asyncio.gather(*(
                be._coalesced_encode(s) for s in batches
            ))
        finally:
            be._inflight_ops = 0
        st = be.coalescer.stats()
        assert st["ops"] == len(batches)
        assert st["launches"] < len(batches), st
        for src, got in zip(batches, outs):
            want = np.asarray(await be._encode_batch(np.asarray(src)))
            assert np.array_equal(np.asarray(got), want)
            if not isinstance(src, np.ndarray):
                assert not isinstance(got, np.ndarray), \
                    "device submitter must get a device result back"

    _run(run())


def test_resident_and_classic_backends_concurrent():
    """A resident and a non-resident backend interleaving writes over
    distinct stores stay bit-identical — the residency tier leaks no
    state across backends."""
    async def run():
        res = await _backend(resident=True)
        cla = await _backend(resident=False)
        assert cla.resident is None
        datas = {f"o{i}": bytes([i + 1]) * (512 + 128 * i)
                 for i in range(8)}
        await asyncio.gather(*(
            be.write(o, d)
            for o, d in datas.items() for be in (res, cla)
        ))
        for o, d in datas.items():
            assert await res.read(o) == d
            assert await cla.read(o) == d

    _run(run())


# -- the resident write path against the host path ------------------------

WRITE_PROFILES = RESIDENT_PROFILES + [
    {"k": "8", "m": "4", "technique": "reed_sol_van"}]

# each case: its writes as (offset, length) from the stripe width; the
# first may create the object, the last is the one under test
WRITE_CASES = {
    "fresh_full_batch": lambda sw: [(0, 5 * sw)],
    "fresh_sub_stripe": lambda sw: [(0, sw // 3)],
    "writefull_same": lambda sw: [(0, sw // 3), (0, sw // 3)],
    "writefull_longer": lambda sw: [(0, sw // 3), (0, 2 * sw + 7)],
    "partial_overwrite": lambda sw: [(0, 3 * sw), (sw + 5, sw // 2)],
    "append": lambda sw: [(0, sw + 100), (sw + 100, sw)],
}


async def _stored(be, oid):
    """Per shard: (store bytes, version attr, hinfo attr)."""
    out = []
    for i in range(be.n):
        attrs = await be.shards[i].get_attrs(oid)
        out.append((await be.shards[i].read_shard(oid),
                    attrs.get("version"), attrs.get("hinfo")))
    return out


async def _write_beside_batchmate(be, oid, data, off, mate, mate_data):
    """Write ``oid`` and ``mate`` concurrently on one resident backend so
    their encodes share ONE coalesced launch, ``mate`` first: ``oid``'s
    shard streams are split out at a nonzero stripe offset.  The extra
    in-flight count holds the flush until both have parked (the
    backend's window is long enough not to fire first)."""
    co = be.coalescer
    st0 = co.stats()
    be._inflight_ops += 1

    async def after_mate_parks():
        while co._npending < 1:
            await asyncio.sleep(0)
        await be.write(oid, data, offset=off)

    async def release_when_both_park():
        try:
            while co._npending < 2:
                await asyncio.sleep(0)
        finally:
            be._inflight_ops -= 1
            co.notify()

    await asyncio.gather(be.write(mate, mate_data), after_mate_parks(),
                         release_when_both_park())
    st = co.stats()
    assert (st["launches"] - st0["launches"],
            st["ops"] - st0["ops"]) == (1, 2), (st0, st)


@pytest.mark.parametrize("launch", ["solo", "shared"])
@pytest.mark.parametrize("case", list(WRITE_CASES))
@pytest.mark.parametrize(
    "profile", WRITE_PROFILES,
    ids=lambda p: f"k{p['k']}m{p['m']}_{p['technique']}")
def test_fused_write_matches_host_path(profile, case, launch):
    """The resident write path (host-built stripes, one launch, one
    jitted split) against the non-resident host path on the same
    writes: the stores hold the same shard bytes, version and hinfo
    attrs, every shard's resident entry holds its store bytes, and both
    read back the same object.  ``shared``: each resident write shares
    its launch with a batchmate write of another size, launched first,
    so the object's streams are split out at a nonzero offset."""
    async def run():
        res = await _backend(profile, resident=True,
                             coalesce_window_us=30e6)
        host = await _backend(profile, resident=False)
        sw = res.sinfo.stripe_width
        rng = np.random.default_rng(7)
        want = bytearray()
        writes = WRITE_CASES[case](sw)
        for j, (off, n) in enumerate(writes):
            data = rng.integers(0, 256, n, np.uint8).tobytes()
            if launch == "shared":
                mate = bytes([j + 1]) * (2 * sw + 3)
                await _write_beside_batchmate(res, "obj", data, off,
                                              f"mate{j}", mate)
                assert await res.read(f"mate{j}") == mate
            else:
                await res.write("obj", data, offset=off)
            await host.write("obj", data, offset=off)
            want[len(want):off + n] = b"\0" * max(0, off + n - len(want))
            want[off:off + n] = data
        stored = await _stored(host, "obj")
        assert await _stored(res, "obj") == stored
        for i in range(res.n):
            ent = res.resident.get(res.resident_ns, "obj", i, count=False)
            assert ent is not None
            assert np.asarray(ent.arr).tobytes() == stored[i][0]
        assert await res.read("obj") == bytes(want)
        assert await host.read("obj") == bytes(want)
        fused = len(writes) * (2 if launch == "shared" else 1)
        assert res.resident_stats()["ec_write_glue_fused"] == fused
        assert host.perf.value("ec_write_glue_fused") == 0

    _run(run())


@pytest.mark.parametrize("resident", [True, False],
                         ids=["resident", "host"])
def test_rmw_gather_skipped_counts_covering_writes(resident):
    """Writes whose new bytes cover every surviving byte of their
    stripes (same-size and longer write_full, a whole-stripe overwrite)
    count in ec_rmw_gather_skipped and read nothing back; a fresh write
    has nothing to cover; a partial overwrite still reads the old bytes
    back and splices them."""
    async def run():
        be = await _backend(resident=resident)
        sw = be.sinfo.stripe_width
        rng = np.random.default_rng(3)
        fetched = []
        read_logical = be._read_logical

        async def counted_read(*a, **kw):
            fetched.append("read")
            return await read_logical(*a, **kw)

        be._read_logical = counted_read
        skipped = lambda: be.perf.value("ec_rmw_gather_skipped")  # noqa
        want = bytearray(rng.integers(0, 256, 2 * sw + 10, np.uint8).tobytes())
        await be.write("o", bytes(want))
        assert skipped() == 0
        want[:] = rng.integers(0, 256, len(want), np.uint8).tobytes()
        await be.write("o", bytes(want))                   # same size
        assert skipped() == 1
        want[:] = rng.integers(0, 256, 3 * sw + 1, np.uint8).tobytes()
        await be.write("o", bytes(want))                   # longer
        assert skipped() == 2
        stripe = rng.integers(0, 256, sw, np.uint8).tobytes()
        await be.write("o", stripe, offset=sw)             # whole stripe
        want[sw:2 * sw] = stripe
        assert skipped() == 3 and fetched == []
        be.extent_cache.clear()
        patch = b"\xa5" * 40
        await be.write("o", patch, offset=sw + 5)          # partial
        want[sw + 5:sw + 45] = patch
        assert skipped() == 3
        assert fetched == ["read"]
        assert await be.read("o") == bytes(want)
        assert be.perf.dump()["ec_rmw_gather_skipped"] == 3

    _run(run())


def test_write_glue_compiles_bounded(monkeypatch):
    """Writes of 70 distinct stripe counts compile one split program
    each, kept by jit's own cache: writing every size again compiles
    nothing (neither the counter nor jit's cache moves), and each object
    reads back its newest bytes."""
    from ceph_tpu.ec.engine import split_shard_streams
    from ceph_tpu.osd import ec_backend

    monkeypatch.setattr(ec_backend, "_SPLIT_SHAPES", set())
    sizes = range(1, 71)

    async def run():
        be = await _backend(resident=True)
        sw = be.sinfo.stripe_width
        compiles = lambda: be.perf.value("ec_write_glue_compiles")  # noqa
        for b in sizes:
            await be.write(f"o{b}", bytes([b]) * (b * sw))
        assert compiles() == len(sizes)
        cached = split_shard_streams._cache_size()
        for b in sizes:
            await be.write(f"o{b}", bytes([b + 1]) * (b * sw))
        assert compiles() == len(sizes)
        assert split_shard_streams._cache_size() == cached
        assert be.resident_stats()["ec_write_glue_compiles"] == len(sizes)
        for b in (1, 40, 70):
            assert await be.read(f"o{b}") == bytes([b + 1]) * (b * sw)

    _run(run())


# -- apply_bytes / encode variant selection ------------------------------


def test_apply_bytes_rejects_unaligned():
    from ceph_tpu.ec import matrix
    from ceph_tpu.ec.pallas_kernels import PallasShardApply

    G = matrix.generator_matrix("reed_sol_van", 4, 2)
    ap = PallasShardApply(G[4:], interpret=True)
    with pytest.raises(ValueError, match="multiple of 4"):
        ap.apply_bytes(np.zeros((4, 1026), np.uint8))


def test_auto_variant_resolves_to_the_chip_checked_kernel():
    """The config default "auto" resolves at set time to AUTO_VARIANT,
    the formulation chip_smoke.py checks on the chip."""
    from ceph_tpu.ec.pallas_kernels import (
        AUTO_VARIANT, ENCODE_VARIANTS, get_encode_variant,
        set_encode_variant)

    set_encode_variant("auto")
    try:
        assert get_encode_variant() == AUTO_VARIANT
        assert AUTO_VARIANT in ENCODE_VARIANTS
    finally:
        set_encode_variant("")
