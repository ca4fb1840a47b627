"""The system under test: an in-process ceph_tpu cluster serving one EC pool.

Everything the benchmark takes from the program goes through here: the
client API the window drives (``IoCtx.write_full`` / ``IoCtx.read``), the
shards the OSDs' stores hold once the window has closed, the OSDs' perf
counters, a kill of an OSD, and, in set-up, the codec and its
coalesced launch compiled for every batch the window can bring.  A
configuration file under ``configs/``
says what to build.
"""

from __future__ import annotations

import asyncio
import time

import numpy as np

# the OSD perf counters the per-layer metrics read, summed over live
# OSDs: counts, and (sum, count) pairs of time and histogram counters
COUNTS = ("ec_coalesce_ops", "ec_coalesce_launches",
          "ec_resident_h2d_bytes", "ec_resident_d2h_bytes")
PAIRS = ("op_latency", "ec_encode_launch_us", "ec_decode_launch_us")


def _stripes(cfg: dict) -> int:
    p = cfg["pool"]["profile"]
    width = int(p["k"]) * int(p["stripe_unit"])
    return -(-int(cfg["object_bytes"]) // width)


def _pow2(n: int) -> int:
    return 1 << max(0, int(n) - 1).bit_length()


def warm_codec(cfg: dict, mix: dict) -> int:
    """Compile the pool codec's device encode (and, where the mix fails an
    OSD, its one-erasure decode) for each power-of-two batch the
    coalescer can launch under this mix, before the OSDs boot: at most
    ``concurrency`` objects share a launch.  Returns the shapes warmed."""
    import jax
    import jax.numpy as jnp

    from ceph_tpu.ec.registry import ErasureCodePluginRegistry

    profile = {k: str(v) for k, v in cfg["pool"]["profile"].items()}
    ec = ErasureCodePluginRegistry().factory(profile["plugin"], profile)
    k, unit = ec.get_data_chunk_count(), int(profile["stripe_unit"])
    max_stripes = int(cfg["conf"].get("osd_ec_coalesce_max_stripes", 4096))
    one = _pow2(_stripes(cfg))
    top = min(_pow2(max_stripes), _pow2(one * int(mix["concurrency"])))
    decode = mix.get("failure") is not None
    b, n = one, 0
    while b <= top:
        chunks = ec.encode_chunks_device(jnp.zeros((b, k, unit), jnp.uint8))
        if decode:
            avail = {i: chunks[:, i] for i in range(1, ec.get_chunk_count())}
            chunks = ec.decode_chunks_device(avail, [0])
        jax.block_until_ready(chunks)
        b, n = b * 2, n + 1
    return n


class ClusterSUT:
    """1 mon and N OSDs in this process's event loop, one EC pool."""

    def __init__(self, cfg: dict):
        self.cfg = cfg
        self.pool = cfg["pool"]["name"]
        self.cluster = self.rados = self.io = None
        self.pool_id = -1

    async def start(self) -> None:
        from ceph_tpu.vstart import DevCluster

        cfg = self.cfg
        self.cluster = DevCluster(n_mons=int(cfg["mons"]),
                                  n_osds=int(cfg["osds"]),
                                  overrides=dict(cfg["conf"]))
        await self.cluster.start()
        self.rados = await self.cluster.client()
        profile = {k: str(v) for k, v in cfg["pool"]["profile"].items()}
        r = await self.rados.mon_command("osd erasure-code-profile set",
                                         name=self.pool, profile=profile)
        if r["rc"] not in (0, -17):
            raise RuntimeError(f"erasure-code-profile set: {r}")
        await self.rados.pool_create(
            self.pool, pg_num=int(cfg["pool"]["pg_num"]),
            pool_type="erasure", erasure_code_profile=self.pool)
        await self.cluster.wait_health_ok(timeout=300)
        self.io = await self.rados.open_ioctx(self.pool)
        self.pool_id = next(p.pool_id for p in
                            self.rados.monc.osdmap.pools.values()
                            if p.name == self.pool)

    def _backend(self):
        """One EC backend of the pool, on the OSD that is its primary."""
        m = self.rados.monc.osdmap
        for ps in range(m.pools[self.pool_id].pg_num):
            acting = m.pg_to_up_acting(self.pool_id, ps)[2]
            daemon = self.cluster.osds.get(acting[0])
            for pgid, pg in (daemon.pgs.items() if daemon else ()):
                if (pgid.pool, pgid.ps) == (self.pool_id, ps) and \
                        getattr(pg, "backend", None) is not None:
                    return pg.backend
        raise RuntimeError(f"no EC backend of pool {self.pool}")

    async def warm_launches(self, mix: dict) -> int:
        """Run the program's coalesced launch once for each number of
        batchmates the mix's concurrency allows (1 .. concurrency objects
        of the config's size), so that the concatenation, padding and
        slicing around the codec are compiled in set-up: an encode of
        device batches, as the resident write path hands them over, for
        a mix that writes; a one-erasure decode of host batches from k
        survivors, as a degraded read gathers them, for a mix that fails
        an OSD.  Returns the launches made."""
        import jax

        be = self._backend()
        if not hasattr(be, "_coalesce_launch"):
            return 0        # no coalesced launch to warm in this program
        k, unit = be.k, be.sinfo.chunk_size
        one = _stripes(self.cfg)
        top = int(mix["concurrency"])
        made = 0
        if "write_full" in mix["ops"]:
            x = jax.device_put(np.zeros((one, k, unit), np.uint8))
            for j in range(1, top + 1):
                jax.block_until_ready(
                    await be._coalesce_launch(("enc",), [x] * j))
                made += 1
        if mix.get("failure"):
            avail = {s: np.zeros((one, unit), np.uint8)
                     for s in range(1, k + 1)}
            key = ("dec", tuple(sorted(avail)), (0,))
            for j in range(1, top + 1):
                jax.block_until_ready(
                    await be._coalesce_launch(key, [avail] * j))
                made += 1
        return made

    async def write(self, name: str, data: bytes) -> None:
        await self.io.write_full(name, data)

    async def read(self, name: str) -> bytes:
        return await self.io.read(name)

    def acting(self, name: str) -> list[int]:
        """OSD id of each shard position of ``name``'s PG."""
        from ceph_tpu.osd.pg import object_to_ps

        m = self.rados.monc.osdmap
        ps = object_to_ps(name, m.pools[self.pool_id].pg_num)
        return list(m.pg_to_up_acting(self.pool_id, ps)[2])

    def shards(self, name: str) -> dict[int, bytes | None]:
        """Shard position -> the bytes its acting OSD's store holds for
        ``name``, for each of the pool's k+m positions: None where the
        store holds none, or where CRUSH left the position without an
        OSD (a hole, -1)."""
        from ceph_tpu.osd.pg import object_to_ps
        from ceph_tpu.store.types import CollectionId, GHObject

        ps = object_to_ps(name, self.rados.monc.osdmap.pools[
            self.pool_id].pg_num)
        out = {}
        for shard, osd in enumerate(self.acting(name)):
            daemon = self.cluster.osds.get(osd) if osd >= 0 else None
            try:
                out[shard] = daemon.store.read(
                    CollectionId(self.pool_id, ps, shard),
                    GHObject(self.pool_id, name, shard=shard))
            except (AttributeError, KeyError, FileNotFoundError):
                out[shard] = None
        return out

    async def kill(self, victim: int) -> None:
        """Stop one OSD and mark it down (the thrasher's way), as an OSD
        failure before ``mon_osd_down_out_interval`` looks to clients."""
        await self.cluster.kill_osd(victim)
        r = await self.rados.mon_command("osd down", ids=[victim])
        if r["rc"] != 0:
            raise RuntimeError(f"osd down: {r}")
        deadline = time.monotonic() + 60
        while self.rados.monc.osdmap.is_up(victim):
            if time.monotonic() > deadline:
                raise TimeoutError(f"osd.{victim} never marked down")
            await asyncio.sleep(0.05)

    def counters(self) -> dict:
        """COUNTS and PAIRS summed over the live OSDs.  A time counter
        with no sample yet dumps as a bare 0."""
        out: dict = {key: 0 for key in COUNTS}
        out.update({key: (0.0, 0) for key in PAIRS})
        for osd in self.cluster.osds.values():
            dump = osd.perf.dump()
            for key in COUNTS:
                out[key] += dump.get(key, 0)
            for key in PAIRS:
                v = dump.get(key)
                if isinstance(v, dict):
                    s, c = out[key]
                    out[key] = (s + v["sum"], c + v.get("avgcount",
                                                         v.get("count", 0)))
        return out

    async def stop(self) -> None:
        if self.rados is not None:
            await self.rados.shutdown()
        if self.cluster is not None:
            await self.cluster.stop()
