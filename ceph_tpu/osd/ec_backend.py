"""ECBackend: the erasure-coded object data path.

The write/read/recover pipeline of reference osd/ECBackend.cc re-designed
around batched device encode:

- writes: pad to stripe bounds, ONE batched device encode for all stripes
  (vs the per-stripe loop in ECUtil::encode, reference ECUtil.cc:123), then
  per-shard store transactions fan out concurrently (the in-process analog
  of the MOSDECSubOpWrite fan-out, ECBackend.cc:2090-2106; the networked
  OSD daemon drives the same object through messenger shards).
- partial overwrites: stripe-granular RMW under a per-object lock (the
  ExtentCache role, reference ExtentCache.h — pins the written extent while
  missing stripe fragments are read back).
- reads: data shards preferred; on shard failure/corruption falls back to
  minimum_to_decode + batched reconstruct
  (objects_read_and_reconstruct / get_min_avail_to_read_shards,
  reference ECBackend.cc:2364,1613).
- recovery: rebuild lost shards from survivors (RecoveryOp
  READING->WRITING, reference ECBackend.h:249-295).
- scrub: recompute parity on device and compare shard hashes
  (the deep-scrub compare, reference PG.cc:3053 scrub_compare_maps —
  recompute-and-compare is cheap on TPU).

Shard IO goes through the ShardIO protocol so the same backend logic runs
over local stores (tests, single host) or network shards (OSD daemons).
"""

from __future__ import annotations

import asyncio
import contextlib
import json
import time
from dataclasses import dataclass
from typing import Mapping, Protocol, Sequence

import numpy as np

from ceph_tpu.common import failpoint as fp
from ceph_tpu.common import tracing
from ceph_tpu.common.crc32c import crc32c
from ceph_tpu.common.perf import CounterType, PerfCounters
from ceph_tpu.common.tracing import current_span
from ceph_tpu.ec import checksum as ec_checksum
from ceph_tpu.osd.ec_util import HashInfo, StripeInfo
from ceph_tpu.osd.object_state import READ, WRITE, ObjectStates
from ceph_tpu.osd.repair import (RepairPlan, minimum_to_decode_cached,
                                 plan_repair, register_repair_counters)
from ceph_tpu.osd.scrub import register_scrub_counters
from ceph_tpu.store import CollectionId, GHObject, ObjectStore, Transaction
from ceph_tpu.store.device_cache import (DeviceShardCache,
                                         register_resident_counters)

HINFO_ATTR = "hinfo"
VERSION_ATTR = "version"

# ``site`` tags of the ec:prep spans: which host-side step of the EC
# path the span times (the span name stays ec:prep for its readers)
_SITE = {s: {"site": s} for s in (
    "stripes", "split", "tobytes", "pad", "trim", "concat", "merge",
    "resident_read", "planes")}

# stripes per call of a sub-chunk repair program: a launch of more runs
# the one compiled shape block by block (a 4 MiB object of a k=8 pool
# at Ceph's 4 KiB stripe unit is 128 stripes)
_REPAIR_BLOCK = 128

# (Bp, n, C, b) shapes the write path's split program
# (engine.split_shard_streams) was called at in this process: jit's own
# cache keeps each compiled program, so the first call at a shape is
# its one compile (counted in ec_write_glue_compiles)
_SPLIT_SHAPES: set = set()


class ShardIO(Protocol):
    """One shard's IO endpoint (local store or remote OSD). ``log`` on
    mutations is an optional PG log entry applied atomically with the
    shard write on the owning OSD (the per-shard pg_log ride-along of
    MOSDECSubOpWrite, reference ECBackend.cc:2090)."""

    async def write_shard(self, oid: str, offset: int, data: bytes,
                          attrs: Mapping[str, bytes],
                          log=None) -> None: ...
    async def read_shard(self, oid: str, offset: int = 0,
                         length: int | None = None) -> bytes: ...
    async def read_shard_ranges(self, oid: str, ranges,
                                version: int | None = None) -> bytes: ...
    async def get_attr(self, oid: str, name: str) -> bytes: ...
    async def remove_shard(self, oid: str, log=None) -> None: ...
    async def stat_shard(self, oid: str) -> dict: ...


class LocalShard:
    """ShardIO over a local ObjectStore collection."""

    def __init__(self, store: ObjectStore, cid: CollectionId, pool: int,
                 shard: int):
        self.store = store
        self.cid = cid
        self.pool = pool
        self.shard = shard

    def _oid(self, name: str) -> GHObject:
        return GHObject(self.pool, name, shard=self.shard)

    def _log_ops(self, t: Transaction, log) -> Transaction:
        if log is not None:
            from ceph_tpu.osd import pg_log
            pg_log.append_ops(t, self.cid.pool, self.cid.pg, log)
        return t

    async def write_shard(self, oid, offset, data, attrs, log=None):
        if fp.ACTIVE:
            await fp.fire("ec.shard_write")
            await fp.fire(f"ec.shard_write.{self.shard}")
        t = Transaction().write(self.cid, self._oid(oid), offset, data)
        for name, val in attrs.items():
            t.setattr(self.cid, self._oid(oid), name, val)
        await self.store.queue_transactions(self._log_ops(t, log))

    async def read_shard(self, oid, offset=0, length=None):
        return self.store.read(self.cid, self._oid(oid), offset, length)

    async def read_shard_ranges(self, oid, ranges, version=None):
        """The (offset, length) ``ranges`` of the shard, concatenated,
        after checking its stored ``version`` (when given): each range
        one ``read_shard``."""
        if version is not None:
            check_version(await self.get_attr(oid, VERSION_ATTR), version,
                          self.shard)
        return b"".join([await self.read_shard(oid, off, ln)
                         for off, ln in ranges])

    async def get_attr(self, oid, name):
        return self.store.getattr(self.cid, self._oid(oid), name)

    async def remove_shard(self, oid, log=None):
        await self.store.queue_transactions(self._log_ops(
            Transaction().remove(self.cid, self._oid(oid)), log
        ))

    async def stat_shard(self, oid):
        return self.store.stat(self.cid, self._oid(oid))

    async def get_attrs(self, oid):
        return self.store.getattrs(self.cid, self._oid(oid))


class ShardReadError(IOError):
    pass


def check_version(raw: bytes, version: int, shard) -> None:
    """A shard's stored version attr must be ``version``: a shard that
    missed a degraded write holds full-length but stale bytes."""
    if int(json.loads(raw)["version"]) != version:
        raise ShardReadError(f"shard {shard}: stale version "
                             f"(want {version})")


def read_ranges(store: ObjectStore, cid: CollectionId, oid: GHObject,
                ranges, version: int | None = None) -> bytes:
    """A shard-side vectored read (Ceph's ECSubRead with ``subchunks``):
    the (offset, length) ``ranges`` of one shard object concatenated, from
    one store read of their span, after checking the stored ``version``
    when one is given.  A range past the object's end raises."""
    if version is not None:
        check_version(store.getattr(cid, oid, VERSION_ATTR), version,
                      oid.shard)
    if not ranges:
        return b""
    lo = min(int(off) for off, _ in ranges)
    hi = max(int(off) + int(ln) for off, ln in ranges)
    span = memoryview(store.read(cid, oid, lo, hi - lo))
    if len(span) < hi - lo:
        raise ShardReadError(f"shard {oid.shard}: short read {len(span)} "
                             f"< {hi - lo} at offset {lo} of {oid.name}")
    return b"".join([span[off - lo:off - lo + ln] for off, ln in ranges])


class ECWriteDegraded(ShardReadError):
    """A live shard missed a strict-mode mutation: the op is NOT acked
    (retryable — the data remains reconstructable and repair is already
    scheduled), distinct from an unrecoverable >m failure."""


@dataclass(frozen=True)
class ECObjectMeta:
    size: int               # logical object size
    version: int


# objects whose metadata one backend keeps: the default of Ceph's
# osd_pg_object_context_cache_count (a backend is one PG's primary)
META_CACHE_OBJECTS = 64

# MetaCache.get's answer for an object it does not hold (None is an
# object affirmed absent)
MISS = object()


class MetaCache:
    """Each object's ``_read_meta`` answer, (size, version) or None for
    an affirmed absence, kept by the primary for its interval (the
    object_info_t of reference ObjectContext, which ``on_change``
    clears).  Every mutation of the PG's objects flows through this one
    backend, and the daemon builds a new backend at each interval change
    and clears this at activation, so the max version over the shards
    moves only through the backend's own mutations: a settled one
    ``put``s its result, an unsettled one ``drop``s the entry.  Recovery
    and scrub repair copy the authoritative attrs and change no answer;
    peering's log rewind, which can, comes before the activation clear.
    LRU-bounded by object count.

    Scrub and recovery read metadata outside the object lock, so a
    fan-in may overlap a mutation it does not order against: a fill
    captures ``begin_fill``'s token and ``end_fill`` keeps its answer
    only if no ``put``/``drop``/``clear`` of the object landed in
    between (the role of ExtentCache's generation).  Only objects with
    a fill in flight keep a counter."""

    def __init__(self, max_objects: int = META_CACHE_OBJECTS):
        from collections import OrderedDict

        self.max_objects = max_objects
        self._metas: "OrderedDict[str, ECObjectMeta | None]" = OrderedDict()
        # oid -> [fills in flight, mutations since the oldest began]
        self._fills: dict[str, list[int]] = {}

    def __len__(self) -> int:
        return len(self._metas)

    def get(self, oid: str):
        """The object's cached metadata (None: absent), or MISS."""
        meta = self._metas.get(oid, MISS)
        if meta is not MISS:
            self._metas.move_to_end(oid)
        return meta

    def begin_fill(self, oid: str) -> int:
        slot = self._fills.setdefault(oid, [0, 0])
        slot[0] += 1
        return slot[1]

    def end_fill(self, oid: str, token: int, meta=MISS) -> None:
        """Close a fill; keep ``meta`` (MISS: nothing to keep) unless a
        mutation superseded it since ``begin_fill`` gave ``token``."""
        slot = self._fills[oid]
        slot[0] -= 1
        if not slot[0]:
            del self._fills[oid]
        if meta is not MISS and slot[1] == token:
            self._store(oid, meta)

    def put(self, oid: str, meta: ECObjectMeta | None) -> None:
        """A settled mutation's result."""
        self._supersede(oid)
        self._store(oid, meta)

    def drop(self, oid: str) -> None:
        """A mutation whose outcome on the shards is not settled."""
        self._supersede(oid)
        self._metas.pop(oid, None)

    def clear(self) -> None:
        for slot in self._fills.values():
            slot[1] += 1
        self._metas.clear()

    def _supersede(self, oid: str) -> None:
        slot = self._fills.get(oid)
        if slot is not None:
            slot[1] += 1

    def _store(self, oid: str, meta: ECObjectMeta | None) -> None:
        self._metas[oid] = meta
        self._metas.move_to_end(oid)
        if len(self._metas) > self.max_objects:
            self._metas.popitem(last=False)


class ExtentCache:
    """Logical-extent cache for the EC overwrite pipeline (the role of
    reference src/osd/ExtentCache.h: pin recently written extents so a
    sub-stripe overwrite can merge WITHOUT re-reading + decoding k
    shards).  Lives inside one primary's ECBackend — all mutations flow
    through it under the per-object lock, and the backend (with its
    cache) is rebuilt at every peering interval, so coherence holds by
    construction.  Extents are coalesced per object; the whole cache is
    LRU-bounded by bytes."""

    def __init__(self, max_bytes: int = 8 << 20):
        from collections import OrderedDict

        self.max_bytes = max_bytes
        # oid -> sorted list of [start, bytearray] non-overlapping
        self._objs: "OrderedDict[str, list]" = OrderedDict()
        self._bytes = 0              # running total (trim is O(evicted))
        self.hits = 0
        self.misses = 0
        # invalidation generations: a writer captures generation(oid)
        # before its (possibly coalesced, so arbitrarily delayed) encode
        # and passes it to note_write, which drops the note if an
        # invalidate() landed in between — a completed-late write must
        # not resurrect extents that were invalidated while it was in
        # flight.  The per-oid ints are tiny and the backend (with its
        # cache) is rebuilt every peering interval, so growth is bounded
        # by the interval's invalidated-object count.
        self._epoch = 0
        self._gen: dict[str, int] = {}

    def get(self, oid: str, start: int, length: int) -> bytes | None:
        """The extent IFF fully covered; None = caller must read."""
        if length <= 0:
            return b""
        extents = self._objs.get(oid)
        if extents is None:
            self.misses += 1
            return None
        for estart, data in extents:
            if estart <= start and start + length <= estart + len(data):
                self._objs.move_to_end(oid)
                self.hits += 1
                return bytes(data[start - estart:
                                  start - estart + length])
        self.misses += 1
        return None

    def generation(self, oid: str) -> tuple[int, int]:
        """Invalidation generation token for ``oid``; capture before a
        write's encode, hand back to note_write (see __init__)."""
        return (self._epoch, self._gen.get(oid, 0))

    def note_write(self, oid: str, start: int, data: bytes,
                   gen: tuple[int, int] | None = None) -> None:
        """Record the post-write logical content of an aligned region,
        coalescing with overlapping/adjacent extents.  ``gen`` (from
        generation()) suppresses the note when an invalidate()/clear()
        superseded it while the write was in flight."""
        if gen is not None and gen != self.generation(oid):
            return
        if not len(data):
            return
        extents = self._objs.setdefault(oid, [])
        new_start, new_end = start, start + len(data)
        merged = bytearray(data)
        keep = []
        for estart, edata in extents:
            eend = estart + len(edata)
            if eend < new_start or estart > new_end:
                keep.append([estart, edata])
                continue
            # overlap/adjacency: splice the older bytes around the new
            if estart < new_start:
                merged = edata[: new_start - estart] + merged
                new_start = estart
            if eend > new_end:
                merged = merged + edata[len(edata) - (eend - new_end):]
                new_end = eend
        keep.append([new_start, bytearray(merged)])
        keep.sort(key=lambda e: e[0])
        self._bytes -= sum(len(d) for _, d in extents)
        self._bytes += sum(len(d) for _, d in keep)
        self._objs[oid] = keep
        self._objs.move_to_end(oid)
        self._trim()

    def invalidate(self, oid: str) -> None:
        self._gen[oid] = self._gen.get(oid, 0) + 1
        extents = self._objs.pop(oid, None)
        if extents:
            self._bytes -= sum(len(d) for _, d in extents)

    def clear(self) -> None:
        self._epoch += 1
        self._gen.clear()
        self._objs.clear()
        self._bytes = 0

    def _trim(self) -> None:
        while self._bytes > self.max_bytes and len(self._objs) > 1:
            _, extents = self._objs.popitem(last=False)
            self._bytes -= sum(len(d) for _, d in extents)
        # a single giant object must honor the budget too (a sequential
        # writer coalesces into one ever-growing extent): shed lowest-
        # offset bytes — farthest from a streaming tail — keeping the
        # hot tail cached
        while self._bytes > self.max_bytes and self._objs:
            _, extents = next(iter(self._objs.items()))
            if not extents:
                self._objs.popitem(last=False)
                continue
            over = self._bytes - self.max_bytes
            start, data = extents[0]
            if len(data) <= over:
                extents.pop(0)
                self._bytes -= len(data)
            else:
                extents[0] = [start + over, data[over:]]
                self._bytes -= over

    def stats(self) -> dict:
        return {"objects": len(self._objs), "bytes": self._bytes,
                "hits": self.hits, "misses": self.misses}


class _CoalesceItem:
    """One op's parked launch request (payload + result future).
    ``span``: the submitting op's ambient SpanCtx (if the op is
    sampled) — the shared launch is recorded under it at flush.
    ``wait``: its ``ec:coalesce_wait`` span, parked to flush."""

    __slots__ = ("payload", "nstripes", "fut", "t0", "span", "wait")

    def __init__(self, payload, nstripes, fut, t0, span=None):
        self.payload = payload
        self.nstripes = nstripes
        self.fut = fut
        self.t0 = t0
        self.span = span
        self.wait = tracing.span("ec:coalesce_wait")


class CoalescedLauncher:
    """Cross-op micro-batcher for device EC launches (the tentpole of
    the dynamic-batching fix for per-op dispatch overhead: PERF.md shows
    the kernel is 3-4x faster when a batch amortizes fixed launch/pack
    costs, yet each OSD op used to dispatch its own handful of stripes).

    Concurrent in-flight ops enqueue their stripe blocks keyed by launch
    geometry — ``('enc',)`` for encode, ``('dec', survivors, todo)`` for
    decode, so mixed failure patterns never share a decode matrix — and
    a single flusher task concatenates batchmates along the leading
    stripe axis and runs ONE device launch per key, scattering each op's
    slice back to its waiter.

    Adaptive micro-window: a flush happens at the FIRST of
      - every in-flight backend op is already parked here (idle: no
        batchmate can arrive, so waiting longer only adds latency),
      - ``max_stripes`` pending stripes,
      - ``window_us`` elapsed since the oldest parked op.

    Failure isolation: a batchmate's exception (shape error, codec
    raise, cancelled waiter) fails only that op.  Cancelled waiters are
    dropped at flush time; a failed batched launch falls back to a
    transparent per-op solo retry so batchmates still get results.
    """

    def __init__(self, backend, window_us: float = 200.0,
                 max_stripes: int = 4096):
        self.backend = backend
        self.window_s = max(0.0, float(window_us)) / 1e6
        self.max_stripes = max(1, int(max_stripes))
        self._items: dict[tuple, list[_CoalesceItem]] = {}
        self._npending = 0          # parked ops not yet flushed
        self._nstripes = 0
        self._flusher: asyncio.Task | None = None
        self._wake: asyncio.Event | None = None
        self._loop = None
        # lifetime stats (admin socket `ec coalesce stats`; the perf
        # counters aggregate across backends per daemon)
        self.launches = 0
        self.ops = 0
        self.solo_retries = 0
        self.failed_ops = 0
        self.cancelled_waiters = 0

    def _bind_loop(self, loop) -> None:
        # A backend may be driven through several event loops over its
        # life (tests run one backend under repeated asyncio.run);
        # asyncio primitives are loop-bound, so rebind lazily.  Parked
        # state never survives a loop: every submitter awaits its future
        # inside the old loop, so the queues are empty by construction
        # when a new loop first submits.
        self._loop = loop
        self._wake = asyncio.Event()
        self._flusher = None
        self._items = {}
        self._npending = 0
        self._nstripes = 0

    def notify(self) -> None:
        """Re-evaluate the flush condition (an op completed, so the
        idle test may newly hold)."""
        if self._wake is not None:
            try:
                if asyncio.get_running_loop() is self._loop:
                    self._wake.set()
            except RuntimeError:
                pass

    async def submit(self, key: tuple, payload, nstripes: int):
        """Park one launch request; resolves with this op's slice of
        the coalesced result."""
        loop = asyncio.get_running_loop()
        if loop is not self._loop:
            self._bind_loop(loop)
        item = _CoalesceItem(payload, int(nstripes),
                             loop.create_future(), loop.time(),
                             span=current_span())
        self._items.setdefault(key, []).append(item)
        self._npending += 1
        self._nstripes += item.nstripes
        if self._flusher is None or self._flusher.done():
            self._flusher = loop.create_task(self._run_flusher())
        self._wake.set()
        try:
            return await item.fut
        except asyncio.CancelledError:
            self.cancelled_waiters += 1
            raise
        finally:
            item.wait.end()

    async def _run_flusher(self) -> None:
        loop = self._loop
        try:
            while self._npending:
                while True:
                    if self._nstripes >= self.max_stripes:
                        break
                    if self._npending >= self.backend._inflight_ops:
                        break       # idle: no batchmate can arrive
                    oldest = min(it.t0 for items in self._items.values()
                                 for it in items)
                    remaining = oldest + self.window_s - loop.time()
                    if remaining <= 0:
                        break
                    self._wake.clear()
                    try:
                        await asyncio.wait_for(self._wake.wait(),
                                               remaining)
                    except asyncio.TimeoutError:
                        break
                batches = self._items
                self._items = {}
                self._npending = 0
                self._nstripes = 0
                for key, items in batches.items():
                    await self._flush_key(key, items)
        finally:
            # flusher teardown (daemon shutdown cancels it): fail any
            # still-parked waiters instead of leaving them hung
            for items in self._items.values():
                for it in items:
                    if not it.fut.done():
                        it.fut.cancel()
            self._items = {}
            self._npending = 0
            self._nstripes = 0

    async def _flush_key(self, key: tuple,
                         items: list[_CoalesceItem]) -> None:
        be = self.backend
        # a waiter cancelled while parked: drop its payload — the
        # remaining batchmates must neither wait for it nor fail
        live = [it for it in items if not it.fut.done()]
        if not live:
            return
        now = self._loop.time()
        for it in live:
            it.wait.end()
            be.perf.hinc("ec_coalesce_wait_hist_us", (now - it.t0) * 1e6)
        wall0 = time.time()
        t0 = time.perf_counter()
        try:
            with tracing.span("osd:ec:launch"):
                outs = await be._coalesce_launch(
                    key, [it.payload for it in live])
        except asyncio.CancelledError:
            raise
        except BaseException as exc:
            if len(live) == 1:
                self.launches += 1
                self.failed_ops += 1
                if not live[0].fut.done():
                    live[0].fut.set_exception(exc)
                return
            # failure isolation: one batchmate poisoned the batch
            # (shape mismatch, codec raise) — transparent solo retry
            # so only the actually-broken op(s) fail
            for it in live:
                if it.fut.done():
                    continue
                self.solo_retries += 1
                self.launches += 1
                try:
                    out = (await be._coalesce_launch(
                        key, [it.payload]))[0]
                except asyncio.CancelledError:
                    raise
                except BaseException as solo_exc:
                    self.failed_ops += 1
                    it.fut.set_exception(solo_exc)
                else:
                    it.fut.set_result(out)
            return
        launch_ms = (time.perf_counter() - t0) * 1e3
        self.launches += 1
        self.ops += len(live)
        be.perf.inc("ec_coalesce_launches")
        be.perf.inc("ec_coalesce_ops", len(live))
        be.perf.tinc("ec_coalesce_occupancy", len(live))
        if be.journal is not None:
            be.journal.emit(
                "coalesce.flush", op=str(key[0]), ops=len(live),
                stripes=sum(it.nstripes for it in live),
                launch_ms=round(launch_ms, 3))
        if be.tracer is not None:
            # one measured device launch serves every sampled
            # batchmate: record the same interval once per interested
            # parent so each trace tree shows the shared launch
            nstripes = sum(it.nstripes for it in live)
            for it in live:
                if it.span is not None:
                    be.tracer.record(
                        "osd:ec:launch", it.span, wall0, launch_ms,
                        op=key[0], occupancy=len(live),
                        stripes=nstripes)
        for it, out in zip(live, outs):
            if not it.fut.done():
                it.fut.set_result(out)

    def stats(self) -> dict:
        return {
            "window_us": self.window_s * 1e6,
            "max_stripes": self.max_stripes,
            "launches": self.launches,
            "ops": self.ops,
            "occupancy": (self.ops / self.launches
                          if self.launches else 0.0),
            "solo_retries": self.solo_retries,
            "failed_ops": self.failed_ops,
            "cancelled_waiters": self.cancelled_waiters,
            "pending_ops": self._npending,
            "pending_stripes": self._nstripes,
        }


class ECBackend:
    def __init__(
        self,
        codec,
        shards: Mapping[int, ShardIO],
        stripe_unit: int | None = None,
        log_hook=None,
        hedge_timeout: float | None = None,
        perf: PerfCounters | None = None,
        tracer=None,
        journal=None,
        coalesce: bool = True,
        coalesce_window_us: float = 200.0,
        coalesce_max_stripes: int = 4096,
        mesh_coalescer=None,
        resident=None,
        resident_ns: str = "",
        resident_max_bytes: int = 256 << 20,
    ):
        """``codec``: an initialised ErasureCodeInterface; ``shards``:
        shard id -> ShardIO for all k+m positions. ``log_hook(oid, op,
        obj_version, prior_version)`` (daemon-provided) allocates the PG
        log entry that rides every shard mutation; None = no logging
        (standalone/library use).  ``mesh_coalescer``: the host-level
        MeshCoalescer (osd/mesh_coalesce.py) that shards this backend's
        launches over every local device, or None."""
        self.ec = codec
        self.k = codec.get_data_chunk_count()
        self.n = codec.get_chunk_count()
        self.m = self.n - self.k
        # Physical shard ids holding the LOGICAL data chunks, in logical
        # order (ECUtil chunk_mapping role).  Mapped layouts (LRC
        # "DDD__..." interleaves parity between data groups) place data
        # at chunk_mapping[:k], NOT 0..k-1 — reads must gather from
        # these shards or they would return parity bytes as data.
        cm = getattr(codec, "chunk_mapping", None)
        self.data_shards = ([int(cm[i]) for i in range(self.k)] if cm
                            else list(range(self.k)))
        unit = stripe_unit or codec.get_chunk_size(0)
        align = getattr(codec, "get_alignment", lambda: 1)()
        if unit % align:
            raise ValueError(
                f"stripe_unit {unit} not aligned to codec alignment {align}"
            )
        self.sinfo = StripeInfo(self.k, unit)
        self.log_hook = log_hook
        # logged mode is STRICT: every live shard must commit a mutation
        # before it is acked (acting-set holes stay tolerated up to m).
        # This is what makes log-based rewind safe — an entry absent from
        # the authoritative log was never acked. Standalone (unlogged)
        # use keeps the lenient tolerate-and-eager-repair behavior.
        self.strict = log_hook is not None
        self.shards = dict(shards)
        if set(self.shards) != set(range(self.n)):
            raise ValueError(f"need shards 0..{self.n - 1}")
        self._repair_tasks: set[asyncio.Task] = set()
        self.extent_cache = ExtentCache()
        # each object's (size, version) for the interval: _read_meta
        # answers from it without a fan-in to every shard
        self.meta_cache = MetaCache()
        # oid -> shards known stale from a failed mutation: a subsequent
        # write must heal them FIRST — otherwise its version bump would
        # make the stale shard pass the per-object version check and
        # serve corrupt ranges (version granularity is the object, not
        # the stripe)
        self._dirty: dict[str, set[int]] = {}
        # observability: proves which plane served a batch (tests and
        # perf counters read these).  *_buckets record the DISTINCT
        # padded batch dims launched — the pow2 shape-bucketing bound on
        # compiled XLA programs is asserted against them.
        self.mesh_stats = {"encodes": 0, "decodes": 0, "repairs": 0,
                           "encode_buckets": set(),
                           "decode_buckets": set()}
        # hedged reads: a data-shard read still pending after
        # hedge_timeout seconds is raced against a minimum_to_decode
        # reconstruction from the surviving shards (None/0 = off)
        self.hedge_timeout = hedge_timeout or None
        self.perf = perf if perf is not None else PerfCounters("ec")
        # per-object reader/writer state: the one lock table
        self._objects = ObjectStates(self.perf)
        # kernel profiler (ec/profiler.py): every device launch below
        # attributes its wall time / stripes / bytes to this backend's
        # codec signature, recorded at the SAME sites with the SAME
        # values as the ec_*_launch_us and ec_launch_bytes counters —
        # attribution of the counters, never a second measurement
        from ceph_tpu.ec.profiler import profiler_for
        self.codec_sig = (f"{type(codec).__name__.lower()}"
                          f"-k{self.k}-m{self.m}")
        self.profiler = profiler_for(self.perf)
        # shared Tracer (daemon-provided): sampled ops get their
        # coalesced device launch recorded into their trace tree
        self.tracer = tracer
        # flight recorder (daemon-provided EventJournal): coalescer
        # window flushes land as structured events
        self.journal = journal
        # ec_launch_bytes: logical bytes fed into device launches (the
        # numerator of achieved-GiB/s: ec_launch_bytes delta over
        # encode+decode launch-us delta — the utilization telemetry's
        # HBM-roofline-% input)
        # ec_write_glue_*: resident writes whose split ran as one
        # jitted program, and the split programs compiled
        # (_note_split_shapes);
        # ec_rmw_gather_skipped: writes whose new bytes covered every
        # surviving byte of their stripes, so nothing was read back;
        # ec_meta_cache_*: _read_meta calls answered by meta_cache, and
        # those that fanned in to the shards;
        # ec_clay_subchunk_repairs / ec_clay_layered_decodes: degraded
        # reads of a CLAY pool whose lost chunk was rebuilt from its
        # helpers' repair sub-chunks / decoded from k whole chunks;
        # ec_subchunk_read_bytes: bytes the ranged sub-ops returned
        for _k in ("hedge_issued", "hedge_won", "hedge_lost",
                   "hedge_meta", "ec_meta_cache_hit", "ec_meta_cache_miss",
                   "ec_clay_subchunk_repairs", "ec_clay_layered_decodes",
                   "ec_subchunk_read_bytes",
                   "ec_coalesce_launches", "ec_coalesce_ops",
                   "ec_coalesce_pad_waste", "ec_device_launches",
                   "ec_launch_bytes",
                   "ec_mesh_launches", "ec_mesh_ops",
                   "ec_mesh_ici_bytes", "ec_mesh_ici_whole_bytes",
                   "ec_write_glue_fused", "ec_rmw_gather_skipped",
                   "ec_write_glue_compiles"):
            self.perf.add(_k, CounterType.U64)
        for _k in ("ec_coalesce_occupancy", "ec_mesh_occupancy"):
            self.perf.add(_k, CounterType.LONGRUNAVG)
        for _k in ("ec_encode_launch_us", "ec_decode_launch_us",
                   "ec_coalesce_wait_hist_us", "ec_mesh_launch_us",
                   # per-shard-read latency as observed by this primary
                   # — the distribution the QoS controller derives each
                   # OSD's adaptive hedge timeout from
                   "ec_shard_read_us"):
            self.perf.add(_k, CounterType.HISTOGRAM)
        # device residency (opt-in): keep shard streams on device in a
        # DeviceShardCache so repeated ops feed the kernel without host
        # round-trips; writes go through to the store before they ack.
        # Requires a codec with device-array entry points.  The transfer
        # counters are registered unconditionally — the non-resident
        # paths account their modeled host<->device traffic under the
        # same names, so cfg7's A/B reads one counter pair either way.
        register_resident_counters(self.perf)
        # batched repair engine counters (accrued by recover_batch;
        # the per-object paths share the plan hit/miss pair)
        register_repair_counters(self.perf)
        # batched scrub counters (accrued by scrub/scrub_batch; the
        # per-object oracle and the batched path share the launch
        # counter so cfg14's A/B reads one name for both arms)
        register_scrub_counters(self.perf)
        self.resident: DeviceShardCache | None = None
        self.resident_ns = resident_ns
        if resident is not None and resident is not False \
                and hasattr(codec, "encode_chunks_device") \
                and hasattr(codec, "decode_chunks_device"):
            self.resident = resident if isinstance(
                resident, DeviceShardCache
            ) else DeviceShardCache(max_bytes=resident_max_bytes,
                                    perf=self.perf)
        # cross-op micro-batching of device launches (the tentpole):
        # ops in flight concurrently share one encode/decode launch
        self._inflight_ops = 0
        self.coalescer = CoalescedLauncher(
            self, window_us=coalesce_window_us,
            max_stripes=coalesce_max_stripes,
        ) if coalesce else None
        # host-level mesh coalescer (osd/mesh_coalesce.py): parked ops
        # from EVERY co-located OSD's backend share one shard_map-
        # sharded launch over the device mesh.  register() refuses
        # 1-device pools and codecs without a dense generator — those
        # keep the per-backend launcher above (graceful degradation).
        # Decode joins only when the codec exposes decode_selection
        # (shec encodes sharded but decodes per backend).  The host
        # handle is kept even when sharded launches are refused: the
        # clay/lrc sub-chunk repair meshes hang off it.
        self._mesh_host = mesh_coalescer
        self.mesh_co = None
        self._mesh_dec_ok = False
        if mesh_coalescer is not None and mesh_coalescer.register(self):
            self.mesh_co = mesh_coalescer
            self._mesh_dec_ok = mesh_coalescer.supports_decode(self)

    def object_lock(self, oid: str, mode: str = WRITE, reqid=None):
        """The object's reader/writer state (``osd/object_state.py``):
        ``"r"`` shared, ``"w"`` alone, FIFO.  Op vectors take it once
        (``OSD._do_ops_ec``); writes, removes, scrub, recovery and
        backfill take it as writers, reads as readers; a call under a
        state its task already holds re-enters it."""
        return self._objects.lock(oid, mode, reqid)

    def _note_split_shapes(self, bp: int, sizes) -> None:
        """Count the split programs a launch of padded batch ``bp``
        with batchmates of ``sizes`` stripes compiles (_SPLIT_SHAPES)."""
        for sz in set(sizes):
            key = (bp, self.n, self.sinfo.chunk_size, sz)
            if key not in _SPLIT_SHAPES:
                _SPLIT_SHAPES.add(key)
                self.perf.inc("ec_write_glue_compiles")

    # -- host<->device boundary ------------------------------------------
    #
    # Both data-path flavors account the logical bytes that cross the
    # host<->device boundary under ec_resident_h2d_bytes /
    # ec_resident_d2h_bytes: the resident path counts at its real
    # conversion points (_to_host/_to_device), the classic
    # numpy path counts the modeled launch traffic (stripes up, chunks
    # down) in _encode_batch/_decode_batch.  Deterministic on CPU —
    # that's what makes the cfg7 A/B counter-verified without a chip.

    @staticmethod
    def _is_device(arr) -> bool:
        """True for jax arrays (the resident representation); numpy /
        bytes are the host representation."""
        return not isinstance(
            arr, (np.ndarray, bytes, bytearray, memoryview))

    def _to_host(self, arr) -> np.ndarray:
        """Materialize on host, counting the transfer when it crosses
        (on the loop thread this waits for the device result)."""
        if isinstance(arr, np.ndarray):
            return arr
        with tracing.span("ec:d2h"):
            out = np.asarray(arr)
        self.perf.inc("ec_resident_d2h_bytes", out.nbytes)
        return out

    def _to_device(self, arr):
        """Upload to device, counting the transfer when it crosses."""
        if not self._is_device(arr):
            import jax.numpy as jnp

            with tracing.span("ec:h2d"):
                arr = np.asarray(arr, np.uint8)
                dev = jnp.asarray(arr)
            self.perf.inc("ec_resident_h2d_bytes", arr.nbytes)
            return dev
        return arr

    @staticmethod
    async def _launch(fn, *args):
        """Run one codec call on a worker thread (a first-time XLA
        compile must not stall heartbeats and leases on the loop),
        spanned there as ``ec:launch``.  A device-array call returns
        once its work is enqueued, so on the device-resident paths the
        span (and the launch time recorded around it) ends at the
        enqueue, not when the device has run it."""
        def run():
            with tracing.span("ec:launch"):
                return fn(*args)

        return await asyncio.to_thread(run)

    async def _encode_batch(self, stripes) -> np.ndarray:
        """(B, k, C) -> (B, k+m, C) through the codec's batch encode.
        A device-resident batch (jax array in) encodes as the write
        path's launch (_encode_write) and stays on device.

        The batch dim is shape-bucketed: B pads up to a power of two
        (zero stripes; rows are independent, result sliced back) so the
        program cache holds at most ceil(log2(max B)) + 1
        distinct encode shapes per codec instead of one per stripe
        count."""
        from ceph_tpu.ec.engine import pad_batch_pow2

        if self._is_device(stripes):
            streams, _ = (await self._encode_write([stripes]))[0]
            return self.sinfo.stream_chunks(streams)
        in_bytes = stripes.nbytes if hasattr(stripes, "nbytes") else 0
        with tracing.span("ec:prep", tags=_SITE["pad"]):
            stripes, b = pad_batch_pow2(stripes)
        if stripes.shape[0] != b:
            self.perf.inc("ec_coalesce_pad_waste", stripes.shape[0] - b)
        self.mesh_stats["encode_buckets"].add(stripes.shape[0])
        self.perf.inc("ec_device_launches")
        self.perf.inc("ec_launch_bytes", in_bytes)
        self.perf.inc("ec_resident_h2d_bytes", in_bytes)
        t0 = time.perf_counter()
        out = np.asarray(await self._launch(
            self.ec.encode_chunks_batch, stripes
        ))[:b]
        dt_us = (time.perf_counter() - t0) * 1e6
        self.perf.hinc("ec_encode_launch_us", dt_us)
        self.profiler.record(f"{self.codec_sig}:enc", dt_us,
                             stripes=b, hbm_bytes=in_bytes)
        self.perf.inc("ec_resident_d2h_bytes", out.nbytes)
        return out

    async def _encode_write(self, payloads: list) -> list:
        """The device encode: one launch for the (b_j, k, C) stripe
        batches of ``payloads`` (host ones are uploaded, counted),
        returning for each its (n, b_j*C) shard-stream batch and the
        tuple of its n per-shard (b_j*C,) device arrays.

        All of it runs on the launch thread: the batchmates'
        concatenation, the pow2 zero padding (B bucketed as in
        _encode_batch), the codec's device encode, then ONE jitted
        split per batchmate (engine.split_shard_streams) — so a
        first-time compile of any of it never stalls the loop."""
        import jax.numpy as jnp

        from ceph_tpu.ec.engine import (pad_batch_to, pow2_bucket,
                                        split_shard_streams)

        payloads = [self._to_device(p) for p in payloads]
        sizes = [int(p.shape[0]) for p in payloads]
        b = sum(sizes)
        bp = pow2_bucket(b)
        in_bytes = sum(int(p.nbytes) for p in payloads)
        self.perf.inc("ec_launch_bytes", in_bytes)
        if bp != b:
            self.perf.inc("ec_coalesce_pad_waste", bp - b)
        self.mesh_stats["encode_buckets"].add(bp)
        self.perf.inc("ec_device_launches")
        self._note_split_shapes(bp, sizes)
        offs = np.cumsum([0] + sizes[:-1], dtype=np.int32)
        encode = self.ec.encode_chunks_device

        def run():
            x = payloads[0] if len(payloads) == 1 \
                else jnp.concatenate(payloads, axis=0)
            chunks = encode(pad_batch_to(x, bp))
            return [split_shard_streams(chunks, off, sz)
                    for off, sz in zip(offs, sizes)]

        t0 = time.perf_counter()
        outs = await self._launch(run)
        dt_us = (time.perf_counter() - t0) * 1e6
        self.perf.hinc("ec_encode_launch_us", dt_us)
        self.profiler.record(f"{self.codec_sig}:enc", dt_us,
                             stripes=b, hbm_bytes=in_bytes,
                             enqueue_only=True)
        return outs

    async def _decode_batch(self, batched: dict, missing: list) -> dict:
        """Batched reconstruct of ``missing`` through the codec's batch
        decode.  Batch dim shape-bucketed like _encode_batch."""
        missing = [int(w) for w in missing]
        if self.resident is not None and any(
                self._is_device(c) for c in batched.values()):
            return await self._decode_batch_device(batched, missing)
        b = next(iter(batched.values())).shape[0] if batched else 0
        in_bytes = sum(c.nbytes for c in batched.values())
        if b:
            from ceph_tpu.ec.engine import pow2_bucket

            bp = pow2_bucket(b)
            if bp != b:
                self.perf.inc("ec_coalesce_pad_waste", bp - b)
                with tracing.span("ec:prep", tags=_SITE["pad"]):
                    batched = {
                        s: np.concatenate([
                            np.asarray(c, np.uint8),
                            np.zeros((bp - b,) + np.shape(c)[1:],
                                     np.uint8),
                        ], axis=0)
                        for s, c in batched.items()
                    }
            self.mesh_stats["decode_buckets"].add(bp)
        self.perf.inc("ec_device_launches")
        self.perf.inc("ec_launch_bytes", in_bytes)
        self.perf.inc("ec_resident_h2d_bytes", in_bytes)
        t0 = time.perf_counter()
        out = await self._launch(
            self.ec.decode_chunks_batch, batched, missing
        )
        # the interval runs through the host copy of the result, so it
        # is the whole launch whatever the codec hands back
        with tracing.span("ec:d2h"):
            res = {w: np.asarray(c)[:b] for w, c in out.items()}
        dt_us = (time.perf_counter() - t0) * 1e6
        self.perf.hinc("ec_decode_launch_us", dt_us)
        self.profiler.record(f"{self.codec_sig}:dec", dt_us,
                             stripes=b, hbm_bytes=in_bytes)
        # only rebuilt chunks cross back down; available targets are
        # passed through as the same host arrays
        self.perf.inc("ec_resident_d2h_bytes", sum(
            c.nbytes for w, c in res.items() if w not in batched))
        return res

    async def _decode_batch_device(self, batched: dict,
                                   missing: list) -> dict:
        """_decode_batch for a (possibly mixed) device-resident batch:
        host chunks are promoted to device (counted uploads), rebuilt
        targets come back as device arrays, and available targets pass
        through in whatever representation they arrived in."""
        from ceph_tpu.ec.engine import pad_batch_pow2_device

        avail = {int(s): self._to_device(c) for s, c in batched.items()}
        b = next(iter(avail.values())).shape[0] if avail else 0
        if b:
            padded = {}
            with tracing.span("ec:prep", tags=_SITE["pad"]):
                for s, c in avail.items():
                    padded[s], _ = pad_batch_pow2_device(c)
            bp = next(iter(padded.values())).shape[0]
            if bp != b:
                self.perf.inc("ec_coalesce_pad_waste", bp - b)
            self.mesh_stats["decode_buckets"].add(int(bp))
            avail = padded
        self.perf.inc("ec_device_launches")
        in_bytes = sum(
            int(getattr(c, "nbytes", 0)) for c in batched.values())
        self.perf.inc("ec_launch_bytes", in_bytes)
        t0 = time.perf_counter()
        out = {w: batched[w][:b] for w in missing if w in batched}
        todo = [w for w in missing if w not in batched]
        if todo:
            if len(avail) < self.k:
                raise IOError(f"cannot decode {todo}")
            rebuilt = await self._launch(
                self.ec.decode_chunks_device, avail, todo)
            with tracing.span("ec:prep", tags=_SITE["trim"]):
                for i, w in enumerate(todo):
                    out[w] = rebuilt[:b, i]
        dt_us = (time.perf_counter() - t0) * 1e6
        self.perf.hinc("ec_decode_launch_us", dt_us)
        self.profiler.record(f"{self.codec_sig}:dec", dt_us,
                             stripes=b, hbm_bytes=in_bytes,
                             enqueue_only=True)
        return out

    # -- cross-op coalescing (CoalescedLauncher front ends) ---------------
    async def _coalesced_encode(self, stripes: np.ndarray) -> np.ndarray:
        """Encode entry for in-flight ops: parks the stripe block on the
        per-backend CoalescedLauncher (one device launch shared across
        concurrent batchmates) or falls through to the direct path when
        coalescing is off.  Shape validation happens HERE, before the op
        joins a batch, so a malformed op can only fail itself.  Device
        batches ride the same launcher as the resident write path's
        (_encode_write) and stay on device end to end; their shard
        streams are stacked back to (B, k+m, C) here."""
        if not self._is_device(stripes):
            stripes = np.asarray(stripes, np.uint8)
        if self.coalescer is None and self.mesh_co is None:
            return await self._encode_batch(stripes)
        self._check_encode_shape(stripes)
        if self.mesh_co is not None:
            # host-wide launcher: batchmates may come from OTHER OSDs'
            # backends, and the launch shards over the whole mesh
            return await self.mesh_co.submit(
                self, ("enc",), stripes, stripes.shape[0])
        out = await self.coalescer.submit(
            ("enc",), stripes, stripes.shape[0])
        if isinstance(out, tuple):
            out = self.sinfo.stream_chunks(out[0])
        return out

    async def _coalesced_encode_write(self, stripes):
        """Encode entry of the resident write path: (B, k, C) device
        stripes -> ((n, B*C) shard-stream batch, tuple of the n
        per-shard (B*C,) device arrays), from one launch shared with
        the op's batchmates (_encode_write)."""
        if self.mesh_co is not None:
            from ceph_tpu.ec.engine import split_shard_streams

            chunks = await self._coalesced_encode(stripes)
            b = int(chunks.shape[0])
            self._note_split_shapes(b, [b])
            return await self._launch(
                split_shard_streams, chunks, np.int32(0), b)
        if self.coalescer is None:
            return (await self._encode_write([stripes]))[0]
        self._check_encode_shape(stripes)
        return await self.coalescer.submit(
            ("enc",), stripes, stripes.shape[0])

    def _check_encode_shape(self, stripes) -> None:
        if stripes.ndim != 3 or stripes.shape[1] != self.k \
                or stripes.shape[2] != self.sinfo.chunk_size:
            raise ValueError(
                f"encode batch shape {stripes.shape} != "
                f"(B, {self.k}, {self.sinfo.chunk_size})"
            )

    async def _coalesced_decode(self, batched: dict,
                                missing: list) -> dict:
        """Decode entry for in-flight ops.  Coalescing groups strictly
        by (available shards, decode targets): only ops with the SAME
        failure pattern share a launch — and hence a decode matrix."""
        missing = [int(w) for w in missing]
        if self.coalescer is None and self._mesh_host is None:
            return await self._decode_batch(batched, missing)
        avail = {
            int(s): c if self._is_device(c) else np.asarray(c, np.uint8)
            for s, c in batched.items()
        }
        bs = {c.shape[0] for c in avail.values()}
        if not avail or len(bs) != 1 or any(
                c.ndim != 2 or c.shape[1] != self.sinfo.chunk_size
                for c in avail.values()):
            raise ValueError(
                f"decode batch shapes "
                f"{ {s: np.shape(c) for s, c in avail.items()} } "
                f"not uniform (B, {self.sinfo.chunk_size})"
            )
        b = bs.pop()
        if self._mesh_host is not None:
            # cross-chip sub-chunk repair: a single-chunk decode from
            # whole chunks on a clay/lrc codec (per-object recovery; a
            # CLAY degraded read fetches only the helpers' planes, see
            # _reconstruct) moves only helper planes / group chunks over
            # the interconnect, not whole survivor chunks
            rep = await self._mesh_subchunk_repair(avail, missing)
            if rep is not None:
                return rep
        key = ("dec", tuple(sorted(avail)), tuple(missing))
        if self.mesh_co is not None and self._mesh_dec_ok:
            return await self.mesh_co.submit(self, key, avail, b)
        if self.coalescer is None:
            return await self._decode_batch(avail, missing)
        return await self.coalescer.submit(key, avail, b)

    async def _coalesce_launch(self, key: tuple, payloads: list):
        """One device launch for a list of batchmate payloads (called
        only by the CoalescedLauncher): concatenate along the leading
        stripe axis, run the direct batch path (which shape-buckets),
        scatter the slices back in order.  An encode with a device
        batch runs as _encode_write and gives each device batchmate its
        (streams, shard rows) pair; a ``("rep", ...)`` key runs
        :meth:`_repair_launch`."""
        if key[0] == "rep":
            return await self._repair_launch(key, payloads)
        if key[0] == "enc":
            if any(self._is_device(p) for p in payloads):
                # device batches take the resident write path's launch;
                # a host batchmate is uploaded (counted) and gets its
                # (B, k+m, C) chunks back down
                outs = await self._encode_write(payloads)
                return [out if self._is_device(p)
                        else self.sinfo.stream_chunks(self._to_host(out[0]))
                        for p, out in zip(payloads, outs)]
            if len(payloads) == 1:
                return [await self._encode_batch(payloads[0])]
            sizes = [p.shape[0] for p in payloads]
            with tracing.span("ec:prep", tags=_SITE["concat"]):
                cat = np.concatenate(payloads, axis=0)
            out = await self._encode_batch(cat)
            res, off = [], 0
            with tracing.span("ec:prep", tags=_SITE["split"]):
                for sz in sizes:
                    res.append(out[off:off + sz])
                    off += sz
            return res
        _, shards, todo = key
        if len(payloads) == 1:
            return [await self._decode_batch(payloads[0], list(todo))]
        sizes = [next(iter(p.values())).shape[0] for p in payloads]
        any_dev = any(
            self._is_device(c) for p in payloads for c in p.values())
        with tracing.span("ec:prep", tags=_SITE["concat"]):
            if any_dev:
                import jax.numpy as jnp
                cat = {
                    s: jnp.concatenate(
                        [self._to_device(p[s]) for p in payloads], axis=0)
                    for s in shards
                }
            else:
                cat = {
                    s: np.concatenate([p[s] for p in payloads], axis=0)
                    for s in shards
                }
        out = await self._decode_batch(cat, list(todo))
        res, off = [], 0
        with tracing.span("ec:prep", tags=_SITE["split"]):
            for p, sz in zip(payloads, sizes):
                host_op = not any(self._is_device(c) for c in p.values())
                sl = {w: c[off:off + sz] for w, c in out.items()}
                if any_dev and host_op:
                    sl = {w: self._to_host(c) for w, c in sl.items()}
                res.append(sl)
                off += sz
        return res

    async def _repair_launch(self, key: tuple, payloads: list) -> list:
        """One device launch rebuilding the lost chunk of every batchmate
        from its d helpers' repair planes (key ``("rep", lost, helpers,
        runs)``; a payload is ``(nstripes, blocks, whole)``: per helper its
        whole chunk extent or only its repair planes, as flagged).

        On the launch thread: the plane gather into one (d, B, P*sc)
        buffer (``ec:prep`` site ``planes``), B padded with zero stripes
        to a power of two, then the codec's repair program over blocks of
        at most ``_REPAIR_BLOCK`` stripes (one compiled shape for large
        objects), then the download.  Returns each batchmate's rebuilt
        (nstripes*C,) chunk."""
        from ceph_tpu.ec.engine import pow2_bucket

        _, lost, helpers, runs = key
        C = self.sinfo.chunk_size
        sc = C // self.ec.get_sub_chunk_count()
        width = sum(n for _, n in runs) * sc
        sizes = [p[0] for p in payloads]
        b = sum(sizes)
        blk = min(pow2_bucket(b), _REPAIR_BLOCK)
        bp = -(-b // blk) * blk
        in_bytes = len(helpers) * b * width
        if bp != b:
            self.perf.inc("ec_coalesce_pad_waste", bp - b)
        self.mesh_stats["decode_buckets"].add(blk)
        self.perf.inc("ec_device_launches")
        self.perf.inc("ec_launch_bytes", in_bytes)
        self.perf.inc("ec_resident_h2d_bytes", in_bytes)

        def run():
            with tracing.span("ec:prep", tags=_SITE["planes"]):
                buf = np.empty((len(helpers), bp, width), np.uint8)
                buf[:, b:] = 0
                row = 0
                for n, blocks, whole in payloads:
                    for h, (src, full) in enumerate(zip(blocks, whole)):
                        # a resident whole chunk is one the read returns:
                        # its merge counts the download, and jax keeps
                        # the host copy made here for it
                        src = np.asarray(src)
                        dst = buf[h, row:row + n]
                        if not full:
                            dst[:] = src.reshape(n, width)
                            continue
                        src = src.reshape(n, C)
                        col = 0
                        for off, cnt in runs:
                            dst[:, col:col + cnt * sc] = \
                                src[:, off * sc:(off + cnt) * sc]
                            col += cnt * sc
                    row += n
            outs = [self.ec.repair_chunks_device(lost, helpers,
                                                 buf[:, i:i + blk])
                    for i in range(0, bp, blk)]
            return np.concatenate([np.asarray(o) for o in outs])

        t0 = time.perf_counter()
        rebuilt = await self._launch(run)
        dt_us = (time.perf_counter() - t0) * 1e6
        self.perf.hinc("ec_decode_launch_us", dt_us)
        self.profiler.record(f"{self.codec_sig}:rep", dt_us, stripes=b,
                             hbm_bytes=in_bytes)
        self.perf.inc("ec_resident_d2h_bytes", b * C)
        out, row = [], 0
        for n in sizes:
            out.append(rebuilt[row:row + n].reshape(-1))
            row += n
        return out

    async def _mesh_subchunk_repair(self, avail: dict,
                                    missing: list) -> dict | None:
        """Single-chunk degraded read over the mesh, moving sub-chunks.

        CLAY: the regenerating-code repair reads only 1/q of each of the
        d helpers' bytes — parallel/clay_sharding extracts the repair
        planes BEFORE its all_gather, so only those planes ride the
        interconnect.  LRC: the lost chunk's local group repairs with a
        group-local all_gather — other groups' chunks never move.  Both
        operators are bit-identical to the plugin decode (their _check
        probes gate the corpus), so a degraded read through here returns
        the same bytes as the classic whole-chunk path.

        Interconnect savings are counter-verified: ec_mesh_ici_bytes
        accrues the modeled moved bytes, ec_mesh_ici_whole_bytes the
        whole-chunk counterfactual (k full survivor chunks).

        Returns None whenever the geometry doesn't fit — multi-chunk
        loss, helpers unavailable, device-resident payloads, or a pool
        the repair meshes can't tile — and the caller falls back to the
        classic decode path.  An operator or compile failure raises."""
        ec = self.ec
        is_clay = hasattr(ec, "sub_chunk_no") and hasattr(ec, "q")
        is_lrc = hasattr(ec, "layers")
        if not (is_clay or is_lrc):
            return None
        todo = [w for w in missing if w not in avail]
        if len(todo) != 1:
            return None
        if any(self._is_device(c) for c in avail.values()):
            return None
        lost = todo[0]
        b = next(iter(avail.values())).shape[0]
        C = self.sinfo.chunk_size
        if is_clay:
            if C % ec.sub_chunk_no:
                return None
            mesh = self._mesh_host.clay_repair_mesh(self.n)
            if mesh is None:
                return None
            from ceph_tpu.ec.repair_operator import \
                clay_repair_operator
            from ceph_tpu.parallel.clay_sharding import (
                clay_repair_ici_bytes, sharded_clay_repair)

            _, helpers, _ = clay_repair_operator(ec, lost)
            if any(h not in avail for h in helpers):
                return None
            moved, whole = clay_repair_ici_bytes(
                ec, len(helpers), b, C)
            repair = sharded_clay_repair
            dp = mesh.shape["dp"]
        else:
            groups = len(ec.layers) - 1
            mesh = self._mesh_host.lrc_repair_mesh(groups)
            if mesh is None:
                return None
            from ceph_tpu.ec.repair_operator import \
                lrc_repair_operator
            from ceph_tpu.parallel.lrc_sharding import (
                lrc_repair_ici_bytes, sharded_lrc_repair)

            _, minimum = lrc_repair_operator(ec, lost)
            if any(h not in avail for h in minimum):
                return None
            moved, whole = lrc_repair_ici_bytes(
                ec, len(minimum), b, C)
            repair = sharded_lrc_repair
            dp = mesh.shape["dp"]
        # dp must divide the launched batch; zero stripes pad (rows are
        # independent) and the pad slices off below
        bp = -(-b // dp) * dp
        chunks = np.zeros((bp, self.n, C), np.uint8)
        for s, c in avail.items():
            chunks[:b, int(s)] = np.asarray(c, np.uint8)
        self.perf.inc("ec_device_launches")
        self.perf.inc("ec_mesh_launches")
        self.perf.inc("ec_launch_bytes", chunks.nbytes)
        self.perf.inc("ec_resident_h2d_bytes", chunks.nbytes)
        t0 = time.perf_counter()
        rec = np.asarray(await self._launch(
            repair, mesh, ec, chunks, lost))[:b]
        launch_us = (time.perf_counter() - t0) * 1e6
        self.perf.hinc("ec_decode_launch_us", launch_us)
        self.perf.hinc("ec_mesh_launch_us", launch_us)
        self.profiler.record(f"{self.codec_sig}:mesh-repair",
                             launch_us, stripes=b,
                             hbm_bytes=chunks.nbytes)
        self.perf.inc("ec_mesh_ici_bytes", moved)
        self.perf.inc("ec_mesh_ici_whole_bytes", whole)
        self.perf.inc("ec_resident_d2h_bytes", rec.nbytes)
        self.mesh_stats["repairs"] += 1
        out = {w: avail[w] for w in missing if w in avail}
        out[lost] = rec
        return out

    def _track_op(self):
        """In-flight op accounting for the coalescer's adaptive window:
        when every tracked op is parked in the launcher, nothing else
        can arrive and the flush happens immediately (the idle case — a
        solo writer never pays the window)."""
        backend = self

        class _Track:
            async def __aenter__(self):
                backend._inflight_ops += 1
                return self

            async def __aexit__(self, *exc):
                backend._inflight_ops -= 1
                if backend.coalescer is not None:
                    backend.coalescer.notify()
                if backend.mesh_co is not None:
                    backend.mesh_co.notify()
                return False

        return _Track()

    # -- metadata --------------------------------------------------------
    async def _attr_all(self, oid: str, name: str,
                        hedged: bool = False) -> list:
        """Fetch one attr from every shard concurrently (metadata is
        replicated per shard; one round-trip worst case instead of k+m
        serial awaits). Each slot is bytes, KeyError (shard affirms the
        object/attr absent), or another exception (shard unreachable).

        ``hedged`` (client IO paths only): with a hedge timeout armed,
        stragglers are cut loose once k shards have answered — a
        committed write lands on at least n-m = k shards, so any k
        answers include a fresh copy (the same bound the write path
        commits with).  Without it, one dead-but-not-yet-marked-down
        peer stalls every meta read for the whole down-detection
        window, which IS the degraded-read tail."""
        tasks = [asyncio.ensure_future(self.shards[i].get_attr(oid,
                                                               name))
                 for i in range(self.n)]
        if hedged and self.hedge_timeout:
            await asyncio.wait(tasks, timeout=self.hedge_timeout)
            pending = [t for t in tasks if not t.done()]
            if pending and len(tasks) - len(pending) >= self.k:
                self.perf.inc("hedge_meta")
                for t in pending:
                    t.cancel()
                await asyncio.gather(*tasks, return_exceptions=True)
                return [
                    (ShardReadError(f"shard {i}: hedged (meta)")
                     if t.cancelled()
                     else t.exception() if t.exception() is not None
                     else t.result())
                    for i, t in enumerate(tasks)
                ]
        return await asyncio.gather(*tasks, return_exceptions=True)

    async def _get_attr_any(self, oid: str, name: str) -> bytes | None:
        """Read an attr from any shard that still has the object. Returns
        None only when at least one shard affirmatively reports it absent;
        if every shard errored transiently, raises — 'unreachable' must
        never be mistaken for 'does not exist' (a write would then reset
        version and skip RMW read-back)."""
        results = await self._attr_all(oid, name, hedged=True)
        errors = []
        absent = False
        for i, r in enumerate(results):
            if isinstance(r, KeyError):
                absent = True
            elif isinstance(r, BaseException):
                errors.append((i, r))
            else:
                return r
        if absent:
            return None
        raise ShardReadError(
            f"all shards unreachable reading {name} of {oid}: {errors}"
        )

    async def _read_meta(self, oid: str) -> ECObjectMeta | None:
        """Authoritative object metadata: the MAX version across all
        answering shards. Taking the first reply would let a shard that
        missed a degraded write serve a stale version as authoritative,
        inverting the stale-shard check (fresh shards would then fail
        version verification). The peering-time authoritative-version
        choice, applied per read.

        Within the interval ``meta_cache`` answers without the fan-in.
        A fan-in fills it with a version found, and with an absence only
        when no shard was unreachable: one that was might hold it."""
        cached = self.meta_cache.get(oid)
        if cached is not MISS:
            self.perf.inc("ec_meta_cache_hit")
            return cached
        self.perf.inc("ec_meta_cache_miss")
        token = self.meta_cache.begin_fill(oid)
        fill = MISS
        try:
            results = await self._attr_all(oid, VERSION_ATTR, hedged=True)
            best: ECObjectMeta | None = None
            errors = []
            absent = False
            for i, r in enumerate(results):
                if isinstance(r, KeyError):
                    absent = True
                elif isinstance(r, BaseException):
                    errors.append((i, r))
                else:
                    try:
                        d = json.loads(r)
                        meta = ECObjectMeta(int(d["size"]),
                                            int(d["version"]))
                    except (ValueError, TypeError, KeyError):
                        continue
                    if best is None or meta.version > best.version:
                        best = meta
            if best is not None:
                fill = best
                return best
            if absent:
                if not errors:
                    fill = None
                return None
            raise ShardReadError(
                f"all shards unreachable reading meta of {oid}: {errors}"
            )
        finally:
            self.meta_cache.end_fill(oid, token, fill)

    @staticmethod
    def _meta_attr(meta: ECObjectMeta) -> bytes:
        return json.dumps(
            {"size": meta.size, "version": meta.version}
        ).encode()

    async def _target_meta(self, oid: str,
                           version: int | None) -> ECObjectMeta | None:
        """Metadata at a PINNED version (any shard that matches), or the
        max-version choice when no target is given."""
        if version is None:
            return await self._read_meta(oid)
        for r in await self._attr_all(oid, VERSION_ATTR):
            if isinstance(r, BaseException):
                continue
            try:
                d = json.loads(r)
            except (ValueError, TypeError):
                continue
            if int(d.get("version", -1)) == version:
                return ECObjectMeta(int(d["size"]), version)
        raise ShardReadError(f"no shard holds {oid} at version {version}")

    # -- write -----------------------------------------------------------
    async def write(self, oid: str, data: bytes, offset: int = 0,
                    version: int | None = None,
                    reqid: str = "") -> ECObjectMeta:
        """Write ``data`` at logical ``offset`` (stripe-granular RMW)."""
        async with self.object_lock(oid), self._track_op():
            await self._heal_dirty(oid)
            # capture the cache generation BEFORE the RMW read/encode:
            # if a concurrent invalidate() lands while our (possibly
            # coalesced) encode is in flight, note_write below becomes
            # a no-op instead of resurrecting stale extents
            cache_gen = self.extent_cache.generation(oid)
            meta = await self._read_meta(oid)
            old_size = meta.size if meta else 0
            new_version = (
                version if version is not None
                else (meta.version + 1 if meta else 1)
            )
            end = offset + len(data)
            new_size = max(old_size, end)
            a_start, a_len = self.sinfo.offset_len_to_stripe_bounds(
                offset, len(data)
            )
            # RMW: the object's surviving bytes in the stripe range are
            # read back only where the new bytes leave some of them
            # standing (a fresh object, a same-size or longer
            # write_full and a whole-stripe overwrite need none)
            keep_len = (min(old_size, a_start + a_len) - a_start
                        if old_size > a_start else 0)
            rmw = keep_len > 0 and not (
                offset <= a_start and end >= a_start + keep_len)
            if keep_len > 0 and not rmw:
                self.perf.inc("ec_rmw_gather_skipped")
            existing = None
            if rmw:
                # the extent cache (ExtentCache role) serves back-to-back
                # overwrites without re-reading + decoding k shards
                existing = self.extent_cache.get(oid, a_start, keep_len)
                if existing is None:
                    existing = await self._read_logical(
                        oid, a_start, keep_len, old_size,
                        meta.version if meta else None)
            # the (B, k, C) stripe batch is built on the host; a write
            # that fills its whole stripe range is reshaped in place
            new = np.frombuffer(bytes(data), np.uint8)
            with tracing.span("ec:prep", oid=oid, tags=_SITE["stripes"]):
                if existing is None and offset == a_start \
                        and new.size == a_len:
                    buf = new
                else:
                    buf = np.zeros(a_len, np.uint8)
                    if existing is not None:
                        buf[:len(existing)] = np.frombuffer(existing,
                                                            np.uint8)
                    buf[offset - a_start:end - a_start] = new
                buf = self.sinfo.split_stripes(buf)
            shard_off = self.sinfo.logical_to_prev_chunk_offset(a_start)
            meta_attr = self._meta_attr(ECObjectMeta(new_size, new_version))
            if self.resident is not None:
                # one counted upload; the device encode and the split
                # run off the event loop: a first-time XLA compile must
                # not stall heartbeats or leases in this process.  Then
                # ONE counted download of the encoded shard streams at
                # the store-persistence boundary (writes go through)
                streams, shards = await self._coalesced_encode_write(
                    self._to_device(buf))
                self.perf.inc("ec_write_glue_fused")
                host = self._to_host(streams)
                shard_bytes = [host[i] for i in range(self.n)]
            else:
                chunks = await self._coalesced_encode(buf)
                with tracing.span("ec:prep", oid=oid, tags=_SITE["split"]):
                    shard_bytes = self.sinfo.shard_bytes(chunks)
            hattrs = await self._update_hinfo(
                oid, shard_off, shard_bytes, old_size
            )
            with tracing.span("ec:prep", oid=oid, tags=_SITE["tobytes"]):
                data_bytes = [c.tobytes() for c in shard_bytes]
            entry = (self.log_hook(oid, "modify", new_version,
                                   meta.version if meta else 0, reqid)
                     if self.log_hook else None)
            try:
                with tracing.span("osd:fanout", reqid=reqid or None,
                                  oid=oid):
                    results = await asyncio.gather(*(
                        self.shards[i].write_shard(
                            oid, shard_off, data_bytes[i],
                            {VERSION_ATTR: meta_attr,
                             HINFO_ATTR: hattrs[i]},
                            log=entry,
                        )
                        for i in range(self.n)
                    ), return_exceptions=True)
                failed = [i for i, r in enumerate(results)
                          if isinstance(r, BaseException)]
                await self._settle_write_failures(
                    "write", oid, failed,
                    lambda live: self._heal_shards(oid, live, entry),
                    entry,
                    causes={i: repr(r) for i, r in enumerate(results)
                            if isinstance(r, BaseException)},
                )
            except BaseException:
                # unsettled on-disk outcome (failure OR cancellation
                # mid-gather, when a subset of shards already hold the
                # new bytes): cached extents can no longer be trusted,
                # nor the cached version
                self.extent_cache.invalidate(oid)
                self.meta_cache.drop(oid)
                if self.resident is not None:
                    self.resident.drop_object(self.resident_ns, oid)
                raise
            self.meta_cache.put(oid, ECObjectMeta(new_size, new_version))
            if self.resident is not None:
                self._resident_install(
                    oid, shard_off, shards, new_version, old_size)
            else:
                self.extent_cache.note_write(oid, a_start,
                                             buf.tobytes(),
                                             gen=cache_gen)
            return ECObjectMeta(new_size, new_version)

    # -- device residency (DeviceShardCache integration) ------------------
    def _resident_install(self, oid: str, shard_off: int, shards,
                          version: int, old_size: int) -> None:
        """Install the write's encoded per-shard streams (``shards``, one
        device array per shard) into the resident cache (spliced over
        any prior entry), then enforce the byte budget.  The store
        already holds the same bytes: entries are copies, never the
        only one."""
        import jax.numpy as jnp

        cache = self.resident
        clen = int(shards[0].shape[0])
        old_len = self.sinfo.logical_to_next_chunk_offset(old_size)
        for i, seg in enumerate(shards):
            ent = cache.get(self.resident_ns, oid, i, count=False)
            if ent is not None and not (
                    shard_off == 0 and clen >= ent.arr.shape[0]):
                base = ent.arr
                if base.shape[0] < shard_off + clen:
                    base = jnp.concatenate([
                        base,
                        jnp.zeros(shard_off + clen - base.shape[0],
                                  jnp.uint8),
                    ])
                arr = base.at[shard_off: shard_off + clen].set(seg)
            elif ent is None and not (shard_off == 0
                                      and clen >= old_len):
                # partial write over a non-resident object: the store
                # stays authoritative; don't cache a stream we only
                # partially know
                continue
            else:
                arr = seg
            cache.put(self.resident_ns, oid, i, arr, version)
        if cache.over_high:
            cache.evict()

    def _resident_read(self, shard: int, oid: str, off: int,
                       length: int, shard_size, version):
        """Serve a shard-range read from the resident cache, or None to
        fall through to the store.  Entries serve only when the
        requested version matches (the cached stream then equals the
        store bytes, version-attr check elided); raw reads
        (version=None) go to the store so corruption checks see real
        store bytes.  Deep scrub reads VERSION-MATCHED (scrub_batch
        passes the authoritative version), so warm entries serve it
        with zero H2D traffic — the tradeoff being that a warm scrub
        verifies the device-resident copy, and at-rest store rot
        surfaces once the entry is evicted (or on a cold sweep)."""
        ent = self.resident.get(self.resident_ns, oid, shard)
        if ent is None or version is None or ent.version != version:
            return None
        arr = ent.arr
        expected = length if shard_size is None else max(
            0, min(length, shard_size - off))
        if arr.shape[0] < off + expected:
            return None
        with tracing.span("ec:prep", oid=oid, shard=shard,
                          tags=_SITE["resident_read"]):
            seg = arr[off: off + length]
            if seg.shape[0] < length:
                import jax.numpy as jnp
                seg = jnp.concatenate([
                    seg, jnp.zeros(length - seg.shape[0], jnp.uint8)])
        return seg

    def resident_stats(self) -> dict:
        """Residency cache stats for this backend's namespace plus the
        transfer counters (the `ec resident stats` asok payload)."""
        if self.resident is None:
            return {"enabled": False}
        out = {"enabled": True,
               **self.resident.stats(ns=self.resident_ns)}
        for key in ("ec_resident_h2d_bytes", "ec_resident_d2h_bytes",
                    "ec_write_glue_fused", "ec_rmw_gather_skipped",
                    "ec_write_glue_compiles"):
            out[key] = int(self.perf.value(key))
        return out

    async def _settle_write_failures(self, what: str, oid: str,
                                     failed: list[int], heal,
                                     entry=None, causes=None) -> None:
        """Resolve a mutation's shard failures. Strict (logged) mode: a
        live-shard miss is healed SYNCHRONOUSLY (``heal``, e.g. rebuild
        from the shards that did commit) so the op still acks as fully
        committed; if healing fails, ECWriteDegraded marks a retryable
        non-ack. Lenient mode keeps tolerate-and-eager-repair. Beyond m
        failures the data is unrecoverable either way."""
        if not failed:
            return
        live = [i for i in failed
                if not getattr(self.shards[i], "is_dead", False)]
        if len(failed) > self.m:
            raise ShardReadError(
                f"{what} {oid}: shards {failed} failed "
                f"(live: {live}, m={self.m}), beyond recoverability"
                + (f"; causes: {causes}" if causes else "")
            )
        if self.strict and live:
            try:
                await heal(live)
            except (ShardReadError, IOError, KeyError) as e:
                # mark the shards stale (gates later writes on healing
                # them) and keep a background repair retrying
                self._schedule_repair(oid, live, entry)
                raise ECWriteDegraded(
                    f"{what} {oid}: live shards {live} missed the "
                    f"commit and healing failed: {e}"
                ) from e
        elif live:
            # degraded write: reads stay safe (stale shards fail the
            # version check) but heal eagerly so redundancy is restored
            # without waiting for re-peering
            self._schedule_repair(oid, live, entry)

    def _schedule_repair(self, oid: str, shards: list[int],
                         entry=None) -> None:
        self._dirty.setdefault(oid, set()).update(shards)

        async def repair():
            try:
                await self._heal_shards(oid, shards, entry)
            except (ShardReadError, IOError, KeyError):
                return      # shard still down; heal-on-next-write or
                            # peering recovery takes over
            dirty = self._dirty.get(oid)
            if dirty is not None:
                dirty.difference_update(shards)
                if not dirty:
                    del self._dirty[oid]

        task = asyncio.get_running_loop().create_task(repair())
        self._repair_tasks.add(task)
        task.add_done_callback(self._repair_tasks.discard)

    async def _heal_shards(self, oid: str, shards: list[int],
                           entry=None) -> None:
        """Bring stale shards current: rebuild from survivors — or, when
        a quorum of shards affirms the object is GONE (a failed remove
        left a straggler), propagate the removal instead. ``entry``
        (when known) is appended to the healed shards' pg logs so the
        heal commits the HISTORY too: a data-healed shard with a log gap
        would undercount appliers in the EC peering filter and could get
        an acked write rewound."""
        shards = sorted(shards)
        absent = sum(
            1 for r in await self._attr_all(oid, VERSION_ATTR)
            if isinstance(r, KeyError)
        )
        if absent >= self.k:
            try:
                for i in shards:
                    try:
                        await self.shards[i].remove_shard(oid, log=entry)
                    except KeyError:
                        pass
            finally:
                # the stragglers' versions leave the fan-in's answer
                self.meta_cache.drop(oid)
            return
        await self.recover_shard(oid, shards)
        if entry is not None:
            await asyncio.gather(*(
                self.shards[i].write_shard(oid, 0, b"", {}, log=entry)
                for i in shards
            ))

    async def _heal_dirty(self, oid: str) -> None:
        """Called under the object lock before a mutation: stale shards
        from an earlier failed attempt must be rebuilt before a new
        version bump could mask them."""
        dirty = self._dirty.get(oid)
        if not dirty:
            return
        try:
            await self._heal_shards(oid, sorted(dirty))
        except (ShardReadError, IOError, KeyError) as e:
            if self.strict:
                raise ECWriteDegraded(
                    f"{oid}: stale shards {sorted(dirty)} from a prior "
                    f"failed write are unhealed: {e}"
                ) from e
            return          # lenient: the new write fails there again,
                            # keeping the shard detectably stale
        self._dirty.pop(oid, None)

    async def try_heal(self, oid: str) -> bool:
        """Settle a prior attempt's shard gaps (used by the daemon when
        a client replays a not-yet-acked op): True when the object has
        no dirty shards left."""
        async with self.object_lock(oid):
            try:
                await self._heal_dirty(oid)
            except ShardReadError:
                return False
            return oid not in self._dirty

    async def _update_hinfo(self, oid: str, shard_off: int,
                            shard_bytes: list[np.ndarray],
                            old_size: int) -> list[bytes]:
        """Cumulative shard crcs, maintained for whole-object writes and
        pure appends only; mid-object overwrites invalidate hinfo (the
        reference likewise only maintains hinfo for append-style EC writes;
        overwrite pools drop it — ECTransaction.cc hinfo handling). An
        empty blob marks 'no hinfo'."""
        hinfo: HashInfo | None = None
        if shard_off == 0:
            hinfo = HashInfo(self.n)
            with tracing.span("ec:hinfo", oid=oid):
                hinfo.append(0, [b.tobytes() for b in shard_bytes])
        elif shard_off == self.sinfo.logical_to_next_chunk_offset(old_size):
            raw = await self._get_attr_any(oid, HINFO_ATTR)
            try:
                if raw:
                    hinfo = HashInfo.from_dict(self.n, json.loads(raw))
            except ValueError:
                hinfo = None
            if hinfo is not None and hinfo.total_chunk_size == shard_off:
                with tracing.span("ec:hinfo", oid=oid):
                    hinfo.append(shard_off,
                                 [b.tobytes() for b in shard_bytes])
            else:
                hinfo = None
        blob = b"" if hinfo is None else json.dumps(hinfo.to_dict()).encode()
        return [blob] * self.n

    # -- read ------------------------------------------------------------
    async def _read_shard_range(self, shard: int, oid: str, off: int,
                                length: int,
                                shard_size: int | None = None,
                                version: int | None = None) -> np.ndarray:
        """Timing shell around :meth:`_read_shard_range_impl`: every
        completed shard read (success or failure) lands one sample in
        the ``ec_shard_read_us`` histogram — the distribution the QoS
        controller derives this OSD's adaptive hedge timeout from.
        Hedge-cancelled stragglers do NOT record: their observed
        latency is the timeout itself, and feeding it back would let
        the controller's own clamp masquerade as a measurement."""
        t0 = time.monotonic()
        try:
            result = await self._read_shard_range_impl(
                shard, oid, off, length, shard_size, version)
        except asyncio.CancelledError:
            raise
        except BaseException:
            self.perf.hinc("ec_shard_read_us",
                           (time.monotonic() - t0) * 1e6)
            raise
        self.perf.hinc("ec_shard_read_us",
                       (time.monotonic() - t0) * 1e6)
        return result

    async def _read_shard_range_impl(
            self, shard: int, oid: str, off: int, length: int,
            shard_size: int | None = None,
            version: int | None = None) -> np.ndarray:
        """Read [off, off+length) of a shard. A read shorter than the
        region the shard is KNOWN to hold (from object metadata) is a
        shard failure — truncation must trigger reconstruction, not
        zero-padded client data. When ``version`` is given, the shard's
        stored object version must match: a shard that missed a degraded
        write holds full-length but STALE bytes, and must be treated as
        failed, not served (the crc/hinfo-verify role of handle_sub_read,
        reference ECBackend.cc:1010)."""
        try:
            if fp.ACTIVE:
                await fp.fire("ec.shard_read")
                await fp.fire(f"ec.shard_read.{shard}")
            if self.resident is not None:
                hit = self._resident_read(shard, oid, off, length,
                                          shard_size, version)
                if hit is not None:
                    # served from the device-resident stream: no store
                    # round trip, no host materialization (downstream
                    # consumers convert at the client boundary only)
                    return hit
            if version is not None:
                check_version(await self.shards[shard].get_attr(
                    oid, VERSION_ATTR), version, shard)
            raw = await self.shards[shard].read_shard(oid, off, length)
        except ShardReadError:
            raise
        except Exception as e:
            raise ShardReadError(f"shard {shard}: {e}") from e
        expected = length if shard_size is None else max(
            0, min(length, shard_size - off)
        )
        if len(raw) < expected:
            raise ShardReadError(
                f"shard {shard}: short read {len(raw)} < {expected} "
                f"at offset {off} of {oid}"
            )
        if len(raw) < length:
            raw = raw + b"\0" * (length - len(raw))
        return np.frombuffer(raw, np.uint8)

    async def _read_shard_ranges(self, shard: int, oid: str, ranges,
                                 version: int | None = None) -> np.ndarray:
        """The (offset, length) ``ranges`` of one shard, concatenated, in
        ONE sub-op whose shard checks ``version`` itself (Ceph's
        ECSubRead with ``subchunks``), or from the resident stream when
        it holds that version.  Timed into ``ec_shard_read_us`` as
        :meth:`_read_shard_range` is; the bytes count into
        ``ec_subchunk_read_bytes``."""
        t0 = time.monotonic()
        want = sum(int(ln) for _, ln in ranges)
        try:
            if fp.ACTIVE:
                await fp.fire("ec.shard_read")
                await fp.fire(f"ec.shard_read.{shard}")
            raw = None
            if self.resident is not None and ranges:
                lo = min(off for off, _ in ranges)
                hi = max(off + ln for off, ln in ranges)
                seg = self._resident_read(shard, oid, lo, hi - lo, None,
                                          version)
                if seg is not None:
                    seg = self._to_host(seg)
                    raw = np.concatenate([seg[off - lo:off - lo + ln]
                                          for off, ln in ranges])
            if raw is None:
                raw = np.frombuffer(await self.shards[shard].read_shard_ranges(
                    oid, ranges, version), np.uint8)
            if len(raw) != want:
                raise ShardReadError(
                    f"shard {shard}: ranged read gave {len(raw)} B, not "
                    f"{want}, of {oid}")
        except asyncio.CancelledError:
            raise
        except ShardReadError:
            self.perf.hinc("ec_shard_read_us", (time.monotonic() - t0) * 1e6)
            raise
        except Exception as e:
            self.perf.hinc("ec_shard_read_us", (time.monotonic() - t0) * 1e6)
            raise ShardReadError(f"shard {shard}: {e}") from e
        self.perf.hinc("ec_shard_read_us", (time.monotonic() - t0) * 1e6)
        self.perf.inc("ec_subchunk_read_bytes", want)
        return raw

    async def _read_logical(self, oid: str, offset: int, length: int,
                            obj_size: int,
                            version: int | None = None,
                            reqid: str = "") -> bytes:
        """Read stripe-aligned logical range, reconstructing if needed.
        Its ``osd:fanout`` span runs from the first shard read to the
        last one a reconstruction fetches, before any decode."""
        if offset % self.sinfo.stripe_width:
            raise ValueError("offset must be stripe aligned")
        nstripes = -(-length // self.sinfo.stripe_width)
        clen = nstripes * self.sinfo.chunk_size
        coff = self.sinfo.aligned_logical_offset_to_chunk_offset(offset)
        ssize = self.sinfo.logical_to_next_chunk_offset(obj_size)

        want = list(self.data_shards)
        fanout = tracing.span("osd:fanout", reqid=reqid or None, oid=oid)
        try:
            if self.hedge_timeout:
                chunks = await self._read_chunks_hedged(
                    oid, coff, clen, ssize, version, want, fanout
                )
            else:
                results = await asyncio.gather(*(
                    self._read_shard_range(i, oid, coff, clen, ssize,
                                           version)
                    for i in want
                ), return_exceptions=True)
                missing = [s for s, r in zip(want, results)
                           if isinstance(r, BaseException)]
                if missing:
                    chunks = await self._reconstruct(
                        oid, coff, clen, missing, results, ssize,
                        version, fanout
                    )
                else:
                    chunks = dict(zip(want, results))
        finally:
            fanout.end()
        # the Objecter/client boundary: resident chunks materialize to
        # host HERE (one counted copy of the payload), not per-launch
        with tracing.span("ec:prep", oid=oid, tags=_SITE["merge"]):
            stripes = np.stack(
                [self._to_host(chunks[i]).reshape(nstripes,
                                                  self.sinfo.chunk_size)
                 for i in self.data_shards], axis=1,
            )
            flat = self.sinfo.merge_stripes(stripes)
            return flat[:length].tobytes()

    async def _read_chunks_hedged(
        self, oid: str, coff: int, clen: int, ssize: int | None,
        version: int | None, want: list[int],
        fanout=tracing.NULL_SPAN,
    ) -> dict[int, np.ndarray]:
        """Hedged shard fan-in: wait ``hedge_timeout`` for the direct
        data-shard reads; shards still pending are treated as slow and
        raced against a minimum_to_decode reconstruction from the
        surviving shards (the tail-latency hedge of degraded-read
        literature).  Bit-identical to the direct path — the race only
        decides WHERE the bytes come from, the decode math is the same
        GF(2^8) inverse the failure path uses."""
        tasks = {
            i: asyncio.create_task(
                self._read_shard_range(i, oid, coff, clen, ssize,
                                       version))
            for i in want
        }
        await asyncio.wait(tasks.values(), timeout=self.hedge_timeout)
        slow = [i for i in want if not tasks[i].done()]
        results = [
            (tasks[i].exception() if tasks[i].done()
             and tasks[i].exception() is not None
             else tasks[i].result() if tasks[i].done()
             else ShardReadError(f"shard {i}: hedged (slow)"))
            for i in want
        ]
        failed = [i for i in want
                  if tasks[i].done() and tasks[i].exception() is not None]
        if not slow:
            if failed:
                return await self._reconstruct(
                    oid, coff, clen, failed, results, ssize, version,
                    fanout)
            return {i: tasks[i].result() for i in want}
        # hedge fires: reconstruct failed+slow positions from survivors
        # while the stragglers keep running; first full answer wins
        self.perf.inc("hedge_issued")
        missing = failed + slow
        rec = asyncio.create_task(self._reconstruct(
            oid, coff, clen, missing, results, ssize, version, fanout))
        slow_all = asyncio.ensure_future(asyncio.gather(
            *(tasks[i] for i in slow), return_exceptions=True))
        pending = {rec, slow_all}
        try:
            while pending:
                done, pending = await asyncio.wait(
                    pending, return_when=asyncio.FIRST_COMPLETED)
                if rec in done and rec.exception() is None:
                    self.perf.inc("hedge_won")
                    return rec.result()
                if slow_all in done:
                    sres = slow_all.result()
                    if not failed and not any(
                            isinstance(r, BaseException) for r in sres):
                        self.perf.inc("hedge_lost")
                        return {i: tasks[i].result() for i in want}
                # a path failed (or landed unusable): wait for the other
        finally:
            rec.cancel()
            slow_all.cancel()
            for i in slow:
                tasks[i].cancel()
            # retrieve loser-side results so cancellation doesn't log
            # "exception was never retrieved" for the racing futures
            await asyncio.gather(rec, slow_all, return_exceptions=True)
        # neither path produced a clean answer on its own: re-evaluate
        # with every read that DID land (a slow-but-successful shard can
        # rescue a reconstruction that lacked survivors)
        final: list = []
        for i in want:
            t = tasks[i]
            if t.done() and not t.cancelled() and t.exception() is None:
                final.append(t.result())
            else:
                final.append(ShardReadError(f"shard {i}: unavailable"))
        missing2 = [i for i, r in zip(want, final)
                    if isinstance(r, BaseException)]
        if not missing2:
            return {i: r for i, r in zip(want, final)}
        return await self._reconstruct(
            oid, coff, clen, missing2, final, ssize, version, fanout)

    async def _reconstruct(
        self, oid: str, coff: int, clen: int,
        missing: Sequence[int], partial, shard_size: int | None = None,
        version: int | None = None, fanout=tracing.NULL_SPAN,
    ) -> dict[int, np.ndarray]:
        """minimum_to_decode-driven repair read + batched decode.
        ``partial`` is aligned with the read path's want set (the data
        shards, in logical order).  Where the codec's plan asks for
        partial sub-chunk ranges (CLAY's single-chunk repair), helpers
        the read does not hold whole are fetched by those ranges alone,
        one sub-op each, and the lost chunk is rebuilt from the d
        helpers' repair planes (:meth:`_repair_launch`); otherwise the
        chunks of the plan are fetched whole and decoded.  The read's
        ``fanout`` span ends once the last helper is fetched, before the
        rebuild; ``ec:repair`` runs from the plan to the rebuilt chunk,
        tagged with the bytes fed to the rebuild and the bytes rebuilt."""
        repair = tracing.span("ec:repair", oid=oid)
        try:
            have = {
                s: r for s, r in zip(self.data_shards, partial)
                if not isinstance(r, BaseException)
            }
            need, runs, planes = await self._fetch_helpers(
                oid, coff, clen, missing, have, shard_size, version)
            fanout.end()
            nstripes = clen // self.sinfo.chunk_size
            if runs is not None:
                lost = int(missing[0])
                helpers = tuple(sorted(int(s) for s in need))
                payload = (nstripes, [have.get(h, planes.get(h))
                                      for h in helpers],
                           tuple(h in have for h in helpers))
                key = ("rep", lost, helpers, runs)
                self.perf.inc("ec_clay_subchunk_repairs")
                if self.coalescer is None:
                    out = await self._repair_launch(key, [payload])
                else:
                    out = [await self.coalescer.submit(key, payload,
                                                       nstripes)]
                out = {lost: out[0]}
                fed = len(helpers) * nstripes * sum(n for _, n in runs) \
                    * (self.sinfo.chunk_size // self.ec.get_sub_chunk_count())
                path = "subchunk"
            else:
                batched = {
                    s: arr.reshape(nstripes, self.sinfo.chunk_size)
                    for s, arr in have.items()
                }
                layered = self.ec.get_sub_chunk_count() > 1
                if layered:
                    self.perf.inc("ec_clay_layered_decodes")
                out = await self._coalesced_decode(batched, list(missing))
                fed = len(batched) * clen
                path = "layered" if layered else "decode"
            chunks = {}
            for i in self.data_shards:
                if i in have:
                    chunks[i] = have[i]
                elif self._is_device(out[i]):
                    chunks[i] = out[i].reshape(-1)
                else:
                    chunks[i] = np.ascontiguousarray(out[i]).reshape(-1)
            repair.set_metadata(path=path, helpers=len(need),
                                helper_bytes=fed,
                                rebuilt_bytes=clen * len(missing))
            return chunks
        finally:
            repair.end()

    async def _fetch_helpers(self, oid, coff, clen, missing, have: dict,
                             shard_size, version):
        """Fetch what the plan for ``missing`` reads beyond ``have`` (the
        whole chunks the read holds; filled in place).  Returns the plan
        (``minimum_to_decode``'s answer), its sub-chunk runs (None: whole
        chunks) and the helpers' repair planes fetched by those runs.
        Availability is discovered, not assumed: shards beyond the
        initial read set may also be dead, so minimum_to_decode is asked
        again against the shrinking available set until a fetch round
        fully succeeds (get_min_avail_to_read_shards semantics,
        ECBackend.cc:1613)."""
        planes: dict[int, np.ndarray] = {}
        dead = set(missing)
        while True:
            avail = [i for i in range(self.n) if i not in dead]
            try:
                need = minimum_to_decode_cached(
                    self.ec, list(missing), avail, perf=self.perf)
            except IOError:
                raise ShardReadError(
                    f"cannot reconstruct {oid}: "
                    f"only {sorted(set(have))} available"
                ) from None
            runs = self._subchunk_runs(need)
            held = have if runs is None else {**have, **planes}
            extra = [s for s in need if s not in held]
            if not extra:
                return need, runs, planes
            if runs is None:
                fetched = await asyncio.gather(*(
                    self._read_shard_range(s, oid, coff, clen, shard_size,
                                           version)
                    for s in extra
                ), return_exceptions=True)
            else:
                ranges = self._subchunk_ranges(runs, coff, clen)
                fetched = await asyncio.gather(*(
                    self._read_shard_ranges(s, oid, ranges, version)
                    for s in extra
                ), return_exceptions=True)
            newly_dead = False
            for s, r in zip(extra, fetched):
                if isinstance(r, BaseException):
                    dead.add(s)
                    newly_dead = True
                else:
                    (have if runs is None else planes)[s] = r
            if not newly_dead:
                return need, runs, planes

    def _subchunk_runs(self, need) -> tuple | None:
        """The sub-chunk (offset, count) runs every helper of a plan is
        read by, when the plan reads partial chunks (CLAY's repair);
        None when it reads whole chunks."""
        if not isinstance(need, dict):
            return None
        whole = [(0, self.ec.get_sub_chunk_count())]
        runs = {tuple((int(o), int(c)) for o, c in r) for r in need.values()}
        if len(runs) != 1:
            return None
        got = next(iter(runs))
        return None if list(got) == whole else got

    def _subchunk_ranges(self, runs: tuple, coff: int,
                         clen: int) -> list[tuple[int, int]]:
        """Shard byte ranges of sub-chunk ``runs`` in every stripe of
        the chunk extent [coff, coff + clen)."""
        C = self.sinfo.chunk_size
        sc = C // self.ec.get_sub_chunk_count()
        return [(t + off * sc, cnt * sc)
                for t in range(coff, coff + clen, C) for off, cnt in runs]

    async def read(self, oid: str, offset: int = 0,
                   length: int | None = None, reqid: str = "") -> bytes:
        # as a reader: the version read and the shards read at it are
        # never half replaced by a write
        async with self.object_lock(oid, READ), self._track_op():
            meta = await self._read_meta(oid)
            if meta is None:
                raise KeyError(f"no such object {oid}")
            if length is None:
                length = meta.size - offset
            length = max(0, min(length, meta.size - offset))
            if length == 0:
                return b""
            a_start, a_len = self.sinfo.offset_len_to_stripe_bounds(
                offset, length
            )
            data = await self._read_logical(oid, a_start, a_len,
                                            meta.size, meta.version,
                                            reqid)
            rel = offset - a_start
            return data[rel: rel + length]

    # -- object metadata ops (fan-out; metadata is replicated per shard) --
    async def remove(self, oid: str, reqid: str = "") -> None:
        """Remove every shard object. A shard that lacks it is fine; IO
        failures beyond m mean the removal did not take and must raise
        (a silently-surviving shard would resurrect the object)."""
        async with self.object_lock(oid):
            # invalidate INSIDE the object lock: outside it, a write
            # already past its gather could note_write AFTER this
            # invalidate and resurrect pre-delete bytes in the cache
            self.extent_cache.invalidate(oid)
            if self.resident is not None:
                self.resident.drop_object(self.resident_ns, oid)
            meta = await self._read_meta(oid) if self.log_hook else None
            entry = (self.log_hook(oid, "delete", 0,
                                   meta.version if meta else 0, reqid)
                     if self.log_hook else None)

            async def rm(i: int):
                try:
                    await self.shards[i].remove_shard(oid, log=entry)
                except KeyError:
                    pass            # already absent on this shard

            async def heal(live):
                for i in live:
                    try:
                        await self.shards[i].remove_shard(oid,
                                                          log=entry)
                    except KeyError:
                        pass
            try:
                results = await asyncio.gather(
                    *(rm(i) for i in range(self.n)),
                    return_exceptions=True
                )
                failed = [i for i, r in enumerate(results)
                          if isinstance(r, BaseException)]
                await self._settle_write_failures("remove", oid, failed,
                                                  heal, entry)
            except BaseException:
                self.meta_cache.drop(oid)
                raise
            self.meta_cache.put(oid, None)
            self._dirty.pop(oid, None)  # nothing left to be stale about

    async def set_attr(self, oid: str, name: str, value: bytes,
                       reqid: str = "") -> None:
        """Set one attr on all shards (zero-length data write carries it);
        tolerates up to m dead shards like a degraded data write. The
        per-object version is bumped and rewritten with the attr so a
        shard that missed the write is distinguishable from a current
        one (stale-version detection, like the degraded data path)."""
        async with self.object_lock(oid):
            await self._heal_dirty(oid)
            meta = await self._read_meta(oid)
            new_meta = ECObjectMeta(
                meta.size if meta else 0,
                meta.version + 1 if meta else 1,
            )
            attrs = {name: bytes(value),
                     VERSION_ATTR: self._meta_attr(new_meta)}
            entry = (self.log_hook(oid, "modify", new_meta.version,
                                   meta.version if meta else 0, reqid)
                     if self.log_hook else None)
            try:
                results = await asyncio.gather(*(
                    self.shards[i].write_shard(oid, 0, b"", attrs,
                                               log=entry)
                    for i in range(self.n)
                ), return_exceptions=True)
                failed = [i for i, r in enumerate(results)
                          if isinstance(r, BaseException)]
                await self._settle_write_failures(
                    "set_attr", oid, failed,
                    lambda live: self._heal_shards(oid, live, entry),
                    entry,
                )
            except BaseException:
                self.meta_cache.drop(oid)
                raise
            self.meta_cache.put(oid, new_meta)
            if self.resident is not None:
                # shard data is untouched; restamp resident entries so
                # version-matched reads keep hitting
                self.resident.bump_version(self.resident_ns, oid,
                                           new_meta.version)

    async def get_attrs(self, oid: str) -> dict[str, bytes]:
        """All attrs, from the answering shard with the HIGHEST stored
        version: attr mutations bump the object version (set_attr), so
        the max-version shard is the one guaranteed current — the first
        responder may have missed a degraded attr write."""
        async def fetch(i: int):
            getattrs = getattr(self.shards[i], "get_attrs", None)
            if getattrs is None:
                raise ShardReadError(f"shard {i}: no get_attrs")
            return dict(await getattrs(oid))

        results = await asyncio.gather(
            *(fetch(i) for i in range(self.n)), return_exceptions=True
        )
        best: dict[str, bytes] | None = None
        best_version = -1
        errors = []
        absent = False
        for i, r in enumerate(results):
            if isinstance(r, KeyError):
                absent = True
            elif isinstance(r, BaseException):
                errors.append((i, r))
            else:
                try:
                    version = int(json.loads(r[VERSION_ATTR])["version"])
                except (KeyError, ValueError, TypeError):
                    version = 0
                if version > best_version:
                    best, best_version = r, version
        if best is not None:
            return best
        if absent:
            return {}
        raise ShardReadError(f"get_attrs {oid}: {errors}")

    # -- recovery --------------------------------------------------------
    async def recover_shard(self, oid: str, lost: Sequence[int],
                            version: int | None = None,
                            stray_read=None,
                            stray_positions: Sequence[int] = ()) -> int:
        async with self._track_op():
            return await self._recover_shard_impl(
                oid, lost, version=version, stray_read=stray_read,
                stray_positions=stray_positions,
            )

    async def _recover_shard_impl(
            self, oid: str, lost: Sequence[int],
            version: int | None = None, stray_read=None,
            stray_positions: Sequence[int] = ()) -> int:
        """Rebuild lost shard objects from survivors (RecoveryOp).
        Source shards are version-verified so a stale survivor (missed
        degraded write) counts as lost, not as a rebuild source.
        ``version`` pins the target explicitly (log-driven recovery,
        incl. REWIND: rebuilding shards that applied a dropped entry
        back to the prior version — their own attrs advertise the
        dropped version, so the max-version guess must not be used).
        ``stray_read(pos, oid, version, shard_len)``: optional extra
        source — when an acting shard cannot serve a position, a
        former holder (stray after a partial remap) is read instead,
        so decode can MIX acting and stray shards (the reference pulls
        from any peer in the missing-loc set, MissingLoc)."""
        try:
            meta = await self._target_meta(oid, version)
        except ShardReadError:
            meta = None
        # probe results (pos -> (arr, attrs, version)) are reused by
        # read_source below — a stray shard is fetched once, not twice
        probe: dict[int, tuple[np.ndarray, dict, int]] = {}
        if meta is None and stray_read is not None:
            # no acting shard even knows the object (total remap):
            # probe the strays for its metadata before deciding.  With
            # no pinned version the MAX across strays wins (the
            # _read_meta rule — a stale stray that missed a degraded
            # write must not pin recovery to its dropped version).
            best = None
            for pos in stray_positions:
                try:
                    arr, attrs = await stray_read(pos, oid, version,
                                                  None)
                    d = json.loads(attrs[VERSION_ATTR])
                    cand = ECObjectMeta(int(d["size"]),
                                        int(d["version"]))
                except (ShardReadError, KeyError, ValueError,
                        TypeError):
                    continue
                probe[pos] = (arr, attrs, cand.version)
                if version is not None:
                    best = cand
                    break
                if best is None or cand.version > best.version:
                    best = cand
            meta = best
        if meta is None:
            raise KeyError(f"no such object {oid}")
        shard_len = self.sinfo.logical_to_next_chunk_offset(meta.size)

        # Positions a stray might serve: still rebuild targets (in
        # ``lost``) but USABLE as decode sources — the partial-overlap
        # case where acting + strays together reach k even though
        # neither alone does.
        stray_avail: set[int] = set(stray_positions or ()) \
            if stray_read is not None else set()

        stray_attrs: dict[int, dict] = {}    # positions served by strays

        async def read_source(s: int) -> np.ndarray:
            try:
                return await self._read_shard_range(
                    s, oid, 0, shard_len, shard_len, meta.version
                )
            except ShardReadError:
                if stray_read is None or s not in stray_avail:
                    raise
                cached = probe.get(s)
                if cached is not None and cached[2] == meta.version \
                        and len(cached[0]) >= shard_len:
                    arr, attrs = cached[0][:shard_len], cached[1]
                else:
                    arr, attrs = await stray_read(
                        s, oid, meta.version, shard_len
                    )
                stray_attrs[s] = attrs
                return arr

        lost = list(lost)
        while True:
            avail = [i for i in range(self.n)
                     if i not in lost or i in stray_avail]
            # memoized: a 1000-object drain with one failure pattern
            # derives the read set once (retry loops shrink avail,
            # which is a new cache key — the fallback stays intact)
            need = minimum_to_decode_cached(
                self.ec, lost, avail, perf=self.perf)
            reads = await asyncio.gather(*(
                read_source(s) for s in need
            ), return_exceptions=True)
            newly_lost = [
                s for s, r in zip(need, reads)
                if isinstance(r, BaseException)
            ]
            if not newly_lost:
                break
            for s in newly_lost:
                stray_avail.discard(s)
                if s not in lost:
                    lost.append(s)
        nstripes = shard_len // self.sinfo.chunk_size
        batched = {
            s: arr.reshape(nstripes, self.sinfo.chunk_size)
            for s, arr in zip(need, reads)
        }
        out = await self._coalesced_decode(batched, lost)
        # copy the FULL attr set from a version-verified survivor — a
        # rebuilt shard missing user xattrs would serve stale attr
        # reads.  Prefer an acting source; when every source was a
        # stray (total remap), its verified attr set serves the role.
        acting_ok = [s for s in need if s not in stray_attrs]
        if acting_ok:
            good = acting_ok[0]
            getattrs = getattr(self.shards[good], "get_attrs", None)
            if getattrs is not None:
                attrs = dict(await getattrs(oid))
            else:
                attrs = {
                    VERSION_ATTR: await self.shards[good].get_attr(
                        oid, VERSION_ATTR
                    ),
                    HINFO_ATTR: await self.shards[good].get_attr(
                        oid, HINFO_ATTR
                    ),
                }
        else:
            attrs = dict(stray_attrs[next(iter(need))])
        await asyncio.gather(*(
            self.shards[s].write_shard(
                oid, 0,
                np.ascontiguousarray(self._to_host(out[s])).tobytes(),
                attrs,
            )
            for s in lost
        ))
        if self.resident is not None:
            # rebuilt store content supersedes whatever the cache held
            # for these positions (an entry would be identical, but
            # dropping is unconditionally safe)
            for s in lost:
                self.resident.drop(self.resident_ns, oid, s)
        # bytes actually written (lost may have GROWN on source-read
        # failures): the caller's motion accounting must reconcile
        # against placement predictions, so guessing from the request
        # is not good enough
        return shard_len * len(lost)

    # -- batched recovery (the repair engine's data path) -----------------
    async def recover_batch(self, names: Sequence[str],
                            lost: Sequence[int],
                            versions: Mapping[str, int] | None = None
                            ) -> dict:
        """Rebuild ``lost`` shard positions of MANY objects through
        shared decode launches (the RepairScheduler's entry point).

        All objects must share the failure pattern ``lost``; the repair
        strategy — plain-RS read set, LRC group-local reads, or CLAY
        helper sub-chunk plane reads — is planned once per (codec,
        lost, avail) and applied batch-wide.  Objects the batch cannot
        serve (metadata/read/write failure, zero length) are simply NOT
        in the returned ``recovered`` list; the caller demotes them to
        the per-object ``recover_shard`` path, which retries, shrinks
        read sets, and pulls stray sources.  Returns::

            {"recovered": [names...], "strategy": "rs|lrc|clay",
             "batches": <decode launches issued>}
        """
        async with contextlib.AsyncExitStack() as held:
            # a writer of every object in the batch, taken in name order
            for oid in sorted(set(names)):
                await held.enter_async_context(self.object_lock(oid))
            async with self._track_op():
                return await self._recover_batch_impl(
                    list(names), list(lost), dict(versions or {}))

    async def _recover_batch_impl(self, names: list, lost: list,
                                  versions: dict) -> dict:
        lost = sorted({int(s) for s in lost})
        avail = [i for i in range(self.n) if i not in lost]
        # strategy selection + memoized plan: IOError (loss beyond
        # repair) propagates — the whole batch demotes
        plan = plan_repair(self.ec, lost, avail, perf=self.perf)
        metas: dict[str, ECObjectMeta] = {}
        by_len: dict[int, list[str]] = {}
        for name in names:
            try:
                meta = await self._target_meta(
                    name, versions.get(name) or None)
            except ShardReadError:
                meta = None
            if meta is None or meta.size <= 0:
                continue          # demote: classic path probes strays
            metas[name] = meta
            by_len.setdefault(
                self.sinfo.logical_to_next_chunk_offset(meta.size), []
            ).append(name)
        recovered: list[str] = []
        batches = 0
        rebuilt_bytes = 0
        for shard_len, group in sorted(by_len.items()):
            done = await self._repair_group(
                group, lost, plan, shard_len, metas)
            recovered.extend(done)
            if done:
                batches += 1
                rebuilt_bytes += shard_len * len(lost) * len(done)
        return {"recovered": recovered, "strategy": plan.strategy,
                "batches": batches, "bytes": rebuilt_bytes}

    async def _repair_group(self, group: list, lost: list,
                            plan: RepairPlan, shard_len: int,
                            metas: dict) -> list:
        """One uniform-shard-length batch: bulk survivor fetch, ONE
        decode launch, rebuilt-shard fan-out.  Returns the names that
        completed end to end."""
        import contextlib

        C = self.sinfo.chunk_size
        nstripes = shard_len // C
        read_set = list(plan.read_set)
        span = (self.tracer.span(
            "osd:ec:repair_batch", current_span(),
            tags={"objects": len(group), "strategy": plan.strategy,
                  "lost": ",".join(str(s) for s in lost),
                  "shard_len": shard_len},
        ) if self.tracer is not None else contextlib.nullcontext())
        with span:
            if plan.strategy == "clay":
                ok, payload = await self._repair_fetch_clay(
                    group, plan, shard_len, nstripes, metas)
            else:
                ok, payload = await self._repair_fetch_whole(
                    group, read_set, shard_len, nstripes, metas)
            if not ok:
                return []
            per_obj_read = (
                len(read_set) * shard_len if plan.strategy != "clay"
                else len(read_set) * nstripes
                * len(plan.planes) * (C // plan.sub_chunk_no))
            whole = self.k * shard_len
            self.perf.inc("ec_repair_read_bytes",
                          per_obj_read * len(ok))
            self.perf.inc("ec_repair_read_bytes_saved",
                          max(0, whole - per_obj_read) * len(ok))
            if plan.strategy == "rs":
                out = ("rs", self._repair_batched_rs(
                    ok, payload, read_set, nstripes))
            elif plan.strategy == "lrc":
                out = await self._repair_decode_lrc(
                    ok, payload, plan, nstripes)
            else:
                out = await self._repair_decode_clay(
                    ok, payload, plan, nstripes)
            self.perf.inc("ec_repair_batches")
            done = await self._repair_writeout(
                ok, lost, read_set, out, shard_len, nstripes)
            self.perf.inc("ec_repair_objects", len(done))
            self.perf.inc("ec_repair_rebuild_bytes",
                          shard_len * len(lost) * len(done))
            return done

    async def _repair_fetch_whole(self, group, read_set, shard_len,
                                  nstripes, metas):
        """Vectored survivor pull, whole shards (rs/lrc strategies):
        every (object, survivor) read runs concurrently; an object with
        any failed read drops out of the batch (demoted).  With the
        device-resident cache on, fetched streams install in one
        vectored pass and the decode consumes the SAME device arrays —
        zero re-upload into the launch."""
        async def read_obj(oid):
            reads = await asyncio.gather(*(
                self._read_shard_range(s, oid, 0, shard_len, shard_len,
                                       metas[oid].version)
                for s in read_set
            ), return_exceptions=True)
            if any(isinstance(r, BaseException) for r in reads):
                return None
            return reads

        per_obj = await asyncio.gather(*(read_obj(o) for o in group))
        ok = [o for o, r in zip(group, per_obj) if r is not None]
        payload = {o: r for o, r in zip(group, per_obj)
                   if r is not None}
        if payload and self.resident is not None:
            entries = []
            for oid, reads in payload.items():
                devs = [self._to_device(r) for r in reads]
                payload[oid] = devs
                entries.extend(
                    (oid, s, d, metas[oid].version)
                    for s, d in zip(read_set, devs))
            self.resident.install_batch(self.resident_ns, entries)
        return ok, payload

    async def _repair_fetch_clay(self, group, plan, shard_len,
                                 nstripes, metas):
        """Vectored helper sub-chunk pull (clay strategy): each helper
        contributes only its repair planes — 1/q of its bytes — in ONE
        ranged sub-op per object, its version checked in the same
        sub-op (consecutive planes coalesce into one range)."""
        from ceph_tpu.parallel.clay_sharding import clay_plane_ranges

        C = self.sinfo.chunk_size
        sc = C // plan.sub_chunk_no
        sorted_planes = sorted(plan.planes)
        runs = clay_plane_ranges(sorted_planes, sc)
        ranges = [(t * C + off, ln) for t in range(nstripes)
                  for off, ln in runs]
        # ranged reads arrive in ascending-plane order; reindex into
        # the operator's plane order (R's input layout)
        order = [sorted_planes.index(p) for p in plan.planes]

        async def read_helper(oid, h):
            arr = self._to_host(await self._read_shard_ranges(
                h, oid, ranges, metas[oid].version))
            return arr.reshape(nstripes, len(sorted_planes), sc)[:, order]

        async def read_obj(oid):
            blocks = await asyncio.gather(*(
                read_helper(oid, h) for h in plan.read_set
            ), return_exceptions=True)
            if any(isinstance(b, BaseException) for b in blocks):
                return None
            # (nstripes, d, P, sc) -> (nstripes, d*P, sc): the helper-
            # major stacking clay_repair_operator probed R against
            flat = np.stack(blocks, axis=1)
            return flat.reshape(nstripes, -1, sc)

        per_obj = await asyncio.gather(*(read_obj(o) for o in group))
        ok = [o for o, r in zip(group, per_obj) if r is not None]
        return ok, {o: r for o, r in zip(group, per_obj)
                    if r is not None}

    def _repair_batched_rs(self, ok, payload, read_set, nstripes):
        """Assemble the rs strategy's batched decode input: every
        object's stripes concatenate along the batch axis, keyed by
        survivor shard id.  The decode itself goes through
        ``_coalesced_decode`` (in writeout), so the launch may merge
        with other in-flight groups in the CoalescedLauncher /
        MeshCoalescer window — the cross-PG coalescing leg."""
        C = self.sinfo.chunk_size
        any_dev = any(self._is_device(c)
                      for oid in ok for c in payload[oid])
        if any_dev:
            import jax.numpy as jnp
            return {s: jnp.concatenate(
                [self._to_device(payload[oid][j]).reshape(nstripes, C)
                 for oid in ok], axis=0)
                for j, s in enumerate(read_set)}
        return {s: np.concatenate(
            [payload[oid][j].reshape(nstripes, C) for oid in ok],
            axis=0)
            for j, s in enumerate(read_set)}

    async def _repair_decode_lrc(self, ok, payload, plan, nstripes):
        """LRC group-local decode: one (1, L) GF(2^8) apply recovers
        every stripe of every object in the batch."""
        from ceph_tpu.parallel.lrc_sharding import \
            batched_lrc_group_repair

        C = self.sinfo.chunk_size
        stacked = np.concatenate([
            np.stack([self._to_host(a).reshape(nstripes, C)
                      for a in payload[oid]], axis=1)
            for oid in ok
        ], axis=0)                            # (b, L, C)
        self.perf.inc("ec_device_launches")
        self.perf.inc("ec_launch_bytes", stacked.nbytes)
        self.perf.inc("ec_resident_h2d_bytes", stacked.nbytes)
        t0 = time.perf_counter()
        rec = await self._launch(
            batched_lrc_group_repair, self.ec, plan.matrix, stacked)
        dt_us = (time.perf_counter() - t0) * 1e6
        self.perf.hinc("ec_decode_launch_us", dt_us)
        self.profiler.record(f"{self.codec_sig}:dec", dt_us,
                             stripes=stacked.shape[0],
                             hbm_bytes=stacked.nbytes)
        self.perf.inc("ec_resident_d2h_bytes", rec.nbytes)
        return rec

    async def _repair_decode_clay(self, ok, payload, plan, nstripes):
        """CLAY plane decode: one (sub, d*P) GF(2^8) apply over the
        gathered repair planes recovers the whole batch."""
        from ceph_tpu.parallel.clay_sharding import \
            batched_clay_plane_repair

        flat = np.concatenate([payload[oid] for oid in ok], axis=0)
        self.perf.inc("ec_device_launches")
        self.perf.inc("ec_launch_bytes", flat.nbytes)
        self.perf.inc("ec_resident_h2d_bytes", flat.nbytes)
        t0 = time.perf_counter()
        rec = await self._launch(
            batched_clay_plane_repair, self.ec, plan.matrix, flat)
        dt_us = (time.perf_counter() - t0) * 1e6
        self.perf.hinc("ec_decode_launch_us", dt_us)
        self.profiler.record(f"{self.codec_sig}:dec", dt_us,
                             stripes=flat.shape[0],
                             hbm_bytes=flat.nbytes)
        self.perf.inc("ec_resident_d2h_bytes", rec.nbytes)
        return rec

    async def _repair_writeout(self, ok, lost, read_set, out,
                               shard_len, nstripes):
        """Fan the rebuilt shards out, per object: full attr set copied
        from a version-verified survivor (rebuilt shards missing user
        xattrs would serve stale attr reads), then write_shard to every
        lost position and drop superseded resident entries."""
        decoded = out
        if isinstance(out, tuple):      # rs path: decode HERE so the
            _, batched = out            # strategy paths share writeout
            decoded = await self._coalesced_decode(batched, lost)
        done: list = []

        async def finish(idx, oid):
            try:
                good = read_set[0]
                getattrs = getattr(self.shards[good], "get_attrs",
                                   None)
                if getattrs is not None:
                    attrs = dict(await getattrs(oid))
                else:
                    attrs = {
                        VERSION_ATTR: await self.shards[good].get_attr(
                            oid, VERSION_ATTR),
                        HINFO_ATTR: await self.shards[good].get_attr(
                            oid, HINFO_ATTR),
                    }
                lo, hi = idx * nstripes, (idx + 1) * nstripes

                def shard_bytes(w):
                    if isinstance(decoded, dict):
                        sl = decoded[w][lo:hi]
                    else:
                        sl = decoded[lo:hi]   # single-loss (b, C)
                    return np.ascontiguousarray(
                        self._to_host(sl)).tobytes()

                await asyncio.gather(*(
                    self.shards[s].write_shard(
                        oid, 0, shard_bytes(s), attrs)
                    for s in lost
                ))
            except (ShardReadError, IOError, KeyError):
                return
            if self.resident is not None:
                for s in lost:
                    self.resident.drop(self.resident_ns, oid, s)
            done.append(oid)

        await asyncio.gather(*(
            finish(i, oid) for i, oid in enumerate(ok)))
        return done

    # -- scrub -----------------------------------------------------------
    async def scrub(self, oid: str) -> dict:
        async with self._track_op():
            return await self._scrub_impl(oid)

    async def _scrub_impl(self, oid: str) -> dict:
        """Deep scrub: recompute parity from data shards on device and
        compare against stored parity + hinfo crcs. Returns a report."""
        meta = await self._read_meta(oid)
        if meta is None:
            raise KeyError(f"no such object {oid}")
        shard_len = self.sinfo.logical_to_next_chunk_offset(meta.size)
        reads = await asyncio.gather(*(
            self._read_shard_range(i, oid, 0, shard_len, shard_len)
            for i in range(self.n)
        ), return_exceptions=True)
        # an unreadable shard is convicted as MISSING, zero-filled to
        # keep the math rectangular (same contract as scrub_batch:
        # parity/crc verdicts are void, repair rebuilds, the next
        # sweep verifies)
        read_missing = {i for i, r in enumerate(reads)
                        if isinstance(r, BaseException)}
        # raw (version=None) reads come from the store, as host arrays
        reads = [np.zeros(shard_len, np.uint8)
                 if isinstance(r, BaseException) else r
                 for r in reads]
        nstripes = shard_len // self.sinfo.chunk_size
        stripes = np.stack(
            [reads[i].reshape(nstripes, self.sinfo.chunk_size)
             for i in self.data_shards], axis=1,
        )
        recomputed = await self._coalesced_encode(stripes)
        self.perf.inc("ec_scrub_launches")
        inconsistent = []
        for i in range(self.n):
            if i in self.data_shards:
                continue        # parity positions only (mapped layouts
                                # interleave them between data groups)
            stored = reads[i].reshape(nstripes, self.sinfo.chunk_size)
            if not np.array_equal(recomputed[:, i], stored):
                inconsistent.append(i)
        stale, missing = await self._scrub_shard_versions(
            oid, meta.version)
        miss = sorted(read_missing | set(missing))
        if miss:
            self.perf.inc("ec_scrub_objects")
            self.perf.inc("ec_scrub_bytes", shard_len * self.n)
            return self._scrub_report(oid, meta.version, [], [],
                                      stale, miss, False)
        crc_mismatch = []
        raw = await self._get_attr_any(oid, HINFO_ATTR) or b""
        if raw:  # empty blob == hinfo invalidated by overwrite
            hinfo = HashInfo.from_dict(self.n, json.loads(raw))
            for i in range(self.n):
                # slice the array view first, THEN convert: one copy of
                # the crc'd prefix instead of materializing the whole
                # shard stream and slicing the bytes
                shard_view = reads[i][: hinfo.total_chunk_size].tobytes()
                if crc32c(0xFFFFFFFF, shard_view) != \
                        hinfo.get_chunk_hash(i):
                    crc_mismatch.append(i)
        self.perf.inc("ec_scrub_objects")
        self.perf.inc("ec_scrub_bytes", shard_len * self.n)
        return self._scrub_report(oid, meta.version, inconsistent,
                                  crc_mismatch, stale, missing,
                                  bool(raw))

    async def _scrub_shard_versions(
            self, oid: str, version: int) -> tuple[list[int], list[int]]:
        """Per-shard version audit: (stale, missing).

        A shard that answers with a DIFFERENT version (or unparseable
        metadata) is STALE — it missed a degraded write and holds old
        bytes.  A shard that cannot answer at all (object/attr absent,
        shard unreachable) is MISSING — there is nothing there to be
        stale.  The two used to be conflated into 'stale', which
        misattributed wholesale shard loss as a version skew."""
        stale: list[int] = []
        missing: list[int] = []
        for i in range(self.n):
            try:
                raw_meta = await self.shards[i].get_attr(
                    oid, VERSION_ATTR)
            except Exception:                  # noqa: BLE001
                missing.append(i)
                continue
            try:
                if int(json.loads(raw_meta)["version"]) != version:
                    stale.append(i)
            except (ValueError, TypeError, KeyError):
                stale.append(i)
        return stale, missing

    def _scrub_report(self, oid: str, version: int,
                      inconsistent: list[int], crc_mismatch: list[int],
                      stale: list[int], missing: list[int],
                      have_hinfo: bool) -> dict:
        return {
            "object": oid,
            "version": version,
            "parity_inconsistent": inconsistent,
            "crc_mismatch": crc_mismatch,
            "stale_version": stale,
            # shards with nothing to verify at all — routed to repair,
            # never reported as 'stale' (satellite of ISSUE 17)
            "missing_shards": missing,
            # whether per-shard crc attribution was available: without
            # it a parity mismatch cannot name the rotten shard
            "hinfo": have_hinfo,
            "clean": not inconsistent and not crc_mismatch
            and not stale and not missing,
        }

    # -- batched scrub (the ScrubEngine data path) ------------------------
    async def scrub_batch(self, names: Sequence[str]) -> dict:
        """Deep-scrub a whole batch of objects in coalesced launches.

        Objects group by shard-stream length (same bucketing as
        recover_batch); each group re-encodes in ONE coalesced device
        launch and verifies parity + per-shard CRC32C in ONE fused
        verify launch (ec/checksum.py) — the host sees per-object
        verdicts, never the shard bytes.  Returns ``{"reports": {name:
        report | None}, "groups": int}`` with reports in the exact
        :meth:`scrub` shape (None: object vanished between listing and
        scrub)."""
        async with self._track_op():
            return await self._scrub_batch_impl(list(names))

    async def _scrub_batch_impl(self, names: list[str]) -> dict:
        reports: dict[str, dict | None] = {}
        metas: dict[str, ECObjectMeta] = {}
        for oid in names:
            meta = await self._read_meta(oid)
            if meta is None:
                reports[oid] = None
                continue
            metas[oid] = meta
        by_len: dict[int, list[str]] = {}
        for oid, meta in metas.items():
            by_len.setdefault(
                self.sinfo.logical_to_next_chunk_offset(meta.size), []
            ).append(oid)
        groups = 0
        for shard_len, group in sorted(by_len.items()):
            if shard_len == 0:
                for oid in group:       # zero-length: nothing to rot
                    reports[oid] = self._scrub_report(
                        oid, metas[oid].version, [], [], [], [], False)
                continue
            await self._scrub_group(sorted(group), shard_len, metas,
                                    reports)
            groups += 1
        return {"reports": reports, "groups": groups}

    async def _scrub_group(self, group: list[str], shard_len: int,
                           metas: dict, reports: dict) -> None:
        """Verify one equal-shard-length group in two device launches:
        a coalesced re-encode of every object's data shards, then the
        fused parity-compare + CRC contraction over the stored
        streams."""
        chunk = self.sinfo.chunk_size
        nstripes = shard_len // chunk
        B, n, k = len(group), self.n, len(self.data_shards)
        missing: dict[str, set[int]] = {oid: set() for oid in group}

        async def fetch(oid: str, i: int):
            # resident first, version-matched: a device-resident entry
            # at the object's authoritative version serves the
            # scrub read with zero H2D traffic (the warm-scrub path)
            if self.resident is not None:
                hit = self._resident_read(
                    i, oid, 0, shard_len, shard_len, metas[oid].version)
                if hit is not None:
                    return hit
            return await self._read_shard_range(
                i, oid, 0, shard_len, shard_len)

        rows: list[list] = []
        for oid in group:
            reads = await asyncio.gather(
                *(fetch(oid, i) for i in range(n)),
                return_exceptions=True)
            row = []
            for i, r in enumerate(reads):
                if isinstance(r, BaseException):
                    # unreadable shard: convicted as missing below;
                    # zero-fill keeps the batch rectangular (its own
                    # parity verdict is void, see report assembly)
                    missing[oid].add(i)
                    row.append(np.zeros(shard_len, np.uint8))
                else:
                    row.append(r)
            rows.append(row)
        if self.resident is not None:
            import jax.numpy as jnp
            rows = [[self._to_device(a) for a in row] for row in rows]
            stored = jnp.stack([jnp.stack(row) for row in rows])
        else:
            stored = np.stack([
                np.stack([np.asarray(a, np.uint8) for a in row])
                for row in rows
            ])
        sd = stored[:, list(self.data_shards), :]
        stripes = sd.reshape(B, k, nstripes, chunk) \
                    .transpose(0, 2, 1, 3).reshape(B * nstripes, k, chunk)
        recomputed = await self._coalesced_encode(stripes)
        self.perf.inc("ec_scrub_launches")
        rec = recomputed.reshape(B, nstripes, n, chunk) \
                        .transpose(0, 2, 1, 3).reshape(B, n, shard_len)
        if ec_checksum.supported_len(shard_len):
            eq, crcs = ec_checksum.verify_batch(rec, stored)
        else:
            eq = ec_checksum.parity_only_batch(rec, stored)
            crcs = None
        self.perf.inc("ec_scrub_launches")
        hraws = await asyncio.gather(
            *(self._get_attr_any(oid, HINFO_ATTR) for oid in group),
            return_exceptions=True)
        for b, oid in enumerate(group):
            stale, vmissing = await self._scrub_shard_versions(
                oid, metas[oid].version)
            miss = sorted(missing[oid] | set(vmissing))
            if miss:
                # with unreadable shards the re-encode ran over
                # zero-fill — parity/crc verdicts for this object are
                # void; repair rebuilds the missing shards and the
                # next sweep verifies the result
                reports[oid] = self._scrub_report(
                    oid, metas[oid].version, [], [], stale, miss,
                    False)
                continue
            inconsistent = [
                i for i in range(n)
                if i not in self.data_shards and not bool(eq[b, i])
            ]
            raw = hraws[b]
            if isinstance(raw, BaseException) or not raw:
                raw = b""
            crc_mismatch: list[int] = []
            hinfo = None
            if raw:
                try:
                    hinfo = HashInfo.from_dict(n, json.loads(raw))
                except (ValueError, KeyError, TypeError):
                    hinfo = None
            if hinfo is not None:
                if crcs is not None \
                        and hinfo.total_chunk_size == shard_len:
                    crc_mismatch = [
                        i for i in range(n)
                        if int(crcs[b, i]) != hinfo.get_chunk_hash(i)
                    ]
                else:
                    # stream beyond the device-CRC gate, or hinfo
                    # covering a prefix only: host-oracle fallback for
                    # this object (the parity verdict stays batched)
                    for i in range(n):
                        view = self._to_host(
                            stored[b, i][: hinfo.total_chunk_size]
                        ).tobytes()
                        if crc32c(0xFFFFFFFF, view) != \
                                hinfo.get_chunk_hash(i):
                            crc_mismatch.append(i)
            reports[oid] = self._scrub_report(
                oid, metas[oid].version, inconsistent, crc_mismatch,
                stale, [], hinfo is not None)
        self.perf.inc("ec_scrub_objects", B)
        self.perf.inc("ec_scrub_batches")
        self.perf.inc("ec_scrub_bytes", B * n * shard_len)
