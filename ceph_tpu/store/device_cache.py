"""DeviceShardCache: on-chip residency tier for EC shard streams.

Device HBM is a compute/cache tier, not durability (see the package
docstring): the cache holds each object's per-shard byte streams as
1-D device uint8 arrays in kernel shard layout, so the EC backend can
feed the coalesced Pallas launches without re-uploading host bytes on
every op.  Keys are ``(ns, oid, shard)`` — ``ns`` namespaces one
shared per-daemon cache across PG backends.

Every entry is a copy of bytes the store already holds (the backend
writes through before it installs), so dropping one never loses data.
Entries are LRU-tracked with a byte budget: when usage crosses the
high watermark the owner calls :meth:`evict`, which drops LRU entries
down to the low watermark.

Counters (``ec_resident_hits/_misses/_evictions`` here; the owner
accounts ``_h2d_bytes/_d2h_bytes`` at its conversion points) mirror
into the shared :class:`PerfCounters` so the PR-5 Prometheus export
picks them up with no extra wiring.
"""

from __future__ import annotations

from collections import OrderedDict

import numpy as np

from ceph_tpu.common.perf import CounterType, PerfCounters

RESIDENT_COUNTERS = (
    "ec_resident_hits",
    "ec_resident_misses",
    "ec_resident_h2d_bytes",
    "ec_resident_d2h_bytes",
    "ec_resident_evictions",
)


def register_resident_counters(perf: PerfCounters) -> None:
    """Idempotently register the residency counter set on ``perf``."""
    for key in RESIDENT_COUNTERS:
        perf.add(key, CounterType.U64)


class _Entry:
    __slots__ = ("arr", "version", "nbytes")

    def __init__(self, arr, version):
        self.arr = arr
        self.version = int(version)
        self.nbytes = int(arr.nbytes)


class DeviceShardCache:
    """LRU byte-budgeted cache of device-resident shard streams."""

    def __init__(self, max_bytes: int = 256 << 20,
                 low_watermark: float = 0.75,
                 perf: PerfCounters | None = None,
                 sharding=None, journal=None):
        if max_bytes <= 0:
            raise ValueError(f"max_bytes must be positive, got {max_bytes}")
        self.max_bytes = int(max_bytes)
        self.low_bytes = int(max_bytes * low_watermark)
        self.perf = perf if perf is not None else PerfCounters("ec_resident")
        register_resident_counters(self.perf)
        self._entries: OrderedDict[tuple, _Entry] = OrderedDict()
        self.bytes = 0
        self.evictions = 0
        self.hits = 0
        self.misses = 0
        # mesh-aware placement (PR 7): when the host runs the mesh-
        # global EC coalescer, installed streams pre-place with the
        # launch's batch sharding so a resident read feeds a sharded
        # launch with neither a host round trip nor a gather-to-one-
        # device copy at launch time (the reshard happens ONCE, at
        # install, on device).
        self.sharding = sharding
        self.reshards = 0
        # flight recorder: the owning daemon's event journal (None for
        # standalone caches); evict() emits one watermark event per pass
        self.journal = journal

    def set_sharding(self, sharding) -> None:
        """Adopt (or drop, with None) the placement applied to
        subsequently installed device entries.  Existing entries keep
        their placement — they reshard lazily if a launch needs it."""
        self.sharding = sharding

    def _place(self, arr):
        """Re-place a device array with the cache sharding when its
        leading axis tiles evenly; host arrays and odd shapes install
        as-is (jax.device_put device->device moves never touch host)."""
        if self.sharding is None or isinstance(
                arr, (np.ndarray, bytes, bytearray, memoryview)):
            return arr
        import jax

        ndev = len(self.sharding.device_set)
        if arr.ndim >= 1 and arr.shape[0] % max(1, ndev) == 0:
            arr = jax.device_put(arr, self.sharding)
            self.reshards += 1
        return arr

    # -- lookup / install -------------------------------------------------

    def get(self, ns, oid, shard, count: bool = True) -> _Entry | None:
        """The entry for (ns, oid, shard), LRU-touched, or None.

        The caller owns version semantics; ``count=False`` skips
        the hit/miss counters for internal bookkeeping lookups.
        """
        ent = self._entries.get((ns, oid, shard))
        if ent is None:
            if count:
                self.misses += 1
                self.perf.inc("ec_resident_misses")
            return None
        self._entries.move_to_end((ns, oid, shard))
        if count:
            self.hits += 1
            self.perf.inc("ec_resident_hits")
        return ent

    def put(self, ns, oid, shard, arr, version: int) -> None:
        """Install (replacing any prior entry) the shard stream ``arr``."""
        key = (ns, oid, shard)
        old = self._entries.pop(key, None)
        if old is not None:
            self.bytes -= old.nbytes
        ent = _Entry(self._place(arr), version)
        self._entries[key] = ent
        self.bytes += ent.nbytes

    def install_batch(self, ns, entries) -> int:
        """Vectored install: ``entries`` is an iterable of
        ``(oid, shard, arr, version)`` tuples, installed in one
        call.  The repair engine's bulk survivor pull lands here — the
        fetched shard streams become resident in the same pass that
        feeds the batched decode launch, so the decode consumes the
        already-placed device arrays with zero re-upload.  Returns the
        number of entries installed."""
        count = 0
        for oid, shard, arr, version in entries:
            self.put(ns, oid, shard, arr, version)
            count += 1
        return count

    # -- invalidation -----------------------------------------------------

    def drop(self, ns, oid, shard) -> None:
        ent = self._entries.pop((ns, oid, shard), None)
        if ent is not None:
            self.bytes -= ent.nbytes

    def drop_object(self, ns, oid) -> None:
        for key in [k for k in self._entries if k[0] == ns and k[1] == oid]:
            self.bytes -= self._entries.pop(key).nbytes

    def drop_ns(self, ns) -> None:
        """Invalidate a whole namespace (PG backend rebuilt at peering)."""
        for key in [k for k in self._entries if k[0] == ns]:
            self.bytes -= self._entries.pop(key).nbytes

    def clear(self) -> None:
        self._entries.clear()
        self.bytes = 0

    def bump_version(self, ns, oid, version: int) -> None:
        """Stamp all of an object's entries with a new version (attr-only
        writes bump the object version without touching shard data)."""
        for key, ent in self._entries.items():
            if key[0] == ns and key[1] == oid:
                ent.version = int(version)

    # -- eviction ---------------------------------------------------------

    @property
    def over_high(self) -> bool:
        return self.bytes > self.max_bytes

    def evict(self, target: int | None = None) -> None:
        """Drop LRU entries until usage <= target (default: low
        watermark)."""
        if target is None:
            target = self.low_bytes
        evicted = freed = 0
        while self.bytes > target and self._entries:
            _, ent = self._entries.popitem(last=False)
            self.bytes -= ent.nbytes
            self.evictions += 1
            evicted += 1
            freed += ent.nbytes
            self.perf.inc("ec_resident_evictions")
        if evicted and self.journal is not None:
            self.journal.emit("cache.evict", evicted=evicted,
                              freed_bytes=freed, bytes=self.bytes,
                              target=int(target))

    # -- introspection ----------------------------------------------------

    def stats(self, ns=None) -> dict:
        entries = nbytes = 0
        for key, ent in self._entries.items():
            if ns is not None and key[0] != ns:
                continue
            entries += 1
            nbytes += ent.nbytes
        return {
            "entries": entries,
            "bytes": nbytes,
            "max_bytes": self.max_bytes,
            "hits": self.hits,
            "misses": self.misses,
            "evictions": self.evictions,
            "sharded": self.sharding is not None,
            "reshards": self.reshards,
        }
