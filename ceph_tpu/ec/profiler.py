"""Device-kernel profiler: per-codec-signature launch attribution.

ROADMAP item 4 (the kernel round) needs ground truth before any
optimization: WHICH kernel burns the wall time, at what achieved
HBM bandwidth, vs the roofline (arxiv 2108.02692's playbook is
unusable without per-kernel measurement).  :class:`KernelProfiler`
attributes every device launch — coalesced encode/decode, resident
decode, mesh repair, host-mesh flush — to its codec signature
(``<codec>-k<k>-m<m>:<kind>``) with:

- ``launches``: launch count,
- ``wall_us``: measured launch wall time (the SAME timer sample that
  feeds ``ec_encode_launch_us``/``ec_decode_launch_us``/
  ``ec_mesh_launch_us``, recorded at the same sites),
- ``stripes``: stripes carried,
- ``hbm_bytes``: logical bytes moved (the SAME increments that feed
  ``ec_launch_bytes``), so per-signature byte totals reconcile with
  the existing counters EXACTLY — the profiler is an attribution of
  the counters, never a second opinion;
- ``enqueue_only``: launches whose interval ends when the work is
  enqueued, not when the device has run it (a device-resident launch
  returns device arrays before the kernel finishes);
- derived ``gibps`` and, on a device in :data:`HBM_PEAK_BYTES_S`,
  ``roofline_pct`` — only for a signature with no enqueue-only
  launch: an enqueue is not a transfer rate, so such a signature
  reads "not measured".

The dump rides the OSD's perf_dump under the ``ec_kernels`` key, the
mgr persists per-signature series into the TSDB, and
``ceph-tpu top --kernels`` renders the table.

One profiler per :class:`~ceph_tpu.common.perf.PerfCounters` instance
(i.e. per daemon), resolved via :func:`profiler_for` — backends and
the host mesh launcher share the daemon's registry the same way they
share its counters.
"""

from __future__ import annotations

import threading
import weakref

_GIB = float(1 << 30)

# Published HBM bandwidth per jax ``device_kind``, bytes/s.  Source:
# Google Cloud documentation, "TPU v5e": 16 GB of HBM at 819 GB/s.
HBM_PEAK_BYTES_S = {"TPU v5 lite": 819e9}


def hbm_peak_gibps() -> float | None:
    """HBM peak of the local device in GiB/s, the roofline every
    ``roofline_pct`` divides by.  None off a TPU: there the share is
    not measured.  A TPU kind missing from the table raises."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return None
    if dev.device_kind not in HBM_PEAK_BYTES_S:
        raise KeyError(f"no published HBM peak for device_kind "
                       f"{dev.device_kind!r}; add it to HBM_PEAK_BYTES_S")
    return HBM_PEAK_BYTES_S[dev.device_kind] / _GIB


def roofline_text(pct: float | None) -> str:
    """Render a roofline share for humans ("not measured" off a TPU)."""
    return "not measured" if pct is None else f"{pct:g}%"


class KernelProfiler:
    """Bounded per-signature accumulator (signatures are a function of
    pool EC profiles — a handful per daemon, never per-op)."""

    def __init__(self):
        self.kernels: dict[str, dict] = {}
        self._lock = threading.Lock()

    def record(self, signature: str, wall_us: float,
               stripes: int = 0, hbm_bytes: int = 0,
               enqueue_only: bool = False) -> None:
        """One launch; ``enqueue_only`` when ``wall_us`` stops at the
        enqueue."""
        with self._lock:
            rec = self.kernels.get(signature)
            if rec is None:
                rec = self.kernels[signature] = {
                    "launches": 0, "wall_us": 0.0,
                    "stripes": 0, "hbm_bytes": 0, "enqueue_only": 0}
            rec["launches"] += 1
            rec["wall_us"] += float(wall_us)
            rec["stripes"] += int(stripes)
            rec["hbm_bytes"] += int(hbm_bytes)
            rec["enqueue_only"] += int(enqueue_only)

    def totals(self) -> dict:
        with self._lock:
            t = {"launches": 0, "wall_us": 0.0, "stripes": 0,
                 "hbm_bytes": 0}
            for rec in self.kernels.values():
                for k in t:
                    t[k] += rec[k]
            return t

    def dump(self, peak_gibps: float | None = None) -> dict:
        """JSON-friendly per-signature table with derived bandwidth
        (and roofline % when a peak is known), both left out for a
        signature with an enqueue-only launch."""
        out: dict[str, dict] = {}
        with self._lock:
            items = sorted((sig, dict(rec))
                           for sig, rec in self.kernels.items())
        for sig, rec in items:
            wall_s = rec["wall_us"] / 1e6
            rec["wall_us"] = round(rec["wall_us"], 1)
            if not rec["enqueue_only"]:
                gibps = (rec["hbm_bytes"] / _GIB / wall_s) \
                    if wall_s > 0 else 0.0
                rec["gibps"] = round(gibps, 3)
                if peak_gibps:
                    rec["roofline_pct"] = round(
                        100.0 * gibps / peak_gibps, 3)
            out[sig] = rec
        return out

    def reset(self) -> None:
        with self._lock:
            self.kernels = {}


# per-PerfCounters registry: every code site holding a daemon's perf
# handle reaches the daemon's ONE profiler without constructor churn
_REGISTRY: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
_REG_LOCK = threading.Lock()


def profiler_for(perf) -> KernelProfiler:
    """The profiler attached to this PerfCounters instance (created on
    first use; lifetime tied to the counters themselves)."""
    with _REG_LOCK:
        prof = _REGISTRY.get(perf)
        if prof is None:
            prof = _REGISTRY[perf] = KernelProfiler()
        return prof
