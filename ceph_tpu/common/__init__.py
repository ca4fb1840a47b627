"""Common runtime substrate (reference src/common, SURVEY.md §2 layer 1).

- ``config``  — typed option schema + live config proxy with observers
  (reference src/common/options.cc get_global_options :355,
  src/common/config.h:70 md_config_t, config_obs.h).
- ``perf``    — perf counters + histograms with dump/reset
  (reference src/common/perf_counters.h:154, src/perf_histogram.h).
- ``log``     — per-subsystem leveled logging with an in-memory ring buffer
  dumped on crash (reference src/common/dout.h:122-176, src/log/Log.cc).
- ``crc32c``  — Castagnoli CRC32 (native C via ctypes: hardware crc32
  with a slice-by-8 fallback, picked from the CPU's feature bits) for
  ECUtil HashInfo parity
  (reference src/common/crc32c.h).
"""

from ceph_tpu.common.config import ConfigProxy, Option  # noqa: F401
from ceph_tpu.common.perf import PerfCounters  # noqa: F401
