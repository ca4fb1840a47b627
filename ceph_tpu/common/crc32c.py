"""crc32c (Castagnoli) in native C: hardware crc32 with a slice-by-8
fallback, picked from the CPU's feature bits.

Loads ceph_tpu/native/libceph_tpu_native.so via ctypes, built with make
on first use from the committed sources (_SOURCES).  The library picks
its path once, when it loads (SSE4.2 or ARMv8 CRC instructions where
the CPU reports them, else the table); ``impl()`` names the pick.  A
build that fails raises; the pure-Python table loop is the reference
the tests compare against. Semantics match ceph_crc32c(seed, buf, len)
(reference src/common/crc32c.h): callers chain seeds; ECUtil HashInfo uses
the previous cumulative crc as the seed for each appended shard extent.
"""

from __future__ import annotations

import ctypes
import pathlib
import subprocess

_NATIVE_DIR = pathlib.Path(__file__).resolve().parents[1] / "native"
_SO = _NATIVE_DIR / "libceph_tpu_native.so"

# the committed sources the .so is built from (the binary is not)
_SOURCES = ("Makefile", "crc32c.c", "wal_engine.cc")

_native = None


def _stale() -> bool:
    """The .so is rebuilt when missing OR older than any source."""
    try:
        so_mtime = _SO.stat().st_mtime
    except FileNotFoundError:
        return True
    return any((_NATIVE_DIR / src).stat().st_mtime > so_mtime
               for src in _SOURCES)


def _build() -> None:
    """Build in a scratch dir and publish with an atomic rename:
    concurrent first-use builds (parallel test workers, several
    daemons in one checkout) each produce a complete .so and the last
    replace wins — a reader can never CDLL a half-linked file."""
    import os
    import shutil
    import tempfile

    with tempfile.TemporaryDirectory(dir=_NATIVE_DIR) as td:
        for src in _SOURCES:
            shutil.copy(_NATIVE_DIR / src, td)
        res = subprocess.run(["make", "-C", td, "-s"],
                             capture_output=True, text=True, timeout=120)
        if res.returncode != 0:
            raise RuntimeError(
                f"native build failed (rc={res.returncode}):\n"
                f"{res.stdout}{res.stderr}")
        os.replace(os.path.join(td, "libceph_tpu_native.so"), _SO)


def _load_native():
    global _native
    if _native is not None:
        return _native
    if _stale():
        _build()
    lib = ctypes.CDLL(str(_SO))
    for fn in (lib.ceph_tpu_crc32c, lib.ceph_tpu_crc32c_table):
        fn.restype = ctypes.c_uint32
        fn.argtypes = (ctypes.c_uint32, ctypes.c_char_p, ctypes.c_size_t)
    lib.ceph_tpu_crc32c_impl.restype = ctypes.c_char_p
    lib.ceph_tpu_crc32c_impl.argtypes = ()
    _native = lib
    return _native


def impl() -> str:
    """The path ``crc32c`` runs in this process: "sse4.2", "armv8-crc"
    or "table" (chosen once, from the CPU's feature bits)."""
    return _load_native().ceph_tpu_crc32c_impl().decode()


_TABLE = None


def _table():
    global _TABLE
    if _TABLE is None:
        tbl = []
        for i in range(256):
            c = i
            for _ in range(8):
                c = (c >> 1) ^ 0x82F63B78 if c & 1 else c >> 1
            tbl.append(c)
        _TABLE = tbl
    return _TABLE


def _py_crc32c(crc: int, data) -> int:
    """The pure-Python table loop: the reference the native paths are
    tested against."""
    tbl = _table()
    c = (~crc) & 0xFFFFFFFF
    for b in data:
        c = tbl[(c ^ b) & 0xFF] ^ (c >> 8)
    return (~c) & 0xFFFFFFFF


def crc32c(crc: int, data: bytes | bytearray | memoryview) -> int:
    """Castagnoli CRC over ``data`` seeded with ``crc``."""
    if not isinstance(data, bytes):
        data = bytes(data)  # bytes pass to ctypes zero-copy
    lib = _load_native()
    if lib:
        return int(lib.ceph_tpu_crc32c(crc & 0xFFFFFFFF, data, len(data)))
    return _py_crc32c(crc, data)
