"""The native crc32c's two paths against each other and against Python.

``ceph_tpu_crc32c`` runs the path the library chose from the CPU's
feature bits (hardware crc32, three interleaved streams, where the CPU
has it); ``ceph_tpu_crc32c_table`` is the slice-by-8 fallback; the
pure-Python table loop (``_py_crc32c``) is the reference.  The lengths
straddle the hardware path's block edges (3 x 256 B short and 3 x 8 KiB
long blocks, the 8-byte words) and every start alignment.
"""

import ctypes
import pathlib
import random

import pytest

from ceph_tpu.common import crc32c as crcmod

LENGTHS = (0, 1, 7, 8, 9, 767, 768, 769, 24575, 24576, 24577,
           512 << 10, (4 << 20) + 3)


def _at(buf, offset):
    """A char pointer ``offset`` bytes into a ctypes buffer."""
    return ctypes.c_char_p(ctypes.addressof(buf) + offset)


@pytest.fixture(scope="module")
def lib():
    return crcmod._load_native()


@pytest.fixture(scope="module")
def block():
    """Random bytes at an 8-byte aligned address, room for any case."""
    rng = random.Random(0xC3C)
    data = rng.randbytes(max(LENGTHS) + 8)
    buf = (ctypes.c_uint64 * (len(data) // 8 + 1))()
    ctypes.memmove(buf, data, len(data))
    return buf, data


@pytest.mark.parametrize("align", range(8))
@pytest.mark.parametrize("length", LENGTHS)
def test_paths_agree(lib, block, length, align):
    buf, data = block
    seed = random.Random(length * 8 + align).getrandbits(32)
    ptr = _at(buf, align)
    want = crcmod._py_crc32c(seed, data[align:align + length])
    assert lib.ceph_tpu_crc32c(seed, ptr, length) == want
    assert lib.ceph_tpu_crc32c_table(seed, ptr, length) == want


@pytest.mark.parametrize("fn", ("ceph_tpu_crc32c",
                                "ceph_tpu_crc32c_table"))
@pytest.mark.parametrize("case", range(6))
def test_seed_chaining(lib, block, fn, case):
    """crc(crc(s, a), b) == crc(s, a + b) at split points on and off
    block and word edges."""
    buf, _ = block
    rng = random.Random(case)
    total = rng.choice((769, 24577, 100_003, 512 << 10))
    split = rng.randrange(total + 1)
    align = rng.randrange(8)
    seed = rng.getrandbits(32)
    crc = getattr(lib, fn)
    head = crc(seed, _at(buf, align), split)
    assert crc(head, _at(buf, align + split), total - split) == \
        crc(seed, _at(buf, align), total)


def test_module_crc32c_runs_the_chosen_path(lib):
    data = random.Random(3).randbytes(70_001)
    assert crcmod.crc32c(0x5EED, data) == \
        lib.ceph_tpu_crc32c_table(0x5EED, data, len(data))


def test_impl_names_the_hardware_path():
    """impl() names the hardware path on a CPU that reports it."""
    try:
        cpuinfo = pathlib.Path("/proc/cpuinfo").read_text()
    except OSError:
        pytest.skip("no /proc/cpuinfo")
    flags = set()
    for line in cpuinfo.splitlines():
        key, _, value = line.partition(":")
        if key.strip() in ("flags", "Features"):
            flags.update(value.split())
    if "sse4_2" in flags:
        assert crcmod.impl() == "sse4.2"
    elif "crc32" in flags:
        assert crcmod.impl() == "armv8-crc"
    else:
        pytest.skip("the CPU reports no crc32c instruction")
