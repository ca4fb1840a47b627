"""Compile the main path's Pallas kernels for a described TPU v5e.

Interpret mode runs the kernel math on the CPU but never asks Mosaic to
lower it, so a kernel can pass every interpret test and still be
refused on the chip (an 8-bit iota once was).  These tests compile at
the bench shapes for a v5e that is described, not attached
(on-chip-measurement guide §2): nothing runs, so they check only that
the chip's compiler accepts each kernel and that the compiled program
holds it (``tpu_custom_call``).

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every xdist worker imports this
file.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from ceph_tpu.ec import pallas_kernels as pk
from ceph_tpu.ec.matrix import generator_matrix

# (technique, k, m, stripes, chunk bytes): bench.py's headline and cfg3
SHAPES = [("reed_sol_van", 8, 4, 16384, 512),
          ("cauchy_good", 10, 4, 1024, 4096)]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but can never be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("variant", pk.ENCODE_VARIANTS)
@pytest.mark.parametrize("technique,k,m,stripes,chunk", SHAPES)
def test_encode_kernel_compiles(one_chip, variant, technique, k, m,
                                stripes, chunk):
    """Every encode variant (production = "") at both bench shapes,
    through apply_bytes — the entry the cluster's encode takes."""
    G = generator_matrix(technique, k, m)
    applier = pk.PallasShardApply(G[k:])
    data = _shape((k, stripes * chunk), jnp.uint8, one_chip)
    pk.set_encode_variant(variant)
    try:
        text = _compiled_text(applier.apply_bytes, data)
    finally:
        pk.set_encode_variant("")
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("technique,k,m,stripes,chunk", SHAPES)
def test_word_kernel_compiles(one_chip, technique, k, m, stripes, chunk):
    """The production word kernel through apply_words (the resident
    and repair paths' entry)."""
    G = generator_matrix(technique, k, m)
    applier = pk.PallasShardApply(G[k:])
    words = _shape((k, stripes * chunk // 4), jnp.int32, one_chip)
    assert "tpu_custom_call" in _compiled_text(applier.apply_words, words)


def test_auto_resolves_to_a_compiled_variant():
    """"auto" picks a variant test_encode_kernel_compiles compiles."""
    pk.set_encode_variant("auto")
    try:
        assert pk.get_encode_variant() == pk.AUTO_VARIANT
        assert pk.AUTO_VARIANT in pk.ENCODE_VARIANTS
    finally:
        pk.set_encode_variant("")


@pytest.fixture(scope="module")
def clay_repair():
    """CLAY k=8 m=4 d=11 single-chunk repair operator and its helper
    rows at bench.py's cfg4 shape (128 stripes x 1 KiB sub-chunks)."""
    from ceph_tpu.ec.registry import ErasureCodePluginRegistry
    from ceph_tpu.ec.repair_operator import clay_repair_operator

    ec = ErasureCodePluginRegistry().factory(
        "clay", {"k": "8", "m": "4", "d": "11"})
    R, helpers, planes = clay_repair_operator(ec, 3)
    return R, len(helpers) * len(planes), 128 * 1024 // 4


def test_grouped_fused_repair_kernel_compiles(one_chip, clay_repair):
    R, rows, n4 = clay_repair
    plan = pk.GroupedPlan(R)
    assert plan.profitable
    applier = pk.PallasGroupedApply(R, plan=plan)
    words = _shape((rows, n4), jnp.int32, one_chip)
    assert "tpu_custom_call" in _compiled_text(applier.apply_words, words)


def test_grouped_paired_repair_kernel_compiles(one_chip, clay_repair):
    """The paired grouped kernel (the fallback for supports too wide
    for the fused one) at the same CLAY plan."""
    R, _, n4 = clay_repair
    plan = pk.GroupedPlan(R)
    bms = _shape(plan.bms.shape, jnp.int8, one_chip)
    gath = _shape((len(plan.groups), plan.cmax, n4), jnp.int32, one_chip)
    tile = pk._pick_gtile(n4, plan.cmax, plan.GRP_ROWS)

    def run(b, g):
        return pk._pallas_apply_grouped(b, g, tile=tile,
                                        grp_rows=plan.GRP_ROWS)

    assert "tpu_custom_call" in _compiled_text(run, bms, gath)


@pytest.mark.parametrize("program", ["encode", "decode", "repair"])
def test_clay_device_programs_compile(one_chip, program):
    """The CLAY k=8 m=4 d=11 device programs at the CLAY cell's shape (a
    4 MiB object: 128 stripes of 4 KiB chunks, 64 B sub-chunks), each
    with the grouped kernel's operands as arguments: the encode, the
    layered decode of chunk 0 from chunks 1..8, and the sub-chunk repair
    of chunk 0 from its 11 helpers' 16 repair planes."""
    from ceph_tpu.ec.engine import BitplaneEngine
    from ceph_tpu.ec.plugins import clay
    from ceph_tpu.ec.registry import ErasureCodePluginRegistry
    from ceph_tpu.ec.repair_operator import clay_repair_operator

    ec = ErasureCodePluginRegistry().factory(
        "clay", {"k": "8", "m": "4", "d": "11"})
    if program == "repair":
        coeff = clay_repair_operator(ec, 0)[0]
        fn, sub = clay._repair_program, 16
        args = (_shape((11, 128, 1024), jnp.uint8, one_chip),)
    else:
        coeff, _ = (ec._probe(None, (8, 9, 10, 11)) if program == "encode"
                    else ec._probe(tuple(range(1, 9)), (0,)))
        fn, sub = (clay._encode_program if program == "encode"
                   else clay._decode_program), 64
        args = ((_shape((128, 8, 4096), jnp.uint8, one_chip),)
                if program == "encode" else
                (tuple(_shape((128, 4096), jnp.uint8, one_chip)
                       for _ in range(8)),))
    spec, operands = BitplaneEngine(use_pallas=True).operator(coeff)
    assert spec[0] == "grouped"
    operands = tuple(_shape(a.shape, a.dtype, one_chip) for a in operands)
    text = fn.lower(operands, *args, spec=spec,
                    sub=sub).compile().as_text()
    assert "tpu_custom_call" in text


def test_sharded_applier_compiles_on_four_chips(monkeypatch, topo):
    """The mesh coalescer's applier: a shard_map over the 2x2 v5e mesh
    with the Pallas kernel inside (the replication check once refused
    the kernel's out_shape, which only a TPU compile shows)."""
    from jax.sharding import Mesh

    from ceph_tpu.ec.engine import default_engine
    from ceph_tpu.parallel.ec_sharding import ShardedApplier

    monkeypatch.setattr(default_engine(), "use_pallas", True)
    mesh = Mesh(np.array(topo.devices).reshape(4, 1), ("dp", "cs"))
    G = generator_matrix("reed_sol_van", 8, 4)
    applier = ShardedApplier(mesh, G[8:])
    batch = _shape((512, 8, 4096), jnp.uint8, applier.sharding())
    assert "tpu_custom_call" in \
        applier._step.lower(batch).compile().as_text()


@pytest.mark.parametrize("rows", [4, 12])
def test_byte_lane_conversions_compile_quickly(one_chip, rows):
    """Parity rows of a 4 MiB object (4 or 12 x 512 KiB): the former
    reshape + bitcast form took 90-170 s to compile for v5e at these
    shapes and stalled every op behind it."""
    import time

    n = 512 * 1024
    t0 = time.monotonic()
    _compiled_text(pk.bytes_to_words, _shape((rows, n), jnp.uint8, one_chip))
    _compiled_text(pk.words_to_bytes,
                   _shape((rows, n // 4), jnp.int32, one_chip))
    assert time.monotonic() - t0 < 60


@pytest.mark.parametrize("n,b,bp", [(12, 128, 128), (12, 128, 2048),
                                    (6, 1, 1), (6, 1, 32)],
                         ids=["write_solo", "write_16_mates",
                              "ycsb_solo", "ycsb_32_mates"])
def test_write_split_program_compiles(one_chip, n, b, bp):
    """The write path's split (engine.split_shard_streams) at the
    cells' shapes: a 4 MiB object of k=8 m=4 (128 stripes of 12 x 4 KiB
    chunks) and a 1,000 B record of k=4 m=2 (one stripe), alone and in
    the largest coalesced launch the cells warm.  A uint8 relayout of
    12 or 6 rows compiles in seconds."""
    import time

    from ceph_tpu.ec.engine import split_shard_streams

    t0 = time.monotonic()
    _compiled_text(lambda c, off: split_shard_streams(c, off, b),
                   _shape((bp, n, 4096), jnp.uint8, one_chip),
                   _shape((), jnp.int32, one_chip))
    assert time.monotonic() - t0 < 60


def test_byte_lane_conversions_match_the_numpy_view():
    data = np.random.default_rng(5).integers(0, 256, (3, 4096), np.uint8)
    words = np.asarray(pk.bytes_to_words(data))
    assert np.array_equal(words, data.view("<i4"))
    assert np.array_equal(np.asarray(pk.words_to_bytes(words)), data)
