"""Pallas fused kernel exactness (interpret mode on CPU) vs the oracle."""

import numpy as np
import pytest

from ceph_tpu.ec import matrix, reference
from ceph_tpu.ec.engine import BitplaneEngine
from ceph_tpu.ec.pallas_kernels import PallasBitplaneApply


def _rand(shape, seed=0):
    return np.random.default_rng(seed).integers(0, 256, shape, dtype=np.uint8)


@pytest.mark.parametrize(
    "technique,k,m,C",
    [
        ("reed_sol_van", 8, 4, 512),
        ("cauchy_good", 10, 4, 128),
        ("isa_cauchy", 4, 2, 1024),
        ("isa_vandermonde", 8, 3, 256),
    ],
)
def test_pallas_encode_bit_identical(technique, k, m, C):
    G = matrix.generator_matrix(technique, k, m)
    data = _rand((3, k, C), seed=k * m + C)
    ap = PallasBitplaneApply(G[k:], interpret=True)
    got = np.asarray(ap(data))
    expect = np.stack([reference.encode(G, data[b])[k:] for b in range(3)])
    assert np.array_equal(got, expect)


def test_pallas_decode_matrix_bit_identical():
    k, m = 8, 4
    G = matrix.generator_matrix("reed_sol_van", k, m)
    data = _rand((k, 256), seed=5)
    chunks = reference.encode(G, data)
    lost = [0, 5, 11]
    survivors = [i for i in range(k + m) if i not in lost][:k]
    D = reference.decode_matrix(G, survivors, lost)
    ap = PallasBitplaneApply(D, interpret=True)
    got = np.asarray(ap(chunks[survivors]))
    for i, w in enumerate(lost):
        assert np.array_equal(got[i], chunks[w])


def test_pallas_unaligned_chunk():
    G = matrix.generator_matrix("reed_sol_van", 4, 2)
    ap = PallasBitplaneApply(G[4:], interpret=True)
    # Not a multiple of the 4-byte lane: rejected.
    with pytest.raises(ValueError):
        ap(_rand((4, 101)))
    # Multiple of 4 but not of the 128-lane tile: padded internally.
    data = _rand((4, 100))
    got = np.asarray(ap(data))
    assert np.array_equal(got, reference.encode(G, data)[4:])


def test_pallas_shard_layout_matches_per_stripe():
    """(k, B*C) shard-stream layout == per-stripe encode, column for column."""
    k, m, B, C = 8, 4, 5, 256
    G = matrix.generator_matrix("reed_sol_van", k, m)
    stripes = _rand((B, k, C), seed=17)
    # shard stream: chunk i of stripe s at columns [s*C, (s+1)*C)
    shard_stream = np.transpose(stripes, (1, 0, 2)).reshape(k, B * C)
    ap = PallasBitplaneApply(G[k:], interpret=True)
    got = np.asarray(ap(shard_stream))
    for s in range(B):
        expect = reference.encode(G, stripes[s])[k:]
        assert np.array_equal(got[:, s * C:(s + 1) * C], expect)


def test_pallas_word_path_bit_identical():
    from ceph_tpu.ec.pallas_kernels import bytes_to_words, words_to_bytes

    k, m = 8, 4
    G = matrix.generator_matrix("cauchy_good", k, m)
    data = _rand((k, 512), seed=23)
    ap = PallasBitplaneApply(G[k:], interpret=True)
    words = bytes_to_words(data)
    out = words_to_bytes(ap.apply_words(words))
    assert np.array_equal(np.asarray(out), reference.encode(G, data)[k:])
    # round trip of the word view itself
    assert np.array_equal(np.asarray(words_to_bytes(words)), data)


def _interpret_engine():
    """Engine whose Pallas appliers run in interpret mode (CPU tests)."""
    from ceph_tpu.ec.pallas_kernels import PallasShardApply

    eng = BitplaneEngine(use_pallas=True)
    eng._pallas_applier = lambda c: PallasShardApply(c, interpret=True)
    return eng


def test_pallas_blocked_contraction_bit_identical():
    """Matrices beyond one VMEM block run the k-blocked kernel with XOR
    accumulation; outputs stay bit-identical to the einsum oracle."""
    from ceph_tpu.ec import bitmatrix as bm
    from ceph_tpu.ec.engine import bitplane_apply
    from ceph_tpu.ec.pallas_kernels import PallasShardApply

    import jax.numpy as jnp

    coeff = _rand((40, 48), seed=3)      # 1280x1536 bm32: 2 k-blocks
    ap = PallasShardApply(coeff, interpret=True)
    assert ap.kblk < ap.kin              # actually exercises blocking
    data = _rand((48, 512), seed=4)
    got = np.asarray(ap(data))
    rbits = jnp.asarray(bm.gf_matrix_to_bitmatrix(coeff), jnp.bfloat16)
    want = np.asarray(bitplane_apply(rbits, jnp.asarray(data)[None])[0])
    assert np.array_equal(got, want)


@pytest.mark.parametrize(
    "technique,k,w",
    [("liberation", 5, 7), ("blaum_roth", 6, 6), ("liber8tion", 6, 8)],
)
def test_packet_fast_path_bitsched(technique, k, w):
    """Bit-schedule codes route through the shard kernel (packet rows as
    0/1 GF(2^8) coefficients) bit-identically to the einsum packet path."""
    from ceph_tpu.ec import bitsched
    from ceph_tpu.ec.engine import packet_bitmatrix_apply

    import jax.numpy as jnp

    if technique == "liberation":
        parity = bitsched.liberation_bitmatrix(k, w)
    elif technique == "blaum_roth":
        parity = bitsched.blaum_roth_bitmatrix(k, w)
    else:
        parity = bitsched.liber8tion_bitmatrix(k)
    BM = bitsched.full_bitmatrix(parity, k, w)[k * w:]
    C = w * 16 * 4
    data = _rand((3, k, C), seed=w)
    got = np.asarray(_interpret_engine().apply_packets(BM, data, w))
    want = np.asarray(packet_bitmatrix_apply(
        jnp.asarray(BM, jnp.bfloat16), jnp.asarray(data), w
    ))
    assert np.array_equal(got, want)


@pytest.mark.parametrize("k,m,w", [(5, 3, 16), (4, 2, 32)])
def test_packet_fast_path_wide_symbols(k, m, w):
    """w=16/32 RS bitmatrices exceed one VMEM block: packet fast path +
    k-blocked kernel together, encode and decode."""
    from ceph_tpu.ec import bitsched
    from ceph_tpu.ec.engine import packet_bitmatrix_apply

    import jax.numpy as jnp

    gen = bitsched.reed_sol_van_w(k, m, w)
    full = bitsched.matrix_to_bitmatrix(gen, w)
    BM = full[k * w:]
    eng = _interpret_engine()
    C = w * 4 * 8
    data = _rand((2, k, C), seed=w)
    got = np.asarray(eng.apply_packets(BM, data, w))
    want = np.asarray(packet_bitmatrix_apply(
        jnp.asarray(BM, jnp.bfloat16), jnp.asarray(data), w
    ))
    assert np.array_equal(got, want)
    # decode matrix (rows = wanted*w) through the same route
    D = bitsched.decode_bitmatrix(
        full, k, w, list(range(1, k + 1)), [0, k + m - 1]
    )
    surv = _rand((2, k, C), seed=w + 1)
    gd = np.asarray(eng.apply_packets(D, surv, w))
    wd = np.asarray(packet_bitmatrix_apply(
        jnp.asarray(D, jnp.bfloat16), jnp.asarray(surv), w
    ))
    assert np.array_equal(gd, wd)


def _sparse_coeff(mout, kin, per_row, seed=0):
    rng = np.random.default_rng(seed)
    coeff = np.zeros((mout, kin), np.uint8)
    for i in range(mout):
        cols = rng.choice(kin, size=per_row, replace=False)
        coeff[i, cols] = rng.integers(1, 256, per_row)
    return coeff


def test_grouped_kernel_bit_identical_random_sparse():
    """Sparse-grouped kernel == dense einsum oracle, including interleaved
    padding rows (mout not a multiple of the group size, odd group count)."""
    import jax.numpy as jnp

    from ceph_tpu.ec import bitmatrix as bm
    from ceph_tpu.ec.engine import bitplane_apply
    from ceph_tpu.ec.pallas_kernels import GroupedPlan, PallasGroupedApply

    for mout, kin, per_row, seed in [(64, 176, 15, 1), (30, 120, 9, 2),
                                     (7, 96, 5, 3)]:
        coeff = _sparse_coeff(mout, kin, per_row, seed)
        plan = GroupedPlan(coeff)
        assert plan.profitable, (mout, kin, per_row)
        ap = PallasGroupedApply(coeff, interpret=True, plan=plan)
        data = _rand((kin, 256), seed=seed + 10)
        got = np.asarray(ap(data))
        rbits = jnp.asarray(bm.gf_matrix_to_bitmatrix(coeff), jnp.bfloat16)
        want = np.asarray(bitplane_apply(rbits, jnp.asarray(data)[None])[0])
        assert np.array_equal(got, want), (mout, kin)


def test_grouped_plan_vmem_gate():
    """A sparse matrix whose group supports are too wide for VMEM must
    NOT be declared groupable (it would fail Mosaic allocation on chip);
    it falls back to the dense/einsum paths instead."""
    from ceph_tpu.ec.pallas_kernels import GroupedPlan

    rng = np.random.default_rng(4)
    kin = 4096
    coeff = np.zeros((8, kin), np.uint8)
    # each 4-row group touches ~2400 distinct columns: profitable by MAC
    # ratio alone, infeasible in VMEM
    for i in range(8):
        cols = rng.choice(kin, size=600, replace=False)
        coeff[i, cols] = 7
    plan = GroupedPlan(coeff)
    assert not plan.profitable


def test_grouped_kernel_clay_repair_operator():
    """The CLAY k=8 m=4 d=11 repair operator routes through the grouped
    kernel and reproduces the host plugin repair bit-for-bit."""
    from ceph_tpu.ec.engine import BitplaneEngine
    from ceph_tpu.ec.pallas_kernels import GroupedPlan, PallasGroupedApply
    from ceph_tpu.ec.registry import ErasureCodePluginRegistry
    from ceph_tpu.ec.repair_operator import clay_repair_operator

    ec = ErasureCodePluginRegistry().factory(
        "clay", {"k": "8", "m": "4", "d": "11"}
    )
    R, helpers, planes = clay_repair_operator(ec, 3)
    plan = GroupedPlan(R)
    assert plan.profitable and plan.mac_ratio < 0.5
    sc = 64
    C = ec.sub_chunk_no * sc
    data = _rand((4, ec.k, C), seed=31)
    chunks = np.asarray(ec.encode_chunks_batch(data))
    flat = np.stack([
        chunks[:, h].reshape(4, ec.sub_chunk_no, sc)[:, planes]
        for h in helpers
    ], axis=1).reshape(4, len(helpers) * len(planes), sc)
    ap = PallasGroupedApply(R, interpret=True, plan=plan)
    got = np.asarray(ap(flat)).reshape(4, C)
    assert np.array_equal(got, chunks[:, 3])
    # engine dispatch picks the grouped path for this matrix
    eng = BitplaneEngine(use_pallas=True)
    assert eng._grouped_applier(R) is not None
    # dense matrices do NOT take the grouped path
    from ceph_tpu.ec import matrix
    G = matrix.generator_matrix("reed_sol_van", 8, 4)
    assert eng._grouped_applier(G[8:]) is None


def test_engine_pallas_flag_matches_einsum():
    """Engine with forced-pallas(interpret) == engine with einsum, byte-for-byte."""
    k, m = 6, 3
    G = matrix.generator_matrix("isa_cauchy", k, m)
    data = _rand((2, k, 384), seed=8)
    eins = BitplaneEngine(use_pallas=False)
    a = np.asarray(eins.encode(G, data))
    pal = BitplaneEngine(use_pallas=True)
    # force interpret mode on CPU
    for key in list(pal._pallas_cache):
        del pal._pallas_cache[key]
    from ceph_tpu.ec import pallas_kernels

    applier = pallas_kernels.PallasBitplaneApply(G[k:], interpret=True)
    pal._pallas_cache[
        G[k:].tobytes() + repr(G[k:].shape).encode()
    ] = applier
    b = np.asarray(pal.encode(G, data))
    assert np.array_equal(a, b)


@pytest.mark.parametrize("variant", ["enc_cmp_expand", "enc_split2"])
@pytest.mark.parametrize(
    "technique,k,m",
    [
        ("reed_sol_van", 8, 4),
        ("cauchy_good", 10, 4),
        ("isa_vandermonde", 8, 3),
    ],
)
def test_encode_variant_bit_identical(variant, technique, k, m):
    """Alternative encode variants: with ec_pallas_encode_variant
    set, PallasShardApply must stay bit-identical to the production
    kernel over representative corpus geometries (this is the CI gate —
    a variant that diverges in interpret mode never reaches a chip)."""
    from ceph_tpu.ec.pallas_kernels import (
        PallasShardApply, bytes_to_words, get_encode_variant,
        set_encode_variant, words_to_bytes)

    G = matrix.generator_matrix(technique, k, m)
    ap = PallasShardApply(G[k:], interpret=True)
    # non-tile-aligned column count exercises the pad path too
    data = _rand((k, 4096 + 512), seed=k * 31 + m)
    words = bytes_to_words(data)
    base = np.asarray(ap.apply_words(words))
    assert get_encode_variant() == ""
    set_encode_variant(variant)
    try:
        got = np.asarray(ap.apply_words(words))
    finally:
        set_encode_variant("")
    assert np.array_equal(got, base)
    assert np.array_equal(
        words_to_bytes(got), reference.encode(G, data)[k:])


def test_encode_variant_unknown_rejected():
    from ceph_tpu.ec.pallas_kernels import (
        get_encode_variant, set_encode_variant)

    with pytest.raises(ValueError, match="unknown encode variant"):
        set_encode_variant("enc_nope")
    assert get_encode_variant() == ""
