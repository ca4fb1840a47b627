"""Fused Pallas TPU kernel for GF(2) bitplane region ops, shard layout.

Why: the XLA einsum path (engine.bitplane_apply) materialises bf16 bit
planes in HBM at 16x the data size, and a per-stripe (B, k, C) kernel with
C=512-byte chunks feeds the 128x128 MXU a 32x64 matmul (12.5% utilization).
This kernel fixes both at once:

- **Shard layout** ``(k, N)``: chunk row i is shard i's byte stream (the
  ECUtil layout — chunk i of stripe s at columns [s*C, (s+1)*C), reference
  ECUtil.h:28-65), so one kernel call covers an arbitrarily large stripe
  batch with fat tiles instead of per-stripe 4KiB blocks.
- **int32 lanes**: bytes ride 4-to-a-lane (no uint8 sublane padding, no
  16x bf16 bit-plane inflation in HBM).  Bit p of byte b of lane word i is
  extracted in-register (32 shift/mask planes per chunk row).
- **Lane-expanded bitmatrix**: byte positions never mix, so the GF(2)
  matrix lifts to a (32m x 32k) block-diagonal matrix
  (bitmatrix.expand_bitmatrix_lanes) — for k=8, m=4 a 128x256 contraction
  that fills the MXU, vs 32x64 for per-byte planes.
- **int8 matmul**: 0/1 operands, int32 accumulation (exact: row sums
  <= 32k < 2^31); int8 runs the MXU at twice the bf16 rate.

Parity packs back to int32 lanes with a shift-OR tree on the VPU.  Measured
on one v5e chip this is HBM-bound (bytes-in + parity-out), the same regime
as isa-l's L1-resident ec_encode_data (reference ErasureCodeIsa.cc:119-129).

Bit order matches bitmatrix.py (LSB-first) and lane order is little-endian
(byte 0 = bits 0..7 of the int32 word), so outputs are bit-identical to the
engine/reference paths — enforced by tests and the corpus.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ceph_tpu.ec import bitmatrix as bm

LANE = 128          # int32 lanes per tile row must be a multiple of this
LANE_BYTES = 4      # bytes packed per int32 lane
DEFAULT_TILE = 8192  # int32 lanes per grid step (32 KiB of data per row)

# Largest int8 matrix BLOCK kept resident in VMEM per grid step (1 MiB).
# Bigger matrices (wide-symbol w=16/32 bitmatrices) run the same kernel
# blocked over the contraction dim with XOR accumulation in the output.
_MAX_MATRIX_BYTES = 1 << 20
# Budget for the (32*mout, tile) int32 accumulator produced by the MXU.
_ACC_BUDGET_BYTES = 4 << 20


def shard_kernel_supported(kin: int, mout: int) -> bool:
    return _pick_kblk(kin, mout) > 0


# -- encode-variant selection ---------------------------------------
#
# Alternative kernel formulations of the same GF(2) contraction, all
# bit-identical to the production kernel: interpret mode checks them in
# tests/test_pallas.py and tests/test_tpu_compile.py compiles each for a
# described v5e.  None has been timed against another on a chip yet.
# Selected process-wide via conf ``ec_pallas_encode_variant``.  Variants
# assume an unblocked contraction (kblocks == 1); matrices big enough
# to need contraction blocking keep the production kernel.
ENCODE_VARIANTS = ("", "enc_cmp_expand", "enc_split2")
# What "auto" selects: the kernel chip_smoke.py has checked on the chip.
AUTO_VARIANT = ""
_encode_variant = ""


def set_encode_variant(name: str) -> None:
    """Select the Pallas encode kernel formulation ("" = production,
    "auto" = AUTO_VARIANT)."""
    global _encode_variant
    if name == "auto":
        name = AUTO_VARIANT
    if name not in ENCODE_VARIANTS:
        raise ValueError(
            f"unknown encode variant {name!r}; one of {ENCODE_VARIANTS}"
        )
    _encode_variant = name


def get_encode_variant() -> str:
    return _encode_variant


def _kernel(bm_ref, data_ref, out_ref, *, mout):
    kb = pl.program_id(1)
    d = data_ref[:]  # (kblk, T) int32
    kin, T = d.shape
    shift = jax.lax.broadcasted_iota(jnp.int32, (1, 32, 1), 1)
    # (k, 32, T): plane 8b+p of chunk i -> row 32i + 8b + p after collapse.
    bits = ((d[:, None, :] >> shift) & 1).reshape(kin * 32, T)
    acc = jnp.dot(
        bm_ref[:], bits.astype(jnp.int8), preferred_element_type=jnp.int32
    )
    accb = (acc & 1).reshape(mout, 32, T)
    # Disjoint bit positions: sum == OR, exact even into the sign bit.
    partial = jnp.sum(accb << shift, axis=1)

    @pl.when(kb == 0)
    def _init():
        out_ref[:] = partial

    @pl.when(kb > 0)
    def _accum():
        # GF(2) accumulation across contraction blocks.
        out_ref[:] = out_ref[:] ^ partial


@functools.partial(jax.jit, static_argnames=("tile", "kblk", "interpret"))
def _pallas_apply_words(bm32, words, *, tile, kblk, interpret=False):
    kin, n4 = words.shape
    mout = bm32.shape[0] // 32
    kblocks = kin // kblk
    return pl.pallas_call(
        functools.partial(_kernel, mout=mout),
        # kb is the fast axis: all contraction blocks of one output tile
        # run consecutively, so the XOR accumulation revisits a resident
        # out block.
        grid=(n4 // tile, kblocks),
        in_specs=[
            pl.BlockSpec((bm32.shape[0], 32 * kblk), lambda t, kb: (0, kb),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((kblk, tile), lambda t, kb: (kb, t),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((mout, tile), lambda t, kb: (0, t),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((mout, n4), jnp.int32),
        interpret=interpret,
    )(bm32, words)


def _pick_kblk(kin: int, mout: int) -> int:
    """Contraction symbols per block: the (32*mout, 32*kblk) int8 matrix
    block must fit _MAX_MATRIX_BYTES.  When blocking (kblk < kin), the
    Mosaic lowering needs block dims divisible by (8, 128), so kblk must
    be a multiple of 8 (32*8 = 256 lane columns).  Returns 0 when even
    one 8-symbol block exceeds the budget (kernel unsupported)."""
    if 32 * mout * 32 * kin <= _MAX_MATRIX_BYTES:
        return kin                          # whole matrix in one block
    kblk = (_MAX_MATRIX_BYTES // (32 * mout * 32)) // 8 * 8
    return min(kblk, kin // 8 * 8)


def _pick_tile(n4: int, mout: int) -> int:
    t = DEFAULT_TILE
    # MXU accumulator is (32*mout, tile) int32: stay inside the budget.
    while t > LANE and 32 * mout * t * 4 > _ACC_BUDGET_BYTES:
        t //= 2
    while t > LANE and n4 % t:
        t //= 2
    return t


def _kernel_cmp_expand(bm_ref, data_ref, out_ref, *, mout):
    """Variant enc_cmp_expand: bit expansion via mask-AND + compare-to-
    zero producing int8 directly — drops the int32 plane intermediate
    AND the separate astype(int8) relayout of the production kernel."""
    d = data_ref[:]
    kin, T = d.shape
    shift = jax.lax.broadcasted_iota(jnp.int32, (1, 32, 1), 1)
    mask = jnp.left_shift(jnp.int32(1), shift)
    bits = ((d[:, None, :] & mask) != 0).astype(jnp.int8) \
        .reshape(kin * 32, T)
    acc = jnp.dot(bm_ref[:], bits, preferred_element_type=jnp.int32)
    accb = (acc & 1).reshape(mout, 32, T)
    out_ref[:] = jnp.sum(accb << shift, axis=1)


def _kernel_split2(bm_ref, data_ref, out_ref, *, mout):
    """Variant enc_split2: software-pipelined halves — two independent
    half-tiles per body so the scheduler may overlap half 2's VPU
    expansion with half 1's MXU contraction."""
    kin, T = data_ref.shape
    half = T // 2
    shift = jax.lax.broadcasted_iota(jnp.int32, (1, 32, 1), 1)
    B = bm_ref[:]
    for h in range(2):
        d = data_ref[:, h * half:(h + 1) * half]
        bits = ((d[:, None, :] >> shift) & 1).reshape(kin * 32, half)
        acc = jnp.dot(B, bits.astype(jnp.int8),
                      preferred_element_type=jnp.int32)
        accb = (acc & 1).reshape(mout, 32, half)
        out_ref[:, h * half:(h + 1) * half] = \
            jnp.sum(accb << shift, axis=1)


_WORD_VARIANT_KERNELS = {
    "enc_cmp_expand": _kernel_cmp_expand,
    "enc_split2": _kernel_split2,
}


@functools.partial(jax.jit,
                   static_argnames=("tile", "variant", "interpret"))
def _pallas_apply_words_variant(bm32, words, *, tile, variant,
                                interpret=False):
    """Word-layout variant launch (unblocked contraction only)."""
    kin, n4 = words.shape
    mout = bm32.shape[0] // 32
    return pl.pallas_call(
        functools.partial(_WORD_VARIANT_KERNELS[variant], mout=mout),
        grid=(n4 // tile,),
        in_specs=[
            pl.BlockSpec(bm32.shape, lambda t: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((kin, tile), lambda t: (0, t),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((mout, tile), lambda t: (0, t),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((mout, n4), jnp.int32),
        interpret=interpret,
    )(bm32, words)


def _device_cached(np_arr: np.ndarray, slot):
    """Device copy of a numpy kernel constant.  Outside a trace the copy
    is cached (returned as the new slot value); under an outer trace it
    is embedded as a fresh constant and the slot stays untouched (a
    cached tracer would poison later traces).  Returns (array, slot)."""
    from ceph_tpu.common.jaxutil import outside_trace

    if not outside_trace():
        return jnp.asarray(np_arr), slot
    if slot is None:
        slot = jnp.asarray(np_arr)
    return slot, slot


def _pick_gtile(n4: int, cmax: int, grp: int) -> int:
    """Grouped-kernel tile: the dominant VMEM tenants per grid step are
    the (2, 32*cmax, tile) int8 bit expansion, the (2, cmax, tile) int32
    data block, and the (2, 32*grp, tile) int32 accumulator — keep their
    sum near half of the ~16 MiB VMEM."""
    per_col = 2 * cmax * (32 + 4) + 2 * grp * 32 * 4
    t = DEFAULT_TILE
    while t > LANE and per_col * t > (8 << 20):
        t //= 2
    while t > LANE and n4 % t:
        t //= 2
    return t


# Byte <-> lane conversion by shifts and strided slices, not by a
# (..., N/4, 4) uint8 reshape + bitcast: for some shapes (4 or 12 rows of
# 512 KiB, the parity of a 4 MiB object) the TPU compiler takes 90-170 s
# over that uint8 relayout, against a few seconds for this form.
@jax.jit
def _bytes_to_words(data):
    b = [data[..., j::LANE_BYTES].astype(jnp.int32)
         for j in range(LANE_BYTES)]
    return b[0] | (b[1] << 8) | (b[2] << 16) | (b[3] << 24)


@jax.jit
def _words_to_bytes(words):
    out = jnp.zeros((*words.shape[:-1], words.shape[-1] * LANE_BYTES),
                    jnp.uint8)
    for j in range(LANE_BYTES):
        out = out.at[..., j::LANE_BYTES].set(
            ((words >> (8 * j)) & 0xFF).astype(jnp.uint8))
    return out


def bytes_to_words(data) -> jax.Array:
    """(..., N) uint8 -> (..., N/4) int32 lanes, little-endian (byte 0 =
    bits 0..7); N % 4 == 0."""
    data = jnp.asarray(data, jnp.uint8)
    if data.shape[-1] % LANE_BYTES:
        raise ValueError(f"byte count {data.shape[-1]} not a multiple of 4")
    return _bytes_to_words(data)


def words_to_bytes(words) -> jax.Array:
    """(..., N4) int32 -> (..., 4*N4) uint8, inverse of bytes_to_words."""
    return _words_to_bytes(jnp.asarray(words, jnp.int32))


def _greedy_groups(nz: np.ndarray, grp_rows: int) -> list[list[int]]:
    """Partition rows into groups of grp_rows minimizing union supports:
    seed each group with the unassigned row of largest support, then add
    the rows whose supports add the fewest new columns."""
    mout = nz.shape[0]
    sups = [frozenset(np.nonzero(nz[i])[0]) for i in range(mout)]
    unassigned = set(range(mout))
    groups: list[list[int]] = []
    while unassigned:
        seed = max(unassigned, key=lambda r: len(sups[r]))
        unassigned.remove(seed)
        grp, union = [seed], set(sups[seed])
        while len(grp) < grp_rows and unassigned:
            best = min(unassigned, key=lambda r: len(sups[r] - union))
            unassigned.remove(best)
            grp.append(best)
            union |= sups[best]
        groups.append(grp)
    return groups


class GroupedPlan:
    """Row-grouped sparse factorization of a GF(2^8) coefficient matrix.

    Repair operators (ceph_tpu.ec.repair_operator) are sparse: CLAY
    k=8 m=4 d=11 single-chunk repair is a (64, 176) matrix with ~15
    nonzeros per row (reference repair_one_lost_chunk touches only the
    d helpers' repair planes plus coupling partners,
    ErasureCodeClay.cc:462-646).  The dense shard kernel pays the full
    (32*mout, 32*kin) contraction regardless; grouping rows by shared
    column support and gathering only those columns cuts the MACs by
    the density factor while keeping the MXU fed with 128-row tiles.
    """

    GRP_ROWS = 4        # 4 GF rows -> 128 bit rows: one full MXU tile

    def __init__(self, coeff: np.ndarray):
        coeff = np.asarray(coeff, np.uint8)
        self.mout, self.kin = coeff.shape
        nz = coeff != 0
        grp = self.GRP_ROWS
        natural = [list(range(g, min(g + grp, self.mout)))
                   for g in range(0, self.mout, grp)]
        greedy = _greedy_groups(nz, grp)

        def cmax_of(groups):
            return max(
                max(1, int(nz[g].any(axis=0).sum())) for g in groups
            )

        groups = min((natural, greedy), key=cmax_of)
        cmax = -(-cmax_of(groups) // 8) * 8
        if len(groups) % 2:
            groups = groups + [[]]      # pair padding (zero group)
        G = len(groups)
        # Profitability: grouped MACs vs the dense contraction, AND the
        # per-pair (2, 32*grp, 32*cmax) bitmatrix block must fit the
        # VMEM budget (the dense path's _MAX_MATRIX_BYTES analog —
        # without this, a wide-support sparse matrix would route to a
        # kernel Mosaic cannot allocate).
        self.mac_ratio = (G * grp * cmax) / float(self.mout * self.kin)
        self.profitable = (
            cmax < self.kin
            and self.mac_ratio <= 0.6
            and 2 * 32 * grp * 32 * cmax <= _MAX_MATRIX_BYTES
        )
        self.cmax, self.groups = cmax, groups
        if not self.profitable:
            return                      # skip the bitmatrix build
        self.cols = np.zeros((G, cmax), np.int32)     # gathered columns
        bms = np.zeros((G, 32 * grp, 32 * cmax), np.int8)
        for gi, rows in enumerate(groups):
            sup = np.nonzero(nz[rows].any(axis=0))[0] if rows else \
                np.zeros(0, np.int64)
            self.cols[gi, :len(sup)] = sup
            if len(rows) == 0:
                continue
            sub = np.zeros((grp, cmax), np.uint8)
            sub[:len(rows), :len(sup)] = coeff[rows][:, sup]
            bms[gi] = bm.expand_bitmatrix_lanes(
                bm.gf_matrix_to_bitmatrix(sub)
            )
        self.bms = bms
        # Real output rows sit at (group, slot) positions; padding slots
        # (short groups, the pair-padding group) are interleaved.  Map
        # kernel row order back to caller row order in one gather.
        real_pos = [gi * grp + j
                    for gi, rows in enumerate(groups)
                    for j in range(len(rows))]
        flat_rows = [r for rows in groups for r in rows]
        order = np.argsort(np.asarray(flat_rows, np.int64), kind="stable")
        self.gather_rows = np.asarray(real_pos, np.int64)[order]


def _gkernel_fused(bm_ref, data_ref, out_ref, *, grp_rows, cols,
                   kin):
    """ALL row groups in one launch: the (kin, T) input block is read
    once, bit-expanded once, and each group's support columns are
    selected IN VMEM with static indices (no HBM-visible gather — the
    paired kernel's host-side ``words[cols]`` materialized a
    support-amplified array every apply, which is what made CLAY
    repair launch/traffic-bound, round-3 weak #2).  HBM traffic is
    input once + output once per tile: the roofline optimum."""
    d = data_ref[:]                          # (kin, T) int32
    _, T = d.shape
    shift = jax.lax.broadcasted_iota(jnp.int32, (1, 32, 1), 1)
    bits = ((d[:, None, :] >> shift) & 1).reshape(kin * 32, T) \
        .astype(jnp.int8)
    for g in range(len(cols)):               # static unroll over groups
        sel = jnp.concatenate(
            [jax.lax.slice_in_dim(bits, 32 * c, 32 * (c + 1))
             for c in cols[g]], axis=0)      # (32*cmax, T)
        acc = jnp.dot(bm_ref[g], sel,
                      preferred_element_type=jnp.int32)
        accb = (acc & 1).reshape(grp_rows, 32, T)
        packed = jnp.sum(accb << shift, axis=1)      # (grp, T)
        out_ref[g * grp_rows:(g + 1) * grp_rows, :] = packed


@functools.partial(jax.jit,
                   static_argnames=("tile", "grp_rows", "cols",
                                    "interpret"))
def _pallas_apply_grouped_fused(bms, words, *, tile, grp_rows, cols,
                                interpret=False):
    kin, n4 = words.shape
    G = bms.shape[0]
    return pl.pallas_call(
        functools.partial(_gkernel_fused, grp_rows=grp_rows,
                          cols=cols, kin=kin),
        grid=(n4 // tile,),
        in_specs=[
            pl.BlockSpec(bms.shape, lambda t: (0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((kin, tile), lambda t: (0, t),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((G * grp_rows, tile), lambda t: (0, t),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((G * grp_rows, n4), jnp.int32),
        interpret=interpret,
    )(bms, words)


def _pick_fused_tile(n4: int, kin: int, cmax: int, grp: int,
                     G: int) -> int:
    """Fused-kernel tile: VMEM tenants per grid step are the whole
    (G, 32*grp, 32*cmax) int8 bitmatrix, the double-buffered (kin,
    tile) int32 input, the (32*kin, tile) int8 bit expansion, one
    (32*cmax, tile) int8 selection, and the (G*grp, tile) int32
    output — keep the tile-dependent sum near ~10 MiB."""
    fixed = G * 32 * grp * 32 * cmax
    per_col = 2 * kin * 4 + 32 * kin + 32 * cmax + 2 * G * grp * 4
    t = DEFAULT_TILE
    while t > LANE and fixed + per_col * t > (10 << 20):
        t //= 2
    while t > LANE and n4 % t:
        t //= 2
    return t


def _gkernel(bm_ref, data_ref, out_ref, *, grp_rows):
    d = data_ref[:]                     # (2, cmax, T) int32: two groups
    _, cin, T = d.shape
    shift = jax.lax.broadcasted_iota(jnp.int32, (1, 1, 32, 1), 2)
    bits = ((d[:, :, None, :] >> shift) & 1).reshape(2, cin * 32, T)
    acc = jax.lax.dot_general(
        bm_ref[:], bits.astype(jnp.int8),
        dimension_numbers=(((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.int32,
    )                                   # (2, 32*grp, T)
    accb = (acc & 1).reshape(2, grp_rows, 32, T)
    packed = jnp.sum(accb << shift, axis=2)       # (2, grp, T)
    out_ref[:] = packed.reshape(2 * grp_rows, T)


@functools.partial(jax.jit, static_argnames=("tile", "grp_rows", "interpret"))
def _pallas_apply_grouped(bms, gath, *, tile, grp_rows, interpret=False):
    G, cmax, n4 = gath.shape
    return pl.pallas_call(
        functools.partial(_gkernel, grp_rows=grp_rows),
        grid=(n4 // tile, G // 2),
        in_specs=[
            pl.BlockSpec((2, 32 * grp_rows, bms.shape[2]),
                         lambda t, g: (g, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((2, cmax, tile), lambda t, g: (g, 0, t),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((2 * grp_rows, tile), lambda t, g: (g, t),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((G * grp_rows, n4), jnp.int32),
        interpret=interpret,
    )(bms, gath)


def apply_operator(op: tuple, words) -> jax.Array:
    """(kin, N4) int32 -> (mout, N4) int32 by an applier's
    ``operator()``: the kernel its static spec names, its operands passed
    at run time.  Traceable (a caller's jit takes the spec as a static
    argument and the operands as arguments); N4 pads to a LANE multiple
    and back."""
    (kind, *spec), operands = op
    kin, n4 = words.shape
    pad = (-n4) % LANE
    if kind == "grouped":
        grp, interpret = spec
        bms, cols, rows = operands
        if pad:
            words = jnp.pad(words, ((0, 0), (0, pad)))
        out = _pallas_apply_grouped(
            bms, words[cols], tile=_pick_gtile(n4 + pad, cols.shape[1], grp),
            grp_rows=grp, interpret=interpret)[rows]
        return out[:, :n4] if pad else out
    kblk, kpad, interpret = spec
    (bm32,) = operands
    mout = bm32.shape[0] // 32
    if pad or kpad != kin:
        words = jnp.pad(words, ((0, kpad - kin), (0, pad)))
    # variant dispatch: alternate kernel formulations cover only the
    # unblocked contraction (kblocks == 1); blocked matrices keep the
    # production kernel
    if _encode_variant and kblk == kin:
        out = _pallas_apply_words_variant(
            bm32, words, tile=_pick_tile(n4 + pad, mout),
            variant=_encode_variant, interpret=interpret)
    else:
        out = _pallas_apply_words(
            bm32, words, tile=_pick_tile(n4 + pad, mout), kblk=kblk,
            interpret=interpret)
    return out[:, :n4] if pad else out


class PallasGroupedApply:
    """Sparse-grouped variant of PallasShardApply for repair operators.

    Same external contract ((k, N)/(B, k, C) uint8 in, parity rows out,
    bit-identical); internally gathers each row group's column support
    and runs a batched 128-row MXU contraction per group pair.
    """

    def __init__(self, coeff: np.ndarray, interpret: bool = False,
                 plan: GroupedPlan | None = None):
        self.plan = plan or GroupedPlan(coeff)
        if not self.plan.profitable:
            raise ValueError("matrix too dense for the grouped kernel")
        self.mout, self.kin = self.plan.mout, self.plan.kin
        self._bms_dev: jax.Array | None = None
        self._cols_dev: jax.Array | None = None
        self._rows_dev: jax.Array | None = None
        self.interpret = interpret

    def _bms_arg(self):
        arr, self._bms_dev = _device_cached(self.plan.bms, self._bms_dev)
        return arr

    def apply_words(self, words) -> jax.Array:
        """(k, N4) int32 -> (m, N4) int32; pads N4 to a LANE multiple."""
        kin, n4 = words.shape
        if kin != self.kin:
            raise ValueError(f"expected {self.kin} chunk rows, got {kin}")
        pad = (-n4) % LANE
        if pad:
            words = jnp.pad(words, ((0, 0), (0, pad)))
        plan = self.plan
        G = len(plan.groups)
        # fused single-launch path: whole bitmatrix resident, static
        # in-VMEM column selection, input read once — preferred
        # whenever the bitmatrix set fits (the paired fallback covers
        # huge supports)
        if G * 32 * plan.GRP_ROWS * 32 * plan.cmax <= (6 << 20):
            tile = _pick_fused_tile(n4 + pad, self.kin, plan.cmax,
                                    plan.GRP_ROWS, G)
            if tile >= LANE and (n4 + pad) % tile == 0:
                cols = tuple(tuple(int(c) for c in row)
                             for row in plan.cols)
                out = _pallas_apply_grouped_fused(
                    self._bms_arg(), words, tile=tile,
                    grp_rows=plan.GRP_ROWS, cols=cols,
                    interpret=self.interpret,
                )
                out = out[plan.gather_rows]
                return out[:, :n4] if pad else out
        out = apply_operator(self.operator(), words)
        return out[:, :n4] if pad else out

    def operator(self) -> tuple:
        """(spec, operands) for :func:`apply_operator`: the paired
        kernel, whose column gather and output row order are run-time
        operands too, so every matrix of one group geometry shares one
        compiled program (the fused kernel above selects its columns
        statically: one program per matrix)."""
        cols, self._cols_dev = _device_cached(self.plan.cols, self._cols_dev)
        rows, self._rows_dev = _device_cached(
            self.plan.gather_rows.astype(np.int32), self._rows_dev)
        return (("grouped", self.plan.GRP_ROWS, self.interpret),
                (self._bms_arg(), cols, rows))

    def __call__(self, data) -> jax.Array:
        data = jnp.asarray(data, jnp.uint8)
        if data.ndim == 2:
            return words_to_bytes(self.apply_words(bytes_to_words(data)))
        batch, kin, C = data.shape
        flat = jnp.transpose(data, (1, 0, 2)).reshape(kin, batch * C)
        par = words_to_bytes(self.apply_words(bytes_to_words(flat)))
        return jnp.transpose(
            par.reshape(self.mout, batch, C), (1, 0, 2)
        )


class PallasShardApply:
    """Apply a GF(2^8) coefficient matrix to shard-layout data on TPU.

    Caches the lane-expanded bitmatrix per coefficient matrix (the
    table-cache role of ErasureCodeIsaTableCache, reference
    ErasureCodeIsaTableCache.cc).
    """

    def __init__(self, coeff: np.ndarray, interpret: bool = False):
        coeff = np.asarray(coeff, np.uint8)
        self.mout, self.kin = coeff.shape
        if not shard_kernel_supported(self.kin, self.mout):
            raise ValueError(
                f"coefficient matrix {coeff.shape} too large for VMEM"
            )
        # The bitmatrix is a *runtime argument* of one module-level jit, so
        # one compiled kernel serves every coefficient matrix of the same
        # geometry (encode and all decode/repair matrices alike).  Kept as
        # numpy here; the device copy is cached lazily and only outside a
        # trace, so constructing the applier inside an outer jit never
        # leaks a tracer.
        bm32 = bm.expand_bitmatrix_lanes(bm.gf_matrix_to_bitmatrix(coeff))
        self.kblk = _pick_kblk(self.kin, self.mout)
        self.kpad = -(-self.kin // self.kblk) * self.kblk
        if self.kpad != self.kin:
            # zero-pad contraction columns to a whole number of blocks;
            # the matching zero data rows contribute nothing
            bm32 = np.pad(bm32, ((0, 0), (0, 32 * (self.kpad - self.kin))))
        self.bm32 = np.asarray(bm32, np.int8)
        self._bm32_dev: jax.Array | None = None
        self.interpret = interpret

    def _bm32_arg(self):
        arr, self._bm32_dev = _device_cached(self.bm32, self._bm32_dev)
        return arr

    def apply_words(self, words) -> jax.Array:
        """(k, N4) int32 -> (m, N4) int32; pads N4 to a LANE multiple."""
        kin, n4 = words.shape
        if kin != self.kin:
            raise ValueError(f"expected {self.kin} chunk rows, got {kin}")
        return apply_operator(self.operator(), words)

    def operator(self) -> tuple:
        """(spec, operands) for :func:`apply_operator`."""
        return (("dense", self.kblk, self.kpad, self.interpret),
                (self._bm32_arg(),))

    def apply_bytes(self, data) -> jax.Array:
        """(k, N) uint8 byte streams -> (m, N) uint8 parity streams."""
        data = jnp.asarray(data, jnp.uint8)
        if data.shape[0] != self.kin:
            raise ValueError(
                f"expected {self.kin} chunk rows, got {data.shape[0]}")
        return words_to_bytes(self.apply_words(bytes_to_words(data)))

    def __call__(self, data) -> jax.Array:
        """(k, N) or (B, k, C) uint8 -> same-layout parity bytes."""
        data = jnp.asarray(data, jnp.uint8)
        if data.ndim == 2:
            return self.apply_bytes(data)
        batch, kin, C = data.shape
        flat = jnp.transpose(data, (1, 0, 2)).reshape(kin, batch * C)
        par = self.apply_bytes(flat)
        return jnp.transpose(
            par.reshape(self.mout, batch, C), (1, 0, 2)
        )


class PallasBitplaneApply(PallasShardApply):
    """Back-compat name: stripe-batch (B, k, C) entry to the shard kernel."""
