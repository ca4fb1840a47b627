"""TPU execution engine: GF(2) bitmatrix region ops as MXU matmuls.

The hot op of the whole framework (the analog of isa-l ``ec_encode_data`` /
``jerasure_matrix_encode`` — reference ErasureCodeIsa.cc:129,
ErasureCodeJerasure.cc:162): apply an (8m x 8k) GF(2) bitmatrix to byte
chunks.

Formulation (see bitmatrix.py): unpack bytes to bit planes, multiply the 0/1
planes with the 0/1 bitmatrix in bf16 on the MXU with exact f32 accumulation
(row sums <= 8k << 2^24, so every intermediate is an exactly-representable
integer), reduce mod 2, repack bytes. One compiled kernel serves encode AND
every decode/repair matrix of the same geometry, because the bitmatrix is a
runtime argument, not a compile-time constant.

Batching: stripes are a leading batch axis; multi-chip sharding shards that
axis (ceph_tpu.parallel).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ceph_tpu.ec import bitmatrix as bm


def bitplane_apply(bits_matrix: jax.Array, data: jax.Array) -> jax.Array:
    """(P, Q) bf16 0/1 matrix x (B, Q/8, C) uint8 -> (B, P/8, C) uint8.

    THE exactness-critical kernel: every execution path (single chip,
    shard_map bodies, Pallas comparisons) must call this one function so the
    corpus oracle covers them all. Traceable; callers jit it or call it
    inside their own jitted/shard_mapped code.
    """
    shifts = jnp.arange(8, dtype=jnp.uint8)
    bits = (data[:, :, None, :] >> shifts[None, None, :, None]) & 1
    batch, k, _, C = bits.shape
    bits = bits.reshape(batch, k * 8, C).astype(jnp.bfloat16)
    acc = jnp.einsum(
        "pq,bqc->bpc",
        bits_matrix,
        bits,
        preferred_element_type=jnp.float32,
    )
    pbits = acc.astype(jnp.int32) & 1
    pbits = pbits.reshape(batch, -1, 8, C)
    weights = (jnp.int32(1) << jnp.arange(8, dtype=jnp.int32))
    out = jnp.sum(pbits * weights[None, None, :, None], axis=2)
    return out.astype(jnp.uint8)


_apply_bitmatrix = jax.jit(bitplane_apply)


@functools.partial(jax.jit, static_argnums=(2,))
def packet_bitmatrix_apply(bits_matrix: jax.Array, data: jax.Array,
                           w: int) -> jax.Array:
    """(P, Q) bf16 0/1 bitmatrix x (B, Q/w chunks, C) uint8 -> (B, P/w, C)
    in PACKET layout: each chunk is w packets of C/w bytes; output packet
    r of chunk i is the GF(2) combination selected by bitmatrix row
    i*w + r (jerasure_schedule_encode semantics). Same MXU formulation
    as bitplane_apply — bytes unpack to bit planes, 0/1 matmul with f32
    accumulation, mod 2, repack — with the packet axis as the symbol
    axis instead of the in-byte bit axis."""
    B, k, C = data.shape
    pkt = C // w
    pk = data.reshape(B, k * w, pkt)
    shifts = jnp.arange(8, dtype=jnp.uint8)
    bits = ((pk[:, :, :, None] >> shifts[None, None, None, :]) & 1)
    bits = bits.reshape(B, k * w, pkt * 8).astype(jnp.bfloat16)
    acc = jnp.einsum(
        "pq,bqc->bpc", bits_matrix, bits,
        preferred_element_type=jnp.float32,
    )
    obits = (acc.astype(jnp.int32) & 1).reshape(B, -1, pkt, 8)
    weights = (jnp.int32(1) << jnp.arange(8, dtype=jnp.int32))
    by = jnp.sum(obits * weights[None, None, None, :], axis=3)
    return by.astype(jnp.uint8).reshape(B, -1, C)


def pow2_bucket(n: int) -> int:
    """Smallest power of two >= n (n >= 1).

    Shape-bucketing policy for batched device launches: every distinct
    leading batch dim B traces/compiles a fresh XLA program (jit caches
    are shape-keyed), so a workload with arbitrary stripe counts pays an
    unbounded compile stream.  Rounding B up to a power of two bounds the
    compiled-program population to ceil(log2(max B)) + 1 buckets per
    codec geometry while wasting < 2x compute worst-case — and GF matrix
    region ops are row-independent, so zero-padded rows never perturb
    real rows (bit-identity is preserved by construction)."""
    n = int(n)
    if n <= 1:
        return 1
    return 1 << (n - 1).bit_length()


def pad_batch_pow2(arr: np.ndarray) -> tuple[np.ndarray, int]:
    """Zero-pad the leading (batch/stripe) axis of ``arr`` up to its
    pow2_bucket.  Returns (padded, original_B); callers slice the result
    back to original_B rows.  No-op (no copy) when B is already a
    bucket size."""
    arr = np.asarray(arr, np.uint8)
    b = arr.shape[0]
    bp = pow2_bucket(b)
    if bp == b:
        return arr, b
    pad = np.zeros((bp - b,) + arr.shape[1:], np.uint8)
    return np.concatenate([arr, pad], axis=0), b


def pad_batch_pow2_device(arr) -> tuple[jax.Array, int]:
    """pad_batch_pow2 for a device-resident batch: the zero padding is
    allocated on device so the array never round-trips through host."""
    b = int(arr.shape[0])
    bp = pow2_bucket(b)
    if bp == b:
        return arr, b
    pad = jnp.zeros((bp - b,) + tuple(arr.shape[1:]), jnp.uint8)
    return jnp.concatenate([arr, pad], axis=0), b


def mesh_bucket(n: int, total_devices: int) -> int:
    """Batch bucket for a mesh-sharded launch: pow2_bucket rounded up to
    a whole number of device blocks, so the 'dp' split hands every mesh
    device the same stripe count.  With a power-of-two device count
    (every real TPU slice) this IS the pow2 bucket once B >= devices, so
    the compiled-program bound of pow2_bucket carries over unchanged."""
    bp = pow2_bucket(n)
    t = max(1, int(total_devices))
    if bp % t:
        bp = -(-bp // t) * t
    return bp


def pad_batch_to(arr, target: int):
    """Zero-pad the leading axis of a host OR device batch up to
    ``target`` rows (>= current B) without changing representation:
    numpy stays numpy, device arrays pad with device-allocated zeros
    (no host round trip).  Rows are independent under GF region ops, so
    padding preserves bit-identity of the real rows."""
    b = int(arr.shape[0])
    if target == b:
        return arr
    if isinstance(arr, np.ndarray):
        pad = np.zeros((target - b,) + arr.shape[1:], np.uint8)
        return np.concatenate([np.asarray(arr, np.uint8), pad], axis=0)
    pad = jnp.zeros((target - b,) + tuple(arr.shape[1:]), jnp.uint8)
    return jnp.concatenate([arr, pad], axis=0)


@functools.partial(jax.jit, static_argnums=(2,))
def split_shard_streams(chunks: jax.Array, off, b: int):
    """Stripes [off, off + b) of an encoded (Bp, n, C) device batch ->
    their (n, b*C) per-shard byte streams and the tuple of the n
    per-shard (b*C,) rows, as ONE program (no eager slice per shard).
    ``off`` is traced, so the batchmates of one coalesced launch that
    share a size share a program; jit's cache keys the rest (b, Bp, n,
    C)."""
    _, n, c = chunks.shape
    rows = jax.lax.dynamic_slice_in_dim(chunks, off, b)
    streams = rows.transpose(1, 0, 2).reshape(n, b * c)
    return streams, tuple(streams[i] for i in range(n))


def _default_use_pallas() -> bool:
    """Fused Pallas kernel on a TPU; XLA einsum elsewhere (CPU tests,
    interpret-mode covers the Pallas math there)."""
    return jax.default_backend() == "tpu"


class BitplaneEngine:
    """Caches device-resident bitmatrices and runs region ops.

    Plays the role of ErasureCodeIsaTableCache (reference
    src/erasure-code/isa/ErasureCodeIsaTableCache.cc): expanded operation
    tables cached per coefficient matrix, here as device arrays keyed by the
    matrix bytes.
    """

    def __init__(self, max_cached_matrices: int = 256,
                 use_pallas: bool | None = None):
        self._max = max_cached_matrices
        self._cache: dict[bytes, jax.Array] = {}
        self._np_cache: dict[bytes, np.ndarray] = {}
        self._pallas_cache: dict[bytes, object] = {}
        self._grouped_cache: dict[bytes, object] = {}
        self.use_pallas = (
            _default_use_pallas() if use_pallas is None else use_pallas
        )

    def _cached(self, cache: dict, coeff: np.ndarray, factory):
        """FIFO-bounded per-coefficient-matrix cache lookup."""
        key = coeff.tobytes() + repr(coeff.shape).encode()
        hit = cache.get(key)
        if hit is None:
            hit = factory(coeff)
            if len(cache) >= self._max:
                cache.pop(next(iter(cache)))
            cache[key] = hit
        return hit

    def _device_bitmatrix(self, coeff: np.ndarray) -> jax.Array:
        from ceph_tpu.common.jaxutil import outside_trace

        np_bits = self._cached(
            self._np_cache, coeff, bm.gf_matrix_to_bitmatrix
        )
        if not outside_trace():
            # Inside an outer trace: embed as a constant; caching a tracer
            # would poison later traces.
            return jnp.asarray(np_bits, jnp.bfloat16)
        return self._cached(
            self._cache,
            coeff,
            lambda c: jnp.asarray(np_bits, jnp.bfloat16),
        )

    def _pallas_applier(self, coeff: np.ndarray):
        from ceph_tpu.ec.pallas_kernels import PallasBitplaneApply

        return self._cached(self._pallas_cache, coeff, PallasBitplaneApply)

    def _grouped_applier(self, coeff: np.ndarray):
        """Sparse-grouped applier for repair operators, or None when the
        matrix is too dense/small for grouping to pay (cached either way)."""
        from ceph_tpu.ec.pallas_kernels import GroupedPlan, PallasGroupedApply

        def factory(c):
            plan = GroupedPlan(c)
            if not plan.profitable:
                return _NOT_GROUPABLE
            return PallasGroupedApply(c, plan=plan)

        hit = self._cached(self._grouped_cache, coeff, factory)
        return None if hit is _NOT_GROUPABLE else hit

    def apply(self, coeff: np.ndarray, data) -> jax.Array:
        """Apply a GF(2^8) coefficient matrix (m, k) to data (B, k, C)."""
        from ceph_tpu.ec.pallas_kernels import (
            LANE_BYTES,
            shard_kernel_supported,
        )

        coeff = np.asarray(coeff, np.uint8)
        data = jnp.asarray(data, jnp.uint8)
        if self.use_pallas and data.shape[-1] % LANE_BYTES == 0:
            grouped = self._grouped_applier(coeff)
            if grouped is not None:
                return grouped(data)
            if shard_kernel_supported(coeff.shape[1], coeff.shape[0]):
                return self._pallas_applier(coeff)(data)
        mat = self._device_bitmatrix(coeff)
        if data.ndim == 2:
            return _apply_bitmatrix(mat, data[None])[0]
        return _apply_bitmatrix(mat, data)

    def _word_applier(self, coeff: np.ndarray):
        """The word kernel for ``coeff``: the grouped applier where
        ``GroupedPlan.profitable``, else the dense shard applier where it
        fits, else None (the bitplane einsum)."""
        from ceph_tpu.ec.pallas_kernels import shard_kernel_supported

        if self.use_pallas:
            grouped = self._grouped_applier(coeff)
            if grouped is not None:
                return grouped
            if shard_kernel_supported(coeff.shape[1], coeff.shape[0]):
                return self._pallas_applier(coeff)
        return None

    def apply_words(self, coeff: np.ndarray, words) -> jax.Array:
        """Word-typed hot path: (k, N4) int32 lanes -> (m, N4) int32.

        Device-resident buffers stay int32 end-to-end (no uint8 relayout
        pass); use pallas_kernels.bytes_to_words/words_to_bytes at the
        boundaries."""
        from ceph_tpu.ec.pallas_kernels import bytes_to_words, words_to_bytes

        coeff = np.asarray(coeff, np.uint8)
        applier = self._word_applier(coeff)
        if applier is not None:
            return applier.apply_words(jnp.asarray(words))
        mat = self._device_bitmatrix(coeff)
        by = words_to_bytes(jnp.asarray(words))
        return bytes_to_words(_apply_bitmatrix(mat, by[None])[0])

    def operator(self, coeff: np.ndarray) -> tuple:
        """``coeff`` prepared for :func:`apply_operator` inside a caller's
        jitted program: ``(spec, operands)``, the spec static (the kernel
        ``apply_words`` picks, and its geometry) and the operands device
        arrays passed at run time, so every matrix of one spec shares one
        compiled program whatever its coefficients (the grouped kernel in
        its paired form: its column gather is an operand too)."""
        coeff = np.asarray(coeff, np.uint8)
        applier = self._word_applier(coeff)
        if applier is not None:
            return applier.operator()
        return ("xla",), (self._device_bitmatrix(coeff),)

    def _device_raw_bitmatrix(self, BM: np.ndarray) -> jax.Array:
        from ceph_tpu.common.jaxutil import outside_trace

        if not outside_trace():
            return jnp.asarray(BM, jnp.bfloat16)
        return self._cached(
            self._cache, BM, lambda b: jnp.asarray(b, jnp.bfloat16)
        )

    def apply_packets(self, BM: np.ndarray, data, w: int) -> jax.Array:
        """Apply a RAW GF(2) bitmatrix (rows, k*w) in packet layout to
        data (B, k, C) with C % w == 0 (the bit-schedule code path:
        liberation / blaum_roth / liber8tion / w=16,32 RS).

        Fast path: an XOR schedule over packets IS a GF(2^8) coefficient
        matrix with entries in {0, 1} acting on packet rows (coefficient
        1 = the 8x8 identity bitmatrix), so the data reshaped to
        (B, k*w, C/w) packet rows feeds the same Pallas shard kernel as
        the GF(2^8) codes — int32 lanes, int8 MXU contraction, no bf16
        bit-plane inflation.  Wide matrices (w=16/32 RS) run blocked
        over the contraction dim."""
        from ceph_tpu.ec.pallas_kernels import shard_kernel_supported

        BM = np.asarray(BM, np.uint8)
        data = jnp.asarray(data, jnp.uint8)
        squeeze = data.ndim == 2
        if squeeze:
            data = data[None]
        B, k, C = data.shape
        pkt = C // w
        rows = BM.shape[0]
        if (
            self.use_pallas
            and pkt % 4 == 0
            and rows % w == 0
            and shard_kernel_supported(BM.shape[1], rows)
        ):
            applier = self._pallas_applier(BM)
            flat = data.reshape(B, k * w, pkt)
            flat = jnp.transpose(flat, (1, 0, 2)).reshape(k * w, B * pkt)
            par = applier(flat)                      # (rows, B*pkt) bytes
            out = jnp.transpose(
                par.reshape(rows, B, pkt), (1, 0, 2)
            ).reshape(B, rows // w, C)
            return out[0] if squeeze else out
        mat = self._device_raw_bitmatrix(BM)
        out = packet_bitmatrix_apply(mat, data, w)
        return out[0] if squeeze else out

    def encode_shards(self, generator: np.ndarray, data) -> jax.Array:
        """Systematic shard-layout encode: (k, N) -> (k+m, N).

        Shard layout = chunk row i is shard i's contiguous byte stream
        (chunk i of stripe s at columns [s*C, (s+1)*C) — the ECUtil
        stripe decomposition, reference ECUtil.h:28-65).  The Pallas fast
        path runs on this layout natively with no transpose.
        """
        k = generator.shape[1]
        data = jnp.asarray(data, jnp.uint8)
        parity = self.apply(generator[k:], data)
        return jnp.concatenate([data, parity], axis=0)

    def encode(self, generator: np.ndarray, data) -> jax.Array:
        """Systematic encode: (B, k, C) -> (B, k+m, C) (data || parity)."""
        k = generator.shape[1]
        data = jnp.asarray(data, jnp.uint8)
        squeeze = data.ndim == 2
        if squeeze:
            data = data[None]
        parity = self.apply(generator[k:], data)
        out = jnp.concatenate([data, parity], axis=-2)
        return out[0] if squeeze else out


_NOT_GROUPABLE = object()


@functools.cache
def default_engine() -> BitplaneEngine:
    return BitplaneEngine()


def apply_operator(op: tuple, words) -> jax.Array:
    """(kin, N4) int32 lanes -> (mout, N4) int32 by the ``(spec,
    operands)`` of :meth:`BitplaneEngine.operator`.  Traceable."""
    from ceph_tpu.ec import pallas_kernels as pk

    (kind, *_), operands = op
    if kind != "xla":
        return pk.apply_operator(op, words)
    by = bitplane_apply(operands[0], pk.words_to_bytes(words)[None])[0]
    return pk.bytes_to_words(by)
