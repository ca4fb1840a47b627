"""BASELINE config #4: CLAY sub-chunk repair as mesh collectives.

CLAY k=8 m=4 d=11 single-chunk repair reads only sub_chunk_no/q of each of
the d helper chunks (reference ErasureCodeClay.cc:462-646,
get_repair_subchunks :366-380).  On a device mesh the helper reads become
ICI collectives: each 'cs'-group device holds a slice of the chunk axis,
extracts just the repair planes (1/q of its bytes — the regenerating-code
bandwidth saving rides the interconnect), and an all_gather assembles the
helper set per group.  The repair schedule itself is a fixed GF(2^8)-linear
map (ceph_tpu.ec.repair_operator), so the post-gather compute is ONE
bitplane-engine apply — no per-plane scalar passes on device.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ceph_tpu.ec.engine import default_engine
from ceph_tpu.ec.repair_operator import clay_repair_operator

from jax import shard_map


def sharded_clay_repair(mesh, ec, chunks, lost: int) -> jax.Array:
    """Repair chunk ``lost`` of a (B, k+m, C) encoded batch over the mesh.

    The chunk axis is sharded over 'cs' (each device holds (k+m)/cs shard
    columns), the stripe batch over 'dp'.  Returns (B, C) recovered
    chunks, bit-identical to the single-device plugin repair.
    """
    chunks = jnp.asarray(chunks, jnp.uint8)
    B, n, C = chunks.shape
    cs = mesh.shape["cs"]
    if n % cs:
        raise ValueError(f"k+m={n} must be divisible by cs={cs}")
    if C % ec.sub_chunk_no:
        raise ValueError(f"C={C} not a multiple of {ec.sub_chunk_no}")
    R, helpers, planes = clay_repair_operator(ec, lost)
    eng = default_engine()
    planes_np = np.asarray(planes, np.int64)
    helpers_np = np.asarray(helpers, np.int64)
    sub = ec.sub_chunk_no
    d, pcnt = len(helpers), len(planes)

    spec = P("dp", "cs", None)
    dev = jax.device_put(chunks, NamedSharding(mesh, spec))

    @jax.jit
    def step(ch):
        def body(blk):  # (b, n/cs, C) per device
            b = blk.shape[0]
            local = blk.reshape(b, n // cs, sub, C // sub)
            # Repair-plane extraction BEFORE the collective: only 1/q of
            # the helper bytes ride the ICI all_gather.
            local = local[:, :, planes_np]  # (b, n/cs, P, sc)
            full = jax.lax.all_gather(local, "cs", axis=1, tiled=True)
            helper = full[:, helpers_np]  # (b, d, P, sc) — drops the lost
            flat = helper.reshape(b, d * pcnt, C // sub)
            # Engine dispatch: Pallas shard kernel on TPU (int32 lanes,
            # int8 MXU), bit-identical XLA einsum elsewhere.
            rec = eng.apply(R, flat)  # (b, sub, sc)
            return rec.reshape(b, C)

        return shard_map(
            body, mesh=mesh, in_specs=spec, out_specs=P("dp", None),
            check_vma=False,
        )(ch)

    return step(dev)


def clay_plane_ranges(planes, sc: int) -> list[tuple[int, int]]:
    """Coalesce repair-plane indices into (offset, length) byte ranges
    inside ONE stripe's chunk bytes (the (sub_chunk_no, sc) layout).

    The repair engine reads survivor shards by these ranges instead of
    whole chunks — consecutive planes merge into one ranged read, so a
    q=4 profile issues at most sub_chunk_no/q reads per helper stripe
    and ships exactly 1/q of the helper's bytes."""
    runs: list[tuple[int, int]] = []
    start = prev = None
    for p in sorted(int(x) for x in planes):
        if prev is not None and p == prev + 1:
            prev = p
            continue
        if start is not None:
            runs.append((start * sc, (prev - start + 1) * sc))
        start = prev = p
    if start is not None:
        runs.append((start * sc, (prev - start + 1) * sc))
    return runs


def batched_clay_plane_repair(ec, R, helper_planes) -> np.ndarray:
    """Recover a batch of lost chunks from pre-extracted helper planes.

    ``helper_planes``: (b, d*P, sc) uint8 — each row stacks the d
    helpers' P repair planes in helper-ascending order (the layout
    ``clay_repair_operator`` probed R against).  Returns (b, C)
    recovered chunks, bit-identical to the plugin repair.  ONE engine
    apply for the whole batch — the repair engine's CLAY decode."""
    helper_planes = np.asarray(helper_planes, np.uint8)
    if helper_planes.ndim != 3:
        raise ValueError(
            f"helper_planes shape {helper_planes.shape} != (b, d*P, sc)"
        )
    b, _, sc = helper_planes.shape
    rec = default_engine().apply(np.asarray(R, np.uint8), helper_planes)
    return np.asarray(rec, np.uint8).reshape(b, ec.sub_chunk_no * sc)


def clay_repair_ici_bytes(ec, n_helpers: int, batch: int,
                          chunk_size: int) -> tuple[int, int]:
    """(moved, whole) modeled interconnect bytes for one sub-chunk
    repair launch of ``batch`` stripes.

    moved: what the plane-extracted all_gather above actually ships —
    each of the d helpers contributes only its repair planes, 1/q of
    its bytes (the regenerating-code saving).  whole: the counterfactual
    a classic RS decode moves — k full survivor chunks to the repair
    site.  Deterministic on CPU, so A/B gates read the counters without
    a chip; the ratio is q*k/d >= 2 for every supported CLAY profile.
    """
    moved = n_helpers * batch * (chunk_size // ec.q)
    whole = ec.k * batch * chunk_size
    return moved, whole


def sharded_clay_repair_check(mesh) -> None:
    """Dryrun/test probe: encode, repair over the mesh, verify bit-identity
    against the encoded chunk and the single-device plugin repair."""
    from ceph_tpu.ec.registry import ErasureCodePluginRegistry

    ec = ErasureCodePluginRegistry().factory(
        "clay", {"k": "8", "m": "4", "d": "11"}
    )
    dp = mesh.shape["dp"]
    B = 2 * dp
    sc = 4
    C = ec.sub_chunk_no * sc
    rng = np.random.default_rng(11)
    data = rng.integers(0, 256, (B, ec.k, C), np.uint8)
    chunks = ec.encode_chunks_batch(data)
    lost = 3
    got = np.asarray(sharded_clay_repair(mesh, ec, chunks, lost))
    if not np.array_equal(got, chunks[:, lost]):
        raise AssertionError("sharded clay repair diverged from encode")
    # Cross-check one stripe against the plugin's host repair path.
    minimum = ec.minimum_to_decode(
        [lost], [i for i in range(ec.get_chunk_count()) if i != lost]
    )
    planes = ec._repair_planes(ec._node_of(lost))
    helper_bytes = {
        h: np.ascontiguousarray(
            chunks[0, h].reshape(ec.sub_chunk_no, sc)[planes]
        ).tobytes()
        for h in minimum
    }
    host = ec._repair([lost], helper_bytes, chunk_size=C)
    if host[lost] != chunks[0, lost].tobytes():
        raise AssertionError("plugin clay repair diverged from encode")
