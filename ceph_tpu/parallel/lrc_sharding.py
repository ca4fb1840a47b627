"""BASELINE config #5: LRC group-local all_gather repair over the mesh.

An lrc kml profile places every chunk in a local group of l+1 members; a
single lost chunk repairs from its group alone (cheapest-layer decode,
reference ErasureCodeLrc.cc:566-735 minimum_to_decode + decode).  On a
device mesh each group's chunks are split over a dedicated 'gs' sub-axis,
so the repair all_gather runs ONLY inside the group (a named-sub-axis
collective = XLA axis_index_groups), never across groups — the locality
that makes LRC repair cheap rides the interconnect topology.

BASELINE.md names k=12 m=4 l=3; the kml form requires l | k+m (reference
ErasureCodeLrc.cc:305 and _parse_kml here), and 16 % 3 != 0, so the
nearest valid profile k=12 m=4 l=4 (archived in the corpus) is used.

The cheapest-layer decode is a fixed GF(2^8)-linear map of the group
members (ceph_tpu.ec.repair_operator.lrc_repair_operator), so post-gather
compute is one bitplane apply.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ceph_tpu.ec.engine import default_engine
from ceph_tpu.ec.repair_operator import lrc_repair_operator

from jax import shard_map

# Profile used by sharded_lrc_repair_check (and the dryrun gate): 4 local
# groups of l+1 = 5 chunks.  Callers needing the device-count constraint
# use LRC_CHECK_GROUPS rather than re-deriving it.
LRC_CHECK_PROFILE = {"k": "12", "m": "4", "l": "4"}
LRC_CHECK_GROUPS = 4


def make_group_mesh(devices, groups: int) -> Mesh:
    """Mesh ('dp', 'grp', 'gs'): one 'grp' row per LRC local group, the
    group's chunks split over 'gs' devices."""
    devices = list(devices)
    n = len(devices)
    if n % groups:
        raise ValueError(f"{groups} LRC groups must divide {n} devices")
    gs = n // groups
    arr = np.array(devices).reshape(1, groups, gs)
    return Mesh(arr, ("dp", "grp", "gs"))


def sharded_lrc_repair(mesh, ec, chunks, lost: int) -> np.ndarray:
    """Repair chunk ``lost`` of a (B, n, C) encoded batch; group-local.

    Returns (B, C), bit-identical to the plugin's cheapest-layer decode.
    """
    chunks = jnp.asarray(chunks, jnp.uint8)
    B, n, C = chunks.shape
    groups = mesh.shape["grp"]
    gs = mesh.shape["gs"]
    if n % groups:
        raise ValueError(f"chunk count {n} must split into {groups} groups")
    per_group = n // groups
    gpad = -(-per_group // gs) * gs  # pad so 'gs' divides the group slice
    g_lost = lost // per_group

    coeffs, minimum = lrc_repair_operator(ec, lost)
    # Lift the minimum-chunk coefficients onto the padded group slots.
    row = np.zeros((1, gpad), np.uint8)
    for j, cid in enumerate(minimum):
        if cid // per_group != g_lost:
            raise ValueError(
                f"minimum chunk {cid} outside lost group {g_lost}; "
                "profile is not group-local"
            )
        row[0, cid - g_lost * per_group] = coeffs[0, j]
    eng = default_engine()

    padded = jnp.zeros((B, groups, gpad, C), jnp.uint8)
    padded = padded.at[:, :, :per_group].set(
        chunks.reshape(B, groups, per_group, C)
    )
    dev = jax.device_put(
        padded.reshape(B, groups, gs, gpad // gs, C),
        NamedSharding(mesh, P("dp", "grp", "gs", None, None)),
    )

    @jax.jit
    def step(ch):
        def body(blk):  # (b, 1, 1, gpad/gs, C)
            b = blk.shape[0]
            # Group-local collective: gathers ONLY over this group's 'gs'
            # devices; other groups' chunks never move.
            grp = jax.lax.all_gather(
                blk[:, 0, 0], "gs", axis=1, tiled=True
            )  # (b, gpad, C)
            # Engine dispatch: Pallas shard kernel on TPU, einsum on CPU.
            rec = eng.apply(row, grp)  # (b, 1, C)
            return rec[:, None]  # (b, 1, 1, C)

        return shard_map(
            body,
            mesh=mesh,
            in_specs=P("dp", "grp", "gs", None, None),
            out_specs=P("dp", "grp", "gs", None),
            check_vma=False,
        )(ch)

    # Slice on device: only the lost group's recovered chunks ever leave
    # the mesh (the gs rows are identical; take the first).
    return np.asarray(step(dev)[:, g_lost, 0])


def batched_lrc_group_repair(ec, coeffs, group_chunks) -> np.ndarray:
    """Recover a batch of lost chunks from their local-group members.

    ``group_chunks``: (b, L, C) uint8 — the ``minimum`` chunks of each
    stripe in ``lrc_repair_operator`` order.  Returns (b, C), bit-
    identical to the plugin's cheapest-layer decode.  ONE engine apply
    for the whole batch — the repair engine's LRC decode (only the
    local group was ever read; the k-L remote chunks never moved)."""
    group_chunks = np.asarray(group_chunks, np.uint8)
    if group_chunks.ndim != 3:
        raise ValueError(
            f"group_chunks shape {group_chunks.shape} != (b, L, C)"
        )
    rec = default_engine().apply(
        np.asarray(coeffs, np.uint8), group_chunks)
    return np.asarray(rec, np.uint8).reshape(
        group_chunks.shape[0], group_chunks.shape[2])


def lrc_repair_ici_bytes(ec, n_helpers: int, batch: int,
                         chunk_size: int) -> tuple[int, int]:
    """(moved, whole) modeled interconnect bytes for one group-local
    repair launch of ``batch`` stripes.

    moved: the group-local all_gather ships only the lost chunk's l
    group members (``n_helpers`` = the minimum_to_decode set).  whole:
    the counterfactual a non-locality-aware decode moves — k full
    survivor chunks.  Ratio k/l >= 2 for every kml profile worth
    deploying (locality below that defeats LRC's point)."""
    moved = n_helpers * batch * chunk_size
    whole = ec.get_data_chunk_count() * batch * chunk_size
    return moved, whole


def sharded_lrc_repair_check(mesh_or_devices) -> None:
    """Dryrun/test probe: kml LRC repair over a group-local mesh."""
    from ceph_tpu.ec.registry import ErasureCodePluginRegistry

    devices = (
        list(np.asarray(mesh_or_devices.devices).ravel())
        if isinstance(mesh_or_devices, Mesh)
        else list(mesh_or_devices)
    )
    ec = ErasureCodePluginRegistry().factory("lrc", LRC_CHECK_PROFILE)
    n = ec.get_chunk_count()
    groups = len(ec.layers) - 1  # one local layer per group
    assert groups == LRC_CHECK_GROUPS, "profile/constant drifted"
    if len(devices) % groups:
        raise ValueError(
            f"need a multiple of {groups} devices, got {len(devices)}"
        )
    mesh = make_group_mesh(devices, groups)
    C = ec.get_chunk_size(12 * 64)
    rng = np.random.default_rng(13)
    B = 4
    data = rng.integers(0, 256, (B, ec.get_data_chunk_count(), C), np.uint8)
    chunks = ec.encode_chunks_batch(data)
    for lost in (0, 6):
        got = sharded_lrc_repair(mesh, ec, chunks, lost)
        if not np.array_equal(got, np.asarray(chunks)[:, lost]):
            raise AssertionError(
                f"sharded lrc repair of chunk {lost} diverged"
            )
