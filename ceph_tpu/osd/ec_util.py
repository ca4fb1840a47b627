"""EC stripe geometry + shard hash tracking.

- StripeInfo: the logical<->chunk offset math of ECUtil::stripe_info_t
  (reference osd/ECUtil.h:28-65: stripe_width/chunk_size invariants,
  logical_to_prev_chunk_offset :45, aligned conversions :60-65).
- stripe (de)composition driving batched device encode/decode — the role
  of ECUtil::encode/decode (reference osd/ECUtil.cc:123,12-109), except
  stripes are batched into ONE device launch instead of a per-stripe loop.
- HashInfo: per-shard cumulative crc32c persisted with each shard object
  (reference osd/ECUtil.cc:182, verified on shard reads by
  ECBackend::handle_sub_read, reference ECBackend.cc:1010).
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from ceph_tpu.common.crc32c import crc32c


@dataclass(frozen=True)
class StripeInfo:
    """Geometry of one EC pool: k chunks of chunk_size bytes per stripe."""

    k: int
    chunk_size: int

    @property
    def stripe_width(self) -> int:
        return self.k * self.chunk_size

    # -- logical (object) offsets <-> chunk offsets ----------------------
    def logical_to_prev_chunk_offset(self, offset: int) -> int:
        return (offset // self.stripe_width) * self.chunk_size

    def logical_to_next_chunk_offset(self, offset: int) -> int:
        return -(-offset // self.stripe_width) * self.chunk_size

    def logical_to_prev_stripe_offset(self, offset: int) -> int:
        return offset - (offset % self.stripe_width)

    def logical_to_next_stripe_offset(self, offset: int) -> int:
        return -(-offset // self.stripe_width) * self.stripe_width

    def aligned_logical_offset_to_chunk_offset(self, offset: int) -> int:
        if offset % self.stripe_width:
            raise ValueError(f"offset {offset} not stripe aligned")
        return offset // self.k

    def aligned_chunk_offset_to_logical_offset(self, offset: int) -> int:
        if offset % self.chunk_size:
            raise ValueError(f"offset {offset} not chunk aligned")
        return offset * self.k

    def offset_len_to_stripe_bounds(self, offset: int, length: int):
        """Expand [offset, offset+len) to stripe-aligned bounds."""
        start = self.logical_to_prev_stripe_offset(offset)
        end = self.logical_to_next_stripe_offset(offset + length)
        return start, end - start

    # -- stripe batching -------------------------------------------------
    def split_stripes(self, data: bytes | np.ndarray) -> np.ndarray:
        """Stripe-aligned logical bytes -> (num_stripes, k, chunk_size),
        the batch layout the device engine consumes."""
        arr = np.frombuffer(data, np.uint8) if isinstance(
            data, (bytes, bytearray, memoryview)
        ) else np.asarray(data, np.uint8)
        if arr.size % self.stripe_width:
            raise ValueError(
                f"{arr.size} bytes not a multiple of stripe width "
                f"{self.stripe_width}"
            )
        return arr.reshape(-1, self.k, self.chunk_size)

    def merge_stripes(self, stripes: np.ndarray) -> np.ndarray:
        """(num_stripes, k, chunk_size) -> flat logical bytes."""
        return np.ascontiguousarray(stripes, np.uint8).reshape(-1)

    def shard_bytes(self, chunks: np.ndarray) -> list[np.ndarray]:
        """(num_stripes, n, chunk_size) encoded batch -> per-shard
        contiguous byte streams (what each shard OSD persists)."""
        n = chunks.shape[1]
        return [np.ascontiguousarray(chunks[:, i]).reshape(-1)
                for i in range(n)]

    def stream_chunks(self, streams):
        """(n, num_stripes * chunk_size) per-shard byte streams -> the
        (num_stripes, n, chunk_size) encoded batch.  Array methods
        only, so device streams stay on device."""
        n = streams.shape[0]
        return streams.reshape(n, -1, self.chunk_size).transpose(1, 0, 2)


@dataclass
class HashInfo:
    """Per-shard cumulative crc32c + total size (ECUtil::HashInfo)."""

    n: int
    total_chunk_size: int = 0
    cumulative_shard_hashes: list[int] = field(default_factory=list)

    def __post_init__(self):
        if not self.cumulative_shard_hashes:
            self.cumulative_shard_hashes = [0xFFFFFFFF] * self.n

    def append(self, old_size: int, shard_chunks: list[bytes]) -> None:
        """Extend hashes with newly appended per-shard bytes; append-only
        (overwrites invalidate, as in the reference where hinfo is only
        maintained for append-style writes)."""
        if old_size != self.total_chunk_size:
            raise ValueError(
                f"append at {old_size} != current {self.total_chunk_size}"
            )
        if len(shard_chunks) != self.n:
            raise ValueError(f"need {self.n} shards")
        sizes = {len(c) for c in shard_chunks}
        if len(sizes) != 1:
            raise ValueError("shards must be equal length")
        for i, chunk in enumerate(shard_chunks):
            self.cumulative_shard_hashes[i] = crc32c(
                self.cumulative_shard_hashes[i], chunk
            )
        self.total_chunk_size += sizes.pop()

    def get_chunk_hash(self, shard: int) -> int:
        return self.cumulative_shard_hashes[shard]

    def to_dict(self) -> dict:
        return {
            "total_chunk_size": self.total_chunk_size,
            "cumulative_shard_hashes": list(self.cumulative_shard_hashes),
        }

    @classmethod
    def from_dict(cls, n: int, d: dict) -> "HashInfo":
        return cls(
            n,
            d["total_chunk_size"],
            list(d["cumulative_shard_hashes"]),
        )
