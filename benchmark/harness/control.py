"""The control: the plain reference put in the cluster's place, with one
guarantee of the configuration broken.

It stores each object as the reference's k+m shards on a seeded
placement over the configuration's OSDs and serves the client API the
window drives.  Each mode breaks one stated guarantee, the step a later
change might be tempted to take:

- ``ack_early``: a write is acknowledged once the k data shards are
  committed; the parity shards are never committed (write-back).
- ``stale_read``: a read returns the state before the newest
  acknowledged write (a cache that is not invalidated).
- ``skip_decode``: with an OSD down, a read returns the lost data chunk
  as zeros instead of rebuilding it.

``mode=None`` is the sound reference store, which the check must pass.
"""

from __future__ import annotations

import asyncio

import numpy as np

from harness import reference

MODES = ("ack_early", "stale_read", "skip_decode")


class ControlSUT:
    def __init__(self, cfg: dict, mode: str | None):
        if mode is not None and mode not in MODES:
            raise ValueError(f"unknown control mode {mode!r}")
        self.cfg, self.mode = cfg, mode
        p = cfg["pool"]["profile"]
        self.k, self.m = int(p["k"]), int(p["m"])
        self.unit = int(p["stripe_unit"])
        self.poly = int(cfg["code"]["polynomial"])
        self.osds = int(cfg["osds"])
        self.store: dict[str, tuple[int, list]] = {}     # name -> (size, shards)
        self.previous: dict[str, bytes] = {}
        self.down: set[int] = set()

    async def start(self) -> None:
        pass

    async def stop(self) -> None:
        pass

    async def warm_launches(self, mix: dict) -> int:
        return 0

    def acting(self, name: str) -> list[int]:
        rng = np.random.default_rng(list(name.encode()))
        return [int(o) for o in rng.permutation(self.osds)[:self.k + self.m]]

    async def write(self, name: str, data: bytes) -> None:
        await asyncio.sleep(0)
        shards = reference.encode(data, self.k, self.m, self.unit, self.poly)
        if self.mode == "ack_early":
            shards = shards[:self.k] + [None] * self.m
        if name in self.store:
            self.previous[name] = self._join(name)
        self.store[name] = (len(data), shards)

    def _join(self, name: str, zero: int | None = None) -> bytes:
        size, shards = self.store[name]
        rows = [np.frombuffer(s, np.uint8) for s in shards[:self.k]]
        if zero is not None:
            rows[zero] = np.zeros_like(rows[zero])
        stripes = np.stack(rows).reshape(self.k, -1, self.unit)
        return stripes.transpose(1, 0, 2).tobytes()[:size]

    async def read(self, name: str) -> bytes:
        await asyncio.sleep(0)
        if self.mode == "stale_read":
            # the state before the newest acknowledged write: the older
            # version, or no object at all before the first
            return self.previous.get(name, b"")
        lost = None
        if self.mode == "skip_decode":
            acting = self.acting(name)
            lost = next((i for i in range(self.k) if acting[i] in self.down),
                        None)
        return self._join(name, zero=lost)

    def shards(self, name: str) -> dict:
        return dict(enumerate(self.store[name][1]))

    async def kill(self, victim: int) -> None:
        self.down.add(victim)

    def counters(self) -> dict:
        return {}
