"""Seeded inputs, made before the cluster starts.

A pool of random base buffers comes from --seed (generating a GiB would
hold the interpreter for seconds on the loop every node shares).  The
payload of write `version` of object `obj` is one of them with a 16-byte
stamp at the start of every chunk: (seed, obj, version * 16 + chunk).  So
every write of a run, and every chunk of a stripe, has bytes of its own,
and a stale, misplaced or lost write reads back as wrong.
"""

from __future__ import annotations

import struct

import numpy as np

MiB = 1 << 20
STAMP = struct.Struct("<QII")
POOL_BYTES = 64 * MiB
SEED_MASK = (1 << 64) - 1


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """An independent generator per (seed, stream...) pair."""
    return np.random.default_rng([seed & SEED_MASK, *stream])


class Payloads:
    def __init__(self, seed: int, object_bytes: int, chunk_size: int):
        if object_bytes % chunk_size or chunk_size <= STAMP.size:
            raise ValueError(f"object of {object_bytes} bytes is not whole "
                             f"chunks of {chunk_size}")
        rng = rng_for(seed, 0)
        n = max(2, POOL_BYTES // object_bytes)
        self.bases = [rng.bytes(object_bytes) for _ in range(n)]
        self.seed = seed & SEED_MASK
        self.object_bytes = object_bytes
        self.chunk_size = chunk_size

    def make(self, obj: int, version: int) -> bytes:
        base = memoryview(self.bases[(obj * 31 + version) % len(self.bases)])
        parts = []
        for i, off in enumerate(range(0, self.object_bytes, self.chunk_size)):
            parts.append(STAMP.pack(self.seed, obj, version * 16 + i))
            parts.append(base[off + STAMP.size: off + self.chunk_size])
        return b"".join(parts)


class WriteBook:
    """Which version of each object a check should find stored.

    Writes to one object are serial (the traffic keeps one writer per
    object), so the newest acknowledged version is what every copy holds,
    unless a later write failed: its bytes may or may not have landed."""

    def __init__(self):
        self.issued: dict[int, int] = {}
        self.acked: dict[int, int] = {}
        self.doubt: dict[int, list[int]] = {}

    def next_version(self, obj: int) -> int:
        v = self.issued.get(obj, -1) + 1
        self.issued[obj] = v
        return v

    def record(self, obj: int, version: int, ok: bool) -> None:
        if ok:
            self.acked[obj] = version
            self.doubt.pop(obj, None)
        else:
            self.doubt.setdefault(obj, []).append(version)

    def candidates(self, obj: int) -> list[int]:
        """Versions whose bytes a stored copy may hold; empty when the
        object was never acknowledged."""
        if obj not in self.acked:
            return []
        return [self.acked[obj]] + self.doubt.get(obj, [])


class Reservoir:
    """A uniform sample of at most `cap` items, the choices drawn from a
    seeded generator."""

    def __init__(self, cap: int, rng: np.random.Generator):
        self.cap = cap
        self.rng = rng
        self.items: list = []
        self.seen = 0

    def offer(self, item) -> None:
        self.seen += 1
        if len(self.items) < self.cap:
            self.items.append(item)
            return
        j = int(self.rng.integers(self.seen))
        if j < self.cap:
            self.items[j] = item
