"""The one traffic generator; each mix is a data file in perfbench/traffic/.

A mix is a JSON object:

  groups        list of client groups, each
                  op          "write" (whole objects) or "read"
                  clients     callers, each in a closed loop: it waits for
                              its reply before its next request
                  share       working set of the group, as a share of the
                              configuration's `working_set_bytes`; every
                              group has objects of its own
                  fill        write every object once in set-up
                  sample      for reads, the answers kept for the check
                              (a seeded reservoir over the whole window);
                              for writes, the objects whose stored copies
                              the check reads back (drawn from the seed;
                              0 or absent: every object written)
  fault         optional {"kill_data_slots": [...]}: fail-stop the nodes
                that hold these data slots of every stripe, in set-up
  why           one line: what the mix stands for

Object draws are uniform and come from --seed.  A writer owns every
`clients`-th object, so each object has one writer and the check knows
which version each copy should hold.
"""

from __future__ import annotations

import asyncio
import json
import time
from collections import Counter
from dataclasses import dataclass, field

from perfbench.data import Payloads, Reservoir, WriteBook, rng_for

GROUP_KEYS = {"op", "clients", "share", "fill", "sample"}
MIX_KEYS = {"groups", "fault", "why"}


@dataclass
class Group:
    index: int
    op: str
    share: float
    clients: int = 0
    fill: bool = False
    sample: int = 0
    objects: int = 0            # resolved against the configuration


@dataclass
class Mix:
    groups: list[Group]
    fault: dict = field(default_factory=dict)


def load_mix(path: str) -> Mix:
    with open(path) as f:
        raw = json.load(f)
    if set(raw) - MIX_KEYS or "groups" not in raw:
        raise ValueError(f"{path}: keys {sorted(raw)}, want {sorted(MIX_KEYS)}")
    groups = []
    for i, g in enumerate(raw["groups"]):
        if set(g) - GROUP_KEYS:
            raise ValueError(f"{path}: group {i} has keys {sorted(g)}")
        grp = Group(index=i, op=g["op"], share=g["share"],
                    clients=g.get("clients", 0), fill=g.get("fill", False),
                    sample=g.get("sample", 0))
        if grp.op not in ("write", "read"):
            raise ValueError(f"{path}: group {i}: op {grp.op!r}")
        if grp.op == "read" and not grp.fill:
            raise ValueError(f"{path}: group {i} reads objects never filled")
        if grp.clients <= 0:
            raise ValueError(f"{path}: group {i} offers no load")
        groups.append(grp)
    return Mix(groups=groups, fault=raw.get("fault") or {})


def resolve(mix: Mix, working_set_bytes: int, object_bytes: int) -> None:
    for g in mix.groups:
        g.objects = int(g.share * working_set_bytes) // object_bytes
        if g.objects < max(1, g.clients if g.op == "write" else 1):
            raise ValueError(f"group {g.index}: {g.objects} objects for "
                             f"{g.clients} writers")


@dataclass
class GroupStats:
    attempted: int = 0
    ok: int = 0
    bytes: int = 0
    latencies: list[float] = field(default_factory=list)
    errors: Counter = field(default_factory=Counter)

    @property
    def failed(self) -> int:
        return self.attempted - self.ok


class Load:
    """Runs the mix's groups against a deployment (see perfbench.sut)."""

    def __init__(self, mix: Mix, dep, payloads: Payloads, seed: int):
        self.mix = mix
        self.dep = dep
        self.payloads = payloads
        self.seed = seed
        self.books = {g.index: WriteBook() for g in mix.groups}
        self.samples = {g.index: Reservoir(g.sample, rng_for(seed, 1, g.index))
                        for g in mix.groups}
        self.stats = {g.index: GroupStats() for g in mix.groups}
        self.last_done = 0.0

    async def fill(self) -> None:
        """Write version 0 of every object of the groups that fill, with
        as many writers as the group has clients (retrying a failed write:
        a run with a hole in its data set cannot be measured)."""
        for g in self.mix.groups:
            if not g.fill:
                continue
            todo = list(range(g.objects))
            book = self.books[g.index]

            async def writer() -> None:
                while todo:
                    obj = todo.pop()
                    v = book.next_version(obj)
                    payload = self.payloads.make(obj, v)
                    for _ in range(3):
                        if await self.dep.write(g.index, obj, payload):
                            book.record(obj, v, True)
                            break
                    else:
                        raise RuntimeError(f"fill of object {obj} failed")

            await asyncio.gather(*(writer() for _ in range(g.clients)))

    async def _one(self, g: Group, obj: int) -> None:
        """One request, timed from its issue to its reply."""
        st = self.stats[g.index]
        st.attempted += 1
        ok = False
        answer = None
        err = "status"
        t_issue = time.perf_counter()
        try:
            if g.op == "write":
                book = self.books[g.index]
                v = book.next_version(obj)
                payload = self.payloads.make(obj, v)
                t_issue = time.perf_counter()
                try:
                    ok = await self.dep.write(g.index, obj, payload)
                finally:     # a write that raised may still have landed
                    book.record(obj, v, ok)
                nbytes = len(payload)
            else:
                ok, data, crcs = await self.dep.read(g.index, obj)
                nbytes = len(data)
                answer = (obj, data, crcs)
        except Exception as e:   # a failed request is counted, not fatal
            err = type(e).__name__
        t_done = time.perf_counter()
        self.last_done = max(self.last_done, t_done)
        st.latencies.append(t_done - t_issue)
        if ok:
            st.ok += 1
            st.bytes += nbytes
            if answer is not None:
                self.samples[g.index].offer(answer)
        else:
            st.errors[err] += 1

    async def _closed(self, g: Group, c: int, deadline: float) -> None:
        rng = rng_for(self.seed, 2, g.index, c)
        own = (range(c, g.objects, g.clients) if g.op == "write"
               else range(g.objects))
        while time.perf_counter() < deadline:
            await self._one(g, own[int(rng.integers(len(own)))])

    async def run(self, t0: float, seconds: float) -> float:
        """Run the mix's callers from `t0` for `seconds`; return when every
        request issued in that time has its reply, with the window's
        length: to the deadline, or to the last reply after it."""
        deadline = t0 + seconds
        await asyncio.gather(*(self._closed(g, c, deadline)
                               for g in self.mix.groups
                               for c in range(g.clients)))
        return max(deadline, self.last_done) - t0
