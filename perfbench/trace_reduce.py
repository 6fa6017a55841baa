"""From a JAX profiler trace to device busy and idle time, per-program
device time, and the longest idle gaps.

`load` reads an `.xplane.pb` with jax.profiler.ProfileData.  Device planes
are `/device:TPU:<n>`; on each, the "XLA Ops" and "Async XLA Ops" lines
hold one event per operation run on the chip (named by its HLO text,
`%raw_crc.1 = s32[...] custom-call(...)`), and the "XLA Modules" line one
event per program launch, named after the jitted function
(`jit_<name>(<fingerprint>)`, one fingerprint per compiled shape).  The
traced window is the host event named WINDOW_MARK, which the harness
opens and closes on its event loop thread.  `reduce` needs nothing of JAX,
so the tests check it on a trace recorded on the chip.

Busy time is the union of the operation intervals on a device, clipped to
the window; the idle share is 1 - busy / window; busy is averaged over the
devices in the trace.
"""

from __future__ import annotations

import bisect
import re
from dataclasses import dataclass, field

WINDOW_MARK = "perfbench_window"
OPS_LINES = ("XLA Ops", "Async XLA Ops")
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")

Interval = tuple[str, int, int]          # (name, start ns, end ns)


@dataclass
class DeviceEvents:
    plane: str
    ops: list[Interval] = field(default_factory=list)
    modules: list[Interval] = field(default_factory=list)


@dataclass
class TraceEvents:
    devices: list[DeviceEvents]
    window: tuple[int, int] | None       # the WINDOW_MARK host event


def load(path: str) -> TraceEvents:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, window = [], None
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            dev = DeviceEvents(plane.name)
            for line in plane.lines:
                if line.name in OPS_LINES:
                    dest = dev.ops
                elif line.name == MODULES_LINE:
                    dest = dev.modules
                else:
                    continue
                dest += [(e.name, int(e.start_ns), int(e.end_ns))
                         for e in line.events]
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW_MARK:
                        window = (int(e.start_ns), int(e.end_ns))
    return TraceEvents(devices, window)


def _clip(ivs: list[Interval], lo: int, hi: int) -> list[Interval]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in ivs
            if e > lo and s < hi]


def _union(ivs: list[Interval]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for _n, s, e in sorted(ivs, key=lambda iv: iv[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def base_name(name: str) -> str:
    """`jit_step(123)` -> `jit_step`: one name per jitted function."""
    return re.sub(r"\(\d+\)$", "", name)


def op_name(name: str, modules: list[Interval], start: int) -> str:
    """`%raw_crc.1 = s32[...] ...` inside a `jit_raw_crc` launch ->
    `jit_raw_crc/%raw_crc.1`."""
    short = name.split(" = ", 1)[0]
    i = bisect.bisect_right(modules, start, key=lambda m: m[1]) - 1
    if i >= 0 and modules[i][2] >= start:
        return f"{base_name(modules[i][0])}/{short}"
    return short


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float                                   # mean over devices
    programs: dict[str, float]                      # base name -> device s
    launches: dict[str, int]                        # base name -> count
    ops: dict[str, float]                           # op name -> device s
    gaps: list[tuple[int, int]]                     # device 0, longest first
    window_ns: tuple[int, int] = (0, 0)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def program_seconds(self, pattern: str) -> float:
        rx = re.compile(pattern)
        return sum(s for n, s in self.programs.items() if rx.search(n))

    def program_launches(self, pattern: str) -> int:
        rx = re.compile(pattern)
        return sum(c for n, c in self.launches.items() if rx.search(n))


def reduce(ev: TraceEvents) -> TraceSummary | None:
    """None when the trace has no device plane or no window mark."""
    if not ev.devices or ev.window is None:
        return None
    lo, hi = ev.window
    busy = 0
    programs: dict[str, float] = {}
    launches: dict[str, int] = {}
    ops: dict[str, float] = {}
    gaps: list[tuple[int, int]] = []
    for i, dev in enumerate(ev.devices):
        union = _union(_clip(dev.ops, lo, hi))
        busy += sum(e - s for s, e in union)
        for n, s, e in _clip(dev.modules, lo, hi):
            b = base_name(n)
            programs[b] = programs.get(b, 0.0) + (e - s) / 1e9
            launches[b] = launches.get(b, 0) + 1
        mods = sorted(dev.modules, key=lambda m: m[1])
        for n, s, e in _clip(dev.ops, lo, hi):
            name = op_name(n, mods, s)
            ops[name] = ops.get(name, 0.0) + (e - s) / 1e9
        if i == 0:
            edges = [lo] + [t for iv in union for t in iv] + [hi]
            gaps = sorted(((edges[j], edges[j + 1])
                           for j in range(0, len(edges), 2)
                           if edges[j + 1] > edges[j]),
                          key=lambda g: g[0] - g[1])
    return TraceSummary(window_s=(hi - lo) / 1e9,
                        busy_s=busy / len(ev.devices) / 1e9,
                        programs=programs, launches=launches, ops=ops,
                        gaps=gaps, window_ns=(lo, hi))


def label_gaps(summary: TraceSummary, spans: list[dict], epoch_at_lo: float,
               top: int = 10) -> list[list]:
    """The `top` longest idle gaps of device 0, each named by what the
    host was doing in it: the server span (a node's handler, by name)
    that overlaps the gap longest, else the client or local span that
    does, or "no span" where none was open.  Spans carry wall-clock
    starts (`t0`, s since the epoch); `epoch_at_lo` is the wall clock at
    the window's start."""
    lo = summary.window_ns[0]
    placed = [(sp["name"], sp.get("kind") == "server",
               lo + (sp["t0"] - epoch_at_lo) * 1e9,
               lo + (sp["t0"] + sp["dur_s"] - epoch_at_lo) * 1e9)
              for sp in spans]
    out = []
    for s, e in summary.gaps[:top]:
        overlap: dict[tuple[bool, str], float] = {}
        for name, server, a, b in placed:
            o = min(b, e) - max(a, s)
            if o > 0:
                overlap[server, name] = overlap.get((server, name), 0.0) + o
        label = (max(overlap, key=lambda k: (k[0], overlap[k]))[1]
                 if overlap else "no span")
        out.append([f"{label} @+{(s - lo) / 1e9:.3f}s", (e - s) / 1e9])
    return out
