"""Host-side monitors the harness runs beside the system under test.

CompileLog counts XLA backend compiles from JAX's own monitoring events
(so a compile inside the measured window shows); LoopLag samples how late
a 10 ms ticker on the cluster's event loop wakes, which is how long every
node, client and heartbeat sharing that loop was held up; StallProbe
says what held it when it was held long.
"""

from __future__ import annotations

import asyncio
import os
import sys
import threading
import time
import traceback


class CompileLog:
    """Counts XLA backend compiles (and persistent-cache hits among them)
    from JAX's own monitoring events."""

    def __init__(self):
        import jax
        from jax._src import dispatch

        self._event = dispatch.BACKEND_COMPILE_EVENT
        self.programs = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._count)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == self._event:
            self.programs += 1
            self.seconds += secs

    def _count(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> tuple[int, float, int]:
        return self.programs, self.seconds, self.cache_hits


class LoopLag:
    """Oversleep of a `period_s` ticker on the running loop: one sample
    per tick, kept while `recording` is set.  `last_tick` is when the
    ticker last ran, for StallProbe."""

    def __init__(self, period_s: float = 0.010):
        self.period_s = period_s
        self.samples: list[float] = []
        self.recording = False
        self.last_tick = time.perf_counter()
        self.loop_tid = 0
        self._task: asyncio.Task | None = None

    async def _run(self) -> None:
        loop = asyncio.get_running_loop()
        while True:
            t = loop.time()
            await asyncio.sleep(self.period_s)
            self.last_tick = time.perf_counter()
            if self.recording:
                self.samples.append(loop.time() - t - self.period_s)

    def start(self) -> None:
        self.loop_tid = threading.get_native_id()
        self.last_tick = time.perf_counter()
        self._task = asyncio.get_running_loop().create_task(self._run())

    async def stop(self) -> None:
        if self._task is not None:
            self._task.cancel()
            await asyncio.gather(self._task, return_exceptions=True)
            self._task = None


def _read(path: str) -> str:
    try:
        with open(path) as f:
            return f.read()
    except OSError:
        return ""


def thread_cpu() -> dict[int, tuple[str, str, int]]:
    """{tid: (name, state, utime + stime in clock ticks)} of this
    process's threads."""
    out = {}
    for tid in os.listdir("/proc/self/task"):
        stat = _read(f"/proc/self/task/{tid}/stat")
        if not stat:
            continue
        name = stat[stat.index("(") + 1:stat.rindex(")")]
        f = stat[stat.rindex(")") + 2:].split()
        out[int(tid)] = (name, f[0], int(f[11]) + int(f[12]))
    return out


class StallProbe:
    """Tells what held the loop in each stall past `threshold_s`: a
    thread beside it takes the loop thread's Python stack while it is
    held, and each thread's CPU time across the stall.  A probe that
    oversleeps too was held itself: the GIL was not released, or the
    process got no CPU."""

    def __init__(self, lag: LoopLag, threshold_s: float = 0.3,
                 keep: int = 8):
        self.lag = lag
        self.threshold_s = threshold_s
        self.keep = keep
        self.stalls: list[dict] = []
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._watch, daemon=True,
                                        name="stall-probe")

    def start(self) -> None:
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread.ident is not None:
            self._thread.join()

    def _watch(self) -> None:
        cur = None
        t_prev = time.perf_counter()
        while not self._stop.wait(0.05):
            now = time.perf_counter()
            probe_late, t_prev = now - t_prev - 0.05, now
            late = now - self.lag.last_tick
            if cur is None and late > self.threshold_s and len(
                    self.stalls) < self.keep:
                cpu = thread_cpu()
                cur = {"t0": self.lag.last_tick, "cpu0": cpu,
                       "probe_late_s": 0.0,
                       "loop_state": cpu.get(self.lag.loop_tid, ("", "?"))[1],
                       "stacks": []}
            if cur is None:
                continue
            cur["probe_late_s"] = max(cur["probe_late_s"], probe_late)
            if late > self.threshold_s:
                if len(cur["stacks"]) < 3:
                    cur["stacks"].append(self._loop_stack())
                continue
            used = sorted(((c[2] - cur["cpu0"].get(t, ("", "", 0))[2], c[0])
                           for t, c in thread_cpu().items()), reverse=True)
            self.stalls.append({
                "s": round(self.lag.last_tick - cur["t0"], 3),
                "probe_late_s": round(cur["probe_late_s"], 3),
                "loop_state": cur["loop_state"],
                "cpu_ticks": [[n, d] for d, n in used[:3] if d],
                "stacks": cur["stacks"]})
            cur = None

    def _loop_stack(self) -> list[str]:
        frames = sys._current_frames()
        for t in threading.enumerate():
            if t.native_id == self.lag.loop_tid and t.ident in frames:
                return [f"{os.path.basename(f.filename)}:{f.lineno} {f.name}"
                        for f in traceback.extract_stack(
                            frames[t.ident])[-6:]]
        return []
