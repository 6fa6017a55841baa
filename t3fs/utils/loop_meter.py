"""Event-loop meter: how much of a loop's wall time is spent waiting in
the selector, and how much running callbacks.

A loop that is busy nearly all the time is saturated: its work sets the
pace of everything on it.  A loop that waits while requests queue is
starved by something outside it (the GIL, threads on disk).  LoopLag-style
probes see only how late a timer fires, which both cases show alike.

`install(loop)` wraps the loop's selector `select()` once; the meter
counts `wait_s` (time inside select), `iterations`, and `busy_s` (wall
time since install minus `wait_s`), exposed as `loop.*` CallbackGauges.
While head sampling is on, a select wait of at least MIN_SPAN_S that wins
its own head-sampling decision (so a share `sample_rate` of them), and one
wait every SNAPSHOT_S whatever its length, also finishes a `loop.wait`
local span (its own trace) tagged with the meter's running totals at its
end, so a reader of the span stream alone can take the counters' deltas
over any window; `recorded_wait_s` is the part of `wait_s` those spans
cover.  The spans export only under `export=all`: an idle wait is no
slow request.  A select that returns at once (a poll with work already
queued) takes a few microseconds; MIN_SPAN_S keeps those out and every
wait in which the loop blocked in.
"""

from __future__ import annotations

import asyncio
import time
import weakref

from t3fs.utils import tracing

MIN_SPAN_S = 10e-6
SNAPSHOT_S = 0.5

_meters: "weakref.WeakKeyDictionary[asyncio.AbstractEventLoop, LoopMeter]" = \
    weakref.WeakKeyDictionary()


class LoopMeter:
    def __init__(self, selector):
        self.wait_s = 0.0
        self.recorded_wait_s = 0.0
        self.iterations = 0
        self._t_install = time.perf_counter()
        self._t_recorded = self._t_install
        inner = selector.select

        def select(timeout=None):
            t0 = time.perf_counter()
            try:
                return inner(timeout)
            finally:
                t1 = time.perf_counter()
                dt = t1 - t0
                self.wait_s += dt
                self.iterations += 1
                if (dt >= MIN_SPAN_S and tracing.head_sample()
                        or t1 - self._t_recorded >= SNAPSHOT_S
                        and tracing.sampling_on()):
                    self._record(t1, dt)

        selector.select = select

    @property
    def busy_s(self) -> float:
        return time.perf_counter() - self._t_install - self.wait_s

    def _record(self, t1: float, dt: float) -> None:
        self._t_recorded = t1
        self.recorded_wait_s += dt
        tracing.record_root(
            "loop.wait", time.time() - dt, dt,
            busy_s=t1 - self._t_install - self.wait_s, wait_s=self.wait_s,
            recorded_wait_s=self.recorded_wait_s,
            iterations=self.iterations)


def install(loop: asyncio.AbstractEventLoop | None = None
            ) -> LoopMeter | None:
    """Meter `loop` (default: the running loop); idempotent per loop, so
    every node of an in-process cluster may call it.  None for a loop
    with no selector to wrap (a proactor loop)."""
    loop = loop or asyncio.get_running_loop()
    m = _meters.get(loop)
    if m is not None:
        return m
    selector = getattr(loop, "_selector", None)
    if selector is None:
        return None
    m = _meters[loop] = LoopMeter(selector)
    from t3fs.utils.metrics import CallbackGauge

    CallbackGauge("loop.busy_s", lambda: m.busy_s)
    CallbackGauge("loop.wait_s", lambda: m.wait_s)
    CallbackGauge("loop.iterations", lambda: m.iterations)
    return m

