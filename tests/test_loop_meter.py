"""The event-loop meter (t3fs/utils/loop_meter.py): a loop's wall time
split into waiting in select() and running callbacks, its loop.* gauges,
and `loop.wait` spans only while head sampling is on, at its rate."""

import asyncio
import time

import pytest

from t3fs.utils import loop_meter, tracing
from t3fs.utils.metrics import all_recorders
from t3fs.utils.tracing import BUFFER, TraceConfig, configure, reset_tracing

SPIN_S = 0.2
SLEEP_S = 0.2


@pytest.fixture(autouse=True)
def _clean_tracing():
    reset_tracing()
    yield
    reset_tracing()


def _drain_all():
    out = []
    while True:
        part = BUFFER.drain()
        if not part:
            return out
        out += part


async def _spin_then_sleep():
    m = loop_meter.install()
    assert loop_meter.install() is m          # once per loop
    b0, w0 = m.busy_s, m.wait_s
    t = time.perf_counter()
    while time.perf_counter() - t < SPIN_S:    # the loop runs a callback
        pass
    await asyncio.sleep(SLEEP_S)               # the loop waits in select
    return m, m.busy_s - b0, m.wait_s - w0


@pytest.mark.parametrize("sampled", [False, True], ids=["off", "sampled"])
def test_meter_splits_busy_from_wait(sampled):
    if sampled:
        configure(TraceConfig(sample_rate=1.0, export="all"))
    m, busy, wait = asyncio.run(_spin_then_sleep())
    assert SPIN_S - 0.005 <= busy <= SPIN_S + 0.1
    assert SLEEP_S - 0.02 <= wait <= SLEEP_S + 0.1
    assert m.iterations >= 2
    assert {"loop.busy_s", "loop.wait_s", "loop.iterations"} <= {
        r.name for r in all_recorders()}
    waits = [s for s in _drain_all() if s["name"] == "loop.wait"]
    if not sampled:
        assert waits == [] and m.recorded_wait_s == 0.0
        assert BUFFER.stats()["finished"] == 0
        return
    assert waits and all(s["root"] and s["kind"] == "local" for s in waits)
    # the sleep is one long select: a span covers it, and the running
    # totals the spans carry grow with the meter's own
    assert max(s["dur_s"] for s in waits) >= SLEEP_S - 0.02
    last = max(waits, key=lambda s: s["tags"]["iterations"])["tags"]
    assert last["recorded_wait_s"] <= last["wait_s"] <= m.wait_s
    assert last["busy_s"] >= busy - 0.005
    assert m.recorded_wait_s >= 0.9 * wait


def test_each_loop_gets_its_own_meter():
    async def meter_of_this_loop():
        return loop_meter.install()

    a = asyncio.run(meter_of_this_loop())
    b = asyncio.run(meter_of_this_loop())
    assert a is not None and b is not None and a is not b


@pytest.mark.parametrize("draw, spans", [(0.75, False), (0.25, True)],
                         ids=["drawn-out", "drawn-in"])
def test_loop_wait_spans_follow_sample_rate(monkeypatch, draw, spans):
    # each wait makes its own head-sampling decision at sample_rate
    monkeypatch.setattr(loop_meter, "SNAPSHOT_S", 3600.0)
    monkeypatch.setattr(tracing.random, "random", lambda: draw)
    configure(TraceConfig(sample_rate=0.5, export="all"))
    m, _, wait = asyncio.run(_spin_then_sleep())
    waits = [s for s in _drain_all() if s["name"] == "loop.wait"]
    assert bool(waits) is spans
    if spans:
        assert m.recorded_wait_s >= 0.9 * wait
    else:
        assert m.recorded_wait_s == 0.0
