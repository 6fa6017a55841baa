"""Connection state-machine schedules (specs/RDMASocket analog).

Each test is one family of deterministic schedules over the REAL
Connection code; seeds make failures reproducible."""

import asyncio

import pytest

from t3fs.net.conn import Connection
from t3fs.testing.conn_sim import SimPair, run_schedule


def run(coro):
    return asyncio.run(coro)


@pytest.mark.parametrize("max_payload", [4096, 3 * Connection.BULK_BYTES])
def test_arbitrary_segmentation_delivers_all_frames(max_payload):
    """C4: duplex calls complete intact under 1..1M-byte delivery chunks,
    small bodies staged and large ones read into their own buffers."""
    async def body():
        for seed in range(8):
            r = await run_schedule(seed, calls=16, max_payload=max_payload)
            assert r["ok"] == 32 and r["err"] == 0, (seed, r)
            assert r["payload_ok"], seed
    run(body())


@pytest.mark.parametrize("max_payload", [4096, 3 * Connection.BULK_BYTES])
def test_cut_mid_stream_fails_pending_cleanly(max_payload):
    """C3: a reset mid-schedule errors every unfinished call, hangs none,
    leaks nothing (with large bodies, also mid-way through one read into
    its own buffer)."""
    async def body():
        saw_err = False
        for seed in range(8):
            r = await run_schedule(seed, calls=16, cut_after=5,
                                   max_payload=max_payload)
            assert r["ok"] + r["err"] == 32, (seed, r)
            saw_err |= r["err"] > 0
        assert saw_err, "cut schedules never produced an error?"
    run(body())


@pytest.mark.parametrize("max_payload", [4096, 3 * Connection.BULK_BYTES])
def test_corruption_closes_and_fails_cleanly(max_payload):
    """A flipped bit in flight must surface as clean connection failure
    (header CRC / frame error), never a hang or a wrong payload."""
    async def body():
        for seed in range(8):
            r = await run_schedule(seed, calls=12, corrupt_after=3,
                                   max_payload=max_payload)
            assert r["ok"] + r["err"] == 24, (seed, r)
            # only the single flipped frame may pass (payload region is
            # app-checksummed, not wire-checksummed); envelope corruption
            # always fails closed
            assert r["bad_payloads"] <= 1, (seed, r)
    run(body())


def test_corruption_of_compressed_frames():
    """Same corruption family with compression on: zlib-level damage must
    also fail closed (FrameError path), not deliver garbage."""
    async def body():
        for seed in range(6):
            r = await run_schedule(seed, calls=10, corrupt_after=4,
                                   compress_threshold=64)
            assert r["ok"] + r["err"] == 20, (seed, r)
            # zlib streams detect most damage; at worst the one frame leaks
            assert r["bad_payloads"] <= 1, (seed, r)
    run(body())


def test_close_during_inflight_handler():
    """close() racing a dispatched handler: reply write fails benignly,
    waiters error, nothing leaks."""
    async def body():
        started = asyncio.Event()
        release = asyncio.Event()

        async def slow(body_, payload, conn):
            started.set()
            await release.wait()
            return None, b"late"

        pair = SimPair({"Sim.slow": slow}, {})
        call = asyncio.create_task(pair.b.call("Sim.slow", None, timeout=5.0))
        # deliver the request, let the handler start
        for _ in range(200):
            pair.ba.pump(1 << 20)
            await asyncio.sleep(0)
            if started.is_set():
                break
        assert started.is_set()
        await pair.a.close()               # close under the handler
        release.set()
        with pytest.raises(Exception):
            await call
        await pair.settle()
        await pair.close()
        pair.check_quiesced()
    run(body())


def test_timeout_then_late_response_ignored():
    """A response landing after the caller timed out must be dropped
    without touching a new call's waiter or crashing the reader."""
    async def body():
        async def slow(body_, payload, conn):
            return None, b"slow-reply"

        pair = SimPair({"Sim.slow": slow}, {})
        # issue with a tiny timeout and DON'T pump: caller times out
        with pytest.raises(Exception):
            await pair.b.call("Sim.slow", None, timeout=0.05)
        # now deliver the stale request + its response end-to-end
        await pair.settle()
        # a fresh call on the same conn still works
        async def echo(body_, payload, conn):
            return None, payload
        pair.a.dispatcher["Sim.echo"] = echo
        _, pay = await asyncio.wait_for(
            _call_with_pump(pair, "Sim.echo", b"fresh"), 5.0)
        assert pay == b"fresh"
        await pair.close()
        pair.check_quiesced()
    run(body())


async def _call_with_pump(pair, method, payload):
    task = asyncio.create_task(pair.b.call(method, None, payload=payload,
                                           timeout=5.0))
    while not task.done():
        pair.ba.pump(1 << 20)
        pair.ab.pump(1 << 20)
        await asyncio.sleep(0)
    return task.result()
