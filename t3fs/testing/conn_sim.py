"""Deterministic-schedule simulation of the RPC connection state machine.

Reference analog: specs/RDMASocket/ — the P-language model of the socket
state machine (connect/handshake/send/recv/teardown races).  Where the
reference checks an abstract model, this simulator drives the REAL
``t3fs.net.conn.Connection`` on both ends of an in-memory byte pipe whose
delivery the scheduler fully controls: bytes move only when the schedule
pumps them, in chunk sizes the schedule picks, through the protocol's own
``get_buffer``/``buffer_updated`` (the real frame parser, as a socket
transport's ``recv_into`` drives it), with optional mid-frame cuts and
single-byte corruption.  Every interleaving the scheduler
produces is one a real TCP socket could produce (arbitrary segmentation,
torn frames, resets), so invariant violations here are real protocol bugs.

Invariants checked after every schedule (``check_quiesced``):

  C1 no leaked waiters:   every issued call resolved (result OR error)
  C2 no leaked handlers:  the dispatcher task set drains once closed
  C3 clean close:         a cut/corrupt stream closes BOTH ends; pending
                          calls fail with RPC_SEND_FAILED, none hang
  C4 framing integrity:   under any segmentation, delivered frames decode
                          to exactly the bytes sent (no tears, no reorders)
"""

from __future__ import annotations

import asyncio
import random
from dataclasses import dataclass, field

from t3fs.net.conn import Connection
from t3fs.utils.status import StatusError


class SimTransport:
    """Just enough asyncio.Transport for Connection: written bytes go to
    an outbox the SCHEDULER pumps into the peer's protocol."""

    def __init__(self, name: str):
        self.name = name
        self.outbox = bytearray()
        self.closed = False      # close() ran: connection_lost follows
        self.reset = False       # cut: writes fail, the protocol not told
        self.paused = False      # the protocol paused reading
        self.protocol: Connection | None = None
        self.peer: SimTransport | None = None

    def write(self, data) -> None:
        if self.closed or self.reset:
            raise ConnectionResetError("write after close")
        self.outbox += data

    def is_closing(self) -> bool:
        return self.closed

    def pause_reading(self) -> None:
        self.paused = True

    def resume_reading(self) -> None:
        self.paused = False

    def close(self) -> None:
        if self.closed:
            return
        self.closed = True
        asyncio.get_running_loop().call_soon(self.protocol.connection_lost,
                                             None)
        # model FIN: the peer's read side sees EOF once our end closes
        self.peer.feed_eof()

    def feed_eof(self) -> None:
        """EOF reached this end (the peer's FIN, or a reset): as a socket
        transport does, tell the protocol, then close."""
        if self.closed:
            return
        if not self.protocol.eof_received():
            self.close()

    def get_extra_info(self, key, default=None):
        return default


@dataclass
class SimLink:
    """One direction of the pipe: tx's outbox -> rx's protocol."""
    tx: SimTransport
    rx: SimTransport
    delivered: int = 0

    def pump(self, n: int) -> int:
        """Deliver up to n pending bytes through ONE get_buffer /
        buffer_updated, as one recv_into would; returns bytes moved.
        Bytes wait in flight while the receiver has paused reading."""
        n = min(n, len(self.tx.outbox))
        if not n or (self.rx.paused and not self.rx.closed):
            return 0
        if self.rx.closed:
            del self.tx.outbox[:n]
            return 0                       # receiver already closed: drop
        buf = self.rx.protocol.get_buffer(n)
        n = min(n, len(buf))
        buf[:n] = self.tx.outbox[:n]
        del self.tx.outbox[:n]
        self.rx.protocol.buffer_updated(n)
        self.delivered += n
        return n

    def corrupt_next(self) -> bool:
        """Flip one bit of the next undelivered byte (header or body)."""
        if not self.tx.outbox:
            return False
        self.tx.outbox[0] ^= 0x40
        return True

    def cut(self) -> None:
        """Drop everything in flight and EOF the receiver (TCP RST)."""
        self.tx.outbox.clear()
        self.tx.reset = True
        self.rx.feed_eof()


class SimPair:
    """Two real Connections over two scheduled links (full duplex)."""

    def __init__(self, dispatcher_a=None, dispatcher_b=None,
                 compress_threshold: int = 0):
        ta, tb = SimTransport("a->b"), SimTransport("b->a")
        ta.peer, tb.peer = tb, ta
        self.ab = SimLink(ta, tb)
        self.ba = SimLink(tb, ta)
        self.a = Connection(dispatcher_a, name="sim-a",
                            compress_threshold=compress_threshold)
        self.b = Connection(dispatcher_b, name="sim-b",
                            compress_threshold=compress_threshold)
        for conn, t in ((self.a, ta), (self.b, tb)):
            t.protocol = conn
            conn.connection_made(t)

    async def settle(self) -> None:
        """Let spawned tasks run until no link has pending bytes and the
        event loop is idle for a tick."""
        for _ in range(50):
            await asyncio.sleep(0)
        while self.ab.tx.outbox or self.ba.tx.outbox:
            self.ab.pump(1 << 20)
            self.ba.pump(1 << 20)
            for _ in range(50):
                await asyncio.sleep(0)

    async def close(self) -> None:
        await self.a.close()
        await self.b.close()
        for _ in range(20):
            await asyncio.sleep(0)

    def check_quiesced(self) -> None:
        for conn in (self.a, self.b):
            assert not conn._waiters, \
                f"{conn.name}: leaked waiters {list(conn._waiters)}"  # C1
            live = [t for t in conn._tasks if not t.done()]
            assert not live, f"{conn.name}: leaked handler tasks {live}"  # C2


async def run_schedule(seed: int, calls: int = 20, cut_after: int | None = None,
                       corrupt_after: int | None = None,
                       compress_threshold: int = 0,
                       max_payload: int = 4096) -> dict:
    """One schedule: issue `calls` concurrent echo calls in BOTH directions
    while pumping bytes in random-sized chunks; optionally cut or corrupt
    the a->b link after N pump steps.  Payloads are 1..max_payload bytes
    (past Connection.BULK_BYTES a body is read into its own buffer).
    Returns counters for assertions."""
    rng = random.Random(seed)

    async def echo(body, payload, conn):
        if rng.random() < 0.3:
            await asyncio.sleep(0)         # reschedule mid-handler
        return body, payload

    dispatcher = {"Sim.echo": echo}
    pair = SimPair(dict(dispatcher), dict(dispatcher),
                   compress_threshold=compress_threshold)

    async def one_call(conn, i):
        try:
            rsp, pay = await conn.call("Sim.echo", None,
                                       payload=bytes([i % 256]) * rng.randint(1, max_payload),
                                       timeout=5.0)
            return ("ok", pay)
        except StatusError as e:
            return ("err", str(e.code))

    tasks = [asyncio.create_task(one_call(pair.a, i)) for i in range(calls)]
    tasks += [asyncio.create_task(one_call(pair.b, i)) for i in range(calls)]

    steps = 0
    cut_done = corrupt_done = False
    while any(not t.done() for t in tasks):
        steps += 1
        if corrupt_after is not None and steps >= corrupt_after \
                and not corrupt_done:
            corrupt_done = pair.ab.corrupt_next()
        if cut_after is not None and steps >= cut_after and not cut_done:
            pair.ab.cut()
            pair.ba.cut()
            cut_done = True
        link = pair.ab if rng.random() < 0.5 else pair.ba
        link.pump(rng.choice([1, 3, 7, 64, 1024, 1 << 20]))
        for _ in range(rng.randint(1, 8)):
            await asyncio.sleep(0)
        if steps > 100_000:
            raise AssertionError("schedule did not quiesce (hang)")  # C3
    # t3fslint: allow(blocking-in-async) — the quiesce loop above completed every worker task
    results = [t.result() for t in tasks]
    await pair.settle()
    await pair.close()
    pair.check_quiesced()
    bad_payloads = sum(
        1 for i, (s, p) in enumerate(results)
        if s == "ok" and p != bytes([i % calls % 256]) * len(p))
    return {
        "ok": sum(1 for s, _ in results if s == "ok"),
        "err": sum(1 for s, _ in results if s == "err"),
        # C4: without corruption this must be 0; WITH corruption at most
        # the one flipped frame may slip through (bulk payload integrity
        # is the app layer's end-to-end checksum, as in the reference) —
        # envelope bytes are wire-CRC'd and always fail closed
        "bad_payloads": bad_payloads,
        "payload_ok": bad_payloads == 0,
        "steps": steps,
    }
