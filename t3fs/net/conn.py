"""Duplex framed connection: waiter table + request dispatch, both directions.

Reference analogs: common/net/Transport.h:22 (connection object),
common/net/Processor.h:28-50 (decode -> dispatch), common/net/Waiter
(uuid -> coroutine wakeup).  Unlike the reference's client->server-only RPC
plus one-sided RDMA verbs, a t3fs connection lets EITHER side issue requests:
that is the TCP emulation of RDMA READ/WRITE (see net/__init__ docstring).

A Connection is the asyncio protocol of its socket.  Inbound frames are
cut out of a per-connection staging buffer, several per ``recv_into``;
a msg or payload past ``BULK_BYTES`` gets a buffer of its own, and the
kernel writes the part of it not yet staged straight there (see
``get_buffer``), so a multi-MiB body costs no pause/resume cycles of the
transport and no copies beyond its staged prefix.

A body of ``THREAD_BYTES`` or more crosses the socket on a mover of its
connection (``_Mover``), a thread outside the interpreter, in either
direction: the loop only hands the body over and takes it back, and
frames below it stay on the loop.
"""

from __future__ import annotations

import asyncio
import ctypes
import itertools
import logging
import os
import time
from typing import Awaitable, Callable

import numpy as np

from t3fs.net.wire import (
    HEADER_SIZE, FLAG_COMPRESS, FLAG_IS_REQ, FrameError, MessagePacket,
    WireStatus, check_msg_crc, decompress_frame, maybe_compress, pack_header,
    unpack_header,
)
from t3fs.native.build import loaded_library
from t3fs.net.rpcstats import RPC_STATS, SERVER_STATS
from t3fs.ops.codec import crc32c
from t3fs.utils import serde, tracing
from t3fs.utils.metrics import CountRecorder
from t3fs.utils.status import Status, StatusCode, StatusError, make_error

log = logging.getLogger("t3fs.net")

# inbound engagement, every asyncio Connection of the process together:
# recv_calls / frames is the reads per frame; a large body read straight
# into its own buffer counts once in direct_bodies
RX_FRAMES = CountRecorder("net.rx.frames")
RX_DIRECT_BODIES = CountRecorder("net.rx.direct_bodies")
RX_RECV_CALLS = CountRecorder("net.rx.recv_calls")
# bodies a connection's mover moved, inbound (each also a direct body)
# and outbound
RX_THREAD_BODIES = CountRecorder("net.rx.thread_bodies")
TX_THREAD_BODIES = CountRecorder("net.tx.thread_bodies")

# bytearray(n) of what malloc gives, not zero-filled: a part the socket
# fills whole is not written (and its pages faulted in) twice, the first
# time on the loop
_bytearray_uninit = ctypes.PYFUNCTYPE(
    ctypes.py_object, ctypes.c_void_p, ctypes.c_ssize_t)(
        ("PyByteArray_FromStringAndSize", ctypes.pythonapi))


def _mover_lib() -> ctypes.CDLL | None:
    """The native library with the mover's functions declared, or None
    until this process has loaded it (loaded_library starts the load)."""
    lib = loaded_library()
    if lib is not None and lib.t3fs_mover_start.restype is not ctypes.c_void_p:
        vp, u64 = ctypes.c_void_p, ctypes.c_uint64
        lib.t3fs_mover_efd.argtypes = [vp]
        lib.t3fs_mover_recv.argtypes = [vp, vp, u64]
        lib.t3fs_mover_recv.restype = None
        lib.t3fs_mover_send.argtypes = [vp, vp, u64, vp, u64]
        lib.t3fs_mover_send.restype = None
        lib.t3fs_mover_result.argtypes = [vp]
        lib.t3fs_mover_stop.argtypes = [vp]
        lib.t3fs_mover_stop.restype = None
        lib.t3fs_mover_start.argtypes = [ctypes.c_int]
        lib.t3fs_mover_start.restype = vp
    return lib


class _Mover:
    """The loop's end of a mover (t3fs/native/sock_io.cpp): a thread of
    one connection, outside the interpreter, that moves one body at a time
    between the socket and memory, waiting on the socket with poll.  It
    works on a dup of the socket, so it never touches a descriptor number
    the transport closed and the process reused.  The loop hears of a
    finished body through the mover's eventfd and gives its result (0, -1
    for the end of the stream, else an errno) to the body's callback.
    stop() shuts the socket down, which ends a body in flight, and joins
    the thread: a body that was in flight gets STOPPED."""

    STOPPED = -2

    def __init__(self, lib: ctypes.CDLL, sock, loop: asyncio.AbstractEventLoop):
        self._lib, self._loop, self._pid = lib, loop, os.getpid()
        self._h = None
        self._h = lib.t3fs_mover_start(os.dup(sock.fileno()))
        if not self._h:
            raise OSError("mover thread did not start")
        self._efd = lib.t3fs_mover_efd(self._h)
        loop.add_reader(self._efd, self._finished)
        # the buffers the thread works on, kept alive, and the callback
        self._job: tuple | None = None

    def recv(self, part: bytearray, k: int, done: Callable[[int], None]) -> None:
        """Fill part[k:] from the socket."""
        buf = np.frombuffer(part, np.uint8)
        self._job = (buf, done)
        self._lib.t3fs_mover_recv(self._h, buf.ctypes.data + k, buf.nbytes - k)

    def send(self, front: bytes, payload, done: Callable[[int], None]) -> None:
        """Send front, then payload (any contiguous buffer)."""
        a, b = np.frombuffer(front, np.uint8), np.frombuffer(payload, np.uint8)
        self._job = (a, b, done)
        self._lib.t3fs_mover_send(self._h, a.ctypes.data, a.nbytes,
                                  b.ctypes.data, b.nbytes)

    def _finished(self) -> None:
        self._done(self._lib.t3fs_mover_result(self._h))

    def _done(self, rc: int) -> None:
        # the buffers' exports end here, before the body goes on (a
        # bytearray with an export cannot be resized)
        job, self._job = self._job, None
        if job is not None:
            done = job[-1]
            del job
            done(rc)

    def stop(self) -> None:
        self._loop.remove_reader(self._efd)
        h, self._h = self._h, None
        self._lib.t3fs_mover_stop(h)
        self._done(self.STOPPED)

    def __del__(self):
        # dropped without stop(): its connection's loop closed under it.
        # The dup would keep the socket open, the peer never seeing EOF.
        # (Not in a forked child: the thread lives in the parent only.)
        if self._h and os.getpid() == self._pid:
            self._lib.t3fs_mover_stop(self._h)

# handler(body, payload, conn) -> (rsp_body, rsp_payload)
Handler = Callable[[object, bytes, "Connection"], Awaitable[tuple[object, bytes]]]


class Connection(asyncio.BufferedProtocol):
    """One duplex framed stream; safe for concurrent calls.  It is the
    protocol of its asyncio transport: ``loop.create_connection`` and
    ``loop.create_server`` take a factory that returns a Connection."""

    _uuid_counter = itertools.count(1)

    # frames past this size compress/decompress in a worker thread so a
    # multi-MiB zlib pass never stalls the event loop (heartbeats, other
    # conns); below it the thread hop costs more than the compression
    OFFLOAD_BYTES = 1 << 20
    # a payload past this ships as a write of its own, and a msg or
    # payload past it arrives into a buffer of its own (no staging copy)
    BULK_BYTES = 64 << 10
    # inbound staging per connection: small frames are parsed out of it
    # several per recv_into.  Twice BULK_BYTES, so a part that waits
    # staged always fits once the staged bytes move to the front
    STAGE_BYTES = 2 * BULK_BYTES
    # a body with this much or more still to cross the socket crosses it
    # on a mover (_Mover), off the loop: past the cost of the hand-over
    # (two loop wake-ups), below the 1 MiB shards of an RS stripe
    THREAD_BYTES = 512 << 10

    def __init__(self, dispatcher: dict[str, Handler] | None = None,
                 name: str = "?",
                 on_close: Callable[["Connection"], None] | None = None,
                 compress_threshold: int = 0, compress_level: int = 1):
        self.dispatcher = dispatcher if dispatcher is not None else {}
        self.name = name
        self.on_close = on_close
        # outbound frames >= threshold bytes ship zlib-compressed
        # (UseCompress analog); 0 disables.  Inbound compressed frames are
        # always understood regardless of this setting.
        self.compress_threshold = compress_threshold
        self.compress_level = compress_level
        # serving address, set by Server on accepted conns: tags server
        # spans with the node that ran the handler (multi-node-in-one-
        # process fabrics can't use a global for this)
        self.local_address = ""
        self._waiters: dict[int, asyncio.Future] = {}
        self._send_lock = asyncio.Lock()
        self._closed = False
        # asyncio holds only weak refs to tasks; keep handlers alive here
        self._tasks: set[asyncio.Task] = set()
        self.transport: asyncio.Transport | None = None
        self._lost: asyncio.Future | None = None   # done at connection_lost
        self._write_paused = False
        self._drain_waiter: asyncio.Future | None = None
        # inbound framing (get_buffer, buffer_updated, _parse): the staged
        # bytes are _stage[_lo:_hi]; _frame is the current frame's
        # [head_ts, msg_len, payload_len, flags, msg_crc] once its header
        # is parsed, _parts its msg and payload as they complete; _part
        # is a part the socket reads straight into, _part_n its fill
        self._stage: bytearray | None = None
        self._stage_mv: memoryview | None = None
        self._lo = self._hi = 0
        self._frame: list | None = None
        self._parts: list = []
        self._part: bytearray | None = None
        self._part_n = 0
        # a frame is being verified off the loop: reading is paused and
        # later frames wait staged, so dispatch keeps the wire's order
        self._held = False
        # the transport's socket (None: no real socket, as in
        # t3fs/testing/conn_sim.py), and the "rx" and "tx" movers, each
        # started on the first body it moves; while _rx_busy the rx mover
        # fills _part and reading is paused
        self._sock = None
        self._movers: dict[str, _Mover] = {}
        self._rx_busy = False

    @property
    def closed(self) -> bool:
        return self._closed

    def _spawn(self, coro, name: str) -> asyncio.Task:
        task = asyncio.create_task(coro, name=name)
        self._tasks.add(task)
        task.add_done_callback(self._tasks.discard)
        return task

    async def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        if self.on_close is not None:
            try:
                self.on_close(self)
            except Exception:
                pass
        if self.transport is not None:
            try:
                self.transport.close()
                await self._lost
            except Exception:
                pass
        err = make_error(StatusCode.RPC_SEND_FAILED, f"connection {self.name} closed")
        for fut in self._waiters.values():
            if not fut.done():
                fut.set_exception(err)
                # the awaiting call() may itself have been cancelled (loop
                # teardown): mark the exception retrieved so asyncio doesn't
                # log "Future exception was never retrieved"; a live awaiter
                # still receives it normally
                fut.exception()
        self._waiters.clear()

    # --- transport callbacks ---

    def connection_made(self, transport: asyncio.Transport) -> None:
        self.transport = transport
        self._lost = asyncio.get_running_loop().create_future()
        self._stage = bytearray(self.STAGE_BYTES)
        self._stage_mv = memoryview(self._stage)
        self._sock = transport.get_extra_info("socket")
        if self.name.endswith("<-"):
            # accepted by Server, which names it "srv<-": add the peer
            self.name += str(transport.get_extra_info("peername"))

    def connection_lost(self, exc: Exception | None) -> None:
        if not self._lost.done():
            self._lost.set_result(None)
        movers, self._movers = self._movers, {}
        for mover in movers.values():
            mover.stop()
        waiter, self._drain_waiter = self._drain_waiter, None
        if waiter is not None and not waiter.done():
            if exc is None:
                waiter.set_result(None)
            else:
                waiter.set_exception(exc)
        # EOF or reset, mid-frame or not; a frame held off the loop is
        # dispatched first, and closes the connection after it
        if not self._held and not self._closed:
            self._spawn(self.close(), f"close-{self.name}")

    def pause_writing(self) -> None:
        self._write_paused = True

    def resume_writing(self) -> None:
        self._write_paused = False
        waiter, self._drain_waiter = self._drain_waiter, None
        if waiter is not None and not waiter.done():
            waiter.set_result(None)

    async def _drain(self) -> None:
        """StreamWriter.drain's semantics: wait while the transport holds
        more than its high-water mark; raise once the connection is lost."""
        if self.transport.is_closing():
            # let connection_lost run, so a lost connection raises below
            await asyncio.sleep(0)
        if self._lost.done():
            raise ConnectionResetError("connection lost")
        if not self._write_paused:
            return
        self._drain_waiter = asyncio.get_running_loop().create_future()
        await self._drain_waiter

    async def _prep_frame(self, packet: MessagePacket, payload: bytes,
                          flags: int) -> tuple[bytes, bytes, bytes]:
        """Serde + (optional) compression + envelope CRC + header.
        Returns (header, msg, payload)."""
        msg = serde.dumps(packet)
        if self.compress_threshold > 0:
            if len(msg) + len(payload) >= self.OFFLOAD_BYTES:
                msg, payload, zflag = await asyncio.to_thread(
                    maybe_compress, msg, payload,
                    self.compress_threshold, self.compress_level)
            else:
                msg, payload, zflag = maybe_compress(
                    msg, payload, self.compress_threshold,
                    self.compress_level)
            flags |= zflag
        # envelope CRC (post-compression bytes); off-thread for big
        # envelopes so the CRC pass never stalls the loop either
        if len(msg) >= self.OFFLOAD_BYTES:
            mcrc = await asyncio.to_thread(crc32c, msg)
        else:
            mcrc = crc32c(msg) if msg else 0
        return pack_header(len(msg), len(payload), flags, mcrc), msg, payload

    def _mover(self, kind: str) -> _Mover | None:
        """The connection's "rx" or "tx" mover, started on the first body
        it moves; None with no real socket, once the connection is lost,
        or while the native library is not loaded."""
        mover = self._movers.get(kind)
        if (mover is None and self._sock is not None and not self._closed
                and not self._lost.done()):
            lib = _mover_lib()
            if lib is not None:
                try:
                    mover = _Mover(lib, self._sock,
                                   asyncio.get_running_loop())
                except OSError as e:
                    log.warning("conn %s: no %s mover: %s", self.name, kind, e)
                    return None
                self._movers[kind] = mover
        return mover

    async def _send_frame(self, packet: MessagePacket, payload: bytes, flags: int) -> None:
        head, msg, payload = await self._prep_frame(packet, payload, flags)
        # frame atomicity: header+payload must hit the stream without
        # interleaving, so drain() deliberately runs under the lock
        async with self._send_lock:  # t3fslint: allow(async-lock-await-discipline)
            if self._closed:
                raise make_error(StatusCode.RPC_SEND_FAILED, "connection closed")
            try:
                mover = (self._mover("tx")
                         if len(payload) >= self.THREAD_BYTES else None)
                if mover is not None:
                    await self._send_moved(mover, head + msg, payload)
                    return
                # ONE buffer -> ONE send syscall: separate write() calls
                # each attempt an immediate send when the transport buffer
                # is empty, tripling the syscall count per frame (profiled
                # at ~30% of client CPU on the multi-process path).  Big
                # payloads, and forwarded RX buffers (a bytearray or a
                # memoryview), are worth a copy-free second write.
                if payload and (len(payload) > self.BULK_BYTES
                                or not isinstance(payload, bytes)):
                    self.transport.write(head + msg)
                    self.transport.write(payload)
                else:
                    self.transport.write(head + msg + payload)
                await self._drain()
            except OSError as e:
                raise make_error(StatusCode.RPC_SEND_FAILED,
                                 f"send on {self.name}: {e}") from None

    async def _send_moved(self, mover: _Mover, front: bytes, payload) -> None:
        """One frame through the tx mover, under the send lock: the
        transport's buffer is sent first, and no transport.write runs
        until the mover has sent the frame's last byte."""
        if self.transport.get_write_buffer_size():
            # pause_writing while any byte is buffered, resume at none
            self.transport.set_write_buffer_limits(high=0)
            try:
                await self._drain()
            finally:
                self.transport.set_write_buffer_limits()
        if self._lost.done():
            raise ConnectionResetError("connection lost")
        done = asyncio.get_running_loop().create_future()
        mover.send(front, payload, done.set_result)
        # the lock is held until the mover lets go of the socket, even by
        # a caller cancelled meanwhile: a frame written now would land
        # inside this one
        cancelled = False
        while True:
            try:
                rc = await asyncio.shield(done)
                break
            except asyncio.CancelledError:
                cancelled = True
        if cancelled:
            raise asyncio.CancelledError
        if rc:
            self._abort()     # a frame cut short: nothing can follow it
            raise ConnectionResetError(
                os.strerror(rc) if rc > 0 else "closed mid-frame")
        TX_THREAD_BODIES.add()

    def _stamp_trace(self, packet: MessagePacket) -> None:
        """Propagate the active span's context onto the envelope.  When no
        span is active (head sampling said no, or tracing is off) the
        fields keep their serde defaults — zero extra state on the wire."""
        sp = tracing.current_span()
        if sp is not None:
            packet.trace_id = sp.trace_id
            packet.parent_span_id = sp.span_id
            packet.sampled = True

    async def post(self, method: str, body: object = None,
                   payload: bytes = b"") -> None:
        """One-way request: uuid 0 means the peer runs the handler but
        sends no response frame, and none is awaited here.  Carries the
        bulk frames of an UPDATE_FRAG stream, whose failures surface on
        the stream's windowed call()s / final update RPC instead.  (The
        uuid counter starts at 1, so 0 can never collide with a waiter.)"""
        packet = MessagePacket(uuid=0, method=method, is_req=True).stamp_called()
        packet.body = body
        self._stamp_trace(packet)
        await self._send_frame(packet, payload, FLAG_IS_REQ)

    async def call(self, method: str, body: object = None, payload: bytes = b"",
                   timeout: float = 30.0) -> tuple[object, bytes]:
        """Issue a request, await the typed response (+ raw payload).
        Raises StatusError on non-OK response or transport failure."""
        uuid = next(self._uuid_counter)
        packet = MessagePacket(uuid=uuid, method=method, is_req=True).stamp_called()
        packet.body = body
        self._stamp_trace(packet)
        fut: asyncio.Future = asyncio.get_running_loop().create_future()
        self._waiters[uuid] = fut
        try:
            await self._send_frame(packet, payload, FLAG_IS_REQ)
            sp = tracing.current_span()
            if sp is not None and sp.name == "rpc." + method:
                # serde, envelope CRC, the send-lock wait, write and drain
                sp.set_tag("send_s", time.time() - packet.ts_client_called)
            try:
                rsp, rsp_payload = await asyncio.wait_for(fut, timeout)
            except asyncio.TimeoutError:
                raise make_error(StatusCode.RPC_TIMEOUT,
                                 f"{method} timed out after {timeout}s") from None
            if rsp.ts_server_replied:
                # latency decomposition (rpcstats module docstring);
                # squeue/server are same-clock server intervals, network
                # is the clock-skew-free remainder
                total = time.time() - packet.ts_client_called
                server_span = rsp.ts_server_replied - rsp.ts_server_received
                started = rsp.ts_server_started or rsp.ts_server_received
                RPC_STATS.record(
                    method, total,
                    squeue=started - rsp.ts_server_received,
                    server=rsp.ts_server_replied - started,
                    network=max(0.0, total - server_span),
                    ok=rsp.status.code == int(StatusCode.OK))
            status = rsp.status.to_status()
            status.raise_if_error()
            return rsp.body, rsp_payload
        finally:
            self._waiters.pop(uuid, None)

    # --- inbound frames ---

    def get_buffer(self, sizehint: int) -> memoryview:
        """Where the transport's next recv_into writes: the rest of a
        part being read straight into its own buffer, else the staging
        buffer's free tail (never empty: see _parse)."""
        if self._part is not None:
            return memoryview(self._part)[self._part_n:]
        return self._stage_mv[self._hi:]

    def buffer_updated(self, nbytes: int) -> None:
        RX_RECV_CALLS.add()
        if self._part is not None:
            self._part_n += nbytes
            if self._part_n < len(self._part):
                return
            self._parts.append(self._part)
            self._part = None
        else:
            self._hi += nbytes
        self._parse()

    def _parse(self) -> None:
        """Dispatch every frame complete in what has arrived, in order.
        A msg or payload past BULK_BYTES gets a bytearray of exactly its
        size, never reused (a forwarding hop hands the same object to
        transport.write): the staged prefix is copied in, and get_buffer
        then points the socket at the rest of it."""
        try:
            while not self._held and self._part is None:
                if self._frame is None:
                    if self._hi - self._lo < HEADER_SIZE:
                        break
                    head_ts = time.time()
                    # magic, header CRC and MAX_FRAME are checked here,
                    # before anything is allocated from the lengths
                    self._frame = [head_ts, *unpack_header(bytes(
                        self._stage_mv[self._lo:self._lo + HEADER_SIZE]))]
                    self._lo += HEADER_SIZE
                    continue
                if len(self._parts) == 2:
                    self._complete_frame()
                    continue
                n = self._frame[1 + len(self._parts)]   # msg_len, payload_len
                staged = self._hi - self._lo
                if n > self.BULK_BYTES:
                    part = _bytearray_uninit(None, n)
                    k = min(staged, n)
                    memoryview(part)[:k] = self._stage_mv[self._lo:self._lo + k]
                    self._lo += k
                    if k < n:
                        RX_DIRECT_BODIES.add()
                        self._part, self._part_n = part, k
                        if n - k >= self.THREAD_BYTES:
                            self._receive_moved()
                        break
                    self._parts.append(part)
                elif n <= staged:
                    self._parts.append(
                        bytes(self._stage_mv[self._lo:self._lo + n]))
                    self._lo += n
                else:
                    break
        except FrameError as e:
            log.warning("conn %s: frame error: %s", self.name, e)
            self._abort()
            return
        except Exception:
            log.exception("conn %s: frame dispatch failed", self.name)
            self._abort()
            return
        if self._lo == self._hi:
            self._lo = self._hi = 0
        elif not self._held and len(self._stage) - self._hi < self.BULK_BYTES:
            # what is staged is less than one waiting part (<= BULK_BYTES)
            # or header: move it to the front (a memoryview assignment
            # memmoves), so the rest of it fits behind
            n = self._hi - self._lo
            self._stage_mv[:n] = self._stage_mv[self._lo:self._hi]
            self._lo, self._hi = 0, n

    def _receive_moved(self) -> None:
        """Hand the rest of _part to the rx mover, where there is one:
        reading pauses (so the mover alone reads the socket, and reads no
        byte past the part) until _received takes the part back."""
        mover = self._mover("rx")
        if mover is None:
            return
        self.transport.pause_reading()
        self._rx_busy = True
        mover.recv(self._part, self._part_n, self._received)

    def _received(self, rc: int) -> None:
        self._rx_busy = False
        if self._closed:
            return
        if rc:
            # the end of the stream or a reset mid-body, as on the loop
            # path: close, failing the pending calls
            self._abort()
            return
        RX_THREAD_BODIES.add()
        self._parts.append(self._part)
        self._part = None
        self._read_on()

    def _read_on(self) -> None:
        """After a pause (a frame verified off the loop, a body the rx
        mover read): dispatch what is staged, then read on, unless that
        paused reading again."""
        self._parse()
        if self._closed or self._held or self._rx_busy:
            return
        if self._lost.done():
            # EOF came while reading was paused
            self._spawn(self.close(), f"close-{self.name}")
        else:
            self.transport.resume_reading()

    def _complete_frame(self) -> None:
        (head_ts, _, _, flags, msg_crc), (msg, payload) = \
            self._frame, self._parts
        self._frame, self._parts = None, []
        RX_FRAMES.add()
        if flags & FLAG_COMPRESS or len(msg) >= self.OFFLOAD_BYTES:
            # verify (and inflate) in a worker thread; reading pauses so
            # the frames behind this one wait staged, in order
            self._held = True
            self.transport.pause_reading()
            self._spawn(self._verify_off_loop(head_ts, flags, msg_crc, msg,
                                              payload),
                        f"verify-{self.name}")
            return
        check_msg_crc(msg, msg_crc)
        self._dispatch_packet(serde.loads(msg), payload, head_ts)

    async def _verify_off_loop(self, head_ts: float, flags: int, msg_crc: int,
                               msg, payload) -> None:
        try:
            if flags & FLAG_COMPRESS:
                # always off-thread: on-wire size says nothing about
                # decompressed size (a zeros-heavy 256 MiB frame can
                # arrive <1 MiB), and the hop is cheap vs any zlib pass
                def _verify_inflate():
                    check_msg_crc(msg, msg_crc)   # CRC covers on-wire bytes
                    return decompress_frame(msg, payload, flags)
                msg, payload = await asyncio.to_thread(_verify_inflate)
            else:
                await asyncio.to_thread(check_msg_crc, msg, msg_crc)
            if not self._closed:
                self._dispatch_packet(serde.loads(msg), payload, head_ts)
        except FrameError as e:
            log.warning("conn %s: frame error: %s", self.name, e)
            self._abort()
            return
        except Exception:
            log.exception("conn %s: frame dispatch failed", self.name)
            self._abort()
            return
        finally:
            self._held = False
        if not self._closed:
            self._read_on()

    def _abort(self) -> None:
        """A frame the connection cannot get past: stop reading now, and
        close both ways."""
        if not self._closed:
            self.transport.close()
            self._spawn(self.close(), f"close-{self.name}")

    def _dispatch_packet(self, packet: MessagePacket, payload: bytes,
                         head_ts: float) -> None:
        """Post-decode dispatch, shared by the in-line and the off-loop
        verify: spawn the handler for requests (stamping the receive
        time), wake the waiter for responses.  head_ts is when the reader
        had the frame's header."""
        if packet.is_req:
            self._spawn(self._handle_request(packet, payload, time.time(),
                                             head_ts),
                        f"req-{packet.method}")
        else:
            fut = self._waiters.get(packet.uuid)
            if fut is not None and not fut.done():
                fut.set_result((packet, payload))

    async def _handle_request(self, packet: MessagePacket, payload: bytes,
                              recv_ts: float, head_ts: float) -> None:
        rsp = MessagePacket(uuid=packet.uuid, method=packet.method, is_req=False)
        rsp.ts_server_received = recv_ts
        rsp.ts_server_started = time.time()   # gap = server-side queueing
        rsp_payload = b""
        handler = self.dispatcher.get(packet.method)
        if packet.sampled and packet.trace_id:
            # server span: the handler (and anything it calls, including
            # downstream RPCs) runs inside it.  wire_s spans both clocks
            # (skew rides in it); queue_s is same-clock loop queueing;
            # frame_s, header to dispatch, is the body read, its CRC
            # check and serde.loads.
            scope = tracing.server_scope(
                packet.method, packet.trace_id, packet.parent_span_id,
                addr=self.local_address,
                wire_s=max(0.0, rsp.ts_server_received - packet.ts_client_called),
                queue_s=rsp.ts_server_started - rsp.ts_server_received)
            scope.span.set_tag("frame_s", rsp.ts_server_received - head_ts)
        else:
            scope = tracing.server_scope(packet.method, 0, 0)   # no-op
        with scope as sp:
            try:
                if handler is None:
                    raise make_error(StatusCode.RPC_METHOD_NOT_FOUND, packet.method)
                rsp.body, rsp_payload = await handler(packet.body, payload, self)
            except StatusError as e:
                rsp.status = WireStatus.from_status(e.status)
                sp.set_status(int(e.status.code))
            except Exception as e:
                log.exception("handler %s failed", packet.method)
                rsp.status = WireStatus(int(StatusCode.INTERNAL), f"{type(e).__name__}: {e}")
                sp.set_status(int(StatusCode.INTERNAL))
        rsp.ts_server_replied = time.time()
        # serving-side per-method stats: unlike the client-side record in
        # call() (which attributes latency to the CALLER's process), this
        # lands in the process that served the request — the per-node
        # signal the monitor's health rollups fold (t3fs/monitor/rollup.py)
        SERVER_STATS.record(
            packet.method, rsp.ts_server_replied - rsp.ts_server_received,
            squeue=rsp.ts_server_started - rsp.ts_server_received,
            server=rsp.ts_server_replied - rsp.ts_server_started,
            network=0.0, ok=rsp.status.code == int(StatusCode.OK))
        if packet.uuid == 0:
            return  # one-way post(): no response frame (errors logged above)
        try:
            await self._send_frame(rsp, rsp_payload, 0)
        except Exception:
            pass  # peer gone; response dropped like a lost ack
