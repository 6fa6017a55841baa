"""Connection's inbound frame reader (asyncio BufferedProtocol): frames cut
out of a staging buffer under any segmentation, large bodies read straight
into a bytearray of their own, corrupt headers refused before anything is
allocated from them, the send side's drain, the write path storing a body
that arrives as a bytearray without copying it back to bytes, and bodies
of THREAD_BYTES or more crossing real sockets on a connection's movers."""

import asyncio
import glob
import os
import socket
import struct

import pytest

from t3fs.net import conn as conn_mod
from t3fs.net.conn import Connection
from t3fs.net.wire import (
    FLAG_IS_REQ, HEADER_FMT, HEADER_SIZE, MAGIC, MAX_FRAME, MessagePacket,
    pack_header,
)
from t3fs.ops.codec import crc32c
from t3fs.utils.status import StatusCode, StatusError

BULK = Connection.BULK_BYTES


class _Transport:
    """What Connection asks of an asyncio transport, recorded."""

    def __init__(self, protocol: Connection):
        self.protocol = protocol
        self.out = bytearray()
        self.closed = False
        self.paused = False

    def write(self, data) -> None:
        self.out += data

    def is_closing(self) -> bool:
        return self.closed

    def pause_reading(self) -> None:
        self.paused = True

    def resume_reading(self) -> None:
        self.paused = False

    def close(self) -> None:
        if not self.closed:
            self.closed = True
            asyncio.get_running_loop().call_soon(
                self.protocol.connection_lost, None)

    def get_extra_info(self, key, default=None):
        return default


class _Rx(Connection):
    """A receiving Connection that records each decoded frame instead of
    dispatching it."""

    def __init__(self):
        super().__init__(name="rx")
        self.got: list[tuple[str, object, object]] = []

    def _dispatch_packet(self, packet, payload, head_ts):
        self.got.append((packet.method, packet.body, payload))


def _attach(conn: Connection) -> _Transport:
    t = _Transport(conn)
    conn.connection_made(t)
    return t


async def _frame(method: str, body=None, payload: bytes = b"",
                 compress_threshold: int = 0) -> tuple[bytes, int, int]:
    """One frame as Connection sends it: (wire bytes, msg_len,
    payload_len)."""
    tx = Connection(compress_threshold=compress_threshold)
    pkt = MessagePacket(uuid=0, method=method, is_req=True)
    pkt.body = body
    head, msg, pay = await tx._prep_frame(pkt, payload, FLAG_IS_REQ)
    return bytes(head) + bytes(msg) + bytes(pay), len(msg), len(pay)


async def _recorded_frames(with_4m: bool):
    """Small frames around a bulk payload, a bulk msg and a compressed
    frame (verified off the loop, so reading pauses behind it); with_4m
    adds a 4 MiB body.  Returns (frames, expected)."""
    spec = [("S.empty", None, b""), ("S.one", 1, b"\x01"),
            ("S.small", "abc", os.urandom(17)),
            ("S.page", {"k": 2}, os.urandom(4096)),
            ("S.bulk", 3, os.urandom(BULK + (16 << 10))),
            ("S.bigmsg", "m" * (BULK + 6000), b"tail"),
            ("S.zipped", 4, b"z" * 3000),
            ("S.after", 5, os.urandom(33))]
    if with_4m:
        spec += [("S.four", 6, os.urandom(4 << 20)), ("S.last", 7, b"!")]
    frames = []
    for method, body, payload in spec:
        frames.append(await _frame(method, body, payload,
                                   compress_threshold=64
                                   if method == "S.zipped" else 0))
    return frames, [(m, b, p) for m, b, p in spec]


def _cuts(frames, how: str) -> list[int]:
    """Stream offsets at which one delivery ends."""
    total = sum(len(f) for f, _, _ in frames)
    if how == "1-byte":
        return list(range(1, total + 1))
    if how == "primes":
        primes = [2, 3, 5, 7, 11, 13, 31, 127, 509, 8191, 65521, 131071]
        out, pos, i = [], 0, 0
        while pos < total:
            pos = min(total, pos + primes[i % len(primes)])
            out.append(pos)
            i += 1
        return out
    if how == "header-msg-body":
        out, pos = [], 0
        for f, msg_len, pay_len in frames:
            out += [pos + HEADER_SIZE, pos + HEADER_SIZE + msg_len,
                    pos + HEADER_SIZE + msg_len + pay_len // 2]
            pos += len(f)
        return sorted(set(c for c in out if 0 < c < total)) + [total]
    if how == "coalesced":
        return [total]
    if how == "4MiB-staged-prefix":
        # everything before the 4 MiB frame, its header and msg, and the
        # first 10 KiB of its body arrive together; the rest in 1 MiB
        pos = sum(len(f) for f, _, _ in frames[:-2])
        _, msg_len, _ = frames[-2]
        out = [pos + HEADER_SIZE + msg_len + (10 << 10)]
        while out[-1] < total:
            out.append(min(total, out[-1] + (1 << 20)))
        return out
    raise AssertionError(how)


async def _feed(rx: _Rx, t: _Transport, data: bytes, cuts: list[int],
                frames: int) -> int:
    """Deliver data, each cut through as many get_buffer/buffer_updated
    rounds as the buffer it is offered asks, as recv_into does; wait while
    reading is paused.  Returns the rounds."""
    pos = rounds = 0
    for end in cuts:
        while pos < end:
            while t.paused:
                await asyncio.sleep(0.001)
            buf = rx.get_buffer(-1)
            assert len(buf), "get_buffer offered no room"
            n = min(len(buf), end - pos)
            buf[:n] = data[pos:pos + n]
            pos += n
            rounds += 1
            rx.buffer_updated(n)
    for _ in range(2000):
        if len(rx.got) == frames and not t.paused:
            break
        await asyncio.sleep(0.001)
    return rounds


SEGMENTATIONS = ["1-byte", "primes", "header-msg-body", "coalesced",
                 "4MiB-staged-prefix"]


@pytest.mark.parametrize("how", SEGMENTATIONS)
def test_reader_decodes_every_frame_under_segmentation(how):
    async def body():
        with_4m = how == "4MiB-staged-prefix"
        frames, expected = await _recorded_frames(with_4m)
        data = b"".join(f for f, _, _ in frames)
        rx = _Rx()
        t = _attach(rx)
        direct0 = conn_mod.RX_DIRECT_BODIES._value
        await _feed(rx, t, data, _cuts(frames, how), len(expected))
        assert not t.closed
        assert [(m, b) for m, b, _ in rx.got] == \
            [(m, b) for m, b, _ in expected]
        for (m, _, got), (_, _, sent) in zip(rx.got, expected):
            assert bytes(got) == sent, m
            if len(sent) > BULK:
                # a large body is its own bytearray, exactly its size
                assert type(got) is bytearray and len(got) == len(sent), m
        if with_4m:
            # the 4 MiB body's staged prefix is copied in, the rest read
            # straight into it
            assert conn_mod.RX_DIRECT_BODIES._value - direct0 >= 1
        assert rx._lo == rx._hi == 0 and rx._part is None
    asyncio.run(body())


def test_small_frames_use_no_more_recv_calls_than_frames():
    """Over a real socket: a burst of small frames is parsed several per
    recv_into out of the staging buffer."""
    async def body():
        loop = asyncio.get_running_loop()
        n = 300
        frames = [(await _frame("S.tiny", i, os.urandom(i % 64)))[0]
                  for i in range(n)]
        a, b = socket.socketpair()
        try:
            rx = _Rx()
            b.sendall(b"".join(frames))
            frames0 = conn_mod.RX_FRAMES._value
            recv0 = conn_mod.RX_RECV_CALLS._value
            await loop.connect_accepted_socket(lambda: rx, a)
            for _ in range(2000):
                if len(rx.got) == n:
                    break
                await asyncio.sleep(0.001)
            got_frames = conn_mod.RX_FRAMES._value - frames0
            recv_calls = conn_mod.RX_RECV_CALLS._value - recv0
            assert [body for _, body, _ in rx.got] == list(range(n))
            assert got_frames == n
            assert 1 <= recv_calls <= got_frames
            await rx.close()
        finally:
            b.close()
    asyncio.run(body())


def _bad_header(kind: str) -> bytes:
    if kind == "header-crc":
        head = bytearray(pack_header(16, 64 << 20, FLAG_IS_REQ, 0))
        head[8] ^= 0x01                     # payload_len, past the CRC
        return bytes(head)
    if kind == "bad-magic":
        return struct.pack(HEADER_FMT, MAGIC ^ 1, 16, 64 << 20,
                           FLAG_IS_REQ, 0, 0)
    if kind == "over-max-frame":
        return pack_header(16, MAX_FRAME + 1, FLAG_IS_REQ, 0)
    raise AssertionError(kind)


@pytest.mark.parametrize("kind", ["header-crc", "bad-magic", "over-max-frame"])
def test_corrupt_header_refused_before_any_allocation(kind, monkeypatch):
    allocated = []

    def tracking_bytearray(*args):
        allocated.append(args)
        return bytearray(*args)

    def tracking_uninit(ptr, n):
        allocated.append((n,))
        return bytearray(n)

    async def body():
        rx = _Rx()
        t = _attach(rx)     # allocates the staging buffer
        # from here on, every bytearray the reader makes goes through these
        monkeypatch.setattr(conn_mod, "bytearray", tracking_bytearray,
                            raising=False)
        monkeypatch.setattr(conn_mod, "_bytearray_uninit", tracking_uninit)
        fut = asyncio.get_running_loop().create_future()
        rx._waiters[99] = fut
        direct0 = conn_mod.RX_DIRECT_BODIES._value
        head = _bad_header(kind)
        buf = rx.get_buffer(-1)
        buf[:len(head)] = head
        rx.buffer_updated(len(head))
        assert t.closed, "a corrupt header must close the connection"
        assert rx._part is None and not rx.got
        assert conn_mod.RX_DIRECT_BODIES._value == direct0
        # pending calls fail with RPC_SEND_FAILED; nothing is left running
        with pytest.raises(StatusError) as ei:
            await asyncio.wait_for(fut, 2.0)
        assert ei.value.code == StatusCode.RPC_SEND_FAILED
        assert rx.closed
        for _ in range(100):
            if not rx._tasks:
                break
            await asyncio.sleep(0)
        assert not rx._tasks
    asyncio.run(body())
    assert allocated == []


@pytest.mark.parametrize("cut,payload_len", [
    ("in-header", 1 << 20), ("in-msg", 1 << 20), ("in-staged-body", 4000),
    ("in-direct-body", 1 << 20)])
def test_eof_mid_frame_fails_pending_calls(cut, payload_len):
    async def body():
        data, msg_len, _ = await _frame("S.cut", "x" * 40,
                                        os.urandom(payload_len))
        upto = {"in-header": HEADER_SIZE // 2,
                "in-msg": HEADER_SIZE + msg_len // 2,
                "in-staged-body": HEADER_SIZE + msg_len + 2000,
                "in-direct-body": HEADER_SIZE + msg_len + (512 << 10)}[cut]
        rx = _Rx()
        t = _attach(rx)
        fut = asyncio.get_running_loop().create_future()
        rx._waiters[7] = fut
        await _feed(rx, t, data, [upto], 0)
        assert not rx.got and not t.closed
        t.closed = True                  # the peer's FIN: the transport
        rx.connection_lost(None)         # closes and tells the protocol
        with pytest.raises(StatusError) as ei:
            await asyncio.wait_for(fut, 2.0)
        assert ei.value.code == StatusCode.RPC_SEND_FAILED
        assert rx.closed
    asyncio.run(body())


@pytest.mark.parametrize("lost", [False, True])
def test_send_drain_waits_for_resume_writing(lost):
    """transport.write, then a drain that waits while the transport is
    over its high-water mark (pause_writing), as StreamWriter.drain."""
    async def body():
        tx = Connection(name="tx")
        t = _attach(tx)
        tx.pause_writing()
        send = asyncio.create_task(tx.post("S.x", 1, payload=b"p" * 100))
        for _ in range(20):
            await asyncio.sleep(0)
        assert not send.done() and t.out, "drain must wait while paused"
        if lost:
            tx.connection_lost(ConnectionResetError("peer reset"))
            with pytest.raises(StatusError) as ei:
                await send
            assert ei.value.code == StatusCode.RPC_SEND_FAILED
        else:
            tx.resume_writing()
            await asyncio.wait_for(send, 2.0)
        await tx.close()
    asyncio.run(body())


# ---- the write path keeps the body it was handed ----

def test_craq_chain_stores_bytearray_body_on_every_replica():
    """A 4 MiB CRAQ write through a 3-replica chain arrives at each hop as
    a bytearray read straight from the socket; every replica stores the
    same bytes and CRC32C, for a fresh write, a whole-chunk overwrite and
    a partial overwrite."""
    from t3fs.client.storage_client import StorageClient
    from t3fs.storage.types import ChunkId, ChunkState
    from t3fs.testing.fabric import StorageFabric

    chunk = 4 << 20

    async def body():
        fab = StorageFabric(num_nodes=3, replicas=3)
        await fab.start()
        kinds: list[str] = []
        targets = [t for n in fab.nodes for t in n.targets.values()]
        for t in targets:
            apply = t.replica.apply_update

            def spy(io, payload, payload_crc=None, _apply=apply):
                kinds.append(type(payload).__name__)
                return _apply(io, payload, payload_crc)
            t.replica.apply_update = spy
        try:
            sc = StorageClient(lambda: fab.routing, client=fab.client)
            cid = ChunkId(42, 0)
            expected = bytearray(os.urandom(chunk))
            writes = [(0, bytes(expected)), (0, os.urandom(chunk)),
                      (1 << 20, os.urandom((1 << 20) + 4096))]
            for offset, data in writes:
                direct0 = conn_mod.RX_DIRECT_BODIES._value
                r = await sc.write_chunk(fab.chain_id, cid, offset, data,
                                         chunk_size=chunk)
                assert r.status.code == int(StatusCode.OK), r.status
                expected[offset:offset + len(data)] = data
                # client -> head -> middle -> tail, each body read direct
                assert conn_mod.RX_DIRECT_BODIES._value - direct0 >= 3
                for t in targets:
                    meta = t.engine.get_meta(cid)
                    assert meta.state == ChunkState.COMMIT
                    assert meta.length == chunk
                    assert meta.checksum == crc32c(bytes(expected))
                    assert t.engine.read(cid) == expected
            assert kinds == ["bytearray"] * 9
        finally:
            await fab.stop()
    asyncio.run(body())


@pytest.mark.parametrize("engine_kind", ["native", "py"])
def test_engine_put_takes_a_bytearray(engine_kind, tmp_path):
    from t3fs.storage.chunk_engine import ChunkEngine
    from t3fs.storage.native_engine import NativeChunkEngine
    from t3fs.storage.types import ChunkId, ChunkMeta, ChunkState

    e = (NativeChunkEngine(str(tmp_path)) if engine_kind == "native"
         else ChunkEngine(str(tmp_path)))
    try:
        cid = ChunkId(3, 1)
        data = bytearray(os.urandom(BULK * 3 + 5))
        e.put(cid, data, ChunkMeta(cid, len(data), 1, 0, 1, crc32c(data),
                                   ChunkState.DIRTY), 1 << 20)
        assert e.read(cid) == data
        assert e.get_meta(cid).length == len(data)
    finally:
        e.close()


# ---- bodies of THREAD_BYTES or more cross the socket on movers ----

THREAD = Connection.THREAD_BYTES


@pytest.fixture(scope="module")
def native():
    """Movers run in the native library: load it (building it if need
    be) before a test's loop starts, as a storage node does at start."""
    from t3fs.native import load_library
    return load_library()


def _movers_alive() -> int:
    n = 0
    for path in glob.glob("/proc/self/task/*/comm"):
        try:
            with open(path) as f:
                n += f.read().strip() == "t3fs-mover"
        except OSError:
            pass            # the thread ended meanwhile
    return n


async def _until(pred, timeout: float = 5.0) -> bool:
    for _ in range(int(timeout / 0.005)):
        if pred():
            return True
        await asyncio.sleep(0.005)
    return pred()


def _tcp_pair(buf: int = 0) -> tuple[socket.socket, socket.socket]:
    """Two ends of a loopback TCP connection; buf > 0 pins both ends'
    kernel buffers that small, so a large body stalls mid-way."""
    with socket.socket() as ls:
        if buf:
            ls.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, buf)
            ls.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, buf)
        ls.bind(("127.0.0.1", 0))
        ls.listen()
        a = socket.socket()
        if buf:
            a.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, buf)
            a.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, buf)
        a.connect(ls.getsockname())
        b, _ = ls.accept()
    return a, b


def _counts() -> tuple[int, int]:
    return (conn_mod.RX_THREAD_BODIES._value,
            conn_mod.TX_THREAD_BODIES._value)


async def _echo_server():
    from t3fs.net.server import Server

    async def echo(body, payload, conn):
        return {"n": len(payload), "crc": crc32c(bytes(payload))}, payload
    srv = Server()
    srv.dispatcher["E.echo"] = echo
    await srv.start()
    return srv


@pytest.mark.parametrize("size", [1 << 20, 4 << 20, THREAD - 1],
                         ids=["1MiB", "4MiB", "THREAD_BYTES-1"])
def test_body_crosses_real_socket_both_ways(size, native):
    """An echo over loopback TCP: the request and the reply body each
    cross on a mover at 1 and 4 MiB (bytes and CRC32C equal both ways);
    one byte under THREAD_BYTES both stay on the loop."""
    from t3fs.net.client import Client

    async def body():
        srv = await _echo_server()
        cli = Client()
        try:
            data = os.urandom(size)
            rx0, tx0 = _counts()
            rsp, got = await asyncio.wait_for(
                cli.call(srv.address, "E.echo", None, data), 10)
            assert rsp == {"n": size, "crc": crc32c(data)}
            assert type(got) is bytearray and bytes(got) == data
            assert crc32c(bytes(got)) == crc32c(data)
            got.extend(b"!")    # the mover left no export on the buffer
            moved = 2 if size >= THREAD else 0
            # the server's send is counted once its mover reports it
            assert await _until(lambda: _counts() == (rx0 + moved,
                                                      tx0 + moved))
        finally:
            await cli.close()
            await srv.stop()
    asyncio.run(body())


@pytest.mark.parametrize("buf", [0, 64 << 10], ids=["autotuned", "64KiB"])
def test_small_frames_queued_behind_a_moved_body_keep_their_order(
        buf, native, monkeypatch):
    """Frames on either side of a body the movers carry arrive in the
    order sent, whole.  The peer starts reading only once all are
    queued: with 64 KiB socket buffers part of the 230 KiB frame before
    the body is then still in the transport's buffer when the body's
    turn comes, and the mover waits for it to go out first."""
    async def body():
        loop = asyncio.get_running_loop()
        a, b = _tcp_pair(buf)
        rx, tx = _Rx(), Connection(name="tx")
        await loop.connect_accepted_socket(lambda: tx, b)
        # what the transport still buffers when the body's turn comes,
        # and when the body goes to the mover
        buffered: dict[str, int] = {}
        turn, post = Connection._send_moved, conn_mod._Mover.send

        async def spy_turn(self, *args):
            buffered["turn"] = self.transport.get_write_buffer_size()
            return await turn(self, *args)

        def spy_post(self, *args):
            buffered["post"] = tx.transport.get_write_buffer_size()
            return post(self, *args)
        monkeypatch.setattr(Connection, "_send_moved", spy_turn)
        monkeypatch.setattr(conn_mod._Mover, "send", spy_post)
        big = os.urandom(4 << 20)
        small = [os.urandom(i) for i in range(40)]
        small[4] = os.urandom(230 << 10)
        rx0, tx0 = _counts()
        # the send lock takes them in this order; the small frames
        # behind the body wait for its mover
        sends = asyncio.gather(*(tx.post("S.x", i, big if i == 5 else small[i])
                                 for i in range(40)))
        await asyncio.sleep(0.05)
        await loop.connect_accepted_socket(lambda: rx, a)
        await asyncio.wait_for(sends, 10)
        assert await _until(lambda: len(rx.got) == 40)
        assert [b for _, b, _ in rx.got] == list(range(40))
        for i, (_, _, got) in enumerate(rx.got):
            assert bytes(got) == (big if i == 5 else small[i])
        assert _counts() == (rx0 + 1, tx0 + 1)
        assert buffered["post"] == 0
        if buf:
            assert buffered["turn"] > 0
        await tx.close()
        await rx.close()
    asyncio.run(body())


async def _stalled(side: str, loop):
    """A Connection with a body in flight on its side's mover that makes
    no progress: the raw peer sends part of a 4 MiB body and stops (rx),
    or reads nothing of one (tx).  Returns (conn, peer, call task or
    None, pending waiter)."""
    a, b = _tcp_pair(buf=64 << 10 if side == "tx" else 0)
    conn = Connection(name=side)
    await loop.connect_accepted_socket(lambda: conn, a)
    pending = loop.create_future()
    conn._waiters[10 ** 9] = pending
    call = None
    if side == "rx":
        data, msg_len, _ = await _frame("S.cut", "x" * 40,
                                        os.urandom(4 << 20))
        await asyncio.to_thread(b.sendall,
                                data[:HEADER_SIZE + msg_len + (1 << 20)])
        assert await _until(lambda: conn._rx_busy)
    else:
        call = asyncio.ensure_future(
            conn.call("S.x", 1, payload=os.urandom(4 << 20), timeout=20))
        assert await _until(lambda: "tx" in conn._movers)
        await asyncio.sleep(0.05)
        assert not call.done(), "the peer reads nothing: the send stalls"
    return conn, b, call, pending


@pytest.mark.parametrize("side", ["rx", "tx"])
@pytest.mark.parametrize("how", ["eof", "reset"])
def test_peer_gone_mid_moved_body_fails_pending_calls(side, how, native):
    async def body():
        loop = asyncio.get_running_loop()
        rx0, tx0 = _counts()
        conn, peer, call, pending = await _stalled(side, loop)
        if how == "reset":
            peer.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                            struct.pack("ii", 1, 0))
            peer.close()
        else:
            peer.shutdown(socket.SHUT_WR)   # FIN: the end of the stream
        futs = [pending] + ([call] if call is not None else [])
        for fut in futs:
            with pytest.raises(StatusError) as ei:
                await asyncio.wait_for(fut, 5)
            assert ei.value.code == StatusCode.RPC_SEND_FAILED
        assert await _until(lambda: conn.closed and not conn._movers)
        assert _counts() == (rx0, tx0)
        peer.close()
    asyncio.run(body())


@pytest.mark.parametrize("side", ["rx", "tx"])
def test_close_mid_moved_body_returns_and_stops_its_movers(side, native):
    async def body():
        loop = asyncio.get_running_loop()
        before = _movers_alive()
        conn, peer, call, pending = await _stalled(side, loop)
        # the thread names itself once it runs
        assert await _until(lambda: _movers_alive() == before + 1)
        await asyncio.wait_for(conn.close(), 5)
        assert not conn._movers and _movers_alive() == before
        for fut in [pending] + ([call] if call is not None else []):
            with pytest.raises(StatusError) as ei:
                await asyncio.wait_for(fut, 5)
            assert ei.value.code == StatusCode.RPC_SEND_FAILED
        peer.close()
    asyncio.run(body())


def test_cancelled_moved_send_finishes_its_frame_before_the_next(native):
    """A caller cancelled while the tx mover sends its body: the send lock
    stays held until the mover has sent the last byte, so the frame
    queued behind it follows it whole."""
    async def body():
        loop = asyncio.get_running_loop()
        a, b = _tcp_pair(buf=64 << 10)
        tx = Connection(name="tx")
        await loop.connect_accepted_socket(lambda: tx, a)
        big = os.urandom(4 << 20)
        first = asyncio.ensure_future(tx.post("S.x", 0, big))
        assert await _until(lambda: "tx" in tx._movers)
        await asyncio.sleep(0.05)
        assert not first.done(), "the peer reads nothing: the send stalls"
        first.cancel()
        second = asyncio.ensure_future(tx.post("S.x", 1, b"small"))
        await asyncio.sleep(0.05)
        assert not first.done() and not second.done()
        rx = _Rx()
        await loop.connect_accepted_socket(lambda: rx, b)   # reads now
        await asyncio.wait([first, second], timeout=10)
        assert first.cancelled()
        await second
        assert await _until(lambda: len(rx.got) == 2)
        assert [m[1] for m in rx.got] == [0, 1]
        assert bytes(rx.got[0][2]) == big and rx.got[1][2] == b"small"
        await tx.close()
        await rx.close()
    asyncio.run(body())


def test_eight_connections_move_4mib_bodies_both_ways_at_once(native):
    """Sixteen 4 MiB echoes over eight connections at once, every body
    on a mover, finish well inside the timeout: movers of one
    connection never wait on another's."""
    from t3fs.net.client import Client

    async def body():
        srv = await _echo_server()
        clients = [Client() for _ in range(8)]
        before = _movers_alive()
        try:
            datas = [os.urandom(4 << 20) for _ in range(16)]
            rx0, tx0 = _counts()
            got = await asyncio.wait_for(asyncio.gather(*(
                clients[i % 8].call(srv.address, "E.echo", None, d)
                for i, d in enumerate(datas))), 60)
            for (rsp, payload), d in zip(got, datas):
                assert rsp["crc"] == crc32c(d) and bytes(payload) == d
            assert await _until(lambda: _counts() == (rx0 + 32, tx0 + 32))
            # a reader and a writer on each end of each connection
            assert await _until(lambda: _movers_alive() == before + 32)
        finally:
            for c in clients:
                await c.close()
            await srv.stop()
        assert _movers_alive() == before
    asyncio.run(body())


def test_craq_write_crosses_every_hop_on_a_mover(native):
    """A 4 MiB CRAQ write through a 3-replica chain: client -> head ->
    middle -> tail, each body received (and sent) on a mover; every
    replica stores the same bytes and CRC32C."""
    from t3fs.client.storage_client import StorageClient
    from t3fs.storage.types import ChunkId, ChunkState
    from t3fs.testing.fabric import StorageFabric

    chunk = 4 << 20

    async def body():
        fab = StorageFabric(num_nodes=3, replicas=3)
        await fab.start()
        try:
            sc = StorageClient(lambda: fab.routing, client=fab.client)
            cid = ChunkId(43, 0)
            data = os.urandom(chunk)
            rx0, tx0 = _counts()
            r = await sc.write_chunk(fab.chain_id, cid, 0, data,
                                     chunk_size=chunk)
            assert r.status.code == int(StatusCode.OK), r.status
            assert _counts()[0] - rx0 == 3
            assert await _until(lambda: _counts()[1] - tx0 == 3)
            targets = [t for n in fab.nodes for t in n.targets.values()]
            assert len(targets) == 3
            for t in targets:
                meta = t.engine.get_meta(cid)
                assert meta.state == ChunkState.COMMIT
                assert meta.checksum == crc32c(data)
                assert t.engine.read(cid) == data
        finally:
            await fab.stop()
    asyncio.run(body())
