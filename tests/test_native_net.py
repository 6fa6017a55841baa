"""Native io_uring socket transport (t3fs/native/net_pump.cpp +
t3fs/net/native_conn.py) vs the asyncio transport.

ROADMAP #2 / r3 verdict missing #2.  jax-free on purpose: this file is
part of the sanitizer suite (`make sanitize`), where jaxlib cannot load.
"""

import asyncio
import os
from dataclasses import dataclass

import pytest

from t3fs.net.client import Client
from t3fs.net.server import Server, rpc_method, service
from t3fs.utils.serde import serde_struct
from t3fs.utils.status import StatusCode, StatusError


def run(coro):
    return asyncio.run(coro)


@serde_struct
@dataclass
class NEchoReq:
    n: int = 0
    tag: str = ""


@service("NEcho")
class EchoSvc:
    @rpc_method
    async def echo(self, req: NEchoReq, payload, conn):
        # payload is a bytes-like buffer (zero-copy RX hands a
        # memoryview); bytes() materializes before the reverse
        return NEchoReq(n=req.n + 1, tag=req.tag), bytes(payload)[::-1]

    @rpc_method
    async def boom(self, req: NEchoReq, payload, conn):
        from t3fs.utils.status import make_error
        raise make_error(StatusCode.INVALID_ARG, "boom")


async def _roundtrip(n_calls: int = 50, payload=b"x" * 100_000):
    srv = Server()
    srv.add_service(EchoSvc())
    await srv.start()
    cli = Client()
    try:
        for i in range(n_calls):
            rsp, p = await cli.call(srv.address, "NEcho.echo",
                                    NEchoReq(n=i, tag="t" * (i % 7)),
                                    payload=payload)
            assert rsp.n == i + 1 and p == payload[::-1]
        with pytest.raises(StatusError) as ei:
            await cli.call(srv.address, "NEcho.boom", NEchoReq())
        assert ei.value.code == StatusCode.INVALID_ARG
    finally:
        await cli.close()
        await srv.stop()


def test_native_transport_roundtrip(monkeypatch):
    monkeypatch.setenv("T3FS_NATIVE_NET", "1")
    run(_roundtrip())


def test_native_transport_concurrent_calls(monkeypatch):
    monkeypatch.setenv("T3FS_NATIVE_NET", "1")

    async def body():
        srv = Server()
        srv.add_service(EchoSvc())
        await srv.start()
        cli = Client()
        try:
            payload = os.urandom(64 << 10)

            async def one(i):
                rsp, p = await cli.call(srv.address, "NEcho.echo",
                                        NEchoReq(n=i), payload=payload)
                assert rsp.n == i + 1 and p == payload[::-1]
            await asyncio.gather(*[one(i) for i in range(200)])
        finally:
            await cli.close()
            await srv.stop()
    run(body())


def test_native_server_asyncio_client_interop(monkeypatch):
    """Same wire format both ways: a native-transport server serves an
    asyncio-transport client and vice versa."""
    async def native_server():
        monkeypatch.setenv("T3FS_NATIVE_NET", "1")
        srv = Server()
        srv.add_service(EchoSvc())
        await srv.start()
        monkeypatch.setenv("T3FS_NATIVE_NET", "0")   # client side: asyncio
        cli = Client()
        try:
            rsp, p = await cli.call(srv.address, "NEcho.echo",
                                    NEchoReq(n=41), payload=b"abc")
            assert rsp.n == 42 and p == b"cba"
        finally:
            await cli.close()
            await srv.stop()
    run(native_server())

    async def native_client():
        monkeypatch.setenv("T3FS_NATIVE_NET", "0")
        srv = Server()
        srv.add_service(EchoSvc())
        await srv.start()
        monkeypatch.setenv("T3FS_NATIVE_NET", "1")
        cli = Client()
        try:
            rsp, p = await cli.call(srv.address, "NEcho.echo",
                                    NEchoReq(n=1), payload=b"xyz")
            assert rsp.n == 2 and p == b"zyx"
        finally:
            await cli.close()
            await srv.stop()
    run(native_client())


def test_native_transport_peer_death(monkeypatch):
    """Server stop must fail in-flight/subsequent calls with a transport
    status, and the client must reconnect to a revived server."""
    monkeypatch.setenv("T3FS_NATIVE_NET", "1")

    async def body():
        srv = Server()
        srv.add_service(EchoSvc())
        await srv.start()
        address = srv.address
        cli = Client()
        try:
            rsp, _ = await cli.call(address, "NEcho.echo", NEchoReq(n=0))
            assert rsp.n == 1
            await srv.stop()
            with pytest.raises(StatusError) as ei:
                await cli.call(address, "NEcho.echo", NEchoReq(n=0),
                               timeout=3.0)
            assert ei.value.code in (StatusCode.RPC_SEND_FAILED,
                                     StatusCode.RPC_CONNECT_FAILED,
                                     StatusCode.RPC_TIMEOUT)
            # revive on the same port; the client's next call reconnects
            host, port = address.rsplit(":", 1)
            srv2 = Server(host=host, port=int(port))
            srv2.add_service(EchoSvc())
            await srv2.start()
            rsp, _ = await cli.call(address, "NEcho.echo", NEchoReq(n=7))
            assert rsp.n == 8
            await srv2.stop()
        finally:
            await cli.close()
    run(body())


def test_native_transport_large_frames(monkeypatch):
    """Multi-megabyte payloads cross the pump intact (partial sends and
    recv reassembly across many 256 KiB reads)."""
    monkeypatch.setenv("T3FS_NATIVE_NET", "1")
    run(_roundtrip(n_calls=4, payload=os.urandom(8 << 20)))


def test_native_transport_compression(monkeypatch):
    monkeypatch.setenv("T3FS_NATIVE_NET", "1")

    async def body():
        srv = Server(compress_threshold=1024)
        srv.add_service(EchoSvc())
        await srv.start()
        cli = Client(compress_threshold=1024)
        try:
            payload = b"A" * 200_000          # highly compressible
            rsp, p = await cli.call(srv.address, "NEcho.echo",
                                    NEchoReq(n=5), payload=payload)
            assert rsp.n == 6 and p == payload[::-1]
        finally:
            await cli.close()
            await srv.stop()
    run(body())


def test_native_server_kills_conn_on_garbage(monkeypatch):
    """A peer sending garbage (bad magic / corrupt CRC) must get its
    connection dropped by the pump's C++ parser without touching other
    clients or the listener."""
    import socket
    import struct

    monkeypatch.setenv("T3FS_NATIVE_NET", "1")

    async def body():
        from t3fs.ops.codec import crc32c

        srv = Server()
        srv.add_service(EchoSvc())
        await srv.start()
        cli = Client()
        try:
            host, port = srv.address.rsplit(":", 1)

            def attack(frame: bytes) -> bool:
                """Send bytes; True ONLY if the server actively closed
                on us (EOF/RST).  A TIMEOUT means the server neither
                answered nor dropped — a stalled-parser regression must
                FAIL here, not pass slowly."""
                s = socket.create_connection((host, int(port)), timeout=5)
                try:
                    s.sendall(frame)
                    s.settimeout(5)
                    try:
                        return s.recv(1) == b""     # EOF = dropped
                    except socket.timeout:
                        return False                # stalled = regression
                except (ConnectionResetError, BrokenPipeError):
                    return True
                finally:
                    s.close()

            from t3fs.net.wire import pack_header

            # bad magic
            assert await asyncio.to_thread(attack, b"GARBAGE!" * 8)
            # valid magic, corrupted header CRC (flip the stored CRC of
            # an otherwise-valid header built from wire.MAGIC, so a
            # future magic bump cannot silently turn this into a plain
            # bad-magic case)
            good = pack_header(8, 0, 0, 0)
            head = good[:20] + struct.pack(
                "<I", struct.unpack("<I", good[20:])[0] ^ 0xFFFF)
            assert await asyncio.to_thread(attack, head + b"x" * 8)
            # valid header, corrupted MESSAGE CRC
            msg = b"m" * 16
            head = pack_header(len(msg), 0, 0, crc32c(msg) ^ 1)
            assert await asyncio.to_thread(attack, head + msg)
            # oversized length field (header itself is self-consistent)
            head = pack_header(1 << 30, 0, 0, 0)
            assert await asyncio.to_thread(attack, head)

            # a real client still works after all of that
            rsp, _ = await cli.call(srv.address, "NEcho.echo",
                                    NEchoReq(n=10))
            assert rsp.n == 11
        finally:
            await cli.close()
            await srv.stop()
    run(body())


def test_native_transport_fragmented_frames(monkeypatch):
    """Frames arriving one byte at a time must reassemble in the pump's
    staging buffer exactly like the asyncio frame reader."""
    import socket

    monkeypatch.setenv("T3FS_NATIVE_NET", "1")

    async def body():
        from t3fs.net.wire import (
            HEADER_SIZE, MessagePacket, pack_header, unpack_header,
        )
        from t3fs.ops.codec import crc32c
        from t3fs.utils import serde

        srv = Server()
        srv.add_service(EchoSvc())
        try:
            await srv.start()
            host, port = srv.address.rsplit(":", 1)

            pkt = MessagePacket(uuid=77, method="NEcho.echo", is_req=True)
            pkt.body = NEchoReq(n=5)
            msg = serde.dumps(pkt)
            payload = b"frag"
            frame = pack_header(len(msg), len(payload), 1, crc32c(msg)) \
                + msg + payload

            def drip():
                s = socket.create_connection((host, int(port)), timeout=10)
                try:
                    for b in frame:
                        s.sendall(bytes([b]))
                    s.settimeout(10)
                    head = b""
                    while len(head) < HEADER_SIZE:
                        chunk = s.recv(HEADER_SIZE - len(head))
                        assert chunk, "server closed instead of replying"
                        head += chunk
                    msg_len, payload_len, _flags, _crc = unpack_header(head)
                    body_b = b""
                    while len(body_b) < msg_len + payload_len:
                        chunk = s.recv(msg_len + payload_len - len(body_b))
                        assert chunk
                        body_b += chunk
                    return serde.loads(body_b[:msg_len])
                finally:
                    s.close()

            rsp = await asyncio.to_thread(drip)
            # a full round trip through the byte-at-a-time reassembly:
            # the ECHOED body, not merely any reply
            assert rsp.status.code == 0 and rsp.body.n == 6, rsp
        finally:
            await srv.stop()
    run(body())


def test_zero_copy_bulk_plane(monkeypatch):
    """r4 verdict missing #3: payloads at/above ZC_MIN must cross the
    native pump WITHOUT a staging copy — TX pins the caller's buffer
    (tx_zc_bytes counts it, tx_staged_bytes only carries headers+small
    frames) and RX hands the payload to handlers as a memoryview over
    the pump's pooled buffer.  Pins must drain once the frames are on
    the wire."""
    monkeypatch.setenv("T3FS_NATIVE_NET", "1")

    async def body():
        from t3fs.net.native_conn import NativePump, ZC_MIN

        seen_types = []

        @service("ZCProbe")
        class Probe:
            @rpc_method
            async def sink(self, req: NEchoReq, payload, conn):
                seen_types.append((len(payload), type(payload).__name__))
                from t3fs.ops.codec import crc32c
                # CRC over the zero-copy view must work w/o materializing
                return NEchoReq(n=crc32c(payload) & 0x7FFFFFFF), b""

        srv = Server()
        srv.add_service(Probe())
        await srv.start()
        cli = Client()
        try:
            from t3fs.ops.codec import crc32c
            big = os.urandom(1 << 20)
            small = os.urandom(256)
            r1, _ = await cli.call(srv.address, "ZCProbe.sink",
                                   NEchoReq(), payload=big)
            assert r1.n == crc32c(big) & 0x7FFFFFFF
            r2, _ = await cli.call(srv.address, "ZCProbe.sink",
                                   NEchoReq(), payload=small)
            assert r2.n == crc32c(small) & 0x7FFFFFFF

            pump = NativePump.get()
            stats = pump.stats()
            # the 1 MiB payload rode the zero-copy path...
            assert stats["tx_zc_bytes"] >= len(big), stats
            # ...and was NOT staged: staged carries only headers + the
            # small frame (well under one big payload)
            assert stats["tx_staged_bytes"] < len(big) // 2, stats
            # the server saw a memoryview for the big payload, bytes for
            # the small one (copy threshold)
            assert dict((n >= ZC_MIN, t) for n, t in seen_types) == {
                True: "memoryview", False: "bytes"}, seen_types
            # pins drain once the kernel is done with the buffers
            for _ in range(100):
                if pump.stats()["tx_pins"] == 0:
                    break
                await asyncio.sleep(0.02)
            assert pump.stats()["tx_pins"] == 0
        finally:
            await cli.close()
            await srv.stop()
    run(body())


def test_zero_copy_remote_buf_plane(monkeypatch):
    """RemoteBuf transfers ride the zero-copy plane: a one-sided READ
    ships the registered region's view directly (send-from-pool), and a
    one-sided WRITE lands the RX view straight into the registered
    buffer."""
    monkeypatch.setenv("T3FS_NATIVE_NET", "1")

    async def body():
        from t3fs.net.rdma import (
            BufferRegistry, remote_read, remote_write,
        )
        reg = BufferRegistry()
        srv = Server()
        srv.add_service(reg)
        await srv.start()
        cli = Client()
        try:
            data = os.urandom(512 << 10)
            handle = reg.register(data)
            conn = await cli._get_conn(srv.address)
            got = await remote_read(conn, handle)
            assert bytes(got) == data
            # one-sided write into a fresh registered region
            h2 = reg.register(len(data))
            await remote_write(conn, h2, data)
            assert bytes(reg.local_view(h2)) == data
        finally:
            await cli.close()
            await srv.stop()
    run(body())
