"""Net layer: echo service, typed bodies, errors, timeouts, duplex RemoteBuf
emulation (reference analogs: tests/common/net/TestEcho.cc, TestProcessor.cc,
tests/common/net/ib/TestRDMA.cc)."""

import asyncio
import os
from dataclasses import dataclass

import pytest

from t3fs.native import load_library
from t3fs.net import Server, Client, rpc_method, service
from t3fs.net import conn as conn_mod
from t3fs.net.conn import Connection
from t3fs.net.rdma import BufferRegistry, RemoteBuf, remote_read, remote_write
from t3fs.utils.serde import serde_struct
from t3fs.utils.status import StatusCode, StatusError, make_error


@serde_struct
@dataclass
class NetEchoReq:
    text: str = ""
    n: int = 0


@serde_struct
@dataclass
class NetEchoRsp:
    text: str = ""
    n: int = 0


@service("Echo")
class EchoService:
    @rpc_method
    async def echo(self, body: NetEchoReq, payload: bytes, conn):
        return NetEchoRsp(text=body.text, n=body.n + 1), payload

    @rpc_method
    async def fail(self, body, payload, conn):
        raise make_error(StatusCode.CHUNK_NOT_FOUND, "nope")

    @rpc_method
    async def slow(self, body, payload, conn):
        await asyncio.sleep(5)
        return None, b""

    @rpc_method
    async def pull(self, body: RemoteBuf, payload: bytes, conn):
        """Server-side one-sided READ of the client's registered buffer."""
        data = await remote_read(conn, body)
        return NetEchoRsp(n=len(data)), data.upper()


@pytest.fixture
def loop_run():
    def run(coro):
        return asyncio.run(coro)
    return run


async def _with_cluster(fn):
    server = Server()
    server.add_service(EchoService())
    await server.start()
    client = Client()
    try:
        await fn(server, client)
    finally:
        await client.close()
        await server.stop()


def test_echo_roundtrip(loop_run):
    async def body(server, client):
        rsp, payload = await client.call(server.address, "Echo.echo",
                                         NetEchoReq(text="hi", n=41), payload=b"bulk")
        assert rsp.text == "hi" and rsp.n == 42 and payload == b"bulk"
        # concurrent calls multiplex one connection
        rsps = await asyncio.gather(*[
            client.call(server.address, "Echo.echo", NetEchoReq(n=i)) for i in range(20)])
        assert sorted(r[0].n for r in rsps) == list(range(1, 21))
    loop_run(_with_cluster(body))


def test_error_propagation(loop_run):
    async def body(server, client):
        with pytest.raises(StatusError) as ei:
            await client.call(server.address, "Echo.fail")
        assert ei.value.code == StatusCode.CHUNK_NOT_FOUND
        with pytest.raises(StatusError) as ei:
            await client.call(server.address, "Echo.nosuch")
        assert ei.value.code == StatusCode.RPC_METHOD_NOT_FOUND
    loop_run(_with_cluster(body))


def test_timeout(loop_run):
    async def body(server, client):
        with pytest.raises(StatusError) as ei:
            await client.call(server.address, "Echo.slow", timeout=0.1)
        assert ei.value.code == StatusCode.RPC_TIMEOUT
    loop_run(_with_cluster(body))


def test_connect_failure(loop_run):
    async def body():
        client = Client(connect_timeout=0.5)
        with pytest.raises(StatusError) as ei:
            await client.call("127.0.0.1:1", "Echo.echo")
        assert ei.value.code == StatusCode.RPC_CONNECT_FAILED
    loop_run(body())


def test_remote_buf_duplex(loop_run):
    """Client registers a buffer; server pulls it (RDMA READ emulation) and
    the response returns transformed payload; then server-side write-back."""
    async def body(server, client):
        bufs = BufferRegistry()
        client.add_service(bufs)
        handle = bufs.register(b"hello one-sided world")
        rsp, payload = await client.call(server.address, "Echo.pull", handle)
        assert rsp.n == len("hello one-sided world")
        assert payload == b"HELLO ONE-SIDED WORLD"
    loop_run(_with_cluster(body))


def test_remote_buf_write_back(loop_run):
    """Server pushes into a client-registered buffer (RDMA WRITE emulation)."""
    @service("Pusher")
    class Pusher:
        @rpc_method
        async def push(self, body: RemoteBuf, payload: bytes, conn):
            await remote_write(conn, body, b"X" * body.length)
            return None, b""

    async def body():
        server = Server()
        server.add_service(Pusher())
        await server.start()
        client = Client()
        bufs = BufferRegistry()
        client.add_service(bufs)
        try:
            handle = bufs.register(8)
            await client.call(server.address, "Pusher.push", handle)
            assert bytes(bufs.local_view(handle)) == b"X" * 8
        finally:
            await client.close()
            await server.stop()
    loop_run(body())


def test_concurrent_calls_share_one_connection(loop_run):
    """200 concurrent 64 KiB echoes multiplex one client connection, and
    each caller gets its own reply back."""
    async def body(server, client):
        payloads = [os.urandom(64 << 10) for _ in range(200)]

        async def one(i):
            rsp, payload = await client.call(server.address, "Echo.echo",
                                             NetEchoReq(n=i),
                                             payload=payloads[i])
            assert rsp.n == i + 1 and payload == payloads[i]
        await asyncio.gather(*[one(i) for i in range(len(payloads))])
        assert len(client._conns) == 1 and len(server._conns) == 1
        assert client.epoch(server.address) == 1
    loop_run(_with_cluster(body))


def test_call_after_server_restart_reconnects(loop_run):
    """Stopping the server fails a call in flight with a transport status
    and a call made while it is down with a connect failure; the next call
    reconnects to a server revived on the same port."""
    async def body():
        server = Server()
        server.add_service(EchoService())
        await server.start()
        address = server.address
        client = Client(connect_timeout=2.0)
        revived = None
        try:
            rsp, _ = await client.call(address, "Echo.echo", NetEchoReq(n=0))
            assert rsp.n == 1
            in_flight = asyncio.ensure_future(
                client.call(address, "Echo.slow", timeout=10.0))
            await asyncio.sleep(0.1)
            await server.stop()
            with pytest.raises(StatusError) as ei:
                await in_flight
            assert ei.value.code == StatusCode.RPC_SEND_FAILED
            with pytest.raises(StatusError) as ei:
                await client.call(address, "Echo.echo", NetEchoReq(n=0))
            assert ei.value.code == StatusCode.RPC_CONNECT_FAILED
            host, port = address.rsplit(":", 1)
            revived = Server(host=host, port=int(port))
            revived.add_service(EchoService())
            await revived.start()
            rsp, _ = await client.call(address, "Echo.echo", NetEchoReq(n=7))
            assert rsp.n == 8
            assert client.epoch(address) == 2
        finally:
            await client.close()
            if revived is not None:
                await revived.stop()
    loop_run(body())


@pytest.mark.parametrize("n", [4 << 10, Connection.THREAD_BYTES, 4 << 20],
                         ids=["4KiB", "THREAD_BYTES", "4MiB"])
def test_remote_buf_read_write_sizes(loop_run, n):
    """remote_read and remote_write move the exact bytes of a region; from
    THREAD_BYTES on, each payload leaves on its connection's tx mover, and
    a payload far past the staged prefix comes in on an rx mover."""
    load_library()          # movers run in the native library

    def thread_bodies() -> tuple[int, int]:
        return (conn_mod.RX_THREAD_BODIES._value,
                conn_mod.TX_THREAD_BODIES._value)

    async def body():
        reg = BufferRegistry()
        server = Server()
        server.add_service(reg)
        await server.start()
        client = Client()
        try:
            data = os.urandom(n)
            conn = await client._get_conn(server.address)
            rx0, tx0 = thread_bodies()
            got = await remote_read(conn, reg.register(data))
            assert bytes(got) == data
            handle = reg.register(n)
            back = bytes(reversed(data))
            await remote_write(conn, handle, back)
            assert bytes(reg.local_view(handle)) == back
            rx, tx = thread_bodies()
            if n < Connection.THREAD_BYTES:
                assert (rx - rx0, tx - tx0) == (0, 0)
            else:
                assert tx - tx0 == 2
            if n >= 2 * Connection.THREAD_BYTES:
                assert rx - rx0 == 2
        finally:
            await client.close()
            await server.stop()
    loop_run(body())


# ---- wire compression (MessagePacket UseCompress analog) ----

def test_compressed_roundtrip(loop_run):
    """Both directions compressed: large compressible body + payload
    round-trip intact through a compress-enabled client and server."""
    async def body():
        server = Server(compress_threshold=1024)
        server.add_service(EchoService())
        await server.start()
        client = Client(compress_threshold=1024)
        try:
            text = "pattern " * 4096            # highly compressible
            payload = b"\x00" * 65536
            rsp, pay = await client.call(server.address, "Echo.echo",
                                         NetEchoReq(text=text, n=1),
                                         payload=payload)
            assert rsp.text == text and pay == payload
        finally:
            await client.close()
            await server.stop()
    loop_run(body())


def test_mixed_peers_compression(loop_run):
    """A compressing client against a non-compressing server (and back):
    receivers always understand FLAG_COMPRESS regardless of local config."""
    async def body():
        server = Server()                        # compression off
        server.add_service(EchoService())
        await server.start()
        client = Client(compress_threshold=128)  # compression on
        try:
            text = "x" * 10000
            rsp, _ = await client.call(server.address, "Echo.echo",
                                       NetEchoReq(text=text))
            assert rsp.text == text
        finally:
            await client.close()
            await server.stop()
    loop_run(body())


def test_maybe_compress_policy():
    from t3fs.net.wire import FLAG_COMPRESS, maybe_compress

    # under threshold: untouched
    m, p, f = maybe_compress(b"abc", b"def", threshold=1024)
    assert (m, p, f) == (b"abc", b"def", 0)
    # compressible above threshold: flagged + smaller
    big = b"A" * 10000
    m, p, f = maybe_compress(big, big, threshold=1024)
    assert f == FLAG_COMPRESS and len(m) + len(p) < 2 * len(big)
    # incompressible (random) payload: shipped raw, no flag
    import os as _os
    rnd = _os.urandom(65536)
    m, p, f = maybe_compress(b"", rnd, threshold=1024)
    assert f == 0 and p is rnd
    # threshold 0 disables
    assert maybe_compress(big, b"", threshold=0)[2] == 0


def test_decompress_bomb_guard():
    import zlib

    import pytest as _pytest

    from t3fs.net.wire import FLAG_COMPRESS, FrameError, decompress_frame

    # corrupt stream -> FrameError (not a crash, not an OOM)
    with _pytest.raises(FrameError):
        decompress_frame(b"not-zlib", b"", FLAG_COMPRESS)
    # a genuine bomb: tiny compressed, expands past MAX_FRAME
    from t3fs.net import wire as _wire
    orig = _wire.MAX_FRAME
    _wire.MAX_FRAME = 1 << 16
    try:
        bomb = zlib.compress(b"\x00" * (1 << 20))
        with _pytest.raises(FrameError):
            decompress_frame(bomb, b"", FLAG_COMPRESS)
    finally:
        _wire.MAX_FRAME = orig


def test_truncated_compressed_frame_rejected():
    import zlib

    import pytest as _pytest

    from t3fs.net.wire import FLAG_COMPRESS, FrameError, decompress_frame

    full = zlib.compress(b"payload " * 1000)
    truncated = full[: len(full) - 4]    # valid prefix, missing final block
    with _pytest.raises(FrameError):
        decompress_frame(truncated, b"", FLAG_COMPRESS)


def test_compressed_large_frame_offload(loop_run):
    """Frames past OFFLOAD_BYTES take the to_thread path; data intact."""
    async def body():
        server = Server(compress_threshold=1024)
        server.add_service(EchoService())
        await server.start()
        client = Client(compress_threshold=1024)
        try:
            payload = b"Z" * (4 << 20)     # > OFFLOAD_BYTES, compressible
            rsp, pay = await client.call(server.address, "Echo.echo",
                                         NetEchoReq(n=7), payload=payload)
            assert rsp.n == 8 and pay == payload
        finally:
            await client.close()
            await server.stop()
    loop_run(body())

def test_rpc_latency_decomposition_and_rpc_top():
    """r3 verdict #7: the wire timestamps must be CONSUMED — every call
    records a queue/server/network split per method, dumps to JSON, and
    the rpc-top CLI renders the table."""
    import json
    import subprocess
    import sys

    from t3fs.net.rpcstats import RPC_STATS, render_top

    async def body():
        from t3fs.net.client import Client
        from t3fs.net.server import Server
        from t3fs.utils.serde import serde_struct
        from dataclasses import dataclass
        from t3fs.net.server import service, rpc_method

        @serde_struct
        @dataclass
        class PingReq:
            n: int = 0

        @service("LatPing")
        class PingSvc:
            @rpc_method
            async def ping(self, req: PingReq, payload, conn):
                await asyncio.sleep(0.01)     # measurable server time
                return PingReq(n=req.n + 1), b""

        RPC_STATS.clear()
        srv = Server()
        srv.add_service(PingSvc())
        await srv.start()
        cli = Client()
        try:
            for i in range(20):
                rsp, _ = await cli.call(srv.address, "LatPing.ping",
                                        PingReq(n=i))
                assert rsp.n == i + 1
        finally:
            await cli.close()
            await srv.stop()

        snap = RPC_STATS.snapshot()
        row = snap["LatPing.ping"]
        assert row["count"] == 20
        # the 10ms handler sleep must show up in the SERVER component
        assert row["server_p50_ms"] >= 9.0, row
        # total >= server, and the network remainder is non-negative
        assert row["total_p50_ms"] >= row["server_p50_ms"], row
        assert row["network_p50_ms"] >= 0.0, row
        return snap

    snap = asyncio.run(body())
    # render via the CLI entry point
    import tempfile, os
    with tempfile.TemporaryDirectory() as td:
        p = os.path.join(td, "rpc.json")
        with open(p, "w") as f:
            json.dump(snap, f)
        out = subprocess.run(
            [sys.executable, "-m", "t3fs.cli.admin", "--mgmtd",
             "127.0.0.1:1", "rpc-top", p],
            capture_output=True, text=True, timeout=60)
        assert out.returncode == 0, (out.stdout, out.stderr)
        assert "LatPing.ping" in out.stdout
        assert "srv50" in out.stdout
    # merged render of two snapshots also works
    assert "LatPing.ping" in render_top([snap, snap])

def test_rpc_top_live_over_core_service():
    """rpc-top --live pulls Core.getRpcStats from a running node and
    renders the same table as the file path (the reference's 8 wire
    timestamps exist for live interrogation, not post-mortems)."""
    import json
    import subprocess
    import sys

    from t3fs.net.rpcstats import RPC_STATS

    # run the server + CLI inside one loop so the CLI subprocess can
    # reach the live process
    async def full():
        from t3fs.core.service import AppInfo, CoreService, EchoReq
        from t3fs.net.client import Client
        from t3fs.net.server import Server

        RPC_STATS.clear()
        srv = Server()
        srv.add_service(CoreService(AppInfo(3, "demo", "")))
        await srv.start()
        cli = Client()
        try:
            for _ in range(5):
                await cli.call(srv.address, "Core.echo",
                               EchoReq(message="hi"))

            def run_cli():
                return subprocess.run(
                    [sys.executable, "-m", "t3fs.cli.admin", "--mgmtd",
                     "127.0.0.1:1", "rpc-top", "--live", srv.address],
                    capture_output=True, text=True, timeout=60)
            out = await asyncio.to_thread(run_cli)
            assert out.returncode == 0, (out.stdout, out.stderr)
            assert "Core.echo" in out.stdout, out.stdout
        finally:
            await cli.close()
            await srv.stop()
    asyncio.run(full())
