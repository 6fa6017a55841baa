"""Distributed tracing: span scopes, wire propagation over real TCP RPC
hops, one trace_id across a 3-replica CRAQ chain write, head+tail sampling,
SpanBuffer bounds, and the monitor round-trip + trace-show rendering.

Reference analog: common/utils/Tracing.h grown Dapper-style — see
docs/observability.md for the span model and sampling policy.
"""

import asyncio

import pytest

from t3fs.cli.admin import render_trace
from t3fs.client.layout import FileLayout
from t3fs.client.storage_client import StorageClient
from t3fs.monitor.reporter import MonitorReporter
from t3fs.monitor.service import (
    MetricsDB, MonitorCollectorServer, QuerySpansReq,
)
from t3fs.net import Client, Server, rpc_method, service
from t3fs.net.conn import Connection
from t3fs.net.wire import MessagePacket
from t3fs.testing.fabric import StorageFabric
from t3fs.utils import serde, tracing
from t3fs.utils.status import StatusCode
from t3fs.utils.tracing import (
    BUFFER, NULL_SPAN, TraceConfig, configure, reset_tracing,
)


# slack between two perf_counter intervals read a few calls apart
EPS_S = 1e-3


def run(coro):
    return asyncio.run(coro)


@pytest.fixture(autouse=True)
def _clean_tracing():
    reset_tracing()
    yield
    reset_tracing()


def _drain_all():
    out = []
    while True:
        batch = BUFFER.drain()
        if not batch:
            return out
        out.extend(batch)


# ---- span scopes (in-process) ----

def test_span_scopes_nest_and_restore_outer():
    configure(TraceConfig(sample_rate=1.0, export="all"))
    with tracing.start_root("root") as root:
        assert tracing.current_span() is root
        with tracing.span("child") as child:
            assert child.trace_id == root.trace_id
            assert child.parent_id == root.span_id
            assert tracing.current_span() is child
        # the outer span is restored, not clobbered to None
        assert tracing.current_span() is root
    assert tracing.current_span() is None
    rows = _drain_all()
    assert {r["name"] for r in rows} == {"root", "child"}
    assert len({r["trace_id"] for r in rows}) == 1


def test_nested_start_root_joins_active_trace():
    configure(TraceConfig(sample_rate=1.0, export="all"))
    with tracing.start_root("outer") as outer:
        with tracing.start_root("inner") as inner:
            # nested roots don't fork a new trace (ckpt restore issuing
            # kvcache/storage reads stays one trace)
            assert inner.trace_id == outer.trace_id
            assert inner.parent_id == outer.span_id


def test_add_event_attaches_to_active_span_and_points():
    configure(TraceConfig(sample_rate=1.0, export="all"))
    tracing.add_event("outside")          # no active span: a no-op
    with tracing.start_root("op") as sp:
        tracing.add_event("both", "detail")
    assert [e[1:] for e in sp.events] == [("both", "detail")]
    assert not any(hasattr(tracing, n)
                   for n in ("Points", "current_trace", "end_trace"))


# ---- head sampling: off means zero overhead ----

def test_unsampled_root_does_no_work_and_ships_default_envelope():
    # sample_rate stays 0 (default): start_root yields the no-op span
    baseline = serde.dumps(MessagePacket(uuid=7, method="Echo.echo"))
    with tracing.start_root("client.op") as sp:
        assert sp is NULL_SPAN and not sp
        assert tracing.current_span() is None
        pkt = MessagePacket(uuid=7, method="Echo.echo")
        Connection()._stamp_trace(pkt)
    # the envelope is byte-identical to one built with tracing never
    # touched: zero extra wire state for unsampled requests
    assert serde.dumps(pkt) == baseline
    assert pkt.trace_id == 0 and not pkt.sampled
    assert BUFFER.stats()["finished"] == 0
    assert BUFFER.pending_export() == 0


def test_sampled_stamp_rides_the_envelope_and_roundtrips():
    configure(TraceConfig(sample_rate=1.0))
    with tracing.start_root("client.op") as sp:
        pkt = MessagePacket(uuid=7, method="Echo.echo")
        Connection()._stamp_trace(pkt)
    assert pkt.trace_id == sp.trace_id
    assert pkt.parent_span_id == sp.span_id and pkt.sampled
    back = serde.loads(serde.dumps(pkt))
    assert (back.trace_id, back.parent_span_id, back.sampled) == \
        (pkt.trace_id, pkt.parent_span_id, True)


# ---- wire propagation over a real TCP hop ----

@service("Echo")
class _EchoService:
    @rpc_method
    async def echo(self, body, payload, conn):
        tracing.add_event("handler.ran")
        return None, payload


def test_rpc_hop_propagates_context():
    configure(TraceConfig(sample_rate=1.0, export="all"))

    async def body():
        server = Server()
        server.add_service(_EchoService())
        await server.start()
        client = Client()
        try:
            with tracing.start_root("test.root", force=True) as root:
                await client.call(server.address, "Echo.echo")
            return root, server.address
        finally:
            await client.close()
            await server.stop()

    root, address = run(body())
    rows = {r["name"]: r for r in _drain_all()}
    client_sp = rows["rpc.Echo.echo"]
    server_sp = rows["Echo.echo"]
    # one trace across the hop; the server span parents to the client span
    assert client_sp["trace_id"] == server_sp["trace_id"] == root.trace_id
    assert client_sp["parent_id"] == root.span_id
    assert server_sp["parent_id"] == client_sp["span_id"]
    assert server_sp["kind"] == "server" and server_sp["root"]
    # the server span carries the wire/queue decomposition + serving addr
    assert server_sp["tags"]["addr"] == address
    assert server_sp["tags"]["wire_s"] >= 0.0
    assert server_sp["tags"]["queue_s"] >= 0.0
    # handler-side add_event attached to the server span
    assert [e[1] for e in server_sp["events"]] == ["handler.ran"]


def test_unsampled_rpc_opens_no_server_span():
    async def body():
        server = Server()
        server.add_service(_EchoService())
        await server.start()
        client = Client()
        try:
            await client.call(server.address, "Echo.echo")
        finally:
            await client.close()
            await server.stop()

    run(body())
    assert BUFFER.stats()["finished"] == 0


# ---- one trace_id across a 3-replica chain write ----

def test_chain_write_is_one_trace_across_all_hops():
    configure(TraceConfig(sample_rate=1.0, export="all"))

    async def body():
        fabric = StorageFabric(num_nodes=3, replicas=3)
        await fabric.start()
        try:
            sc = StorageClient(lambda: fabric.routing, client=fabric.client)
            lay = FileLayout(chunk_size=4096, chains=[fabric.chain_id])
            results = await sc.write_file_range(lay, inode=7, offset=0,
                                                data=b"x" * 1000)
            assert all(r.status.code == int(StatusCode.OK) for r in results)
        finally:
            await fabric.stop()

    run(body())
    rows = _drain_all()
    roots = [r for r in rows if r["name"] == "storage_client.write_chunk"]
    assert len(roots) == 1
    tid = roots[0]["trace_id"]
    trace = [r for r in rows if r["trace_id"] == tid]
    by_id = {r["span_id"]: r for r in trace}
    servers = [r for r in trace if r["kind"] == "server"]
    # head + two forward hops, each on its own node
    assert len(servers) == 3
    assert len({s["tags"]["addr"] for s in servers}) == 3
    # every hop's span walks parent links back to the client root
    for s in servers:
        cur = s
        hops = 0
        while cur["parent_id"]:
            cur = by_id[cur["parent_id"]]
            hops += 1
            assert hops < 16
        assert cur is roots[0]
    # the apply/forward decomposition from the storage trace dict rides
    # the server spans (the tail's forward_s times the no-successor probe)
    assert all("apply_s" in s["tags"] for s in servers)
    assert all("forward_s" in s["tags"] for s in servers)
    # ... and the finer split: each client leg's send, each hop's frame
    # read, CRC and engine apply.  The default pipeline (off) awaits the
    # CRC before the apply interval, which then holds the engine alone.
    legs = [r for r in trace if r["kind"] == "client"
            and r["name"].startswith("rpc.Storage.")]
    assert len(legs) == 3
    assert all(0 <= c["tags"]["send_s"] <= c["dur_s"] for c in legs)
    for s in servers:
        t = s["tags"]
        assert 0 <= t["frame_s"] <= t["wire_s"] + EPS_S
        assert t["crc_s"] >= 0
        assert 0 <= t["engine_s"] <= t["apply_s"] + EPS_S

    text = render_trace(trace)
    assert f"trace {tid:#x}" in text
    assert text.count("[server]") == 3
    for token in ("send=", "wire=", "frame=", "queue=", "crc=", "apply=",
                  "engine=", "forward="):
        assert token in text


def _sampled_chain_write(**fabric_kw) -> list[dict]:
    """One sampled 3-replica chain write; the server spans of its trace."""
    configure(TraceConfig(sample_rate=1.0, export="all"))

    async def body():
        fabric = StorageFabric(num_nodes=3, replicas=3, **fabric_kw)
        await fabric.start()
        try:
            sc = StorageClient(lambda: fabric.routing, client=fabric.client)
            lay = FileLayout(chunk_size=4096, chains=[fabric.chain_id])
            await sc.write_file_range(lay, inode=7, offset=0,
                                      data=b"x" * 1000)
        finally:
            await fabric.stop()

    run(body())
    return [r for r in _drain_all() if r["kind"] == "server"]


def test_overlap_write_apply_holds_crc_and_engine():
    # under the overlap pipeline a non-tail hop awaits its CRC inside the
    # apply interval, so crc_s and engine_s are its parts
    servers = _sampled_chain_write(write_pipeline="overlap")
    assert len(servers) == 3
    forwarding = [s["tags"] for s in servers
                  if "forward_status" in s["tags"]]
    assert len(forwarding) == 2                      # head and mid
    for t in forwarding:
        assert t["crc_s"] + t["engine_s"] <= t["apply_s"] + EPS_S


def test_unsampled_chain_write_ships_default_envelopes(monkeypatch):
    sent = []
    real = Connection._send_frame

    async def spy(self, packet, payload, flags):
        sent.append(packet)
        await real(self, packet, payload, flags)

    monkeypatch.setattr(Connection, "_send_frame", spy)

    async def body():
        fabric = StorageFabric(num_nodes=3, replicas=3)
        await fabric.start()
        try:
            sc = StorageClient(lambda: fabric.routing, client=fabric.client)
            lay = FileLayout(chunk_size=4096, chains=[fabric.chain_id])
            await sc.write_file_range(lay, inode=7, offset=0,
                                      data=b"x" * 1000)
        finally:
            await fabric.stop()

    run(body())
    writes = [p for p in sent if p.method.startswith("Storage.")]
    assert writes
    assert all(p.trace_id == 0 and p.parent_span_id == 0 and not p.sampled
               for p in sent)
    # no span opened anywhere, so no tag was set on one
    assert BUFFER.stats()["finished"] == 0


# ---- tail sampling ----

def test_tail_sampling_promotes_slow_and_errored_only():
    # fast + clean: buffered, never exported
    configure(TraceConfig(sample_rate=1.0, export="tail", slow_ms=1e6))
    with tracing.start_root("fast.op"):
        with tracing.span("leg"):
            pass
    assert BUFFER.pending_export() == 0
    assert BUFFER.stats()["buffered"] == 2

    # slow (per-method threshold): the whole trace promotes at root finish
    configure(TraceConfig(sample_rate=1.0, export="tail", slow_ms=1e6,
                          slow_ms_by_method="slow.op=0"))
    with tracing.start_root("slow.op"):
        with tracing.span("leg"):
            pass
    promoted = _drain_all()
    assert {r["name"] for r in promoted} == {"slow.op", "leg"}

    # errored child: promotes even though fast
    configure(TraceConfig(sample_rate=1.0, export="tail", slow_ms=1e6))
    with tracing.start_root("err.op"):
        with tracing.span("leg") as leg:
            leg.set_status(int(StatusCode.INTERNAL))
    promoted = _drain_all()
    assert {r["name"] for r in promoted} == {"err.op", "leg"}


def test_scope_exit_records_exception_status_and_promotes():
    configure(TraceConfig(sample_rate=1.0, export="tail", slow_ms=1e6))
    with pytest.raises(ValueError):
        with tracing.start_root("boom.op"):
            raise ValueError("nope")
    promoted = _drain_all()
    assert len(promoted) == 1 and promoted[0]["status"] != 0


def test_late_spans_of_promoted_trace_export_directly():
    # an overlap-pipeline forward can outlive the handler that promoted
    # the trace; its span must still reach the export queue
    configure(TraceConfig(sample_rate=1.0, export="tail",
                          slow_ms_by_method="root.op=0"))
    with tracing.start_root("root.op") as root:
        late = tracing.start_span("late.leg")
    assert BUFFER.pending_export() == 1          # root promoted at finish
    late.finish()
    rows = _drain_all()
    assert {r["name"] for r in rows} == {"root.op", "late.leg"}
    assert rows[-1]["trace_id"] == root.trace_id


def test_lone_root_spans_skip_the_buffer():
    # record_root: a finished span that is a whole trace (loop.wait,
    # ec.flush) exports at once under export=all, else is forgotten: the
    # tail rule is for requests, and a long idle wait is none
    configure(TraceConfig(sample_rate=1.0, export="tail", slow_ms=100.0))
    tracing.record_root("loop.wait", 1.0, 0.001, wait_s=0.5)
    tracing.record_root("loop.wait", 2.0, 0.2, wait_s=0.7)
    assert BUFFER.stats()["buffered"] == 0
    assert BUFFER.pending_export() == 0
    assert BUFFER.stats()["finished"] == 2
    configure(TraceConfig(sample_rate=1.0, export="all"))
    tracing.record_root("ec.flush", 3.0, 0.2, rows=1)
    (row,) = _drain_all()
    assert row["root"] and row["parent_id"] == 0 and row["kind"] == "local"
    assert (row["t0"], row["dur_s"], row["tags"]) == (3.0, 0.2, {"rows": 1})


# ---- SpanBuffer bounds ----

def test_span_buffer_bounded_under_churn():
    configure(TraceConfig(sample_rate=1.0, export="tail", slow_ms=1e6,
                          max_spans=64))
    for _ in range(300):
        with tracing.start_root("churn.op"):
            with tracing.span("leg"):
                pass
    stats = BUFFER.stats()
    assert stats["buffered"] <= 64
    assert stats["dropped"] > 0
    assert BUFFER.pending_export() == 0          # nothing promoted


def test_per_trace_span_cap():
    configure(TraceConfig(sample_rate=1.0, export="tail", slow_ms=1e6,
                          max_trace_spans=8))
    with tracing.start_root("big.op"):
        for _ in range(50):
            with tracing.span("leg"):
                pass
    stats = BUFFER.stats()
    assert stats["buffered"] <= 8
    assert stats["dropped"] >= 42


def test_export_queue_bounded():
    configure(TraceConfig(sample_rate=1.0, export="all", export_max=16))
    for _ in range(64):
        with tracing.start_root("op"):
            pass
    assert BUFFER.pending_export() <= 16
    assert BUFFER.stats()["dropped"] >= 48


# ---- monitor round-trip + trace-show rendering ----

def test_monitor_round_trip_and_render():
    configure(TraceConfig(sample_rate=1.0, export="all"))

    async def body():
        srv = MonitorCollectorServer()
        await srv.start()
        with tracing.start_root("op.root") as root:
            with tracing.span("op.leg"):
                tracing.add_event("hit", "x=1")
        tid = root.trace_id
        reporter = MonitorReporter(srv.address, node_id=9,
                                   node_type="storage")
        cli = Client()
        try:
            rsp = None
            for _ in range(100):     # reporter thread drains ~every 0.2s
                rsp, _ = await cli.call(srv.address, "Monitor.query_spans",
                                        QuerySpansReq(trace_id=tid))
                if len(rsp.spans) >= 2:
                    break
                await asyncio.sleep(0.05)
            assert len(rsp.spans) == 2
            leg = next(s for s in rsp.spans if s["name"] == "op.leg")
            assert leg["node_id"] == 9 and leg["node_type"] == "storage"

            text = render_trace(rsp.spans)
            assert f"trace {tid:#x}" in text and "(2 spans)" in text
            # the child renders indented under the root, events under it
            root_line = next(l for l in text.splitlines()
                             if "op.root" in l)
            leg_line = next(l for l in text.splitlines() if "op.leg" in l)
            assert not root_line.startswith(" ")
            assert leg_line.startswith("  ")
            assert ". +" in text and "hit x=1" in text

            # trace-slow style query: local roots only, name-filtered
            rsp, _ = await cli.call(srv.address, "Monitor.query_spans",
                                    QuerySpansReq(name_prefix="op.",
                                                  roots_only=True))
            assert [s["name"] for s in rsp.spans] == ["op.root"]
        finally:
            reporter.close()
            await cli.close()
            await srv.stop()

    run(body())


def test_spans_table_retention():
    db = MetricsDB(max_rows=3)
    for i in range(7):
        db.insert_spans(1, "storage", float(i), [
            {"trace_id": 100 + i, "span_id": i + 1, "parent_id": 0,
             "name": "op", "kind": "local", "t0": float(i),
             "dur_s": 0.001, "status": 0, "root": True}])
    rows = db.query_spans(name_prefix="op")
    assert len(rows) == 3
    # oldest-first pruning kept the newest traces
    assert {r["trace_id"] for r in rows} == {104, 105, 106}
    db.close()


def test_render_trace_orphans_root_at_top_level():
    # a parent tail-dropped on another node must not hide its children
    spans = [{"trace_id": 5, "span_id": 2, "parent_id": 999,
              "name": "orphan.leg", "kind": "server", "t0": 1.0,
              "dur_s": 0.01, "status": 0, "tags": {}, "events": []}]
    text = render_trace(spans)
    assert "orphan.leg" in text
    assert render_trace([]) == "(no spans)"
