"""Structured trace log -> Parquet.

Reference analogs: src/analytics/StructuredTraceLog.h (serde objects -> Parquet row groups),
StorageEventTrace per update (StorageOperator.h:153).
"""

import asyncio
import os
import tempfile

from t3fs.analytics.trace_log import (
    StorageEventTrace, StructuredTraceLog, read_trace,
)


def test_structured_trace_log_roundtrip():
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "events.parquet")
        tl = StructuredTraceLog(StorageEventTrace, path, rows_per_group=8)
        for i in range(20):
            tl.append(StorageEventTrace(ts=float(i), node_id=1,
                                        chunk_id=f"c{i}", update_ver=i,
                                        update_type="write", length=4096))
        tl.close()
        assert tl.rows_written == 20
        rows = list(read_trace(path, StorageEventTrace))
        assert len(rows) == 20
        assert rows[5].chunk_id == "c5" and rows[5].length == 4096
        assert isinstance(rows[0], StorageEventTrace)


def test_storage_update_writes_event_trace():
    """End to end: CRAQ writes produce one trace row per update hop."""
    from t3fs.testing.cluster import LocalCluster

    async def body():
        with tempfile.TemporaryDirectory() as d:
            cl = LocalCluster(num_nodes=3, replicas=3)
            await cl.start()
            logs = {}
            for nid, ss in cl.storage.items():
                path = os.path.join(d, f"n{nid}.parquet")
                logs[nid] = ss.node.trace_log = StructuredTraceLog(
                    StorageEventTrace, path, flush_interval_s=0.05)
            try:
                from t3fs.client.layout import FileLayout
                lay = FileLayout(chunk_size=4096, chains=[1])
                await cl.sc.write_file_range(lay, 9, 0, b"x" * 4096)
            finally:
                for tl in logs.values():
                    tl.close()
                rows = []
                for nid, tl in logs.items():
                    if os.path.exists(tl.path):
                        rows += [(nid, r) for r in read_trace(
                            tl.path, StorageEventTrace)]
                await cl.stop()
            # 3-replica chain: the update traversed all 3 nodes
            assert len(rows) == 3, rows
            assert all(r.update_type == "write" and r.commit_status == 0
                       for _, r in rows)
            assert all(r.latency_s > 0 and r.target_id > 0 for _, r in rows)
    asyncio.run(body())


def test_trace_query_top_and_filters():
    """The reader half: aggregate a written trace
    into per-group latency/error stats and filtered row streams."""
    from t3fs.analytics.trace_query import iter_rows, top

    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "ev.parquet")
        tl = StructuredTraceLog(StorageEventTrace, path,
                                flush_interval_s=0.05)
        for i in range(60):
            tl.append(StorageEventTrace(
                ts=float(i), node_id=1 + i % 3, target_id=101 + i % 3,
                chain_id=1 + i % 2, chunk_id=f"7.{i}",
                update_type="write", length=4096,
                commit_status=0 if i % 10 else 5016,
                latency_s=0.001 * (1 + i % 3)))
        tl.close()

        stats = top([path], by="node")
        assert len(stats) == 3 and sum(g.count for g in stats) == 60
        # sorted slowest-p99 first: node 3 sees the 3ms latencies
        assert stats[0].key == "node 3" and stats[0].p99_ms >= 3.0
        assert all(g.errors == 2 for g in stats)   # 6 errors spread 3 ways

        by_chain = {g.key: g for g in top([path], by="chain")}
        assert by_chain["chain 1"].count == 30

        # filters: node + errors_only; directory expansion
        rows = list(iter_rows([tmp], node=2, errors_only=True))
        assert rows and all(r["node_id"] == 2 and r["commit_status"] == 5016
                            for r in rows)


def test_trace_cli_commands():
    """trace-read / trace-top through the real CLI entry point."""
    import subprocess
    import sys

    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "ev.parquet")
        tl = StructuredTraceLog(StorageEventTrace, path,
                                flush_interval_s=0.05)
        for i in range(10):
            tl.append(StorageEventTrace(
                ts=float(i), node_id=1, target_id=101, chain_id=1,
                chunk_id=f"9.{i}", update_type="write", length=512,
                latency_s=0.002))
        tl.close()

        def cli(*argv):
            out = subprocess.run(
                [sys.executable, "-m", "t3fs.cli.admin",
                 "--mgmtd", "127.0.0.1:1", *argv],
                capture_output=True, text=True, timeout=60)
            assert out.returncode == 0, (argv, out.stdout, out.stderr)
            return out.stdout

        s = cli("trace-read", path, "--limit", "5")
        assert "chunk=9.0" in s and "(5 rows)" in s
        s = cli("trace-top", path, "--by", "target")
        line = next(l for l in s.splitlines() if l.startswith("target 101"))
        assert line.split()[2] == "10", line    # count column
