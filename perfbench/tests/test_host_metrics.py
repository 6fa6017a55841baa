"""The readers of the host-side per-layer metrics (loop busy share, wire
send and frame read, CRC and engine split, batcher queue and flush) on
synthetic readings with hand-worked values, on readings of a program
without those spans, and on a traced run of every cell at a tiny size."""

import json
import os
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from perfbench.readings import Readings, load_reader  # noqa: E402
from perfbench.spec import load_cell  # noqa: E402

NEW = ["loop_busy_share.write", "loop_busy_share.read", "wire_send_ms.write",
       "wire_frame_ms.write", "service_crc_ms", "service_engine_ms",
       "service_read_engine_ms", "crc_queue_ms.write", "crc_result_ms.write",
       "ec_queue_ms.write", "ec_queue_ms.read", "ec_flush_ms.write",
       "ec_flush_ms.read"]


def _read(name, r):
    path = os.path.join(ROOT, "perfbench", "layer_metrics", name + ".py")
    return load_reader(path)(r)


def _span(name, kind, dur_s=0.0, **tags):
    return {"name": name, "kind": kind, "t0": 100.0, "dur_s": dur_s,
            "tags": tags}


def _readings(spans):
    return Readings(config={}, groups=[], fault={}, stats={}, window_s=1.0,
                    setup_s=0.0, loop_lag=[], counters={}, spans=spans)


SPANS = [
    # two write hops and their client legs
    _span("Storage.write_packed", "server", 0.05, wire_s=0.010,
          queue_s=0.020, apply_s=0.004, frame_s=0.002, crc_s=0.006,
          engine_s=0.004, crc_queue_s=0.001, crc_result_s=0.005),
    _span("Storage.update_packed", "server", 0.03, wire_s=0.012,
          queue_s=0.010, apply_s=0.008, frame_s=0.004, crc_s=0.010,
          engine_s=0.008, crc_queue_s=0.003, crc_result_s=0.007),
    _span("rpc.Storage.write_packed", "client", 0.06, send_s=0.001),
    _span("rpc.Storage.update_packed", "client", 0.04, send_s=0.003),
    _span("rpc.Mgmtd.heartbeat", "client", 0.01, send_s=9.0),
    # a read
    _span("Storage.batch_read", "server", 0.02, wire_s=0.001, queue_s=0.0,
          engine_s=0.012),
    # the loop meter: 0.3 s busy and 0.7 s waiting between first and last
    _span("loop.wait", "local", 0.01, busy_s=1.0, wait_s=2.0,
          recorded_wait_s=1.5, iterations=10),
    _span("loop.wait", "local", 0.01, busy_s=1.3, wait_s=2.7,
          recorded_wait_s=2.1, iterations=99),
    _span("loop.wait", "local", 0.01, busy_s=1.1, wait_s=2.2,
          recorded_wait_s=1.7, iterations=50),
    # ECCodec: encodes of 1 and 3 rows, one decode of 2
    _span("ec.flush", "local", 0.004, op="encv", rows=1, queue_s=0.002),
    _span("ec.flush", "local", 0.008, op="encv", rows=3, queue_s=0.010),
    _span("ec.flush", "local", 0.006, op="recv", rows=2, queue_s=0.001),
]

EXPECTED = {
    "loop_busy_share.write": 30.0, "loop_busy_share.read": 30.0,
    "wire_send_ms.write": 2.0, "wire_frame_ms.write": 3.0,
    "service_crc_ms": 8.0, "service_engine_ms": 6.0,
    "service_read_engine_ms": 12.0,
    "crc_queue_ms.write": 2.0, "crc_result_ms.write": 6.0,
    "ec_queue_ms.write": 3.0, "ec_queue_ms.read": 0.5,
    "ec_flush_ms.write": 6.0, "ec_flush_ms.read": 6.0,
}


@pytest.mark.parametrize("name", NEW)
def test_reader_hand_worked(name):
    assert _read(name, _readings(SPANS)) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", NEW)
def test_reader_finds_nothing_in_an_older_program(name):
    """Spans as a program without the new tags and local spans records
    them: every new reader returns None, and none raises."""
    old = [_span("Storage.write_packed", "server", 0.05, wire_s=0.01,
                 queue_s=0.02, apply_s=0.004),
           _span("rpc.Storage.write_packed", "client", 0.06),
           _span("Storage.batch_read", "server", 0.02, wire_s=0.001,
                 queue_s=0.0)]
    assert _read(name, _readings(old)) is None
    assert _read(name, _readings([])) is None


def test_benchmark_json_lists_each_new_metric():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        m = per_layer[name]
        assert m["workloads"] and m["source"] in ("program_span",
                                                  "program_counter")
        want = "read_MBps" if "read" in name else "write_MBps"
        assert m["moves"] == want, name


# the traced run at a tiny size: EC chunks (4 KiB) stay under the nodes'
# 64 KiB device CRC threshold, so no EC-cell CRC row reaches the batcher
TINY = {"craq": {"chunk_size": 64 << 10, "working_set_bytes": 2 << 20},
        "ec": {"chunk_size": 4096, "working_set_bytes": 1 << 20}}
HOST_CRC_AT_TINY = {"rs8p2_stripe_write": {"crc_queue_ms.write",
                                           "crc_result_ms.write"}}


@pytest.mark.parametrize("cell", ["craq3_4m_write", "rs8p2_stripe_write",
                                  "rs8p2_degraded_read"])
def test_traced_tiny_run_reports_the_new_metrics(cell):
    from perfbench.harness import run_cell

    c = load_cell(ROOT, cell)
    res = run_cell(c, 2**31 + 17, 1.0, True, root=ROOT,
                   t_process=time.perf_counter(), on_cpu=True,
                   config_overrides=TINY[c.config["layout"]])
    assert res["correct"], res["checks"]
    want = {m["name"] for m in c.per_layer if m["name"] in NEW}
    want -= HOST_CRC_AT_TINY.get(cell, set())
    assert want and want <= set(res["metrics"]), want - set(res["metrics"])
    share = [v["value"] for k, v in res["metrics"].items()
             if k.startswith("loop_busy_share")]
    assert all(0 < s <= 100 for s in share)
