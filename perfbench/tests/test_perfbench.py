"""The benchmark's harness on the CPU: the trace reduction on a trace
recorded on a v5e chip, the work counts, the peaks table, the refusals,
and every cell at tiny sizes through the harness's own entry, sound and
with the timed path broken underneath (each break must make `correct`
false)."""

import json
import os
import re
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from perfbench import reference, trace_reduce, work  # noqa: E402
from perfbench.spec import load_cell  # noqa: E402

TINY_TRACE = os.path.join(HERE, "data", "tiny_v5e.xplane.pb")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


# --- the trace reduction ---

def test_reduce_recorded_v5e_trace():
    """A trace recorded on a v5e chip: three launches of the 1 MiB CRC
    program and two of the fused encode step inside the window mark."""
    s = trace_reduce.reduce(trace_reduce.load(TINY_TRACE))
    assert s is not None
    assert s.program_launches(r"^jit_raw_crc$") == 3
    assert s.program_launches(r"^jit_step$") == 2
    assert 0 < s.busy_s < s.window_s
    assert 0 < s.idle_share < 1
    assert s.program_seconds(r"^jit_raw_crc$") > 0
    assert s.program_seconds(r"^jit_step$") > 0
    # every operation runs inside a program launch
    assert s.busy_s <= sum(s.programs.values()) * (1 + 1e-9)
    assert any(n.startswith("jit_raw_crc/%raw_crc") for n in s.ops)
    assert s.gaps and all(b > a for a, b in s.gaps)


def test_reduce_clips_unions_and_finds_gaps():
    dev = trace_reduce.DeviceEvents(
        "/device:TPU:0",
        ops=[("a", 0, 30), ("b", 20, 50), ("c", 70, 80), ("d", 95, 130)],
        modules=[("jit_f(7)", 0, 50), ("jit_f(7)", 70, 80),
                 ("jit_g(9)", 95, 130)])
    s = trace_reduce.reduce(trace_reduce.TraceEvents([dev], (10, 100)))
    assert s.window_s == pytest.approx(90e-9)
    assert s.busy_s == pytest.approx((40 + 10 + 5) * 1e-9)   # clipped union
    assert s.programs["jit_f"] == pytest.approx(50e-9)
    assert s.launches == {"jit_f": 2, "jit_g": 1}
    assert s.gaps == [(50, 70), (80, 95)]
    spans = [{"name": "Storage.write", "t0": 1000.0 + 55e-9, "dur_s": 10e-9}]
    assert trace_reduce.label_gaps(s, spans, 1000.0)[0][0].startswith(
        "Storage.write")
    assert trace_reduce.reduce(trace_reduce.TraceEvents([], (0, 1))) is None


# --- work counts and peaks ---

def test_work_bytes_hand_worked():
    MiB = 1 << 20
    assert work.crc32c_bytes(4 * MiB) == 4194308
    # 8 data + 2 parity shards of 1 MiB, 10 CRCs
    assert work.encode_bytes(8, 2, MiB) == 10 * MiB + 40
    # 8 present in, 2 rebuilt out, 10 CRCs
    assert work.decode_bytes(8, 2, MiB) == 10 * MiB + 40
    assert work.decode_bytes(8, 1, MiB) == 9 * MiB + 36
    # 819 GB moved in one second is the v5e HBM roofline itself
    peak = work.peaks_for("TPU v5 lite")["hbm_bytes_per_s"]
    assert peak == 819e9
    assert work.roofline_share(819e9, peak, 1.0) == pytest.approx(100.0)
    assert work.roofline_share(819e9, peak, 4.0) == pytest.approx(25.0)
    assert work.roofline_share(1, peak, 0.0) is None


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        work.peaks_for("TPU v99")


# --- the host monitors ---

def test_stall_probe_names_what_held_the_loop():
    import asyncio

    from perfbench.hostmon import LoopLag, StallProbe

    def hold_the_loop():
        time.sleep(0.6)

    async def main():
        lag = LoopLag()
        probe = StallProbe(lag)
        lag.start()
        probe.start()
        await asyncio.sleep(0.1)
        hold_the_loop()
        await asyncio.sleep(0.2)
        await lag.stop()
        probe.stop()
        return probe.stalls

    (stall,) = asyncio.run(main())
    assert 0.5 < stall["s"] < 1.5
    assert stall["probe_late_s"] < 0.3       # the probe itself ran on
    assert any("hold_the_loop" in line for line in stall["stacks"][0])


# --- the reference ---

def test_reference_parity_matches_the_raid6_definition():
    """P and Q of the reference against the program's own generator
    matrix, evaluated by its numpy oracle (the definition on disk)."""
    from t3fs.ops.rs import default_rs

    rng = np.random.default_rng(3)
    data = rng.integers(0, 256, (8, 4096), dtype=np.uint8)
    assert np.array_equal(reference.raid6_parity(data),
                          default_rs(8, 2).encode_ref(data))
    assert reference.crc32c(b"123456789") == 0xE3069283


# --- refusals ---

def _run_cli(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", "craq3_4m_write",
         "--seed", "2147483659", "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_cli_refuses_without_a_tpu():
    out = _run_cli(ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "needs 1 TPU" in out.stderr


def test_cli_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "perfbench"), tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run_cli(tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""


# --- BENCHMARK.json and the files it names ---

def test_benchmark_json_names_its_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert bench["command"] == ["python3", "perfbench/run.py"]
    assert bench["paths"] == ["perfbench"]
    for c in bench["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            conf = json.load(f)
        assert conf["name"] == c["name"] and conf["reduced"] == c["reduced"]
        assert all(NAME.match(k) for k in c["reduced"])
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] == 1
        cell = load_cell(ROOT, w["name"])
        assert os.path.exists(cell.mix_path)
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.end_to_end:
            assert os.path.exists(cell.reader_path(m, False)), m["name"]
        for m in cell.per_layer:
            assert os.path.exists(cell.reader_path(m, True)), m["name"]
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names)) and all(map(NAME.match, names))
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert all(m["moves"] in e2e for m in bench["per_layer"])


# --- every cell through the harness, sound and broken ---

TINY = {"craq": {"chunk_size": 64 << 10, "working_set_bytes": 2 << 20},
        "ec": {"chunk_size": 4096, "working_set_bytes": 1 << 20}}


def _run(name, control=None, seconds=1.0):
    from perfbench.harness import run_cell

    cell = load_cell(ROOT, name)
    return run_cell(cell, 2**31 + 11, seconds, False, root=ROOT,
                    t_process=time.perf_counter(), control=control,
                    on_cpu=True,
                    config_overrides=TINY[cell.config["layout"]])


CELLS = ["craq3_4m_write", "rs8p2_stripe_write", "rs8p2_degraded_read"]


@pytest.mark.parametrize("name", CELLS)
def test_cell_is_correct_at_a_tiny_size(name):
    res = _run(name)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    cell = load_cell(ROOT, name)
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(m["value"] > 0 for m in res["metrics"].values())


CONTROLS = [(c, "null_checksum") for c in CELLS] + [
    ("rs8p2_stripe_write", "data_shards_only")]


@pytest.mark.parametrize("name,control", CONTROLS)
def test_control_comes_out_not_correct(name, control):
    res = _run(name, control)
    assert not res["correct"], res["checks"]


def _state_unchanged(mp):
    """Every write returns OK and stores nothing."""
    from t3fs.client.storage_client import StorageClient
    from t3fs.storage.types import IOResult

    async def write_chunk(self, *args, **kwargs):
        return IOResult()
    mp.setattr(StorageClient, "write_chunk", write_chunk)


def _half_batch(mp):
    """Every odd row of a device batch is left out (computed as zeros)."""
    from t3fs.client.ec_codec import ECCodec
    from t3fs.storage.codec_backend import DeviceChecksumBackend

    ec_flush, crc_dispatch = ECCodec._flush, DeviceChecksumBackend._dispatch

    def flush(self, groups):
        for items in groups.values():
            for it in items[1::2]:
                it.rows = np.zeros_like(it.rows)
        return ec_flush(self, groups)

    def dispatch(self, groups):
        for items in groups.values():
            for it in items[1::2]:
                it.data = bytes(len(it.data))
        return crc_dispatch(self, groups)
    mp.setattr(ECCodec, "_flush", flush)
    mp.setattr(DeviceChecksumBackend, "_dispatch", dispatch)


def _exchange_left_out(mp):
    """A chain's head never forwards to its successors."""
    from t3fs.storage.service import StorageService

    async def forward(self, *args, **kwargs):
        return None
    mp.setattr(StorageService, "_forward", forward)


def _flip(b: bytes) -> bytes:
    return b[:100] + bytes([b[100] ^ 1]) + b[101:] if len(b) > 100 else b


def _stored_altered(mp):
    """One byte of each update flipped as a node applies it (its CRC
    computed before)."""
    from t3fs.storage.chunk_replica import ChunkReplica

    apply = ChunkReplica.apply_update

    def apply_update(self, io, payload, payload_crc=None):
        return apply(self, io, _flip(payload), payload_crc)
    mp.setattr(ChunkReplica, "apply_update", apply_update)


def _answer_altered(mp):
    """One byte of each read's payload flipped as the client gets it."""
    from t3fs.client.storage_client import StorageClient

    read = StorageClient.batch_read

    async def batch_read(self, ios, **kwargs):
        results, payloads = await read(self, ios, **kwargs)
        return results, [_flip(p) for p in payloads]
    mp.setattr(StorageClient, "batch_read", batch_read)


FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
          "exchange_left_out": _exchange_left_out,
          "stored_altered": _stored_altered,
          "answer_altered": _answer_altered}
BROKEN = [("craq3_4m_write", f) for f in (
    "state_unchanged", "half_batch", "exchange_left_out", "stored_altered")] + [
    ("rs8p2_stripe_write", "state_unchanged"),
    ("rs8p2_stripe_write", "half_batch"),
    ("rs8p2_stripe_write", "stored_altered"),
    ("rs8p2_degraded_read", "half_batch"),
    ("rs8p2_degraded_read", "answer_altered")]


@pytest.mark.parametrize("name,fault", BROKEN)
def test_broken_timed_path_comes_out_not_correct(name, fault, monkeypatch):
    """The fault is in place for the measured window only: set-up and the
    read-back after the window run the sound program."""
    from perfbench.traffic import Load

    run = Load.run

    async def run_broken(self, t0, seconds):
        with pytest.MonkeyPatch.context() as mp:
            FAULTS[fault](mp)
            return await run(self, t0, seconds)
    monkeypatch.setattr(Load, "run", run_broken)
    res = _run(name)
    assert not res["correct"], res["checks"]
