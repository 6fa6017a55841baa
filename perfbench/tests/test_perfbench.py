"""The benchmark's harness on the CPU: the trace reduction on a trace
recorded on a v5e chip, the work counts, the peaks table, the reference's
codes, the refusals, and every cell of BENCHMARK.json (and an RS(4+3)
cell of the tests' own) at tiny sizes through the harness's own entry,
sound and with the timed path broken underneath (each break must make
`correct` false)."""

import atexit
import functools
import json
import os
import re
import shutil
import subprocess
import sys
import tempfile
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


# sha256 of the 10 shards the RAID-6 reference stored for this stripe
# before it took other codes: the bytes for m = 2 have not moved
RAID6_8P2_SHARDS_SHA256 = (
    "de8ba8dc5da72dc8080dfe1a2cd94b6a4afa103135b19e62cd3be08b65e85d83")


def test_reference_raid6_stripe_bytes_are_unchanged():
    import hashlib

    stripe = np.random.default_rng(26).bytes(8 * 4096)
    shards = reference.stripe_shards(stripe, 8, 2, reference.RAID6)
    assert hashlib.sha256(b"".join(shards)).hexdigest() == \
        RAID6_8P2_SHARDS_SHA256
    data = np.frombuffer(stripe, np.uint8).reshape(8, -1)
    assert [bytes(r) for r in reference.raid6_parity(data)] == shards[8:]
    # the RAID-6 rows through the general encode give the Horner bytes
    assert np.array_equal(reference.encode(
        reference.parity_rows(8, 2, reference.RAID6), data),
        reference.raid6_parity(data))


@pytest.mark.parametrize("k,m", [(10, 4), (6, 3), (4, 3)])
def test_reference_rrvand_is_the_programs_parity(k, m):
    """What t3fs stores for m != 2 (its numpy oracle, the definition on
    disk) is the reference's systematic Vandermonde code."""
    from t3fs.ops.rs import default_rs

    rs = default_rs(k, m)
    assert rs.code_id == reference.RRVAND
    data = np.random.default_rng(k * 16 + m).integers(
        0, 256, (k, 4096), dtype=np.uint8)
    shards = reference.stripe_shards(data.tobytes(), k, m, reference.RRVAND)
    assert shards[:k] == [bytes(r) for r in data]
    assert shards[k:] == [bytes(r) for r in rs.encode_ref(data)]


@pytest.mark.parametrize("k,m,patterns", [(10, 4, 1001), (6, 3, 84)])
def test_reference_cauchy_is_mds(k, m, patterns):
    """Any k of the k + m shards rebuild the stripe: every k x k
    submatrix of [I; C] inverts, by the reference's own inverse."""
    from itertools import combinations

    g = np.concatenate([np.eye(k, dtype=np.uint8),
                        reference.parity_rows(k, m, reference.CAUCHY)])
    n = 0
    for rows in combinations(range(k + m), k):
        sub = g[list(rows)]
        assert np.array_equal(
            reference.gf_matmul(sub, reference.gf_inverse(sub)),
            np.eye(k, dtype=np.uint8)), rows
        n += 1
    assert n == patterns


def _clmul_mod(a: int, b: int) -> int:
    """a * b in GF(2^8) by shift and add, reduced by 0x11D."""
    out = 0
    for i in range(8):
        if b >> i & 1:
            out ^= a << i
    for bit in range(14, 7, -1):
        if out >> bit & 1:
            out ^= 0x11D << (bit - 8)
    return out


def test_reference_cauchy_hand_worked_coefficient():
    """Row k, column 0 of RS(10+4): 1 / (10 XOR 0).  By hand, 10 * 0xDD =
    (x^3 + x)(x^7 + x^6 + x^4 + x^3 + x^2 + 1) = 0x752, reduced by
    0x11D << 2, << 1, << 0 to 1."""
    rows = reference.parity_rows(10, 4, reference.CAUCHY)
    assert rows[0, 0] == 0xDD
    assert _clmul_mod(10, 0xDD) == 1
    assert all(_clmul_mod(i ^ j, int(rows[i - 10, j])) == 1
               for i in range(10, 14) for j in range(10))


def test_reference_refuses_codes_it_does_not_know():
    with pytest.raises(ValueError, match="2 parity shards"):
        reference.parity_rows(10, 4, reference.RAID6)
    with pytest.raises(ValueError, match="unknown parity code"):
        reference.parity_rows(10, 4, "rs-legacy")
    with pytest.raises(ValueError, match="singular"):
        reference.gf_inverse(np.ones((2, 2), dtype=np.uint8))


def test_reference_imports_nothing_of_t3fs():
    """With t3fs unimportable, the reference and the check still load
    and encode every code."""
    code = (
        "import sys\n"
        "sys.modules['t3fs'] = None\n"
        f"sys.path.insert(0, {ROOT!r})\n"
        "from perfbench import reference, verify\n"
        "for k, m, c in [(8, 2, 'raid6-g2-11d'), (10, 4, 'rrvand-11d'),\n"
        "                (10, 4, 'cauchy-11d')]:\n"
        "    assert len(reference.stripe_shards(bytes(k * 64), k, m, c))"
        " == k + m\n"
        "print('ok')\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120)
    assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr


def test_configuration_names_its_code():
    from perfbench.verify import ec_code

    assert ec_code({"k": 8, "m": 2}) == reference.RAID6
    assert ec_code({"k": 10, "m": 4, "code": "cauchy-11d"}) == \
        reference.CAUCHY
    with pytest.raises(ValueError, match="`code`"):
        ec_code({"k": 10, "m": 4})
    with pytest.raises(ValueError, match="none of"):
        ec_code({"k": 10, "m": 4, "code": "vandermonde"})


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

# cells of the tests alone, each added to a copy of the tree as a later
# cell is added: a configuration file and a mix file (perfbench/tests/
# data/), entries in BENCHMARK.json's `configs` and `workloads`, and its
# name in the `workloads` list of every metric that the committed cell it
# is like reports.  An RS(4+3) configuration, so a code with more than two
# parity shards runs through the harness, written and read with three data
# slots lost.  name -> (configuration, mix, the committed cell it is like)
TEST_CELLS = {
    "rs4p3_write": ("rs4p3", "write_8", "rs8p2_stripe_write"),
    "rs4p3_degraded_read": ("rs4p3", "degraded_read_slots_0_2",
                            "rs8p2_degraded_read")}


def _with_test_cells(bench: dict) -> dict:
    """BENCHMARK.json with TEST_CELLS added: entries and list items only."""
    bench = json.loads(json.dumps(bench))
    for config in sorted({c for c, _, _ in TEST_CELLS.values()}):
        bench["configs"].append(
            {"name": config, "source": f"perfbench/tests/data/{config}.json",
             "file": f"perfbench/configs/{config}.json", "reduced": [],
             "why": "a test configuration"})
    for name, (config, mix, like) in TEST_CELLS.items():
        bench["workloads"].append({"name": name, "config": config,
                                   "traffic": mix, "chips": 1,
                                   "why": f"as {like}"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if like in m.get("workloads", ()):
                m["workloads"].append(name)
    return bench


@functools.cache
def _test_root() -> str:
    """A copy of BENCHMARK.json with TEST_CELLS added, and the files they
    name, in a directory laid out as the checkout."""
    root = tempfile.mkdtemp(prefix="perfbench-cells-")
    atexit.register(shutil.rmtree, root, True)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = _with_test_cells(json.load(f))
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    shutil.copytree(os.path.join(ROOT, "perfbench", "traffic"),
                    os.path.join(root, "perfbench", "traffic"))
    os.makedirs(os.path.join(root, "perfbench", "configs"))
    for config, mix, _ in TEST_CELLS.values():
        shutil.copy(os.path.join(HERE, "data", config + ".json"),
                    os.path.join(root, "perfbench", "configs"))
        src = os.path.join(HERE, "data", mix + ".json")
        if os.path.exists(src):
            shutil.copy(src, os.path.join(root, "perfbench", "traffic"))
    return root


def _cell(name):
    """A workload of BENCHMARK.json, or one of TEST_CELLS, each through
    the harness's own load_cell."""
    return load_cell(_test_root() if name in TEST_CELLS else ROOT, name)


@pytest.mark.parametrize("name", list(TEST_CELLS))
def test_a_cell_added_as_data_reports_what_its_like_reports(name):
    """Files, entries and list items are all a new cell needs: it loads
    with the metrics (and their readers) of the committed cell it is like,
    and no entry of BENCHMARK.json changed but by an item appended."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(_test_root(), "BENCHMARK.json")) as f:
        added = json.load(f)
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        assert len(added[key]) >= len(bench[key])
        for e, a in zip(bench[key], added[key]):
            if "workloads" in e:        # items appended, none changed
                e = {**e, "workloads": e["workloads"]
                     + a["workloads"][len(e["workloads"]):]}
            assert a == e
    cell, like = _cell(name), load_cell(ROOT, TEST_CELLS[name][2])
    for kind in ("end_to_end", "per_layer"):
        assert [m["name"] for m in getattr(cell, kind)] == \
            [m["name"] for m in getattr(like, kind)]
    assert cell.config["name"] == TEST_CELLS[name][0]
    assert os.path.exists(cell.mix_path)
    assert all(os.path.exists(cell.reader_path(m, True))
               for m in cell.per_layer)


def _run(name, control=None, seconds=1.0, overrides=None):
    from perfbench.harness import run_cell

    cell = _cell(name)
    return run_cell(cell, 2**31 + 11, seconds, False, root=ROOT,
                    t_process=time.perf_counter(), control=control,
                    on_cpu=True,
                    config_overrides={**TINY[cell.config["layout"]],
                                      **(overrides or {})})


def _workloads() -> list[str]:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


CELLS = _workloads() + list(TEST_CELLS)


@pytest.mark.parametrize("name", CELLS)
def test_cell_is_correct_at_a_tiny_size(name):
    res = _run(name)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    cell = _cell(name)
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(m["value"] > 0 for m in res["metrics"].values())


def _writes_ec(name) -> bool:
    from perfbench.traffic import load_mix

    cell = _cell(name)
    return cell.config["layout"] == "ec" and any(
        g.op == "write" for g in load_mix(cell.mix_path).groups)


CONTROLS = [(c, "null_checksum") for c in CELLS] + [
    (c, "data_shards_only") for c in CELLS if _writes_ec(c)]


@pytest.mark.parametrize("name,control", CONTROLS)
def test_control_comes_out_not_correct(name, control):
    res = _run(name, control)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("name", list(TEST_CELLS))
def test_mismatched_code_is_refused_before_the_window(name, monkeypatch):
    """A configuration naming another code than the layout stores (the
    program stores rrvand-11d for RS(4+3)) never reaches the window."""
    from perfbench.traffic import Load

    async def window(self, t0, seconds):
        raise AssertionError("the window ran")
    monkeypatch.setattr(Load, "run", window)
    with pytest.raises(ValueError, match="cauchy-11d"):
        _run(name, overrides={"code": "cauchy-11d"})


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
