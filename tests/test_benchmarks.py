"""Bench harnesses run end to end with tiny budgets.

Reference analogs: benchmarks/storage_bench (StorageBench.cc modes/flags)
and benchmarks/fio_usrbio (small-IO randread path).
"""

import asyncio

import pytest

from benchmarks.storage_bench import parse_args as sb_args, run_bench as sb_run
from benchmarks.usrbio_bench import parse_args as ub_args, run_bench as ub_run


def test_storage_bench_write_mode():
    res = asyncio.run(sb_run(sb_args(
        ["--mode", "write", "--seconds", "1", "--chunk-size", "65536",
         "--concurrency", "4", "--num-chunks", "8"])))
    assert res["ops"] > 0 and res["errors"] == 0
    assert res["MB_s"] > 0 and res["p99_ms"] > 0


def test_storage_bench_read_mode_with_checksum_verify():
    res = asyncio.run(sb_run(sb_args(
        ["--mode", "read", "--seconds", "1", "--chunk-size", "65536",
         "--concurrency", "4", "--num-chunks", "8", "--verify-checksums"])))
    assert res["ops"] > 0 and res["errors"] == 0


def test_storage_bench_survives_fault_injection():
    """DebugFlags-driven injected server errors are absorbed by retries
    (reference: storage_bench -injectRandomServerError)."""
    res = asyncio.run(sb_run(sb_args(
        ["--mode", "write", "--seconds", "1", "--chunk-size", "65536",
         "--concurrency", "4", "--num-chunks", "8",
         "--inject-server-error", "0.05"])))
    assert res["ops"] > 0 and res["errors"] == 0


@pytest.mark.slow
def test_usrbio_bench_randread():
    res = asyncio.run(ub_run(ub_args(
        ["--seconds", "1", "--depth", "16", "--file-size", "1048576"])))
    assert res["reads"] > 0 and res["errors"] == 0
    assert res["iops"] > 0


def test_meta_bench_phases():
    """mdtest-analog metadata bench end to end on a tiny budget: every
    phase completes and reports a positive op rate."""
    from benchmarks.meta_bench import parse_args as mb_args, run_bench as mb_run
    res = asyncio.run(mb_run(mb_args(
        ["--dirs", "2", "--files", "8", "--concurrency", "8"])))
    for phase in ("mkdir", "create", "stat", "batch_stat", "list",
                  "rename", "remove"):
        assert res[phase]["ops"] > 0 and res[phase]["ops_s"] > 0, phase
    assert res["batch_stat"]["inodes_s"] > 0


def test_meta_bench_fuse_mode():
    """--fuse drives the phases through a real kernel mount."""
    import os
    if os.geteuid() != 0 or not os.path.exists("/dev/fuse"):
        pytest.skip("needs root + /dev/fuse")
    from benchmarks.meta_bench import parse_args as mb_args, run_bench as mb_run
    res = asyncio.run(mb_run(mb_args(
        ["--fuse", "--dirs", "2", "--files", "4", "--concurrency", "4"])))
    assert res["path"] == "fuse-kernel-mount"
    for phase in ("mkdir", "create", "stat", "list", "rename", "remove"):
        assert res[phase]["ops"] > 0 and res[phase]["ops_s"] > 0, phase


@pytest.mark.slow
def test_ckpt_bench_save_restore_degraded():
    """Checkpoint bench end to end on a tiny budget: save, healthy
    restore, and (--kill) degraded restore all report positive MB/s,
    medians carry their runs arrays (bench_protocol rule 1)."""
    from benchmarks.ckpt_bench import parse_args as cb_args, run_bench as cb_run
    res = asyncio.run(cb_run(cb_args(
        ["--leaves", "2", "--leaf-mb", "1", "--chunk-size", "65536",
         "--runs", "3", "--kill"])))
    assert res["verified"]
    assert res["save_MB_s"] > 0 and len(res["save_runs"]) == 3
    assert res["restore_MB_s"] > 0 and len(res["restore_runs"]) == 3
    assert res["degraded_restore_MB_s"] > 0
    assert res["stripes"] > 0 and res["bytes"] == 2 << 20


def test_storage_bench_trace_ab():
    from benchmarks.storage_bench import trace_ab

    res = trace_ab(value_size=65536, num_ops=6)
    for label in ("off", "rate_0.01", "rate_1.0"):
        assert res[label]["ok"] == 6 and res[label]["errors"] == 0
    assert res["rate_1.0"]["p50_vs_off"] > 0
    # the bench must leave the process untraced
    from t3fs.utils.tracing import get_config
    assert get_config().sample_rate == 0.0


def test_host_probe_loop_stacks_and_thread_cpu():
    """The host probe's loop-stack sampler: shares per innermost frame
    of the sampled thread, only inside the window asked for; and the
    per-thread CPU split it reads from /proc."""
    import threading
    import time

    from benchmarks.perfbench_host_probe import LoopStacks, _thread_cpu_s

    stop = threading.Event()
    ident: list[int] = []

    def spin_here():
        ident.append(threading.get_ident())
        while not stop.is_set():
            sum(range(1000))

    t = threading.Thread(target=spin_here)
    t.start()
    try:
        while not ident:
            time.sleep(0.001)
        s = LoopStacks(0.001)
        s.start(ident[0])
        lo = time.time()
        time.sleep(0.3)
        hi = time.time()
        s.stop()
    finally:
        stop.set()
        t.join(timeout=5)
    assert not t.is_alive()
    got = s.shares(lo, hi)
    assert got["samples"] > 10 and got["idle_select_share"] == 0
    assert sum(v for _, v in got["leaf_share"]) <= 100.0 + 1e-9
    assert got["leaf_share"][0][0].startswith(
        "test_benchmarks.py:test_host_probe_loop_stacks_and_thread_cpu."
        "<locals>.spin_here:")
    assert s.shares(hi + 10, hi + 20) == {"samples": 0}
    user, system = _thread_cpu_s(threading.get_native_id())
    assert user >= 0 and system >= 0
