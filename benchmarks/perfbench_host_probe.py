#!/usr/bin/env python3
"""One run of one perfbench cell, as `perfbench/run.py` makes it, plus
what the harness does not pass to a metric reader:

- the loop meter's (`t3fs/utils/loop_meter.py`) busy and wait time over
  the whole measured window, read from its counters, so untraced runs
  have a loop busy share too, and the share of the wait that `loop.wait`
  spans cover (`recorded_wait_s` over `wait_s`);
- with --trace 1: the profiler anchor (`time.time()` just after the
  window mark opens) and the width of its bracket (`time.time()` just
  before and just after), the device time per launch of each jitted
  codec program, the share of device idle time during which no
  `loop.wait` span was open (spans placed on the trace's clock with the
  anchor), and the loop's wait inside each of the ten longest idle gaps;
- with --trace 0 --sample-rate R: the process's tracing set to head
  sampling at R with tail export from the window's start on, so the
  rate at R costs what tracing costs a deployment that samples R;
- the asyncio frame reader's counters over the window (`net.rx.frames`,
  `net.rx.direct_bodies`, `net.rx.recv_calls`, and the bodies a
  connection's helper thread moved, `net.rx.thread_bodies` and
  `net.tx.thread_bodies`; each absent where the program has no such
  counter): reads per frame, and bodies per frame and per acknowledged
  operation;
- the loop's busy time per acknowledged operation, and the loop
  thread's own user and system CPU time over the window (from
  `/proc/self/task/<tid>/stat`) beside the whole process's;
- with --loop-stacks-ms M: the loop thread's Python stack sampled every
  M ms over the window from a thread of its own, as shares of the
  samples per innermost frame (file:function:line), per innermost frame
  inside the t3fs package, and idle in the selector's `select`.  A
  sample needs the interpreter lock, so samples gather where the loop
  thread lets go of it (system calls) more than its time there alone
  would place them: the thread's CPU split above has no such lean.

    python3 benchmarks/perfbench_host_probe.py --workload craq3_4m_write \\
        --seed 7 --seconds 20 --trace 1 --out probe.json

The last line of standard output is a JSON object: the cell, whether it
was correct, its metrics and `probe`, the numbers above.  --out also
writes the harness's whole result with `probe` added.  --cpu runs a
rehearsal at a tiny size on the CPU-interpreted codec.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()     # set-up is timed from here, as in run.py

import argparse  # noqa: E402
import collections  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CPU_SIZES = {"craq": {"chunk_size": 64 << 10, "working_set_bytes": 2 << 20},
             "ec": {"chunk_size": 4096, "working_set_bytes": 1 << 20}}
PROGRAMS = ("jit_raw_crc", "jit_step")
# the frame counters of t3fs/net/conn.py: name in the probe -> module name
NET_COUNTERS = {"rx_frames": "RX_FRAMES", "rx_direct_bodies": "RX_DIRECT_BODIES",
                "rx_recv_calls": "RX_RECV_CALLS",
                "rx_thread_bodies": "RX_THREAD_BODIES",
                "tx_thread_bodies": "TX_THREAD_BODIES"}
TOP_FRAMES = 25


def _thread_cpu_s(native_id: int) -> tuple[float, float] | None:
    """(user, system) CPU seconds of one thread of this process."""
    try:
        with open(f"/proc/self/task/{native_id}/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
    except (OSError, IndexError):
        return None
    tick = os.sysconf("SC_CLK_TCK")
    return int(fields[11]) / tick, int(fields[12]) / tick


def _where(frame) -> str:
    code = frame.f_code
    return (f"{os.path.basename(code.co_filename)}:{code.co_qualname}:"
            f"{frame.f_lineno}")


class LoopStacks:
    """Samples one thread's innermost Python frames every interval_s from
    a daemon thread of its own; shares() counts those taken in [lo, hi]."""

    def __init__(self, interval_s: float):
        self.interval_s = interval_s
        self._got: list[tuple[float, str, str]] = []
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None

    def start(self, ident: int) -> None:
        self._thread = threading.Thread(target=self._run, args=(ident,),
                                        name="loop-stacks", daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)

    def _run(self, ident: int) -> None:
        pkg = os.sep + "t3fs" + os.sep
        while not self._stop.wait(self.interval_s):
            frame = sys._current_frames().get(ident)
            if frame is None:
                continue
            own, f = "", frame
            while f is not None and not own:
                if pkg in f.f_code.co_filename:
                    own = _where(f)
                f = f.f_back
            self._got.append((time.time(), _where(frame), own))

    def shares(self, lo: float, hi: float) -> dict:
        got = [(leaf, own) for t, leaf, own in self._got if lo <= t <= hi]
        n = len(got)
        if not n:
            return {"samples": 0}
        leaf = collections.Counter(g[0] for g in got)
        own = collections.Counter(g[1] or "(outside t3fs)" for g in got)
        idle = sum(v for k, v in leaf.items()
                   if k.startswith("selectors.py:") and ".select:" in k)
        return {"samples": n, "per_s": n / (hi - lo),
                "idle_select_share": 100 * idle / n,
                "leaf_share": [[k, 100 * v / n]
                               for k, v in leaf.most_common(TOP_FRAMES)],
                "t3fs_frame_share": [[k, 100 * v / n]
                                     for k, v in own.most_common(TOP_FRAMES)]}


def _merge(intervals: list[tuple[float, float]]) -> list[list[float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _overlap_ns(merged: list[list[float]], g0: float, g1: float) -> float:
    return sum(max(0.0, min(b, g1) - max(a, g0)) for a, b in merged)


def _trace_numbers(got: dict, waits: list[dict]) -> dict:
    s, epoch_lo = got["summary"], got["epoch_lo"]
    lo = s.window_ns[0]
    out = {"anchor_bracket_us": 1e6 * (got["bracket_hi"] - got["bracket_lo"]),
           "anchor_after_bracket_us": 1e6 * (epoch_lo - got["bracket_hi"])}
    merged = _merge([(lo + (w["t0"] - epoch_lo) * 1e9,
                      lo + (w["t0"] + w["dur_s"] - epoch_lo) * 1e9)
                     for w in waits])
    idle = sum(g1 - g0 for g0, g1 in s.gaps)
    waiting = sum(_overlap_ns(merged, g0, g1) for g0, g1 in s.gaps)
    out["idle_loop_busy_share"] = (100 * (1 - waiting / idle) if idle
                                   else None)
    out["top_gaps_ms_and_loop_wait_pct"] = [
        [(g1 - g0) / 1e6, 100 * _overlap_ns(merged, g0, g1) / (g1 - g0)]
        for g0, g1 in s.gaps[:10]]
    for prog in PROGRAMS:
        n = s.program_launches(f"^{prog}$")
        if n:
            out[f"{prog}_ms_per_launch"] = (
                1e3 * s.program_seconds(f"^{prog}$") / n)
    return out


def main(argv=None) -> int:
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    ap = argparse.ArgumentParser(prog="benchmarks/perfbench_host_probe.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sample-rate", type=float, default=None,
                    help="with --trace 0: head sampling at this rate, tail "
                         "export, from the window's start")
    ap.add_argument("--loop-stacks-ms", type=float, default=None,
                    help="sample the loop thread's Python stack every this "
                         "many ms over the window")
    ap.add_argument("--out", default=None)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args(argv)
    if args.sample_rate is not None and args.trace:
        ap.error("--sample-rate goes with --trace 0")
    stacks = (LoopStacks(args.loop_stacks_ms / 1e3)
              if args.loop_stacks_ms else None)

    import jax

    from perfbench import harness, readings, trace_reduce
    from perfbench.spec import load_cell
    from t3fs.utils import loop_meter, tracing

    if not args.cpu:
        cache = os.path.join(ROOT, ".perfbench_jax_cache")
        os.makedirs(cache, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache)

    got: dict = {"loop": [], "net": [], "edges": [], "cpu": []}
    counters = harness._counters
    from t3fs.net import conn as net_conn
    net = {k: getattr(net_conn, v) for k, v in NET_COUNTERS.items()
           if hasattr(net_conn, v)}

    def counters_and_loop(dep):
        # called at the window's edges (and the profile window's): the
        # first call is the window's start
        if not got["loop"] and args.sample_rate is not None:
            tracing.configure(tracing.TraceConfig(
                sample_rate=args.sample_rate, export="tail"))
        if not got["loop"]:
            got["native_id"] = threading.get_native_id()
            if stacks is not None:
                stacks.start(threading.get_ident())
        m = loop_meter.install()
        got["loop"].append((m.busy_s, m.wait_s, m.recorded_wait_s,
                            m.iterations))
        # each collect returns the count since the one before: the
        # first call's is before the window
        got["net"].append({k: c.collect()["value"] for k, c in net.items()})
        got["edges"].append(time.time())
        got["cpu"].append((_thread_cpu_s(got["native_id"]), os.times()))
        return counters(dep)

    harness._counters = counters_and_loop

    mark = jax.profiler.TraceAnnotation

    class BracketedMark:
        def __init__(self, name):
            self._mark = mark(name)

        def __enter__(self):
            got["bracket_lo"] = time.time()
            self._mark.__enter__()
            got["bracket_hi"] = time.time()
            return self

        def __exit__(self, *exc):
            return self._mark.__exit__(*exc)

    jax.profiler.TraceAnnotation = BracketedMark

    label = trace_reduce.label_gaps

    def label_and_keep(summary, spans, epoch_lo, top=10):
        got["summary"], got["epoch_lo"] = summary, epoch_lo
        return label(summary, spans, epoch_lo, top)

    trace_reduce.label_gaps = label_and_keep

    load = harness.load_reader

    def load_and_keep(path):
        fn = load(path)

        def read(r):
            got["r"] = r
            return fn(r)
        return read

    harness.load_reader = load_and_keep

    cell = load_cell(ROOT, args.workload)
    try:
        res = harness.run_cell(
            cell, args.seed, args.seconds, bool(args.trace), root=ROOT,
            t_process=T_PROCESS, on_cpu=args.cpu,
            config_overrides=CPU_SIZES[cell.config["layout"]] if args.cpu
            else None)
    finally:
        if stacks is not None:
            stacks.stop()

    probe: dict = {}
    if len(got["loop"]) >= 2:
        (b0, w0, rw0, i0), (b1, w1, rw1, i1) = got["loop"][0], got["loop"][-1]
        busy, wait = b1 - b0, w1 - w0
        probe["loop_busy_share"] = 100 * busy / (busy + wait)
        probe["loop_wait_coverage"] = (rw1 - rw0) / wait if wait else None
        probe["loop_iterations_per_s"] = (i1 - i0) / (busy + wait)
    if len(got["net"]) >= 2:
        for k in net:
            probe[k] = sum(d[k] for d in got["net"][1:])
        frames = probe.get("rx_frames")
        if frames:
            for k in net:
                if k != "rx_frames":
                    probe[f"{k}_per_frame"] = probe[k] / frames
    if len(got["cpu"]) >= 2:
        (t0, p0), (t1, p1) = got["cpu"][0], got["cpu"][-1]
        if t0 and t1:
            probe["loop_thread_user_s"] = t1[0] - t0[0]
            probe["loop_thread_sys_s"] = t1[1] - t0[1]
        probe["process_user_s"] = p1.user - p0.user
        probe["process_sys_s"] = p1.system - p0.system
        probe["window_s"] = got["edges"][-1] - got["edges"][0]
    if stacks is not None and len(got["edges"]) >= 2:
        probe["loop_stacks"] = stacks.shares(got["edges"][0],
                                             got["edges"][-1])
    r = got.get("r")
    if r is not None:
        op = "read" if r.op_stats("read") else "write"
        probe["MBps"] = readings.mb_per_s(r, op)
        acked = sum(s.ok for s in r.op_stats(op))
        probe["acked_ops"] = acked
        if acked:
            for k in net:
                if k in probe and k != "rx_recv_calls":
                    probe[f"{k}_per_op"] = probe[k] / acked
            if len(got["loop"]) >= 2:
                probe["loop_busy_ms_per_op"] = (
                    1e3 * (got["loop"][-1][0] - got["loop"][0][0]) / acked)
        if "summary" in got:
            waits = [s for s in r.spans if s["name"] == "loop.wait"]
            probe.update(_trace_numbers(got, waits))
    res["probe"] = probe
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(res, f, default=str)
    print(json.dumps({
        "cell": args.workload, "trace": args.trace,
        "sample_rate": args.sample_rate, "correct": res["correct"],
        "metrics": {k: v["value"] for k, v in res["metrics"].items()},
        "probe": probe}, default=str), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
