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
  `net.rx.direct_bodies`, `net.rx.recv_calls`, absent where the program
  has no such counters): reads per frame, and direct body reads per
  acknowledged operation.

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
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CPU_SIZES = {"craq": {"chunk_size": 64 << 10, "working_set_bytes": 2 << 20},
             "ec": {"chunk_size": 4096, "working_set_bytes": 1 << 20}}
PROGRAMS = ("jit_raw_crc", "jit_step")


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
    ap.add_argument("--out", default=None)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args(argv)
    if args.sample_rate is not None and args.trace:
        ap.error("--sample-rate goes with --trace 0")

    import jax

    from perfbench import harness, readings, trace_reduce
    from perfbench.spec import load_cell
    from t3fs.utils import loop_meter, tracing

    if not args.cpu:
        cache = os.path.join(ROOT, ".perfbench_jax_cache")
        os.makedirs(cache, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache)

    got: dict = {"loop": [], "rx": []}
    counters = harness._counters
    try:
        from t3fs.net import conn as net_conn
        rx = {k: getattr(net_conn, f"RX_{k.upper()}")
              for k in ("frames", "direct_bodies", "recv_calls")}
    except AttributeError:
        rx = {}

    def counters_and_loop(dep):
        # called at the window's edges (and the profile window's): the
        # first call is the window's start
        if not got["loop"] and args.sample_rate is not None:
            tracing.configure(tracing.TraceConfig(
                sample_rate=args.sample_rate, export="tail"))
        m = loop_meter.install()
        got["loop"].append((m.busy_s, m.wait_s, m.recorded_wait_s,
                            m.iterations))
        # each collect returns the count since the one before: the
        # first call's is before the window
        got["rx"].append({k: c.collect()["value"] for k, c in rx.items()})
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
    res = harness.run_cell(
        cell, args.seed, args.seconds, bool(args.trace), root=ROOT,
        t_process=T_PROCESS, on_cpu=args.cpu,
        config_overrides=CPU_SIZES[cell.config["layout"]] if args.cpu
        else None)

    probe: dict = {}
    if len(got["loop"]) >= 2:
        (b0, w0, rw0, i0), (b1, w1, rw1, i1) = got["loop"][0], got["loop"][-1]
        busy, wait = b1 - b0, w1 - w0
        probe["loop_busy_share"] = 100 * busy / (busy + wait)
        probe["loop_wait_coverage"] = (rw1 - rw0) / wait if wait else None
        probe["loop_iterations_per_s"] = (i1 - i0) / (busy + wait)
    if rx and len(got["rx"]) >= 2:
        for k in rx:
            probe[f"rx_{k}"] = sum(d[k] for d in got["rx"][1:])
        if probe["rx_frames"]:
            probe["rx_recv_calls_per_frame"] = (probe["rx_recv_calls"]
                                                / probe["rx_frames"])
    r = got.get("r")
    if r is not None:
        op = "read" if r.op_stats("read") else "write"
        probe["MBps"] = readings.mb_per_s(r, op)
        acked = sum(s.ok for s in r.op_stats(op))
        if rx and acked:
            probe["rx_direct_bodies_per_op"] = (probe["rx_direct_bodies"]
                                                / acked)
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
