#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once, on the chip this process owns.

    python3 perfbench/run.py --workload craq3_4m_write --seed 7 \\
        --seconds 10 --trace 0

The last line of standard output is one JSON object: correct, attempted,
failed, metrics (with --trace 0 the cell's end-to-end metrics, with
--trace 1 its per-layer ones), device, with --trace 1 breakdown, and last
the numbers compared with the reference, each beside its limit; the last
lines of standard error repeat those numbers.  Without a TPU, or with
fewer chips than the cell asks for, it prints no result and exits 2.

`--control null_checksum|data_shards_only` runs the program with one
guarantee of the configuration broken (stored CRCs left out; parity
shards left unwritten): such a run must come out not correct.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()     # set-up is timed from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# a directory of the benchmark's own: entries another tool left in the
# program's default cache directory cannot break this one's writes
CACHE_DIR = os.path.join(ROOT, ".perfbench_jax_cache")


def main(argv=None) -> int:
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from perfbench.spec import load_cell
    from perfbench.sut import CONTROLS

    ap = argparse.ArgumentParser(prog="perfbench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", default=None, choices=CONTROLS)
    args = ap.parse_args(argv)
    cell = load_cell(ROOT, args.workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"perfbench: {args.workload} needs {cell.chips} TPU chip(s); "
              f"JAX has {len(devices)} {devices[0].platform!r} device(s)",
              file=sys.stderr)
        return 2
    # the compile cache lives in this checkout, at a fixed path (the path
    # is part of each entry's key), whatever the environment names
    os.makedirs(CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    from perfbench.harness import run_cell

    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      root=ROOT, t_process=T_PROCESS, control=args.control)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
