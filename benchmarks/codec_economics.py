"""Device-offload economics: does the CRC/RS batching seam scale to line
rate on a co-located chip?  The answer turns on round-trip cost vs batch
size.

End-to-end device-offload numbers from a CPU box say nothing about
production.  What IS measurable here, and
platform-independent, is the BATCHING BEHAVIOR of the seam: how many
payload bytes the micro-batcher accumulates per kernel launch under real
CRAQ write load (the batch window closes on the event loop's schedule,
not the device's).  Combined with the on-device kernel rate (69.9
GB/s/chip, commit 9a98cf6) and standard interconnect numbers, that bounds
what a co-located chip sustains:

    t(batch) = launch_overhead + bytes/pcie_bw + bytes/kernel_rate
    sustained = bytes / t(batch)

Run:  python -m benchmarks.codec_economics --json
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys

# measured on-device (round 2, commit 9a98cf6; bench.py re-measures when
# the chip is reachable)
KERNEL_GBPS = 69.9
LINE_RATE_GBPS = 50.0          # 2 x 200 Gbps per storage node
LAUNCH_S = 30e-6               # typical TPU dispatch overhead, co-located
INTERCONNECTS = {              # host->device copy bandwidth, GB/s
    "pcie3x16": 12.0,
    "pcie4x16": 24.0,
    "co-packaged (CI/offload engine)": 100.0,
}


def sustained_gbps(batch_bytes: float, pcie_gbps: float) -> float:
    """SERIAL (store-and-forward) bound: copy, then compute."""
    if batch_bytes <= 0:
        return 0.0
    t = (LAUNCH_S + batch_bytes / (pcie_gbps * 1e9)
         + batch_bytes / (KERNEL_GBPS * 1e9))
    return batch_bytes / t / 1e9


def pipelined_gbps(batch_bytes: float, pcie_gbps: float) -> float:
    """DOUBLE-BUFFERED bound: H2D of batch n+1 overlaps compute of batch
    n, so throughput approaches min(copy, kernel) as batches amortize the
    launch overhead.  This is what the seam must implement to scale."""
    if batch_bytes <= 0:
        return 0.0
    per_batch = max(batch_bytes / (pcie_gbps * 1e9),
                    LAUNCH_S + batch_bytes / (KERNEL_GBPS * 1e9))
    return batch_bytes / per_batch / 1e9


def batch_for_line_rate(pcie_gbps: float) -> float | None:
    """Smallest batch (bytes) that sustains LINE_RATE_GBPS, or None when
    the interconnect itself cannot carry line rate."""
    # 1/sustained = LAUNCH/B + 1/pcie + 1/kernel  -> solve for B
    budget = 1.0 / (LINE_RATE_GBPS * 1e9)
    per_byte = 1.0 / (pcie_gbps * 1e9) + 1.0 / (KERNEL_GBPS * 1e9)
    if per_byte >= budget:
        return None
    return LAUNCH_S / (budget - per_byte)


async def measure_batching(chunk_size: int, seconds: float,
                           concurrency: int) -> dict:
    """Drive CRAQ writes through the in-process fabric with the device
    codec (interpret on CPU — the batching window is set by the event
    loop, not the device) and read the micro-batcher's counters."""
    from benchmarks.storage_bench import parse_args, run_bench
    from t3fs.testing import fabric as fabric_mod

    stats = {}
    orig_start = fabric_mod.StorageFabric.start

    async def spying_start(self):
        out = await orig_start(self)
        stats["nodes"] = list(self.nodes)
        return out
    fabric_mod.StorageFabric.start = spying_start
    try:
        args = parse_args(["--mode", "write", "--nodes", "1",
                           "--replicas", "1",
                           "--chunk-size", str(chunk_size),
                           "--num-chunks", "64",
                           "--concurrency", str(concurrency),
                           "--seconds", str(seconds),
                           "--checksum-backend", "tpu"])
        res = await run_bench(args)
    finally:
        fabric_mod.StorageFabric.start = orig_start
    codec = stats["nodes"][0].codec
    batches = max(1, codec.batches)
    items = codec.batched_items
    return {
        "write_MB_s": res.get("MB_s"),
        "batches": codec.batches,
        "batched_items": items,
        "items_per_batch": round(items / batches, 2),
        "batch_bytes": round(items / batches * chunk_size),
    }


async def main_async(args) -> dict:
    out = {"kernel_GBps": KERNEL_GBPS, "line_rate_GBps": LINE_RATE_GBPS,
           "launch_overhead_us": LAUNCH_S * 1e6, "measured": {},
           "model": {}}
    for cs in args.chunk_sizes:
        m = await measure_batching(cs, args.seconds, args.concurrency)
        out["measured"][f"chunk_{cs}"] = m
        per_if = {}
        for name, bw in INTERCONNECTS.items():
            per_if[name] = {
                "serial_GBps_at_measured_batch": round(
                    sustained_gbps(m["batch_bytes"], bw), 2),
                "pipelined_GBps_at_measured_batch": round(
                    pipelined_gbps(m["batch_bytes"], bw), 2),
                "pipelined_vs_line_rate": round(
                    pipelined_gbps(m["batch_bytes"], bw)
                    / LINE_RATE_GBPS, 3),
            }
            need = batch_for_line_rate(bw)
            per_if[name]["serial_min_batch_for_line_rate"] = (
                round(need) if need is not None else "unreachable")
        out["model"][f"chunk_{cs}"] = per_if
    return out


def parse_args(argv=None):
    ap = argparse.ArgumentParser(prog="codec_economics")
    ap.add_argument("--chunk-sizes", type=int, nargs="+",
                    default=[65536, 1 << 20])
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--concurrency", type=int, default=16)
    ap.add_argument("--json", action="store_true")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    res = asyncio.run(main_async(args))
    if args.json:
        print(json.dumps(res, indent=1))
    else:
        for k, v in res.items():
            print(k, json.dumps(v, indent=1) if isinstance(v, dict) else v)
    return 0


if __name__ == "__main__":
    sys.exit(main())
