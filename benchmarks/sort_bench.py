"""GraySort-analog: two-phase partition sort with t3fs as the shuffle medium.

Reference analog: README.md:38-40 — GraySort via smallpond on 3FS (110.5 TiB
in 30m14s across 25 storage + 50 compute nodes).  The job shape is the
classic external sort: phase 1 scans the input, range-partitions records by
key, and writes partition runs back to the FS; phase 2 reads each
partition's runs, sorts, and writes sorted output.  Every byte crosses the
storage stack four times (input read, run write, run read, output write) —
it is a *filesystem* benchmark wearing a sort costume, which is exactly why
the reference uses it as a headline.

t3fs version: records are gensort-layout (10-byte key + 90-byte payload);
the data path is StorageClient file ranges over CRAQ chains (zero-metadata
placement); run lengths are discovered via query_last_chunk like real
readers, not smuggled through memory.  The per-partition key sort is
pluggable: `numpy` (np.lexsort oracle, default) or `device`
(t3fs/ops/device_sort.py — lax.sort of uint32 key columns on the TPU,
permutation applied host-side).

    python -m benchmarks.sort_bench --mb 64 --partitions 8 --json
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time

import numpy as np

from t3fs.client.layout import FileLayout
from t3fs.client.storage_client import StorageClient
from t3fs.ops.device_sort import REC_LEN, lexsort_rows

# inode-space convention for the job's files (disjoint from meta's growing
# ids and from kvcache's (1<<63)|hash space)
IN_INODE = 0x5027 << 40          # + worker
RUN_INODE = 0x5027 << 40 | 1 << 32   # + (worker<<16 | partition)
OUT_INODE = 0x5027 << 40 | 2 << 32   # + partition


def _partition_of(rows: np.ndarray, parts: int) -> np.ndarray:
    """Range partition by the key's high 64 bits (parts must be 2^k so the
    cut points are exact bit shifts)."""
    hi = rows[:, 0:8].copy().view(">u8").ravel()
    if parts == 1:
        return np.zeros(len(hi), dtype=np.int64)
    return (hi >> np.uint64(64 - parts.bit_length() + 1)).astype(np.int64)


def _check_pow2(n: int, what: str) -> None:
    if n < 1 or n & (n - 1):
        raise SystemExit(f"{what} must be a power of two, got {n}")


async def run_bench(args) -> dict:
    _check_pow2(args.partitions, "--partitions")
    from benchmarks._env import make_env
    env, sc, chains = await make_env(args)
    try:
        return await _run_job(args, sc, chains)
    finally:
        await sc.close()
        await env.stop()


async def _cleanup_job_files(args, sc: StorageClient,
                             lay: FileLayout) -> None:
    """Remove this job's IN/RUN/OUT files.  Run both before (a previous
    crashed/differently-sized invocation against a live cluster leaves runs
    whose stale lengths would corrupt this one) and after (don't leak
    chunks on the cluster)."""
    inodes = ([IN_INODE + w for w in range(args.workers)]
              + [RUN_INODE + (w << 16 | p) for w in range(args.workers)
                 for p in range(args.partitions)]
              + [OUT_INODE + p for p in range(args.partitions)])
    await asyncio.gather(*(sc.remove_file_chunks(lay, inode)
                           for inode in inodes))


async def _run_job(args, sc: StorageClient, chains: list[int]) -> dict:
    lay = FileLayout(chunk_size=args.chunk_size, chains=chains)
    workers, parts = args.workers, args.partitions
    total_bytes = args.mb << 20
    rec_per_worker = total_bytes // REC_LEN // workers
    total_records = rec_per_worker * workers
    total_bytes = total_records * REC_LEN

    sorter = lexsort_rows
    if args.sort_backend == "device":
        from t3fs.ops.device_sort import make_device_sorter
        sorter = make_device_sorter()

    await _cleanup_job_files(args, sc, lay)

    # --- input generation (not timed: gensort is the reference's untimed
    # input producer too) ---
    in_sum = np.uint64(0)
    for w in range(workers):
        rng = np.random.default_rng(args.seed + w)
        rows = rng.integers(0, 256, (rec_per_worker, REC_LEN), dtype=np.uint8)
        in_sum ^= np.bitwise_xor.reduce(
            rows[:, 0:8].copy().view(">u8").ravel())
        await sc.write_file_range(lay, IN_INODE + w, 0, rows.tobytes())

    t_job0 = time.perf_counter()

    # --- phase 1: scan input, range-partition, write runs ---
    async def map_worker(w: int) -> None:
        data, _ = await sc.read_file_range(
            lay, IN_INODE + w, 0, rec_per_worker * REC_LEN)
        rows = np.frombuffer(data, dtype=np.uint8).reshape(-1, REC_LEN)
        p = _partition_of(rows, parts)
        order = np.argsort(p, kind="stable")
        sp = p[order]
        bounds = np.searchsorted(sp, np.arange(parts + 1))
        writes = []
        for part in range(parts):
            seg = rows[order[bounds[part]:bounds[part + 1]]]
            if len(seg):
                writes.append(sc.write_file_range(
                    lay, RUN_INODE + (w << 16 | part), 0, seg.tobytes()))
        await asyncio.gather(*writes)

    await asyncio.gather(*(map_worker(w) for w in range(workers)))
    t_p1 = time.perf_counter()

    # --- phase 2: per partition, read runs (lengths via query_last_chunk),
    # sort, write output ---
    async def read_run(part: int, w: int) -> np.ndarray | None:
        inode = RUN_INODE + (w << 16 | part)
        length = await sc.query_last_chunk(lay, inode)
        if not length:
            return None
        data, _ = await sc.read_file_range(lay, inode, 0, length)
        return np.frombuffer(data, dtype=np.uint8).reshape(-1, REC_LEN)

    async def reduce_worker(part: int) -> tuple[int, np.uint64]:
        segs = [s for s in await asyncio.gather(
            *(read_run(part, w) for w in range(workers))) if s is not None]
        if not segs:
            return 0, np.uint64(0)
        rows = np.concatenate(segs) if len(segs) > 1 else segs[0]
        rows = rows[sorter(rows)]
        await sc.write_file_range(lay, OUT_INODE + part, 0, rows.tobytes())
        return len(rows), np.bitwise_xor.reduce(
            rows[:, 0:8].copy().view(">u8").ravel())

    reduced = await asyncio.gather(*(reduce_worker(p) for p in range(parts)))
    t_p2 = time.perf_counter()

    # --- validation (untimed): outputs are sorted, contiguous across
    # partitions, and no record was lost or invented ---
    out_records = sum(n for n, _ in reduced)
    out_sum = np.uint64(0)
    for _, s in reduced:
        out_sum ^= s
    assert out_records == total_records, (out_records, total_records)
    assert out_sum == in_sum, "key checksum mismatch: records corrupted"
    prev_last = None
    for part in range(parts):
        n = reduced[part][0]
        if n == 0:
            continue
        data, _ = await sc.read_file_range(lay, OUT_INODE + part,
                                           0, n * REC_LEN)
        rows = np.frombuffer(data, dtype=np.uint8).reshape(-1, REC_LEN)
        # sorted iff a stable key-sort of the output is the identity
        assert np.array_equal(lexsort_rows(rows), np.arange(len(rows))), \
            f"partition {part} unsorted"
        flat = rows[:, :10].tobytes()
        if prev_last is not None:
            assert prev_last <= flat[:10], "partition boundary out of order"
        prev_last = flat[-10:]

    await _cleanup_job_files(args, sc, lay)

    wall = t_p2 - t_job0
    return {
        "records": total_records, "bytes": total_bytes,
        "workers": workers, "partitions": parts,
        "sort_backend": args.sort_backend,
        "phase1_s": round(t_p1 - t_job0, 3),
        "phase2_s": round(t_p2 - t_p1, 3),
        "sort_wall_s": round(wall, 3),
        "sort_MB_s": round(total_bytes / wall / 1e6, 2),
        "verified": True,
    }


def parse_args(argv=None):
    ap = argparse.ArgumentParser(prog="sort_bench")
    ap.add_argument("--mgmtd", default="",
                    help="live cluster address; omit for in-process fabric")
    ap.add_argument("--nodes", type=int, default=3)
    ap.add_argument("--replicas", type=int, default=1)
    ap.add_argument("--mb", type=int, default=32, help="input size in MiB")
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--partitions", type=int, default=8)
    ap.add_argument("--chunk-size", type=int, default=1 << 20)
    ap.add_argument("--sort-backend", choices=["numpy", "device"],
                    default="numpy")
    ap.add_argument("--seed", type=int, default=2026)
    ap.add_argument("--no-aio", action="store_true")
    ap.add_argument("--json", action="store_true")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    if args.sort_backend == "device":
        from benchmarks._env import require_tpu
        require_tpu()
    result = asyncio.run(run_bench(args))
    if args.json:
        print(json.dumps(result))
    else:
        for k, v in result.items():
            print(f"{k:>14}: {v}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
