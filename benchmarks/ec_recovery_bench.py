"""EC recovery bench — BASELINE config #4: RS(8+2) stripe writes, degraded
reads after a node loss, and shard repair (reconstruct + write back).

The reference has no EC data path (SURVEY header note) — its config-#4
analog is plain replica resync (src/storage/sync/ResyncWorker.cc:101-389).
t3fs's EC client makes recovery a *decode*: parity masks a lost node at
read time, and `repair_chunk` rebuilds the lost shards from the survivors.

Phases (all timed separately, MB/s of logical stripe data):
  write     — RS(8+2)-encoded stripe writes across single-replica chains
  degraded  — full-stripe reads with one node fail-stopped (reconstruction
              masks its shards on the fly)
  repair    — reconstruct the dead node's shards and re-write them to the
              recovered chains (the resync-with-decode path)

    python -m benchmarks.ec_recovery_bench --stripes 24 --json
    (--device runs RS on the TPU and fails without one; the default
     numpy codec runs anywhere)

With --device the bench also runs a decode microbench on synthetic
survivors: the fused word-packed decode+verify launch
(make_stripe_decode_step_words) and the word reconstruct alone.
Regardless of flags the bench ends with a one-line JSON decode metric
(rs{k}+{m}_reconstruct GB/s + degraded-read MB/s) for log scraping.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time

import numpy as np

from t3fs.client.ec_client import ECLayout, ECStorageClient
from t3fs.testing.cluster import LocalCluster
from t3fs.utils.status import StatusCode


async def run_bench(args) -> dict:
    k, m = args.k, args.m
    num_chains = k + m
    # one chain per shard slot, single replica: parity replaces replication
    cluster = LocalCluster(num_nodes=args.nodes, replicas=1,
                           num_chains=num_chains, heartbeat_timeout_s=0.6)
    await cluster.start()
    try:
        return await _run(args, cluster, k, m, num_chains)
    finally:
        await cluster.stop()


async def _run(args, cluster: LocalCluster, k: int, m: int,
               num_chains: int) -> dict:
    lay = ECLayout.create(k=k, m=m, chunk_size=args.chunk_size,
                          chains=list(range(1, num_chains + 1)))
    ec = ECStorageClient(cluster.sc, use_device_codec=args.device)
    stripe_len = k * args.chunk_size
    rng = np.random.default_rng(11)
    payloads = [rng.integers(0, 256, stripe_len, dtype=np.uint8).tobytes()
                for _ in range(4)]
    inode = 0xEC0
    total = args.stripes * stripe_len

    # --- write ---
    t0 = time.perf_counter()
    for s0 in range(0, args.stripes, args.concurrency):
        batch = range(s0, min(s0 + args.concurrency, args.stripes))
        res = await asyncio.gather(*(
            ec.write_stripe(lay, inode, s, payloads[s % len(payloads)])
            for s in batch))
        for rs_ in res:
            assert all(r.status.code == int(StatusCode.OK) for r in rs_)
    t_write = time.perf_counter() - t0

    # --- fail-stop one node; wait for chains to notice ---
    victim = args.nodes  # last node
    lost_chains = [c.chain_id for c in
                   cluster.mgmtd.state.routing().chains.values()
                   if any(t.node_id == victim for t in c.targets)]
    await cluster.kill_storage_node(victim)
    for _ in range(200):
        routing = cluster.mgmtd.state.routing()
        if all(routing.chains[c].chain_ver >= 2 for c in lost_chains):
            break
        await asyncio.sleep(0.05)
    else:
        raise TimeoutError("chains never noticed the node kill — the "
                           "'degraded' phase would measure stale routing")
    await cluster.mgmtd_client.refresh()

    # --- degraded reads (reconstruction masks the dead node's shards) ---
    t0 = time.perf_counter()
    for s0 in range(0, args.stripes, args.concurrency):
        batch = range(s0, min(s0 + args.concurrency, args.stripes))
        datas = await asyncio.gather(*(
            ec.read_stripe(lay, inode, s, stripe_len) for s in batch))
        for s, d in zip(batch, datas):
            assert d == payloads[s % len(payloads)], f"stripe {s} corrupt"
    t_degraded = time.perf_counter() - t0

    # --- repair: rebuild the dead node's shards onto the (restarted)
    # chains.  Restart the node empty: chains walk back to SERVING and the
    # repair writes land on the fresh target — simulated chunk loss. ---
    import shutil
    shutil.rmtree(cluster.node_root(victim), ignore_errors=True)
    await cluster.start_storage_node(victim)
    for _ in range(300):
        routing = cluster.mgmtd.state.routing()
        if all(routing.chains[c].head() is not None for c in lost_chains):
            break
        await asyncio.sleep(0.05)
    else:
        raise TimeoutError("restarted node's chains never returned to "
                           "service — repair phase has nowhere to write")
    await cluster.mgmtd_client.refresh()

    stripe_losses = {
        s: tuple(j for j in range(k + m)
                 if lay.shard_chain(s, j) in lost_chains)
        for s in range(args.stripes)}
    n_shards = sum(len(v) for v in stripe_losses.values())
    t0 = time.perf_counter()
    # survivor-read-balanced scheduling (the BIBD objective): the planner
    # picks WHICH k survivors each stripe reads.  The placement weights
    # are wired for parity with real deployments, but in THIS replicas-1
    # topology they are inert: the weighted chains are exactly the lost
    # chains, which never appear as survivors — the measured imbalance
    # improvement comes from the k-subset pick alone
    from t3fs.client.repair import RepairDriver, RepairJob
    from t3fs.mgmtd.placement import chain_recovery_weights
    weights = chain_recovery_weights(cluster.mgmtd.state.routing(),
                                     {victim})
    driver = RepairDriver(ec, concurrency=args.concurrency,
                          initial_load=weights)
    report = await driver.run([RepairJob(
        layout=lay, inode=inode,
        stripe_len_of={s: stripe_len for s in range(args.stripes)},
        losses=stripe_losses)])
    assert not report.failed, report.failed
    assert report.repaired_shards == n_shards
    t_repair = time.perf_counter() - t0
    repaired_bytes = n_shards * args.chunk_size

    # --- full (non-degraded) read-back proves the repair ---
    for s in range(args.stripes):
        d = await ec.read_stripe(lay, inode, s, stripe_len)
        assert d == payloads[s % len(payloads)], f"post-repair stripe {s}"

    # which codec implementation actually served the calls (pallas-words /
    # xla-bitmatmul / ...), plus batching effectiveness
    codec_stats = None
    if ec.codec is not None:
        codec_stats = {
            "counts": dict(ec.codec.codec_counts),
            "batches": ec.codec.batches,
            "batched_items": ec.codec.batched_items,
        }
        await ec.close()

    return {
        "k": k, "m": m, "chunk_size": args.chunk_size,
        "stripes": args.stripes, "bytes": total,
        "codec": "device" if args.device else "numpy",
        "codec_stats": codec_stats,
        "write_MB_s": round(total / t_write / 1e6, 2),
        "degraded_read_MB_s": round(total / t_degraded / 1e6, 2),
        "repaired_shards": n_shards,
        "repair_MB_s": round(repaired_bytes / t_repair / 1e6, 2),
        # IO accounting from RepairReport (ISSUE 9): survivor bytes pulled
        # per rebuilt byte — the number the reduced-read drill bench drives
        # below 0.5 with LRC locals (repair_drill_bench.py)
        "repair_bytes_read": report.bytes_read,
        "repair_bytes_repaired": report.bytes_repaired,
        "repair_stripes_failed": report.stripes_failed,
        "repair_read_amplification": round(
            report.bytes_read / max(report.bytes_repaired, 1), 3),
        # survivor-read balance achieved by the k-subset planner
        # (1.0 = perfectly flat)
        "survivor_read_imbalance": round(
            report.max_chain_reads / report.min_chain_reads, 3)
        if report.min_chain_reads else None,
        "survivor_reads_max_min": [report.max_chain_reads,
                                   report.min_chain_reads],
        "verified": True,
    }


def _decode_microbench(args) -> dict | None:
    """Kernel-level decode throughput on synthetic survivors (no cluster
    IO in the way).  Times the fused word-packed decode+verify launch —
    reconstruct of the 2 lost shards AND CRC32C of all k+|want| shards
    in ONE kernel pass — and the word reconstruct alone.  GB/s counts
    survivor bytes in per launch (n*k*L), the same convention as the
    encode bench.
    Runs only with --device, which main() holds to a TPU."""
    if not args.device:
        return None
    import jax

    from t3fs.ops.blocks import pick_block
    from t3fs.ops.pallas_codec import (
        make_rs_reconstruct_words_pallas, make_stripe_decode_step_words,
    )
    from t3fs.ops.rs import rs_code, stored_code

    k, m = args.k, args.m
    rs = rs_code(k, m, stored_code(k, m))
    L = args.chunk_size - args.chunk_size % 512
    n = max(1, args.decode_batch)
    present = tuple(range(2, k + 2))     # drop shards 0 and 1 (double erasure)
    want = (0, 1)
    rng = np.random.default_rng(7)
    words = rng.integers(0, 256, (n, k, L), dtype=np.uint8).view(
        np.uint32).reshape(n, k, L // 4)
    iters = 20

    def gbps(fn, x):
        out = jax.block_until_ready(fn(x))       # compile + warm
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(x)
        jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) / iters
        return round(n * k * L / dt / 1e9, 3)

    res: dict = {"L": L, "batch": n}
    fused = jax.jit(make_stripe_decode_step_words(
        L // 4, present, want, k=k, m=m, code=rs.code_id))
    res["fused_decode_verify_GB_s"] = gbps(fused, words)
    rec_w = jax.jit(make_rs_reconstruct_words_pallas(
        present, want, rs, block_w=pick_block(L // 4, 16384)))
    res["word_reconstruct_GB_s"] = gbps(rec_w, words)
    return res


def parse_args(argv=None):
    ap = argparse.ArgumentParser(prog="ec_recovery_bench")
    ap.add_argument("--nodes", type=int, default=5)
    ap.add_argument("--k", type=int, default=8)
    ap.add_argument("--m", type=int, default=2)
    ap.add_argument("--chunk-size", type=int, default=256 << 10)
    ap.add_argument("--stripes", type=int, default=16)
    ap.add_argument("--concurrency", type=int, default=4)
    ap.add_argument("--device", action="store_true",
                    help="RS encode/decode on the accelerator")
    ap.add_argument("--decode-batch", type=int, default=4,
                    help="stripes per launch in the decode microbench")
    ap.add_argument("--json", action="store_true")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    if args.device:
        from benchmarks._env import require_tpu
        platform = require_tpu()
    result = asyncio.run(run_bench(args))
    if args.device:
        result["platform"] = platform
    micro = _decode_microbench(args)
    if micro is not None:
        result["decode_microbench"] = micro
    if args.json:
        print(json.dumps(result))
    else:
        for kk, v in result.items():
            print(f"{kk:>20}: {v}")
    # one-line scrapable decode metric, printed in BOTH output modes
    print(json.dumps({"decode_metric": {
        f"rs{args.k}+{args.m}_reconstruct_GB_s":
            (micro or {}).get("fused_decode_verify_GB_s"),
        "degraded_read_MB_s": result["degraded_read_MB_s"],
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
