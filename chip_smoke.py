#!/usr/bin/env python
"""chip_smoke: drive t3fs's storage data path once, end to end, on one TPU.

Everything runs in this one process, which owns the chip: an in-process
LocalCluster (mgmtd, meta, 10 storage nodes and the clients, all real
servers over loopback TCP) whose storage nodes checksum on the device
(checksum_backend="tpu"), and the EC client's ECCodec, which encodes,
decodes and repairs on the same device.  Phases, in order:

  device    fail unless JAX's device is a TPU
  warm-up   precompile every kernel shape the later phases run
  craq      CRAQ x3 (BASELINE cfgs 1-2): 64 x 4 MiB chunks through
            StorageClient, read back from every replica, stored
            checksums checked against the host crc32c
  ckpt      RS(8+2) (BASELINE cfg 3): a >= 1 GiB pytree from --seed saved
            through CheckpointWriter (1 MiB chunks, 10 single-replica EC
            chains, fused device encode+CRC) and restored leaf by leaf
  loss      BASELINE cfg 4: two nodes holding data slots 0 and 1 of every
            stripe fail-stop; a degraded restore decodes both (fused
            decode+verify), the nodes restart empty, every stripe is
            repaired, and a scrub finds nothing lost or corrupt
  pm-msr    64 stripes at 64 KiB chunks under local_scheme="pm-msr"; one
            node wiped and every stripe's lost shard repaired from
            projections that read 0.5625x of a full-k repair
  codecs    which implementation served each codec call; the XLA
            fallbacks must not appear on this path

Each phase prints one line (with its wall seconds: set-up evidence, not a
metric); any failure exits non-zero.  The last line of a passing run is
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}.

    python chip_smoke.py [--seed N]
"""

from __future__ import annotations

import argparse
import asyncio
import gc
import json
import shutil
import sys
import time
from dataclasses import dataclass

import numpy as np

MiB = 1 << 20
# codec implementations that must not serve a call on the chip's path
FALLBACK_CODECS = ("xla-bitmatmul", "xla-repair-words")


@dataclass
class Sizes:
    """Scale of each phase.  The defaults are the deployment's own sizes;
    tests shrink them (widths and the mix of operations stay)."""
    craq_chunks: int = 64
    craq_chunk: int = 4 * MiB
    ckpt_chunk: int = 1 * MiB
    ckpt_layers: int = 15               # 15 x 64 MiB + a 115.5 MiB embed
    ckpt_width: int = 1024
    window: int = 8                     # stripes in flight
    msr_stripes: int = 64
    msr_chunk: int = 64 << 10


class Failed(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise Failed(what)


class CompileLog:
    """Counts XLA backend compiles (and persistent-cache hits among them)
    from JAX's own monitoring events."""

    def __init__(self):
        import jax

        from jax._src import dispatch
        self._event = dispatch.BACKEND_COMPILE_EVENT
        self.programs = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._count)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == self._event:
            self.programs += 1
            self.seconds += secs

    def _count(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> tuple[int, float, int]:
        return self.programs, self.seconds, self.cache_hits


class LoopLag:
    """The event loop's longest stall since the last take(), measured as a
    sleeper task's oversleep.  Every in-process node's heartbeat and lease
    runs on this loop, so a stall of seconds reads as node failure."""

    def __init__(self):
        self.max = 0.0
        self._task: asyncio.Task | None = None

    async def _run(self) -> None:
        loop = asyncio.get_running_loop()
        while True:
            t = loop.time()
            await asyncio.sleep(0.05)
            self.max = max(self.max, loop.time() - t - 0.05)

    def start(self) -> None:
        self._task = asyncio.get_running_loop().create_task(self._run())

    def take(self) -> float:
        lag, self.max = self.max, 0.0
        return lag

    async def stop(self) -> None:
        self._task.cancel()
        await asyncio.gather(self._task, return_exceptions=True)


def make_tree(sizes: Sizes, seed: int) -> dict:
    """Transformer-shaped float32 parameters from `seed`.  The embedding's
    length ends 3.5 chunks into a stripe, so its tail stripe has full
    shards 0-2, a trimmed shard 3 and zero holes 4-7."""
    rng = np.random.default_rng(seed)
    d = sizes.ckpt_width

    def leaf(*shape):
        n = int(np.prod(shape))
        return np.frombuffer(rng.bytes(4 * n), dtype=np.float32).reshape(shape)

    stripe_rows = 8 * sizes.ckpt_chunk // (4 * d)
    embed_rows = 14 * stripe_rows + 7 * stripe_rows // 16
    return {"embed": leaf(embed_rows, d),
            "layers": [{"w_in": leaf(d, 8 * d), "w_out": leaf(8 * d, d)}
                       for _ in range(sizes.ckpt_layers)]}


def same_tree(a: dict, b: dict) -> bool:
    """Byte equality leaf by leaf (random float bits include NaNs).  The
    element compare releases the GIL, so a thread running this leaves the
    cluster's event loop (heartbeats, leases) responsive."""
    from t3fs.ckpt import flatten_tree
    la, ta = flatten_tree(a)
    lb, tb = flatten_tree(b)
    return ta == tb and len(la) == len(lb) and all(
        pa == pb and x.dtype == y.dtype and x.shape == y.shape
        and np.array_equal(x.view(np.uint8), y.view(np.uint8))
        for (pa, x), (pb, y) in zip(la, lb))


async def wait_until(cond, what: str, timeout_s: float = 60.0) -> None:
    for _ in range(int(timeout_s / 0.05)):
        if cond():
            return
        await asyncio.sleep(0.05)
    raise Failed(f"timed out waiting for {what}")


async def kill_nodes(cluster, nodes: list[int]) -> None:
    """Fail-stop `nodes` and wait until routing serves none of their
    targets and every chain that kept a replica has a live head."""
    affected = [c.chain_id
                for c in cluster.mgmtd.state.routing().chains.values()
                if any(t.node_id in nodes for t in c.targets)]
    for n in nodes:
        await cluster.kill_storage_node(n)

    def settled() -> bool:
        routing = cluster.mgmtd.state.routing()
        for c in affected:
            chain = routing.chains[c]
            if any(t.node_id in nodes for t in chain.serving()):
                return False
            if chain.head() is None and any(t.node_id not in nodes
                                            for t in chain.targets):
                return False
        return True

    await wait_until(settled, "chains to drop the killed nodes")
    await cluster.mgmtd_client.refresh()


async def restart_empty(cluster, nodes: list[int], chains: list[int]) -> None:
    """Wipe the killed nodes' disks, restart them, and wait until every
    chain in `chains` has a serving head again."""
    for n in nodes:
        # unlinking a node's chunk files takes a third of a second on the
        # chip host: off the loop that carries every heartbeat
        await asyncio.to_thread(shutil.rmtree, cluster.node_root(n), True)
        await cluster.start_storage_node(n)
    await wait_until(lambda: all(
        cluster.mgmtd.state.routing().chains[c].head() is not None
        for c in chains), "restarted chains to serve")
    await cluster.mgmtd_client.refresh()


def device_crc_rows(cluster) -> tuple[int, int]:
    """(payloads checksummed, zero rows padded) by the live nodes' device
    CRC batchers."""
    codecs = [ss.node.codec for ss in cluster.storage.values()]
    return (sum(getattr(c, "batched_items", 0) for c in codecs),
            sum(getattr(c, "padded_rows", 0) for c in codecs))


async def run_phases(sizes: Sizes, seed: int, checksum_backend, log,
                     compiles: CompileLog) -> dict:
    """Run every phase after the device check; `log(line)` prints one
    line per phase.  Raises Failed (or the underlying error) on the first
    failed phase.  Returns ECCodec.codec_counts."""
    from t3fs.ckpt import CheckpointReader, CheckpointWriter, flatten_tree
    from t3fs.client.ec_client import ECLayout, ECStorageClient, RepairIOStats
    from t3fs.client.ec_codec import ECCodec
    from t3fs.fuse.vfs import FileSystem
    from t3fs.ops.codec import crc32c
    from t3fs.ops.msr import default_msr
    from t3fs.storage.codec_backend import make_checksum_backend
    from t3fs.storage.server import WARMUP_PAYLOAD_SIZES
    from t3fs.storage.types import BatchReadReq, ChunkId, ReadIO
    from t3fs.testing.cluster import LocalCluster
    from t3fs.utils.status import StatusCode

    OK = int(StatusCode.OK)
    k, m = 8, 2
    rng = np.random.default_rng(seed)
    ec_chains = list(range(2, 2 + k + m))    # chain 1 is the CRAQ chain
    rs_lay = ECLayout.create(k=k, m=m, chunk_size=sizes.ckpt_chunk,
                             chains=ec_chains)
    msr_lay = ECLayout.create(k=k, m=m, chunk_size=sizes.msr_chunk,
                              chains=ec_chains, local_scheme="pm-msr")
    lost = (0, 1)              # data slots of every stripe on the victims
    msr_lost = 3
    present = tuple(s for s in range(k + m) if s not in lost)

    lag = LoopLag()

    def phase_compiles(before) -> str:
        p, s, h = compiles.snapshot()
        return (f" loop stall max {lag.take():.3f} s;"
                f" compiles={p - before[0] - (h - before[2])}"
                f" cache_hits={h - before[2]}")

    mark = compiles.snapshot

    # --- warm-up: every kernel shape the phases below run ---
    t0, c0 = time.perf_counter(), mark()
    crc = make_checksum_backend(checksum_backend)
    codec = ECCodec()
    try:
        n_warm = crc.warmup(WARMUP_PAYLOAD_SIZES)
    finally:
        await crc.close()
    n_warm += codec.warmup_encode(sizes.ckpt_chunk, k, m)
    n_warm += codec.warmup_decode([(present, lost)], sizes.ckpt_chunk, k, m)
    n_warm += codec.warmup_msr([msr_lost], sizes.msr_chunk, k, m)
    p, s, h = compiles.snapshot()
    log(f"warm-up: {n_warm} programs run, {p - c0[0]} jit programs built "
        f"({h - c0[2]} from the persistent cache) in {s - c0[1]:.3f} s of "
        f"compile; {time.perf_counter() - t0:.3f} s wall")

    # all data is made before the cluster starts: generating a GiB holds
    # the interpreter for seconds, which would stall every node's
    # heartbeat on the shared event loop
    payloads = [rng.bytes(sizes.craq_chunk)
                for _ in range(sizes.craq_chunks)]
    payload_crcs = [crc32c(p) for p in payloads]
    tree = make_tree(sizes, seed)
    nbytes = sum(x.nbytes for _, x in flatten_tree(tree)[0])
    stripe_bytes = k * sizes.msr_chunk
    blob = rng.bytes(sizes.msr_stripes * stripe_bytes)
    # what warm-up and set-up built lives to the end: keep it out of the
    # collector, whose full passes over it (0.1-0.3 s on the chip host)
    # would stall the shared loop mid-phase
    gc.collect()
    gc.freeze()

    # LocalCluster's own liveness (0.6 s heartbeat timeout, 5 s lease): a
    # stall of the shared loop longer than that reads as node failure
    cluster = LocalCluster(num_nodes=k + m, replicas=3, num_chains=1,
                           ec_chains=k + m, with_meta=True,
                           checksum_backend=checksum_backend)
    await cluster.start()
    ec = ECStorageClient(cluster.sc, codec=codec)
    lag.start()
    try:
        # --- CRAQ x3 write path ---
        t0, c0 = time.perf_counter(), mark()
        items0, pad0 = device_crc_rows(cluster)
        ids = [ChunkId(0x5EED, i) for i in range(sizes.craq_chunks)]
        res = await asyncio.gather(*(
            cluster.sc.write_chunk(1, cid, 0, p, chunk_size=sizes.craq_chunk)
            for cid, p in zip(ids, payloads)))
        check(all(r.status.code == OK for r in res), "CRAQ write failed")
        chain = cluster.chain(1)
        bad = 0
        for t in chain.targets:
            addr = cluster.storage[t.node_id].server.address
            for g in range(0, len(ids), 8):
                rsp, data = await cluster.admin.call(
                    addr, "Storage.batch_read", BatchReadReq(ios=[
                        ReadIO(chunk_id=cid, chain_id=1)
                        for cid in ids[g:g + 8]]))
                pos = 0
                for r, p, c in zip(rsp.results, payloads[g:g + 8],
                                   payload_crcs[g:g + 8]):
                    got = data[pos:pos + r.length]
                    pos += r.length
                    bad += (r.status.code != OK or got != p
                            or int(r.checksum) != c)
        items, pad = device_crc_rows(cluster)
        items, pad = items - items0, pad - pad0
        check(bad == 0, f"CRAQ read-back: {bad} replica reads wrong")
        check(items >= sizes.craq_chunks * len(chain.targets),
              f"CRAQ: only {items} payloads checksummed on the device")
        log(f"craq x{len(chain.targets)}: {sizes.craq_chunks} x "
            f"{sizes.craq_chunk >> 10} KiB written, read back from "
            f"{len(chain.targets)} replicas, 0 mismatches, {items} device "
            f"CRCs (+{pad} zero rows padded);{phase_compiles(c0)} "
            f"{time.perf_counter() - t0:.3f} s")

        # --- RS(8+2) checkpoint save + restore ---
        t0, c0 = time.perf_counter(), mark()
        fs = FileSystem(cluster.mc, cluster.sc)
        writer = CheckpointWriter(ec, fs, rs_lay, "/smoke/ckpt",
                                  window=sizes.window)
        enc0 = codec.codec_counts.get("pallas-encode-words", 0)
        stats = await writer.save(1, tree, resume=False)
        t_save = time.perf_counter() - t0
        check(codec.codec_counts.get("pallas-encode-words", 0) > enc0,
              "ckpt save did not run the fused device encode")
        reader = CheckpointReader(ec, fs, "/smoke/ckpt", window=sizes.window)
        t1 = time.perf_counter()
        check(await asyncio.to_thread(same_tree, await reader.restore(),
                                      tree),
              "ckpt restore differs from the saved tree")
        log(f"ckpt rs8+2: {nbytes} bytes in {stats.stripes_total} stripes "
            f"({stats.shards_written} shards) saved in {t_save:.3f} s, "
            f"restored in {time.perf_counter() - t1:.3f} s, 0 mismatches;"
            f"{phase_compiles(c0)} {time.perf_counter() - t0:.3f} s")

        # --- node loss: degraded restore, repair, scrub ---
        t0, c0 = time.perf_counter(), mark()
        routing = cluster.mgmtd.state.routing()
        victims = [routing.chains[rs_lay.shard_chain(0, s)].head().node_id
                   for s in lost]
        check(all(routing.chains[rs_lay.shard_chain(st, s)].head().node_id
                  == v for st in range(k + m)
                  for s, v in zip(lost, victims)),
              "victims do not hold slots 0 and 1 of every stripe")
        await kill_nodes(cluster, victims)
        dec0 = codec.codec_counts.get("pallas-decode-words", 0)
        t1 = time.perf_counter()
        check(await asyncio.to_thread(same_tree, await reader.restore(),
                                      tree),
              "degraded restore differs from the saved tree")
        t_degraded = time.perf_counter() - t1
        decodes = codec.codec_counts.get("pallas-decode-words", 0) - dec0
        check(decodes > 0, "degraded restore ran no device decode")
        await restart_empty(cluster, victims,
                            [rs_lay.shard_chain(0, s) for s in lost])
        manifest = await reader.store.load(1)
        window = asyncio.Semaphore(sizes.window)

        async def repair(lf, s):
            async with window:
                return await ec.repair_stripe(rs_lay, lf.inode, s, lost,
                                              lf.stripe_len(rs_lay, s))

        t1 = time.perf_counter()
        outs = await asyncio.gather(*(repair(lf, s) for lf in manifest.leaves
                                      for s in range(lf.num_stripes)))
        t_repair = time.perf_counter() - t1
        # zero-hole shards of short stripes "repair" as a REMOVE
        failed = sum(r.status.code not in (OK, int(StatusCode.CHUNK_NOT_FOUND))
                     for out in outs for r in out)
        check(failed == 0, f"repair: {failed} shard write-backs failed")
        t1 = time.perf_counter()
        report = await reader.scrub(1, repair=False)
        t_scrub = time.perf_counter() - t1
        check(report.shards_missing == 0 and report.shards_corrupt == 0,
              f"scrub after repair: {report}")
        log(f"node loss: nodes {victims} (data slots {lost}) killed; "
            f"degraded restore 0 mismatches via {decodes} device decode "
            f"batches in {t_degraded:.3f} s; {len(outs)} stripes repaired "
            f"in {t_repair:.3f} s; scrub of {report.shards_checked} shards: "
            f"0 lost, 0 corrupt in {t_scrub:.3f} s;{phase_compiles(c0)} "
            f"{time.perf_counter() - t0:.3f} s")

        # --- PM-MSR single-loss repair ---
        t0, c0 = time.perf_counter(), mark()
        inode = 0xA5A5
        res = await asyncio.gather(*(
            ec.write_stripe(msr_lay, inode, s,
                            blob[s * stripe_bytes:(s + 1) * stripe_bytes])
            for s in range(sizes.msr_stripes)))
        check(all(r.status.code == OK for out in res for r in out),
              "pm-msr stripe write failed")
        routing = cluster.mgmtd.state.routing()
        victim = routing.chains[msr_lay.shard_chain(0, msr_lost)] \
            .head().node_id
        await kill_nodes(cluster, [victim])
        await restart_empty(cluster, [victim],
                            [msr_lay.shard_chain(0, msr_lost)])
        rstats = RepairIOStats()
        outs = await asyncio.gather(*(
            ec.repair_stripe(msr_lay, inode, s, (msr_lost,), stripe_bytes,
                             stats=rstats)
            for s in range(sizes.msr_stripes)))
        check(all(r.status.code == OK for out in outs for r in out),
              "pm-msr repair write-back failed")
        check(rstats.reduced_shards == sizes.msr_stripes
              and rstats.fallback_shards == 0,
              f"pm-msr repair left the projection path: {rstats}")
        bad = 0
        for s in range(sizes.msr_stripes):
            data, crcs = await ec.read_stripe_with_crcs(msr_lay, inode, s,
                                                        stripe_bytes)
            want = blob[s * stripe_bytes:(s + 1) * stripe_bytes]
            shard = want[msr_lost * sizes.msr_chunk:
                         (msr_lost + 1) * sizes.msr_chunk]
            bad += data != want or crcs[msr_lost] != crc32c(shard)
        check(bad == 0, f"pm-msr: {bad} repaired stripes read back wrong")
        full_k = sizes.msr_stripes * k * sizes.msr_chunk
        ratio = rstats.bytes_read / full_k
        check(ratio == default_msr(k, m).d / (2 * k),
              f"pm-msr survivor bytes {ratio}x of full-k, want 0.5625x")
        log(f"pm-msr: {sizes.msr_stripes} stripes x {sizes.msr_chunk >> 10} "
            f"KiB, node {victim} (slot {msr_lost}) wiped and repaired, 0 "
            f"mismatches, survivor bytes {rstats.bytes_read} = {ratio}x of "
            f"full-k;{phase_compiles(c0)} "
            f"{time.perf_counter() - t0:.3f} s")
    finally:
        await lag.stop()
        await ec.close()
        await cluster.stop()
        gc.unfreeze()

    counts = dict(sorted(codec.codec_counts.items()))
    log(f"codec_counts: {json.dumps(counts)}; {codec.batches} EC launches "
        f"of {codec.batched_items} requests + {codec.padded_rows} zero rows "
        f"padded")
    served = [c for c in FALLBACK_CODECS if counts.get(c)]
    check(not served, f"fallback codecs served calls on the chip path: "
                      f"{served}")
    return counts


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="chip_smoke")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    from t3fs.storage.codec_backend import enable_persistent_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU: JAX's device is {dev.platform!r} "
              f"({dev.device_kind})", file=sys.stderr)
        return 2
    enable_persistent_cache()
    print(f"device: {dev.device_kind} x{jax.device_count()} "
          f"(compile cache {jax.config.jax_compilation_cache_dir})",
          flush=True)
    compiles = CompileLog()
    t0 = time.perf_counter()
    try:
        asyncio.run(run_phases(Sizes(), args.seed, "tpu",
                               lambda line: print(line, flush=True),
                               compiles))
    except Failed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    p, s, h = compiles.snapshot()
    print(f"total: {p} jit programs built ({h} from the persistent cache, "
          f"{s:.3f} s of compile); {time.perf_counter() - t0:.3f} s wall",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
