"""EC stripe path over a live cluster: encode on write, reconstruct on node
loss, repair-back (BASELINE configs #3/#4 — data path absent in reference)."""

import asyncio

import pytest

from t3fs.client.ec_client import ECLayout, ECStorageClient
from t3fs.client.ec_codec import ECCodec
from t3fs.testing.cluster import LocalCluster
from t3fs.utils.status import StatusCode


def test_ec_layout_addressing():
    lay = ECLayout.create(k=4, m=2, chunk_size=100, chains=[1, 2, 3, 4, 5, 6])
    # all shards of one stripe land on distinct chains
    chains = [lay.shard_chain(0, s) for s in range(6)]
    assert len(set(chains)) == 6
    # rotation: stripe 1 starts at a different chain
    assert lay.shard_chain(1, 0) == lay.shard_chain(0, 0)  # 6 % 6 == 0 rotation
    lay7 = ECLayout.create(k=4, m=2, chunk_size=100, chains=[1, 2, 3, 4, 5, 6, 7])
    assert lay7.shard_chain(1, 0) != lay7.shard_chain(0, 0)


def test_ec_legacy_layout_refuses_current_decoder():
    """A layout serialized before code_id existed must NOT be decoded with
    the current generator matrix (ADVICE r1: silent garbage reconstruction)."""
    from t3fs.ops.rs import default_rs
    from t3fs.utils import serde
    from t3fs.utils.status import StatusError
    lay = ECLayout.create(k=4, m=2, chunk_size=100, chains=[1, 2, 3, 4, 5, 6])
    # current-format layout round-trips and passes
    lay2 = serde.loads(serde.dumps(lay))
    lay2.check_code(default_rs(4, 2))
    # legacy blob: code_id field absent -> deserializes to the legacy id
    legacy = ECLayout(k=4, m=2, chunk_size=100, chains=[1, 2, 3, 4, 5, 6])
    assert legacy.code_id == "rrvand-11d"
    with pytest.raises(StatusError) as ei:
        legacy.check_code(default_rs(4, 2))
    assert ei.value.status.code == int(StatusCode.EC_FORMAT_MISMATCH)


def test_ec_write_read_roundtrip_and_reconstruct(monkeypatch):
    # force the SHIPPING Pallas kernels under the interpreter: the CPU
    # platform otherwise dispatches to the XLA path (r3 verdict weak #3)
    # and this test is the suite's coverage of the device kernels
    monkeypatch.setenv("T3FS_FORCE_PALLAS_INTERPRET", "1")

    async def body():
        # 6 chains, replication factor 1: parity replaces replication
        cluster = LocalCluster(num_nodes=3, replicas=1, num_chains=6,
                               heartbeat_timeout_s=0.6)
        await cluster.start()
        try:
            lay = ECLayout.create(k=4, m=2, chunk_size=2048,
                           chains=[1, 2, 3, 4, 5, 6])
            ec = ECStorageClient(cluster.sc)
            data = bytes(range(256)) * 32  # 8192 = exactly one 4-chunk stripe
            results = await ec.write_stripe(lay, 9, 0, data)
            assert all(r.status.code == int(StatusCode.OK) for r in results)
            got = await ec.read_stripe(lay, 9, 0, len(data))
            assert got == data

            # fail-stop node 2 (its chains lose their only target)
            await cluster.kill_storage_node(2)
            for _ in range(100):
                if all(c.chain_ver >= 2 for c in
                       cluster.mgmtd.state.routing().chains.values()
                       if any(t.node_id == 2 for t in c.targets)):
                    break
                await asyncio.sleep(0.1)
            # refresh client routing
            await cluster.mgmtd_client.refresh()

            # reads still return full data via RS reconstruction
            got = await ec.read_stripe(lay, 9, 0, len(data))
            assert got == data, "EC reconstruction must mask the lost node"

            # the SHIPPING codec path served the calls: the FUSED word
            # encode+CRC for the writes (stored CRCs ride along as
            # write_chunk checksums), the FUSED word decode+verify for the
            # degraded read (the EC client previously used
            # the slow XLA path while bench.py measured the word kernels),
            # and no other codec
            assert ec.codec.codec_counts.get("pallas-encode-words", 0) >= 1, \
                ec.codec.codec_counts
            assert ec.codec.codec_counts.get("pallas-decode-words", 0) >= 1, \
                ec.codec.codec_counts
            assert set(ec.codec.codec_counts) == {
                "pallas-encode-words", "pallas-decode-words"}, \
                ec.codec.codec_counts
            await ec.close()
        finally:
            await cluster.stop()
    asyncio.run(body())


def test_ec_codec_micro_batches_concurrent_stripes():
    """Concurrent write_stripe calls share ONE device launch per shape:
    the codec's batch axis is where the TPU path wins (mirrors the CRC
    backend's micro-batching on the storage write path)."""
    async def body():
        cluster = LocalCluster(num_nodes=3, replicas=1, num_chains=6,
                               heartbeat_timeout_s=0.6)
        await cluster.start()
        try:
            lay = ECLayout.create(k=4, m=2, chunk_size=2048,
                                  chains=[1, 2, 3, 4, 5, 6])
            ec = ECStorageClient(cluster.sc)
            data = bytes(range(256)) * 32
            n = 8
            results = await asyncio.gather(
                *(ec.write_stripe(lay, 9, s, data) for s in range(n)))
            for rs_ in results:
                assert all(r.status.code == int(StatusCode.OK) for r in rs_)
            # all encodes ran, in FEWER batches than stripes (>=2 stripes
            # coalesced at least once under gather's concurrency)
            assert ec.codec.batched_items == n
            assert ec.codec.batches < n, (
                ec.codec.batches, ec.codec.batched_items)
            for s in range(n):
                assert await ec.read_stripe(lay, 9, s, len(data)) == data
            await ec.close()
        finally:
            await cluster.stop()
    asyncio.run(body())


def test_ec_short_stripe_and_repair():
    async def body():
        cluster = LocalCluster(num_nodes=3, replicas=1, num_chains=6)
        await cluster.start()
        try:
            lay = ECLayout.create(k=4, m=2, chunk_size=1024, chains=[1, 2, 3, 4, 5, 6])
            ec = ECStorageClient(cluster.sc)
            data = b"short stripe!" * 100  # 1300B: chunk0 full, chunk1 partial
            await ec.write_stripe(lay, 10, 0, data)
            got = await ec.read_stripe(lay, 10, 0, len(data))
            assert got == data

            # delete one data shard, then repair it from parity
            cid = lay.data_chunk(10, 0, 0)
            chain_id = lay.shard_chain(0, 0)
            from t3fs.storage.types import RemoveChunksReq
            routing = cluster.mgmtd.state.routing()
            head = routing.chains[chain_id].head()
            await cluster.admin.call(
                routing.node_address(head.node_id), "Storage.remove_chunks",
                RemoveChunksReq(chain_id=chain_id, inode=10,
                                begin_index=0, end_index=1))
            r = await ec.repair_chunk(lay, 10, 0, 0, stripe_len=len(data))
            assert r.status.code == int(StatusCode.OK)
            got = await ec.read_stripe(lay, 10, 0, len(data))
            assert got == data
        finally:
            await cluster.stop()
    asyncio.run(body())


def test_ec_node_killed_mid_stripe_writes():
    """BASELINE config #4 fault-injection gate: a storage node dies WHILE a
    stream of stripe writes is in flight; every acked stripe must read back
    exactly, via TPU/XLA RS reconstruction where the lost node's shards are
    gone."""
    async def body():
        # compile the encode before the cluster starts: a first RS compile
        # takes seconds, and inside the stream it could starve the 0.6 s
        # heartbeats (the killer must land mid-STREAM, not mid-compile)
        codec = ECCodec()
        codec.warmup_encode(1024, k=4, m=2, batch_sizes=(1,))
        cluster = LocalCluster(num_nodes=3, replicas=1, num_chains=6,
                               heartbeat_timeout_s=0.6)
        await cluster.start()
        try:
            lay = ECLayout.create(k=4, m=2, chunk_size=1024,
                                  chains=[1, 2, 3, 4, 5, 6])
            # fast-fail writer client: single-replica chains on the dead
            # node never recover, so long retry tails would stall the test
            from t3fs.client.storage_client import (
                StorageClient, StorageClientConfig,
            )
            wsc = StorageClient(
                cluster.mgmtd_client.routing,
                config=StorageClientConfig(max_retries=3,
                                           retry_backoff_s=0.02),
                refresh_routing=cluster.mgmtd_client.refresh)
            ec_w = ECStorageClient(wsc, codec=codec)
            ec = ECStorageClient(cluster.sc)
            stripe_len = 4 * 1024
            acked: dict[int, bytes] = {}

            warm = b"w" * stripe_len
            results = await ec_w.write_stripe(lay, 19, 0, warm)
            assert all(r.status.code == int(StatusCode.OK) for r in results)
            acked[19] = warm

            async def writer():
                rng = __import__("random").Random(3)
                for i in range(30):
                    data = bytes([rng.randrange(256)]) * stripe_len
                    try:
                        results = await ec_w.write_stripe(lay, 20 + i, 0, data)
                    except Exception:
                        continue  # mid-kill failures are allowed (unacked)
                    if all(r.status.code == int(StatusCode.OK)
                           for r in results):
                        acked[20 + i] = data
                    await asyncio.sleep(0.01)

            async def killer():
                # land mid-stream: after a few acks, whatever the box's load
                for _ in range(2000):
                    if len(acked) >= 5:
                        break
                    await asyncio.sleep(0.005)
                await cluster.kill_storage_node(2)

            await asyncio.gather(writer(), killer())
            assert len(acked) >= 5, "too few acked stripes to be meaningful"

            # wait for the reshape, then every acked stripe reconstructs
            for _ in range(100):
                routing = cluster.mgmtd.state.routing()
                if all(c.chain_ver >= 2 for c in routing.chains.values()
                       if any(t.node_id == 2 for t in c.targets)):
                    break
                await asyncio.sleep(0.1)
            await cluster.mgmtd_client.refresh()
            for inode, data in acked.items():
                got = await ec.read_stripe(lay, inode, 0, stripe_len)
                assert got == data, f"stripe {inode} lost after mid-write kill"
            await wsc.close()
        finally:
            await cluster.stop()
    asyncio.run(body())


def test_ec_repair_stripe_double_loss_one_pass():
    """repair_stripe rebuilds BOTH lost shards of a stripe from one
    survivor read + one decode (the recovery-traffic shape the BIBD
    placement balances)."""
    async def body():
        cluster = LocalCluster(num_nodes=3, replicas=1, num_chains=6)
        await cluster.start()
        try:
            lay = ECLayout.create(k=4, m=2, chunk_size=1024,
                                  chains=[1, 2, 3, 4, 5, 6])
            ec = ECStorageClient(cluster.sc)
            data = bytes(range(256)) * 16  # 4096 = one full stripe
            await ec.write_stripe(lay, 30, 0, data)

            # wipe shard 1 (data) and shard 5 (parity) — a double loss
            from t3fs.storage.types import RemoveChunksReq
            routing = cluster.mgmtd.state.routing()
            for shard in (1, 5):
                chain_id = lay.shard_chain(0, shard)
                cid = (lay.data_chunk(30, 0, shard) if shard < 4
                       else lay.parity_chunk(30, 0, shard - 4))
                head = routing.chains[chain_id].head()
                await cluster.admin.call(
                    routing.node_address(head.node_id),
                    "Storage.remove_chunks",
                    RemoveChunksReq(chain_id=chain_id, inode=cid.inode,
                                    begin_index=cid.index,
                                    end_index=cid.index + 1))

            res = await ec.repair_stripe(lay, 30, 0, (1, 5),
                                         stripe_len=len(data))
            assert all(r.status.code == int(StatusCode.OK) for r in res)
            got = await ec.read_stripe(lay, 30, 0, len(data))
            assert got == data
            # the repaired parity is byte-correct, not just readable:
            # wipe a DIFFERENT data shard and decode through shard 5
            chain_id = lay.shard_chain(0, 2)
            cid = lay.data_chunk(30, 0, 2)
            head = routing.chains[chain_id].head()
            await cluster.admin.call(
                routing.node_address(head.node_id), "Storage.remove_chunks",
                RemoveChunksReq(chain_id=chain_id, inode=cid.inode,
                                begin_index=cid.index,
                                end_index=cid.index + 1))
            got = await ec.read_stripe(lay, 30, 0, len(data))
            assert got == data
        finally:
            await cluster.stop()
    asyncio.run(body())


def test_ec_repair_stripe_zero_hole_stays_absent():
    """Repairing a short stripe's zero-hole data shard must NOT materialize
    an empty chunk — absent == zeros is the decode contract write_stripe
    enforces with REMOVE."""
    async def body():
        cluster = LocalCluster(num_nodes=3, replicas=1, num_chains=6)
        await cluster.start()
        try:
            lay = ECLayout.create(k=4, m=2, chunk_size=1024,
                                  chains=[1, 2, 3, 4, 5, 6])
            ec = ECStorageClient(cluster.sc)
            data = b"z" * 1500   # shards 0,1 hold data; shards 2,3 are holes
            await ec.write_stripe(lay, 40, 0, data)

            # "repair" a lost shard set that includes hole shard 3 plus the
            # real shard 1 (the by-chain selection a recovery driver makes)
            res = await ec.repair_stripe(lay, 40, 0, (1, 3),
                                         stripe_len=len(data))
            assert all(r.status.code == int(StatusCode.OK) for r in res)
            got = await ec.read_stripe(lay, 40, 0, len(data))
            assert got == data

            # hole shard 3's chunk must not exist on its chain
            from t3fs.storage.types import QueryChunkReq
            cid = lay.data_chunk(40, 0, 3)
            chain_id = lay.shard_chain(0, 3)
            routing = cluster.mgmtd.state.routing()
            head = routing.chains[chain_id].head()
            rsp, _ = await cluster.admin.call(
                routing.node_address(head.node_id), "Storage.query_chunk",
                QueryChunkReq(chain_id=chain_id, chunk_id=cid))
            assert not rsp.found, "phantom empty chunk materialized for a " \
                                  "zero-hole shard"
        finally:
            await cluster.stop()
    asyncio.run(body())

def test_ec_codec_cpu_platform_dispatches_to_xla(monkeypatch):
    """r3 verdict weak #3: interpreted Pallas was the ONLY CpU path and
    cost a 3-4x EC regression.  Default dispatch on the CPU backend must
    be the compiled XLA bit-matmul (the oracle), with the Pallas
    interpreter reachable only behind T3FS_FORCE_PALLAS_INTERPRET."""
    import jax
    import numpy as np
    from t3fs.client.ec_codec import ECCodec
    from t3fs.ops.rs import default_rs

    if jax.devices()[0].platform != "cpu":
        pytest.skip("CPU-dispatch semantics; on-device tier ships Pallas")
    monkeypatch.delenv("T3FS_FORCE_PALLAS_INTERPRET", raising=False)

    async def body():
        codec = ECCodec()
        try:
            rng = np.random.default_rng(7)
            data = rng.integers(0, 256, (4, 2048), dtype=np.uint8)
            parity = await codec.encode(data, 4, 2)
            assert codec.last_codec == "xla-bitmatmul"
            # reconstruct data shard 1 from a survivor set, same dispatch
            rs = default_rs(4, 2)
            shards = np.concatenate([data, parity])
            present = (0, 2, 3, 4)
            got = await codec.reconstruct(shards[list(present)], present,
                                          (1,), 4, 2)
            assert codec.last_codec == "xla-bitmatmul"
            np.testing.assert_array_equal(got[0], data[1])
            assert "pallas-words" not in codec.codec_counts
        finally:
            await codec.close()
    asyncio.run(body())


async def _wipe_shards(cluster, lay, inode, stripe, shards):
    """Remove shard chunks of a stripe from their chains (lost chunks)."""
    from t3fs.storage.types import RemoveChunksReq
    routing = cluster.mgmtd.state.routing()
    for shard in shards:
        chain_id = lay.shard_chain(stripe, shard)
        cid = (lay.data_chunk(inode, stripe, shard) if shard < lay.k
               else lay.parity_chunk(inode, stripe, shard - lay.k))
        head = routing.chains[chain_id].head()
        await cluster.admin.call(
            routing.node_address(head.node_id), "Storage.remove_chunks",
            RemoveChunksReq(chain_id=chain_id, inode=cid.inode,
                            begin_index=cid.index,
                            end_index=cid.index + 1))


def test_ec_rs10p4_cauchy_roundtrip_four_data_nodes_down(monkeypatch):
    """HDFS's RS-10-4 shape on 14 nodes stores Cauchy parity and is served
    by the word kernels alone: write, repair a single and a triple loss,
    then read with the nodes of data slots 0-3 fail-stopped (every read a
    4-of-10 decode).  Stored parity is the reference's cauchy-11d."""
    monkeypatch.setenv("T3FS_FORCE_PALLAS_INTERPRET", "1")
    from perfbench import reference
    from t3fs.storage.types import ReadIO

    async def body():
        cluster = LocalCluster(num_nodes=14, replicas=1, num_chains=0,
                               ec_chains=14, heartbeat_timeout_s=0.6)
        await cluster.start()
        try:
            lay = ECLayout.create(k=10, m=4, chunk_size=4096,
                                  chains=list(range(1, 15)))
            assert lay.code_id == "cauchy-11d"
            ec = ECStorageClient(cluster.sc)
            full = bytes((7 * i + 3) % 251 for i in range(10 * 4096))
            short = full[: 3 * 4096 + 100]
            for stripe, data in ((0, full), (1, short)):
                res = await ec.write_stripe(lay, 50, stripe, data)
                assert all(r.status.code == int(StatusCode.OK) for r in res)
            # the stored parity is HDFS's, byte for byte
            ios = [ReadIO(chunk_id=lay.parity_chunk(50, 0, p),
                          chain_id=lay.shard_chain(0, 10 + p))
                   for p in range(4)]
            _res, parity = await cluster.sc.batch_read(ios)
            assert [bytes(p) for p in parity] == reference.stripe_shards(
                full, 10, 4, reference.CAUCHY)[10:]

            for lost in ((6,), (1, 8, 12)):
                await _wipe_shards(cluster, lay, 50, 0, lost)
                res = await ec.repair_stripe(lay, 50, 0, lost,
                                             stripe_len=len(full))
                assert all(r.status.code == int(StatusCode.OK) for r in res)
                assert await ec.read_stripe(lay, 50, 0, len(full)) == full

            routing = cluster.mgmtd.state.routing()
            victims = {routing.chains[lay.shard_chain(0, s)].head().node_id
                       for s in range(4)}
            for n in victims:
                await cluster.kill_storage_node(n)
            for _ in range(100):
                routing = cluster.mgmtd.state.routing()
                if not any(t.node_id in victims for c in routing.chains.values()
                           for t in c.serving()):
                    break
                await asyncio.sleep(0.1)
            await cluster.mgmtd_client.refresh()
            for stripe, data in ((0, full), (1, short)):
                assert await ec.read_stripe(lay, 50, stripe, len(data)) == data

            counts = ec.codec.codec_counts
            assert counts.get("pallas-decode-words", 0) >= 1, counts
            assert set(counts) <= {"pallas-encode-words",
                                   "pallas-decode-words",
                                   "pallas-repair-words"}, counts
            await ec.close()
        finally:
            await cluster.stop()
    asyncio.run(body())


def test_ec_rrvand_4p3_layout_roundtrip_and_refuses_other_decoders(
        monkeypatch):
    """RS(4+3) layouts keep storing rrvand-11d and decode with their own
    matrix on the word kernel; a decoder of another code is refused."""
    monkeypatch.setenv("T3FS_FORCE_PALLAS_INTERPRET", "1")
    from t3fs.ops.rs import rs_code
    from t3fs.utils.status import StatusError

    async def body():
        cluster = LocalCluster(num_nodes=3, replicas=1, num_chains=7)
        await cluster.start()
        try:
            lay = ECLayout.create(k=4, m=3, chunk_size=1024,
                                  chains=list(range(1, 8)))
            assert lay.code_id == "rrvand-11d"
            ec = ECStorageClient(cluster.sc)
            data = bytes(range(256)) * 16
            await ec.write_stripe(lay, 60, 0, data)
            await _wipe_shards(cluster, lay, 60, 0, (0, 2, 5))
            assert await ec.read_stripe(lay, 60, 0, len(data)) == data
            assert ec.codec.codec_counts.get("pallas-decode-words", 0) >= 1
            await ec.close()
        finally:
            await cluster.stop()
    asyncio.run(body())
    lay = ECLayout.create(k=4, m=3, chunk_size=1024, chains=list(range(7)))
    lay.check_code(rs_code(4, 3, "rrvand-11d"))
    with pytest.raises(StatusError) as ei:
        lay.check_code(rs_code(4, 3, "cauchy-11d"))
    assert ei.value.status.code == int(StatusCode.EC_FORMAT_MISMATCH)


def test_ec_layout_create_stamps_stored_code():
    from t3fs.ops.msr import msr_code_id
    for (k, m), code in {(10, 4): "cauchy-11d", (6, 3): "cauchy-11d",
                         (8, 2): "raid6-g2-11d", (4, 2): "raid6-g2-11d",
                         (4, 3): "rrvand-11d", (12, 4): "rrvand-11d"}.items():
        lay = ECLayout.create(k=k, m=m, chunk_size=1024,
                              chains=list(range(k + m)))
        assert lay.code_id == code and lay.rs.code_id == code
    msr = ECLayout.create(k=8, m=2, chunk_size=1 << 16,
                          chains=list(range(10)), local_scheme="pm-msr")
    assert msr.code_id == msr_code_id(8, 2)
