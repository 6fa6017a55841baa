"""End-to-end elastic recovery: fail-stop, chain reshape, writes continue,
rejoin, resync, promotion back to serving.

Reference analogs: tests/storage/service/TestStorageServiceFailStop.cc,
tests/storage/sync/TestSyncStartAndDone.cc / TestSyncForward.cc.
"""

import asyncio

import pytest

from t3fs.client.layout import FileLayout
from t3fs.mgmtd.types import PublicTargetState
from t3fs.testing.cluster import LocalCluster
from t3fs.utils.status import StatusCode


async def wait_for(predicate, timeout=10.0, interval=0.05, desc="condition"):
    loop = asyncio.get_running_loop()
    deadline = loop.time() + timeout
    while loop.time() < deadline:
        if predicate():
            return
        await asyncio.sleep(interval)
    raise AssertionError(f"timeout waiting for {desc}")


# write-pipeline matrix for the tests that push data through the chain:
# streamed runs with a small threshold so these modest payloads fragment
PIPELINE_MODES = [("off", None), ("overlap", None), ("streamed", 2048)]
PIPELINE_IDS = [m for m, _ in PIPELINE_MODES]


@pytest.mark.parametrize("write_pipeline,stream_threshold", PIPELINE_MODES,
                         ids=PIPELINE_IDS)
def test_cluster_write_read(write_pipeline, stream_threshold):
    async def body():
        cluster = LocalCluster(num_nodes=3, replicas=3,
                               write_pipeline=write_pipeline,
                               stream_threshold=stream_threshold)
        await cluster.start()
        try:
            lay = FileLayout(chunk_size=4096, chains=[1])
            data = b"mgmtd-backed" * 500
            results = await cluster.sc.write_file_range(lay, 1, 0, data)
            assert all(r.status.code == int(StatusCode.OK) for r in results)
            got, _ = await cluster.sc.read_file_range(lay, 1, 0, len(data))
            assert got == data
        finally:
            await cluster.stop()
    asyncio.run(body())


@pytest.mark.parametrize("write_pipeline,stream_threshold", PIPELINE_MODES,
                         ids=PIPELINE_IDS)
def test_failstop_reshape_write_rejoin_resync(write_pipeline,
                                              stream_threshold):
    async def body():
        cluster = LocalCluster(num_nodes=3, replicas=3,
                               heartbeat_timeout_s=0.6,
                               write_pipeline=write_pipeline,
                               stream_threshold=stream_threshold)
        await cluster.start()
        try:
            lay = FileLayout(chunk_size=4096, chains=[1])
            data1 = b"before-failure" * 300
            await cluster.sc.write_file_range(lay, 1, 0, data1)

            # fail-stop the middle chain member (node 2 / target 201)
            victim_target = cluster.target_id(2, 0)
            await cluster.kill_storage_node(2)

            # mgmtd detects silence and reshapes: victim moves to tail OFFLINE
            await wait_for(
                lambda: cluster.chain().chain_ver >= 2 and
                all(t.target_id != victim_target
                    for t in cluster.chain().serving()),
                desc="chain reshape after fail-stop")
            assert len(cluster.chain().serving()) == 2

            # writes continue on the shortened chain
            data2 = b"during-failure" * 300
            results = await cluster.sc.write_file_range(lay, 2, 0, data2)
            assert all(r.status.code == int(StatusCode.OK) for r in results), \
                [r.status for r in results]

            # node 2 returns with its old (stale) disk
            await cluster.start_storage_node(2)
            # mgmtd: OFFLINE+alive -> SYNCING; resync runs; -> SERVING
            await wait_for(
                lambda: any(t.target_id == victim_target
                            for t in cluster.chain().serving()),
                timeout=15.0, desc="victim promoted back to serving")
            assert len(cluster.chain().serving()) == 3

            # the rejoined replica must hold BOTH files' data, byte-exact
            returned = cluster.storage[2].node.targets[victim_target]
            from t3fs.storage.types import ChunkId
            for inode, data in ((1, data1), (2, data2)):
                got = b""
                for idx in range((len(data) + 4095) // 4096):
                    got += returned.engine.read(ChunkId(inode, idx))
                assert got == data, f"inode {inode} diverged on rejoined node"

            # and reads served by the whole cluster still match
            got, _ = await cluster.sc.read_file_range(lay, 2, 0, len(data2))
            assert got == data2
        finally:
            await cluster.stop()
    asyncio.run(body())


def test_rejoining_node_drops_extra_chunks():
    """Chunks deleted while a node was down are removed during resync."""
    async def body():
        cluster = LocalCluster(num_nodes=3, replicas=3,
                               heartbeat_timeout_s=0.6)
        await cluster.start()
        try:
            lay = FileLayout(chunk_size=4096, chains=[1])
            data = b"doomed" * 100
            await cluster.sc.write_file_range(lay, 5, 0, data)

            victim_target = cluster.target_id(2, 0)
            await cluster.kill_storage_node(2)
            await wait_for(lambda: len(cluster.chain().serving()) == 2,
                           desc="reshape")
            # remove the file while node 2 is down
            await cluster.sc.remove_file_chunks(lay, 5)

            await cluster.start_storage_node(2)
            await wait_for(
                lambda: any(t.target_id == victim_target
                            for t in cluster.chain().serving()),
                timeout=15.0, desc="rejoin")
            returned = cluster.storage[2].node.targets[victim_target]
            assert returned.engine.query_range(5) == [], \
                "stale chunks must be dropped by resync"
        finally:
            await cluster.stop()
    asyncio.run(body())


@pytest.mark.parametrize("write_pipeline,stream_threshold", PIPELINE_MODES,
                         ids=PIPELINE_IDS)
def test_disk_failure_offline_replace_resync(write_pipeline,
                                             stream_threshold):
    """Disk dies under a LIVE node mid-writes: write error marks the target
    OFFLINE, heartbeats propagate, mgmtd pulls it from the chain with no
    acked-write loss; operator 'replaces the disk' and the target resyncs
    back to serving (StorageOperator.cc:604-606 +
    worker/CheckWorker analogs)."""
    async def body():
        cluster = LocalCluster(num_nodes=3, replicas=3,
                               heartbeat_timeout_s=0.6,
                               write_pipeline=write_pipeline,
                               stream_threshold=stream_threshold)
        await cluster.start()
        try:
            lay = FileLayout(chunk_size=4096, chains=[1])
            data1 = b"pre-disk-failure" * 300
            await cluster.sc.write_file_range(lay, 1, 0, data1)

            # node 2's disk dies: engine.put starts raising EIO
            victim_target = cluster.target_id(2, 0)
            node2 = cluster.storage[2].node
            target = node2.targets[victim_target]
            real_put = target.engine.put

            def broken_put(*a, **kw):
                raise OSError(5, "Input/output error")
            target.engine.put = broken_put

            # writes keep succeeding (chain retries through the reshape)
            data2 = b"during-disk-failure" * 300
            results = await cluster.sc.write_file_range(lay, 2, 0, data2)
            assert all(r.status.code == int(StatusCode.OK) for r in results), \
                [str(r.status) for r in results]

            # mgmtd pulled the disk-failed target out of the serving set
            await wait_for(
                lambda: all(t.target_id != victim_target
                            for t in cluster.chain().serving()),
                desc="disk-failed target leaves the serving set")

            # operator replaces the disk: engine works again, target ONLINE
            from t3fs.mgmtd.types import LocalTargetState
            target.engine.put = real_put
            node2.local_states[victim_target] = LocalTargetState.ONLINE

            await wait_for(
                lambda: any(t.target_id == victim_target
                            for t in cluster.chain().serving()),
                timeout=15.0, desc="replaced target promoted to serving")

            # the rejoined replica holds both files byte-exact
            from t3fs.storage.types import ChunkId
            for inode, data in ((1, data1), (2, data2)):
                got = b""
                for idx in range((len(data) + 4095) // 4096):
                    got += target.engine.read(ChunkId(inode, idx))
                assert got == data, f"inode {inode} diverged after disk swap"
        finally:
            await cluster.stop()
    asyncio.run(body())


def test_partitioned_head_self_fences():
    """Reference suicide.cc at lease/2: a storage
    node partitioned from mgmtd stops acking writes BEFORE mgmtd's
    heartbeat timeout can promote a successor — a stale head can never
    keep acknowledging data the reshaped chain won't have."""
    async def body():
        cluster = LocalCluster(num_nodes=3, replicas=3,
                               heartbeat_timeout_s=1.2)
        await cluster.start()
        try:
            lay = FileLayout(chunk_size=4096, chains=[1])
            data = b"pre-partition" * 300
            results = await cluster.sc.write_file_range(lay, 1, 0, data)
            assert all(r.status.code == int(StatusCode.OK) for r in results)

            # the head of chain 1 is node 1; partition it from mgmtd by
            # killing its heartbeat loop (the node itself stays up and
            # reachable by clients — the dangerous half-partition)
            head = cluster.storage[1]
            assert head.mgmtd.lease_s > 0          # lease learned via hb
            import time as _t
            t_cut = _t.monotonic()
            head.mgmtd._hb_task.cancel()

            # the node must fence itself BEFORE the failure-detection
            # window (1.2s) elapses — i.e. before mgmtd could possibly
            # have promoted a successor.  Measuring wall time (not just
            # "eventually fenced") is what actually pins the lease/2
            # property: a regression to lease*2 would fence too late and
            # fail here.
            await wait_for(lambda: head.node.fenced(), timeout=5.0,
                           desc="head self-fence")
            fenced_after = _t.monotonic() - t_cut
            assert fenced_after < cluster.mgmtd_cfg.heartbeat_timeout_s, \
                f"fenced after {fenced_after:.2f}s — later than the " \
                f"{cluster.mgmtd_cfg.heartbeat_timeout_s}s promotion window"

            # a write sent straight at the stale head is refused
            from t3fs.storage.types import ChunkId, UpdateIO, UpdateType
            from t3fs.net.client import Client
            probe = Client()
            try:
                from t3fs.storage.service import WriteReq
                io = UpdateIO(chunk_id=ChunkId(9, 0), chain_id=1,
                              chain_ver=1, update_ver=1, offset=0,
                              length=4, chunk_size=4096,
                              update_type=UpdateType.WRITE)
                rsp, _ = await probe.call(head.server.address,
                                          "Storage.write",
                                          WriteReq(io=io), payload=b"dead")
                assert rsp.result.status.code == int(
                    StatusCode.TARGET_OFFLINE), rsp.result.status
                assert "self-fenced" in rsp.result.status.message
            finally:
                await probe.close()

            # the CLUSTER keeps accepting writes: mgmtd times the head
            # out, reshapes chain 1, and the client lands on the new head
            data2 = b"post-partition" * 300
            results2 = await cluster.sc.write_file_range(lay, 2, 0, data2)
            assert all(r.status.code == int(StatusCode.OK)
                       for r in results2)
            got, _ = await cluster.sc.read_file_range(lay, 2, 0, len(data2))
            assert got == data2
        finally:
            await cluster.stop()
    asyncio.run(body())


def test_fresh_flag_survives_lastsrv_routing_view():
    """ADVICE r4: a wiped target's LASTSRV seat in the routing view always
    predates the wipe (mgmtd never seats a known-fresh target as LASTSRV),
    so the heartbeat provider must keep reporting fresh while the view
    shows LASTSRV — clearing there raced mgmtd's fresh-LASTSRV demotion
    tick and reopened the seed-2802880 acked-write loss.  Only a SERVING
    seat (or sync_done) ends freshness, matching craq_sim's disk_fresh."""
    from t3fs.mgmtd.types import ChainInfo, ChainTargetInfo, RoutingInfo
    from t3fs.storage.server import StorageServer

    class _T:
        def __init__(self):
            self.booted_fresh = True

    class _Node:
        def __init__(self, routing):
            self._r = routing
            self.targets = {101: _T()}

        def routing(self):
            return self._r

    def view(state):
        return RoutingInfo(chains={1: ChainInfo(chain_id=1, targets=[
            ChainTargetInfo(target_id=101, node_id=1, public_state=state)])})

    srv = StorageServer.__new__(StorageServer)   # unit: bypass full init

    # stale LASTSRV view: still fresh, still reported
    srv.node = _Node(view(PublicTargetState.LASTSRV))
    assert srv._fresh_targets() == [101]
    assert srv.node.targets[101].booted_fresh

    # OFFLINE / SYNCING views: same
    for st in (PublicTargetState.OFFLINE, PublicTargetState.SYNCING):
        srv.node = _Node(view(st))
        srv.node.targets[101].booted_fresh = True
        assert srv._fresh_targets() == [101], st

    # a SERVING seat is the lineage — freshness ends, flag clears
    srv.node = _Node(view(PublicTargetState.SERVING))
    assert srv._fresh_targets() == []
    assert not srv.node.targets[101].booted_fresh
