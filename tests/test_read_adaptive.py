"""Adaptive read path: latency-aware selection, hedged batch reads, and
first-k EC stripe reads (ISSUE 5).

Covers the read-path failover edges: the attempt-walk across every
selection policy, hedge-vs-primary duplicate-result races, hedge budget
exhaustion falling back to the plain path, the off-mode byte-for-byte RPC
sequence, and first-k stripe reads converging on verified bytes with 1 and
2 straggling/killed shards.
"""

import asyncio
import time

import pytest

from t3fs.client.storage_client import (
    StorageClient, StorageClientConfig, TargetSelection, _HedgeBudget,
)
from t3fs.mgmtd.types import ChainInfo, ChainTargetInfo, NodeInfo, \
    PublicTargetState, RoutingInfo
from t3fs.net.rpcstats import READ_STATS, ReadStats
from t3fs.storage.types import ChunkId, ReadIO
from t3fs.testing.fabric import StorageFabric
from t3fs.utils.status import StatusCode


def run(coro):
    return asyncio.run(coro)


@pytest.fixture(autouse=True)
def _fresh_read_stats():
    READ_STATS.clear()
    yield
    READ_STATS.clear()


# --- tracker units ---

def test_read_stats_latency_and_inflight():
    rs = ReadStats()
    assert rs.p50("a:1") == 0.0 and rs.inflight("a:1") == 0
    rs.begin("a:1")
    assert rs.inflight("a:1") == 1
    rs.end("a:1", "Storage.batch_read", 0.010, True)
    assert rs.inflight("a:1") == 0
    assert rs.p50("a:1") == pytest.approx(0.010)
    assert rs.p9x("a:1") == pytest.approx(0.010)
    # failed calls and non-read methods adjust in-flight, not latency
    rs.begin("a:1")
    rs.end("a:1", "Storage.batch_read", 9.0, False)
    rs.begin("a:1")
    rs.end("a:1", "Storage.write", 9.0, True)
    assert rs.p9x("a:1") < 1.0
    rs.hedge("a:1", fired=3, won=2, wasted=1)
    snap = rs.snapshot()["a:1"]
    assert (snap["hedge_fired"], snap["hedge_won"], snap["hedge_wasted"]) \
        == (3, 2, 1)


def test_read_stats_streaming_quantile_converges():
    rs = ReadStats()
    # steady stream at 10ms with a 100ms outlier every 20 samples: p9x
    # should sit well above p50 and below the outlier
    for i in range(600):
        rs.begin("b:1")
        rs.end("b:1", "Storage.batch_read",
               0.100 if i % 20 == 0 else 0.010, True)
    assert 0.008 < rs.p50("b:1") < 0.020
    assert rs.p50("b:1") < rs.p9x("b:1") < 0.150


def test_hedge_budget_token_bucket():
    b = _HedgeBudget(pct=0.05, burst=4)
    assert b.take(10) == 4          # starts full, capped at burst
    assert b.take(1) == 0           # empty
    b.earn(100)                     # 5 tokens earned, capped at 4
    assert b.take(10) == 4
    b.earn(10)                      # 0.5 tokens: not yet a whole hedge
    assert b.take(1) == 0
    b.earn(10)
    assert b.take(1) == 1
    zero = _HedgeBudget(pct=0.0, burst=0)
    zero.earn(10_000)
    assert zero.take(1) == 0


# --- selection policies ---

def _fake_routing(n=3):
    routing = RoutingInfo(version=1)
    targets = []
    for i in range(n):
        routing.nodes[i + 1] = NodeInfo(i + 1, f"10.0.0.{i + 1}:9000")
        targets.append(ChainTargetInfo((i + 1) * 100, i + 1,
                                       PublicTargetState.SERVING))
    routing.chains[7] = ChainInfo(chain_id=7, chain_ver=1, targets=targets)
    return routing


def test_pick_read_target_attempt_walk_all_policies(monkeypatch):
    """Every policy's attempt-walk visits the whole chain: attempt k picks
    serving[(first_pick + k) % len] — the failover contract retries rely
    on."""
    routing = _fake_routing()
    chain = routing.chains[7]
    serving = chain.serving()
    # pin the random sources so load_balance and adaptive tie-breaks are
    # deterministic for the walk assertion
    import random as _random
    monkeypatch.setattr(_random, "randrange", lambda n: 0)
    # seed ADAPTIVE scores: node 2 idle+fast, others loaded — it must win
    READ_STATS.begin("10.0.0.1:9000")
    for addr, lat in (("10.0.0.1:9000", 0.050), ("10.0.0.2:9000", 0.001),
                      ("10.0.0.3:9000", 0.050)):
        READ_STATS.begin(addr)
        READ_STATS.end(addr, "Storage.batch_read", lat, True)
    first = {TargetSelection.HEAD_TARGET: 0,
             TargetSelection.TAIL_TARGET: 2,
             TargetSelection.LOAD_BALANCE: 0,   # randrange pinned to 0
             TargetSelection.ADAPTIVE: 1}       # lowest score
    for sel, want0 in first.items():
        sc = StorageClient(lambda: routing,
                           config=StorageClientConfig(read_selection=sel))
        for attempt in range(5):
            pick = sc._pick_read_target(chain, attempt, routing)
            assert pick is serving[(want0 + attempt) % 3], (sel, attempt)
    # round-robin advances per CALL, then walks per attempt
    sc = StorageClient(lambda: routing, config=StorageClientConfig(
        read_selection=TargetSelection.ROUND_ROBIN))
    assert sc._pick_read_target(chain, 0, routing) is serving[0]
    assert sc._pick_read_target(chain, 0, routing) is serving[1]
    assert sc._pick_read_target(chain, 1, routing) is serving[0]


def test_pick_hedge_target_excludes_primary():
    routing = _fake_routing()
    chain = routing.chains[7]
    sc = StorageClient(lambda: routing)
    alt = sc._pick_hedge_target(chain, routing, "10.0.0.1:9000")
    assert routing.node_address(alt.node_id) != "10.0.0.1:9000"
    single = _fake_routing(n=1)
    assert sc._pick_hedge_target(single.chains[7], single,
                                 "10.0.0.1:9000") is None


# --- hedged batch reads over the fabric ---

def _head_cfg(**kw) -> StorageClientConfig:
    """Deterministic primary (head) so the injected straggler is always
    the first pick."""
    return StorageClientConfig(
        read_selection=TargetSelection.HEAD_TARGET, **kw)


def test_read_hedging_off_is_plain_rpc_sequence():
    """read_hedging=off must issue byte-for-byte today's RPC sequence —
    exactly one Storage.batch_read to the primary per call, no hedge RPCs,
    even with a straggler present."""
    async def body():
        fab = StorageFabric(num_nodes=3, replicas=3)
        await fab.start()
        try:
            sc = StorageClient(lambda: fab.routing, client=fab.client,
                               config=_head_cfg(read_hedging="off",
                                                hedge_delay_floor_s=0.001))
            data = b"x" * 4096
            await sc.write_chunk(fab.chain_id, ChunkId(5, 0), 0, data, 4096)
            fab.nodes[0].read_delay_s = 0.05   # head lags; off must wait
            seen = []
            orig = fab.client.call

            async def spy(addr, method, req=None, **kw):
                if method == "Storage.batch_read":
                    seen.append(addr)
                return await orig(addr, method, req, **kw)
            fab.client.call = spy
            stats = {}
            for _ in range(3):
                res, payloads = await sc.batch_read(
                    [ReadIO(chunk_id=ChunkId(5, 0), chain_id=fab.chain_id)],
                    stats=stats)
                assert res[0].status.code == int(StatusCode.OK)
                assert payloads[0] == data
            assert seen == [fab.head_address()] * 3
            assert stats == {"hedge_fired": 0, "hedge_won": 0,
                             "hedge_wasted": 0}
        finally:
            fab.nodes[0].read_delay_s = 0.0
            await fab.stop()
    run(body())


def test_hedged_read_beats_straggling_primary():
    async def body():
        fab = StorageFabric(num_nodes=3, replicas=3)
        await fab.start()
        try:
            sc = StorageClient(
                lambda: fab.routing, client=fab.client,
                config=_head_cfg(read_hedging="on",
                                 hedge_delay_floor_s=0.01,
                                 hedge_delay_cap_s=0.05))
            data = b"h" * 8192
            for i in range(4):
                await sc.write_chunk(fab.chain_id, ChunkId(6, i), 0, data,
                                     8192)
            fab.nodes[0].read_delay_s = 0.5    # head = slow primary
            stats = {}
            t0 = time.perf_counter()
            res, payloads = await sc.batch_read(
                [ReadIO(chunk_id=ChunkId(6, i), chain_id=fab.chain_id)
                 for i in range(4)], stats=stats)
            elapsed = time.perf_counter() - t0
            assert all(r.status.code == int(StatusCode.OK) for r in res)
            assert all(p == data for p in payloads)
            assert stats["hedge_fired"] >= 1
            assert stats["hedge_won"] >= 1
            assert elapsed < 0.4, "hedge should beat the 0.5s straggler"
            snap = READ_STATS.snapshot()[fab.head_address()]
            assert snap["hedge_fired"] == stats["hedge_fired"]
        finally:
            fab.nodes[0].read_delay_s = 0.0
            await fab.stop()
    run(body())


def test_hedge_vs_primary_duplicate_result_race():
    """Primary nearly ties the hedge: both responses arrive; first OK wins
    and the duplicate is discarded — payloads stay correct every round."""
    async def body():
        fab = StorageFabric(num_nodes=3, replicas=3)
        await fab.start()
        try:
            sc = StorageClient(
                lambda: fab.routing, client=fab.client,
                config=_head_cfg(read_hedging="on",
                                 hedge_delay_floor_s=0.002,
                                 hedge_delay_cap_s=0.004,
                                 hedge_budget_burst=64))
            data = b"r" * 2048
            await sc.write_chunk(fab.chain_id, ChunkId(7, 0), 0, data, 2048)
            fab.nodes[0].read_delay_s = 0.005  # ~= the hedge delay: races
            for _ in range(20):
                res, payloads = await sc.batch_read(
                    [ReadIO(chunk_id=ChunkId(7, 0), chain_id=fab.chain_id)])
                assert res[0].status.code == int(StatusCode.OK)
                assert payloads[0] == data
        finally:
            fab.nodes[0].read_delay_s = 0.0
            await fab.stop()
    run(body())


def test_hedge_budget_exhaustion_falls_back_to_plain_wait():
    async def body():
        fab = StorageFabric(num_nodes=3, replicas=3)
        await fab.start()
        try:
            sc = StorageClient(
                lambda: fab.routing, client=fab.client,
                config=_head_cfg(read_hedging="on",
                                 hedge_delay_floor_s=0.005,
                                 hedge_budget_pct=0.0,
                                 hedge_budget_burst=0))
            data = b"b" * 1024
            await sc.write_chunk(fab.chain_id, ChunkId(8, 0), 0, data, 1024)
            fab.nodes[0].read_delay_s = 0.08
            stats = {}
            t0 = time.perf_counter()
            res, payloads = await sc.batch_read(
                [ReadIO(chunk_id=ChunkId(8, 0), chain_id=fab.chain_id)],
                stats=stats)
            elapsed = time.perf_counter() - t0
            assert res[0].status.code == int(StatusCode.OK)
            assert payloads[0] == data
            assert stats["hedge_fired"] == 0
            assert elapsed >= 0.07, "no budget: must wait out the primary"
        finally:
            fab.nodes[0].read_delay_s = 0.0
            await fab.stop()
    run(body())


def test_batch_read_does_not_restamp_callers_readios():
    """The satellite fix: a refresh-capable client stamps chain_ver on
    PRIVATE clones, so a caller-reused ReadIO list never carries a stale
    stamped version into its next call."""
    async def body():
        fab = StorageFabric(num_nodes=2, replicas=2)
        await fab.start()
        try:
            sc = StorageClient(lambda: fab.routing, client=fab.client,
                               refresh_routing=lambda: None)
            data = b"c" * 512
            await sc.write_chunk(fab.chain_id, ChunkId(9, 0), 0, data, 512)
            ios = [ReadIO(chunk_id=ChunkId(9, 0), chain_id=fab.chain_id)]
            res, payloads = await sc.batch_read(ios)
            assert payloads[0] == data
            assert ios[0].chain_ver == 0, \
                "caller's ReadIO must not be restamped in place"
            # a caller-versioned IO is respected (and left alone)
            ios[0].chain_ver = fab.chain().chain_ver
            res, _ = await sc.batch_read(ios)
            assert res[0].status.code == int(StatusCode.OK)
            assert ios[0].chain_ver == fab.chain().chain_ver
        finally:
            await fab.stop()
    run(body())


# --- first-k EC stripe reads ---

def _ec_env():
    """6 chains x 1 replica, one chain per node: every shard of an
    EC(4+2) stripe has an independently delayable/killable home."""
    return StorageFabric(num_nodes=6, replicas=1, num_chains=6)


def _node_of_chain(fab: StorageFabric, chain_id: int) -> int:
    """Index into fab.nodes of the chain's single serving node."""
    return fab.routing.chains[chain_id].targets[0].node_id - 1


def test_first_k_stripe_read_with_straggling_shard():
    """Acceptance: a data shard delayed INDEFINITELY (30s >> any timeout)
    must not stall read_stripe — parity beats the straggler through the
    fused decode, returning CRC-verified bytes fast."""
    from t3fs.client.ec_client import ECLayout, ECStorageClient
    from t3fs.ops.codec import crc32c

    async def body():
        fab = _ec_env()
        await fab.start()
        try:
            sc = StorageClient(lambda: fab.routing, client=fab.client)
            lay = ECLayout.create(k=4, m=2, chunk_size=2048,
                                  chains=fab.chain_ids)
            ec = ECStorageClient(sc, use_device_codec=False)
            data = bytes((7 * i) % 256 for i in range(4 * 2048))
            res = await ec.write_stripe(lay, 31, 0, data)
            assert all(r.status.code == int(StatusCode.OK) for r in res)
            lagger = _node_of_chain(fab, lay.shard_chain(0, 0))
            fab.nodes[lagger].read_delay_s = 30.0
            t0 = time.perf_counter()
            got, crcs = await ec.read_stripe_with_crcs(lay, 31, 0, len(data))
            elapsed = time.perf_counter() - t0
            assert got == data
            assert elapsed < 10.0, "first-k must not wait out the straggler"
            # every shard's CRC is reported: stored CRC for direct reads;
            # the oracle codec has no fused CRC, so shard 0 reports None
            for j in range(1, 4):
                assert crcs[j] == crc32c(data[j * 2048:(j + 1) * 2048])
        finally:
            for node in fab.nodes:
                node.read_delay_s = 0.0
            await fab.stop()
    run(body())


def test_first_k_stripe_read_with_two_straggling_shards():
    from t3fs.client.ec_client import ECLayout, ECStorageClient

    async def body():
        fab = _ec_env()
        await fab.start()
        try:
            sc = StorageClient(lambda: fab.routing, client=fab.client)
            lay = ECLayout.create(k=4, m=2, chunk_size=1024,
                                  chains=fab.chain_ids)
            ec = ECStorageClient(sc, use_device_codec=False)
            data = bytes((3 * i + 1) % 256 for i in range(4 * 1024))
            await ec.write_stripe(lay, 32, 0, data)
            for j in (1, 2):   # m=2 covers exactly two erasures
                fab.nodes[_node_of_chain(fab, lay.shard_chain(0, j))] \
                    .read_delay_s = 30.0
            t0 = time.perf_counter()
            got = await ec.read_stripe(lay, 32, 0, len(data))
            assert got == data
            assert time.perf_counter() - t0 < 10.0
        finally:
            for node in fab.nodes:
                node.read_delay_s = 0.0
            await fab.stop()
    run(body())


def test_first_k_stripe_read_with_killed_shards():
    """Two shard homes hard-stopped (connects fail, routing unchanged):
    the fan-out collects the surviving k and decodes — no patient-retry
    stall, no TARGET_OFFLINE."""
    from t3fs.client.ec_client import ECLayout, ECStorageClient

    async def body():
        fab = _ec_env()
        await fab.start()
        try:
            sc = StorageClient(lambda: fab.routing, client=fab.client)
            lay = ECLayout.create(k=4, m=2, chunk_size=1024,
                                  chains=fab.chain_ids)
            ec = ECStorageClient(sc, use_device_codec=False,
                                 fast_read_retries=1)
            data = bytes((5 * i + 2) % 256 for i in range(4 * 1024))
            await ec.write_stripe(lay, 33, 0, data)
            for j in (0, 3):
                await fab.servers[
                    _node_of_chain(fab, lay.shard_chain(0, j))].stop()
            got = await ec.read_stripe(lay, 33, 0, len(data))
            assert got == data
        finally:
            await fab.stop()
    run(body())


def test_stripe_read_reports_crc_of_shard_recovered_by_patient_wave():
    """m shard homes dead AND a live data shard's fast read failing: the
    fan-out drains short of k, the patient wave reads that shard back
    directly, and the read reports its stored CRC (not None) beside the
    device CRCs of the decoded shards."""
    from t3fs.client.ec_client import ECLayout, ECStorageClient
    from t3fs.ops.codec import crc32c
    from t3fs.utils.status import make_error

    async def body():
        fab = _ec_env()
        await fab.start()
        ec = None
        try:
            sc = StorageClient(lambda: fab.routing, client=fab.client)
            sc.cfg.max_retries = 2       # the patient wave's dead-node reads
            lay = ECLayout.create(k=4, m=2, chunk_size=1024,
                                  chains=fab.chain_ids)
            ec = ECStorageClient(sc, fast_read_retries=1)
            data = bytes((11 * i + 3) % 256 for i in range(4 * 1024))
            res = await ec.write_stripe(lay, 34, 0, data)
            assert all(r.status.code == int(StatusCode.OK) for r in res)
            for j in (0, 3):
                await fab.servers[
                    _node_of_chain(fab, lay.shard_chain(0, j))].stop()
            flaky = lay.shard_chain(0, 1)
            fast_read = ec._fast.batch_read

            async def failing_fast_read(ios):
                if ios[0].chain_id == flaky:
                    raise make_error(StatusCode.TIMEOUT, "fast read lost")
                return await fast_read(ios)
            ec._fast.batch_read = failing_fast_read
            got, crcs = await ec.read_stripe_with_crcs(lay, 34, 0, len(data))
            assert got == data
            assert crcs == [crc32c(data[j * 1024:(j + 1) * 1024])
                            for j in range(4)]
        finally:
            if ec is not None:
                await ec.close()
            await fab.stop()
    run(body())


def test_first_k_short_stripe_holes_count_free():
    """A short stripe's zero holes need no IO: with one live data shard
    straggling, holes + parity still reach k without reading them."""
    from t3fs.client.ec_client import ECLayout, ECStorageClient

    async def body():
        fab = _ec_env()
        await fab.start()
        try:
            sc = StorageClient(lambda: fab.routing, client=fab.client)
            lay = ECLayout.create(k=4, m=2, chunk_size=1024,
                                  chains=fab.chain_ids)
            ec = ECStorageClient(sc, use_device_codec=False)
            data = b"z" * 1500   # shards 0-1 live, 2-3 are zero holes
            await ec.write_stripe(lay, 34, 0, data)
            fab.nodes[_node_of_chain(fab, lay.shard_chain(0, 1))] \
                .read_delay_s = 30.0
            t0 = time.perf_counter()
            got = await ec.read_stripe(lay, 34, 0, len(data))
            assert got == data
            assert time.perf_counter() - t0 < 10.0
        finally:
            for node in fab.nodes:
                node.read_delay_s = 0.0
            await fab.stop()
    run(body())


# --- kvcache rides the hedged path ---

def test_kvcache_get_many_hedges_and_reports_stats():
    from t3fs.lib.kvcache import KVCacheConfig, KVCacheStore

    async def body():
        fab = StorageFabric(num_nodes=3, replicas=3)
        await fab.start()
        try:
            # client-wide hedging off: the kvcache view opts in on its own
            sc = StorageClient(
                lambda: fab.routing, client=fab.client,
                config=_head_cfg(read_hedging="off",
                                 hedge_delay_floor_s=0.01,
                                 hedge_delay_cap_s=0.05))
            kv = KVCacheStore(sc, [fab.chain_id],
                              config=KVCacheConfig(read_hedging="on"))
            assert kv._hedging == "on"
            assert sc.cfg.read_hedging == "off"
            keys = [f"k{i}".encode() for i in range(6)]
            for key in keys:
                await kv.put(key, b"v:" + key)
            fab.nodes[0].read_delay_s = 0.2
            stats = {}
            t0 = time.perf_counter()
            values = await kv.get_many(keys, stats=stats)
            elapsed = time.perf_counter() - t0
            assert values == [b"v:" + k for k in keys]
            assert stats["hedge_fired"] >= 1
            assert stats["hedge_won"] >= 1
            assert elapsed < 0.18, "hedges should beat the straggler"
            # inherit mode passes no per-call override
            kv2 = KVCacheStore(sc, [fab.chain_id], namespace="n2",
                               config=KVCacheConfig(read_hedging="inherit"))
            assert kv2._hedging is None
        finally:
            fab.nodes[0].read_delay_s = 0.0
            await fab.stop()
    run(body())
