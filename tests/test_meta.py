"""Meta store/service: per-op tests over MemKV + StorageClientInMem
(reference analogs: tests/meta/store/ops/Test{Create,Open,Rename,...}.cc)."""

import asyncio

import pytest

from t3fs.client.layout import FileLayout
from t3fs.client.meta_client import MetaClient
from t3fs.client.storage_client_inmem import StorageClientInMem
from t3fs.kv.engine import MemKVEngine
from t3fs.meta.schema import InodeType, ROOT_INODE_ID
from t3fs.meta.service import MetaServer, MetaService
from t3fs.meta.store import ChainAllocator, MetaStore
from t3fs.mgmtd.types import ChainInfo, ChainTable, ChainTargetInfo, PublicTargetState, RoutingInfo
from t3fs.net.server import Server
from t3fs.utils.status import StatusCode, StatusError


def make_routing(num_chains=3):
    r = RoutingInfo()
    for c in range(1, num_chains + 1):
        r.chains[c] = ChainInfo(c, 1, [ChainTargetInfo(c * 100, 1,
                                                       PublicTargetState.SERVING)])
    r.chain_tables[1] = ChainTable(1, list(r.chains))
    return r


@pytest.fixture(params=["mem", "wal"])
def store(request, tmp_path):
    """Per-op suite runs over BOTH KV engines (reference parameterizes meta
    tests over MemKV and FoundationDB, tests/meta/MetaTestBase.h:29-30)."""
    from t3fs.kv.wal_engine import open_kv_engine
    if request.param == "mem":
        kv = MemKVEngine()
    else:
        kv = open_kv_engine(f"wal:{tmp_path}/meta-kv?sync=os")
    routing = make_routing()
    yield MetaStore(kv, ChainAllocator(lambda: routing, default_chunk_size=4096))
    if hasattr(kv, "close"):
        kv.close()


def run(coro):
    return asyncio.run(coro)


def test_mkdirs_stat_readdir(store):
    async def body():
        await store.mkdirs("/a/b/c")
        inode = await store.stat("/a/b/c")
        assert inode.itype == InodeType.DIRECTORY
        with pytest.raises(StatusError) as ei:
            await store.mkdirs("/a/b/c")
        assert ei.value.code == StatusCode.META_EXISTS
        with pytest.raises(StatusError):
            await store.mkdirs("/x/y", recursive=False)
        entries = await store.readdir("/a")
        assert [e.name for e in entries] == ["b"]
        root = await store.readdir("/")
        assert [e.name for e in root] == ["a"]
    run(body())


def test_create_open_close(store):
    async def body():
        await store.mkdirs("/d")
        inode, sess = await store.create("/d/f", chunk_size=4096,
                                         session_client="c1")
        assert inode.itype == InodeType.FILE and sess
        assert inode.layout.chunk_size == 4096
        with pytest.raises(StatusError) as ei:
            await store.create("/d/f")
        assert ei.value.code == StatusCode.META_EXISTS
        got, sess2 = await store.open_file("/d/f", write=True,
                                           session_client="c2")
        assert got.inode_id == inode.inode_id
        sessions = await store.sessions_of(inode.inode_id)
        assert len(sessions) == 2
        await store.close_file(inode.inode_id, sess, length=100)
        await store.close_file(inode.inode_id, sess2)
        assert await store.sessions_of(inode.inode_id) == []
        assert (await store.stat("/d/f")).length == 100
    run(body())


def test_resolve_symlinks(store):
    async def body():
        await store.mkdirs("/real/dir")
        await store.create("/real/dir/file")
        await store.symlink("/link", "/real/dir")
        inode = await store.stat("/link/file")
        assert inode.itype == InodeType.FILE
        # readlink-style stat without follow
        raw = await store.stat("/link", follow=False)
        assert raw.itype == InodeType.SYMLINK and raw.symlink_target == "/real/dir"
        # loop detection
        await store.symlink("/loop1", "/loop2")
        await store.symlink("/loop2", "/loop1")
        with pytest.raises(StatusError) as ei:
            await store.stat("/loop1/x")
        assert ei.value.code == StatusCode.META_TOO_MANY_SYMLINKS
    run(body())


def test_rename_and_overwrite(store):
    async def body():
        await store.mkdirs("/src")
        await store.create("/src/a")
        await store.mkdirs("/dst")
        await store.rename("/src/a", "/dst/b")
        assert (await store.stat("/dst/b")).itype == InodeType.FILE
        with pytest.raises(StatusError):
            await store.stat("/src/a")
        # rename over existing file replaces it
        await store.create("/src/c")
        await store.rename("/src/c", "/dst/b")
        # rename dir updates parent
        await store.mkdirs("/src/sub")
        await store.rename("/src/sub", "/dst/sub")
        real = await store.get_real_path((await store.stat("/dst/sub")).inode_id)
        assert real == "/dst/sub"
    run(body())


def test_hardlink_nlink_and_remove(store):
    async def body():
        await store.create("/f1")
        inode = await store.hardlink("/f1", "/f2")
        assert inode.nlink == 2
        await store.remove("/f1")
        assert (await store.stat("/f2")).nlink == 1
        # removing the last link queues GC
        await store.remove("/f2")
        gc = await store.gc_pop()
        assert [i.inode_id for i in gc] == [inode.inode_id]
    run(body())


def test_remove_recursive(store):
    async def body():
        await store.mkdirs("/t/a/b")
        await store.create("/t/a/b/f1")
        await store.create("/t/f2")
        with pytest.raises(StatusError) as ei:
            await store.remove("/t")
        assert ei.value.code == StatusCode.META_NOT_EMPTY
        await store.remove("/t", recursive=True)
        with pytest.raises(StatusError):
            await store.stat("/t")
        gc = await store.gc_pop()
        assert len(gc) == 2  # both files queued for chunk reclamation
    run(body())


def test_meta_service_rpc_and_gc():
    """Full slice: RPC meta service + InMem storage client + GC worker."""
    async def body():
        kv = MemKVEngine()
        routing = make_routing()
        store = MetaStore(kv, ChainAllocator(lambda: routing,
                                             default_chunk_size=1024))
        sc = StorageClientInMem()
        server = Server()
        meta_server = MetaServer(store, sc, gc_period_s=0.05)
        server.add_service(meta_server.service)
        await server.start()
        await meta_server.start()
        mc = MetaClient([server.address])
        try:
            await mc.mkdirs("/data")
            inode, sess = await mc.create("/data/file", chunk_size=1024)
            # write through the storage client against the file's layout
            data = b"meta+storage" * 200
            await sc.write_file_range(inode.layout, inode.inode_id, 0, data)
            # close with unknown length -> server settles via query_last_chunk
            closed = await mc.close(inode.inode_id, sess)
            assert closed.length == len(data)
            got = await mc.stat("/data/file")
            assert got.length == len(data)
            # remove -> GC worker reclaims chunks from storage
            await mc.remove("/data/file")
            for _ in range(100):
                if await sc.query_last_chunk(inode.layout, inode.inode_id) == 0:
                    break
                await asyncio.sleep(0.05)
            assert await sc.query_last_chunk(inode.layout, inode.inode_id) == 0
            # rename + readdir through RPC
            await mc.mkdirs("/data/sub")
            await mc.rename("/data/sub", "/data/sub2")
            names = [e.name for e in await mc.readdir("/data")]
            assert names == ["sub2"]
        finally:
            await mc.close_conn()
            await meta_server.stop()
            await server.stop()
    run(body())


def test_session_prune_unblocks_gc(store):
    """A dead client's session must not pin deferred deletion forever."""
    async def body():
        inode, sess = await store.create("/pinned", session_client="dead-client")
        await store.remove("/pinned")
        assert await store.gc_pop() == []          # session pins it
        assert await store.prune_sessions(ttl_s=0.0) == 1
        gc = await store.gc_pop()
        assert [i.inode_id for i in gc] == [inode.inode_id]
    run(body())


def test_create_without_write_session_does_not_pin_gc(store):
    """mknod-style create (want_session=False) must not leave a write
    session behind: remove -> immediately GC-able."""
    async def body():
        inode, sess = await store.create("/bare", session_client="c1",
                                         request_id="r1", want_session=False)
        assert sess == ""
        await store.remove("/bare")
        gc = await store.gc_pop()
        assert [i.inode_id for i in gc] == [inode.inode_id]
    run(body())


def test_dead_client_session_prune(store):
    """Sessions of clients absent from mgmtd's registry are reaped after
    the grace period (SessionManager x MgmtdClientSessionsChecker)."""
    async def body():
        inode, _ = await store.create("/dead", session_client="ghost")
        inode2, _ = await store.create("/alive", session_client="live")
        await store.remove("/dead")
        await store.remove("/alive")
        assert await store.gc_pop() == []
        # ghost confirmed dead -> reaped; live's session survives
        pruned = await store.prune_dead_client_sessions({"ghost"})
        assert pruned == [inode.inode_id]
        gc = await store.gc_pop()
        assert [i.inode_id for i in gc] == [inode.inode_id]
    run(body())


def test_dead_client_grace_requires_continuous_absence(store):
    """One missing observation (mgmtd failover / client<->mgmtd blip) must
    NOT reap a mature session; continuous absence past the grace must."""
    from t3fs.client.storage_client_inmem import StorageClientInMem
    from t3fs.meta.service import MetaServer

    async def body():
        live: set = set()
        async def provider():
            return set(live)
        srv = MetaServer(store, StorageClientInMem(),
                         live_clients_provider=provider)
        srv.cfg.dead_client_grace_s = 0.2
        # a session far older than the grace period
        inode, _ = await store.create("/f", session_client="mount-1")
        sess = (await store.scan_sessions())[0]
        sess.created_at -= 3000   # mature, but inside the 3600s TTL
        from t3fs.utils import serde as _s
        from t3fs.meta.schema import FileSession
        async def age(txn):
            txn.set(FileSession.key(sess.inode_id, sess.session_id),
                    _s.dumps(sess))
        from t3fs.kv.engine import with_transaction
        await with_transaction(store.kv, age)
        import time as _t
        # first observation of absence: session must survive (grace)
        assert await srv._prune_sessions_once(_t.time()) == []
        # client returns: missing-tracker resets
        live.add("mount-1")
        assert await srv._prune_sessions_once(_t.time()) == []
        assert srv._client_missing_since == {}
        # absent continuously past the grace: reaped
        live.clear()
        assert await srv._prune_sessions_once(_t.time()) == []
        await asyncio.sleep(0.25)
        assert await srv._prune_sessions_once(_t.time()) == [inode.inode_id]
    run(body())


# ---- multi-server robustness (Idempotent.h, Distributor.h, lockDirectory) ----

def _mk_store(kv):
    from t3fs.meta.store import ChainAllocator, MetaStore
    from t3fs.mgmtd.types import ChainInfo, ChainTable, ChainTargetInfo, \
        PublicTargetState, RoutingInfo
    routing = RoutingInfo(version=1)
    routing.chains[1] = ChainInfo(1, 1, [
        ChainTargetInfo(101, 1, PublicTargetState.SERVING)])
    routing.chain_tables[1] = ChainTable(1, [1])
    return MetaStore(kv, ChainAllocator(lambda: routing))


def test_idempotent_create_replay():
    async def body():
        from t3fs.kv.engine import MemKVEngine
        kv = MemKVEngine()
        a, b = _mk_store(kv), _mk_store(kv)
        ino1, sess1 = await a.create("/f", session_client="c1",
                                     request_id="rq-1")
        # replay of the same request against ANOTHER meta server on the same
        # KV: returns the recorded result instead of META_EXISTS
        ino2, sess2 = await b.create("/f", session_client="c1",
                                     request_id="rq-1")
        assert ino2.inode_id == ino1.inode_id and sess2 == sess1
        # a DIFFERENT request creating the same path still conflicts
        with pytest.raises(StatusError):
            await b.create("/f", session_client="c1", request_id="rq-2")
    asyncio.run(body())


def test_idempotent_remove_and_rename_replay():
    async def body():
        from t3fs.kv.engine import MemKVEngine
        kv = MemKVEngine()
        a, b = _mk_store(kv), _mk_store(kv)
        await a.create("/f", session_client="c1", request_id="r1")
        await a.rename("/f", "/g", client_id="c1", request_id="r2")
        # replayed rename: recorded no-op success, not META_NOT_FOUND
        await b.rename("/f", "/g", client_id="c1", request_id="r2")
        await a.remove("/g", client_id="c1", request_id="r3")
        await b.remove("/g", client_id="c1", request_id="r3")  # replay ok
        with pytest.raises(StatusError):
            await b.remove("/g", client_id="c1", request_id="r4")
    asyncio.run(body())


def test_concurrent_create_stress_two_servers():
    """Hammer one KV from two meta stores: every logical request applies
    exactly once even with client-level replays."""
    async def body():
        from t3fs.kv.engine import MemKVEngine
        kv = MemKVEngine()
        a, b = _mk_store(kv), _mk_store(kv)

        async def worker(store, wid):
            results = []
            for i in range(10):
                rid = f"w{wid}-i{i}"
                ino, _ = await store.create(f"/d{wid}-{i}",
                                            session_client=f"c{wid}",
                                            request_id=rid)
                # unconditional replay (lost-response retry)
                ino2, _ = await store.create(f"/d{wid}-{i}",
                                             session_client=f"c{wid}",
                                             request_id=rid)
                assert ino2.inode_id == ino.inode_id
                results.append(ino.inode_id)
            return results
        got = await asyncio.gather(worker(a, 0), worker(b, 1), worker(a, 2))
        ids = [i for r in got for i in r]
        assert len(ids) == len(set(ids)) == 30   # no double-applies
        # prune keeps fresh records
        assert await a.prune_idem_records(ttl_s=3600) == 0
        # one record per LOGICAL request (replays don't add records)
        assert await a.prune_idem_records(ttl_s=-1) == 30
    asyncio.run(body())


def test_lock_directory_blocks_other_clients():
    async def body():
        from t3fs.kv.engine import MemKVEngine
        kv = MemKVEngine()
        st = _mk_store(kv)
        await st.mkdirs("/locked")
        await st.lock_directory("/locked", "admin-1")
        # other clients cannot mutate entries under it
        with pytest.raises(StatusError) as ei:
            await st.create("/locked/f", session_client="other")
        assert "locked" in str(ei.value)
        with pytest.raises(StatusError):
            await st.mkdirs("/locked/sub", client_id="other")
        # the lock owner can
        ino, _ = await st.create("/locked/f", session_client="admin-1")
        assert ino.inode_id
        await st.rename("/locked/f", "/locked/g", client_id="admin-1")
        with pytest.raises(StatusError):
            await st.rename("/locked/g", "/elsewhere", client_id="other")
        # removing the locked directory (or anything inside it) is itself a
        # forbidden mutation — remove -r must not bypass the lock
        with pytest.raises(StatusError):
            await st.remove("/locked", recursive=True, client_id="other")
        with pytest.raises(StatusError):
            await st.remove("/locked/g", client_id="other")
        # rename-overwrite of a locked empty dir is blocked too
        await st.mkdirs("/lockedempty")
        await st.lock_directory("/lockedempty", "admin-1")
        await st.mkdirs("/srcdir", client_id="other")
        with pytest.raises(StatusError):
            await st.rename("/srcdir", "/lockedempty", client_id="other")
        # re-lock by someone else fails until unlocked
        with pytest.raises(StatusError):
            await st.lock_directory("/locked", "admin-2")
        await st.lock_directory("/locked", "admin-1", unlock=True)
        await st.create("/locked/h", session_client="other")
    asyncio.run(body())


def test_batch_stat():
    async def body():
        from t3fs.kv.engine import MemKVEngine
        kv = MemKVEngine()
        st = _mk_store(kv)
        await st.mkdirs("/a")
        i1, _ = await st.create("/a/x")
        i2, _ = await st.create("/a/y")
        inodes = await st.batch_stat(["/a/x", "/missing", "/a/y", "/"])
        assert inodes[0].inode_id == i1.inode_id
        assert inodes[1] is None
        assert inodes[2].inode_id == i2.inode_id
        assert inodes[3].inode_id == 1
        by_id = await st.batch_stat_inodes([i2.inode_id, 999999])
        assert by_id[0].inode_id == i2.inode_id and by_id[1] is None
    asyncio.run(body())


def test_distributor_partition():
    from t3fs.meta.distributor import Distributor
    servers = [1, 2, 3]
    dists = {n: Distributor(n, lambda: servers) for n in servers}
    owners = {k: dists[1].owner(k) for k in range(200)}
    # all servers agree on ownership, every key has exactly one owner
    for n in (2, 3):
        assert all(dists[n].owner(k) == owners[k] for k in range(200))
    counts = {n: sum(1 for o in owners.values() if o == n) for n in servers}
    assert all(c > 30 for c in counts.values())   # roughly balanced
    # removal of a server redistributes only its keys
    servers2 = [1, 3]
    d2 = Distributor(1, lambda: servers2)
    moved = sum(1 for k in range(200)
                if owners[k] != d2.owner(k) and owners[k] in servers2)
    assert moved == 0   # HRW minimal disruption property
    # solo server owns everything
    solo = Distributor(7, None)
    assert solo.is_mine(12345)


def test_rename_same_inode_posix_noop():
    """rename where src and dst resolve to the same inode must be a no-op,
    never an unlink-then-relink (that destroys the last link)."""
    async def body():
        from t3fs.kv.engine import MemKVEngine
        kv = MemKVEngine()
        st = _mk_store(kv)
        ino, _ = await st.create("/f")
        # same entry
        await st.rename("/f", "/f")
        assert (await st.stat("/f")).inode_id == ino.inode_id
        # hardlink alias: rename a -> b where b is a link to the same inode
        await st.hardlink("/f", "/f2")
        await st.rename("/f", "/f2")
        assert (await st.stat("/f")).inode_id == ino.inode_id
        assert (await st.stat("/f2")).inode_id == ino.inode_id
        assert (await st.stat("/f")).nlink == 2
        # entry-level variant
        await st.rename_at(1, "f", 1, "f2")
        assert (await st.stat("/f2")).inode_id == ino.inode_id
    asyncio.run(body())


def test_unlink_at_type_discrimination():
    async def body():
        from t3fs.kv.engine import MemKVEngine
        kv = MemKVEngine()
        st = _mk_store(kv)
        await st.mkdirs("/d")
        await st.create("/f")
        with pytest.raises(StatusError):   # rmdir(file) -> NOT_DIR
            await st.unlink_at(1, "f", must_dir=True)
        with pytest.raises(StatusError):   # unlink(dir) -> IS_DIR
            await st.unlink_at(1, "d", must_dir=False)
        await st.unlink_at(1, "f", must_dir=False)
        await st.unlink_at(1, "d", must_dir=True)
    asyncio.run(body())


def test_entry_ops_reject_file_parent():
    async def body():
        from t3fs.kv.engine import MemKVEngine
        kv = MemKVEngine()
        st = _mk_store(kv)
        f, _ = await st.create("/f")
        with pytest.raises(StatusError):
            await st.create_at(f.inode_id, "child")
        with pytest.raises(StatusError):
            await st.mkdir_at(f.inode_id, "child")
    asyncio.run(body())


def test_list_inodes_and_dirents_raw_scan():
    """Raw table scans with pagination (DumpInodes/DumpDirEntries analog)."""
    async def body():
        from t3fs.kv.engine import MemKVEngine
        kv = MemKVEngine()
        st = _mk_store(kv)
        await st.mkdirs("/d")
        for i in range(5):
            await st.create(f"/d/f{i}")
        inodes = await st.list_inodes()
        ids = [i.inode_id for i in inodes]
        assert ids == sorted(ids) and len(ids) == 7  # root + dir + 5 files
        # paginate after the first page
        page1 = await st.list_inodes(limit=3)
        page2 = await st.list_inodes(after_inode=page1[-1].inode_id, limit=10)
        assert [i.inode_id for i in page1 + page2] == ids
        dents = await st.list_dirents()
        assert sorted(d.name for d in dents) == ["d", "f0", "f1", "f2",
                                                 "f3", "f4"]
    asyncio.run(body())


def test_dead_writer_length_reconciliation(store):
    """A crashed writer (no close) leaves a stale settled length; pruning its
    session triggers query_last_chunk reconciliation (design_notes.md:91-95)."""
    async def body():
        from t3fs.client.storage_client_inmem import StorageClientInMem
        from t3fs.meta.service import MetaServer

        sc = StorageClientInMem()
        server = MetaServer(store, sc, gc_period_s=3600)   # loops quiescent
        inode, _sess = await store.create("/crashed", chunk_size=1024,
                                          session_client="dead")
        data = b"x" * 3000
        await sc.write_file_range(inode.layout, inode.inode_id, 0, data)
        await store.report_write_position(inode.inode_id, 100)  # stale hint
        assert (await store.stat("/crashed")).length == 100
        pruned = await store.prune_sessions_report(ttl_s=0.0)
        assert pruned == [inode.inode_id]
        assert await server.reconcile_lengths(pruned) == 1
        assert (await store.stat("/crashed")).length == len(data)
    run(body())


def test_prune_session_rpc_client_scoped(store):
    """Client-initiated session prune (reference PruneSession RPC):
    removes only the calling client's sessions, reconciles lengths,
    refuses an empty client_id."""
    from t3fs.meta.service import MetaServer, MetaService, PruneSessionReq

    async def body():
        srv = MetaServer(store, StorageClientInMem(), gc_period_s=3600)
        svc = srv.service
        await store.mkdirs("/p")
        _, s1 = await store.create("/p/a", session_client="mount-A")
        _, s2 = await store.create("/p/b", session_client="mount-B")
        assert len(await store.scan_sessions()) == 2

        with pytest.raises(StatusError):
            await svc.prune_session(PruneSessionReq(), b"", None)

        # scoped to one session id of mount-A
        await svc.prune_session(
            PruneSessionReq(client_id="mount-A", session_ids=[s1]), b"", None)
        left = await store.scan_sessions()
        assert [s.client_id for s in left] == ["mount-B"]

        # whole-client prune doesn't touch other clients
        await svc.prune_session(
            PruneSessionReq(client_id="mount-A"), b"", None)
        assert [s.client_id for s in await store.scan_sessions()] == ["mount-B"]
        await svc.prune_session(
            PruneSessionReq(client_id="mount-B"), b"", None)
        assert await store.scan_sessions() == []
    run(body())


def test_prune_session_conn_identity_enforced(store):
    """A connection bound to client A (by its own open/create) cannot
    prune client B's sessions by naming B in the request (ADVICE r2:
    request-supplied client_id was trusted blindly)."""
    from t3fs.meta.service import MetaServer, PathReq, PruneSessionReq

    class FakeConn:
        pass

    async def body():
        srv = MetaServer(store, StorageClientInMem(), gc_period_s=3600)
        svc = srv.service
        await store.mkdirs("/p")
        conn_a = FakeConn()
        await svc.create(PathReq(path="/p/a", write=True,
                                 client_id="mount-A"), b"", conn_a)
        await svc.create(PathReq(path="/p/b", write=True,
                                 client_id="mount-B"), b"", FakeConn())
        assert len(await store.scan_sessions()) == 2

        # conn_a bound to mount-A: pruning mount-B is refused
        with pytest.raises(StatusError) as ei:
            await svc.prune_session(
                PruneSessionReq(client_id="mount-B"), b"", conn_a)
        assert ei.value.code == StatusCode.META_NO_PERMISSION
        assert len(await store.scan_sessions()) == 2

        # its own sessions prune fine
        await svc.prune_session(
            PruneSessionReq(client_id="mount-A"), b"", conn_a)
        assert [s.client_id for s in await store.scan_sessions()] \
            == ["mount-B"]

        # an unbound conn binds on first prune, then stays scoped
        conn_c = FakeConn()
        await svc.prune_session(
            PruneSessionReq(client_id="mount-C"), b"", conn_c)
        with pytest.raises(StatusError):
            await svc.prune_session(
                PruneSessionReq(client_id="mount-B"), b"", conn_c)
        assert [s.client_id for s in await store.scan_sessions()] \
            == ["mount-B"]
    run(body())


def test_hardlink_bumps_ctime_not_mtime(store):
    """POSIX link(): the linked file's mtime must NOT change (backup tools
    key on it); only ctime bumps.  Covers both the path op and link_at."""
    async def body():
        inode, _ = await store.create("/orig")
        before = await store.stat("/orig")
        await asyncio.sleep(0.01)
        linked = await store.hardlink("/orig", "/via-path")
        assert linked.mtime == before.mtime
        assert linked.ctime > before.ctime
        linked2 = await store.link_at(inode.inode_id, 1, "via-entry")
        assert linked2.mtime == before.mtime
        assert linked2.nlink == 3
    run(body())


def test_lock_directory_inode_actions():
    """The four LockDirectory actions (LockDirectory.cc:32-56):
    try_lock refuses a held lock, preempt_lock steals, unlock needs the
    holder, clear force-clears; non-dirs and bad actions are rejected."""
    async def body():
        from t3fs.kv.engine import MemKVEngine
        from t3fs.utils.status import StatusCode
        kv = MemKVEngine()
        st = _mk_store(kv)
        d = await st.mkdirs("/d")
        f, _ = await st.create("/f", session_client="x")

        # try_lock: idempotent for the owner, refused for others
        assert (await st.lock_directory_inode(
            d.inode_id, "a", "try_lock")).dir_lock == "a"
        assert (await st.lock_directory_inode(
            d.inode_id, "a", "try_lock")).dir_lock == "a"
        with pytest.raises(StatusError) as ei:
            await st.lock_directory_inode(d.inode_id, "b", "try_lock")
        assert ei.value.code == StatusCode.META_DIR_LOCKED

        # preempt_lock steals unconditionally
        assert (await st.lock_directory_inode(
            d.inode_id, "b", "preempt_lock")).dir_lock == "b"

        # unlock: wrong owner refused, holder succeeds, empty refused
        with pytest.raises(StatusError):
            await st.lock_directory_inode(d.inode_id, "a", "unlock")
        assert (await st.lock_directory_inode(
            d.inode_id, "b", "unlock")).dir_lock == ""
        with pytest.raises(StatusError):
            await st.lock_directory_inode(d.inode_id, "b", "unlock")

        # clear: force-clears any holder, idempotent on unlocked
        await st.lock_directory_inode(d.inode_id, "a", "try_lock")
        assert (await st.lock_directory_inode(
            d.inode_id, "zzz", "clear")).dir_lock == ""
        assert (await st.lock_directory_inode(
            d.inode_id, "zzz", "clear")).dir_lock == ""

        # non-directory / bad action / missing inode
        with pytest.raises(StatusError) as ei:
            await st.lock_directory_inode(f.inode_id, "a", "try_lock")
        assert ei.value.code == StatusCode.META_NOT_DIR
        with pytest.raises(StatusError) as ei:
            await st.lock_directory_inode(d.inode_id, "a", "lock")
        assert ei.value.code == StatusCode.INVALID_ARG
        with pytest.raises(StatusError):
            await st.lock_directory_inode(999999, "a", "try_lock")
    asyncio.run(body())


def test_rename_at_noreplace_and_exchange():
    """renameat2 flag semantics (rename_at flags: NOREPLACE=1,
    EXCHANGE=2): NOREPLACE gives EEXIST on any existing dst (even a
    hardlink alias of src); EXCHANGE atomically swaps entries of any
    types, updates dir parent pointers, and refuses cycles."""
    async def body():
        from t3fs.kv.engine import MemKVEngine
        from t3fs.utils.status import StatusCode
        kv = MemKVEngine()
        st = _mk_store(kv)
        root = 1
        a, _ = await st.create("/a", session_client="x")
        b, _ = await st.create("/b", session_client="x")
        d1 = await st.mkdirs("/d1")
        d2 = await st.mkdirs("/d2")
        sub = await st.mkdirs("/d1/sub")

        # NOREPLACE: free dst works, occupied dst is EEXIST
        await st.rename_at(root, "a", root, "a2", flags=1)
        with pytest.raises(StatusError) as ei:
            await st.rename_at(root, "a2", root, "b", flags=1)
        assert ei.value.code == StatusCode.META_EXISTS
        # hardlink alias of src is still EEXIST
        await st.link_at(a.inode_id, root, "alias")
        with pytest.raises(StatusError) as ei:
            await st.rename_at(root, "a2", root, "alias", flags=1)
        assert ei.value.code == StatusCode.META_EXISTS

        # EXCHANGE file<->file: contents swap places
        await st.rename_at(root, "a2", root, "b", flags=2)
        assert (await st.lookup(root, "b")).inode_id == a.inode_id
        assert (await st.lookup(root, "a2")).inode_id == b.inode_id

        # EXCHANGE dir<->file across parents: parent pointers follow
        await st.rename_at(root, "b", d2.inode_id, "sub?", flags=1)  # move
        f_in_d2 = await st.lookup(d2.inode_id, "sub?")
        await st.rename_at(d1.inode_id, "sub", d2.inode_id, "sub?",
                           flags=2)
        moved_dir = await st.lookup(d2.inode_id, "sub?")
        assert moved_dir.inode_id == sub.inode_id
        assert moved_dir.parent == d2.inode_id
        assert (await st.lookup(d1.inode_id, "sub")).inode_id \
            == f_in_d2.inode_id

        # EXCHANGE with missing dst: ENOENT (plain rename would create)
        with pytest.raises(StatusError) as ei:
            await st.rename_at(root, "d1", root, "nope", flags=2)
        assert ei.value.code == StatusCode.META_NOT_FOUND

        # EXCHANGE that would cycle (dir with entry under itself): EINVAL
        deep = await st.mkdirs("/d1/x/y")
        with pytest.raises(StatusError) as ei:
            await st.rename_at(root, "d1", (await st.stat("/d1/x")).inode_id,
                               "y", flags=2)
        assert ei.value.code == StatusCode.INVALID_ARG

        # bad flags
        with pytest.raises(StatusError) as ei:
            await st.rename_at(root, "d1", root, "z", flags=3)
        assert ei.value.code == StatusCode.INVALID_ARG
    asyncio.run(body())
