"""POSIX permission enforcement in the meta store (perm/uid/gid were once
stored but META_NO_PERMISSION had no raisers).

Reference analog: per-op inode.acl.checkPermission
(src/meta/store/ops/SetAttr.h:76,99) with UserInfo on every RPC.
"""

import asyncio

import pytest

from t3fs.client.storage_client_inmem import StorageClientInMem
from t3fs.kv.engine import MemKVEngine
from t3fs.meta.acl import UserInfo
from t3fs.meta.store import ChainAllocator, MetaStore
from t3fs.mgmtd.types import (
    ChainInfo, ChainTable, ChainTargetInfo, PublicTargetState, RoutingInfo,
)
from t3fs.utils.status import StatusCode, StatusError


def run(coro):
    return asyncio.run(coro)


@pytest.fixture
def store():
    routing = RoutingInfo(version=1)
    routing.chains[1] = ChainInfo(1, 1, [
        ChainTargetInfo(101, 1, PublicTargetState.SERVING)])
    routing.chain_tables[1] = ChainTable(1, [1])
    kv = MemKVEngine()
    return MetaStore(kv, ChainAllocator(lambda: routing,
                                        default_chunk_size=4096))


ROOT = UserInfo(uid=0)
ALICE = UserInfo(uid=1000, gids=[1000])
BOB = UserInfo(uid=1001, gids=[1001])
CAROL = UserInfo(uid=1002, gids=[1000, 1002])   # shares alice's group


def denied(excinfo):
    assert excinfo.value.code == StatusCode.META_NO_PERMISSION, \
        excinfo.value



async def mk_owned(store, path, owner: UserInfo, perm: int):
    """Trusted scaffolding: mkdir + chown, like an admin provisioning a
    user's home directory."""
    await store.mkdirs(path, perm=perm)
    await store.set_attr(path, uid=owner.uid,
                         gid=owner.gids[0] if owner.gids else 0)


def test_open_modes_enforced(store):
    async def body():
        await store.mkdirs("/home", perm=0o777)
        await store.create("/home/secret", perm=0o600, user=ALICE)
        # owner reads and writes
        await store.open_file("/home/secret", user=ALICE)
        await store.open_file("/home/secret", write=True,
                              session_client="c", user=ALICE)
        # others: even O_RDONLY is EACCES on 0o600
        with pytest.raises(StatusError) as ei:
            await store.open_file("/home/secret", user=BOB)
        denied(ei)
        with pytest.raises(StatusError) as ei:
            await store.open_file("/home/secret", write=True,
                                  session_client="c", user=BOB)
        denied(ei)
        # root bypasses
        await store.open_file("/home/secret", write=True,
                              session_client="c", user=ROOT)

        # 0o000: NOBODY but root opens, not even the owner
        await store.create("/home/locked", perm=0o000, user=ALICE)
        with pytest.raises(StatusError) as ei:
            await store.open_file("/home/locked", user=ALICE)
        denied(ei)
        await store.open_file("/home/locked", user=ROOT)
    run(body())


def test_group_bits(store):
    async def body():
        await store.mkdirs("/g", perm=0o777)
        await store.create("/g/shared", perm=0o640, user=ALICE)
        # carol shares gid 1000 -> group R applies; write still denied
        await store.open_file("/g/shared", user=CAROL)
        with pytest.raises(StatusError) as ei:
            await store.open_file("/g/shared", write=True,
                                  session_client="c", user=CAROL)
        denied(ei)
        # bob is other: 0 bits
        with pytest.raises(StatusError) as ei:
            await store.open_file("/g/shared", user=BOB)
        denied(ei)
    run(body())


def test_traversal_x_required(store):
    async def body():
        await mk_owned(store, "/private", ALICE, 0o700)
        await store.create("/private/f", perm=0o644, user=ALICE)
        # bob cannot even stat THROUGH the 0o700 directory
        with pytest.raises(StatusError) as ei:
            await store.stat("/private/f", user=BOB)
        denied(ei)
        with pytest.raises(StatusError) as ei:
            await store.open_file("/private/f", user=BOB)
        denied(ei)
        # alice can
        assert (await store.stat("/private/f", user=ALICE)).perm == 0o644
    run(body())


def test_create_unlink_need_parent_write(store):
    async def body():
        await mk_owned(store, "/ro", ALICE, 0o755)
        # bob: no W on the parent
        with pytest.raises(StatusError) as ei:
            await store.create("/ro/f", user=BOB)
        denied(ei)
        with pytest.raises(StatusError) as ei:
            await store.mkdirs("/ro/d", user=BOB)
        denied(ei)
        with pytest.raises(StatusError) as ei:
            await store.symlink("/ro/s", "/tmp", user=BOB)
        denied(ei)
        # alice creates; bob cannot remove from alice's dir
        await store.create("/ro/f", user=ALICE)
        with pytest.raises(StatusError) as ei:
            await store.remove("/ro/f", user=BOB)
        denied(ei)
        await store.remove("/ro/f", user=ALICE)
    run(body())


def test_readdir_needs_read(store):
    async def body():
        # x-only directory: traversal fine, listing denied
        await mk_owned(store, "/lst", ALICE, 0o711)
        await store.create("/lst/f", perm=0o644, user=ALICE)
        with pytest.raises(StatusError) as ei:
            await store.readdir("/lst", user=BOB)
        denied(ei)
        # ...but direct access through it works (mode 0o711 semantics)
        await store.open_file("/lst/f", user=BOB)
        assert len(await store.readdir("/lst", user=ALICE)) == 1
    run(body())


def test_chmod_chown_rules(store):
    async def body():
        await store.mkdirs("/o", perm=0o777)
        inode, _ = await store.create("/o/f", perm=0o644, user=ALICE)
        # chmod: owner yes, stranger no
        await store.set_attr("/o/f", perm=0o600, user=ALICE)
        with pytest.raises(StatusError) as ei:
            await store.set_attr("/o/f", perm=0o777, user=BOB)
        denied(ei)
        # chown uid: even the owner may not give the file away
        with pytest.raises(StatusError) as ei:
            await store.set_attr("/o/f", uid=BOB.uid, user=ALICE)
        denied(ei)
        await store.set_attr("/o/f", uid=BOB.uid, user=ROOT)
        # chgrp: owner only into own groups
        await store.set_attr("/o/f", uid=ALICE.uid, user=ROOT)
        await store.set_attr("/o/f", gid=1000, user=ALICE)
        with pytest.raises(StatusError) as ei:
            await store.set_attr("/o/f", gid=1001, user=ALICE)
        denied(ei)
        # utimes (inode-level): non-owner without W denied
        await store.set_attr("/o/f", perm=0o600, user=ALICE)
        ino = await store.stat("/o/f")
        with pytest.raises(StatusError) as ei:
            await store.set_attr_inode(ino.inode_id, mtime=1.0, user=BOB)
        denied(ei)
        await store.set_attr_inode(ino.inode_id, mtime=1.0, user=ALICE)
    run(body())


def test_sticky_bit_restricted_deletion(store):
    async def body():
        await store.mkdirs("/tmpdir", perm=0o1777)   # like /tmp
        await store.create("/tmpdir/a", perm=0o644, user=ALICE)
        await store.create("/tmpdir/b", perm=0o644, user=BOB)
        # bob may not delete alice's entry despite W on the dir
        with pytest.raises(StatusError) as ei:
            await store.remove("/tmpdir/a", user=BOB)
        denied(ei)
        # nor rename it away
        with pytest.raises(StatusError) as ei:
            await store.rename("/tmpdir/a", "/tmpdir/stolen", user=BOB)
        denied(ei)
        # owner and root may
        await store.remove("/tmpdir/a", user=ALICE)
        await store.remove("/tmpdir/b", user=ROOT)
    run(body())


def test_rename_needs_both_parents_writable(store):
    async def body():
        await store.mkdirs("/src", perm=0o777)
        await mk_owned(store, "/dst", ALICE, 0o755)
        await store.create("/src/f", perm=0o644, user=BOB)
        # bob: W on /src ok, but /dst is alice's 0o755
        with pytest.raises(StatusError) as ei:
            await store.rename("/src/f", "/dst/f", user=BOB)
        denied(ei)
        await store.rename("/src/f", "/dst/f", user=ALICE)
    run(body())


def test_entry_level_ops_enforced(store):
    async def body():
        await mk_owned(store, "/e", ALICE, 0o700)
        d = await store.stat("/e")
        inode, _ = await store.create("/e/f", perm=0o600, user=ALICE)
        # lookup through 0o700 denied for bob
        with pytest.raises(StatusError) as ei:
            await store.lookup(d.inode_id, "f", user=BOB)
        denied(ei)
        with pytest.raises(StatusError) as ei:
            await store.readdir_inode(d.inode_id, user=BOB)
        denied(ei)
        with pytest.raises(StatusError) as ei:
            await store.create_at(d.inode_id, "g", user=BOB)
        denied(ei)
        with pytest.raises(StatusError) as ei:
            await store.open_inode(inode.inode_id, user=BOB)
        denied(ei)
        with pytest.raises(StatusError) as ei:
            await store.unlink_at(d.inode_id, "f", user=BOB)
        denied(ei)
        # alice passes everywhere
        await store.lookup(d.inode_id, "f", user=ALICE)
        await store.open_inode(inode.inode_id, user=ALICE)
        await store.create_at(d.inode_id, "g", user=ALICE)
        await store.unlink_at(d.inode_id, "g", user=ALICE)
    run(body())


def test_new_inode_ownership(store):
    async def body():
        await store.mkdirs("/own", perm=0o777)
        inode, _ = await store.create("/own/f", user=ALICE)
        assert inode.uid == ALICE.uid and inode.gid == 1000
        d = await store.mkdirs("/own/d", user=CAROL)
        assert d.uid == CAROL.uid and d.gid == 1000   # first gid
        # trusted caller (no user): root-owned, as before
        inode2, _ = await store.create("/own/g")
        assert inode2.uid == 0 and inode2.gid == 0
    run(body())


def test_batch_stat_masks_denied_paths(store):
    async def body():
        await store.mkdirs("/pub", perm=0o777)
        await mk_owned(store, "/priv", ALICE, 0o700)
        await store.create("/pub/a", user=ALICE)
        await store.create("/priv/b", user=ALICE)
        out = await store.batch_stat(["/pub/a", "/priv/b"], user=BOB)
        assert out[0] is not None and out[1] is None
    run(body())


def test_admin_identity_bypasses(store):
    async def body():
        admin = UserInfo(uid=5000, is_admin=True)
        await mk_owned(store, "/adm", ALICE, 0o700)
        await store.create("/adm/f", perm=0o600, user=ALICE)
        # is_admin acts as root regardless of uid
        await store.open_file("/adm/f", user=admin)
        await store.set_attr("/adm/f", perm=0o640, user=admin)
    run(body())


def test_token_authenticator_blocks_forged_identity(store):
    """With an authenticator, the REGISTRY record (not the claim) is what
    the checks see: a forged uid/gids in the request cannot escalate, and
    a bad token is refused outright (reference: token-verified UserInfo
    on every RPC)."""
    from t3fs.client.storage_client_inmem import StorageClientInMem
    from t3fs.kv.engine import MemKVEngine
    from t3fs.meta.auth import make_token_authenticator
    from t3fs.meta.service import MetaServer, PathReq

    async def body():
        # registry: alice uid 1000 with a token
        reg_kv = MemKVEngine()
        from t3fs.core.service import _user_key
        from t3fs.kv.engine import with_transaction
        from t3fs.utils import serde as _serde
        alice = UserInfo(uid=1000, token="tok-alice", gids=[1000])

        async def seed(txn):
            txn.set(_user_key(1000), _serde.dumps(alice))
        await with_transaction(reg_kv, seed)

        srv = MetaServer(store, StorageClientInMem(), gc_period_s=3600)
        svc = srv.service
        svc.authenticator = make_token_authenticator(reg_kv)

        await store.mkdirs("/home", perm=0o777)
        await store.create("/home/alice.txt", perm=0o600, user=ALICE)

        # good token: opens her own 0o600 file
        ok = UserInfo(uid=1000, token="tok-alice")
        rsp, _ = await svc.open(PathReq(path="/home/alice.txt", user=ok),
                                b"", None)
        assert rsp.inode is not None

        # bad token: refused before any file check
        with pytest.raises(StatusError) as ei:
            await svc.open(PathReq(
                path="/home/alice.txt",
                user=UserInfo(uid=1000, token="wrong")), b"", None)
        denied(ei)

        # unknown uid: refused
        with pytest.raises(StatusError) as ei:
            await svc.open(PathReq(
                path="/home/alice.txt",
                user=UserInfo(uid=4242, token="x")), b"", None)
        denied(ei)

        # forged claim: right token for uid 1000 but the CLAIM says
        # is_admin/gids — the registry record wins, so bob's 0o600 file
        # (uid 1001) stays closed
        await store.create("/home/bob.txt", perm=0o600,
                           user=UserInfo(uid=1001, gids=[1001]))
        forged = UserInfo(uid=1000, token="tok-alice", is_admin=True,
                          gids=[1001])
        with pytest.raises(StatusError) as ei:
            await svc.open(PathReq(path="/home/bob.txt", user=forged),
                           b"", None)
        denied(ei)
    run(body())


def test_authenticated_deployment_requires_identity(store):
    """Code-review r3: with an authenticator configured, OMITTING the
    user field must be a refusal, not a trusted-caller bypass."""
    from t3fs.client.storage_client_inmem import StorageClientInMem
    from t3fs.kv.engine import MemKVEngine
    from t3fs.meta.auth import make_token_authenticator
    from t3fs.meta.service import MetaServer, PathReq

    async def body():
        srv = MetaServer(store, StorageClientInMem(), gc_period_s=3600)
        svc = srv.service
        svc.authenticator = make_token_authenticator(MemKVEngine())
        await store.mkdirs("/home", perm=0o777)
        await store.create("/home/f", perm=0o600, user=ALICE)
        with pytest.raises(StatusError) as ei:
            await svc.open(PathReq(path="/home/f"), b"", None)   # no user
        denied(ei)
    run(body())


def test_open_rdwr_needs_read_and_write(store):
    """Code-review r3: O_RDWR on a write-only (0o200) file must be
    refused — W alone is not enough when the handle can read."""
    async def body():
        await store.mkdirs("/wo", perm=0o777)
        await store.create("/wo/log", perm=0o200, user=ALICE)
        await store.set_attr("/wo/log", gid=1000, user=ALICE)
        # owner: O_WRONLY fine, O_RDWR and O_RDONLY denied (no R bit)
        await store.open_file("/wo/log", write=True, session_client="c",
                              user=ALICE)
        with pytest.raises(StatusError) as ei:
            await store.open_file("/wo/log", write=True, session_client="c",
                                  user=ALICE, rdwr=True)
        denied(ei)
        with pytest.raises(StatusError) as ei:
            await store.open_file("/wo/log", user=ALICE)
        denied(ei)
        # same by inode
        ino = await store.stat("/wo/log")
        with pytest.raises(StatusError) as ei:
            await store.open_inode(ino.inode_id, write=True,
                                   session_client="c", user=ALICE,
                                   rdwr=True)
        denied(ei)
    run(body())
