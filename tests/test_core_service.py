"""CoreService: config introspection / hot update / users on every server.

Reference analog: src/core/service/ops/ (getConfig, renderConfig,
hotUpdateConfig, getLastConfigUpdateRecord) + fbs/core user ctrl.
"""

import asyncio
import tomllib
from dataclasses import dataclass

import pytest

from t3fs.core.service import (
    AppInfo, CoreService, EchoReq, GetConfigReq, HotUpdateConfigReq,
    RenderConfigReq, UserInfo, UserReq,
)
from t3fs.kv.engine import MemKVEngine
from t3fs.net.client import Client
from t3fs.net.server import Server
from t3fs.utils.config import ConfigBase, citem, cobj, to_toml
from t3fs.utils.status import StatusError


@dataclass
class SubCfg(ConfigBase):
    depth: int = citem(3)


@dataclass
class DemoCfg(ConfigBase):
    period_s: float = citem(0.5, validator=lambda v: v > 0)
    name: str = citem("demo", hot=False)
    sub: SubCfg = cobj(SubCfg)


@pytest.fixture
def core_server():
    async def make():
        cfg = DemoCfg()
        core = CoreService(AppInfo(7, "demo", ""), config=cfg,
                           kv=MemKVEngine(), admin_token="tok")
        srv = Server()
        srv.add_service(core)
        await srv.start()
        return srv, core, cfg, Client()
    return make


async def _run(make, body):
    srv, core, cfg, cli = await make()
    try:
        return await body(srv, core, cfg, cli)
    finally:
        await cli.close()
        await srv.stop()


def test_echo_and_appinfo(core_server):
    async def body(srv, core, cfg, cli):
        rsp, _ = await cli.call(srv.address, "Core.echo", EchoReq("ping"))
        assert rsp.message == "ping"
        rsp, _ = await cli.call(srv.address, "Core.getAppInfo", None)
        assert rsp.info.node_type == "demo"
        assert rsp.info.pid > 0
    asyncio.run(_run(core_server, body))


def test_get_and_hot_update_config(core_server):
    async def body(srv, core, cfg, cli):
        rsp, _ = await cli.call(srv.address, "Core.getConfig", GetConfigReq())
        parsed = tomllib.loads(rsp.toml)
        assert parsed["period_s"] == 0.5
        assert parsed["sub"]["depth"] == 3

        # config mutation needs the admin token when one is configured
        with pytest.raises(StatusError):
            await cli.call(srv.address, "Core.hotUpdateConfig",
                           HotUpdateConfigReq({"period_s": 1.5}))

        rsp, _ = await cli.call(
            srv.address, "Core.hotUpdateConfig",
            HotUpdateConfigReq({"period_s": 1.5, "sub.depth": 9}, "tok"))
        assert sorted(rsp.updated_keys) == ["period_s", "sub.depth"]
        assert cfg.period_s == 1.5 and cfg.sub.depth == 9

        rec, _ = await cli.call(srv.address, "Core.getLastConfigUpdateRecord", None)
        assert rec.record.ok and "period_s" in rec.record.updated_keys

        # non-hot key refused, config untouched
        with pytest.raises(StatusError):
            await cli.call(srv.address, "Core.hotUpdateConfig",
                           HotUpdateConfigReq({"name": "x", "period_s": 9.0}, "tok"))
        assert cfg.period_s == 1.5 and cfg.name == "demo"
        # validator refused (including a raising validator: 'str' > 0)
        with pytest.raises(StatusError):
            await cli.call(srv.address, "Core.hotUpdateConfig",
                           HotUpdateConfigReq({"period_s": -1.0}, "tok"))
        with pytest.raises(StatusError):
            await cli.call(srv.address, "Core.hotUpdateConfig",
                           HotUpdateConfigReq({"period_s": "fast"}, "tok"))
        assert cfg.period_s == 1.5
    asyncio.run(_run(core_server, body))


def test_render_config_is_dry_run(core_server):
    async def body(srv, core, cfg, cli):
        rsp, _ = await cli.call(srv.address, "Core.renderConfig",
                                RenderConfigReq({"period_s": 2.0},
                                                admin_token="tok"))
        assert tomllib.loads(rsp.toml)["period_s"] == 2.0
        assert cfg.period_s == 0.5  # not committed
    asyncio.run(_run(core_server, body))


def test_user_ctrl(core_server):
    async def body(srv, core, cfg, cli):
        with pytest.raises(StatusError):  # bad token
            await cli.call(srv.address, "Core.userAdd",
                           UserReq("wrong", UserInfo(1, "alice")))
        rsp, _ = await cli.call(srv.address, "Core.userAdd",
                                UserReq("tok", UserInfo(1, "alice", is_admin=True)))
        token = rsp.users[0].token
        assert token  # auto-generated
        # without admin or the user's own token, the credential is redacted
        rsp, _ = await cli.call(srv.address, "Core.userGet", UserReq(user=UserInfo(1)))
        assert rsp.users[0].name == "alice" and rsp.users[0].token == ""
        # with the user's own token it is returned
        rsp, _ = await cli.call(srv.address, "Core.userGet",
                                UserReq(user=UserInfo(1, token=token)))
        assert rsp.users[0].token == token
        # admin sees it too
        rsp, _ = await cli.call(srv.address, "Core.userGet",
                                UserReq("tok", UserInfo(1)))
        assert rsp.users[0].token == token
        await cli.call(srv.address, "Core.userAdd", UserReq("tok", UserInfo(2, "bob")))
        # uid=255: low byte 0xff must not fall off the range-scan end
        await cli.call(srv.address, "Core.userAdd", UserReq("tok", UserInfo(255, "ff")))
        rsp, _ = await cli.call(srv.address, "Core.userList", UserReq("tok"))
        assert {u.name for u in rsp.users} == {"alice", "bob", "ff"}
        with pytest.raises(StatusError):  # uid out of range -> INVALID_ARG
            await cli.call(srv.address, "Core.userAdd",
                           UserReq("tok", UserInfo(-1, "neg")))
        await cli.call(srv.address, "Core.userRemove", UserReq("tok", UserInfo(255)))
        await cli.call(srv.address, "Core.userRemove", UserReq("tok", UserInfo(1)))
        with pytest.raises(StatusError):
            await cli.call(srv.address, "Core.userGet", UserReq(user=UserInfo(1)))
    asyncio.run(_run(core_server, body))


def test_to_toml_roundtrip():
    d = {"a": 1, "b": 2.5, "c": "hi \"q\"", "flag": True,
         "xs": [1, 2, 3], "t": {"y": "z", "inner": {"k": 4}}}
    assert tomllib.loads(to_toml(d)) == d


def test_cluster_servers_host_core():
    from t3fs.testing.cluster import LocalCluster

    async def body():
        cl = LocalCluster(num_nodes=1, replicas=1, with_meta=True)
        await cl.start()
        try:
            cli = cl.admin
            # mgmtd hosts Core next to Mgmtd (MgmtdServer.cc:33-34 analog)
            rsp, _ = await cli.call(cl.mgmtd_rpc.address, "Core.getAppInfo", None)
            assert rsp.info.node_type == "mgmtd"
            # storage node: hot-update the resync period end to end
            ss = cl.storage[1]
            rsp, _ = await cli.call(
                ss.server.address, "Core.hotUpdateConfig",
                HotUpdateConfigReq({"resync_period_s": 0.05}))
            assert rsp.updated_keys == ["resync_period_s"]
            assert ss.resync.period_s == 0.05
            # meta hosts Core too
            rsp, _ = await cli.call(cl.meta_rpc.address, "Core.getConfig",
                                    GetConfigReq())
            assert "gc_period_s" in rsp.toml
        finally:
            await cl.stop()
    asyncio.run(body())
