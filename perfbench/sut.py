"""The system under test: one in-process t3fs deployment built from a
configuration file, and the client calls the traffic makes on it.

`layout` "craq": `chains` CRAQ chains of `replicas` targets (chain c on
nodes c..c+replicas-1), one object = one chunk, written with
StorageClient.write_chunk and read with a one-IO StorageClient.batch_read.
`layout` "ec": `chains` single-replica EC chains on `nodes` nodes, one
object = one RS(k+m) stripe of k chunks (any k and m; its parity code is
the program's for that k and m, `ECLayout.code_id`), written with
ECStorageClient.write_stripe and read with
ECStorageClient.read_stripe_with_crcs.
"""

from __future__ import annotations

import asyncio

INODE_BASE = 0x7E570000
COPIES_PER_RPC = 8

# controls: the program with one guarantee of the configuration broken
# (stored CRCs left out; parity shards never written)
CONTROLS = ("null_checksum", "data_shards_only")


async def wait_until(cond, what: str, timeout_s: float = 60.0) -> None:
    for _ in range(int(timeout_s / 0.05)):
        if cond():
            return
        await asyncio.sleep(0.05)
    raise TimeoutError(f"timed out waiting for {what}")


class Deployment:
    def __init__(self, config: dict, checksum_backend, ec_codec=None,
                 trace=None, control: str | None = None):
        self.cfg = config
        self.layout_kind = config["layout"]
        self.checksum_backend = ("null" if control == "null_checksum"
                                 else checksum_backend)
        self.data_shards_only = control == "data_shards_only"
        self.ec_codec = ec_codec
        self.trace = trace
        self.cluster = None
        self.ec = None
        self.ec_layout = None

    @property
    def object_bytes(self) -> int:
        if self.layout_kind == "ec":
            return self.cfg["k"] * self.cfg["chunk_size"]
        return self.cfg["chunk_size"]

    async def start(self) -> None:
        from t3fs.testing.cluster import LocalCluster

        c = self.cfg
        if self.layout_kind == "craq":
            self.cluster = LocalCluster(
                num_nodes=c["nodes"], replicas=c["replicas"],
                num_chains=c["chains"], trace=self.trace,
                checksum_backend=self.checksum_backend)
        elif self.layout_kind == "ec":
            self.cluster = LocalCluster(
                num_nodes=c["nodes"], replicas=1, num_chains=0,
                ec_chains=c["chains"], trace=self.trace,
                checksum_backend=self.checksum_backend)
        else:
            raise ValueError(f"unknown layout {self.layout_kind!r}")
        await self.cluster.start()
        if self.layout_kind == "ec":
            from t3fs.client.ec_client import ECLayout, ECStorageClient

            self.ec = ECStorageClient(self.cluster.sc, codec=self.ec_codec)
            self.ec_layout = ECLayout.create(
                k=c["k"], m=c["m"], chunk_size=c["chunk_size"],
                chains=list(range(1, c["chains"] + 1)))

    async def stop(self) -> None:
        if self.ec is not None:
            await self.ec.close()
        if self.cluster is not None:
            await self.cluster.stop()

    # --- the calls the traffic makes ---

    def _chunk(self, group: int, obj: int):
        from t3fs.storage.types import ChunkId

        return (obj % self.cfg["chains"] + 1,
                ChunkId(INODE_BASE + group, obj))

    async def write(self, group: int, obj: int, payload: bytes) -> bool:
        """True when the write is acknowledged (status OK)."""
        if self.layout_kind == "craq":
            chain, cid = self._chunk(group, obj)
            r = await self.cluster.sc.write_chunk(
                chain, cid, 0, payload, chunk_size=self.cfg["chunk_size"])
            return r.status.code == 0
        shards = (tuple(range(self.cfg["k"])) if self.data_shards_only
                  else None)
        res = await self.ec.write_stripe(self.ec_layout, INODE_BASE + group,
                                         obj, payload, shards=shards)
        return all(r.status.code == 0 for r in res)

    async def read(self, group: int, obj: int
                   ) -> tuple[bool, bytes, list[int | None]]:
        """(ok, bytes, CRC32C of each chunk as the read reports it)."""
        if self.layout_kind == "craq":
            from t3fs.storage.types import ReadIO

            chain, cid = self._chunk(group, obj)
            results, payloads = await self.cluster.sc.batch_read(
                [ReadIO(chunk_id=cid, chain_id=chain)])
            r = results[0]
            return r.status.code == 0, payloads[0], [int(r.checksum)]
        data, crcs = await self.ec.read_stripe_with_crcs(
            self.ec_layout, INODE_BASE + group, obj, self.object_bytes)
        return True, data, crcs

    # --- what the check reads back: every stored copy ---

    def _copy_places(self, group: int, obj: int) -> list[tuple]:
        """(node address, chain, chunk id) of every stored copy: each
        replica of a CRAQ chunk, or each of the k+m shards of a stripe."""
        routing = self.cluster.mgmtd.state.routing()

        def addr(node_id: int) -> str:
            return self.cluster.storage[node_id].server.address

        if self.layout_kind == "craq":
            chain, cid = self._chunk(group, obj)
            return [(addr(t.node_id), chain, cid)
                    for t in routing.chains[chain].targets]
        lay = self.ec_layout
        out = []
        for s in range(lay.k + lay.m):
            chain = lay.shard_chain(obj, s)
            node = routing.chains[chain].targets[0].node_id
            out.append((addr(node), chain,
                        lay.shard_chunk(INODE_BASE + group, obj, s)))
        return out

    async def copies(self, group: int, objs: list[int]
                     ) -> dict[int, list[tuple[bool, bytes, int]]]:
        """Read every stored copy of `objs` straight from its node:
        {obj: [(ok, bytes, stored checksum) per copy]}."""
        from t3fs.storage.types import BatchReadReq, ReadIO

        by_addr: dict[str, list[tuple[int, int, int, object]]] = {}
        out: dict[int, list] = {}
        for obj in objs:
            places = self._copy_places(group, obj)
            out[obj] = [None] * len(places)
            for i, (address, chain, cid) in enumerate(places):
                by_addr.setdefault(address, []).append((obj, i, chain, cid))
        for address, wants in by_addr.items():
            for g in range(0, len(wants), COPIES_PER_RPC):
                part = wants[g:g + COPIES_PER_RPC]
                rsp, data = await self.cluster.admin.call(
                    address, "Storage.batch_read", BatchReadReq(ios=[
                        ReadIO(chunk_id=cid, chain_id=chain)
                        for _o, _i, chain, cid in part]))
                pos = 0
                for (obj, i, _c, _id), r in zip(part, rsp.results):
                    out[obj][i] = (r.status.code == 0, data[pos:pos + r.length],
                                   int(r.checksum))
                    pos += r.length
        return out

    # --- faults and counters ---

    async def kill_data_slots(self, slots: list[int]) -> list[int]:
        """Fail-stop the nodes that hold data slots `slots` of every
        stripe, and wait until routing serves none of their targets."""
        cluster, lay = self.cluster, self.ec_layout
        routing = cluster.mgmtd.state.routing()
        victims = sorted({routing.chains[lay.shard_chain(0, s)].head().node_id
                          for s in slots})
        for st in range(lay.k + lay.m):
            for s in slots:
                head = routing.chains[lay.shard_chain(st, s)].head()
                if head.node_id not in victims:
                    raise RuntimeError(f"slot {s} of stripe {st} is not on "
                                       f"nodes {victims}")
        affected = [c.chain_id for c in routing.chains.values()
                    if any(t.node_id in victims for t in c.targets)]
        for n in victims:
            await cluster.kill_storage_node(n)

        def settled() -> bool:
            routing = cluster.mgmtd.state.routing()
            return not any(t.node_id in victims
                           for c in affected
                           for t in routing.chains[c].serving())

        await wait_until(settled, "routing to drop the killed nodes")
        await cluster.mgmtd_client.refresh()
        return victims

    def crc_counters(self) -> dict[str, int]:
        """Launches and real rows of the live nodes' device CRC batchers."""
        codecs = [ss.node.codec for ss in self.cluster.storage.values()]
        return {"crc_launches": sum(getattr(c, "batches", 0) for c in codecs),
                "crc_rows": sum(getattr(c, "batched_items", 0)
                                for c in codecs)}

    def ec_counters(self) -> dict[str, int]:
        c = self.ec_codec
        if c is None:
            return {}
        return {"ec_launches": c.batches, "ec_rows": c.batched_items}
