"""storage_main: storage node binary (reference: src/storage/storage.cpp,
TwoPhaseApplication<StorageServer>).

    python -m t3fs.app.storage_main --config configs/storage1.toml
    python -m t3fs.app.storage_main --fetch-config-from 127.0.0.1:9000 \
        --set node_id=2 --set data_dir='"/var/t3fs/n2"'
"""

from __future__ import annotations

import asyncio
import os
from dataclasses import dataclass

from t3fs.app.base import ApplicationBase, LogConfig
from t3fs.storage.server import StorageConfig, StorageServer
from t3fs.utils.config import ConfigBase, citem, cobj


@dataclass
class StorageMainConfig(ConfigBase):
    node_id: int = citem(0, hot=False, validator=lambda v: v >= 0)
    mgmtd_address: str = citem("127.0.0.1:9000", hot=False)
    data_dir: str = citem("", hot=False)
    # target ids hosted by this node; chunk roots live at data_dir/t{id}
    target_ids: list[int] = citem(factory=list, hot=False)
    engine_backend: str = citem("native", hot=False)
    admin_token: str = citem("", hot=False)
    port_file: str = citem("", hot=False)
    monitor_address: str = citem("", hot=False)   # push metrics here
    metrics_period_s: float = citem(10.0, hot=False)
    service: StorageConfig = cobj(StorageConfig)
    log: LogConfig = cobj(LogConfig)


async def serve(cfg: StorageMainConfig, app: ApplicationBase) -> None:
    ss = StorageServer(
        cfg.node_id, cfg.mgmtd_address, cfg=cfg.service,
        admin_token=cfg.admin_token,
        default_root=cfg.data_dir,
        discover_targets=bool(cfg.data_dir))
    for tid in cfg.target_ids:
        root = os.path.join(cfg.data_dir or ".", f"t{tid}")
        ss.add_target(tid, root, engine_backend=cfg.engine_backend)

    async def start():
        await ss.start()
        app.start_metrics(cfg.monitor_address, cfg.node_id,
                          cfg.metrics_period_s)
        if cfg.port_file:
            # t3fslint: allow(blocking-in-async) — one-shot port-file write at startup
            with open(cfg.port_file, "w") as f:
                f.write(str(ss.server.port))

    await app.run(start, ss.stop)


def main(argv: list[str] | None = None) -> None:
    app = ApplicationBase("storage", StorageMainConfig)
    cfg = app.boot(argv)
    if cfg.service.checksum_backend == "tpu":
        from t3fs.storage.codec_backend import enable_persistent_cache
        enable_persistent_cache()
    asyncio.run(serve(cfg, app))


if __name__ == "__main__":
    main()
