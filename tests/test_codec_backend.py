"""ChecksumBackend seam: batching device offload vs host CRC oracle.

Reference seam analog: src/storage/store/StorageTarget.h:85-162 (engine
switch); the CPU path replaced is folly::crc32c (fbs/storage/Common.h:158).
"""

import asyncio
import os
import time

import numpy as np
import pytest

from t3fs.ops.crc32c import crc32c_ref
from t3fs.storage.codec_backend import (
    CpuChecksumBackend, DeviceChecksumBackend, NullChecksumBackend,
    make_checksum_backend,
)

rng = np.random.default_rng(11)


def run(coro):
    return asyncio.run(coro)


def test_cpu_backend_matches_oracle():
    async def body():
        b = CpuChecksumBackend()
        for n in (0, 1, 511, 512, 513, 300_000):
            data = rng.integers(0, 256, n, dtype=np.uint8).tobytes()
            assert await b.payload_crc(data) == crc32c_ref(data)
    run(body())


def test_null_backend():
    async def body():
        b = NullChecksumBackend()
        assert await b.payload_crc(b"anything") == 0
        assert not b.verify_enabled
    run(body())


def test_device_backend_batches_concurrent_payloads():
    async def body():
        b = DeviceChecksumBackend(min_device_bytes=0, max_wait_us=2000,
                                  max_batch=16, interpret_on_cpu=True)
        try:
            # mixed lengths -> multiple buckets in one flush; includes
            # non-segment-multiple lengths (front-padding path)
            datas = [rng.integers(0, 256, n, dtype=np.uint8).tobytes()
                     for n in (100, 512, 700, 2048, 4096, 5000, 100, 3333)]
            crcs = await asyncio.gather(*(b.payload_crc(d) for d in datas))
            for d, c in zip(datas, crcs):
                assert c == crc32c_ref(d), len(d)
            assert b.batched_items == len(datas)
            assert b.batches >= 1
        finally:
            await b.close()
    run(body())


def test_device_backend_small_payload_host_path():
    async def body():
        b = DeviceChecksumBackend(interpret_on_cpu=True)  # small stays on host
        data = b"123456789"
        assert await b.payload_crc(data) == 0xE3069283
        assert b.batched_items == 0
    run(body())


def test_close_fails_inflight_futures():
    async def body():
        # huge wait window so items sit in the batch when close() lands
        b = DeviceChecksumBackend(min_device_bytes=0, max_wait_us=10_000_000,
                                  interpret_on_cpu=True)
        data = rng.integers(0, 256, 1024, dtype=np.uint8).tobytes()
        task = asyncio.ensure_future(b.payload_crc(data))
        await asyncio.sleep(0.05)  # worker collects the item, waits for more
        await b.close()
        # must be the backend-closed error, NOT wait_for's TimeoutError —
        # a hanging future (the bug this guards) would otherwise still pass
        from t3fs.utils.status import StatusError
        with pytest.raises(StatusError, match="closed"):
            await asyncio.wait_for(task, timeout=2)
    run(body())


def test_payload_crc_after_close_fails_fast():
    """Regression: payload_crc() AFTER close() used to re-spawn the worker
    task and enqueue into a dead pool (hang or late failure).  It must
    fail fast with the backend-closed StatusError — and must NOT restart
    the worker — even for sub-threshold payloads that would otherwise
    take the host path."""
    from t3fs.utils.status import StatusError

    async def body():
        b = DeviceChecksumBackend(min_device_bytes=0, interpret_on_cpu=True)
        await b.close()
        with pytest.raises(StatusError, match="closed"):
            await b.payload_crc(b"x" * 1024)
        with pytest.raises(StatusError, match="closed"):
            await b.payload_crc(b"tiny")      # small-payload path too
        assert b._worker is None              # close() killed it; not revived
    run(body())


def test_null_backend_end_to_end_write_read_verify():
    """null backend must be self-consistent: writes store 0, appends combine
    to 0, reads with verify_checksum pass (nothing spuriously mismatches)."""
    from t3fs.storage.types import (
        BatchReadReq, ChunkId, ReadIO, UpdateIO, UpdateType, WriteReq,
    )
    from t3fs.testing.fabric import StorageFabric
    from t3fs.utils.status import StatusCode

    async def body():
        fab = StorageFabric(num_nodes=1, replicas=1, checksum_backend="null")
        await fab.start()
        try:
            cid = ChunkId(77, 0)
            for seq, (off, data) in enumerate(
                    [(0, b"x" * 1000), (1000, b"y" * 500)], 1):
                req = WriteReq(io=UpdateIO(
                    chunk_id=cid, chain_id=fab.chain_id,
                    chain_ver=fab.chain().chain_ver,
                    update_type=UpdateType.WRITE, offset=off,
                    length=len(data), chunk_size=4096,
                    checksum=crc32c_ref(data),  # ignored: verify disabled
                    channel=3, channel_seq=seq, client_id="t", inline=True))
                rsp, _ = await fab.client.call(
                    fab.head_address(), "Storage.write", req, payload=data)
                assert rsp.result.status.code == int(StatusCode.OK), \
                    rsp.result.status
                assert rsp.result.checksum == 0
            rreq = BatchReadReq(ios=[ReadIO(
                chunk_id=cid, chain_id=fab.chain_id, verify_checksum=True)])
            rsp, payload = await fab.client.call(
                fab.head_address(), "Storage.batch_read", rreq)
            assert rsp.results[0].status.code == int(StatusCode.OK), \
                rsp.results[0].status
            assert payload == b"x" * 1000 + b"y" * 500
        finally:
            await fab.stop()
    run(body())


def test_factory():
    assert make_checksum_backend("cpu").name == "cpu"
    assert make_checksum_backend("tpu").name == "device"
    assert make_checksum_backend("null").name == "null"
    inst = NullChecksumBackend()
    assert make_checksum_backend(inst) is inst
    assert make_checksum_backend(lambda: NullChecksumBackend()).name == "null"
    with pytest.raises(ValueError):
        make_checksum_backend("bogus")


@pytest.mark.skipif(bool(os.environ.get("T3FS_ON_DEVICE")),
                    reason="needs a platform that is not a TPU")
def test_tpu_backend_refuses_to_interpret_on_cpu():
    """checksum_backend="tpu" never falls back to the Pallas interpreter:
    off a TPU it fails at claim_device (storage node start) and on the
    first device-sized payload alike."""
    from t3fs.storage.server import StorageServer

    b = make_checksum_backend("tpu")
    with pytest.raises(RuntimeError, match="needs a TPU"):
        b.claim_device()

    async def body():
        try:
            with pytest.raises(RuntimeError, match="needs a TPU"):
                await b.payload_crc(b"x" * b.min_device_bytes)
            ss = StorageServer(1, "127.0.0.1:1", checksum_backend="tpu")
            with pytest.raises(RuntimeError, match="needs a TPU"):
                await ss.start()
            await ss.stop()
        finally:
            await b.close()
    run(body())


@pytest.mark.parametrize("env_dir", [True, False], ids=["env", "default"])
def test_compile_cache_placement(tmp_path, env_dir):
    """JAX_COMPILATION_CACHE_DIR, when set, holds every persisted program;
    unset, the cache is the fixed in-checkout DEFAULT_CACHE_DIR."""
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=repo)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    script = (
        "import jax, jax.numpy as jnp\n"
        "from t3fs.storage.codec_backend import (DEFAULT_CACHE_DIR,\n"
        "    enable_persistent_cache)\n"
        "enable_persistent_cache()\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
        "print(DEFAULT_CACHE_DIR)\n")
    if env_dir:
        script += (
            "jax.config.update('jax_persistent_cache_min_compile_time_secs',"
            " 0)\n"
            "jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()\n")
    out = subprocess.run([sys.executable, "-c", script], env=env, cwd=tmp_path,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    used, default = out.stdout.split()
    assert default == os.path.join(repo, ".jax_cache")
    if env_dir:
        assert used == str(tmp_path / "cache")
        assert os.listdir(used), "no compiled program landed in the env dir"
    else:
        assert used == default


def test_device_backend_wait_counters(monkeypatch):
    """A caller under a span gets crc_queue_s (entry to its launch) and
    crc_result_s (launch to the caller resuming), inside its wall time;
    an untraced caller's rows are never timed."""
    from t3fs.storage import codec_backend
    from t3fs.utils import tracing

    made, real = [], codec_backend._Pending

    def pending(*a):
        made.append(real(*a))
        return made[-1]

    monkeypatch.setattr(codec_backend, "_Pending", pending)
    datas = [rng.integers(0, 256, n, dtype=np.uint8).tobytes()
             for n in (512, 700, 2048, 4096)]

    async def traced(b, d):
        t0 = time.perf_counter()
        with tracing.start_root("op") as sp:
            assert await b.payload_crc(d) == crc32c_ref(d)
        return sp.tags, time.perf_counter() - t0

    async def body():
        b = DeviceChecksumBackend(min_device_bytes=0, max_wait_us=2000,
                                  max_batch=16, interpret_on_cpu=True)
        try:
            await asyncio.gather(*(b.payload_crc(d) for d in datas))
            assert len(made) == len(datas)
            assert all(it.t0 == it.t_launch == 0.0 for it in made)

            tracing.configure(tracing.TraceConfig(sample_rate=1.0))
            got = await asyncio.gather(*(traced(b, d) for d in datas))
            assert b.batched_items == 2 * len(datas)
            for tags, wall in got:
                assert tags["crc_queue_s"] >= 0 and tags["crc_result_s"] > 0
                assert tags["crc_queue_s"] + tags["crc_result_s"] <= wall
        finally:
            tracing.reset_tracing()
            await b.close()
    run(body())
