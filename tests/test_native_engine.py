"""Native C++ chunk engine: parity with the Python engine, crash-replay,
hardware CRC32C vs the scalar oracle.

Reference test analogs: tests/storage/store/* (TestChunkMetaStore,
TestStorageTarget) and the Rust engine's inline #[cfg(test)] units
(src/storage/chunk_engine/src/core/engine.rs)."""

import os

import pytest

from t3fs.ops.crc32c import crc32c_combine_ref, crc32c_ref
from t3fs.storage.chunk_engine import ChunkEngine
from t3fs.storage.native_engine import (
    NativeChunkEngine, crc32c_combine_native, crc32c_native)
from t3fs.storage.types import ChunkId, ChunkMeta, ChunkState
from t3fs.utils.status import StatusError


def test_crc32c_native_matches_oracle():
    rng = os.urandom
    for ln in (0, 1, 3, 7, 8, 9, 63, 64, 100, 4096, 10000):
        d = rng(ln)
        assert crc32c_native(d) == crc32c_ref(d)
    # streaming continuation
    a, b = rng(123), rng(77)
    assert crc32c_native(b, crc32c_native(a)) == crc32c_ref(a + b)
    # combine
    ca, cb = crc32c_native(a), crc32c_native(b)
    assert crc32c_combine_native(ca, cb, len(b)) == crc32c_ref(a + b)
    assert crc32c_combine_native(ca, cb, len(b)) == \
        crc32c_combine_ref(ca, cb, len(b))


@pytest.fixture(params=["native", "py"])
def engine(request, tmp_path):
    root = str(tmp_path / request.param)
    e = (NativeChunkEngine(root) if request.param == "native"
         else ChunkEngine(root))
    yield e
    e.close()


def test_engine_basic_ops(engine):
    cid = ChunkId(5, 3)
    data = os.urandom(5000)
    meta = ChunkMeta(cid, len(data), 1, 0, 1, crc32c_ref(data),
                     ChunkState.DIRTY)
    engine.put(cid, data, meta, 4096)
    assert engine.read(cid) == data
    assert engine.read(cid, 100, 50) == data[100:150]
    m = engine.get_meta(cid)
    assert (m.length, m.update_ver, m.state) == (5000, 1, ChunkState.DIRTY)

    engine.set_meta(cid, ChunkMeta(cid, len(data), 1, 1, 1, meta.checksum,
                                   ChunkState.COMMIT))
    assert engine.get_meta(cid).state == ChunkState.COMMIT
    assert engine.get_meta(cid).commit_ver == 1

    # COW overwrite
    engine.put(cid, b"x" * 4000,
               ChunkMeta(cid, 4000, 2, 2, 1, 0, ChunkState.COMMIT), 4096)
    assert engine.read(cid) == b"x" * 4000

    assert engine.get_meta(ChunkId(9, 9)) is None
    with pytest.raises(StatusError):
        engine.read(ChunkId(9, 9))


def test_engine_range_and_stats(engine):
    for i in range(10):
        c = ChunkId(7, i)
        engine.put(c, bytes([i]) * 1000,
                   ChunkMeta(c, 1000, 1, 1, 1, 0, ChunkState.COMMIT), 4096)
    assert len(engine.query_range(7)) == 10
    got = engine.query_range(7, 2, 5)
    assert [m.chunk_id.index for m in got] == [2, 3, 4]
    assert len(engine.all_metas()) == 10
    assert engine.stats().chunks == 10
    assert engine.remove(ChunkId(7, 0))
    assert not engine.remove(ChunkId(7, 0))
    assert engine.stats().chunks == 9


def test_native_wal_replay_and_snapshot(tmp_path):
    root = str(tmp_path / "e")
    e = NativeChunkEngine(root)
    cid = ChunkId(1, 1)
    e.put(cid, b"v1" * 100, ChunkMeta(cid, 200, 1, 1, 1, 0,
                                      ChunkState.COMMIT), 4096)
    e.put(cid, b"v2" * 100, ChunkMeta(cid, 200, 2, 2, 1, 0,
                                      ChunkState.DIRTY), 4096)
    del e  # simulate crash: no close() -> no snapshot, WAL only

    e2 = NativeChunkEngine(root)
    assert e2.read(cid) == b"v2" * 100
    assert e2.uncommitted()[0].chunk_id == cid
    e2.close()  # snapshot + wal truncate

    # garbage appended to the WAL (torn tail) must not break replay
    with open(os.path.join(root, "meta.wal"), "ab") as f:
        f.write(b"\xde\xad\xbe\xef torn record")
    e3 = NativeChunkEngine(root)
    assert e3.read(cid) == b"v2" * 100
    e3.close()


def test_native_block_reuse(tmp_path):
    """Freed blocks are reused (group-bitmap allocator)."""
    e = NativeChunkEngine(str(tmp_path / "e"))
    cid = ChunkId(1, 1)
    for ver in range(1, 20):
        e.put(cid, os.urandom(4000),
              ChunkMeta(cid, 4000, ver, ver, 1, 0, ChunkState.COMMIT), 4096)
    # 19 COW rewrites of one chunk must not allocate 19 blocks' worth of space
    assert e.stats().allocated_bytes <= 3 * 4096
    e.close()


def test_native_artifact_keyed_on_sources_and_flags(tmp_path):
    """The .so name hashes the sources and flags: a library built from
    other sources (a stale or foreign file) is never the one loaded."""
    from t3fs.native import build as nb
    src = tmp_path / "a.cpp"
    src.write_text("int x;")
    a = nb._artifact(["-O2"], "", [str(src)])
    assert nb._artifact(["-O2"], "", [str(src)]) == a
    src.write_text("int y;")
    b = nb._artifact(["-O2"], "", [str(src)])
    c = nb._artifact(["-O1"], "", [str(src)])
    assert len({a, b, c}) == 3
    assert all(p.startswith(nb._LIB_STEM + "-") and p.endswith(".so")
               for p in (a, b, c))
