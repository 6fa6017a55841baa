"""ECCodec end-to-end in interpret mode — the tier-1-visible smoke of the
word-packed decode path (this module imports only t3fs.client.ec_codec and
the ops layer, so it collects on interpreters where t3fs.testing.cluster
can't).

Covers: encode -> drop 2 shards -> batched reconstruct_verified -> CRC
verify against crc32c_ref, all through the ("recv", ...) fused key with
T3FS_FORCE_PALLAS_INTERPRET=1, plus warmup_decode and the routing of
other codes.
"""

import asyncio

import numpy as np
import pytest

from t3fs.client.ec_codec import ECCodec
from t3fs.ops.crc32c import crc32c_ref
from t3fs.ops.rs import RSCode, default_rs, rs_code, stored_code

rng = np.random.default_rng(13)


@pytest.fixture
def interpret_env(monkeypatch):
    """Force the Pallas word kernels under the interpreter on CPU — the
    same dispatch the suite pins for encode (_use_pallas=True,
    _interpret=True on a CPU backend)."""
    monkeypatch.setenv("T3FS_FORCE_PALLAS_INTERPRET", "1")


def test_ec_codec_end_to_end_fused_decode(interpret_env):
    """Encode -> lose 2 shards -> BATCHED reconstruct_verified -> every
    rebuilt byte and every device CRC checks out; the fused launch is the
    one that served it (codec_counts['pallas-decode-words'])."""
    k, m, L = 8, 2, 2048
    rs = default_rs(k, m)
    stripes = [rng.integers(0, 256, (k, L), dtype=np.uint8)
               for _ in range(3)]
    lost = (1, 9)                                    # one data + one parity
    present = tuple(i for i in range(k + m) if i not in lost)[:k]

    async def body():
        codec = ECCodec(max_wait_us=2000)
        try:
            parities = await asyncio.gather(*(
                codec.encode(s, k, m) for s in stripes))
            fulls = [np.concatenate([s, p], axis=0)
                     for s, p in zip(stripes, parities)]
            for f, s in zip(fulls, stripes):         # encode sanity
                assert np.array_equal(f[k:], rs.encode_ref(s))
            outs = await asyncio.gather(*(
                codec.reconstruct_verified(f[list(present)], present,
                                           lost, k, m)
                for f in fulls))
            for f, (rebuilt, crcs) in zip(fulls, outs):
                for j, s in enumerate(lost):
                    assert np.array_equal(rebuilt[j], f[s])
                for j, s in enumerate(present):      # survivor CRCs
                    assert int(crcs[j]) == crc32c_ref(f[s].tobytes())
                for j, s in enumerate(lost):         # rebuilt CRCs
                    assert int(crcs[k + j]) == crc32c_ref(f[s].tobytes())
            assert codec.codec_counts.get("pallas-words", 0) >= 1
            assert codec.codec_counts.get("pallas-decode-words", 0) >= 1
            # micro-batching actually stacked concurrent same-key requests
            assert codec.batched_items >= 6
            assert ("recv", present, lost, k, m, L, rs.code_id) in codec._fns
        finally:
            await codec.close()

    asyncio.run(body())


def test_ec_codec_plain_reconstruct_word_path(interpret_env):
    """reconstruct() (no CRCs) routes through the word SWAR kernel on
    RAID-6 — 'pallas-rec-words' and no other codec."""
    k, m, L = 8, 2, 1024
    rs = default_rs(k, m)
    data = rng.integers(0, 256, (k, L), dtype=np.uint8)
    full = np.concatenate([data, rs.encode_ref(data)], axis=0)
    lost = (0, 5)
    present = tuple(i for i in range(k + m) if i not in lost)[:k]

    async def body():
        codec = ECCodec()
        try:
            out = await codec.reconstruct(full[list(present)], present,
                                          lost, k, m)
            for j, s in enumerate(lost):
                assert np.array_equal(out[j], full[s])
            assert set(codec.codec_counts) == {"pallas-rec-words"}, \
                codec.codec_counts
        finally:
            await codec.close()

    asyncio.run(body())


def test_ec_codec_non_raid6_reconstruct_word_path(interpret_env):
    """k=4, m=3 is not RAID-6, and its layouts store rrvand-11d: decode
    runs the word kernel, which serves every code."""
    k, m, L = 4, 3, 512
    rs = RSCode(k, m)
    data = rng.integers(0, 256, (k, L), dtype=np.uint8)
    full = np.concatenate([data, rs.encode_ref(data)], axis=0)
    lost = (0, 4, 6)
    present = tuple(i for i in range(k + m) if i not in lost)[:k]

    async def body():
        codec = ECCodec()
        try:
            out = await codec.reconstruct(full[list(present)], present,
                                          lost, k, m)
            for j, s in enumerate(lost):
                assert np.array_equal(out[j], full[s])
            assert set(codec.codec_counts) == {"pallas-rec-words"}, \
                codec.codec_counts
        finally:
            await codec.close()

    asyncio.run(body())


@pytest.mark.parametrize("k,m,code", [(8, 2, None), (10, 4, "cauchy-11d")],
                         ids=["rs8p2", "rs10p4-cauchy"])
@pytest.mark.parametrize("op", ["encode", "reconstruct", "encode_verified",
                                "reconstruct_verified"])
def test_odd_length_ops_take_the_xla_program(interpret_env, op, k, m, code):
    """A length that is no whole number of words (L = 1022) takes the XLA
    bit-matmul program in every operation, also on a codec that runs the
    Pallas word kernels: bytes and CRCs equal the oracles', and no Pallas
    codec served the call."""
    L = 1022
    rs = rs_code(k, m, code or stored_code(k, m))
    data = rng.integers(0, 256, (k, L), dtype=np.uint8)
    full = np.concatenate([data, rs.encode_ref(data)], axis=0)
    lost = (1, k + m - 1) if m == 2 else (0, 3, k, k + m - 1)
    present = tuple(i for i in range(k + m) if i not in lost)[:k]

    def crcs_of(slots) -> list[int]:
        return [crc32c_ref(full[s].tobytes()) for s in slots]

    async def body():
        codec = ECCodec()
        try:
            if op == "encode":
                parity = await codec.encode(data, k, m, code)
                assert np.array_equal(parity, full[k:])
            elif op == "encode_verified":
                parity, crcs = await codec.encode_verified(data, k, m, code)
                assert np.array_equal(parity, full[k:])
                assert [int(c) for c in crcs] == crcs_of(range(k + m))
            elif op == "reconstruct":
                out = await codec.reconstruct(full[list(present)], present,
                                              lost, k, m, code)
                assert np.array_equal(out, full[list(lost)])
            else:
                out, crcs = await codec.reconstruct_verified(
                    full[list(present)], present, lost, k, m, code)
                assert np.array_equal(out, full[list(lost)])
                assert [int(c) for c in crcs] == crcs_of(present + lost)
            assert codec._use_pallas                 # the env took effect
            assert set(codec.codec_counts) == {"xla-bitmatmul"}, \
                codec.codec_counts
        finally:
            await codec.close()

    asyncio.run(body())


def test_warmup_decode_precompiles_recv_keys(interpret_env):
    """warmup_decode compiles the fused decode fns off-path (the
    DeviceChecksumBackend.warmup analog): the ("recv", ...) keys land in
    the jit cache and a later reconstruct_verified reuses them."""
    k, m, L = 8, 2, 1024
    patterns = [(tuple(i for i in range(10) if i not in (a, b))[:8], (a, b))
                for a, b in [(0, 1), (8, 9)]]

    async def body():
        codec = ECCodec()
        try:
            assert codec.warmup_decode(patterns, L, k=k, m=m,
                                       batch_sizes=(1,)) == 2
            for present, want in patterns:
                assert ("recv", present, want, k, m, L,
                        "raid6-g2-11d") in codec._fns
            # warmed compiles ran the real fn, so counts reflect them
            assert codec.codec_counts.get("pallas-decode-words", 0) >= 2
        finally:
            await codec.close()
        # post-close warmup must be a clean no-op, not a RuntimeError
        codec.warmup_decode(patterns, L, k=k, m=m)

    asyncio.run(body())


@pytest.mark.parametrize("sampled", [False, True], ids=["off", "sampled"])
def test_ec_codec_wait_counters(sampled):
    """While head sampling is on, each launch finishes one `ec.flush`
    span: the codec thread's time in it, inside the wall time, tagged
    with its rows and their summed queue wait (submit to flush).  Off,
    no span is recorded and no row is timed."""
    from t3fs.utils import tracing

    k, m, L = 8, 2, 2048
    stripes = [rng.integers(0, 256, (k, L), dtype=np.uint8)
               for _ in range(3)]

    async def body():
        codec = ECCodec(max_wait_us=2000)
        try:
            t0 = asyncio.get_running_loop().time()
            await asyncio.gather(*(codec.encode_verified(s, k, m)
                                   for s in stripes))
            return codec, asyncio.get_running_loop().time() - t0
        finally:
            await codec.close()

    if sampled:
        tracing.configure(tracing.TraceConfig(sample_rate=1.0,
                                              export="all"))
    try:
        codec, wall = asyncio.run(body())
        flushes = [s for s in tracing.BUFFER.drain()
                   if s["name"] == "ec.flush"]
        finished = tracing.BUFFER.stats()["finished"]
    finally:
        tracing.reset_tracing()
    assert codec.batched_items == len(stripes)
    if not sampled:
        assert flushes == [] and finished == 0
        return
    assert len(flushes) == codec.batches
    assert sum(s["tags"]["rows"] for s in flushes) == len(stripes)
    assert {s["tags"]["op"] for s in flushes} == {"encv"}
    assert 0 < sum(s["dur_s"] for s in flushes) <= wall
    assert 0 < sum(s["tags"]["queue_s"] for s in flushes) \
        <= len(stripes) * wall


def test_ec_flush_spans_carry_code_and_gf_ops(interpret_env):
    """Each sampled encode or decode launch's `ec.flush` span names the
    parity code it ran and the VPU ops per word position of the baked
    word program (pallas_codec.gf_words_program)."""
    from t3fs.ops.pallas_codec import gf_words_program
    from t3fs.ops.rs import rs_code
    from t3fs.utils import tracing

    k, m, L = 10, 4, 512
    rs = rs_code(k, m, "cauchy-11d")
    data = rng.integers(0, 256, (k, L), dtype=np.uint8)
    full = np.concatenate([data, rs.encode_ref(data)])
    lost = (0, 1, 2, 3)
    present = tuple(range(4, 14))

    async def body():
        codec = ECCodec()
        try:
            parity, _ = await codec.encode_verified(data, k, m)
            rebuilt, _ = await codec.reconstruct_verified(
                full[list(present)], present, lost, k, m)
            return parity, rebuilt
        finally:
            await codec.close()

    tracing.configure(tracing.TraceConfig(sample_rate=1.0, export="all"))
    try:
        parity, rebuilt = asyncio.run(body())
        flushes = {s["tags"]["op"]: s["tags"] for s in tracing.BUFFER.drain()
                   if s["name"] == "ec.flush"}
    finally:
        tracing.reset_tracing()
    assert np.array_equal(parity, full[k:])
    assert np.array_equal(rebuilt, full[list(lost)])
    enc = gf_words_program(rs, rs.parity_rows)
    dec = gf_words_program(rs, rs.reconstruct_gfmatrix(list(present),
                                                       list(lost)))
    assert flushes["encv"]["code"] == flushes["recv"]["code"] == "cauchy-11d"
    assert flushes["encv"]["gf_ops"] == enc.gf_ops
    assert flushes["recv"]["gf_ops"] == dec.gf_ops
