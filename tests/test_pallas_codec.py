"""Pallas kernel correctness vs the scalar oracles.

Default tier: interpret mode on the CPU backend — pins the math (SWAR
GF(2^8) programs, CRC weight slices, word packing) against crc32c_ref /
RSCode.encode_ref and the XLA bit-matmul without hardware.

On-device tier: T3FS_ON_DEVICE=1 runs the SAME tests with
interpret=False on the real chip, so a Mosaic-compile or on-device-math
regression fails the suite instead of surfacing in a round artifact."""

import os

import numpy as np
import pytest

# interpret=True on CPU (default) / interpret=False on the real chip
INTERPRET = not bool(os.environ.get("T3FS_ON_DEVICE"))

from t3fs.ops.crc32c import crc32c_ref
from t3fs.ops.pallas_codec import (
    make_crc32c_words, make_rs_encode_words_pallas,
    make_rs_reconstruct_words_pallas, make_stripe_decode_step_words,
    make_stripe_encode_step_words)
from t3fs.ops.rs import default_rs

rng = np.random.default_rng(7)


def _to_words(byts: np.ndarray) -> np.ndarray:
    """uint8 (..., L) -> little-endian uint32 (..., L//4) word view."""
    return byts.reshape(*byts.shape[:-1], byts.shape[-1] // 4, 4) \
        .view(np.uint32).reshape(*byts.shape[:-1], byts.shape[-1] // 4)


@pytest.mark.parametrize("block_w,L", [
    (512, 2048),     # COLS = bw fallback branch
    (4096, 16384),   # COLS = 2048 branch (the shipping bench configuration)
])
def test_rs_encode_words_matches_oracle(block_w, L):
    import jax.numpy as jnp

    rs = default_rs()
    enc = make_rs_encode_words_pallas(rs, block_w=block_w, interpret=INTERPRET)
    data = rng.integers(0, 256, (2, 8, L), dtype=np.uint8)
    got = np.asarray(enc(jnp.asarray(_to_words(data))))
    got_bytes = got.view(np.uint8).reshape(2, 2, L)
    for i in range(2):
        assert np.array_equal(got_bytes[i], rs.encode_ref(data[i]))


def test_crc32c_words_matches_oracle():
    import jax.numpy as jnp

    L = 2048  # 4 segments of 512 bytes
    crc = make_crc32c_words(L // 4, block_r=8, interpret=INTERPRET)
    rows = rng.integers(0, 256, (3, L), dtype=np.uint8)
    got = np.asarray(crc(jnp.asarray(_to_words(rows))))
    for r in range(3):
        assert int(got[r]) == crc32c_ref(rows[r].tobytes())


def test_stripe_step_words_matches_oracle():
    import jax.numpy as jnp

    L = 2048
    rs = default_rs()
    step = make_stripe_encode_step_words(L // 4, interpret=INTERPRET)
    stripes = rng.integers(0, 256, (2, 8, L), dtype=np.uint8)
    parity, crcs = step(jnp.asarray(_to_words(stripes)))
    parity = np.asarray(parity).view(np.uint8).reshape(2, 2, L)
    crcs = np.asarray(crcs)
    for i in range(2):
        ref_par = rs.encode_ref(stripes[i])
        assert np.array_equal(parity[i], ref_par)
        for s in range(8):
            assert int(crcs[i, s]) == crc32c_ref(stripes[i, s].tobytes())
        for j in range(2):
            assert int(crcs[i, 8 + j]) == crc32c_ref(ref_par[j].tobytes())


def _erasure_masks(n_shards: int = 10):
    """All 55 single/double-erasure (present, want) patterns of RS(8+2)."""
    masks = []
    for a in range(n_shards):
        masks.append(((a,),))
    for a in range(n_shards):
        for b in range(a + 1, n_shards):
            masks.append(((a, b),))
    return [m[0] for m in masks]


def test_rs_reconstruct_words_all_masks_differential():
    """TENTPOLE differential: the word-packed SWAR reconstruct kernel vs
    the jax_codec bit-matmul oracle over EVERY single/double-erasure mask
    of RS(8+2) — 55 (present, want) patterns, bit-identical bytes."""
    import jax.numpy as jnp

    from t3fs.ops.jax_codec import make_rs_reconstruct

    rs = default_rs()
    L = 512                                 # 128 words per shard
    data = rng.integers(0, 256, (8, L), dtype=np.uint8)
    parity = rs.encode_ref(data)
    allsh = np.concatenate([data, parity], axis=0)
    masks = _erasure_masks()
    assert len(masks) == 55
    for lost in masks:
        present = tuple(i for i in range(10) if i not in lost)[:8]
        want = tuple(lost)
        surv = allsh[list(present)][None]           # (1, 8, L)
        oracle = np.asarray(make_rs_reconstruct(present, want, rs)(
            jnp.asarray(surv)))
        rec = make_rs_reconstruct_words_pallas(present, want, rs,
                                               block_w=128,
                                               interpret=INTERPRET)
        got = np.asarray(rec(jnp.asarray(_to_words(surv))))
        got_bytes = got.view(np.uint8).reshape(1, len(want), L)
        assert np.array_equal(got_bytes, oracle), (present, want)
        for i, s in enumerate(want):
            assert np.array_equal(got_bytes[0, i], allsh[s]), (present, want)


@pytest.mark.parametrize("lost", [(0, 9), (3, 4), (8, 9), (5,)])
def test_stripe_decode_step_words_fused(lost):
    """Fused decode+verify: ONE launch returns the rebuilt shards AND the
    CRC32C of survivors + rebuilt shards (read-path mirror of
    make_stripe_encode_step_words)."""
    import jax.numpy as jnp

    L = 2048
    rs = default_rs()
    data = rng.integers(0, 256, (2, 8, L), dtype=np.uint8)
    parity = np.stack([rs.encode_ref(d) for d in data])
    allsh = np.concatenate([data, parity], axis=1)      # (2, 10, L)
    present = tuple(i for i in range(10) if i not in lost)[:8]
    want = tuple(lost)
    step = make_stripe_decode_step_words(L // 4, present, want,
                                         interpret=INTERPRET)
    surv = allsh[:, list(present)]
    rebuilt, crcs = step(jnp.asarray(_to_words(surv)))
    rebuilt = np.asarray(rebuilt).view(np.uint8).reshape(2, len(want), L)
    crcs = np.asarray(crcs)
    assert crcs.shape == (2, 8 + len(want))
    for i in range(2):
        for j, s in enumerate(want):
            assert np.array_equal(rebuilt[i, j], allsh[i, s]), (i, s)
        for j, s in enumerate(present):                 # survivor CRCs
            assert int(crcs[i, j]) == crc32c_ref(allsh[i, s].tobytes())
        for j, s in enumerate(want):                    # rebuilt CRCs
            assert int(crcs[i, 8 + j]) == crc32c_ref(allsh[i, s].tobytes())
