"""JAX/XLA batched CRC32C + RS(k+m) — the TPU data plane.

Design: all hot math is int8 0/1 matmuls with int32 accumulation (MXU), with
bit unpack/pack as vector ops around them.  Matrices come from the host-side
builders in crc32c.py / rs.py and are closed over as constants so XLA folds
them into the compiled executable.

Shapes are static per (batch, chunk_len) pair; first call compiles, repeats
hit the cache.  This module is the portable XLA path; a fused Pallas kernel
(unpack+matmul in VMEM, avoiding the 8x HBM blowup of materialized bit
planes) is the planned fast path — until it lands, this is what runs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from t3fs.ops.crc32c import default_matrices
from t3fs.ops.rs import RSCode, default_rs

DEFAULT_SEG_BYTES = 512


def unpack_bits(x: jax.Array) -> jax.Array:
    """uint8 (..., B) -> int8 (..., 8B), LSB-first per byte."""
    shifts = jnp.arange(8, dtype=jnp.uint8)
    bits = (x[..., None] >> shifts) & jnp.uint8(1)
    return bits.reshape(*x.shape[:-1], x.shape[-1] * 8).astype(jnp.int8)


def pack_bits_u32(bits: jax.Array) -> jax.Array:
    """int32 0/1 (..., 32) -> uint32 (...)."""
    weights = (jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32))
    return jnp.sum(bits.astype(jnp.uint32) * weights, axis=-1, dtype=jnp.uint32)


def pack_bits_u8(bits: jax.Array) -> jax.Array:
    """int32 0/1 (..., 8B) -> uint8 (..., B)."""
    b = bits.reshape(*bits.shape[:-1], bits.shape[-1] // 8, 8).astype(jnp.uint8)
    weights = (jnp.uint8(1) << jnp.arange(8, dtype=jnp.uint8))
    return jnp.sum(b * weights, axis=-1, dtype=jnp.uint8)


def _mod2(x: jax.Array) -> jax.Array:
    return jnp.bitwise_and(x, 1)


def make_crc32c_raw(padded_len: int, seg_bytes: int = DEFAULT_SEG_BYTES):
    """Shared raw-CRC core (no init/final affine): jittable
    (n, padded_len) uint8 chunks -> (n, 32) int32 0/1 raw CRC.

    This single function backs the batch CRC, the stripe encode step, and the
    mesh-sharded path, so hot-path changes (Pallas, dtype/layout) land once.
    Bit-unpack happens INSIDE, on the (n, S, B) segment view — XLA fuses it
    into the segment matmul there; pre-unpacked 2D bit tensors measured 2x
    slower on v5e."""
    assert padded_len % seg_bytes == 0, (padded_len, seg_bytes)
    mats = default_matrices()
    nseg = padded_len // seg_bytes
    Lj = jnp.asarray(mats.segment_matrix(seg_bytes).astype(np.int8))       # (8B, 32)
    Pj = jnp.asarray(mats.combine_stack(nseg, seg_bytes).astype(np.int32)) # (S, 32, 32)

    def raw(chunks: jax.Array) -> jax.Array:
        n = chunks.shape[0]
        bits = unpack_bits(chunks.reshape(n, nseg, seg_bytes))   # (n, S, 8B)
        seg_crc = _mod2(
            jax.lax.dot_general(
                bits, Lj, (((2,), (0,)), ((), ())),
                preferred_element_type=jnp.int32,
            )
        )                                                        # (n, S, 32)
        return _mod2(jnp.einsum("skl,nsl->nk", Pj, seg_crc))     # (n, 32)

    return raw


def make_crc32c_batch(chunk_len: int, seg_bytes: int = DEFAULT_SEG_BYTES):
    """Build a jittable fn: (n, chunk_len) uint8 -> (n,) uint32 CRC32C.

    Leading-zero padding trick: crc_raw is 0-preserving, so chunks are
    front-padded to a whole number of segments while the affine constant uses
    the true length — bit-exact with the scalar reference for any length."""
    nseg = -(-chunk_len // seg_bytes)
    pad = nseg * seg_bytes - chunk_len
    raw = make_crc32c_raw(nseg * seg_bytes, seg_bytes)
    affine = np.uint32(default_matrices().affine_const(chunk_len))

    def crc(chunks: jax.Array) -> jax.Array:
        if pad:
            chunks = jnp.pad(chunks, ((0, 0), (pad, 0)))
        return pack_bits_u32(raw(chunks)) ^ affine

    return crc


@functools.lru_cache(maxsize=64)
def crc32c_batch_jit(chunk_len: int, seg_bytes: int = DEFAULT_SEG_BYTES):
    return jax.jit(make_crc32c_batch(chunk_len, seg_bytes))


def crc32c(data: bytes | np.ndarray) -> int:
    """Single-buffer convenience (device path, any length).

    NOTE: compiles one executable per distinct length — fine for tests and
    fixed-size chunks, wrong for arbitrary variable-length streams (use
    fixed-size batches + Crc32cMatrix.combine there)."""
    arr = np.frombuffer(bytes(data), dtype=np.uint8)
    if arr.size == 0:
        return 0
    fn = crc32c_batch_jit(arr.size)
    return int(fn(jnp.asarray(arr)[None, :])[0])


# --- Reed-Solomon ---

def _make_xtimes32(poly: int):
    """SWAR multiply-by-x on four packed GF(2^8) bytes in a uint32 lane.

    Per byte: (b << 1) ^ (poly_low if high bit was set).  The reduction
    constant is spread per byte by shifting the per-byte 0/1 mask (which
    sits at byte bit 0), so any shift 0..7 stays inside its byte — every
    8-bit poly low byte is supported (0x1D for the conventional 0x11D)."""
    low = poly & 0xFF
    shifts = [b for b in range(8) if (low >> b) & 1]
    assert shifts and max(shifts) < 8

    def xtimes32(x: jax.Array) -> jax.Array:
        hi = (x >> 7) & jnp.uint32(0x01010101)   # 1 per byte with high bit
        x2 = (x << 1) & jnp.uint32(0xFEFEFEFE)
        red = x2 ^ x2  # zeros
        for b in shifts:
            red = red ^ (hi << b)
        return x2 ^ red

    return xtimes32


def make_rs_encode_raid6(rs: RSCode):
    """Fast encode for the m=2 RAID-6-style code: P = XOR fold, Q = Horner
    in xtimes, all on uint32-packed words.  ~8x faster than the bit matmul
    on v5e (the GF(2) matmuls are VPU-bound; this touches each byte a
    handful of times at 4 bytes/lane)."""
    assert rs.raid6
    xtimes32 = _make_xtimes32(rs.gf.poly)

    def encode(data: jax.Array) -> jax.Array:
        n, k, Lb = data.shape
        assert Lb % 4 == 0, f"chunk length {Lb} not a multiple of 4 " \
            "(make_rs_encode falls back to the matmul path for these)"
        w = jax.lax.bitcast_convert_type(
            data.reshape(n, k, Lb // 4, 4), jnp.uint32)          # (n, k, L/4)
        p = w[:, 0]
        q = w[:, 0]
        for s in range(1, k):
            p = p ^ w[:, s]
            q = xtimes32(q) ^ w[:, s]
        parity = jnp.stack([p, q], axis=1)                       # (n, 2, L/4)
        return jax.lax.bitcast_convert_type(
            parity, jnp.uint8).reshape(n, 2, Lb)

    return encode


def make_rs_encode(rs: RSCode | None = None):
    """(n, k, L) uint8 data shards -> (n, m, L) parity shards.

    Dispatches to the RAID-6 word path when available: standalone (EC
    client stripe writes, parity regeneration) it is ~100x faster than the
    bit matmul.  The FUSED stripe-encode step keeps the matmul encoder
    (make_rs_encode_matmul): there the CRC dominates and XLA folds the
    matmul RS into the same HBM passes nearly for free, while mixing the
    word-SWAR path with the byte-wise CRC measured 3x SLOWER end to end on
    v5e (layout churn between u32 and u8 views)."""
    rs = rs or default_rs()
    if not getattr(rs, "raid6", False):
        return make_rs_encode_matmul(rs)
    fast = make_rs_encode_raid6(rs)
    slow = make_rs_encode_matmul(rs)

    def encode(data: jax.Array) -> jax.Array:
        # the word path needs whole u32 lanes; odd lengths (possible via
        # caller-chosen ECLayout.chunk_size) take the matmul path
        return fast(data) if data.shape[-1] % 4 == 0 else slow(data)

    return encode


def make_rs_encode_matmul(rs: RSCode | None = None):
    """Bit-matmul encoder (any m); also the best encoder INSIDE the fused
    stripe step (see make_rs_encode)."""
    rs = rs or default_rs()
    B = jnp.asarray(rs.parity_bitmatrix.astype(np.int8))         # (8k, 8m)

    def encode(data: jax.Array) -> jax.Array:
        n, k, Lb = data.shape
        x = jnp.swapaxes(data, 1, 2)                             # (n, L, k)
        bits = unpack_bits(x)                                    # (n, L, 8k)
        pbits = _mod2(
            jax.lax.dot_general(
                bits, B, (((2,), (0,)), ((), ())),
                preferred_element_type=jnp.int32,
            )
        )                                                        # (n, L, 8m)
        parity = pack_bits_u8(pbits)                             # (n, L, m)
        return jnp.swapaxes(parity, 1, 2)

    return encode


def make_rs_reconstruct(present: tuple[int, ...], want: tuple[int, ...],
                        rs: RSCode | None = None):
    """(n, k, L) uint8 present shards (rows in `present` order) -> (n, |want|, L)."""
    rs = rs or default_rs()
    W = jnp.asarray(rs.reconstruct_bitmatrix(list(present), list(want)).astype(np.int8))

    def reconstruct(shards: jax.Array) -> jax.Array:
        x = jnp.swapaxes(shards, 1, 2)
        bits = unpack_bits(x)
        out = _mod2(
            jax.lax.dot_general(
                bits, W, (((2,), (0,)), ((), ())),
                preferred_element_type=jnp.int32,
            )
        )
        return jnp.swapaxes(pack_bits_u8(out), 1, 2)

    return reconstruct


def make_stripe_encode_step(chunk_len: int, k: int = 8, m: int = 2,
                            seg_bytes: int = DEFAULT_SEG_BYTES):
    """The storage write-path hot op (BASELINE north star): for a batch of
    stripes (n, k, chunk_len) uint8, produce RS parity (n, m, chunk_len) and
    CRC32C of all k+m shards (n, k+m) uint32 — one fused jittable step.

    NOTE on structure: concatenating shard BYTES and unpacking inside the CRC
    core lets XLA fuse the bit-unpack into the segment matmul; feeding the RS
    encoder's bit planes to the CRC directly (return_bits=True) measured ~20x
    SLOWER on v5e — the materialized (n, k+m, 8L) int8 concat plus the strided
    bit transpose defeats fusion.  Keep the byte path."""
    assert chunk_len % seg_bytes == 0, (chunk_len, seg_bytes)
    rs_enc = make_rs_encode_matmul(default_rs(k, m))
    raw = make_crc32c_raw(chunk_len, seg_bytes)
    affine = np.uint32(default_matrices().affine_const(chunk_len))

    def step(stripes: jax.Array):
        n = stripes.shape[0]
        parity = rs_enc(stripes)
        allsh = jnp.concatenate([stripes, parity], axis=1)       # (n, k+m, L) bytes
        crcs = (pack_bits_u32(raw(allsh.reshape(n * (k + m), chunk_len)))
                ^ affine).reshape(n, k + m)
        return parity, crcs

    return step
