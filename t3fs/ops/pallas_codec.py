"""Pallas TPU kernels of the codec hot path: RS encode/decode, repair and
CRC32C on word-packed chunk bytes.

Chunk bytes stay packed four to a uint32 lane (the little-endian
``.view(np.uint32)`` of the byte shards) from HBM through VMEM and back:

  gf_words:    a constant GF(2^8) matrix (an RS code's parity rows to
               encode, a reconstruction matrix to decode) applied at every
               word position as a host-scheduled SWAR xtimes/XOR program
               (repair_program.schedule_matrix_program), about 2 VPU ops
               per byte; `rs_encode_words` and `rs_decode_words`.
  repair:      one scheduled repair row over h helper sub-shards (a Horner
               ladder over bit planes; all-ones rows are a pure XOR fold).
  crc_words:   512-byte segments as 128-word rows; bit (c, b) of each lane
               feeds one small int8 (R, 128) @ (128, 32) matmul whose weight
               slice is the segment matrix rows 8*(4w+c)+b; one more matmul
               combines the segment CRCs (`crc32c_seg_words`).
  fused steps: encode + CRC of every shard (the write path), decode + CRC
               of survivors and rebuilt shards (degraded reads), repair +
               CRC of the rebuilt sub-shard, each one device program.

Off the TPU, and for lengths these kernels cannot take, callers use the
XLA bit-matmul programs of jax_codec.py; rs.RSCode and crc32c_ref are the
oracles.  Matrix conventions come from rs.RSCode and
Crc32cMatrix.segment_matrix; cf. reference CPU analog folly::crc32c at
src/fbs/storage/Common.h:158 (the reference has no RS data path at all —
SURVEY.md preamble).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from t3fs.ops.crc32c import default_matrices
from t3fs.ops.repair_program import MatrixProgram, schedule_matrix_program
from t3fs.ops.rs import RSCode, default_rs, rs_code, stored_code

# word-kernel blocks (uint32 words per grid cell) of the fused steps; each
# is capped to a divisor of the chunk.  benchmarks/codec_block_sweep.py
# measures the alternatives on the chip (PERF.md, PR 21)
ENCODE_BLOCK_W = 131072
DECODE_BLOCK_W = 16384
REPAIR_BLOCK_W = 131072
# double-buffered in + out block bytes a word kernel may take of the 16 MiB
# scoped VMEM: what RS(8+2) encode fills at ENCODE_BLOCK_W (_fit_block)
VMEM_BLOCK_BYTES = 10 << 20


# --- word-packed GF(2^8) matrix and CRC kernels ----------------------------
#
#   gf_words:       all SWAR on uint32 lanes, ~2 VPU ops/byte; for RAID-6 P
#                   is an XOR fold and Q a Horner fold in g.  Inside a
#                   kernel, so no XLA bitcast relayout (which pins the XLA
#                   version to ~6 GB/s in HBM).
#   crc_words:      no transposes, no concat: extract -> MXU -> accumulate.
#                   int32 accumulation is exact (counts <= 128).
#
# Combine across segments is ONE bf16 matmul (n, S*32) @ (S*32, 32) built from
# the combine stack — counts <= S*32 < 2^24 so f32 accumulation stays exact.

WORD_SEG_BYTES = 512          # one CRC segment = 128 uint32 words
_SEG_W = WORD_SEG_BYTES // 4


def _xtimes_u32(x, shifts):
    """SWAR multiply-by-x of 4 packed GF(2^8) bytes per uint32 lane."""
    hi = (x >> 7) & jnp.uint32(0x01010101)
    x2 = (x << 1) & jnp.uint32(0xFEFEFEFE)
    for b in shifts:
        x2 = x2 ^ (hi << b if b else hi)
    return x2


def _poly_shifts(rs: RSCode) -> tuple[int, ...]:
    low = rs.gf.poly & 0xFF
    return tuple(b for b in range(8) if (low >> b) & 1)


def gf_words_program(rs: RSCode, rows) -> MatrixProgram:
    """The scheduled program a word kernel bakes for constant GF(2^8) rows
    (repair_program.schedule_matrix_program); its gf_ops is what ECCodec's
    `ec.flush` spans carry."""
    return schedule_matrix_program(
        tuple(tuple(int(c) for c in row) for row in rows), rs.gf.poly)


def _eval_gf_words(prog: MatrixProgram, xs: list, shifts: tuple[int, ...]
                   ) -> list:
    """Evaluate a MatrixProgram on packed uint32 words: xs[s] is input s,
    one output per row, in the program's schedule (fully unrolled, the
    constants baked in)."""
    rows = prog.rows
    acc: list = [None] * len(rows)
    if prog.horner:
        # per row: bit planes folded Horner-style from the top bit down
        for r, row in enumerate(rows):
            for b in range(max(row).bit_length() - 1, -1, -1):
                if acc[r] is not None:
                    acc[r] = _xtimes_u32(acc[r], shifts)
                for s, c in enumerate(row):
                    if (c >> b) & 1:
                        acc[r] = xs[s] if acc[r] is None else acc[r] ^ xs[s]
    else:
        # per input: one xtimes ladder (t, x*t, x^2*t, ...) whose rungs are
        # XORed into the rows whose coefficient has that bit set
        for s, col in enumerate(zip(*rows)):
            nbits = max(col).bit_length()
            t = xs[s]
            for b in range(nbits):
                for r, c in enumerate(col):
                    if (c >> b) & 1:
                        acc[r] = t if acc[r] is None else acc[r] ^ t
                if b + 1 < nbits:
                    t = _xtimes_u32(t, shifts)
    return [a if a is not None else jnp.zeros_like(xs[0]) for a in acc]


def _gf_words_kernel(x_ref, out_ref, *, prog: MatrixProgram,
                     shifts: tuple[int, ...]):
    x = x_ref[0]                                         # (k, R, C) uint32
    outs = _eval_gf_words(prog, [x[s] for s in range(x.shape[0])], shifts)
    for r, out in enumerate(outs):                       # (R, C): full vregs
        out_ref[0, r] = out


def _make_gf_words_call(rs: RSCode, rows, block_w: int, interpret: bool,
                        name: str):
    """(n, k, W) uint32 words -> (n, len(rows), W) uint32: the constant
    (len(rows), k) GF(2^8) matrix applied at every word position.

    Words are little-endian packed chunk bytes (byte j of the chunk is byte
    j%4 of word j//4), i.e. exactly numpy .view(uint32) of the byte shards.
    Internally the word axis is viewed (W//2048, 2048) so per-shard slices
    occupy full (8, 128)-lane vregs instead of single sublane rows."""
    prog = gf_words_program(rs, rows)
    shifts = _poly_shifts(rs)
    k, nout = rs.k, len(prog.rows)

    def call(words: jax.Array) -> jax.Array:
        n, kk, W = words.shape
        assert kk == k, (words.shape, k)
        bw = min(block_w, W)
        assert W % bw == 0, (W, bw)
        COLS = 2048 if bw % 2048 == 0 else bw
        nrows = bw // COLS
        v = words.reshape(n, k, W // COLS, COLS)
        out = pl.pallas_call(
            functools.partial(_gf_words_kernel, prog=prog, shifts=shifts),
            out_shape=jax.ShapeDtypeStruct((n, nout, W // COLS, COLS),
                                           jnp.uint32),
            grid=(n, W // bw),
            in_specs=[pl.BlockSpec((1, k, nrows, COLS),
                                   lambda i, j: (i, 0, j, 0))],
            out_specs=pl.BlockSpec((1, nout, nrows, COLS),
                                   lambda i, j: (i, 0, j, 0)),
            interpret=interpret,
            name=name,
        )(v)
        return out.reshape(n, nout, W)

    return call


def make_rs_encode_words_pallas(rs: RSCode | None = None, block_w: int = 16384,
                                interpret: bool = False):
    """(n, k, W) uint32 words -> (n, m, W) uint32 parity words, any RS code:
    the parity rows as a scheduled constant program (for RAID-6, P's XOR
    fold and Q's Horner fold in g)."""
    rs = rs or default_rs()
    return _make_gf_words_call(rs, rs.parity_rows, block_w, interpret,
                               "rs_encode_words")


def _norm_block_r(block_r: int) -> int:
    """Mosaic requires the second-minor block dim be a multiple of the 8-row
    sublane granule (or equal the array dim); interpret mode accepts any
    value.  Round up so tiny test/bench block sizes still compile on the
    chip."""
    return -(-block_r // 8) * 8


def _crc_words_kernel(x_ref, m_ref, out_ref):
    x = jax.lax.bitcast_convert_type(x_ref[...], jnp.int32)  # (R, 128) free
    acc = None
    for c in range(4):
        for b in range(8):
            # int8 planes + int8 weights with int32 accumulation: ~25%
            # faster than bf16 on v5e (cheaper cast, faster MXU path);
            # counts <= 128 so int32 accumulation is exact
            plane = ((x >> (8 * c + b)) & 1).astype(jnp.int8)
            part = jax.lax.dot_general(
                plane, m_ref[c * 8 + b], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)        # (R, 32)
            acc = part if acc is None else acc + part
    out_ref[...] = acc & 1


@functools.lru_cache(maxsize=16)
def _crc_word_weights() -> np.ndarray:
    """(32, 128, 32) f32: weight slice for bit b of byte c of each word lane;
    index c*8+b, rows are segment_matrix rows 8*(4w+c)+b."""
    Lseg = default_matrices().segment_matrix(WORD_SEG_BYTES)     # (4096, 32)
    out = np.zeros((32, _SEG_W, 32), dtype=np.float32)
    for c in range(4):
        for b in range(8):
            rows = 8 * (4 * np.arange(_SEG_W) + c) + b
            out[c * 8 + b] = Lseg[rows]
    return out


def make_crc_seg_words_pallas(block_r: int = 512, interpret: bool = False):
    """(R, 128) uint32 segment rows -> (R, 32) int32 0/1 raw segment CRCs.

    block_r is rounded up to a multiple of 8 (_norm_block_r); R must be a
    multiple of the NORMALIZED block_r (pad with zero rows: CRC of zeros
    is 0)."""
    block_r = _norm_block_r(block_r)
    Mj = jnp.asarray(_crc_word_weights().astype(np.int8))

    def seg_crc(rows: jax.Array) -> jax.Array:
        R, W = rows.shape
        assert W == _SEG_W and R % block_r == 0, (rows.shape, block_r)
        return pl.pallas_call(
            _crc_words_kernel,
            out_shape=jax.ShapeDtypeStruct((R, 32), jnp.int32),
            grid=(R // block_r,),
            in_specs=[
                pl.BlockSpec((block_r, _SEG_W), lambda i: (i, 0)),
                pl.BlockSpec((32, _SEG_W, 32), lambda i: (0, 0, 0)),
            ],
            out_specs=pl.BlockSpec((block_r, 32), lambda i: (i, 0)),
            interpret=interpret,
            name="crc32c_seg_words",
        )(rows, Mj)

    return seg_crc


def make_crc32c_words_raw(chunk_words: int, block_r: int = 512,
                          interpret: bool = False,
                          return_bits: bool = False):
    """(n, chunk_words) uint32 word rows -> (n,) uint32 RAW CRC (no init/final
    affine).  Raw CRC is zero-preserving, so callers may FRONT-pad shorter
    buffers with zero bytes and apply affine_const(true_len) themselves —
    this is how the storage codec backend batches variable-length payloads.

    return_bits=True yields the (n, 32) 0/1 int32 rows before packing —
    the mesh codec applies per-shard tail-shift matrices to the bit rows
    and packs only after the cp psum (parallel/codec_mesh.py).

    chunk_words must be a multiple of 128 (512-byte segments)."""
    from t3fs.ops.jax_codec import pack_bits_u32

    assert chunk_words % _SEG_W == 0, chunk_words
    block_r = _norm_block_r(block_r)
    nseg = chunk_words // _SEG_W
    mats = default_matrices()
    # combine as one bf16 matmul: raw = mod2( seg_bits (n, S*32) @ C (S*32, 32) )
    P = mats.combine_stack(nseg, WORD_SEG_BYTES)                 # (S, 32, 32)
    C = jnp.asarray(
        P.transpose(0, 2, 1).reshape(nseg * 32, 32).astype(np.float32),
        dtype=jnp.bfloat16)
    seg = make_crc_seg_words_pallas(block_r, interpret)

    def raw_crc(words: jax.Array) -> jax.Array:
        n = words.shape[0]
        rows = words.reshape(n * nseg, _SEG_W)
        R = rows.shape[0]
        pad = (-R) % block_r
        if pad:
            rows = jnp.pad(rows, ((0, pad), (0, 0)))
        seg_bits = seg(rows)[:R].astype(jnp.bfloat16)            # (R, 32)
        raw = jax.lax.dot_general(
            seg_bits.reshape(n, nseg * 32), C, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(jnp.int32) & 1
        if return_bits:
            return raw
        return pack_bits_u32(raw)

    return raw_crc


def make_crc32c_words(chunk_words: int, block_r: int = 512,
                      interpret: bool = False):
    """(n, chunk_words) uint32 word rows -> (n,) uint32 CRC32C (full chunks).

    chunk_words must be a multiple of 128 (512-byte segments)."""
    affine = np.uint32(default_matrices().affine_const(chunk_words * 4))
    raw = make_crc32c_words_raw(chunk_words, block_r, interpret)

    def crc(words: jax.Array) -> jax.Array:
        return raw(words) ^ affine

    return crc


def _fit_block(chunk_words: int, block_w: int, slots: int) -> int:
    """pick_block(chunk_words, block_w), halved until the double-buffered
    in and out blocks of `slots` shards (k inputs + rows outputs) fit the
    10 MiB that RS(8+2) fills at 128Ki words; 16 MiB is the scoped VMEM."""
    from t3fs.ops.blocks import pick_block
    bw = pick_block(chunk_words, block_w)
    while 2 * 4 * slots * bw > VMEM_BLOCK_BYTES and bw % 2 == 0 and bw > 2048:
        bw //= 2
    return bw


def make_stripe_encode_step_words(chunk_words: int, k: int = 8, m: int = 2,
                                  interpret: bool = False,
                                  block_w: int = ENCODE_BLOCK_W,
                                  code: str | None = None):
    """Word-packed fused stripe step — the shipping TPU write-path op:
    (n, k, chunk_words) uint32 -> parity (n, m, chunk_words) uint32,
    crcs (n, k+m) uint32, under `code` (default: stored_code(k, m)).
    Input is the little-endian uint32 view of the byte shards (numpy:
    arr.view(np.uint32)); parity output views back the same way.  Replaces
    the reference's CPU folly::crc32c (src/fbs/storage/Common.h:158); the
    RS data path is a t3fs addition."""
    rs = rs_code(k, m, code or stored_code(k, m))
    # block_w 128Ki words (256Ki overflows the 16 MiB scoped VMEM at
    # RS(8+2)) and block_r 2048: the fastest pair in an earlier round's
    # v5e sweep; wider stripes take smaller blocks (_fit_block)
    rs_enc = make_rs_encode_words_pallas(
        rs, block_w=_fit_block(chunk_words, block_w, k + m),
        interpret=interpret)
    crc = make_crc32c_words(chunk_words, block_r=2048, interpret=interpret)

    def step(words: jax.Array):
        n = words.shape[0]
        parity = rs_enc(words)
        # CRC data and parity via free reshapes — no (k+m)-wide concat pass
        dcrc = crc(words.reshape(n * k, chunk_words)).reshape(n, k)
        pcrc = crc(parity.reshape(n * m, chunk_words)).reshape(n, m)
        return parity, jnp.concatenate([dcrc, pcrc], axis=1)

    return step


# --- word-packed reconstruct (the decode-side analog of the word encode) ----
#
# Decode coefficients are GF(2^8) constants from RSCode.reconstruct_gfmatrix,
# and multiplying packed words by a CONSTANT c needs no bit planes at all:
# c*x = XOR over the set bits b of c of xtimes^b(x).  The matrix runs through
# the same scheduled program as the encode (_eval_gf_words): a bit-plane
# Horner fold per rebuilt shard, or one xtimes ladder per survivor shared by
# every rebuilt shard, whichever issues fewer ops — the same ~2 VPU ops per
# byte regime as the encode kernel.  The program is built host-side per (present, want) pattern; the kernel is
# fully unrolled with the constants baked in.


def make_rs_reconstruct_words_pallas(present: tuple[int, ...],
                                     want: tuple[int, ...],
                                     rs: RSCode | None = None,
                                     block_w: int = 16384,
                                     interpret: bool = False):
    """(n, k, W) uint32 present-shard words -> (n, |want|, W) uint32 rebuilt.

    Word-packed decode of any RS code and any erasure pattern it survives
    (the decode matrix approach is pattern-agnostic; only the baked-in
    constants change).  Words are the little-endian uint32 view of the byte
    shards, same contract as make_rs_encode_words_pallas."""
    rs = rs or default_rs()
    assert len(present) == rs.k, (present, rs.k)
    return _make_gf_words_call(
        rs, rs.reconstruct_gfmatrix(list(present), list(want)), block_w,
        interpret, "rs_decode_words")


# --- word-packed sub-shard repair (reduced-read single-erasure path) --------
#
# Single-shard repair is ONE decode-matrix row evaluated over whatever helper
# set the read path fetched (k survivors, or just an LRC local group), and the
# read path hands us SUB-chunk slices (chunk_size/r bytes per helper), so the
# kernel is "many small rows" rather than "few big stripes".  The coefficient
# row is pre-scheduled host-side by repair_program.schedule_repair_program
# into bit planes + one Horner ladder (<= 7 xtimes TOTAL vs a private ladder
# per helper) and baked into the kernel, exactly like the reconstruct kernel
# bakes its constant chain.  All-ones programs (P-row / LRC-local repair)
# compile to a pure XOR fold — the XOR-scheduled fast path.


def _repair_words_kernel(x_ref, out_ref, *,
                         planes: tuple[tuple[int, ...], ...],
                         shifts: tuple[int, ...]):
    x = x_ref[0]                                         # (h, R, C) uint32
    top = len(planes) - 1
    acc = None
    for i in planes[top]:                                # top plane is nonempty
        acc = x[i] if acc is None else acc ^ x[i]
    for b in range(top - 1, -1, -1):
        acc = _xtimes_u32(acc, shifts)
        for i in planes[b]:
            acc = acc ^ x[i]
    out_ref[0] = acc


def make_repair_subshard_words(program, rs: RSCode | None = None,
                               block_w: int = 16384,
                               interpret: bool = False):
    """(n, h, W) uint32 helper sub-shard words -> (n, W) uint32 rebuilt words.

    `program` is a repair_program.RepairProgram over h helpers; words are the
    little-endian uint32 view of the helper byte slices (same packing contract
    as the encode/reconstruct word kernels).  Each grid cell evaluates the
    scheduled Horner-over-bit-planes program on full (8, 128)-lane vregs."""
    rs = rs or default_rs()
    shifts = _poly_shifts(rs)
    h = program.num_helpers
    planes = program.planes

    def repair(words: jax.Array) -> jax.Array:
        n, hh, W = words.shape
        assert hh == h, (words.shape, h)
        bw = min(block_w, W)
        assert W % bw == 0, (W, bw)
        COLS = 2048 if bw % 2048 == 0 else bw
        rows = bw // COLS
        v = words.reshape(n, h, W // COLS, COLS)
        out = pl.pallas_call(
            functools.partial(_repair_words_kernel,
                              planes=planes, shifts=shifts),
            out_shape=jax.ShapeDtypeStruct((n, W // COLS, COLS), jnp.uint32),
            grid=(n, W // bw),
            in_specs=[pl.BlockSpec((1, h, rows, COLS),
                                   lambda i, j: (i, 0, j, 0))],
            out_specs=pl.BlockSpec((1, rows, COLS),
                                   lambda i, j: (i, j, 0)),
            interpret=interpret,
        )(v)
        return out.reshape(n, W)

    return repair


def make_repair_step_words(sub_words: int, program,
                           interpret: bool = False,
                           block_w: int = REPAIR_BLOCK_W):
    """Fused sub-shard repair + CRC: (n, h, sub_words) uint32 helper words ->
    rebuilt (n, sub_words) uint32, crcs (n,) uint32 (CRC32C of each rebuilt
    sub-shard).  The client stitches the r per-sub-shard CRCs into the
    full-chunk write-back checksum with crc32c_combine, so repair pays no
    host CRC pass.  sub_words must be a multiple of 128 (512-byte segments)."""
    from t3fs.ops.blocks import pick_block
    rep = make_repair_subshard_words(
        program, block_w=pick_block(sub_words, block_w), interpret=interpret)
    crc = make_crc32c_words(sub_words, block_r=2048, interpret=interpret)

    def step(words: jax.Array):
        rebuilt = rep(words)
        return rebuilt, crc(rebuilt)

    return step


def make_stripe_decode_step_words(chunk_words: int, present: tuple[int, ...],
                                  want: tuple[int, ...], k: int = 8,
                                  m: int = 2, interpret: bool = False,
                                  block_w: int = DECODE_BLOCK_W,
                                  code: str | None = None):
    """Word-packed fused decode+verify — the read-path mirror of
    make_stripe_encode_step_words: (n, k, chunk_words) uint32 present-shard
    words -> rebuilt (n, |want|, chunk_words) uint32,
    crcs (n, k + |want|) uint32 (CRC32C of the k survivors in `present`
    order, then the rebuilt shards in `want` order), under `code`
    (default: stored_code(k, m)).

    One device program rebuilds the missing shards AND checksums both the
    survivors and the rebuilt bytes, so a degraded read / repair pays no
    per-shard CPU crc32c after the round trip — the write path's fused
    economics (~107 GB/s two-point on v5e), now on the path that matters
    when the system is degraded and every stripe read is a decode."""
    rs = rs_code(k, m, code or stored_code(k, m))
    # 16Ki words = one (8, 2048) tile row-block, the smallest block the
    # TPU lowering takes, and the fastest of 16Ki..128Ki on v5e for every
    # two-erasure pattern (benchmarks/codec_block_sweep.py); 128Ki, the
    # encode block, overflows the 16 MiB scoped VMEM with two data erasures
    nwant = len(want)
    rec = make_rs_reconstruct_words_pallas(
        present, want, rs, block_w=_fit_block(chunk_words, block_w, k + nwant),
        interpret=interpret)
    crc = make_crc32c_words(chunk_words, block_r=2048, interpret=interpret)

    def step(words: jax.Array):
        n = words.shape[0]
        rebuilt = rec(words)
        # CRC survivors and rebuilt via free reshapes — no wide concat pass
        scrc = crc(words.reshape(n * k, chunk_words)).reshape(n, k)
        rcrc = crc(rebuilt.reshape(n * nwant, chunk_words)).reshape(n, nwant)
        return rebuilt, jnp.concatenate([scrc, rcrc], axis=1)

    return step
