"""Mesh-sharded RS(8+2)+CRC32C encode/decode — the multi-chip data plane.

Parallelism mapping (SURVEY.md §2.9/§5.7): a file-system's "parallelism" is
data distribution.  On a TPU pod slice the codec pipeline shards two ways:

  dp — stripe batch across devices (independent stripes, no comms)
  cp — chunk length across devices ("long-sequence" axis).  RS parity is
       byte-position-local so it needs NO communication under cp.  CRC is a
       GF(2) linear scan, so each device computes the raw CRC of its local
       span, multiplies by its tail shift matrix Mb^(bytes_after), and the
       chunk CRC is a psum (XOR under mod 2) over cp — one small collective
       of (n, k+m, 32) int32, riding ICI.

This mirrors how the reference distributes bulk data over chains/stripes
(meta/components/ChainAllocator.h:48-81) while the consistency math rides a
separate small-control path.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from t3fs.ops.crc32c import default_matrices
from t3fs.ops.jax_codec import (
    DEFAULT_SEG_BYTES, unpack_bits, pack_bits_u32, _mod2,
    make_crc32c_raw, make_rs_encode_matmul,
)
from t3fs.ops.rs import rs_code, stored_code


def make_mesh(n_devices: int | None = None, dp: int | None = None) -> Mesh:
    """Build a (dp, cp) mesh over the available devices, favoring cp (the
    chunk axis) so the CRC-combine collective is exercised widely."""
    devs = jax.devices()
    n = n_devices or len(devs)
    assert n <= len(devs), f"requested {n} devices, only {len(devs)} available"
    if dp is None:
        cp = 1
        for cand in (4, 2, 1):
            if n % cand == 0:
                cp = cand
                break
        dp = n // cp
    assert n % dp == 0, f"dp={dp} must divide n_devices={n}"
    cp = n // dp
    arr = np.array(devs[:n]).reshape(dp, cp)
    return Mesh(arr, ("dp", "cp"))


def _tail_combine(mesh: Mesh, local_bytes: int, total_bytes: int):
    """THE shift-weighted cp psum: bit rows (n*nshards, 32) of each
    device's local raw CRC -> full-chunk CRCs (n, nshards) uint32.  One
    construction shared by the byte-path AND word-path steps — the
    tail-shift exponent/affine math must never diverge between codecs."""
    cp = mesh.shape["cp"]
    mats = default_matrices()
    # tail-shift matrix per cp rank: Mb^(bytes strictly after this shard)
    tails = jnp.asarray(np.stack([
        mats.shift_matrix(local_bytes * (cp - 1 - r)).astype(np.int32)
        for r in range(cp)
    ]))
    affine = np.uint32(mats.affine_const(total_bytes))

    def combine(raw: jax.Array, n: int, nshards: int) -> jax.Array:
        r = jax.lax.axis_index("cp")
        shifted = _mod2(jnp.einsum("kl,nl->nk", tails[r], raw))
        total = _mod2(jax.lax.psum(shifted, axis_name="cp"))
        return pack_bits_u32(total).reshape(n, nshards) ^ affine

    return combine


def _crc_combine_setup(mesh: Mesh, chunk_len: int, seg_bytes: int):
    """Byte-path scaffolding for the cp-sharded CRC: local raw-CRC core
    plus the shared _tail_combine closure."""
    cp = mesh.shape["cp"]
    assert chunk_len % cp == 0 and (chunk_len // cp) % seg_bytes == 0, (
        f"chunk_len {chunk_len} must split into {cp} cp shards of whole "
        f"{seg_bytes}-byte segments")
    local_len = chunk_len // cp
    raw_local = make_crc32c_raw(local_len, seg_bytes)
    return local_len, raw_local, _tail_combine(mesh, local_len, chunk_len)


def make_sharded_encode_step(mesh: Mesh, chunk_len: int, k: int = 8, m: int = 2,
                             seg_bytes: int = DEFAULT_SEG_BYTES):
    """Full sharded encode step: stripes (n, k, chunk_len) uint8, sharded
    P('dp', None, 'cp') -> (parity (n, m, chunk_len) same sharding,
                            crcs (n, k+m) uint32 replicated over cp).

    Returns (jitted_fn, in_sharding) — callers place inputs with in_sharding.
    """
    local_len, raw_local, crc_combine = _crc_combine_setup(
        mesh, chunk_len, seg_bytes)
    # pinned to the matmul encoder: in the FUSED RS+CRC step the matmul
    # folds into the CRC's HBM passes nearly free, while the word-SWAR
    # path mixed with the byte-wise CRC measured 3x slower end to end
    # (same reasoning as jax_codec.make_stripe_encode_step)
    rs_encode = make_rs_encode_matmul(rs_code(k, m, stored_code(k, m)))

    def local_step(stripes: jax.Array):
        # stripes: (n_local, k, local_len); byte-concat then unpack inside the
        # CRC core — see make_stripe_encode_step for why not bit planes
        n = stripes.shape[0]
        parity = rs_encode(stripes)                              # local: RS is positionwise
        allsh = jnp.concatenate([stripes, parity], axis=1)
        raw = raw_local(allsh.reshape(n * (k + m), local_len))
        crcs = crc_combine(raw, n, k + m)
        return parity, crcs

    mapped = jax.shard_map(
        local_step, mesh=mesh,
        in_specs=P("dp", None, "cp"),
        out_specs=(P("dp", None, "cp"), P("dp", None)),
    )
    in_sharding = jax.NamedSharding(mesh, P("dp", None, "cp"))
    return jax.jit(mapped), in_sharding


def _crc_combine_words_setup(mesh: Mesh, chunk_words: int,
                             interpret: bool):
    """Word-kernel sibling of _crc_combine_setup: local raw CRC via the
    Pallas word kernels (returning BIT rows) + the shared _tail_combine
    psum.  Tail exponents are in BYTES (4x the word span)."""
    from t3fs.ops.pallas_codec import make_crc32c_words_raw

    cp = mesh.shape["cp"]
    local_words = chunk_words // cp
    assert chunk_words % cp == 0 and local_words % 128 == 0, (
        f"chunk_words {chunk_words} must split into {cp} cp shards of "
        f"whole 128-word (512-byte) segments")
    raw_bits = make_crc32c_words_raw(local_words, interpret=interpret,
                                     return_bits=True)
    return local_words, raw_bits, _tail_combine(
        mesh, local_words * 4, chunk_words * 4)


def make_sharded_encode_step_words(mesh: Mesh, chunk_words: int,
                                   k: int = 8, m: int = 2,
                                   interpret: bool = False,
                                   code: str | None = None):
    """The SHIPPING word-packed kernels under the mesh (r3 verdict #4:
    the sharded path previously ran only the XLA bit-matmul codec, so
    the multi-chip story and bench.py's measured configuration were
    different programs).  Same kernels as bench.py's
    make_stripe_encode_step_words, sharded dp over stripes and cp over
    the word axis:

      words (n, k, chunk_words) uint32, sharded P('dp', None, 'cp')
        -> parity (n, m, chunk_words) uint32 same sharding,
           crcs (n, k+m) uint32 replicated over cp.

    The SWAR parity of any RS code (`code`, default stored_code(k, m))
    is word-position-local (zero comms under cp); each device CRCs its
    local word span via the word kernel and the chunk CRC rides the same
    shift-weighted psum as the byte path.  interpret=True runs the
    kernels under the Pallas interpreter on the CPU mesh (tests/dryrun);
    on real chips pass False."""
    from t3fs.ops.pallas_codec import make_rs_encode_words_pallas

    local_words, raw_bits, crc_combine = _crc_combine_words_setup(
        mesh, chunk_words, interpret)
    rs_enc = make_rs_encode_words_pallas(
        rs_code(k, m, code or stored_code(k, m)), interpret=interpret)

    def local_step(words: jax.Array):
        n = words.shape[0]                  # (n_local, k, local_words)
        parity = rs_enc(words)
        dbits = raw_bits(words.reshape(n * k, local_words))
        pbits = raw_bits(parity.reshape(n * m, local_words))
        bits = jnp.concatenate(
            [dbits.reshape(n, k, 32), pbits.reshape(n, m, 32)],
            axis=1).reshape(n * (k + m), 32)
        crcs = crc_combine(bits, n, k + m)
        return parity, crcs

    mapped = jax.shard_map(
        local_step, mesh=mesh,
        in_specs=P("dp", None, "cp"),
        out_specs=(P("dp", None, "cp"), P("dp", None)),
        check_vma=False,   # pallas_call outputs carry no vma annotation
    )
    in_sharding = jax.NamedSharding(mesh, P("dp", None, "cp"))
    return jax.jit(mapped), in_sharding


def make_sharded_reconstruct_step_words(mesh: Mesh, chunk_len: int,
                                        present: tuple[int, ...],
                                        want: tuple[int, ...],
                                        k: int = 8, m: int = 2,
                                        interpret: bool = False,
                                        code: str | None = None):
    """Word-kernel recovery path under the mesh: the SWAR word
    reconstruct (same kernel the EC client ships, any RS code; `code`
    defaults to stored_code(k, m)) decodes each device's local span with
    bytes kept packed 4-per-uint32-lane, and the rebuilt shards' CRCs
    ride the word-kernel CRC + cp psum.

      survivors (n, k, chunk_len) uint8 sharded P('dp', None, 'cp')
        -> rebuilt (n, |want|, chunk_len) uint8 same sharding,
           crcs (n, |want|) uint32 replicated over cp.
    """
    from t3fs.ops.blocks import pick_block
    from t3fs.ops.pallas_codec import make_rs_reconstruct_words_pallas

    cp = mesh.shape["cp"]
    assert chunk_len % (4 * cp) == 0, (chunk_len, cp)
    local_len = chunk_len // cp
    local_words, raw_bits, crc_combine = _crc_combine_words_setup(
        mesh, chunk_len // 4, interpret)
    rs = rs_code(k, m, code or stored_code(k, m))
    w = len(want)
    rec_words = make_rs_reconstruct_words_pallas(
        present, want, rs, block_w=pick_block(local_words, 16384),
        interpret=interpret)

    def local_step(survivors: jax.Array):
        n = survivors.shape[0]              # (n_local, k, local_len) uint8
        # free little-endian reinterpret to packed uint32 words (same
        # layout as numpy .view(np.uint32) on the host), decode in word
        # space, reinterpret back — no unpack/repack passes
        words = jax.lax.bitcast_convert_type(
            survivors.reshape(n, k, local_words, 4), jnp.uint32)
        rwords = rec_words(words)           # (n, w, local_words) uint32
        rebuilt = jax.lax.bitcast_convert_type(
            rwords, jnp.uint8).reshape(n, w, local_len)
        crcs = crc_combine(
            raw_bits(rwords.reshape(n * w, local_words)), n, w)
        return rebuilt, crcs

    mapped = jax.shard_map(
        local_step, mesh=mesh,
        in_specs=P("dp", None, "cp"),
        out_specs=(P("dp", None, "cp"), P("dp", None)),
        check_vma=False,   # pallas_call outputs carry no vma annotation
    )
    in_sharding = jax.NamedSharding(mesh, P("dp", None, "cp"))
    return jax.jit(mapped), in_sharding


def make_sharded_reconstruct_step(mesh: Mesh, chunk_len: int,
                                  present: tuple[int, ...],
                                  want: tuple[int, ...],
                                  k: int = 8, m: int = 2,
                                  seg_bytes: int = DEFAULT_SEG_BYTES):
    """Mesh-sharded RS reconstruct + CRC of the rebuilt shards — the
    multi-chip recovery path (BASELINE config #4 at pod scale).

    GF(2^8) reconstruction is a per-byte-position linear map over the shard
    axis, so under cp (chunk-length) sharding it needs ZERO communication —
    each device decodes its local span.  The only collective is the same
    shift-weighted CRC psum as the encode step, verifying every rebuilt
    shard's checksum before it is written back to its chain.

    survivors (n, |present|, chunk_len) uint8 sharded P('dp', None, 'cp')
      -> (rebuilt (n, |want|, chunk_len) same sharding,
          crcs (n, |want|) uint32 replicated over cp)
    """
    local_len, raw_local, crc_combine = _crc_combine_setup(
        mesh, chunk_len, seg_bytes)
    from t3fs.ops.jax_codec import make_rs_reconstruct
    reconstruct = make_rs_reconstruct(present, want,
                                      rs_code(k, m, stored_code(k, m)))

    def local_step(survivors: jax.Array):
        n = survivors.shape[0]
        rebuilt = reconstruct(survivors)        # local: decode is positionwise
        raw = raw_local(rebuilt.reshape(n * len(want), local_len))
        crcs = crc_combine(raw, n, len(want))
        return rebuilt, crcs

    mapped = jax.shard_map(
        local_step, mesh=mesh,
        in_specs=P("dp", None, "cp"),
        out_specs=(P("dp", None, "cp"), P("dp", None)),
    )
    in_sharding = jax.NamedSharding(mesh, P("dp", None, "cp"))
    return jax.jit(mapped), in_sharding
