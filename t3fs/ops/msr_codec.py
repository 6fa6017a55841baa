"""Device paths for the pm-msr coupled-layer code (ops/msr.py).

Three jittable steps, mirroring the plain-RS trio in pallas_codec /
jax_codec and consumed by ECCodec:

  * make_msr_encode_step — data words -> coupled parity + CRCs of all
    k+m shards.  The per-plane scalar-RS fold IS the RAID-6 word kernel
    (it applies plane-wise, and planes are just word ranges), so the
    Pallas dispatch reuses make_rs_encode_words_pallas; the coupling
    transforms are constant GF multiplies on full vregs around it.
  * make_msr_repair_step — the single-loss projection rebuild: helper
    projections (d survivors x beta sub-chunks) -> the whole rebuilt
    chunk + its CRC32C in one program.  Stages A/C are 2-coefficient
    scheduled programs evaluated as SWAR constant multiplies; stage B is
    two scheduled repair programs over the plane batch, dispatched to
    make_repair_subshard_words (Pallas) or the same Horner fold in plain
    jnp (the odd-length/CPU XLA word fallback — identical op structure).
  * make_msr_decode_step — multi-loss / degraded full-k decode via the
    cached dense decode matrix as a GF(2) bit-matmul (the rare 2-loss
    path; reads exactly k full shards, never more than plain RS).

Word paths require sub-chunk length % 512 (CRC segment granularity on
words); anything else — including byte-odd chunk sizes — takes the XLA
byte path, which shares every schedule and differs only in dtype.
"""

from __future__ import annotations

import functools

import numpy as np

from t3fs.ops.msr import MSRCode


def _shifts(poly: int) -> tuple[int, ...]:
    low = poly & 0xFF
    return tuple(b for b in range(8) if (low >> b) & 1)


def _xtimes_u8(x, shifts):
    """SWAR multiply-by-x on uint8 lanes (byte-path twin of _xtimes_u32)."""
    import jax.numpy as jnp
    hi = (x >> 7) & jnp.uint8(1)
    x2 = (x << 1) & jnp.uint8(0xFE)
    for b in shifts:
        x2 = x2 ^ (hi << b)
    return x2


def _make_mulc(words: bool, shifts: tuple[int, ...]):
    """Constant GF(2^8) multiply on packed lanes: XOR of the xtimes-ladder
    rungs the constant's set bits select (same chain the word kernels
    bake in ladder form; see pallas_codec._eval_gf_words)."""
    from t3fs.ops.pallas_codec import _xtimes_u32
    xt = (lambda x: _xtimes_u32(x, shifts)) if words else \
         (lambda x: _xtimes_u8(x, shifts))

    def mulc(x, c: int):
        assert 0 < c < 256, c
        acc = None
        t = x
        for b in range(c.bit_length()):
            if (c >> b) & 1:
                acc = t if acc is None else acc ^ t
            if b + 1 < c.bit_length():
                t = xt(t)
        return acc

    return mulc


def _make_horner(words: bool, shifts: tuple[int, ...], prog):
    """Evaluate a scheduled RepairProgram over stacked inputs along axis 1:
    (n, h, ...) -> (n, ...) — the jnp twin of _repair_words_kernel."""
    from t3fs.ops.pallas_codec import _xtimes_u32
    xt = (lambda x: _xtimes_u32(x, shifts)) if words else \
         (lambda x: _xtimes_u8(x, shifts))
    planes = prog.planes
    top = len(planes) - 1

    def run(x):
        acc = None
        for i in planes[top]:
            acc = x[:, i] if acc is None else acc ^ x[:, i]
        for b in range(top - 1, -1, -1):
            acc = xt(acc)
            for i in planes[b]:
                acc = acc ^ x[:, i]
        return acc

    return run


# --------------------------------------------------------------- encode

def make_msr_encode_step(code: MSRCode, chunk_len: int,
                         interpret: bool = False, use_pallas: bool = False):
    """(n, k, chunk_len) uint8 raw data shards -> (parity (n, m, chunk_len)
    uint8, crcs (n, k+m) uint32) — the pm-msr twin of
    make_stripe_encode_step_words, one jittable program."""
    import jax
    import jax.numpy as jnp

    k, m, alpha, t = code.k, code.m, code.alpha, code.t
    sub = code.subchunk_len(chunk_len)
    words = use_pallas and chunk_len % 512 == 0
    sh = _shifts(code.gf.poly)
    mulc = _make_mulc(words, sh)
    # static plane index maps: perm[y] flips digit y; unpaired masks
    perm = [np.arange(alpha) ^ (1 << y) for y in range(t)]
    unpaired = np.zeros((k, alpha), dtype=bool)
    for s in range(k):
        for z in range(alpha):
            unpaired[s, z] = code.unpaired(s, z)
    top = 1 << (t - 1)
    ztop = (np.arange(alpha) & top) != 0

    if words:
        from t3fs.ops.blocks import pick_block
        from t3fs.ops.pallas_codec import (make_crc32c_words,
                                           make_rs_encode_words_pallas)
        W = chunk_len // 4
        rs_enc = make_rs_encode_words_pallas(
            code.rs, block_w=pick_block(W, 131072), interpret=interpret)
        crc = make_crc32c_words(W, block_r=2048, interpret=interpret)
    else:
        from t3fs.ops.jax_codec import _make_xtimes32, make_crc32c_batch
        crc_bytes = make_crc32c_batch(chunk_len)

    def build(stacked):
        n = stacked.shape[0]
        lanes = sub // 4 if words else sub
        v = stacked.reshape(n, k, alpha, lanes)
        # uncouple the data columns
        us = []
        for s in range(k):
            y = s >> 1
            own = v[:, s]
            par = v[:, s ^ 1][:, perm[y]]
            mixed = mulc(own, code.inv_delta) ^ mulc(par, code.g_inv_delta)
            mask = jnp.asarray(unpaired[s])[None, :, None]
            us.append(jnp.where(mask, own, mixed))
        U = jnp.stack(us, axis=1).reshape(n, k, alpha * lanes)
        # per-plane scalar RS == the RAID-6 fold over the whole word axis
        if words:
            pu = rs_enc(U)
        else:
            p = U[:, 0]
            q = U[:, 0]
            for s in range(1, k):
                p = p ^ U[:, s]
                q = _xtimes_u8(q, sh) ^ U[:, s]
            pu = jnp.stack([p, q], axis=1)
        pu = pu.reshape(n, m, alpha, lanes)
        u8_, u9_ = pu[:, 0], pu[:, 1]
        # couple the parity column (y = t-1)
        zt = jnp.asarray(ztop)[None, :, None]
        p0 = jnp.where(zt, u8_ ^ mulc(u9_[:, perm[t - 1]], code.gamma), u8_)
        p1 = jnp.where(zt, u9_, mulc(u8_[:, perm[t - 1]], code.gamma) ^ u9_)
        parity = jnp.stack([p0, p1], axis=1).reshape(n, m, alpha * lanes)
        if words:
            dcrc = crc(stacked.reshape(n * k, W)).reshape(n, k)
            pcrc = crc(parity.reshape(n * m, W)).reshape(n, m)
        else:
            dcrc = crc_bytes(stacked.reshape(n * k, chunk_len)).reshape(n, k)
            pcrc = crc_bytes(parity.reshape(n * m, chunk_len)).reshape(n, m)
        return parity, jnp.concatenate([dcrc, pcrc], axis=1)

    step = jax.jit(build)

    def run(stacked: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        n = stacked.shape[0]
        if words:
            wv = stacked.view(np.uint32).reshape(n, k, W)
            parity, crcs = step(wv)
            parity = np.asarray(parity).view(np.uint8).reshape(
                n, m, chunk_len)
        else:
            parity, crcs = step(stacked)
            parity = np.asarray(parity)
        return parity, np.asarray(crcs)

    run.program = step     # the jitted device program (AOT compile tests)
    return run


# --------------------------------------------------------------- repair

def make_msr_repair_step(code: MSRCode, f: int, chunk_len: int,
                         interpret: bool = False, use_pallas: bool = False):
    """(n, d, beta_len) uint8 helper projections (survivors in ascending
    slot order, each the selected sub-chunks concatenated in ascending
    plane order) -> (rebuilt (n, chunk_len) uint8, crc (n,) uint32 of the
    whole rebuilt chunk) — the pm-msr twin of make_repair_step_words."""
    import jax
    import jax.numpy as jnp

    sch = code.schedule(f)
    d, npl, alpha = code.d, sch.npl, code.alpha
    sub = code.subchunk_len(chunk_len)
    assert chunk_len == alpha * sub
    beta_len = npl * sub
    words = use_pallas and sub % 512 == 0
    sh = _shifts(code.gf.poly)
    mulc = _make_mulc(words, sh)

    cm = sch.copy_mask[:, :, None]
    src_own = sch.src_own.ravel()
    src_pair = sch.src_pair.ravel()
    sel_z = np.asarray([z for z in range(alpha) if sch.out_sel[z] >= 0])
    nonsel_z = np.asarray([w for w, _, _ in sch.nonsel])
    nonsel_p2 = np.asarray([p2 for _, p2, _ in sch.nonsel])
    nonsel_c = np.asarray([c for _, _, c in sch.nonsel])
    c_up = code.gf_mul_const(code.inv_gamma, code.delta)

    if words:
        from t3fs.ops.blocks import pick_block
        from t3fs.ops.pallas_codec import (make_crc32c_words,
                                           make_repair_subshard_words)
        sw = sub // 4
        fold_f = make_repair_subshard_words(
            sch.prog_f, code.rs, block_w=pick_block(npl * sw, 131072),
            interpret=interpret)
        fold_p = make_repair_subshard_words(
            sch.prog_p, code.rs, block_w=pick_block(npl * sw, 131072),
            interpret=interpret)
        crc = make_crc32c_words(chunk_len // 4, block_r=2048,
                                interpret=interpret)
    else:
        from t3fs.ops.jax_codec import make_crc32c_batch
        fold_f = None
        horner_f = _make_horner(words, sh, sch.prog_f)
        horner_p = _make_horner(words, sh, sch.prog_p)
        crc_bytes = make_crc32c_batch(chunk_len)

    def build(stacked):
        n = stacked.shape[0]
        lanes = sub // 4 if words else sub
        flat = stacked.reshape(n, d * npl, lanes)
        # stage A: uncouple the 8 out-of-column helpers
        own = flat[:, src_own].reshape(n, code.k, npl, lanes)
        pr = flat[:, src_pair].reshape(n, code.k, npl, lanes)
        mixed = mulc(own, code.inv_delta) ^ mulc(pr, code.g_inv_delta)
        U = jnp.where(jnp.asarray(cm)[None], own, mixed)
        # stage B: two scheduled programs over the plane batch
        uf_in = U[:, np.asarray(sch.idx_f)].reshape(n, len(sch.idx_f),
                                                    npl * lanes)
        up_in = U[:, np.asarray(sch.idx_p)].reshape(n, len(sch.idx_p),
                                                    npl * lanes)
        if words:
            Uf = fold_f(uf_in).reshape(n, npl, lanes)
            Up = fold_p(up_in).reshape(n, npl, lanes)
        else:
            Uf = horner_f(uf_in).reshape(n, npl, lanes)
            Up = horner_p(up_in).reshape(n, npl, lanes)
        # stage C: scatter selected planes, fold the coupled ones
        out = jnp.zeros((n, alpha, lanes), dtype=stacked.dtype)
        out = out.at[:, sel_z].set(Uf)
        cp = flat[:, nonsel_c]
        val = mulc(cp, code.inv_gamma) ^ mulc(Up[:, nonsel_p2], c_up)
        out = out.at[:, nonsel_z].set(val)
        rebuilt = out.reshape(n, alpha * lanes)
        c = crc(rebuilt) if words else crc_bytes(rebuilt)
        return rebuilt, c

    step = jax.jit(build)

    def run(stacked: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        n = stacked.shape[0]
        assert stacked.shape[1:] == (d, beta_len), (stacked.shape, d,
                                                    beta_len)
        if words:
            wv = np.ascontiguousarray(stacked).view(np.uint32).reshape(
                n, d, beta_len // 4)
            rebuilt, crcs = step(wv)
            rebuilt = np.asarray(rebuilt).view(np.uint8).reshape(
                n, chunk_len)
        else:
            rebuilt, crcs = step(stacked)
            rebuilt = np.asarray(rebuilt)
        return rebuilt, np.asarray(crcs)

    run.program = step     # the jitted device program (AOT compile tests)
    return run


# --------------------------------------------------------------- decode

def make_msr_decode_step(code: MSRCode, present: tuple[int, ...],
                         want: tuple[int, ...], chunk_len: int):
    """(n, k, chunk_len) uint8 stored bytes of the `present` slots ->
    (rebuilt (n, len(want), chunk_len) uint8, crcs (n, k+len(want))
    uint32: survivors then rebuilt) — the multi-loss / degraded-read
    step.  One GF(2) bit-matmul over the flattened (slot, plane) symbol
    space on both platforms (the dense mask matrix has no word-SWAR
    shortcut; this path reads exactly k full shards, like plain RS)."""
    import jax
    import jax.numpy as jnp

    from t3fs.ops.jax_codec import (make_crc32c_batch, pack_bits_u8,
                                    unpack_bits)

    k, alpha = code.k, code.alpha
    sub = code.subchunk_len(chunk_len)
    nw = len(want)
    M = code.decode_matrix(tuple(present), tuple(want))
    Wb = jnp.asarray(code.gf.gfmat_to_bitmatrix(M).T.astype(np.int8))
    crcf = make_crc32c_batch(chunk_len)

    @jax.jit
    def step(stacked):
        n = stacked.shape[0]
        x = stacked.reshape(n, k * alpha, sub)
        bits = unpack_bits(jnp.swapaxes(x, 1, 2))        # (n, sub, 8*k*alpha)
        out = jax.lax.dot_general(
            bits, Wb, (((2,), (0,)), ((), ())),
            preferred_element_type=jnp.int32) & 1
        rebuilt = jnp.swapaxes(pack_bits_u8(out), 1, 2).reshape(
            n, nw, chunk_len)
        scrc = crcf(stacked.reshape(n * k, chunk_len)).reshape(n, k)
        rcrc = crcf(rebuilt.reshape(n * nw, chunk_len)).reshape(n, nw)
        return rebuilt, jnp.concatenate([scrc, rcrc], axis=1)

    def run(stacked: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        rebuilt, crcs = step(stacked)
        return np.asarray(rebuilt), np.asarray(crcs)

    return run
