"""The plain reference the run's answers are compared with.

It imports nothing of t3fs.  What a deployment stores is defined by its
configuration: every replica (CRAQ) holds the acknowledged bytes, and an
RS(k+m) stripe holds its k data shards followed by m parity shards.  Each
parity shard is a GF(2^8) combination of the data shards, byte by byte,
with the coefficients of the systematic code that the configuration names
(`CODES`).  The field is built here from its definition: polynomial
x^8 + x^4 + x^3 + x^2 + 1 (0x11D), generator alpha = 2, log and exp
tables.  Every stored chunk carries the CRC32C (Castagnoli) of its bytes,
here from the google-crc32c library, an implementation independent of the
program's own.
"""

from __future__ import annotations

import google_crc32c
import numpy as np

GF_POLY = 0x11D         # x^8 + x^4 + x^3 + x^2 + 1
GF_POLY_LOW = 0x1D      # the same, less the x^8 term

RAID6 = "raid6-g2-11d"
RRVAND = "rrvand-11d"
CAUCHY = "cauchy-11d"
CODES = (RAID6, RRVAND, CAUCHY)


def _log_exp() -> tuple[np.ndarray, np.ndarray]:
    """exp[i] = alpha^i (doubled, so exp[a + b] needs no mod) and its
    inverse log over the 255 non-zero elements."""
    exp = np.zeros(510, dtype=np.uint8)
    log = np.zeros(256, dtype=np.int64)
    x = 1
    for i in range(255):
        exp[i] = exp[i + 255] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= GF_POLY
    return log, exp


LOG, EXP = _log_exp()
# MUL[a, b] = a * b in GF(2^8); MUL[c] maps every byte to c times it
MUL = np.where((np.arange(256)[:, None] == 0) | (np.arange(256)[None] == 0),
               np.uint8(0), EXP[LOG[:, None] + LOG[None, :]]).astype(np.uint8)


def crc32c(data: bytes) -> int:
    return google_crc32c.value(bytes(data))


def gf_pow2(n: int) -> int:
    """alpha^n with alpha = 2."""
    return int(EXP[n % 255])


def gf_inv(a: int) -> int:
    if not 0 < a < 256:
        raise ZeroDivisionError(f"{a} has no inverse in GF(2^8)")
    return int(EXP[(255 - LOG[a]) % 255])


def gf_matmul(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """(r, n) x (n, c) matrix product over GF(2^8)."""
    a, b = np.asarray(a, np.uint8), np.asarray(b, np.uint8)
    return np.bitwise_xor.reduce(MUL[a[:, :, None], b[None, :, :]], axis=1)


def gf_inverse(a: np.ndarray) -> np.ndarray:
    """Gauss-Jordan inverse of a square matrix over GF(2^8); ValueError
    when it is singular."""
    n = len(a)
    aug = np.concatenate([np.array(a, np.uint8),
                          np.eye(n, dtype=np.uint8)], axis=1)
    for col in range(n):
        nonzero = np.flatnonzero(aug[col:, col])
        if not len(nonzero):
            raise ValueError("singular matrix over GF(2^8)")
        piv = col + int(nonzero[0])
        aug[[col, piv]] = aug[[piv, col]]
        aug[col] = MUL[gf_inv(int(aug[col, col])), aug[col]]
        for r in range(n):
            if r != col and aug[r, col]:
                aug[r] ^= MUL[aug[r, col], aug[col]]
    return aug[:, n:]


def parity_rows(k: int, m: int, code: str) -> np.ndarray:
    """(m, k) uint8: parity shard p is the sum over data shards s of
    rows[p, s] * d_s.

    raid6-g2-11d   RAID-6 P and Q (H. P. Anvin, "The mathematics of
                   RAID-6", 2004): P has every coefficient 1, Q_s =
                   g^(k-1-s) with g = 2.  m = 2 only.
    rrvand-11d     systematic Vandermonde: V[i][j] = alpha^(i*j) for
                   i < k+m, j < k, G = V (V[:k])^-1, the parity G[k:] (the
                   row-reduced construction of J. S. Plank and Y. Ding,
                   "Note: Correction to the 1997 tutorial on Reed-Solomon
                   coding", Software: Practice and Experience, 2005).
    cauchy-11d     ISA-L's gf_gen_cauchy1_matrix (erasure_code/ec_base.c),
                   which Hadoop's RSUtil.genCauchyMatrix ports and the
                   HDFS RS-k-m-1024k policies store: the coefficient of
                   row i = k..k+m-1 and column j is 1 / (i XOR j).
    """
    if not (k >= 1 and m >= 1 and k + m <= 255):
        raise ValueError(f"no RS({k}+{m}) code over GF(2^8)")
    if code == RAID6:
        if m != 2:
            raise ValueError(f"{RAID6} has 2 parity shards, not {m}")
        return np.array([[1] * k, [gf_pow2(k - 1 - s) for s in range(k)]],
                        dtype=np.uint8)
    if code == RRVAND:
        v = np.array([[gf_pow2(i * j) for j in range(k)]
                      for i in range(k + m)], dtype=np.uint8)
        return gf_matmul(v, gf_inverse(v[:k]))[k:]
    if code == CAUCHY:
        return np.array([[gf_inv(i ^ j) for j in range(k)]
                         for i in range(k, k + m)], dtype=np.uint8)
    raise ValueError(f"unknown parity code {code!r} (known: {CODES})")


def xtime(a: np.ndarray) -> np.ndarray:
    """Multiply every byte by g = 2 in GF(2^8)."""
    return (a << 1) ^ ((a >> 7) * np.uint8(GF_POLY_LOW))


def raid6_parity(data: np.ndarray) -> np.ndarray:
    """(k, L) uint8 data shards -> (2, L) uint8 parity [P, Q], by Horner's
    rule: RAID-6 from its own definition, a witness for `encode` under
    the raid6-g2-11d rows."""
    p = np.zeros(data.shape[1], dtype=np.uint8)
    q = np.zeros(data.shape[1], dtype=np.uint8)
    for row in data:            # Horner: q ends as sum g^(k-1-s) d_s
        p ^= row
        q = xtime(q) ^ row
    return np.stack([p, q])


def encode(rows: np.ndarray, data: np.ndarray) -> np.ndarray:
    """(m, k) coefficients applied to (k, L) data shards -> (m, L)."""
    out = np.zeros((len(rows), data.shape[1]), dtype=np.uint8)
    for p, row in enumerate(rows):
        for c, shard in zip(row, data):
            out[p] ^= MUL[c][shard]
    return out


def stripe_shards(stripe: bytes, k: int, m: int, code: str) -> list[bytes]:
    """The k + m chunk contents an RS(k+m) stripe of `code` stores, data
    first."""
    data = np.frombuffer(stripe, dtype=np.uint8).reshape(k, -1)
    parity = encode(parity_rows(k, m, code), data)
    return [bytes(r) for r in data] + [bytes(r) for r in parity]
