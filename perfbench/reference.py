"""The plain reference the run's answers are compared with.

It imports nothing of t3fs.  What a deployment stores is defined by its
configuration: every replica (CRAQ) holds the acknowledged bytes, and an
RS(k+2) stripe holds its k data shards plus RAID-6 parity over GF(2^8)
with the polynomial 0x11D, P = d_0 ^ ... ^ d_(k-1) and
Q = sum over s of g^(k-1-s) * d_s with g = 2.  Every stored chunk carries
the CRC32C (Castagnoli) of its bytes, here from the google-crc32c
library, an implementation independent of the program's own.
"""

from __future__ import annotations

import google_crc32c
import numpy as np

GF_POLY_LOW = 0x1D      # x^8 + x^4 + x^3 + x^2 + 1, less the x^8 term


def crc32c(data: bytes) -> int:
    return google_crc32c.value(bytes(data))


def xtime(a: np.ndarray) -> np.ndarray:
    """Multiply every byte by g = 2 in GF(2^8)."""
    return (a << 1) ^ ((a >> 7) * np.uint8(GF_POLY_LOW))


def raid6_parity(data: np.ndarray) -> np.ndarray:
    """(k, L) uint8 data shards -> (2, L) uint8 parity [P, Q]."""
    p = np.zeros(data.shape[1], dtype=np.uint8)
    q = np.zeros(data.shape[1], dtype=np.uint8)
    for row in data:            # Horner: q ends as sum g^(k-1-s) d_s
        p ^= row
        q = xtime(q) ^ row
    return np.stack([p, q])


def stripe_shards(stripe: bytes, k: int, m: int) -> list[bytes]:
    """The k + m chunk contents an RS(k+m) stripe stores, data first."""
    if m != 2:
        raise ValueError(f"reference parity is RAID-6 (m = 2), not m = {m}")
    data = np.frombuffer(stripe, dtype=np.uint8).reshape(k, -1)
    return [bytes(r) for r in data] + [bytes(r) for r in raid6_parity(data)]
