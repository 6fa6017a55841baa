"""Systematic Reed-Solomon RS(k+m) over GF(2^8), bit-matmul formulation.

The reference has NO erasure-coding data path (EC exists only as a placement
option in deploy/data_placement/src/model/data_placement.py:484); RS(8+2)
encode/decode is a capability t3fs adds per BASELINE.json.  Three systematic
constructions, any k of k+m rows invertible: RAID-6 P/Q (m = 2), row-reduced
Vandermonde, and ISA-L/HDFS Cauchy; `stored_code` picks the one a new layout
stores.  The XLA fallback is the GF(2) expansion: for byte position j across
shards, parity bits = Gbits @ data bits, i.e. a (positions, 8k) @ (8k, 8m)
matmul — MXU-shaped and batched over arbitrarily many positions.
"""

from __future__ import annotations

import functools

import numpy as np

from t3fs.ops.gf256 import GF256, default_field
from t3fs.utils.status import StatusCode, make_error


class RSCode:
    """RS(k+m): shards 0..k-1 are data, k..k+m-1 are parity.

    `code` names the parity rows by format id (`code_id`, suffixed with
    the field polynomial): `raid6-g2-11d`, `rrvand-11d` or `cauchy-11d`.
    None takes RAID-6 for m = 2 and row-reduced Vandermonde otherwise."""

    def __init__(self, k: int = 8, m: int = 2, field: GF256 | None = None,
                 code: str | None = None):
        self.k = k
        self.m = m
        self.gf = field or default_field()
        poly = f"{self.gf.poly:x}"
        if code is None:
            code = (f"raid6-g2-{poly}" if m == 2 and k <= 254
                    else f"rrvand-{poly}")
        # identifies the parity FORMAT on the wire/disk: decode with a
        # different generator matrix silently corrupts, so layouts carry
        # this id and clients cross-check it
        self.code_id = code
        self.raid6 = code == f"raid6-g2-{poly}"
        if self.raid6:
            # RAID-6-style rows: P = XOR of all shards, Q = Horner chain in
            # the generator (coefficients g^(k-1-s)).  MDS for k <= 254
            # (distinct nonzero coefficients; the 2x2 minors [[1,1],[g^a,
            # g^b]] are invertible).  Chosen over row-reduced Vandermonde
            # because encode becomes k-1 XORs + k-1 xtimes on PACKED WORDS
            # — ~8x faster than the GF(2) bit matmul on the VPU
            # (jax_codec.make_rs_encode fast path).
            if m != 2 or k > 254:
                raise ValueError(f"{code} is RS(k+2) for k <= 254, "
                                 f"not RS({k}+{m})")
            G = np.zeros((k + 2, k), dtype=np.uint8)
            G[:k] = np.eye(k, dtype=np.uint8)
            G[k, :] = 1
            G[k + 1, :] = [self.gf.pow(2, k - 1 - s) for s in range(k)]
            self.G = G
        elif code == f"rrvand-{poly}":
            self.G = self.gf.systematic_generator(k, m)      # (k+m, k) GF(2^8)
        elif code == f"cauchy-{poly}":
            # ISA-L gf_gen_cauchy1_matrix (Hadoop RSUtil.genCauchyMatrix,
            # the HDFS RS-k-m-1024k policies): parity row i = k..k+m-1,
            # column j has 1 / (i XOR j).  Any k x k minor of [I; C] is a
            # Cauchy minor, so the code is MDS for k + m <= 256.
            if k + m > 256:
                raise ValueError(f"no {code} code for RS({k}+{m})")
            G = np.zeros((k + m, k), dtype=np.uint8)
            G[:k] = np.eye(k, dtype=np.uint8)
            for i in range(k, k + m):
                G[i] = [self.gf.inv(i ^ j) for j in range(k)]
            self.G = G
        else:
            raise ValueError(f"unknown RS parity code {code!r}")
        self.parity_rows = self.G[k:]                        # (m, k)
        # (8k, 8m) 0/1 matrix: unpacked data bits @ this = parity bits
        self.parity_bitmatrix = np.ascontiguousarray(
            self.gf.gfmat_to_bitmatrix(self.parity_rows).T
        )
        self._recon_cache: dict = {}  # per-instance memo (no global pinning)

    # --- host/numpy oracle path ---

    def encode_ref(self, data: np.ndarray) -> np.ndarray:
        """data: (k, L) uint8 -> parity (m, L) uint8. Numpy GF math (oracle)."""
        data = np.asarray(data, dtype=np.uint8)
        assert data.shape[0] == self.k
        out = np.zeros((self.m, data.shape[1]), dtype=np.uint8)
        for p in range(self.m):
            acc = np.zeros(data.shape[1], dtype=np.uint8)
            for i in range(self.k):
                acc ^= self.gf.mul(self.parity_rows[p, i], data[i])
            out[p] = acc
        return out

    def reconstruct_gfmatrix(self, present: list[int], want: list[int]) -> np.ndarray:
        """GF(2^8) matrix W (len(want) x k) with shards[want] = W @ shards[present].

        `present` must list exactly k distinct shard indices (0..k+m-1); any k
        suffice by the systematic-Vandermonde property."""
        assert len(present) == self.k
        sub = self.G[np.array(present)]                      # (k, k)
        inv = self.gf.mat_inv(sub)                           # data = inv @ present
        return self.gf.matmul(self.G[np.array(want)], inv)   # want = G[want] @ data

    def _recon_cached(self, present: tuple[int, ...], want: tuple[int, ...]):
        v = self._recon_cache.get((present, want))
        if v is None:
            W = self.reconstruct_gfmatrix(list(present), list(want))
            v = self._recon_cache[(present, want)] = (
                W, np.ascontiguousarray(self.gf.gfmat_to_bitmatrix(W).T))
        return v

    def reconstruct_bitmatrix(self, present: list[int], want: list[int]) -> np.ndarray:
        """(8k, 8*len(want)) 0/1 matrix for the bit-matmul decode path."""
        return self._recon_cached(tuple(present), tuple(want))[1]

    def decode_ref(self, shards: dict[int, np.ndarray], want: list[int]) -> np.ndarray:
        """Reconstruct `want` shard rows from any k present shards (oracle)."""
        present = sorted(shards.keys())[: self.k]
        W = self._recon_cached(tuple(present), tuple(want))[0]
        L = next(iter(shards.values())).shape[0]
        out = np.zeros((len(want), L), dtype=np.uint8)
        for r in range(len(want)):
            acc = np.zeros(L, dtype=np.uint8)
            for c, idx in enumerate(present):
                acc ^= self.gf.mul(W[r, c], shards[idx])
            out[r] = acc
        return out


@functools.lru_cache(maxsize=None)
def default_rs(k: int = 8, m: int = 2) -> RSCode:
    return RSCode(k, m)


# RS shapes with m >= 3 that Hadoop ships as built-in HDFS erasure-coding
# policies (RS-6-3-1024k, RS-10-4-1024k): their stripes store Cauchy parity
# byte for byte as HDFS and ISA-L write it
HDFS_CAUCHY_SHAPES = frozenset({(6, 3), (10, 4)})


def stored_code(k: int, m: int) -> str:
    """The parity code a new RS(k+m) layout stores, and the one ECCodec
    serves when a call names none."""
    if (k, m) in HDFS_CAUCHY_SHAPES:
        return f"cauchy-{default_field().poly:x}"
    return default_rs(k, m).code_id


@functools.lru_cache(maxsize=None)
def rs_code(k: int, m: int, code_id: str) -> RSCode:
    """The cached RSCode of a stored format id; EC_FORMAT_MISMATCH for an
    id this build cannot decode."""
    rs = default_rs(k, m)
    if code_id == rs.code_id:
        return rs
    try:
        return RSCode(k, m, code=code_id)
    except ValueError as e:
        raise make_error(StatusCode.EC_FORMAT_MISMATCH,
                         f"RS({k}+{m}) parity code {code_id!r}: {e}") from e
