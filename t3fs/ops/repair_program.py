"""Repair programs: scheduled GF(2^8) linear combinations for EC repair.

A single-shard repair is one row of a decode matrix: the lost shard is
sum_i c_i * helper_i over GF(2^8).  The naive evaluation walks a private
xtimes ladder per helper (sum of bit_length(c_i)-1 xtimes ops).  This module
schedules the row as a SHARED program instead (the XOR-program optimization
of arxiv 2108.02692, specialized to one output row):

    result = sum_b x^b * S_b      where  S_b = XOR of helpers with bit b set

evaluated Horner-style from the top bit down — at most 7 xtimes ops TOTAL
regardless of helper count, plus popcount(c_i) XORs per helper.  Two shapes
fall out for free:

  * all-ones rows (RAID-6 P repair, LRC local-parity repair) collapse to a
    pure XOR fold — zero xtimes ops (`is_xor` fast path);
  * the RAID-6 Q row has coefficients g^j (single-bit for j < 8), so its
    plane sets are singletons and the Horner fold IS the optimal schedule.

The program is host-built once per (coeffs) pattern and baked into the
Pallas word kernel (pallas_codec.make_repair_subshard_words) the same way
the reconstruct kernel bakes its constant chain; `eval_program_np` is the
bit-exact numpy reference the differential tests pin both against.

A whole constant matrix (RS parity rows for encode, a reconstruction matrix
for decode) is scheduled by `schedule_matrix_program`: either this per-row
Horner form, or one xtimes ladder per input shared by every row, whichever
issues fewer VPU ops (`MatrixProgram.gf_ops`).  A dense 4x10 Cauchy decode
takes the Horner form (<= 28 xtimes against up to 70); the RAID-6 encode
rows are the Horner fold itself.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Sequence

import numpy as np

from t3fs.ops.rs import RSCode, default_rs


@dataclass(frozen=True)
class RepairProgram:
    """Scheduled evaluation of sum_i coeffs[i] * helper_i over GF(2^8).

    planes[b] lists the helper indices whose coefficient has bit b set;
    trailing all-empty planes are trimmed so len(planes)-1 == top bit.
    xor_ops / xtimes_ops are the scheduled device-op counts (per word);
    naive_xtimes_ops is what the per-helper ladder would have cost."""

    coeffs: tuple[int, ...]
    planes: tuple[tuple[int, ...], ...]
    is_xor: bool
    xor_ops: int
    xtimes_ops: int
    naive_xtimes_ops: int

    @property
    def num_helpers(self) -> int:
        return len(self.coeffs)


def schedule_repair_program(coeffs: Sequence[int]) -> RepairProgram:
    """Build the bit-plane/Horner schedule for one GF(2^8) coefficient row.

    All coefficients must be in 1..255: zero-coefficient helpers carry no
    information and must be dropped by the caller before scheduling (the
    read path then never fetches them at all)."""
    cs = tuple(int(c) for c in coeffs)
    if not cs:
        raise ValueError("repair program needs at least one helper")
    for c in cs:
        if not 0 < c < 256:
            raise ValueError(f"coefficient {c} out of GF(2^8) range (or zero)")
    top = max(c.bit_length() for c in cs) - 1
    planes = tuple(
        tuple(i for i, c in enumerate(cs) if (c >> b) & 1)
        for b in range(top + 1))
    assert planes[top], cs
    xor_ops = sum(int(c).bit_count() for c in cs) - 1
    naive = sum(c.bit_length() - 1 for c in cs)
    return RepairProgram(coeffs=cs, planes=planes, is_xor=(top == 0),
                         xor_ops=xor_ops, xtimes_ops=top,
                         naive_xtimes_ops=naive)


def xor_program(num_helpers: int) -> RepairProgram:
    """The all-ones program: pure XOR fold (P-row / LRC-local repair)."""
    return schedule_repair_program((1,) * num_helpers)


def single_row_program(rs: RSCode | None, present: Sequence[int],
                       lost: int) -> RepairProgram:
    """Program rebuilding shard `lost` from the k shards in `present`."""
    rs = rs or default_rs()
    row = rs.reconstruct_gfmatrix(list(present), [lost])[0]
    return schedule_repair_program([int(c) for c in row])


def _xtimes_np(x: np.ndarray, poly_low: int) -> np.ndarray:
    hi = (x >> 7).astype(np.uint8)
    return (((x.astype(np.uint16) << 1) & 0xFF).astype(np.uint8)
            ^ (hi * np.uint8(poly_low)))


def eval_program_np(prog: RepairProgram, helpers: np.ndarray,
                    rs: RSCode | None = None) -> np.ndarray:
    """Numpy reference: helpers (h, L) uint8 -> (L,) uint8 rebuilt bytes.

    Executes the SAME schedule the kernel bakes in (Horner over bit planes),
    so kernel-vs-reference diffs isolate word-packing bugs, while
    reference-vs-gf.mul diffs (tests) isolate scheduling bugs."""
    rs = rs or default_rs()
    helpers = np.ascontiguousarray(helpers, dtype=np.uint8)
    if helpers.ndim != 2 or helpers.shape[0] != prog.num_helpers:
        raise ValueError(f"helpers {helpers.shape} != (h={prog.num_helpers}, L)")
    poly_low = rs.gf.poly & 0xFF

    def plane_sum(idx: tuple[int, ...]) -> np.ndarray | None:
        acc = None
        for i in idx:
            acc = helpers[i].copy() if acc is None else acc ^ helpers[i]
        return acc

    top = len(prog.planes) - 1
    acc = plane_sum(prog.planes[top])
    for b in range(top - 1, -1, -1):
        acc = _xtimes_np(acc, poly_low)
        s = plane_sum(prog.planes[b])
        if s is not None:
            acc ^= s
    return acc


def xtimes_vpu_ops(poly: int) -> int:
    """32-bit VPU ops of one SWAR multiply-by-x (pallas_codec._xtimes_u32):
    shift and mask twice, then a shift (none for bit 0) and an XOR per set
    bit of the polynomial's low byte; 11 for 0x11D."""
    low = poly & 0xFF
    return 4 + sum(2 if b else 1 for b in range(8) if (low >> b) & 1)


@dataclass(frozen=True)
class MatrixProgram:
    """Scheduled evaluation of out_r = sum_s rows[r][s] * in_s over GF(2^8)
    on packed words.  horner: each row is its own bit-plane Horner fold (at
    most 7 xtimes a row); else each input walks one xtimes ladder whose
    rungs every row shares (at most 7 xtimes an input).  Both issue the
    same XORs; gf_ops counts XORs plus xtimes times their own ops, per
    word position."""

    rows: tuple[tuple[int, ...], ...]
    horner: bool
    xor_ops: int
    xtimes_ops: int
    gf_ops: int


def _top(coeffs) -> int:
    """xtimes a ladder or fold over these coefficients needs; -1 if all 0."""
    return max(coeffs).bit_length() - 1


@functools.lru_cache(maxsize=512)
def schedule_matrix_program(rows: tuple[tuple[int, ...], ...],
                            poly: int) -> MatrixProgram:
    """Pick the cheaper schedule for a constant (r, k) GF(2^8) matrix,
    counted here on the host; Horner on a tie.  A zero row costs nothing
    (its output is zeros)."""
    rows = tuple(tuple(int(c) for c in row) for row in rows)
    xors = sum(max(0, sum(c.bit_count() for c in row) - 1) for row in rows)
    horner = sum(max(0, _top(row)) for row in rows)
    ladder = sum(max(0, _top(col)) for col in zip(*rows))
    xt = min(horner, ladder)
    return MatrixProgram(rows=rows, horner=horner <= ladder, xor_ops=xors,
                         xtimes_ops=xt,
                         gf_ops=xors + xt * xtimes_vpu_ops(poly))
