"""The RS parity codes t3fs stores and the word kernels that serve them.

RS(10+4) and RS(6+3) layouts store HDFS's Cauchy parity (`cauchy-11d`,
ISA-L gf_gen_cauchy1_matrix); every other shape stores what it always did.
The rows are checked against the benchmark's reference, which imports
nothing of t3fs, and the word encode and fused decode+verify kernels (under
the Pallas interpreter) against the numpy oracle and the host CRC32C.
"""

import numpy as np
import pytest

from perfbench import reference
from t3fs.ops.crc32c import crc32c_ref
from t3fs.ops.repair_program import schedule_matrix_program, xtimes_vpu_ops
from t3fs.ops.rs import RSCode, default_rs, rs_code, stored_code
from t3fs.utils.status import StatusCode, StatusError

CAUCHY = "cauchy-11d"


@pytest.mark.parametrize("k,m", [(10, 4), (6, 3)])
def test_cauchy_rows_equal_the_reference(k, m):
    rs = rs_code(k, m, CAUCHY)
    assert rs.code_id == CAUCHY and not rs.raid6
    assert np.array_equal(rs.parity_rows,
                          reference.parity_rows(k, m, reference.CAUCHY))
    assert np.array_equal(rs.G[:k], np.eye(k, dtype=np.uint8))


def test_stored_code_table():
    """HDFS's built-in RS shapes with m >= 3 store Cauchy parity; every
    other shape stores what default_rs gives, which is unchanged."""
    assert stored_code(10, 4) == stored_code(6, 3) == CAUCHY
    assert stored_code(8, 2) == stored_code(4, 2) == "raid6-g2-11d"
    assert stored_code(4, 3) == stored_code(12, 4) == "rrvand-11d"
    assert default_rs(10, 4).code_id == default_rs(6, 3).code_id \
        == "rrvand-11d"
    assert default_rs(8, 2).code_id == "raid6-g2-11d"


def test_rs_code_is_cached_and_refuses_unknown_ids():
    assert rs_code(8, 2, "raid6-g2-11d") is default_rs(8, 2)
    assert rs_code(10, 4, CAUCHY) is rs_code(10, 4, CAUCHY)
    for k, m, code in [(10, 4, "raid6-g2-11d"), (8, 2, "vandermonde-11d"),
                       (8, 2, "cauchy-11b")]:
        with pytest.raises(StatusError) as ei:
            rs_code(k, m, code)
        assert ei.value.status.code == int(StatusCode.EC_FORMAT_MISMATCH)
    with pytest.raises(ValueError):
        RSCode(10, 4, code="raid6-g2-11d")


def test_cauchy_any_k_of_k_plus_m_decode():
    """The oracle decodes every 4-erasure pattern of RS(10+4) Cauchy."""
    from itertools import combinations

    rs = rs_code(10, 4, CAUCHY)
    data = np.random.default_rng(5).integers(0, 256, (10, 64),
                                             dtype=np.uint8)
    full = np.concatenate([data, rs.encode_ref(data)])
    for lost in combinations(range(14), 4):
        shards = {s: full[s] for s in range(14) if s not in lost}
        out = rs.decode_ref(shards, list(lost))
        assert np.array_equal(out, full[list(lost)]), lost


def test_raid6_rows_schedule_as_the_raid6_fold():
    """The general word kernel's schedule of the RAID-6 (8+2) parity rows
    issues the ops of the P/Q fold it replaced: k-1 XORs for P, k-1
    xtimes and k-1 XORs for Q."""
    k = 8
    rs = default_rs(k, 2)
    prog = schedule_matrix_program(
        tuple(tuple(int(c) for c in r) for r in rs.parity_rows), rs.gf.poly)
    assert prog.horner
    assert (prog.xor_ops, prog.xtimes_ops) == (2 * (k - 1), k - 1)
    assert prog.gf_ops == 2 * (k - 1) + (k - 1) * xtimes_vpu_ops(0x11D)
    assert xtimes_vpu_ops(0x11D) == 11


def test_dense_decode_takes_the_horner_schedule():
    """Four data shards of RS(10+4) Cauchy lost: a dense 4x10 matrix.  The
    per-row Horner form needs at most 7 xtimes a row, the per-survivor
    ladder up to 7 a survivor, so the chosen schedule costs about half."""
    rs = rs_code(10, 4, CAUCHY)
    present = list(range(4, 14))
    rows = tuple(tuple(int(c) for c in r)
                 for r in rs.reconstruct_gfmatrix(present, [0, 1, 2, 3]))
    prog = schedule_matrix_program(rows, rs.gf.poly)
    ladder = sum(max(col).bit_length() - 1 for col in zip(*rows))
    assert prog.horner and prog.xtimes_ops <= 28 < ladder
    ladder_ops = prog.xor_ops + ladder * xtimes_vpu_ops(rs.gf.poly)
    assert prog.gf_ops < 0.6 * ladder_ops


def test_ladder_is_kept_where_cheaper():
    """A matrix whose columns each hold one high coefficient: one ladder per
    input is shared by every row, cheaper than a fold per row."""
    rows = ((128, 1), (128, 1), (128, 1))
    prog = schedule_matrix_program(rows, 0x11D)
    assert not prog.horner and prog.xtimes_ops == 7


# --- the word kernels under the interpreter ------------------------------

L = 512                          # one CRC segment: a whole word program


def _stripes(k, m, n=2, seed=27):
    rs = rs_code(k, m, stored_code(k, m))
    data = np.random.default_rng(seed).integers(0, 256, (n, k, L),
                                                dtype=np.uint8)
    return rs, data, np.stack([np.concatenate([d, rs.encode_ref(d)])
                               for d in data])


@pytest.mark.parametrize("k,m", [(10, 4), (6, 3), (8, 2), (4, 3)])
def test_encode_step_words_matches_oracle(k, m):
    from t3fs.ops.pallas_codec import make_stripe_encode_step_words

    rs, data, full = _stripes(k, m)
    step = make_stripe_encode_step_words(L // 4, k, m, interpret=True)
    parity, crcs = step(data.view(np.uint32))
    parity = np.asarray(parity).view(np.uint8).reshape(len(data), m, L)
    crcs = np.asarray(crcs)
    assert np.array_equal(parity, full[:, k:])
    for i in range(len(data)):
        assert [int(c) for c in crcs[i]] == \
            [crc32c_ref(full[i, s].tobytes()) for s in range(k + m)]


# a seeded sample of the patterns RS(10+4) survives: four data shards,
# a mix of data and parity, all four parity shards, and each single loss
_MIXED = tuple(sorted(int(s) for s in np.random.default_rng(104).choice(
    14, 4, replace=False)))
CAUCHY_LOSSES = [(0, 1, 2, 3), _MIXED, (10, 11, 12, 13)] + [
    (s,) for s in range(14)]


@pytest.mark.parametrize("lost", CAUCHY_LOSSES,
                         ids=lambda t: "-".join(map(str, t)))
def test_cauchy_decode_step_words_matches_oracle(lost):
    from t3fs.ops.pallas_codec import make_stripe_decode_step_words

    k, m = 10, 4
    rs, _data, full = _stripes(k, m, seed=sum(lost))
    present = tuple(s for s in range(k + m) if s not in lost)[:k]
    step = make_stripe_decode_step_words(L // 4, present, lost, k, m,
                                         interpret=True, code=CAUCHY)
    rows = np.ascontiguousarray(full[:, list(present)])
    rebuilt, crcs = step(rows.view(np.uint32))
    rebuilt = np.asarray(rebuilt).view(np.uint8).reshape(len(full),
                                                         len(lost), L)
    crcs = np.asarray(crcs)
    for i in range(len(full)):
        oracle = rs.decode_ref({s: full[i, s] for s in present}, list(lost))
        assert np.array_equal(rebuilt[i], oracle)
        assert np.array_equal(rebuilt[i], full[i, list(lost)])
        assert [int(c) for c in crcs[i]] == \
            [crc32c_ref(full[i, s].tobytes()) for s in present + lost]


def test_ladder_schedule_words_match_oracle():
    """Where parity rows outnumber data shards (RS(2+3) Cauchy), the
    per-input ladder is the cheaper schedule: the word encode and decode
    evaluate it right."""
    from itertools import combinations

    from t3fs.ops.pallas_codec import (gf_words_program,
                                       make_stripe_decode_step_words,
                                       make_stripe_encode_step_words)

    k, m = 2, 3
    rs = rs_code(k, m, CAUCHY)
    assert not gf_words_program(rs, rs.parity_rows).horner
    data = np.random.default_rng(23).integers(0, 256, (2, k, L),
                                              dtype=np.uint8)
    parity, _ = make_stripe_encode_step_words(
        L // 4, k, m, interpret=True, code=CAUCHY)(data.view(np.uint32))
    parity = np.asarray(parity).view(np.uint8).reshape(2, m, L)
    full = np.concatenate([data, parity], axis=1)
    for i in range(2):
        assert np.array_equal(parity[i], rs.encode_ref(data[i]))
    lost = next(
        lost for n in (3, 2, 1) for lost in combinations(range(k + m), n)
        if not gf_words_program(rs, rs.reconstruct_gfmatrix(
            [s for s in range(k + m) if s not in lost][:k], list(lost))).horner)
    present = tuple(s for s in range(k + m) if s not in lost)[:k]
    rebuilt, _ = make_stripe_decode_step_words(
        L // 4, present, lost, k, m, interpret=True, code=CAUCHY)(
            np.ascontiguousarray(full[:, list(present)]).view(np.uint32))
    rebuilt = np.asarray(rebuilt).view(np.uint8).reshape(2, len(lost), L)
    assert np.array_equal(rebuilt, full[:, list(lost)])
