"""The comparison that decides `correct`.

Every number here is a count of comparisons against the plain reference
(perfbench.reference) that came out wrong; each has the limit 0, since
the comparison is exact.  Copies are what the nodes store once the window
has closed, read straight from each node: every replica of a CRAQ chunk,
all k+m shards (parity included) of an RS(k+m) stripe, its parity that of
the code the configuration names (`ec_code`).  Answers are the bytes and
CRC32Cs that reads returned in the window (a seeded sample).
"""

from __future__ import annotations

from collections import Counter

from perfbench import reference

LIMITS = {
    "requests_failed": 0,
    "copies_missing": 0,
    "copies_bytes_wrong": 0,
    "copies_crc_wrong": 0,
    "answers_bytes_wrong": 0,
    "answers_crc_wrong": 0,
}


def ec_code(cfg: dict) -> str:
    """The parity code an EC configuration names under `code`.  Without
    the key, m = 2 means RAID-6 P and Q, what the first EC configurations
    store; any other m has to name its code."""
    code = cfg.get("code")
    if code is None:
        if cfg["m"] != 2:
            raise ValueError(
                f"an RS({cfg['k']}+{cfg['m']}) configuration has to name its "
                f"parity under the key `code`, one of {reference.CODES}")
        code = reference.RAID6
    if code not in reference.CODES:
        raise ValueError(f"the configuration's `code` {code!r} is none of "
                         f"{reference.CODES}")
    return code


def expected_chunks(cfg: dict, content: bytes) -> list[bytes]:
    """What each stored copy of an object holds: the same bytes on every
    replica, or the stripe's data and parity shards."""
    if cfg["layout"] == "ec":
        return reference.stripe_shards(content, cfg["k"], cfg["m"],
                                       ec_code(cfg))
    return [content] * cfg["replicas"]


def check_copies(cfg: dict, copies: dict, book, payloads) -> Counter:
    """Compare every stored copy of every acknowledged object with each
    version it may hold (the newest acknowledged, or a later write that
    failed and may have landed)."""
    out = Counter()
    for obj, got in copies.items():
        options = [expected_chunks(cfg, payloads.make(obj, v))
                   for v in book.candidates(obj)]
        crcs = [[reference.crc32c(c) for c in exp] for exp in options]
        for i, copy in enumerate(got):
            out["copies_checked"] += 1
            ok, data, checksum = copy
            if not ok:
                out["copies_missing"] += 1
                continue
            match = [j for j, exp in enumerate(options) if exp[i] == data]
            if not match:
                out["copies_bytes_wrong"] += 1
            elif all(crcs[j][i] != checksum for j in match):
                out["copies_crc_wrong"] += 1
    return out


def check_answers(cfg: dict, answers: list, book, payloads) -> Counter:
    """Compare sampled read answers (obj, bytes, per-chunk CRC32C) with the
    object's filled content (read groups never overwrite it)."""
    out = Counter()
    cs = cfg["chunk_size"]
    for obj, data, crcs in answers:
        out["answers_checked"] += 1
        (v,) = book.candidates(obj)
        want = payloads.make(obj, v)
        if data != want:
            out["answers_bytes_wrong"] += 1
            continue
        # a read reports the CRC of each chunk it returns: a stored
        # checksum, or the CRC that a device decode computed
        want_crcs = [reference.crc32c(want[i:i + cs])
                     for i in range(0, len(want), cs)]
        if list(crcs) != want_crcs:
            out["answers_crc_wrong"] += 1
    return out


def judge(counts: Counter) -> tuple[bool, dict]:
    """(correct, {name: {"value": n, "limit": l}}): every number within
    its limit, and something was compared."""
    checks = {name: {"value": int(counts.get(name, 0)), "limit": limit}
              for name, limit in LIMITS.items()}
    compared = counts.get("copies_checked", 0) + counts.get(
        "answers_checked", 0)
    correct = compared > 0 and all(c["value"] <= c["limit"]
                                   for c in checks.values())
    return correct, checks
