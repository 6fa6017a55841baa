"""Stripes per ECCodec device launch over the measured window (real
rows; padding rows not counted)."""


def read(r):
    n = r.counters.get("ec_launches", 0)
    return r.counters["ec_rows"] / n if n else None
