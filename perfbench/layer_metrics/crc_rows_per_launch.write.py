"""Payloads per device CRC launch of the storage nodes' batchers over the
measured window (real rows; padding rows not counted)."""


def read(r):
    n = r.counters.get("crc_launches", 0)
    return r.counters["crc_rows"] / n if n else None
