"""Bytes each codec operation needs to move through HBM, from its shapes.

A share of the roofline counts the work the operation needs, whatever
implements it: each input byte read once and each output byte written
once, for real rows only (zero rows a batcher pads with are waste, not
work).  These codec kernels run integer ops on the VPU, for which v5e
publishes no peak, so HBM bandwidth is the only roofline they have and
every share here is bound by bytes.

  crc32c     one payload of L bytes in, one 4-byte CRC out
  encode     RS(k+m) fused encode+CRC (`encv`): k*L in, m*L parity and
             4*(k+m) CRC bytes out
  decode     fused decode+verify (`recv`), `w` shards rebuilt from k
             present: k*L in, w*L and 4*(k+w) CRC bytes out
"""

from __future__ import annotations

import json
import os

CRC_BYTES = 4
PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks_for(device_kind: str) -> dict:
    """The chip's published peaks (peaks.json, with their source).  A
    device that is not in the table is an error, not a default."""
    with open(PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS}; add them with their source")
    return table[device_kind]


def crc32c_bytes(payload_len: int) -> int:
    return payload_len + CRC_BYTES


def encode_bytes(k: int, m: int, chunk_len: int) -> int:
    return k * chunk_len + m * chunk_len + CRC_BYTES * (k + m)


def decode_bytes(k: int, rebuilt: int, chunk_len: int) -> int:
    return k * chunk_len + rebuilt * chunk_len + CRC_BYTES * (k + rebuilt)


def roofline_share(needed_bytes: float, hbm_bytes_per_s: float,
                   device_s: float) -> float | None:
    """Per cent of the HBM roofline: the least time the chip could take
    to move the bytes, over the device time the program took.  None when
    the program did not run in the traced window."""
    if device_s <= 0 or needed_bytes <= 0:
        return None
    return 100.0 * needed_bytes / hbm_bytes_per_s / device_s
