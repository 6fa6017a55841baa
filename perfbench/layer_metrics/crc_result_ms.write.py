"""Mean time from a device CRC row's launch to its caller resuming, in
ms: the double-buffer hold, H2D, kernel, D2H and the hop back to the loop
(crc_result_s of the write path's server spans)."""

from perfbench.readings import WRITE_SPANS
from perfbench.span_tags import tag_mean_ms


def read(r):
    return tag_mean_ms(r.server_spans(WRITE_SPANS), "crc_result_s")
