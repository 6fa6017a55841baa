"""Mean frame_s of the write path's server spans, in ms: from the read
loop having a frame's header to its dispatch (the body read, its CRC
check and serde.loads)."""

from perfbench.readings import WRITE_SPANS
from perfbench.span_tags import tag_mean_ms


def read(r):
    return tag_mean_ms(r.server_spans(WRITE_SPANS), "frame_s")
