"""Mean wait of one device CRC row in its node's batcher, from
payload_crc to its launch, in ms (crc_queue_s of the write path's server
spans)."""

from perfbench.readings import WRITE_SPANS
from perfbench.span_tags import tag_mean_ms


def read(r):
    return tag_mean_ms(r.server_spans(WRITE_SPANS), "crc_queue_s")
