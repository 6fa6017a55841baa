"""Mean wait of one stripe (encode) in ECCodec's batcher, from submit
to the start of its flush on the codec thread, in ms (ec.flush spans)."""

from perfbench.span_tags import ENCODE_OPS, ec_queue_ms


def read(r):
    return ec_queue_ms(r, ENCODE_OPS)
