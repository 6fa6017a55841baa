"""Mean time ECCodec's codec thread spends on one encode launch, in ms:
stacking, launch, the result pull and the per-row split (ec.flush spans)."""

from perfbench.span_tags import ENCODE_OPS, ec_flush_ms


def read(r):
    return ec_flush_ms(r, ENCODE_OPS)
