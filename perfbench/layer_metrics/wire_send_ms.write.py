"""Mean send_s of the write path's client RPC spans (the client's call to
the head and every forward down a chain), in ms: serde, the envelope
CRC, the wait on the connection's send lock, write and drain."""

from perfbench.span_tags import WRITE_CLIENT_SPANS, client_spans, tag_mean_ms


def read(r):
    return tag_mean_ms(client_spans(r, WRITE_CLIENT_SPANS), "send_s")
