"""Mean engine_s of the Storage.batch_read server spans, in ms: the
summed time of the request's chunk reads (chain check and engine read)."""

from perfbench.readings import READ_SPANS
from perfbench.span_tags import tag_mean_ms


def read(r):
    return tag_mean_ms(r.server_spans(READ_SPANS), "engine_s")
