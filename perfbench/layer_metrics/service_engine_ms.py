"""Mean engine_s of the write path's server spans, in ms: the await on
the target's update thread for the engine apply of one update."""

from perfbench.readings import WRITE_SPANS
from perfbench.span_tags import tag_mean_ms


def read(r):
    return tag_mean_ms(r.server_spans(WRITE_SPANS), "engine_s")
