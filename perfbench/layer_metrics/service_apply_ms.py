"""Mean apply_s of the write path's server spans, at every replica and
shard target, in ms: the local CRC plus the engine apply of one update."""

from perfbench.readings import WRITE_SPANS, mean_ms


def read(r):
    return mean_ms([s["tags"]["apply_s"] for s in r.server_spans(WRITE_SPANS)
                    if "apply_s" in s["tags"]])
