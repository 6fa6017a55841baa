"""Mean wire_s + queue_s of the write path's server spans (the client's
call to the head and every forward down a chain), in ms: time on the wire
(both clocks) and waiting for the serving loop."""

from perfbench.readings import WRITE_SPANS, mean_ms


def read(r):
    return mean_ms([s["tags"]["wire_s"] + s["tags"]["queue_s"]
                    for s in r.server_spans(WRITE_SPANS)])
