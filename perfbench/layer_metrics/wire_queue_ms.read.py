"""Mean wire_s + queue_s of the Storage.batch_read server spans, in ms."""

from perfbench.readings import READ_SPANS, mean_ms


def read(r):
    return mean_ms([s["tags"]["wire_s"] + s["tags"]["queue_s"]
                    for s in r.server_spans(READ_SPANS)])
