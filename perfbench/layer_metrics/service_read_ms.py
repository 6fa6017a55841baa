"""Mean duration of the Storage.batch_read server spans, in ms."""

from perfbench.readings import READ_SPANS, mean_ms


def read(r):
    return mean_ms([s["dur_s"] for s in r.server_spans(READ_SPANS)])
