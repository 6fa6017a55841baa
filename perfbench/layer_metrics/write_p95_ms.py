"""95th percentile latency of every write issued in the window, from
issue to reply, in ms (host clock, traced run)."""

from perfbench.readings import latency_ms


def read(r):
    return latency_ms(r, "write", 95)
