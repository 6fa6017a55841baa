"""Seconds from process start to the first timed request: data, warm-up
and compile, cluster start, fill and any fault the mix injects."""


def read(r):
    return r.setup_s
