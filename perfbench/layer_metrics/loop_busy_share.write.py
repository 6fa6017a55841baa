"""Per cent of the shared event loop's wall time spent running callbacks
rather than waiting in select(), over the window (the loop meter's
busy_s / (busy_s + wait_s), from the running totals its loop.wait spans
carry)."""

from perfbench.span_tags import loop_busy_share


def read(r):
    return loop_busy_share(r)
