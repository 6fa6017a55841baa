"""Per cent of the traced window in which no operation ran on the
device: 1 - (union of the device's operation intervals) / window,
averaged over the devices in the trace."""


def read(r):
    if r.trace is None:
        return None
    return 100.0 * r.trace.idle_share
