"""99th percentile oversleep of a 10 ms ticker on the event loop that
every node, client and heartbeat shares, over the measured window, in ms."""

import numpy as np


def read(r):
    if not r.loop_lag:
        return None
    return float(np.percentile(r.loop_lag, 99)) * 1e3
