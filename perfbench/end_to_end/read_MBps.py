"""User bytes returned by successful reads over the measured window, in
MB/s (10^6 bytes): all the work and all the time of the window."""

from perfbench.readings import mb_per_s


def read(r):
    return mb_per_s(r, "read")
