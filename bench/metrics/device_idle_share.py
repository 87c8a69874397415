"""Device: 1 - (union of device op intervals) / traced window, in %."""


def read(r):
    return 100.0 * r.trace.idle_share
