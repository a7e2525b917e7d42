"""Device: the share of the traced window in which no operation runs on
the chip, 1 - (union of device op intervals / window), in %."""


def read(w):
    if w.trace is None:
        return None
    return 100.0 * w.trace.idle_share
