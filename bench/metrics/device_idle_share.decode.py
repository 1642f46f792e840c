"""Device: share of the traced window with no operation on the chip
(percent)."""

from bench import measure


def read(record):
    return measure.idle_share(record)
