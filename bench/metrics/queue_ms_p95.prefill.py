"""Scheduler: 95th percentile of the wait from a request's due time to the
step after which it is in ``engine.active`` (ms), over the window's requests."""

from bench import measure


def read(record):
    return measure.queue_p95_ms(record)
