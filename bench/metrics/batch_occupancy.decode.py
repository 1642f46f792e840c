"""Scheduler: requests that gained a token per decode step over the slots,
counted by the benchmark's loop over the window's decode steps (percent)."""

from bench import measure


def read(record):
    return measure.batch_occupancy(record)
