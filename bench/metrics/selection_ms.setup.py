"""Selection: host time inside ``KernelSelector.select_op`` during set-up
(ms), timed by the benchmark's wrapper around the selector."""

from bench import measure


def read(record):
    return measure.selection_ms(record)
