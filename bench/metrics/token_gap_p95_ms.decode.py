"""Scheduler: 95th percentile of all gaps between successive tokens of a
request inside the window (ms), in a closed loop at full load, where the
gaps are a per-layer reading and tokens/s is the end-to-end one."""

from bench import measure


def read(record):
    return measure.tpot_p95_ms(record)
