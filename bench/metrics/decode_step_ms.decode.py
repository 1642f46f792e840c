"""Model step: device busy time per traced step that decodes and carries no
prefill chunk (ms)."""

from bench import measure


def read(record):
    return measure.step_ms(record, chunk=False)
