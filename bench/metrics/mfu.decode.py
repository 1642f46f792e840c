"""Model step: model FLOPs that the traced decode-only steps needed, over
their device busy time, as a share of the chip's bf16 peak (percent)."""

from bench import measure


def read(record):
    return measure.mfu(record, chunk=False)
