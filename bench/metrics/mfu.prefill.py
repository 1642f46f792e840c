"""Model step: model FLOPs that the traced steps carrying a prefill chunk
needed (the chunk and the rows decoded beside it), over their device busy
time, as a share of the chip's bf16 peak (percent)."""

from bench import measure


def read(record):
    return measure.mfu(record, chunk=True)
