"""Scheduler: the decode step's host work around its device program,
preparing the batch (``engine.decode.prepare``) and sampling its tokens
(``engine.sample``), from the program's own spans in the traced window:
the median over the decode-only steps (ms)."""

from bench import program_spans


def read(record):
    return program_spans.decode_host_ms(record.program)
