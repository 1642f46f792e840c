"""Kernels: least time of the Pallas GEMMs of the traced decode-only steps
over the device time of their Pallas kernels (percent)."""

from bench import measure


def read(record):
    return measure.gemm_roofline(record, chunk=False)
