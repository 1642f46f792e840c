"""Shared helpers for the Pallas GEMM kernels: padding, the fused epilogue
applier, mixed-dtype MACs, and trace-time ``pallas_call`` launch counting
(how tests assert the fused grouped path really issues ONE kernel for all G
expert groups)."""

from __future__ import annotations

from contextlib import contextmanager
from typing import Iterator, List, Optional

import jax.numpy as jnp

from repro.core.workpart import cdiv

#: active launch log (None when counting is off); see :func:`count_launches`.
_launch_log: Optional[List[str]] = None


def kernel_name(base: str, tag: str = "") -> str:
    """A ``pallas_call`` name: the kernel's own (which names its tile),
    after the dispatcher's GEMM tag when one is given, so that a profile
    tells the GEMMs of one shape apart (``mlp_gate__dp_gemm_64x128x256``).
    Kernels of one shape and tile under two tags are two kernels."""
    return f"{tag}__{base}" if tag else base


def record_launch(name: str) -> None:
    """Note one ``pallas_call`` built by a kernel wrapper.

    Called at *trace time* (when the wrapper function body runs under jit
    tracing), so it counts launches per compiled executable — the trace/
    launch cost the dispatcher pays — not per device invocation. No-op
    unless a :func:`count_launches` scope is active. Because jit caches
    traces, a wrapper re-invoked at an identical static signature does not
    re-trace: counting tests use fresh shapes or ``jax.clear_caches()``."""
    if _launch_log is not None:
        _launch_log.append(name)


@contextmanager
def count_launches() -> Iterator[List[str]]:
    """Collect kernel-launch names traced within the scope.

    >>> with count_launches() as launches:
    ...     jax.eval_shape(fn, *args)   # or run fn; tracing records
    >>> len(launches)
    """
    global _launch_log
    prev = _launch_log
    _launch_log = log = []
    try:
        yield log
    finally:
        _launch_log = prev


def pad_to(x, mults):
    """Zero-pad each dim of ``x`` up to a multiple of ``mults``. Zero padding
    is exact for GEMM (contributes 0 to every dot product)."""
    pads = []
    needs = False
    for dim, mult in zip(x.shape, mults):
        target = cdiv(dim, mult) * mult
        pads.append((0, target - dim))
        needs = needs or target != dim
    return jnp.pad(x, pads) if needs else x


def unpad(x, shape):
    """Slice back to an original (unpadded) shape."""
    if tuple(x.shape) == tuple(shape):
        return x
    slices = tuple(slice(0, d) for d in shape)
    return x[slices]


import jax

from repro.core.op import Epilogue, as_epilogue


def apply_epilogue(acc, epilogue, bias=None, operand=None, scale=None, scale_a=None):
    """Epilogue applied to the f32 accumulator before the final cast/store —
    the Composable-Kernel-style fusion the paper's library is built from (CK
    composes GEMM + epilogue functors; ours compose the same way on the
    fix-up/flush path, so the epilogue costs zero extra HBM round-trips).

    ``epilogue`` is an :class:`repro.core.op.Epilogue` (legacy bare
    activation strings still accepted). ``bias``/``operand`` are the already
    block-sliced extra inputs for bias-add and binary (swiglu-mul /
    residual-add) epilogues. ``scale`` is the per-output-channel dequant
    row vector of an int8-weight op (see :mod:`repro.core.quant`): it
    multiplies the raw accumulator FIRST — restoring the real-valued
    product ``(A @ V) * s == A @ (V * s)`` — so bias/activation/binary
    stages compose on dequantized values exactly as they do for dense
    weights. ``scale_a`` is the per-M-row activation dequant column vector
    of an int8xint8 op: applied alongside ``scale`` it forms the rank-1
    rescale ``s_a (x) s_b`` on the raw integer-accumulated product.
    """
    spec: Epilogue = as_epilogue(epilogue)
    if scale_a is not None:
        acc = acc * scale_a.astype(jnp.float32)
    if scale is not None:
        acc = acc * scale.astype(jnp.float32)
    return spec.apply(acc, bias=bias, operand=operand)


def prep_scale(scale, n, bn):
    """Per-output-channel dequant vector -> the padded (1, Np) f32 row the
    flush/fix-up kernels block-slice (one definition of the layout for all
    three kernel families). ``scale``: (N,) or (1, N)."""
    if scale is None:
        return None
    return pad_to(scale.reshape(1, n).astype(jnp.float32), (1, bn))


def prep_scale_a(scale_a, m, bm):
    """Per-M-row activation dequant vector -> the padded (Mp, 1) f32 column
    the flush/fix-up kernels block-slice as ``(bm, 1)`` tiles (the rank-1
    partner of :func:`prep_scale`'s row). ``scale_a``: (M,) or (M, 1)."""
    if scale_a is None:
        return None
    return pad_to(scale_a.reshape(m, 1).astype(jnp.float32), (bm, 1))


def mixed_dot(a_blk, b_blk):
    """One k-iteration MAC handling mixed activation x weight dtypes.

    Same-dtype float blocks keep the legacy MXU path (bf16 x bf16 /
    f32 x f32, f32 accumulation) bit-for-bit. Both-integer blocks — int8
    activations against int8 weights — accumulate on the integer MXU path
    (``preferred_element_type=int32``) and convert the k-step partial to
    f32: each partial is bounded by ``bk * 127^2`` (<= 16.5M for the
    largest bk=1024 tile), well under both int32 range and f32's 2^24
    exact-integer window, so the conversion is exact and the f32
    accumulator chain stays identical to the float families'. Mixed blocks
    — f32/bf16 activations against int8 weight tiles — widen both operands
    to f32 in VMEM before the dot: the int8 tile already paid its 1-byte
    HBM fare (the point of weight quantization), and int8 -> f32
    conversion is exact, so the MAC is numerically the dense f32 MAC on
    dequant-without-scale values."""
    if jnp.issubdtype(a_blk.dtype, jnp.integer) and jnp.issubdtype(
        b_blk.dtype, jnp.integer
    ):
        return jnp.dot(a_blk, b_blk, preferred_element_type=jnp.int32).astype(
            jnp.float32
        )
    if a_blk.dtype != b_blk.dtype:
        a_blk = a_blk.astype(jnp.float32)
        b_blk = b_blk.astype(jnp.float32)
    return jnp.dot(a_blk, b_blk, preferred_element_type=jnp.float32)
