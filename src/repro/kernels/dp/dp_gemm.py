"""Conventional data-parallel tiled GEMM (the paper's comparison baseline),
as a Pallas TPU kernel.

Grid ``(n_region_tiles, iters_per_tile)``: the first dimension walks output
tiles (optionally starting at ``tile_offset`` — that is how the Stream-K++
HYBRID policies run their data-parallel region over tiles the Stream-K sweep
did not claim), the second streams the K dimension. The f32 accumulator
lives in VMEM scratch and is copied into the output block on the last
k-step, so the C dtype can be narrower than the accumulator.

Epilogue operands (bias column vector, binary operand matrix for
swiglu-mul / residual-add) stream in as extra blocked inputs and are applied
to the accumulator in the flush — fused, never a separate HBM pass.

With ``tile_offset > 0`` the kernel runs with ``input_output_aliases`` so the
tiles it does not visit keep the values already present in the aliased C
buffer (the fixed-up Stream-K tiles).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.policies import TileConfig
from repro.core.workpart import cdiv
from repro.core.quant import unpack_int4
from repro.kernels.common import (
    apply_epilogue,
    kernel_name,
    mixed_dot,
    record_launch,
)


def _dp_kernel(
    a_ref,
    b_ref,
    *rest,
    ipt: int,
    epilogue="none",
    has_scale: bool = False,
    has_scale_a: bool = False,
    has_bias: bool = False,
    has_operand: bool = False,
    b_bits: int = 8,
):
    """rest = [scale_ref?, scale_a_ref?, bias_ref?, operand_ref?, c_in_ref?]
    + (c_ref, acc_ref).

    ``b_bits == 4``: the B block arrives packed ``(bk/2, bn)`` (two int4
    nibbles per byte along K) and is unpacked to int8 in the prologue —
    the unpack lives in VMEM, so HBM still only moved half a byte per
    element. ``c_in_ref`` (the aliased C input under ``tile_offset > 0``)
    is never read — aliasing alone preserves unvisited tiles."""
    c_ref, acc_ref = rest[-2], rest[-1]
    extras = list(rest[:-2])
    scale_ref = extras.pop(0) if has_scale else None
    scale_a_ref = extras.pop(0) if has_scale_a else None
    bias_ref = extras.pop(0) if has_bias else None
    operand_ref = extras.pop(0) if has_operand else None

    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)

    b_blk = b_ref[...]
    if b_bits == 4:
        b_blk = unpack_int4(b_blk)
    acc_ref[...] += mixed_dot(a_ref[...], b_blk)

    @pl.when(k == ipt - 1)
    def _flush():
        out = apply_epilogue(
            acc_ref[...],
            epilogue,
            bias=None if bias_ref is None else bias_ref[...],
            operand=None if operand_ref is None else operand_ref[...],
            scale=None if scale_ref is None else scale_ref[...],
            scale_a=None if scale_a_ref is None else scale_a_ref[...],
        )
        c_ref[...] = out.astype(c_ref.dtype)


def dp_gemm_region(
    a,
    b,
    cfg: TileConfig,
    *,
    tile_offset: int = 0,
    c_init=None,
    out_dtype=None,
    interpret: bool = False,
    epilogue="none",
    bias=None,
    operand=None,
    scale=None,
    scale_a=None,
    b_bits: int = 8,
    g: int = 0,
    tag: str = "",
):
    """Tiled GEMM over output tiles [tile_offset, m_tiles*n_tiles).

    a: (Mp, Kp), b: (Kp, Np) — already padded to tile multiples; so are the
    optional epilogue operands ``bias`` (1, Np), ``operand`` (Mp, Np), the
    int8-weight dequant row vector ``scale`` (1, Np) and the int8-activation
    dequant column vector ``scale_a`` (Mp, 1), applied to the accumulator at
    the flush before the other epilogue stages. ``b_bits == 4``: ``b`` is
    int4-packed ``(Kp/2, Np)`` — two nibbles per byte along K, padded to
    ``bk/2`` multiples — and each block is unpacked in the kernel prologue.
    ``c_init``: existing C buffer whose tiles < tile_offset must be kept
    (required iff tile_offset > 0).

    ``g`` > 0 launches the region in whole waves of ``g`` programs (the
    tuned grid size): the tile dimension is padded up to a multiple of ``g``
    and the surplus programs redundantly recompute the final tile (their
    index maps clamp to it, so every write is the same deterministic value).
    This makes wave quantization — what the cost model scores ``g`` on — a
    real property of the launched grid. ``g`` == 0 keeps the exact legacy
    one-program-per-tile grid. ``tag`` goes in front of the kernel's name
    (:func:`repro.kernels.common.kernel_name`).

    Cost of padding: up to ``g - 1`` redundant tile recomputes, and the
    padded tile dim drops to sequential (ARBITRARY) semantics because the
    surplus programs alias the final tile. The analytical model does not
    price that serialization — but on hardware the tuner's
    ``measure_wallclock`` times this exact kernel per swept ``g``, so a
    ``g`` whose padding costs more than its quantization win loses the
    sweep where it matters.
    """
    mp, kp = a.shape
    kp2, np_ = b.shape
    bk_b = cfg.bk // 2 if b_bits == 4 else cfg.bk
    assert kp2 == (kp // 2 if b_bits == 4 else kp), (a.shape, b.shape, b_bits)
    m_tiles, n_tiles = mp // cfg.bm, np_ // cfg.bn
    ipt = kp // cfg.bk
    n_total = m_tiles * n_tiles
    n_region = n_total - tile_offset
    assert n_region > 0, "empty DP region"
    out_dtype = out_dtype or a.dtype
    n_prog = cdiv(n_region, g) * g if g > 0 else n_region

    def tm(i):
        i = jnp.minimum(i, n_region - 1) if n_prog != n_region else i
        return (i + tile_offset) // n_tiles

    def tn(i):
        i = jnp.minimum(i, n_region - 1) if n_prog != n_region else i
        return (i + tile_offset) % n_tiles

    a_spec = pl.BlockSpec((cfg.bm, cfg.bk), lambda i, k: (tm(i), k))
    # packed-int4 B keeps the SAME k-block index map: ceil(ceil(K/2)/(bk/2))
    # == ceil(K/bk) for even bk, so packed block k covers logical k-block k.
    b_spec = pl.BlockSpec((bk_b, cfg.bn), lambda i, k: (k, tn(i)))
    c_spec = pl.BlockSpec((cfg.bm, cfg.bn), lambda i, k: (tm(i), tn(i)))
    scratch = [pltpu.VMEM((cfg.bm, cfg.bn), jnp.float32)]
    # A padded grid clamps its surplus programs onto the final tile, so the
    # tile dim no longer writes disjoint blocks — it must be ARBITRARY
    # (sequential, last identical write wins), not PARALLEL.
    tile_sem = pltpu.ARBITRARY if n_prog != n_region else pltpu.PARALLEL
    params = pltpu.CompilerParams(
        dimension_semantics=(tile_sem, pltpu.ARBITRARY)
    )
    out_shape = jax.ShapeDtypeStruct((mp, np_), out_dtype)

    operands = [a, b]
    in_specs = [a_spec, b_spec]
    if scale is not None:
        operands.append(scale)
        in_specs.append(pl.BlockSpec((1, cfg.bn), lambda i, k: (0, tn(i))))
    if scale_a is not None:
        operands.append(scale_a)
        in_specs.append(pl.BlockSpec((cfg.bm, 1), lambda i, k: (tm(i), 0)))
    if bias is not None:
        operands.append(bias)
        in_specs.append(pl.BlockSpec((1, cfg.bn), lambda i, k: (0, tn(i))))
    if operand is not None:
        operands.append(operand)
        in_specs.append(c_spec)
    kernel = functools.partial(
        _dp_kernel,
        ipt=ipt,
        epilogue=epilogue,
        has_scale=scale is not None,
        has_scale_a=scale_a is not None,
        has_bias=bias is not None,
        has_operand=operand is not None,
        b_bits=b_bits,
    )

    record_launch(f"dp_gemm_{cfg.name}")
    if tile_offset == 0:
        return pl.pallas_call(
            kernel,
            grid=(n_prog, ipt),
            in_specs=in_specs,
            out_specs=c_spec,
            out_shape=out_shape,
            scratch_shapes=scratch,
            interpret=interpret,
            compiler_params=params,
            name=kernel_name(f"dp_gemm_{cfg.name}", tag),
        )(*operands)

    assert c_init is not None, "tile_offset > 0 requires c_init"
    operands.append(c_init.astype(out_dtype))
    in_specs.append(c_spec)
    return pl.pallas_call(
        kernel,
        grid=(n_prog, ipt),
        in_specs=in_specs,
        out_specs=c_spec,
        out_shape=out_shape,
        scratch_shapes=scratch,
        input_output_aliases={len(operands) - 1: 0},
        interpret=interpret,
        compiler_params=params,
        name=kernel_name(f"dp_gemm_region_{cfg.name}", tag),
    )(*operands)
