"""Stream-K++ GEMM as Pallas TPU kernels (Algorithm 1, TPU-adapted).

Two kernels compose one GEMM:

**Phase 1 — work-centric sweep** (``_streamk_kernel``). The Pallas grid is
``(g, iters_per_wg)``: program row ``x`` is one persistent workgroup of
Algorithm 1, step ``j`` is one flattened MAC iteration of its contiguous
range ``[x*ipw, min((x+1)*ipw, total))``. The BlockSpec index maps perform
Algorithm 1 lines 9-12 *in the index computation*: flattened iteration ->
(output tile, local k-iter) -> (A block row, k block) / (k block, B block
col). The f32 accumulator lives in the *output block* and exploits Pallas
revisiting semantics: consecutive steps of one program that land in the same
output tile keep the block in VMEM; the block is flushed to HBM exactly when
the program crosses a tile boundary — the TPU-idiomatic replacement for the
paper's per-tile epilogue.

Partial tiles: a GPU Stream-K workgroup resolves split tiles with
``atomic_add`` (Algorithm 1 line 17). TPUs have no HBM float atomics, so we
use the deterministic two-phase reduction the paper itself recommends in
§5.3: every contributor writes its partial accumulator to a workspace slot
``partials[tile, x - first_wg(tile)]`` — slots are disjoint by construction
because workgroup ranges are contiguous and sorted, so no synchronisation is
needed at all.

**Phase 2 — fix-up reduction** (``_fixup_kernel``). Grid ``(sk_tiles,)``;
tile ``t`` masks-and-sums its contributor slots (the count is pure integer
math on ``t``, computed in-kernel) and writes the final C tile. Data-parallel
region tiles (``tile >= sk_tiles`` under HYBRID policies) never touch the
workspace: a third classic tiled kernel (``dp`` package) handles them
directly, scheduled after the Stream-K sweep so its compute overlaps the
fix-up traffic (§4.1 of the paper).

Numerics: inputs bf16/f32, accumulation f32 (`preferred_element_type`), C in
the caller's dtype. Deterministic: unlike GPU atomics, the reduction order is
fixed, so results are bit-reproducible run-to-run.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quant import unpack_int4
from repro.core.workpart import Partition, cdiv
from repro.kernels.common import (
    apply_epilogue,
    kernel_name,
    mixed_dot,
    record_launch,
)


def _range_math(part: Partition):
    """Static integers the index maps close over."""
    ipt = part.iters_per_tile
    total = part.sk_total_iters
    ipw = cdiv(total, part.g) if total else 1
    mc = part.max_contributors
    return ipt, total, ipw, mc


def _flat_iter(x, j, ipw, total):
    """Clamped flattened iteration for grid point (x, j)."""
    it = x * ipw + j
    return jnp.minimum(it, total - 1)


# --------------------------------------------------------------------------
# Phase 1: the Stream-K sweep
# --------------------------------------------------------------------------


def _streamk_kernel(a_ref, b_ref, partials_ref, *, part: Partition, b_bits: int = 8):
    ipt, total, ipw, mc = _range_math(part)
    x = pl.program_id(0)
    j = pl.program_id(1)
    it_raw = x * ipw + j
    my_end = jnp.minimum((x + 1) * ipw, total)
    valid = it_raw < my_end

    it = jnp.minimum(it_raw, total - 1)
    local_k = it % ipt

    # Fresh tile for this program: first step of the program or first k-iter
    # of a tile inside its range. Trash steps (invalid) also re-init — they
    # only ever touch the dedicated trash slot.
    is_start = jnp.logical_or(j == 0, local_k == 0)

    @pl.when(is_start)
    def _init():
        partials_ref[...] = jnp.zeros(partials_ref.shape, partials_ref.dtype)

    @pl.when(valid)
    def _mac():
        b_blk = b_ref[...]
        if b_bits == 4:
            # packed (bk/2, bn) int4 block -> (bk, bn) int8 in the prologue
            b_blk = unpack_int4(b_blk)
        acc = mixed_dot(a_ref[...], b_blk)
        partials_ref[...] += acc[None, None]


def _sk_block_indices(x, j, part: Partition):
    """(tile, slot) for grid point (x, j); invalid steps -> trash slot."""
    ipt, total, ipw, mc = _range_math(part)
    it_raw = x * ipw + j
    my_end = jnp.minimum((x + 1) * ipw, total)
    valid = it_raw < my_end
    it = jnp.minimum(it_raw, total - 1)
    tile = it // ipt
    first_wg = (tile * ipt) // ipw
    slot = jnp.clip(x - first_wg, 0, mc - 1)
    tile = jnp.where(valid, tile, part.sk_tiles - 1)
    slot = jnp.where(valid, slot, mc)  # trash slot
    return tile, slot


def streamk_phase1(
    a, b, part: Partition, *, interpret: bool = False, b_bits: int = 8, tag: str = ""
):
    """Run the Stream-K sweep; returns partials[sk_tiles, mc+1, bm, bn] f32.

    ``a``/``b`` must already be padded to tile multiples. ``b_bits == 4``:
    ``b`` is int4-packed (Kp/2, Np), padded to ``bk/2`` multiples, and the
    kernel unpacks each block in its prologue (the packed k-block count
    equals the logical one for even bk, so the index maps are unchanged).
    ``tag`` goes in front of the kernel's name (``kernel_name``).
    """
    cfg = part.cfg
    ipt, total, ipw, mc = _range_math(part)
    assert part.sk_tiles > 0
    bk_b = cfg.bk // 2 if b_bits == 4 else cfg.bk

    def a_index(x, j):
        tile, _ = _sk_block_indices(x, j, part)
        it = _flat_iter(x, j, ipw, total)
        return (tile // part.n_tiles, it % ipt)

    def b_index(x, j):
        tile, _ = _sk_block_indices(x, j, part)
        it = _flat_iter(x, j, ipw, total)
        return (it % ipt, tile % part.n_tiles)

    def out_index(x, j):
        tile, slot = _sk_block_indices(x, j, part)
        return (tile, slot, 0, 0)

    out_shape = jax.ShapeDtypeStruct(
        (part.sk_tiles, mc + 1, cfg.bm, cfg.bn), jnp.float32
    )
    kernel = functools.partial(_streamk_kernel, part=part, b_bits=b_bits)
    record_launch(f"streamk_p1_{cfg.name}_g{part.g}")
    return pl.pallas_call(
        kernel,
        grid=(part.g, ipw),
        in_specs=[
            pl.BlockSpec((cfg.bm, cfg.bk), a_index),
            pl.BlockSpec((bk_b, cfg.bn), b_index),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, cfg.bm, cfg.bn), out_index
        ),
        out_shape=out_shape,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.PARALLEL, pltpu.ARBITRARY),
        ),
        name=kernel_name(f"streamk_p1_{cfg.name}_g{part.g}", tag),
    )(a, b)


# --------------------------------------------------------------------------
# Phase 2: deterministic fix-up reduction
# --------------------------------------------------------------------------


def _fixup_kernel(
    partials_ref,
    *rest,
    part: Partition,
    epilogue="none",
    has_scale: bool = False,
    has_scale_a: bool = False,
    has_bias: bool = False,
    has_operand: bool = False,
):
    """rest = [scale_ref?, scale_a_ref?, bias_ref?, operand_ref?] + (c_ref,)."""
    c_ref = rest[-1]
    extras = list(rest[:-1])
    scale_ref = extras.pop(0) if has_scale else None
    scale_a_ref = extras.pop(0) if has_scale_a else None
    bias_ref = extras.pop(0) if has_bias else None
    operand_ref = extras.pop(0) if has_operand else None
    ipt, total, ipw, mc = _range_math(part)
    t = pl.program_id(0)
    first_wg = (t * ipt) // ipw
    last_wg = ((t + 1) * ipt - 1) // ipw
    n_contrib = last_wg - first_wg + 1
    # Mask garbage slots (>= n_contrib) before reducing. (2-D iota: TPU has
    # no 1-D iota.)
    n_slots = partials_ref.shape[1]
    slots = jax.lax.broadcasted_iota(jnp.int32, (n_slots, 1, 1), 0)
    mask = slots < n_contrib
    acc = jnp.sum(
        jnp.where(mask, partials_ref[0], 0.0), axis=0, dtype=jnp.float32
    )
    out = apply_epilogue(
        acc,
        epilogue,
        bias=None if bias_ref is None else bias_ref[...],
        operand=None if operand_ref is None else operand_ref[...],
        scale=None if scale_ref is None else scale_ref[...],
        scale_a=None if scale_a_ref is None else scale_a_ref[...],
    )
    c_ref[0] = out.astype(c_ref.dtype)


def streamk_fixup(
    partials, part: Partition, out_dtype, *, interpret: bool = False,
    epilogue="none", bias=None, operand=None, scale=None, scale_a=None,
    tag: str = "",
):
    """Reduce contributor slots per SK tile -> C tiles, shaped
    (sk_tiles, bm, bn). The epilogue (activation, bias-add, swiglu-mul /
    residual operand) fuses here — after the full accumulation — so it costs
    no extra HBM pass; an int8-weight op's dequant ``scale`` (1, Np) and an
    int8-activation op's per-row ``scale_a`` (Mp, 1) apply to the reduced
    accumulator first — together the rank-1 rescale of an int8xint8 op (see
    ``apply_epilogue``). ``bias`` (1, Np) / ``operand`` (Mp, Np) are padded
    full-size arrays; their blocks are gathered per SK tile in row-major
    tile order (matching ``_scatter_sk_tiles``). ``tag`` goes in front of
    the kernel's name (``kernel_name``)."""
    cfg = part.cfg
    nt = part.n_tiles
    kernel = functools.partial(
        _fixup_kernel,
        part=part,
        epilogue=epilogue,
        has_scale=scale is not None,
        has_scale_a=scale_a is not None,
        has_bias=bias is not None,
        has_operand=operand is not None,
    )
    operands = [partials]
    in_specs = [
        pl.BlockSpec(
            (1, partials.shape[1], cfg.bm, cfg.bn), lambda t: (t, 0, 0, 0)
        )
    ]
    if scale is not None:
        operands.append(scale)
        in_specs.append(pl.BlockSpec((1, cfg.bn), lambda t: (0, t % nt)))
    if scale_a is not None:
        operands.append(scale_a)
        in_specs.append(pl.BlockSpec((cfg.bm, 1), lambda t: (t // nt, 0)))
    if bias is not None:
        operands.append(bias)
        in_specs.append(pl.BlockSpec((1, cfg.bn), lambda t: (0, t % nt)))
    if operand is not None:
        operands.append(operand)
        in_specs.append(
            pl.BlockSpec((cfg.bm, cfg.bn), lambda t: (t // nt, t % nt))
        )
    record_launch(f"streamk_fixup_{cfg.name}")
    return pl.pallas_call(
        kernel,
        grid=(part.sk_tiles,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, cfg.bm, cfg.bn), lambda t: (t, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(
            (part.sk_tiles, cfg.bm, cfg.bn), out_dtype
        ),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.PARALLEL,),
        ),
        name=kernel_name(f"streamk_fixup_{cfg.name}", tag),
    )(*operands)
