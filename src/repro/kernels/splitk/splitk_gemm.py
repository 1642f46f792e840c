"""Split-K GEMM Pallas kernel — the pre-Stream-K strategy (§2 of the paper):
the K dimension is split by a *fixed* factor ``s`` and each split's partial
C is reduced afterwards. Stream-K generalises this (the split adapts to the
work instead of being a fixed hyper-parameter); it is implemented here as a
baseline the benchmarks compare against.

Grid ``(m_tiles * n_tiles, s, k_per_split)``: each (tile, split) pair
accumulates its K-range into ``partials[s]``; the wrapper reduces over
``s`` (a tiny XLA reduction, exactly the "separate partial result
accumulation step" the paper describes split-K needing).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.policies import TileConfig
from repro.core.quant import unpack_int4
from repro.core.workpart import cdiv
from repro.kernels.common import mixed_dot, record_launch


def _splitk_kernel(a_ref, b_ref, p_ref, acc_ref, *, kps: int, b_bits: int = 8):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)

    b_blk = b_ref[...]
    if b_bits == 4:
        # packed (bk/2, bn) int4 block -> (bk, bn) int8 in the prologue
        b_blk = unpack_int4(b_blk)
    acc_ref[...] += mixed_dot(a_ref[...], b_blk)

    @pl.when(k == kps - 1)
    def _flush():
        p_ref[0] = acc_ref[...]


def splitk_partials(
    a,
    b,
    cfg: TileConfig,
    s: int,
    *,
    interpret: bool = False,
    g: int = 0,
    b_bits: int = 8,
):
    """Returns partials (s, Mp, Np) f32; caller reduces over axis 0.

    a, b already padded; K must split into s * k_per_split * bk.
    ``b_bits == 4``: ``b`` is int4-packed (Kp/2, Np) and each block is
    unpacked in the kernel prologue (same k-block index map — the packed
    block count equals the logical one for even bk).
    ``g`` > 0 pads the tile dimension up to whole waves of ``g`` programs
    (surplus programs redundantly recompute the last tile — deterministic,
    same value); 0 keeps the exact legacy one-program-per-tile grid.
    """
    mp, kp = a.shape
    _, np_ = b.shape
    bk_b = cfg.bk // 2 if b_bits == 4 else cfg.bk
    m_tiles, n_tiles = mp // cfg.bm, np_ // cfg.bn
    ipt = kp // cfg.bk
    assert ipt % s == 0, "split factor must divide k-iterations"
    kps = ipt // s
    n_total = m_tiles * n_tiles
    n_prog = cdiv(n_total, g) * g if g > 0 else n_total

    def tm(i):
        i = jnp.minimum(i, n_total - 1) if n_prog != n_total else i
        return i // n_tiles

    def tn(i):
        i = jnp.minimum(i, n_total - 1) if n_prog != n_total else i
        return i % n_tiles

    record_launch(f"splitk_gemm_{cfg.name}_s{s}")
    return pl.pallas_call(
        functools.partial(_splitk_kernel, kps=kps, b_bits=b_bits),
        grid=(n_prog, s, kps),
        in_specs=[
            pl.BlockSpec((cfg.bm, cfg.bk), lambda i, sp, k: (tm(i), sp * kps + k)),
            pl.BlockSpec((bk_b, cfg.bn), lambda i, sp, k: (sp * kps + k, tn(i))),
        ],
        out_specs=pl.BlockSpec(
            (1, cfg.bm, cfg.bn), lambda i, sp, k: (sp, tm(i), tn(i))
        ),
        out_shape=jax.ShapeDtypeStruct((s, mp, np_), jnp.float32),
        scratch_shapes=[pltpu.VMEM((cfg.bm, cfg.bn), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            # surplus programs of a padded grid alias the final tile's
            # partials slot: that dim must drop to ARBITRARY (see dp_gemm)
            dimension_semantics=(
                pltpu.ARBITRARY if n_prog != n_total else pltpu.PARALLEL,
                pltpu.PARALLEL,
                pltpu.ARBITRARY,
            )
        ),
        name=f"splitk_gemm_{cfg.name}_s{s}",
    )(a, b)
