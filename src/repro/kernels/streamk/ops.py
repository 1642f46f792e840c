"""jit'd public wrapper for the Stream-K++ GEMM.

Composes the policy's phases (§4.1 of the paper):
  1. Stream-K sweep over the SK region (``streamk_phase1``),
  2. deterministic fix-up writing SK tiles into C (``streamk_fixup``,
     in-place via input/output aliasing),
  3. data-parallel region over remaining tiles (``dp_gemm_region``, aliased
     into the same C) — on hardware this phase overlaps the fix-up traffic.

Also owns padding (inputs are zero-padded to tile multiples — exact for
GEMM) and policy routing: a DP policy skips phases 1-2 entirely; ALL_SK has
no phase 3.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.policies import DP, Policy, PolicyKind, TileConfig
from repro.core.workpart import GemmShape, cdiv, partition
from repro.kernels.common import pad_to, prep_scale, prep_scale_a, unpad
from repro.kernels.dp.dp_gemm import dp_gemm_region
from repro.kernels.streamk.streamk_gemm import streamk_fixup, streamk_phase1


def _scatter_sk_tiles(sk_tiles_out, part, out_dtype, interpret):
    """Write fixed-up SK tiles into a fresh padded C via the fix-up kernel's
    aliasing path; here done with pure reshapes (no data-dependent scatter):
    tile t -> C[tm*bm:(tm+1)*bm, tn*bn:(tn+1)*bn] in row-major tile order."""
    cfg = part.cfg
    mt, nt = part.m_tiles, part.n_tiles
    n_total = mt * nt
    pad_tiles = n_total - part.sk_tiles
    grid = sk_tiles_out
    if pad_tiles:
        grid = jnp.concatenate(
            [grid, jnp.zeros((pad_tiles, cfg.bm, cfg.bn), grid.dtype)], axis=0
        )
    c = grid.reshape(mt, nt, cfg.bm, cfg.bn).transpose(0, 2, 1, 3)
    return c.reshape(mt * cfg.bm, nt * cfg.bn).astype(out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "policy", "cfg", "g", "interpret", "out_dtype", "epilogue", "b_bits",
        "tag",
    ),
)
def gemm(
    a: jax.Array,
    b: jax.Array,
    *,
    policy: Policy = DP,
    cfg: TileConfig = TileConfig(128, 128, 128),
    g: int = 8,
    interpret: bool = False,
    out_dtype=None,
    epilogue="none",
    bias: jax.Array = None,
    operand: jax.Array = None,
    scale: jax.Array = None,
    scale_a: jax.Array = None,
    b_bits: int = 8,
    tag: str = "",
) -> jax.Array:
    """``a @ b`` under a Stream-K++ scheduling policy, with an optional fused
    epilogue (Composable-Kernel style: applied post-accumulation in the
    fix-up / DP flush — zero extra HBM passes).

    a: (M, K), b: (K, N) -> (M, N). Accumulation is always f32. ``epilogue``
    is an :class:`repro.core.op.Epilogue` or legacy activation string;
    ``bias`` (N,) and ``operand`` (M, N) feed its bias-add / binary stages.
    ``scale`` (N,) is the per-output-channel dequant vector of an
    int8-weight op (``b`` int8): it enters every policy's flush/fix-up as
    an extra blocked operand ahead of the other epilogue stages, so the
    kernels accumulate raw int8 weights and never materialise a dense
    dequantized B. ``scale_a`` (M,) is the per-row activation dequant of an
    int8xint8 op (``a`` int8 too): together they form the rank-1 rescale
    ``s_a (x) s_b`` on the f32 accumulator. ``b_bits == 4``: ``b`` is
    int4-packed (ceil(K/2), N) — K comes from ``a``, and every kernel
    unpacks its packed block in the prologue (B HBM traffic is 0.5
    bytes/element). ``tag`` goes in front of every kernel's name
    (:func:`repro.kernels.common.kernel_name`).
    """
    if a.ndim != 2 or b.ndim != 2:
        raise ValueError(f"bad gemm operands {a.shape} @ {b.shape}")
    k_rows = (a.shape[1] + 1) // 2 if b_bits == 4 else a.shape[1]
    if b.shape[0] != k_rows:
        raise ValueError(
            f"bad gemm operands {a.shape} @ {b.shape} (b_bits={b_bits})"
        )
    m, k = a.shape
    _, n = b.shape
    out_dtype = out_dtype or a.dtype

    ap = pad_to(a, (cfg.bm, cfg.bk))
    bp = pad_to(b, (cfg.bk // 2 if b_bits == 4 else cfg.bk, cfg.bn))
    biasp = None if bias is None else pad_to(bias.reshape(1, n), (1, cfg.bn))
    operandp = None if operand is None else pad_to(operand, (cfg.bm, cfg.bn))
    scalep = prep_scale(scale, n, cfg.bn)
    scale_ap = prep_scale_a(scale_a, m, cfg.bm)
    part = partition(GemmShape(m, n, k), cfg, g, policy)
    epi = dict(
        epilogue=epilogue,
        bias=biasp,
        operand=operandp,
        scale=scalep,
        scale_a=scale_ap,
    )

    if part.sk_tiles == 0:
        # policy degraded to pure DP (DP itself, or a HYBRID whose remainder
        # wave is empty at this g): the DP region still launches in waves of
        # the selected grid size
        cp = dp_gemm_region(
            ap, bp, cfg, out_dtype=out_dtype, interpret=interpret, g=g,
            b_bits=b_bits, tag=tag, **epi,
        )
        return unpad(cp, (m, n))

    partials = streamk_phase1(ap, bp, part, interpret=interpret, b_bits=b_bits, tag=tag)
    sk_c = streamk_fixup(
        partials, part, out_dtype, interpret=interpret, tag=tag, **epi
    )
    c_sk = _scatter_sk_tiles(sk_c, part, out_dtype, interpret)

    if part.dp_tiles == 0:
        return unpad(c_sk, (m, n))

    cp = dp_gemm_region(
        ap,
        bp,
        cfg,
        tile_offset=part.sk_tiles,
        c_init=c_sk,
        out_dtype=out_dtype,
        interpret=interpret,
        g=g,
        b_bits=b_bits,
        tag=tag,
        **epi,
    )
    return unpad(cp, (m, n))
