"""One-kernel ragged grouped (MoE) GEMM: Stream-K over the *concatenated*
expert tile space.

The per-group dispatch loop (``core/gemm.py``'s loop backend) launches one
``pallas_call`` per expert group: trace cost, launch overhead and wave
quantization all scale with G. This module collapses the whole grouped
product into ONE persistent-grid ``pallas_call`` by flattening every group's
output tiles into a single concatenated tile space:

* Group ``i`` owns ``rows_i = ceil(sizes_i / bm)`` row-blocks of A; the
  groups' row-blocks are concatenated into ``A_cat`` of shape
  ``(R * bm, Kp)`` with ``R = sum(rows_i)`` (each group zero-padded to its
  own row-block boundary, so ragged group sizes never share a tile).
* The concatenated tile space is ``T = R * nt`` output tiles
  (``nt = Np / bn``): tile ``t`` covers global row-block ``r = t // nt``
  and column-block ``tn = t % nt``.
* A scalar-prefetch table ``blk_group[r] -> i`` (shape ``(R,)`` int32,
  computed on the host from the static group sizes) lets the B / bias /
  scale index maps gather the right expert's operand block: B is the
  stacked ``(G, Kp, Np)`` weight tensor indexed with block
  ``(blk_group[r], lk, tn)``. A, C and the binary epilogue operand are
  concatenated like ``A_cat`` and never need the table.

Two launch forms, selected by policy:

**Stream-K form** (ALL_SK and every HYBRID). Grid ``(g, ipw)`` with
``ipw = ceil(T * ipt / g)`` — Algorithm 1's persistent workgroups, but over
the concatenated tile space, so one grid covers all experts and the
quantization remainder is amortised once instead of per group. Both grid
dimensions are ARBITRARY (sequential): the flattened step ``it = x*ipw + j``
is monotone, so a single VMEM accumulator carries partial sums across
workgroup boundaries — tiles split between workgroups finish without a
partials workspace or fix-up kernel. (That sequential carry is exactly why
this stays ONE kernel; a HYBRID policy has no separate DP region here and
degenerates to ALL_SK — the cost model scores them identically for fused
grouped ops.)

**DP form** (DP policy). Grid ``(ceil(T/g)*g, ipt)``: classic tiled GEMM
over the concatenated tile space, wave-padded to the tuned grid size with
clamped index maps (surplus programs deterministically recompute the last
tile, as in ``dp_gemm_region``).

Numerics match the per-group loop bit-for-bit in f32 accumulation: each
output tile's MAC order over k is identical, padding contributes exact
zeros, and the fused epilogue (dequant scale -> bias -> activation/binary)
applies per tile at the flush exactly as the loop kernels apply it.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.policies import ALL_SK, Policy, PolicyKind, TileConfig
from repro.core.quant import unpack_int4
from repro.core.workpart import cdiv
from repro.kernels.common import (
    apply_epilogue,
    kernel_name,
    mixed_dot,
    pad_to,
    record_launch,
)


def _extras_split(rest, has_scale, has_scale_a, has_bias, has_operand):
    """Unpack [scale?, scale_a?, bias?, operand?] + (c_ref, acc_ref) tail."""
    c_ref, acc_ref = rest[-2], rest[-1]
    extras = list(rest[:-2])
    scale_ref = extras.pop(0) if has_scale else None
    scale_a_ref = extras.pop(0) if has_scale_a else None
    bias_ref = extras.pop(0) if has_bias else None
    operand_ref = extras.pop(0) if has_operand else None
    return scale_ref, scale_a_ref, bias_ref, operand_ref, c_ref, acc_ref


def _unpack_b(b_blk, b_bits):
    """Prologue unpack: packed (bk/2, bn) int4 block -> (bk, bn) int8."""
    return unpack_int4(b_blk) if b_bits == 4 else b_blk


# --------------------------------------------------------------------------
# Stream-K form: grid (g, ipw), sequential carry across workgroup boundaries
# --------------------------------------------------------------------------


def _sk_kernel(
    tab_ref,
    a_ref,
    b_ref,
    *rest,
    ipt: int,
    ipw: int,
    total: int,
    epilogue="none",
    has_scale: bool = False,
    has_scale_a: bool = False,
    has_bias: bool = False,
    has_operand: bool = False,
    b_bits: int = 8,
):
    """One flattened MAC step of the concatenated-tile-space sweep.

    Executes strictly sequentially (both grid dims ARBITRARY), so the
    accumulator scratch carries a split tile's partial sum from the end of
    workgroup ``x`` into the start of workgroup ``x+1`` — no fix-up pass.
    Steps past ``total`` clamp onto the final tile's last k-iteration: MAC
    and init are guarded off and the flush harmlessly rewrites the same
    finished value.
    """
    scale_ref, scale_a_ref, bias_ref, operand_ref, c_ref, acc_ref = _extras_split(
        rest, has_scale, has_scale_a, has_bias, has_operand
    )
    del tab_ref  # only the index maps consume the group table
    x = pl.program_id(0)
    j = pl.program_id(1)
    it_raw = x * ipw + j
    valid = it_raw < total
    it = jnp.minimum(it_raw, total - 1)
    lk = it % ipt

    # `valid &` matters when ipt == 1: a clamped trash step has lk == 0 AND
    # lk == ipt-1, and must not zero the accumulator before its flush.
    @pl.when(jnp.logical_and(valid, lk == 0))
    def _init():
        acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)

    @pl.when(valid)
    def _mac():
        acc_ref[...] += mixed_dot(a_ref[...], _unpack_b(b_ref[0], b_bits))

    @pl.when(lk == ipt - 1)
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


# --------------------------------------------------------------------------
# DP form: grid (wave-padded T, ipt), one program per concatenated tile
# --------------------------------------------------------------------------


def _dp_kernel(
    tab_ref,
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
    """Classic tiled-GEMM body over the concatenated tile space."""
    scale_ref, scale_a_ref, bias_ref, operand_ref, c_ref, acc_ref = _extras_split(
        rest, has_scale, has_scale_a, has_bias, has_operand
    )
    del tab_ref
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)

    acc_ref[...] += mixed_dot(a_ref[...], _unpack_b(b_ref[0], b_bits))

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


def _fused_call(
    tab,
    a_cat,
    b_pad,
    *,
    policy: Policy,
    cfg: TileConfig,
    g: int,
    nt: int,
    ipt: int,
    n_tiles: int,
    out_dtype,
    interpret: bool,
    epilogue,
    bias,
    operand,
    scale,
    scale_a,
    b_bits: int = 8,
    tag: str = "",
):
    """Build and issue THE single ``pallas_call`` over the concatenated tile
    space. ``tab``: (R,) int32 row-block -> group table (scalar-prefetched);
    ``a_cat``: (R*bm, Kp); ``b_pad``: (G, Kp, Np) — or (G, Kp/2, Np) packed
    int4 when ``b_bits == 4``, unpacked per block in the kernel prologue;
    optional ``bias``/``scale`` (G, Np), ``scale_a`` (R*bm, 1) concatenated
    like A, and ``operand`` (R*bm, Np). Returns C_cat (R*bm, Np)."""
    total = n_tiles * ipt
    rp, np_ = a_cat.shape[0], b_pad.shape[2]
    bk_b = cfg.bk // 2 if b_bits == 4 else cfg.bk
    sk_form = policy.kind != PolicyKind.DP

    if sk_form:
        ipw = cdiv(total, g)
        grid = (g, ipw)

        def _tile(x, j):
            it = jnp.minimum(x * ipw + j, total - 1)
            return it // ipt, it % ipt

        def a_index(x, j, tab):
            t, lk = _tile(x, j)
            return (t // nt, lk)

        def b_index(x, j, tab):
            t, lk = _tile(x, j)
            return (tab[t // nt], lk, t % nt)

        def c_index(x, j, tab):
            t, _ = _tile(x, j)
            return (t // nt, t % nt)

        def vec_index(x, j, tab):
            t, _ = _tile(x, j)
            return (tab[t // nt], 0, t % nt)

        def row_index(x, j, tab):
            t, _ = _tile(x, j)
            return (t // nt, 0)

        kernel = functools.partial(
            _sk_kernel,
            ipt=ipt,
            ipw=ipw,
            total=total,
            epilogue=epilogue,
            has_scale=scale is not None,
            has_scale_a=scale_a is not None,
            has_bias=bias is not None,
            has_operand=operand is not None,
            b_bits=b_bits,
        )
        # Both dims sequential: the accumulator carry across workgroup
        # boundaries is only sound under a strict flattened execution order.
        semantics = (pltpu.ARBITRARY, pltpu.ARBITRARY)
        name = kernel_name(f"grouped_sk_{cfg.name}_g{g}", tag)
    else:
        n_prog = cdiv(n_tiles, g) * g if g > 0 else n_tiles
        grid = (n_prog, ipt)

        def _tile_dp(i):
            if n_prog != n_tiles:
                i = jnp.minimum(i, n_tiles - 1)
            return i

        def a_index(i, k, tab):
            return (_tile_dp(i) // nt, k)

        def b_index(i, k, tab):
            t = _tile_dp(i)
            return (tab[t // nt], k, t % nt)

        def c_index(i, k, tab):
            t = _tile_dp(i)
            return (t // nt, t % nt)

        def vec_index(i, k, tab):
            t = _tile_dp(i)
            return (tab[t // nt], 0, t % nt)

        def row_index(i, k, tab):
            t = _tile_dp(i)
            return (t // nt, 0)

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
        tile_sem = pltpu.ARBITRARY if n_prog != n_tiles else pltpu.PARALLEL
        semantics = (tile_sem, pltpu.ARBITRARY)
        name = kernel_name(f"grouped_dp_{cfg.name}", tag)

    operands = [a_cat, b_pad]
    in_specs = [
        pl.BlockSpec((cfg.bm, cfg.bk), a_index),
        pl.BlockSpec((1, bk_b, cfg.bn), b_index),
    ]
    # per-group row vectors ride as (G, 1, Np): a (1, bn) block of a (G, Np)
    # array would leave a second-minor block dim the TPU cannot tile
    vec_spec = pl.BlockSpec((None, 1, cfg.bn), vec_index)
    if scale is not None:
        operands.append(scale[:, None, :])
        in_specs.append(vec_spec)
    if scale_a is not None:
        operands.append(scale_a)
        in_specs.append(pl.BlockSpec((cfg.bm, 1), row_index))
    if bias is not None:
        operands.append(bias[:, None, :])
        in_specs.append(vec_spec)
    if operand is not None:
        operands.append(operand)
        in_specs.append(pl.BlockSpec((cfg.bm, cfg.bn), c_index))

    record_launch(name)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((cfg.bm, cfg.bn), c_index),
            scratch_shapes=[pltpu.VMEM((cfg.bm, cfg.bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((rp, np_), out_dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(dimension_semantics=semantics),
        name=name,
    )(tab, *operands)


@functools.partial(
    jax.jit,
    static_argnames=(
        "policy", "cfg", "g", "interpret", "out_dtype", "epilogue",
        "group_sizes", "b_bits", "tag",
    ),
)
def gemm_grouped_streamk(
    a: jax.Array,
    b: jax.Array,
    *,
    policy: Policy = ALL_SK,
    cfg: TileConfig = TileConfig(128, 128, 128),
    g: int = 8,
    interpret: bool = False,
    out_dtype=None,
    epilogue="none",
    bias: Optional[jax.Array] = None,
    operand: Optional[jax.Array] = None,
    scale: Optional[jax.Array] = None,
    scale_a: Optional[jax.Array] = None,
    group_sizes: Optional[Tuple[int, ...]] = None,
    b_bits: int = 8,
    tag: str = "",
) -> jax.Array:
    """Batched-by-expert GEMM ``c[i] = a[i] @ b[i]`` in ONE ``pallas_call``.

    a: (G, M, K) activations, b: (G, K, N) per-expert weights -> (G, M, N).
    ``group_sizes`` (static tuple, default ``(M,) * G``) gives each expert's
    real row count for ragged MoE batches: only the first ``sizes[i]`` rows
    of group ``i`` participate; output rows beyond them are zero. A size of
    0 (expert received no tokens) contributes no tiles at all.

    Epilogue operands are per-expert: ``bias`` (G, N), ``scale`` (G, N) —
    the int8-weight dequant rows — ``scale_a`` (G, M) per-row activation
    dequant columns (int8xint8 ops), and ``operand`` (G, M, N) for binary
    stages. ``b_bits == 4``: ``b`` is int4-packed (G, ceil(K/2), N), each
    kernel block unpacked in the prologue. Accumulation is f32; policies
    other than DP run the Stream-K persistent form (HYBRID degenerates to
    ALL_SK — one launch admits no separate DP region). ``tag`` goes in
    front of the kernel's name (:func:`repro.kernels.common.kernel_name`).
    """
    if a.ndim != 3 or b.ndim != 3 or a.shape[0] != b.shape[0]:
        raise ValueError(f"bad grouped operands {a.shape} @ {b.shape}")
    k_rows = (a.shape[2] + 1) // 2 if b_bits == 4 else a.shape[2]
    if b.shape[1] != k_rows:
        raise ValueError(
            f"bad grouped operands {a.shape} @ {b.shape} (b_bits={b_bits})"
        )
    n_groups, m, k = a.shape
    n = b.shape[2]
    out_dtype = out_dtype or a.dtype
    sizes = group_sizes if group_sizes is not None else (m,) * n_groups
    if len(sizes) != n_groups or any(s < 0 or s > m for s in sizes):
        raise ValueError(f"bad group_sizes {sizes} for M={m}, G={n_groups}")

    row_blocks = [cdiv(s, cfg.bm) for s in sizes]
    r_total = sum(row_blocks)
    if r_total == 0:
        return jnp.zeros((n_groups, m, n), out_dtype)

    kp = cdiv(k, cfg.bk) * cfg.bk
    np_pad = cdiv(n, cfg.bn) * cfg.bn
    nt = np_pad // cfg.bn
    ipt = kp // cfg.bk

    # Concatenate each expert's live rows, padded to its own row-block
    # boundary — ragged boundaries never share a tile.
    a_parts = [
        pad_to(a[i, : sizes[i], :], (cfg.bm, cfg.bk))
        for i in range(n_groups)
        if row_blocks[i]
    ]
    a_cat = jnp.concatenate(a_parts, axis=0) if len(a_parts) > 1 else a_parts[0]
    b_pad = pad_to(b, (1, cfg.bk // 2 if b_bits == 4 else cfg.bk, cfg.bn))
    tab = jnp.asarray(
        np.repeat(np.arange(n_groups, dtype=np.int32), row_blocks)
    )

    biasp = None if bias is None else pad_to(
        bias.reshape(n_groups, n), (1, cfg.bn)
    )
    scalep = None if scale is None else pad_to(
        scale.reshape(n_groups, n).astype(jnp.float32), (1, cfg.bn)
    )
    scale_ap = None
    if scale_a is not None:
        # concatenated like A: group i's live rows padded to its row-block
        # boundary -> an (R*bm, 1) column the tiles slice by row-block
        sa_parts = [
            pad_to(
                scale_a[i, : sizes[i]].reshape(-1, 1).astype(jnp.float32),
                (cfg.bm, 1),
            )
            for i in range(n_groups)
            if row_blocks[i]
        ]
        scale_ap = (
            jnp.concatenate(sa_parts, axis=0)
            if len(sa_parts) > 1
            else sa_parts[0]
        )
    operandp = None
    if operand is not None:
        op_parts = [
            pad_to(operand[i, : sizes[i], :], (cfg.bm, cfg.bn))
            for i in range(n_groups)
            if row_blocks[i]
        ]
        operandp = (
            jnp.concatenate(op_parts, axis=0)
            if len(op_parts) > 1
            else op_parts[0]
        )

    c_cat = _fused_call(
        tab,
        a_cat,
        b_pad,
        policy=policy,
        cfg=cfg,
        g=g,
        nt=nt,
        ipt=ipt,
        n_tiles=r_total * nt,
        out_dtype=out_dtype,
        interpret=interpret,
        epilogue=epilogue,
        bias=biasp,
        operand=operandp,
        scale=scalep,
        scale_a=scale_ap,
        b_bits=b_bits,
        tag=tag,
    )

    # Scatter concatenated rows back to the dense (G, M, N) layout; padding
    # rows (and empty experts) come back as zeros.
    outs = []
    off = 0
    for i in range(n_groups):
        rb = row_blocks[i]
        if rb == 0:
            outs.append(jnp.zeros((m, n), out_dtype))
            continue
        blk = c_cat[off * cfg.bm : (off + rb) * cfg.bm, :n][: sizes[i]]
        if sizes[i] < m:
            blk = jnp.pad(blk, ((0, m - sizes[i]), (0, 0)))
        outs.append(blk)
        off += rb
    return jnp.stack(outs, axis=0)
