"""Public GEMM dispatch API — the paper's technique as a first-class framework
feature.

Every matmul in ``repro.models`` (attention/MLP projections, grouped MoE
expert GEMMs, batched cross-attention precomputes) routes through one of the
entry points here. At trace time the dispatcher:

  1. builds a :class:`repro.core.op.GemmOp` — the full problem fingerprint:
     global dims, per-shard local dims (callers pass the sharding divisors
     their GSPMD spec implies), group count, dtypes, and the fused
     :class:`~repro.core.op.Epilogue`;
  2. asks the :class:`KernelSelector` (tuned DB -> Bloom filters -> cost
     model, keyed on the op fingerprint) for a (policy, tile config);
  3. executes via the backend registered under the context's backend name.

Backends are *pluggable*: :func:`register_backend` installs a new execution
strategy without touching this module. Built-ins:

  * ``xla``               — jnp einsum (CPU / dry-run lowering; selection
                            still exercised + logged, epilogue fused by XLA),
  * ``pallas``            — the Stream-K++ Pallas kernels (TPU only; epilogue
                            fused into the kernel flush / fix-up phase),
  * ``pallas_interpret``  — same kernels, interpret mode (CPU-validated).

The default backend is derived from the platform (:func:`platform_backend`):
``pallas`` on TPU, ``xla`` elsewhere. Interpret mode is only ever chosen
explicitly, and ``pallas`` on a non-TPU platform raises.

Under an installed :class:`~repro.dist.sharding.ShardingPlan` with any
divisor > 1, the backend runs inside ``jax.shard_map`` over the plan's mesh
(:func:`_run_sharded`), so the kernel sees exactly the per-shard problem
that :attr:`GemmOp.local` fingerprints.

Entry points: :func:`gemm` (2-D weight, the original per-call surface),
:func:`gemm_grouped` (stacked ``(G, K, N)`` expert weights — each group is
the same local problem, one selection covers the group; by default all G
groups execute as ONE fused kernel over the concatenated expert tile
space, fingerprinted separately via the 8-part ``grouped_fused`` op key),
and :func:`gemm_batched` (independent per-batch operands of equal shape).

Backend and selector are ambient (context-managed) so model code stays
declarative. Every decision is appended to the active ``SelectionLog`` for
tests/benchmarks to introspect.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import re
import threading
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.op import Epilogue, GemmOp, as_epilogue
from repro.core.policies import Policy, TileConfig
from repro.core.quant import (
    QuantizedTensor,
    is_quantized,
    quantize_activations,
    unpack_int4,
)
from repro.core.selector import KernelSelector, Selection, default_selector
from repro.core.tuner import LEGACY_GRID
from repro.dist.sharding import current_plan
from repro.utils.timing import span

_state = threading.local()


# ---------------------------------------------------------------------------
# Backend registry
# ---------------------------------------------------------------------------

#: BackendFn(x, w, *, op, policy, cfg, g, bias, operand, scale, scale_a,
#:            b_bits) -> out
#:   x: (G, M, K), w: (G, K, N), bias: (G, N) | None, operand: (G, M, N) | None
#:   returns (G, M, N) in op.out_dtype. G == 1 for plain 2-D dispatches.
#:   ``g`` is the selected grid size (persistent-workgroup count) the kernel
#:   partitions the flattened iteration space over; backends without a grid
#:   concept (xla) may ignore it. ``scale``: (G, N) f32 — the
#:   per-output-channel dequant vector of an int8-weight op (``w`` is then
#:   the raw int8 values); backends must apply it to the f32 accumulator
#:   BEFORE the op's epilogue stages (see ``QuantizedTensor``). ``scale_a``:
#:   (G, M) f32 — the per-row activation dequant of an int8xint8 op (``x``
#:   is then int8), applied alongside ``scale`` as the rank-1 rescale.
#:   ``b_bits == 4``: ``w`` is int4-packed (G, ceil(K/2), N) — two nibbles
#:   per byte along K — and the backend must unpack (or let its kernels
#:   unpack per block). The dispatcher passes scale/scale_a/b_bits only for
#:   quantized ops, so backends that predate them keep serving dense
#:   traffic and fail loudly on quantized (unexpected kwarg) instead of
#:   silently skipping a dequant stage.
BackendFn = Callable[..., jax.Array]

_BACKENDS: Dict[str, BackendFn] = {}


def register_backend(name: str, fn: BackendFn, *, overwrite: bool = False) -> None:
    """Register an execution backend under ``name`` (see BackendFn contract).

    New backends plug in without touching the dispatcher: selection,
    logging, and the public API are backend-agnostic."""
    if name in _BACKENDS and not overwrite:
        raise ValueError(f"backend {name!r} already registered")
    _BACKENDS[name] = fn


def list_backends() -> Tuple[str, ...]:
    """Registered backend names, sorted."""
    return tuple(sorted(_BACKENDS))


def get_backend(name: str) -> BackendFn:
    """Resolve a backend by name; raises with the valid names on a miss."""
    try:
        return _BACKENDS[name]
    except KeyError:
        raise ValueError(
            f"unknown gemm backend {name!r}; registered backends: "
            f"{list(list_backends())}"
        ) from None


def _xla_backend(
    x, w, *, op: GemmOp, policy, cfg, g, bias, operand, scale=None,
    scale_a=None, b_bits=8, tag="",
):
    if b_bits == 4:
        # packed int4 weights: unpack to int8 and drop the odd-K pad row
        w = unpack_int4(w)[:, : x.shape[2], :]
    if jnp.issubdtype(x.dtype, jnp.integer) and jnp.issubdtype(
        w.dtype, jnp.integer
    ):
        # int8 x int8 op: integer contraction (exact in int32 for the
        # K <= ~130k these models dispatch), converted to f32 for the
        # rank-1 rescale below — mirroring the kernels' integer mixed_dot
        acc = jnp.einsum(
            "gmk,gkn->gmn", x, w, preferred_element_type=jnp.int32
        ).astype(jnp.float32)
    else:
        if w.dtype != x.dtype and not jnp.issubdtype(w.dtype, jnp.floating):
            # int8-weight op: contract in f32 (conversion from int8 is
            # exact), mirroring the kernels' mixed_dot widening
            x = x.astype(jnp.float32)
            w = w.astype(jnp.float32)
        acc = jnp.einsum(
            "gmk,gkn->gmn", x, w, preferred_element_type=jnp.float32
        )
    if scale_a is not None:
        acc = acc * scale_a[:, :, None].astype(jnp.float32)
    if scale is not None:
        acc = acc * scale[:, None, :].astype(jnp.float32)
    acc = op.epilogue.apply(
        acc,
        bias=None if bias is None else bias[:, None, :],
        operand=operand,
    )
    return acc.astype(op.out_dtype)


def _make_pallas_backend(interpret: bool) -> BackendFn:
    def backend(
        x, w, *, op: GemmOp, policy, cfg, g, bias, operand, scale=None,
        scale_a=None, b_bits=8, tag="",
    ):
        from repro.kernels.common import record_launch
        from repro.kernels.streamk import ops as sk_ops
        from repro.kernels.streamk.grouped import gemm_grouped_streamk

        if getattr(op, "fused", False):
            # Fused grouped form: ONE pallas_call spans the concatenated
            # tile space of all G expert groups (a scalar-prefetched
            # row-block -> group table steers the B/bias/scale gathers).
            # Trace and launch cost are G-independent; the per-group loop
            # below remains as the differential oracle (fused=False).
            return gemm_grouped_streamk(
                x,
                w,
                policy=policy,
                cfg=cfg,
                g=g,
                interpret=interpret,
                out_dtype=jnp.dtype(op.out_dtype),
                epilogue=op.epilogue,
                bias=bias,
                operand=operand,
                scale=scale,
                scale_a=scale_a,
                b_bits=b_bits,
                tag=tag,
            )

        # Loop form: one pallas_call per group, so trace cost grows with G
        # (tracked by benchmarks/perf_trajectory.py). Grouped dispatches
        # default to the fused branch above; this path serves batched ops,
        # explicit fused=False grouped calls, and legacy 7-part journal
        # entries, and doubles as the fused kernel's numerics oracle.
        outs = []
        for i in range(x.shape[0]):  # static group count
            # every group is a distinct runtime kernel launch even when the
            # (identical-shape) trace is jit-cached — count it as one
            record_launch(f"group[{i}]:{policy.name}_{cfg.name}")
            outs.append(
                sk_ops.gemm(
                    x[i],
                    w[i],
                    policy=policy,
                    cfg=cfg,
                    g=g,
                    interpret=interpret,
                    out_dtype=jnp.dtype(op.out_dtype),
                    epilogue=op.epilogue,
                    bias=None if bias is None else bias[i],
                    operand=None if operand is None else operand[i],
                    scale=None if scale is None else scale[i],
                    scale_a=None if scale_a is None else scale_a[i],
                    b_bits=b_bits,
                    tag=tag,
                )
            )
        return jnp.stack(outs)

    return backend


register_backend("xla", _xla_backend)
register_backend("pallas", _make_pallas_backend(interpret=False))
register_backend("pallas_interpret", _make_pallas_backend(interpret=True))


@functools.lru_cache(maxsize=None)
def _platform() -> str:
    return jax.default_backend()


def platform_backend() -> str:
    """The default backend, derived once from the platform: the Stream-K++
    Pallas kernels on TPU, XLA's dot elsewhere (CPU tests, dry-run
    lowering). Interpret mode is never a default."""
    return "pallas" if _platform() == "tpu" else "xla"


def _resolve_backend(name: str) -> str:
    get_backend(name)  # fail fast on unknown names
    if name == "pallas" and _platform() != "tpu":
        raise ValueError(
            f"backend 'pallas' compiles Mosaic kernels for a TPU, but this "
            f"process runs on {_platform()!r}; use 'pallas_interpret' to run "
            "the kernels in interpret mode"
        )
    return name


# ---------------------------------------------------------------------------
# Dispatch context + selection log
# ---------------------------------------------------------------------------


@dataclass
class SelectionLogEntry:
    """One dispatch decision: the op fingerprint, what was selected, and
    the caller's tag (e.g. ``"moe.in"``) for test/benchmark introspection."""

    op: GemmOp
    selection: Selection
    tag: str = ""

    @property
    def global_mnk(self) -> Tuple[int, int, int]:
        """Unsharded problem dims of the logged op."""
        return self.op.global_mnk

    @property
    def local_mnk(self) -> Tuple[int, int, int]:
        """Per-shard local dims of the logged op."""
        return self.op.local

    @property
    def g(self) -> int:
        """Group/batch count of the logged op (1 for plain)."""
        return self.op.g


@dataclass
class GemmContext:
    """Ambient dispatch state: the selector, backend name, and log."""

    selector: KernelSelector
    backend: str = field(default_factory=platform_backend)
    log: List[SelectionLogEntry] = field(default_factory=list)
    #: name each kernel after its GEMM's tag (see :func:`tagged_kernels`)
    kernel_tags: bool = False


def _ctx() -> GemmContext:
    ctx = getattr(_state, "ctx", None)
    if ctx is None:
        ctx = GemmContext(selector=default_selector())
        _state.ctx = ctx
    return ctx


@contextmanager
def gemm_context(
    selector: Optional[KernelSelector] = None,
    backend: Optional[str] = None,
    log: Optional[List[SelectionLogEntry]] = None,
):
    """Install a dispatch context for the duration of a trace/eval.

    Unset fields inherit from the enclosing context; ``log`` defaults to a
    fresh list (pass :func:`current_log` to keep appending to the ambient
    one)."""
    old = getattr(_state, "ctx", None)
    base = old or _ctx()
    _state.ctx = GemmContext(
        selector=selector if selector is not None else base.selector,
        backend=_resolve_backend(backend) if backend is not None else base.backend,
        log=log if log is not None else [],
    )
    try:
        yield _state.ctx
    finally:
        _state.ctx = old


@contextmanager
def tagged_kernels(on: bool = True):
    """Within this scope (with ``on``) the dispatcher puts each GEMM's tag in
    front of its kernels' names (``[A-Za-z0-9_]`` only), so that a profile
    tells the GEMMs of one shape apart. Each tag then makes a kernel of its
    own, traced and compiled apart: use it for a program compiled once (a
    jitted step), not for one lowered anew on every call."""
    ctx = _ctx()
    old = ctx.kernel_tags
    ctx.kernel_tags = on
    try:
        yield
    finally:
        ctx.kernel_tags = old


def current_log() -> List[SelectionLogEntry]:
    """The active context's selection log (created on first use)."""
    return _ctx().log


def current_selector() -> KernelSelector:
    """The active context's selector (created on first use)."""
    return _ctx().selector


# ---------------------------------------------------------------------------
# Core dispatch
# ---------------------------------------------------------------------------


def _dispatch(
    x: jax.Array,  # (G, M, K)
    w: jax.Array,  # (G, K, N)
    op: GemmOp,
    *,
    tag: str,
    policy: Optional[Policy],
    cfg: Optional[TileConfig],
    g: Optional[int],
    bias: Optional[jax.Array],
    operand: Optional[jax.Array],
    scale: Optional[jax.Array] = None,
    scale_a: Optional[jax.Array] = None,
    b_bits: int = 8,
) -> jax.Array:
    ctx = _ctx()
    with span("gemm.select", None, tag=tag) as sp:
        if policy is None and cfg is None and g is None:
            sel = ctx.selector.select_op(op)
        elif policy is not None and cfg is not None:
            sel = ctx.selector.record_forced(
                op, policy, cfg, g=g if g is not None else LEGACY_GRID
            )
        else:
            # partial override: fill the missing parts from selection, but
            # log what actually runs (source "forced") — never the
            # selector's own pick, which may pair a different policy with
            # this cfg/g
            sel = ctx.selector.select_partial(op, policy, cfg, g=g)
        sp.annotate(source=sel.source)
    ctx.selector.stats.select_s += sp.seconds
    ctx.log.append(SelectionLogEntry(op, sel, tag))
    static = dict(policy=sel.policy, cfg=sel.cfg, g=sel.g)
    if b_bits != 8:
        static["b_bits"] = b_bits
    if ctx.kernel_tags and tag:
        static["tag"] = re.sub(r"[^A-Za-z0-9_]", "_", tag)
    # only quantized ops pass the dequant operands: backends registered
    # against the pre-quantization BackendFn signature keep serving dense
    # traffic unchanged, and a quantized dispatch through one fails loudly
    # (unexpected 'scale') instead of silently skipping the dequant stage
    arrays = {"scale": scale, "scale_a": scale_a}
    arrays = {k: v for k, v in arrays.items() if v is not None}
    backend = get_backend(ctx.backend)
    plan = current_plan()
    if plan is None or max(*op.divisors, op.g_divisor) == 1:
        return backend(x, w, op=op, bias=bias, operand=operand, **static, **arrays)
    return _run_sharded(plan, backend, x, w, op, static, bias, operand, arrays)


def _mesh_split(div: int, axes: Tuple[str, ...], mesh, dim: str):
    """The mesh axes a GEMM dim with sharding divisor ``div`` splits over
    (None when unsplit). A divisor the plan's axes cannot honour is a
    caller bug: ``serve_gemm_div``/``train_gemm_div`` demote those."""
    if div == 1:
        return None
    size = math.prod(mesh.shape[a] for a in axes)
    if div != size:
        raise ValueError(
            f"gemm {dim} divisor {div} matches no split of the plan's mesh "
            f"{dict(mesh.shape)} (axes {axes} have size {size})"
        )
    return axes[0] if len(axes) == 1 else axes


def _run_sharded(plan, backend, x, w, op: GemmOp, static, bias, operand, arrays):
    """Run ``backend`` on each shard of the plan's mesh (``jax.shard_map``).

    A Mosaic kernel cannot be partitioned automatically, and the op
    fingerprints the per-shard problem, so the kernel is given exactly that
    problem: tokens (M) split over the batch axes, N over ``model`` for
    column-parallel weights, K over ``model`` for row-parallel ones (whose
    f32 partial products are then summed with ``psum``), and G over
    ``model`` for expert-parallel groups."""
    mesh = plan.mesh
    axes = plan.gemm_axes()
    dm, dn, dk = op.divisors
    m_ax = _mesh_split(dm, axes["batch"], mesh, "M")
    n_ax = _mesh_split(dn, axes["model"], mesh, "N")
    k_ax = _mesh_split(dk, axes["model"], mesh, "K")
    g_ax = _mesh_split(op.g_divisor, axes["model"], mesh, "G")
    if k_ax is not None and not op.epilogue.is_none:
        raise ValueError(
            f"epilogue {op.epilogue.name!r} cannot run on the partial sums of a "
            "K-sharded (row-parallel) gemm"
        )
    specs = dict(
        bias=P(g_ax, n_ax),
        operand=P(g_ax, m_ax, n_ax),
        scale=P(g_ax, n_ax),
        scale_a=P(g_ax, m_ax),
    )
    arrays = dict(arrays, bias=bias, operand=operand)
    arrays = {k: v for k, v in arrays.items() if v is not None}
    names = tuple(arrays)
    # row-parallel partials are summed in f32, then cast once
    run_op = dataclasses.replace(op, out_dtype="float32") if k_ax else op

    def body(xs, ws, *extra):
        kw = {"bias": None, "operand": None, **dict(zip(names, extra))}
        out = backend(xs, ws, op=run_op, **static, **kw)
        if k_ax is not None:
            out = jax.lax.psum(out, k_ax).astype(op.out_dtype)
        return out

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(g_ax, m_ax, k_ax), P(g_ax, k_ax, n_ax), *(specs[n] for n in names)),
        out_specs=P(g_ax, m_ax, None if k_ax else n_ax),
        check_vma=False,
    )(x, w, *arrays.values())


def _check_epilogue(epilogue: Epilogue, bias, operand) -> None:
    if epilogue.bias != (bias is not None):
        raise ValueError(
            f"epilogue {epilogue.name!r} expects bias={epilogue.bias} but "
            f"bias operand is {'missing' if bias is None else 'present'}"
        )
    if (epilogue.binary != "none") != (operand is not None):
        raise ValueError(
            f"epilogue {epilogue.name!r} expects "
            f"{'an' if epilogue.binary != 'none' else 'no'} binary operand"
        )


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------


def gemm(
    x: jax.Array,
    w: Union[jax.Array, QuantizedTensor],
    *,
    divisors: Tuple[int, int, int] = (1, 1, 1),
    out_dtype=None,
    tag: str = "",
    policy: Optional[Policy] = None,
    cfg: Optional[TileConfig] = None,
    g: Optional[int] = None,
    epilogue: Union[None, str, Epilogue] = None,
    bias: Optional[jax.Array] = None,
    operand: Optional[jax.Array] = None,
) -> jax.Array:
    """``x @ w`` with Stream-K++ kernel selection.

    x: (..., K); w: (K, N) -> (..., N). ``divisors`` are the GSPMD sharding
    factors (dm, dn, dk) so selection keys on the per-shard local shape.
    ``epilogue`` fuses bias/activation/binary post-ops into the kernel
    (``bias``: (N,), ``operand``: (..., N) matching the output).
    ``policy``/``cfg``/``g`` override selection (used by the tuner itself);
    otherwise the selector chooses all three jointly.

    ``w`` may be a :class:`~repro.core.quant.QuantizedTensor`: the op then
    fingerprints with the mixed ``"<x_dtype>*<w_dtype>"`` in_dtype — e.g.
    ``"float32*int8"``, ``"float32*int4"`` (packed nibbles, unpacked in the
    kernel prologues), or ``"int8*int8"`` when the weight requests dynamic
    activation quantization (``act_bits=8``) — tuning/pruning independently
    of the dense op at the same MNK. The weight scales (and, for int8
    activations, the per-row activation scales computed here at dispatch
    time) ride into the kernel's flush/fix-up as fused dequant epilogue
    stages.
    """
    scale = None
    scale_a = None
    b_bits = 8
    w_name = None
    act_quant = False
    w_shape = w.shape  # QuantizedTensor reports the LOGICAL (K, N)
    if is_quantized(w):
        scale = w.scales
        b_bits = 4 if w.bits == 4 else 8
        w_name = w.dtype_name
        act_quant = w.act_bits == 8
        w = w.values
    if x.shape[-1] != w_shape[0]:
        raise ValueError(f"gemm contraction mismatch: {x.shape} @ {w_shape}")
    epilogue = _infer_epilogue(epilogue, bias, operand)
    lead = x.shape[:-1]
    m_global = 1
    for d in lead:
        m_global *= int(d)
    k_global, n_global = int(w_shape[0]), int(w_shape[1])
    # capture out_dtype from the ORIGINAL activations — dynamic activation
    # quantization must not leak int8 into the output dtype default
    out_dtype = jnp.dtype(out_dtype or x.dtype)
    if act_quant and jnp.issubdtype(x.dtype, jnp.floating):
        x, sa = quantize_activations(x)
        scale_a = sa.reshape(1, m_global)
    op = GemmOp(
        m_global,
        n_global,
        k_global,
        in_dtype=_in_dtype_fingerprint(x, w, w_name=w_name),
        out_dtype=str(out_dtype),
        divisors=tuple(divisors),
        epilogue=epilogue,
    )
    out = _dispatch(
        x.reshape(1, m_global, k_global),
        w[None],
        op,
        tag=tag,
        policy=policy,
        cfg=cfg,
        g=g,
        bias=None if bias is None else bias.reshape(1, n_global),
        operand=None if operand is None else operand.reshape(1, m_global, n_global),
        scale=None if scale is None else scale.reshape(1, n_global),
        scale_a=scale_a,
        b_bits=b_bits,
    )
    return out.reshape(*lead, n_global)


def _gemm_stacked(
    kind: str,
    x: jax.Array,
    w: jax.Array,
    *,
    divisors: Tuple[int, int, int],
    g_divisor: int,
    out_dtype,
    tag: str,
    policy: Optional[Policy],
    cfg: Optional[TileConfig],
    grid: Optional[int],
    epilogue: Union[None, str, Epilogue],
    bias: Optional[jax.Array],
    operand: Optional[jax.Array],
    fused: bool = False,
) -> jax.Array:
    scale = None
    scale_a = None
    b_bits = 8
    w_name = None
    act_quant = False
    w_shape = w.shape  # QuantizedTensor reports the LOGICAL (G, K, N)
    if is_quantized(w):
        scale = w.scales
        b_bits = 4 if w.bits == 4 else 8
        w_name = w.dtype_name
        act_quant = w.act_bits == 8
        w = w.values
    if x.ndim != 3 or len(w_shape) != 3:
        raise ValueError(
            f"gemm_{kind} expects x (G, M, K) and w (G, K, N); got "
            f"{x.shape} @ {tuple(w_shape)}"
        )
    if x.shape[0] != w_shape[0] or x.shape[2] != w_shape[1]:
        raise ValueError(f"gemm_{kind} mismatch: {x.shape} @ {tuple(w_shape)}")
    epilogue = _infer_epilogue(epilogue, bias, operand)
    g, m, k = (int(d) for d in x.shape)
    n = int(w_shape[2])
    # capture out_dtype before any dynamic activation quantization
    out_dtype = jnp.dtype(out_dtype or x.dtype)
    if act_quant and jnp.issubdtype(x.dtype, jnp.floating):
        x, scale_a = quantize_activations(x)  # scales (G, M)
    op = GemmOp(
        m,
        n,
        k,
        g=g,
        kind=kind,
        in_dtype=_in_dtype_fingerprint(x, w, w_name=w_name),
        out_dtype=str(out_dtype),
        divisors=tuple(divisors),
        g_divisor=g_divisor,
        epilogue=epilogue,
        fused=fused,
    )
    if bias is not None and bias.ndim == 1:
        bias = jnp.broadcast_to(bias[None], (g, n))
    return _dispatch(
        x,
        w,
        op,
        tag=tag,
        policy=policy,
        cfg=cfg,
        g=grid,
        bias=bias,
        operand=operand,
        scale=scale,
        scale_a=scale_a,
        b_bits=b_bits,
    )


def gemm_grouped(
    x: jax.Array,
    w: Union[jax.Array, QuantizedTensor],
    *,
    divisors: Tuple[int, int, int] = (1, 1, 1),
    g_divisor: int = 1,
    out_dtype=None,
    tag: str = "",
    policy: Optional[Policy] = None,
    cfg: Optional[TileConfig] = None,
    grid: Optional[int] = None,
    epilogue: Union[None, str, Epilogue] = None,
    bias: Optional[jax.Array] = None,
    operand: Optional[jax.Array] = None,
    fused: bool = True,
) -> jax.Array:
    """Grouped GEMM over stacked weights: x (G, M, K) @ w (G, K, N) ->
    (G, M, N) — the MoE expert shape (G experts, M = expert capacity).

    All groups share one local problem, so a single selection covers the
    group; the op fingerprint still records ``G`` (and ``g_divisor``, the
    expert-parallel sharding factor) so grouped shapes tune and prune
    independently of the plain 2-D path. ``bias``: (G, N) or (N,);
    ``operand``: (G, M, N). ``grid`` overrides the selected grid size
    (named to avoid clashing with the group count ``G``). ``w`` may be a
    stacked :class:`~repro.core.quant.QuantizedTensor` (int8 values
    (G, K, N) + scales (G, N)) — the MoE expert weights of the quantized
    serving path.

    ``fused`` (default True) runs all G groups as ONE kernel over the
    concatenated expert tile space (``kernels/streamk/grouped``) and
    fingerprints the op with the 8-part ``grouped_fused`` key so it tunes,
    journals, prunes and federates independently of the per-group loop.
    ``fused=False`` keeps the legacy one-launch-per-group path — the
    differential oracle and the dispatch form of legacy 7-part journal
    records.
    """
    return _gemm_stacked(
        "grouped",
        x,
        w,
        divisors=divisors,
        g_divisor=g_divisor,
        out_dtype=out_dtype,
        tag=tag,
        policy=policy,
        cfg=cfg,
        grid=grid,
        epilogue=epilogue,
        bias=bias,
        operand=operand,
        fused=fused,
    )


def gemm_batched(
    x: jax.Array,
    w: Union[jax.Array, QuantizedTensor],
    *,
    divisors: Tuple[int, int, int] = (1, 1, 1),
    g_divisor: int = 1,
    out_dtype=None,
    tag: str = "",
    policy: Optional[Policy] = None,
    cfg: Optional[TileConfig] = None,
    grid: Optional[int] = None,
    epilogue: Union[None, str, Epilogue] = None,
    bias: Optional[jax.Array] = None,
    operand: Optional[jax.Array] = None,
) -> jax.Array:
    """Batched GEMM: x (B, M, K) @ w (B, K, N) -> (B, M, N), independent
    per-batch operands of equal shape (one selection covers the batch)."""
    return _gemm_stacked(
        "batched",
        x,
        w,
        divisors=divisors,
        g_divisor=g_divisor,
        out_dtype=out_dtype,
        tag=tag,
        policy=policy,
        cfg=cfg,
        grid=grid,
        epilogue=epilogue,
        bias=bias,
        operand=operand,
    )


def _in_dtype_fingerprint(
    x: jax.Array, w: jax.Array, w_name: Optional[str] = None
) -> str:
    """Input-dtype component of the op key. Mixed activation/weight dtypes
    (e.g. bf16 activations against int8 weights) select different kernels,
    so they must not collide on one fingerprint. Quantized weights pass
    their logical ``w_name`` (``"int8"``/``"int4"`` — the stored dtype of a
    packed int4 tensor is int8 bytes) and ALWAYS fingerprint in the mixed
    ``"a*w"`` form: an ``"int8*int8"`` dynamic-quantization op must not
    collide with a hypothetical plain int8 op's key."""
    xd = str(x.dtype)
    if w_name is not None:
        return f"{xd}*{w_name}"
    wd = str(w.dtype)
    return xd if xd == wd else f"{xd}*{wd}"


def _infer_epilogue(
    epilogue: Union[None, str, Epilogue], bias, operand
) -> Epilogue:
    """Normalise the epilogue argument and cross-check it against the
    supplied operands (a bias without ``bias=True`` in the spec — or vice
    versa — is a caller bug, not something to guess around)."""
    if epilogue is None and (bias is not None or operand is not None):
        raise ValueError(
            "bias/operand supplied without an epilogue spec; pass "
            "epilogue=Epilogue(bias=..., binary=...)"
        )
    spec = as_epilogue(epilogue)
    _check_epilogue(spec, bias, operand)
    return spec
