"""Logical-axis sharding: ArraySpec pytrees -> PartitionSpecs via named rules.

Model code never names mesh axes. Parameters and activations carry *logical*
axis names (``"embed"``, ``"heads"``, ``"batch"``, ...) in ``ArraySpec``s;
a ``ShardingPlan`` binds those names to the axes of a concrete ``jax.Mesh``
through a rule table (``DEFAULT_RULES`` + per-cell overrides). The solver
demotes an axis to replication when

  * the rule maps to mesh axes absent from this mesh (e.g. ``pod`` on a
    single-pod mesh),
  * every mapped mesh axis has size 1 (sharding would be a no-op),
  * the dim is not divisible by the mapped axis product (GSPMD would pad), or
  * a mesh axis was already consumed by an earlier dim of the same array
    (an axis may shard at most one dim).

``constrain``/``constrain_uneven`` are the activation-side entry points: they
are no-ops unless a plan is installed via ``use_plan`` (so model code runs
unchanged in single-device tests), and ``constrain_uneven`` skips the
divisibility demotion for cases where GSPMD padding is intended (e.g. 56
heads over 16 devices).
"""

from __future__ import annotations

import math
import threading
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any, Dict, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

#: logical axis -> mesh axis (or tuple of mesh axes, outermost first).
#: ``batch`` spans the pure data-parallel axes; tensor-parallel dims ride
#: ``model``; ``embed`` is FSDP-sharded over ``data``.
DEFAULT_RULES: Dict[str, Any] = {
    "batch": ("pod", "data"),
    "embed": "data",
    "heads": "model",
    "kv_heads": "model",
    "ffn": "model",
    "vocab": "model",
    "experts": "model",
    "ssm_inner": "model",
    "frames": None,
    "seq": None,
    "kv_seq": None,
    "stack": None,
}


@dataclass
class ArraySpec:
    """Shape + dtype + logical sharding axes (+ init) for one array."""

    shape: Tuple[int, ...]
    dtype: str = "float32"
    axes: Tuple[Optional[str], ...] = ()
    init: str = "normal"  # "normal" | "zeros" | "ones"

    def __post_init__(self):
        self.shape = tuple(int(d) for d in self.shape)
        self.axes = tuple(self.axes)
        if len(self.axes) != len(self.shape):
            raise ValueError(
                f"axes/shape rank mismatch: {self.axes} vs {self.shape}"
            )

    def abstract(self) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct(self.shape, jnp.dtype(self.dtype))


def _is_spec(x) -> bool:
    return isinstance(x, ArraySpec)


class ShardingPlan:
    """Binds logical axis names to the axes of a concrete mesh."""

    def __init__(self, mesh: Mesh, rules: Optional[Mapping[str, Any]] = None):
        self.mesh = mesh
        self.rules: Dict[str, Any] = dict(DEFAULT_RULES)
        if rules:
            self.rules.update(rules)

    # -- solving -----------------------------------------------------------
    def _mesh_axes_for(self, logical: Optional[str]) -> Tuple[str, ...]:
        """Mesh axes (present in this mesh, size > 1) a logical axis maps to."""
        if logical is None:
            return ()
        rule = self.rules.get(logical)
        if rule is None:
            return ()
        names = (rule,) if isinstance(rule, str) else tuple(rule)
        return tuple(
            a for a in names if a in self.mesh.shape and self.mesh.shape[a] > 1
        )

    def axis_divisor(self, logical: str) -> int:
        """Sharding factor a logical axis implies on this mesh."""
        return math.prod(
            (self.mesh.shape[a] for a in self._mesh_axes_for(logical)), start=1
        )

    def gemm_div(self) -> Dict[str, int]:
        """Per-shard GEMM divisor table for this mesh — the ``div`` dict
        model layers thread into dispatch (``div.get("batch")`` /
        ``div.get("model")``). Tokens shard over the batch axes (``pod`` x
        ``data``); tensor-parallel weight dims (heads/ffn/vocab/experts)
        ride the mesh's ``model`` axis. Dividing the global MNK by these is
        what makes a :class:`~repro.core.op.GemmOp` fingerprint the *local*
        per-device problem the Pallas kernel actually sees under
        ``shard_map`` — so a tuning record produced on one host is an exact
        database hit on every identically-sharded host, which is the
        invariant federated tuning (``repro.core.federate``) relies on.

        Caveat: this is the mesh-level table the model layers already
        thread by hand; like those hand-built tables it does not see
        :meth:`spec_for`'s per-array divisibility demotion. A weight dim
        the solver demotes to replication (e.g. an odd vocab on a model=4
        mesh) executes at its global size while the fingerprint still
        divides. Call sites that know the concrete arrays should probe
        :meth:`demoted_dims` and demote the table accordingly — the serve
        engine does (``repro.serve.engine.serve_gemm_div``), so serving
        fingerprints never claim a split the weights don't execute."""
        return {
            "batch": self.axis_divisor("batch"),
            "model": int(self.mesh.shape.get("model", 1)),
        }

    def gemm_axes(self) -> Dict[str, Tuple[str, ...]]:
        """The mesh axes behind each :meth:`gemm_div` entry (empty where the
        divisor is 1): the axes a sharded GEMM dispatch splits over."""
        model = ("model",) if self.mesh.shape.get("model", 1) > 1 else ()
        return {"batch": self._mesh_axes_for("batch"), "model": model}

    def demoted_dims(self, specs, mesh_axis: str = "model"):
        """Per-array divisibility probe: every (shape, axes, dim_index, dim)
        in the ArraySpec tree whose logical axis maps onto ``mesh_axis``
        but which :meth:`spec_for`'s solver would demote to replication
        (non-divisible dim, same demotion rule, non-uneven path). Empty
        means the mesh-level :meth:`gemm_div` entry for that axis is exact
        for every array in the tree."""
        out = []

        def visit(s: ArraySpec):
            used: set = set()
            for i, (dim, logical) in enumerate(zip(s.shape, s.axes)):
                axes = tuple(
                    a for a in self._mesh_axes_for(logical) if a not in used
                )
                if not axes:
                    continue
                div = math.prod(self.mesh.shape[a] for a in axes)
                if dim % div:
                    if mesh_axis in axes:
                        out.append((s.shape, s.axes, i, dim))
                else:
                    used.update(axes)
            return s

        jax.tree.map(visit, specs, is_leaf=_is_spec)
        return out

    def spec_for(self, spec: ArraySpec, *, uneven: bool = False) -> P:
        """PartitionSpec for one array, with demotion (see module doc)."""
        used: set = set()
        entries = []
        for dim, logical in zip(spec.shape, spec.axes):
            axes = tuple(a for a in self._mesh_axes_for(logical) if a not in used)
            if axes:
                div = math.prod(self.mesh.shape[a] for a in axes)
                if not uneven and dim % div:
                    axes = ()
            used.update(axes)
            if not axes:
                entries.append(None)
            elif len(axes) == 1:
                entries.append(axes[0])
            else:
                entries.append(axes)
        return P(*entries)

    # -- trees -------------------------------------------------------------
    def sharding_for(self, spec: ArraySpec) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec_for(spec))

    def tree_shardings(self, tree):
        return jax.tree.map(self.sharding_for, tree, is_leaf=_is_spec)


# -- ambient plan -----------------------------------------------------------

_plan_state = threading.local()


def current_plan() -> Optional[ShardingPlan]:
    return getattr(_plan_state, "plan", None)


@contextmanager
def use_plan(plan: Optional[ShardingPlan]):
    old = current_plan()
    _plan_state.plan = plan
    try:
        yield plan
    finally:
        _plan_state.plan = old


def _constrain(x: jax.Array, axes: Sequence[Optional[str]], uneven: bool):
    plan = current_plan()
    if plan is None:
        return x
    spec = ArraySpec(tuple(x.shape), str(x.dtype), tuple(axes))
    pspec = plan.spec_for(spec, uneven=uneven)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(plan.mesh, pspec)
    )


def ambient_gemm_div() -> Dict[str, int]:
    """GEMM divisor table of the installed plan (see
    :meth:`ShardingPlan.gemm_div`); empty — every divisor 1, fingerprints
    key on global shapes — when no plan is installed, so single-device
    tests and examples run unchanged."""
    plan = current_plan()
    return plan.gemm_div() if plan is not None else {}


def constrain(x: jax.Array, *axes: Optional[str]) -> jax.Array:
    """Sharding hint by logical axis names; no-op without an installed plan."""
    return _constrain(x, axes, uneven=False)


def constrain_uneven(x: jax.Array, *axes: Optional[str]) -> jax.Array:
    """Like :func:`constrain` but keeps axes whose dim is not divisible —
    GSPMD pads (e.g. 56 heads over a 16-way model axis)."""
    return _constrain(x, axes, uneven=True)


# -- materialization ---------------------------------------------------------


def abstract_tree(tree):
    """ArraySpec tree -> ShapeDtypeStruct tree (for eval_shape/lowering)."""
    return jax.tree.map(lambda s: s.abstract(), tree, is_leaf=_is_spec)


def _init_leaf(shape: Tuple[int, ...], dtype: str, init: str, key) -> jax.Array:
    dtype = jnp.dtype(dtype)
    if init == "zeros":
        return jnp.zeros(shape, dtype)
    if init == "ones":
        return jnp.ones(shape, dtype)
    # fan-in-scaled normal; the stacked-layer axis (leading) never counts as
    # fan-in because specs are stacked after the per-layer shape is fixed.
    if len(shape) >= 2:
        fan_in = shape[-2]
    else:
        fan_in = shape[-1]
    std = 1.0 / math.sqrt(max(1, fan_in))
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def materialize_tree(tree, key, shardings=None):
    """Instantiate an ArraySpec tree with deterministic per-leaf RNG.

    Each leaf is made by one jitted program, so a seed gives the same
    weights on every mesh. With ``shardings`` (a matching tree of
    ``Sharding``s) the program writes straight into the leaf's sharding, so
    no device ever holds a whole sharded leaf or its f32 draw."""
    leaves, treedef = jax.tree.flatten(tree, is_leaf=_is_spec)
    keys = jax.random.split(key, max(1, len(leaves)))
    if shardings is None:
        shs = [None] * len(leaves)
    else:
        shs = treedef.flatten_up_to(shardings)
    out = []
    for s, k, sh in zip(leaves, keys, shs):
        placed = {} if sh is None else {"out_shardings": sh}
        init = jax.jit(_init_leaf, static_argnums=(0, 1, 2), **placed)
        out.append(init(s.shape, s.dtype, s.init, k))
    return jax.tree.unflatten(treedef, out)
