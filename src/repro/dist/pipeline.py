"""GPipe pipeline parallelism.

``split_stages`` folds the stacked-layer axis (L, ...) into (S, L/S, ...).
``pipeline_apply`` runs the classic GPipe schedule: microbatch ``m`` is
processed by stage ``s`` at step ``s + m``; activations move one stage
forward per step, so the whole batch drains in ``M + S - 1`` steps.

Two executions of the same schedule:

* **mesh path** (``mesh``/``axis`` given, stage count divisible by the axis
  size): ``shard_map`` pins each mesh slice to its own contiguous block of
  stages and moves activations with an explicit ``ppermute`` ring — the
  canonical pipeline formulation (explicit point-to-point, no partitioner
  guessing). Differentiable end-to-end (``ppermute`` transposes to the
  reverse ring).
* **fallback** (no mesh): a scanned rotating buffer computes every stage
  each step via ``vmap``; warm-up/cool-down garbage never reaches the
  output (clamped write indices are overwritten by the first valid write).

Both are exactly equal to sequential layer application — same
floating-point order per microbatch.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax


def split_stages(params, n_stages: int):
    """(L, ...) stacked params -> (S, L/S, ...) staged params."""

    def split(a):
        l = a.shape[0]
        assert l % n_stages == 0, f"{l} layers not divisible into {n_stages} stages"
        return a.reshape(n_stages, l // n_stages, *a.shape[1:])

    return jax.tree.map(split, params)


def _pipeline_local(stage_fn, stage_params, x):
    """Single-device GPipe: rotating buffer over a scanned schedule."""
    s = jax.tree.leaves(stage_params)[0].shape[0]
    m = x.shape[0]
    apply_stages = jax.vmap(stage_fn)

    def step(carry, t):
        buf, outs = carry
        feed = x[jnp.clip(t, 0, m - 1)]
        shifted = jnp.concatenate([feed[None], buf[:-1]], axis=0)
        newbuf = apply_stages(stage_params, shifted)
        outs = lax.dynamic_update_index_in_dim(
            outs, newbuf[-1], jnp.clip(t - (s - 1), 0, m - 1), 0
        )
        return (newbuf, outs), None

    buf0 = jnp.zeros((s, *x.shape[1:]), x.dtype)
    (_, outs), _ = lax.scan(step, (buf0, jnp.zeros_like(x)), jnp.arange(m + s - 1))
    return outs


def pipeline_apply(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params,
    x: jax.Array,  # (M, MB, ...) microbatches
    *,
    mesh=None,
    axis: Optional[str] = None,
):
    """Run ``stage_fn`` over all stages in GPipe order; returns (M, MB, ...)."""
    s = jax.tree.leaves(stage_params)[0].shape[0]
    m = x.shape[0]
    if mesh is None or axis is None or axis not in mesh.shape or s % mesh.shape[axis]:
        return _pipeline_local(stage_fn, stage_params, x)

    from jax.sharding import PartitionSpec as P

    n = mesh.shape[axis]  # pipeline ranks; each owns s // n stages
    s_loc = s // n
    ring = [(i, (i + 1) % n) for i in range(n)]

    def local(sp, xfull):
        # sp: (s_loc, ...) this rank's stages; xfull: (M, MB, ...) replicated
        j = lax.axis_index(axis)

        def chain(h):
            for i in range(s_loc):
                h = stage_fn(jax.tree.map(lambda a: a[i], sp), h)
            return h

        def step(carry, t):
            recv, outs = carry
            feed = jnp.where(j == 0, xfull[jnp.clip(t, 0, m - 1)], recv)
            h = chain(feed)
            outs = lax.dynamic_update_index_in_dim(
                outs, h, jnp.clip(t - (n - 1), 0, m - 1), 0
            )
            recv_next = lax.ppermute(h, axis, ring)
            return (recv_next, outs), None

        recv0 = jnp.zeros(xfull.shape[1:], xfull.dtype)
        (_, outs), _ = lax.scan(
            step, (recv0, jnp.zeros_like(xfull)), jnp.arange(m + n - 1)
        )
        # only the last rank holds finished microbatches; psum replicates
        outs = jnp.where(j == n - 1, outs, jnp.zeros_like(outs))
        return lax.psum(outs, axis)

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(
            jax.tree.map(lambda a: P(*((axis,) + (None,) * (a.ndim - 1))), stage_params),
            P(*((None,) * x.ndim)),
        ),
        out_specs=P(*((None,) * x.ndim)),
        check_vma=False,
    )(stage_params, x)
