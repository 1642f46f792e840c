"""Random weights from the seed, made on the device in one jitted call.

The benchmark makes the weights itself, so that the reference never takes
anything the program made: both are handed these arrays. The tree's layout
(leaf names, shapes, served dtypes, which leaves start at one) is read from
the model's parameter specs; the values are the benchmark's: a normal draw
scaled by one over the square root of the fan-in (the second-to-last axis;
a leading stacked-layer axis never counts), one per leaf from the seed.

A configuration's ``weight_scale`` multiplies the named leaves' draws by a
factor; a leaf is named by its dotted key path in the spec tree
(``layers.moe.router``). Draws, their order and their keys are the same with
or without it, so that leaves it does not name are bit for bit as before.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp


def leaf_key(seed: int):
    """A PRNG key from any whole seed, also one above 32 bits."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def make(specs: Any, seed: int, is_spec, scale: Optional[Dict[str, float]] = None) -> Any:
    """Arrays for an ``ArraySpec`` tree (``shape``, ``dtype``, ``init``),
    each drawn leaf that ``scale`` names multiplied by its factor."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(specs, is_leaf=is_spec)
    names = [jax.tree_util.keystr(p, simple=True, separator=".") for p, _ in paths]
    scale = dict(scale or {})
    drawn = {n for n, (_, s) in zip(names, paths) if s.init not in ("ones", "zeros")}
    if set(scale) - drawn:
        raise ValueError(f"weight_scale names no drawn leaf: {sorted(set(scale) - drawn)}; drawn: {sorted(drawn)}")
    layout: List[Tuple[Tuple[int, ...], str, str, Optional[float]]] = [
        (tuple(s.shape), str(s.dtype), s.init, scale.get(n)) for n, (_, s) in zip(names, paths)
    ]

    def build(key):
        out = []
        for i, (shape, dtype, init, factor) in enumerate(layout):
            if init == "ones":
                out.append(jnp.ones(shape, dtype))
            elif init == "zeros":
                out.append(jnp.zeros(shape, dtype))
            else:
                fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
                draw = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
                w = draw / math.sqrt(fan_in)
                if factor is not None:
                    w = w * float(factor)
                out.append(w.astype(dtype))
        return out

    return jax.tree.unflatten(treedef, jax.jit(build)(leaf_key(seed)))
