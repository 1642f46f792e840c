"""Random weights from the seed, made on the device in one jitted call.

The benchmark makes the weights itself, so that the reference never takes
anything the program made: both are handed these arrays. The tree's layout
(leaf names, shapes, served dtypes, which leaves start at one) is read from
the model's parameter specs; the values are the benchmark's: a normal draw
scaled by one over the square root of the fan-in (the second-to-last axis;
a leading stacked-layer axis never counts), one per leaf from the seed.
"""

from __future__ import annotations

import math
from typing import Any, List, Tuple

import jax
import jax.numpy as jnp


def leaf_key(seed: int):
    """A PRNG key from any whole seed, also one above 32 bits."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def make(specs: Any, seed: int, is_spec) -> Any:
    """Arrays for an ``ArraySpec`` tree (``shape``, ``dtype``, ``init``)."""
    leaves, treedef = jax.tree.flatten(specs, is_leaf=is_spec)
    layout: List[Tuple[Tuple[int, ...], str, str]] = [(tuple(s.shape), str(s.dtype), s.init) for s in leaves]

    def build(key):
        out = []
        for i, (shape, dtype, init) in enumerate(layout):
            if init == "ones":
                out.append(jnp.ones(shape, dtype))
            elif init == "zeros":
                out.append(jnp.zeros(shape, dtype))
            else:
                fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
                draw = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
                out.append((draw / math.sqrt(fan_in)).astype(dtype))
        return out

    return jax.tree.unflatten(treedef, jax.jit(build)(leaf_key(seed)))
