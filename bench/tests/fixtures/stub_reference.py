"""A reference module that keeps the contract and nothing of a model: every
served token lies ``GAP`` below its best, and each call is counted."""

import dataclasses

import jax.numpy as jnp

GAP = 0.5
calls = []


@dataclasses.dataclass(frozen=True)
class RefConfig:
    vocab: int

    @classmethod
    def from_config(cls, c):
        return cls(vocab=c["vocab_size"])


def gaps(params, tokens, nxt, c, control=False):
    calls.append((len(tokens), c, control))
    gap = jnp.full(tokens.shape, GAP, jnp.float32)
    return gap, (2 * gap if control else jnp.zeros_like(gap))
