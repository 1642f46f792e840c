"""Operations and bytes from shapes: the model's required FLOPs per step and
each GEMM's least time on the chip.

Model FLOPs count what the model needs, not what the program computes: a
GEMM is 2*M*N*K, attention is 4 * heads * d_head per (query, key) pair the
causal mask admits, a prefill chunk needs logits at its last position only,
and an MoE token needs its router and its top-k experts, never capacity
padding.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, Tuple


@dataclasses.dataclass(frozen=True)
class Dims:
    """The shapes of a decoder as its configuration file states them."""

    layers: int
    d_model: int
    heads: int
    kv_heads: int
    d_head: int
    d_ff: int
    vocab: int
    experts: int = 0
    top_k: int = 0

    @classmethod
    def from_config(cls, c: Dict) -> "Dims":
        return cls(
            layers=c["num_hidden_layers"],
            d_model=c["hidden_size"],
            heads=c["num_attention_heads"],
            kv_heads=c["num_key_value_heads"],
            d_head=c["head_dim"],
            d_ff=c["intermediate_size"],
            vocab=c["vocab_size"],
            experts=c.get("num_experts", 0),
            top_k=c.get("num_experts_per_tok", 0),
        )

    def layer_matmul_params(self) -> int:
        """Weights one token multiplies through in one layer."""
        d, dh = self.d_model, self.d_head
        attn = d * self.heads * dh + 2 * d * self.kv_heads * dh + self.heads * dh * d
        mlp = 3 * d * self.d_ff  # SwiGLU: gate, in, out
        if self.experts:
            return attn + d * self.experts + self.top_k * mlp
        return attn + mlp


def token_flops(dims: Dims, context: int, *, logits: bool) -> float:
    """FLOPs one token needs at a position that attends ``context`` keys
    (itself included), with or without its output logits."""
    f = 2.0 * dims.layers * dims.layer_matmul_params()
    f += 4.0 * dims.layers * dims.heads * dims.d_head * context
    if logits:
        f += 2.0 * dims.d_model * dims.vocab
    return f


def decode_flops(dims: Dims, contexts: Iterable[int]) -> float:
    """A decode step: one token per live row, each with logits; ``contexts``
    holds each live row's keys (its position + 1)."""
    return sum(token_flops(dims, c, logits=True) for c in contexts)


def chunk_flops(dims: Dims, start: int, size: int) -> float:
    """A prefill chunk of ``size`` tokens at positions ``start..``: causal
    attention over everything before each, logits at the last one only."""
    f = 2.0 * size * dims.layers * dims.layer_matmul_params()
    # sum over positions p of (p + 1) keys
    keys = size * start + size * (size + 1) // 2
    f += 4.0 * dims.layers * dims.heads * dims.d_head * keys
    return f + 2.0 * dims.d_model * dims.vocab


def gemm_flops_bytes(
    m: int, n: int, k: int, g: int, a_bytes: float, b_bytes: float, out_bytes: float
) -> Tuple[float, float]:
    """FLOPs and the least HBM bytes of a (grouped) GEMM: each operand read
    once, the output written once."""
    flops = 2.0 * g * m * n * k
    nbytes = g * (m * k * a_bytes + k * n * b_bytes + m * n * out_bytes)
    return flops, nbytes


def gemm_least_s(
    m: int, n: int, k: int, g: int, a_bytes: float, b_bytes: float, out_bytes: float, peaks: Dict[str, float]
) -> float:
    """The least time the chip could take: the larger of FLOPs over the bf16
    peak and bytes over HBM bandwidth."""
    flops, nbytes = gemm_flops_bytes(m, n, k, g, a_bytes, b_bytes, out_bytes)
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])
