"""A plain float32 decoder: the reference that decides ``correct``.

Written from the published descriptions of the two families the benchmark
serves, with no kernels, cache or batching, and nothing imported from the
program:

- dense (granite-8b, Llama architecture): token embedding; per layer
  ``x += Wo · attn(RoPE(Wq·n1(x)), RoPE(Wk·n1(x)), Wv·n1(x))`` with grouped
  query heads and a causal softmax, then ``x += Wout · (Win·n2(x) ⊙
  silu(Wgate·n2(x)))``; a final RMSNorm and an untied output head.
- MoE (olmoe-1b-7b): the same attention, and in place of the MLP a router
  ``softmax(x·R)`` whose top-k experts each apply the SwiGLU MLP above; the
  outputs are summed with the top-k probabilities (renormalised to sum to 1
  where the configuration says so). Every token reaches all of its top-k
  experts: there is no capacity and nothing is dropped.

RMSNorm is ``x / sqrt(mean(x^2) + eps) * scale``; RoPE rotates the two halves
of each head with frequencies ``theta^(-i/half)``. Every matrix product runs
at ``HIGHEST`` precision, set on each product. The weights are given as a tree with the keys
``embed``, ``layers`` (stacked over layers), ``final_norm`` and ``lm_head``.

``quant=True`` computes the same forward pass with every projection's
weights rounded to int8 per output channel and its input rows rounded to
int8 per row (the router stays in float32): the control, one precision
below what the configurations state.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict

import jax
import jax.numpy as jnp

#: every product of the reference runs at full float32 precision
HI = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class RefConfig:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    d_head: int
    d_ff: int
    vocab: int
    rope_theta: float
    eps: float
    experts: int = 0
    top_k: int = 0
    norm_topk: bool = True

    @classmethod
    def from_config(cls, c: Dict, layers: int = 0) -> "RefConfig":
        return cls(
            layers=layers or c["num_hidden_layers"],
            d_model=c["hidden_size"],
            heads=c["num_attention_heads"],
            kv_heads=c["num_key_value_heads"],
            d_head=c["head_dim"],
            d_ff=c["intermediate_size"],
            vocab=c["vocab_size"],
            rope_theta=float(c["rope_theta"]),
            eps=float(c["rms_norm_eps"]),
            experts=c.get("num_experts", 0),
            top_k=c.get("num_experts_per_tok", 0),
            norm_topk=bool(c.get("norm_topk_prob", True)),
        )


def _q8(x, axis):
    """Symmetric int8 rounding along ``axis`` (one scale per other index)."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-12) / 127.0
    return jnp.round(x / s) * s


def _proj(x, w, quant: bool):
    """``x @ w`` in float32; with ``quant``, int8 rows times int8 columns."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if quant:
        x = _q8(x, -1)
        w = _q8(w, -2)
    return jnp.matmul(x, w, precision=HI)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale.astype(jnp.float32)


def _rope(x, theta):
    """x: (S, H, dh), position = row index."""
    s, _, dh = x.shape
    half = dh // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(p, h, c: RefConfig, quant: bool):
    s = h.shape[0]
    q = _rope(_proj(h, p["wq"], quant).reshape(s, c.heads, c.d_head), c.rope_theta)
    k = _rope(_proj(h, p["wk"], quant).reshape(s, c.kv_heads, c.d_head), c.rope_theta)
    v = _proj(h, p["wv"], quant).reshape(s, c.kv_heads, c.d_head)
    rep = c.heads // c.kv_heads
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / math.sqrt(c.d_head)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v, precision=HI)
    return _proj(out.reshape(s, c.heads * c.d_head), p["wo"], quant)


def _mlp(p, h, quant: bool):
    gate = _proj(h, p["w_gate"], quant)
    return _proj(_proj(h, p["w_in"], quant) * jax.nn.silu(gate), p["w_out"], quant)


def _moe(p, h, c: RefConfig, quant: bool):
    probs = jax.nn.softmax(jnp.matmul(h, p["router"].astype(jnp.float32), precision=HI), axis=-1)  # (S, E)
    top, idx = jax.lax.top_k(probs, c.top_k)
    if c.norm_topk:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    weight = jnp.zeros_like(probs).at[jnp.arange(h.shape[0])[:, None], idx].set(top)  # (S, E)

    f32 = lambda w: w.astype(jnp.float32)  # noqa: E731
    w_gate, w_in, w_out = f32(p["w_gate"]), f32(p["w_in"]), f32(p["w_out"])
    if quant:
        h = _q8(h, -1)
        w_gate, w_in, w_out = _q8(w_gate, -2), _q8(w_in, -2), _q8(w_out, -2)
    gate = jnp.einsum("sd,edf->esf", h, w_gate, precision=HI)
    act = jnp.einsum("sd,edf->esf", h, w_in, precision=HI) * jax.nn.silu(gate)  # (E, S, F)
    if quant:
        act = _q8(act, -1)
    # each expert's output, weighted by its routing probability, summed
    return jnp.einsum("esf,efd->sd", act * weight.T[:, :, None], w_out, precision=HI)


def _layer(c: RefConfig, quant: bool, x, p):
    h = _rms(x, p["norm1"]["scale"], c.eps)
    x = x + _attention(p["attn"], h, c, quant)
    h = _rms(x, p["norm2"]["scale"], c.eps)
    x = x + (_moe(p["moe"], h, c, quant) if c.experts else _mlp(p["mlp"], h, quant))
    return x, None


def _forward(params, tokens, c: RefConfig, quant: bool):
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
    x, _ = jax.lax.scan(functools.partial(_layer, c, quant), x, params["layers"])
    x = _rms(x, params["final_norm"]["scale"], c.eps)
    return _proj(x, params["lm_head"], quant)  # (S, V)


def logits(params, tokens, c: RefConfig, quant: bool = False):
    """Float32 logits (S, V) at every position of ``tokens`` (S,)."""
    return _forward(params, tokens, c, quant)


@functools.partial(jax.jit, static_argnums=(3, 4))
def gaps(params, tokens, nxt, c: RefConfig, control: bool = False):
    """Per position i: how far the reference's logit of ``nxt[i]`` lies
    below its best. With ``control``, also the gap of the token that the
    int8 forward pass puts first. Returns (gap, control_gap or zeros)."""
    ref = _forward(params, tokens, c, False)
    best = jnp.max(ref, axis=-1)
    gap = best - jnp.take_along_axis(ref, nxt[:, None], axis=-1)[:, 0]
    if not control:
        return gap, jnp.zeros_like(gap)
    low = jnp.argmax(_forward(params, tokens, c, True), axis=-1)
    return gap, best - jnp.take_along_axis(ref, low[:, None], axis=-1)[:, 0]
