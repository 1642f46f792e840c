"""Shared model building blocks (pure JAX, no flax).

Every projection routes through ``repro.core.gemm`` so the paper's Stream-K++
selection layer sees every matmul in every architecture. Attention uses a
chunked online-softmax (memory-efficient, O(S*chunk) score memory) so 32k
prefill and 4k training fit without a fused attention kernel; decode attends
directly against the KV cache.

Layer-param *specs* (``ArraySpec`` pytrees) and *apply* functions live side
by side; specs carry the logical sharding axes consumed by
``repro.dist.sharding``.
"""

from __future__ import annotations

import math
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.gemm import gemm, gemm_grouped
from repro.core.op import Epilogue
from repro.core.quant import is_quantized
from repro.dist.sharding import ArraySpec, constrain, constrain_uneven
from repro.models.config import ModelConfig

Params = Dict[str, jax.Array]


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def norm_spec(cfg: ModelConfig, d: Optional[int] = None) -> Dict[str, ArraySpec]:
    d = d or cfg.d_model
    spec = {"scale": ArraySpec((d,), "float32", (None,), init="ones")}
    if cfg.norm == "layernorm":
        spec["bias"] = ArraySpec((d,), "float32", (None,), init="zeros")
    return spec


def norm_apply(p: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        out = (xf - mu) * jax.lax.rsqrt(var + 1e-6) * p["scale"] + p["bias"]
    else:
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        out = xf * jax.lax.rsqrt(ms + 1e-6) * p["scale"]
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, dh); positions: (B, S) or (S,)."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = jnp.exp(
        -math.log(theta) * jnp.arange(half, dtype=jnp.float32) / half
    )
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B, S, half)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1
    ).astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


def attn_specs(cfg: ModelConfig, cross: bool = False) -> Dict[str, ArraySpec]:
    d, h, kv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    dt = cfg.dtype
    return {
        "wq": ArraySpec((d, h * dh), dt, ("embed", "heads")),
        "wk": ArraySpec((d, kv * dh), dt, ("embed", "kv_heads")),
        "wv": ArraySpec((d, kv * dh), dt, ("embed", "kv_heads")),
        "wo": ArraySpec((h * dh, d), dt, ("heads", "embed")),
    }


def _is_static_nowindow(window) -> bool:
    return isinstance(window, (int, float)) and window == 0


def _mask(kind: str, qpos, kpos, window):
    """(Sq, Sk) bool validity mask from position vectors. ``window`` may be a
    traced scalar (gemma3: per-layer local/global selected by a scanned
    flag)."""
    q = qpos[:, None]
    k = kpos[None, :]
    if kind == "bidir":
        return jnp.ones((qpos.shape[0], kpos.shape[0]), bool)
    m = q >= k
    if kind == "window" and not _is_static_nowindow(window):
        m = jnp.logical_and(m, q - k < window)
    return m


def kv_quantize(x: jax.Array):
    """Per-(…, head) symmetric int8 quantisation over the head_dim axis.
    x: (..., kv, dh) -> (int8 values, f32 scales (..., kv))."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(
        jnp.round(x.astype(jnp.float32) / scale[..., None]), -127, 127
    ).astype(jnp.int8)
    return q, scale


def kv_dequantize(q: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def chunked_attention(
    q: jax.Array,  # (B, Sq, H, dh)
    k: jax.Array,  # (B, Sk, KV, dh)
    v: jax.Array,  # (B, Sk, KV, dh)
    *,
    mask_kind: str,
    window: int = 0,
    q_positions: jax.Array,  # (Sq,)
    k_positions: jax.Array,  # (Sk,)
    chunk: int = 1024,
    remat_step: bool = False,
) -> jax.Array:
    """Online-softmax attention over KV chunks: score memory is
    O(B*H*Sq*chunk) instead of O(B*H*Sq*Sk)."""
    b, sq, h, dh = q.shape
    _, sk, kvh, _ = k.shape
    groups = h // kvh
    scale = 1.0 / math.sqrt(dh)
    n_chunks = -(-sk // chunk)
    pad = n_chunks * chunk - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_positions = jnp.pad(k_positions, (0, pad), constant_values=-(10**9))
    kc = k.reshape(b, n_chunks, chunk, kvh, dh)
    vc = v.reshape(b, n_chunks, chunk, kvh, dh)
    pc = k_positions.reshape(n_chunks, chunk)
    qg = q.reshape(b, sq, kvh, groups, dh).astype(jnp.float32)

    def step(carry, xs):
        m_prev, l_prev, acc = carry
        kb, vb, pb = xs  # (B, chunk, KV, dh), (chunk,)
        s = jnp.einsum(
            "bqkgd,bckd->bqkgc", qg, kb.astype(jnp.float32)
        ) * scale  # (B, Sq, KV, G, chunk)
        valid = _mask(mask_kind, q_positions, pb, window)  # (Sq, chunk)
        # chunk padding carries sentinel position -1e9: never attendable
        # (the causal test q >= k alone would wrongly admit it)
        valid = jnp.logical_and(valid, (pb >= 0)[None, :])
        s = jnp.where(valid[None, :, None, None, :], s, -1e30)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_cur[..., None])
        corr = jnp.exp(m_prev - m_cur)
        l_cur = l_prev * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bqkgc,bckd->bqkgd", p, vb.astype(jnp.float32)
        )
        return (m_cur, l_cur, acc), None

    if remat_step:
        # flash-attention-style: recompute scores/probs in the backward
        # instead of saving (B,Sq,KV,G,chunk) tensors per chunk step
        step = jax.checkpoint(step)
    init = (
        jnp.full((b, sq, kvh, groups), -jnp.inf, jnp.float32),
        jnp.zeros((b, sq, kvh, groups), jnp.float32),
        jnp.zeros((b, sq, kvh, groups, dh), jnp.float32),
    )
    (m_f, l_f, acc), _ = jax.lax.scan(
        step,
        init,
        (
            jnp.moveaxis(kc, 1, 0),
            jnp.moveaxis(vc, 1, 0),
            pc,
        ),
    )
    out = acc / jnp.maximum(l_f, 1e-30)[..., None]
    return out.reshape(b, sq, h, dh).astype(q.dtype)


def decode_attention(
    q: jax.Array,  # (B, Sq, H, dh) — Sq == 1 for token decode, > 1 for chunks
    k_cache: jax.Array,  # (B, S, KV, dh)
    v_cache: jax.Array,
    cur_pos: jax.Array,  # (B,) position of the (single) new token, or
    #                      (B, Sq) absolute position of every query row
    *,
    window: int = 0,
) -> jax.Array:
    """Attention of Sq query tokens against the full cache (O(Sq*S) work).

    The single-token decode case (Sq == 1) keeps its historical einsum so
    existing decode traces stay bit-identical; the Sq > 1 case serves
    *chunked prefill*: a prompt chunk whose KV rows were just scattered
    into the cache attends causally over everything at positions
    <= its own (cache prefix + intra-chunk causal, one mask)."""
    b, sq, h, dh = q.shape
    _, s, kvh, _ = k_cache.shape
    groups = h // kvh
    scale = 1.0 / math.sqrt(dh)
    qpos = cur_pos if cur_pos.ndim == 2 else cur_pos[:, None]  # (B, Sq)
    kpos = jnp.arange(s)  # (S,)
    if sq == 1:
        qg = q.reshape(b, kvh, groups, dh).astype(jnp.float32)
        scores = jnp.einsum(
            "bkgd,bskd->bkgs", qg, k_cache.astype(jnp.float32)
        ) * scale
        valid = kpos[None, :] <= qpos[:, 0][:, None]
        if not _is_static_nowindow(window):
            valid = jnp.logical_and(valid, qpos[:, 0][:, None] - kpos < window)
        scores = jnp.where(valid[:, None, None, :], scores, -1e30)
        p = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bkgs,bskd->bkgd", p, v_cache.astype(jnp.float32))
        return out.reshape(b, 1, h, dh).astype(q.dtype)
    qg = q.reshape(b, sq, kvh, groups, dh).astype(jnp.float32)
    scores = jnp.einsum(
        "bqkgd,bskd->bqkgs", qg, k_cache.astype(jnp.float32)
    ) * scale
    valid = kpos[None, None, :] <= qpos[:, :, None]  # (B, Sq, S)
    if not _is_static_nowindow(window):
        valid = jnp.logical_and(valid, qpos[:, :, None] - kpos[None, None, :] < window)
    scores = jnp.where(valid[:, :, None, None, :], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bqkgs,bskd->bqkgd", p, v_cache.astype(jnp.float32))
    return out.reshape(b, sq, h, dh).astype(q.dtype)


def decode_attention_ring(
    q: jax.Array,  # (B, 1, H, dh)
    k_ring: jax.Array,  # (B, W, KV, dh) rolling window, slot j holds the
    v_ring: jax.Array,  # most recent position p with p % W == j
    cur_pos: jax.Array,  # (B,)
    window: int,
) -> jax.Array:
    """Single-token attention over a ring-buffer window cache: O(W) work and
    O(W) reads instead of O(S) — the windowed-cache serving optimization for
    local-attention layers (gemma3's 5:6 of the stack)."""
    b, _, h, dh = q.shape
    w = k_ring.shape[1]
    kvh = k_ring.shape[2]
    groups = h // kvh
    scale = 1.0 / math.sqrt(dh)
    qg = q.reshape(b, kvh, groups, dh).astype(jnp.float32)
    scores = jnp.einsum("bkgd,bskd->bkgs", qg, k_ring.astype(jnp.float32)) * scale
    # slot j currently holds position cur - ((cur - j) mod W)
    slots = jnp.arange(w)[None, :]
    kpos = cur_pos[:, None] - jnp.mod(cur_pos[:, None] - slots, w)
    valid = jnp.logical_and(kpos >= 0, cur_pos[:, None] - kpos < window)
    scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p, v_ring.astype(jnp.float32))
    return out.reshape(b, 1, h, dh).astype(q.dtype)


def attn_apply_ring(
    p: Params,
    x: jax.Array,  # (B, 1, D)
    cfg: ModelConfig,
    *,
    div: Dict[str, int],
    cache: Dict[str, jax.Array],  # k/v rings (B, W, kv, dh)
    cur_pos: jax.Array,  # (B,)
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Decode step for a local-attention layer against a ring cache."""
    b, s, d = x.shape
    h, kv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    db, dtp = div.get("batch", 1), div.get("model", 1)
    w = cache["k"].shape[1]

    q = gemm(x, p["wq"], divisors=(db, dtp, 1), tag="attn.q").reshape(b, 1, h, dh)
    knew = gemm(x, p["wk"], divisors=(db, dtp, 1), tag="attn.k").reshape(b, 1, kv, dh)
    vnew = gemm(x, p["wv"], divisors=(db, dtp, 1), tag="attn.v").reshape(b, 1, kv, dh)
    q = rope(q, cur_pos[:, None], cfg.rope_theta)
    knew = rope(knew, cur_pos[:, None], cfg.rope_theta)

    bidx = jnp.arange(b)
    slot = jnp.mod(cur_pos, w)
    k_ring = cache["k"].at[bidx, slot].set(knew[:, 0])
    v_ring = cache["v"].at[bidx, slot].set(vnew[:, 0])
    out = decode_attention_ring(q, k_ring, v_ring, cur_pos, cfg.window)
    y = gemm(out.reshape(b, 1, h * dh), p["wo"], divisors=(db, 1, dtp), tag="attn.o")
    return y, {"k": k_ring, "v": v_ring}


def attn_apply(
    p: Params,
    x: jax.Array,  # (B, S, D)
    cfg: ModelConfig,
    *,
    div: Dict[str, int],
    mask_kind: str = "causal",
    window: int = 0,
    positions: Optional[jax.Array] = None,  # (S,) or (B,S) absolute positions
    cache: Optional[Dict[str, jax.Array]] = None,
    cur_pos: Optional[jax.Array] = None,  # (B,) decode position
    kv_override: Optional[Tuple[jax.Array, jax.Array]] = None,  # cross-attn
    use_rope: bool = True,
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """GQA attention. Modes:
      * train/prefill: ``cache=None`` -> chunked attention over x itself
        (returns fresh cache when ``positions`` is provided and prefill=True
        handled by caller via returned k/v).
      * decode: ``cache`` + ``cur_pos`` -> one-token attention, cache updated.
      * cross: ``kv_override`` supplies fixed (k, v).
    """
    b, s, d = x.shape
    h, kv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    # Per-shard GEMM divisors: tokens are sharded over the batch axes; the
    # output dim of column-parallel projections over "model"; FSDP-sharded
    # contraction dims are all-gathered by GSPMD so K stays full.
    db, dtp = div.get("batch", 1), div.get("model", 1)

    q = gemm(x, p["wq"], divisors=(db, dtp, 1), tag="attn.q")
    q = q.reshape(b, s, h, dh)

    if kv_override is not None:
        knew = vnew = None
        k_full, v_full = kv_override
    else:
        knew = gemm(x, p["wk"], divisors=(db, dtp, 1), tag="attn.k").reshape(
            b, s, kv, dh
        )
        vnew = gemm(x, p["wv"], divisors=(db, dtp, 1), tag="attn.v").reshape(
            b, s, kv, dh
        )

    if positions is None:
        positions = jnp.arange(s)
    if use_rope and kv_override is None:
        q = rope(q, positions, cfg.rope_theta)
        knew = rope(knew, positions, cfg.rope_theta)
    elif use_rope:
        q = rope(q, positions, cfg.rope_theta)

    new_cache = None
    if cache is not None and cur_pos is not None and s > 1:
        # chunked prefill: scatter the chunk's KV rows at absolute positions
        # cur_pos..cur_pos+s-1, then attend each query row over the cache
        # prefix plus the intra-chunk causal span — one decode_attention
        # mask covers both. (kv_cache_dtype == "int8" quantizes the whole
        # chunk at once; kv_quantize is shape-generic over leading axes.)
        bidx = jnp.arange(b)[:, None]
        pos_block = cur_pos[:, None] + jnp.arange(s)[None, :]  # (B, S)
        if cfg.kv_cache_dtype == "int8":
            kq, ks = kv_quantize(knew)
            vq, vs = kv_quantize(vnew)
            k_cache = cache["k"].at[bidx, pos_block].set(kq)
            v_cache = cache["v"].at[bidx, pos_block].set(vq)
            k_scale = cache["k_scale"].at[bidx, pos_block].set(ks)
            v_scale = cache["v_scale"].at[bidx, pos_block].set(vs)
            new_cache = {
                "k": k_cache,
                "v": v_cache,
                "k_scale": k_scale,
                "v_scale": v_scale,
            }
            k_full = kv_dequantize(k_cache, k_scale, cfg.dtype)
            v_full = kv_dequantize(v_cache, v_scale, cfg.dtype)
            out = decode_attention(q, k_full, v_full, pos_block, window=window)
        else:
            k_cache = cache["k"].at[bidx, pos_block].set(knew)
            v_cache = cache["v"].at[bidx, pos_block].set(vnew)
            new_cache = {"k": k_cache, "v": v_cache}
            out = decode_attention(q, k_cache, v_cache, pos_block, window=window)
    elif cache is not None and cur_pos is not None:
        # decode: scatter the new token into the cache, attend over it all
        bidx = jnp.arange(b)
        if cfg.kv_cache_dtype == "int8":
            # quantized KV cache: int8 values + per-(token, head) scales —
            # halves the decode memory term (the dominant roofline term of
            # the decode cells); dequant fuses into the attention dots
            kq, ks = kv_quantize(knew[:, 0])
            vq, vs = kv_quantize(vnew[:, 0])
            k_cache = cache["k"].at[bidx, cur_pos].set(kq)
            v_cache = cache["v"].at[bidx, cur_pos].set(vq)
            k_scale = cache["k_scale"].at[bidx, cur_pos].set(ks)
            v_scale = cache["v_scale"].at[bidx, cur_pos].set(vs)
            new_cache = {
                "k": k_cache,
                "v": v_cache,
                "k_scale": k_scale,
                "v_scale": v_scale,
            }
            k_full = kv_dequantize(k_cache, k_scale, cfg.dtype)
            v_full = kv_dequantize(v_cache, v_scale, cfg.dtype)
            out = decode_attention(q, k_full, v_full, cur_pos, window=window)
        else:
            k_cache = cache["k"].at[bidx, cur_pos].set(knew[:, 0])
            v_cache = cache["v"].at[bidx, cur_pos].set(vnew[:, 0])
            new_cache = {"k": k_cache, "v": v_cache}
            out = decode_attention(q, k_cache, v_cache, cur_pos, window=window)
    elif cfg.attn_impl == "mha_expand" and kv_override is None:
        # perf variant: expand KV to the full head count and shard the head
        # dim (unevenly if needed — GSPMD pads, e.g. 56 heads over 16) so
        # the score tensors stay head-parallel instead of replicated.
        groups = h // kv
        k_full = jnp.repeat(knew, groups, axis=2)
        v_full = jnp.repeat(vnew, groups, axis=2)
        q = constrain_uneven(q, "batch", None, "heads", None)
        k_full = constrain_uneven(k_full, "batch", None, "heads", None)
        v_full = constrain_uneven(v_full, "batch", None, "heads", None)
        out = chunked_attention(
            q,
            k_full,
            v_full,
            mask_kind=mask_kind,
            window=window,
            q_positions=positions if positions.ndim == 1 else positions[0],
            k_positions=positions if positions.ndim == 1 else positions[0],
            chunk=cfg.attn_chunk,
            remat_step=cfg.attn_remat,
        )
        new_cache = {"k": knew, "v": vnew}
    elif kv_override is not None:
        sk = k_full.shape[1]
        out = chunked_attention(
            q,
            k_full,
            v_full,
            mask_kind="bidir",
            q_positions=jnp.arange(s),
            k_positions=jnp.arange(sk),
            chunk=cfg.attn_chunk,
            remat_step=cfg.attn_remat,
        )
    else:
        out = chunked_attention(
            q,
            knew,
            vnew,
            mask_kind=mask_kind,
            window=window,
            q_positions=positions if positions.ndim == 1 else positions[0],
            k_positions=positions if positions.ndim == 1 else positions[0],
            chunk=cfg.attn_chunk,
            remat_step=cfg.attn_remat,
        )
        new_cache = {"k": knew, "v": vnew}  # prefill: caller may keep these

    y = gemm(
        out.reshape(b, s, h * dh), p["wo"], divisors=(db, 1, dtp), tag="attn.o"
    )
    return y, new_cache


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------


def mlp_specs(cfg: ModelConfig) -> Dict[str, ArraySpec]:
    d, f, dt = cfg.d_model, cfg.d_ff, cfg.dtype
    spec = {
        "w_in": ArraySpec((d, f), dt, ("embed", "ffn")),
        "w_out": ArraySpec((f, d), dt, ("ffn", "embed")),
    }
    if cfg.mlp_act == "swiglu":
        spec["w_gate"] = ArraySpec((d, f), dt, ("embed", "ffn"))
    return spec


def mlp_apply(p: Params, x: jax.Array, cfg: ModelConfig, *, div: Dict[str, int]):
    """Activations ride the GEMM epilogue (applied to the f32 accumulator in
    the kernel flush / fix-up phase) instead of running as separate XLA ops;
    swiglu fuses the gate-multiply into the up-projection's epilogue."""
    db, dtp = div.get("batch", 1), div.get("model", 1)
    if cfg.mlp_act == "swiglu":
        g = gemm(x, p["w_gate"], divisors=(db, dtp, 1), tag="mlp.gate")
        h = gemm(
            x,
            p["w_in"],
            divisors=(db, dtp, 1),
            tag="mlp.in",
            epilogue=Epilogue(binary="mul_silu"),
            operand=g,
        )
    elif cfg.mlp_act == "squared_relu":  # nemotron-4
        h = gemm(x, p["w_in"], divisors=(db, dtp, 1), tag="mlp.in", epilogue="square")
    else:
        h = gemm(x, p["w_in"], divisors=(db, dtp, 1), tag="mlp.in", epilogue="gelu")
    return gemm(h, p["w_out"], divisors=(db, 1, dtp), tag="mlp.out")


# ---------------------------------------------------------------------------
# MoE (capacity-based expert-parallel dispatch; GShard-style, deterministic,
# no sort: position-in-expert via rank-major cumsum)
# ---------------------------------------------------------------------------


def moe_specs(cfg: ModelConfig) -> Dict[str, ArraySpec]:
    d, f, e, dt = cfg.d_model, cfg.d_ff, cfg.n_experts, cfg.dtype
    spec = {
        "router": ArraySpec((d, e), "float32", ("embed", None)),
        "w_in": ArraySpec((e, d, f), dt, ("experts", "embed", None)),
        "w_out": ArraySpec((e, f, d), dt, ("experts", None, "embed")),
    }
    if cfg.mlp_act == "swiglu":
        spec["w_gate"] = ArraySpec((e, d, f), dt, ("experts", "embed", None))
    return spec


def moe_apply(
    p: Params, x: jax.Array, cfg: ModelConfig, *, div: Dict[str, int]
) -> Tuple[jax.Array, jax.Array]:
    """Returns (output, aux_load_balance_loss)."""
    if cfg.moe_impl == "sharded":
        return moe_apply_sharded(p, x, cfg, div=div)
    if cfg.moe_impl in ("shard_map", "shard_map_bf16"):
        from repro.dist.sharding import current_plan

        # quantized expert weights fall through to the capacity-dispatch
        # path: shard_map in_specs are rank-pinned P(...) specs for dense
        # (E, K, N) arrays and cannot describe a QuantizedTensor's
        # (values, scales) leaf pair — semantics are identical either way
        if current_plan() is not None and not is_quantized(p["w_in"]):
            return moe_apply_shard_map(p, x, cfg, div=div)
        # no mesh installed (CPU tests): fall through — semantics identical
    hinted = cfg.moe_impl == "hinted"
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    t = b * s
    xf = x.reshape(t, d)
    if hinted:
        xf = constrain(xf, "batch", None)

    logits = gemm(
        xf.astype(jnp.float32), p["router"], divisors=(div.get("batch", 1), 1, 1),
        tag="moe.router",
    )
    probs = jax.nn.softmax(logits, axis=-1)  # (T, E)
    gates, idx = jax.lax.top_k(probs, k)  # (T, k)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)

    # capacity per expert; the min(t, 16) floor makes tiny-T dispatch
    # (single-token decode) drop-free — a token can always place its top-k
    cap = max(int(cfg.capacity_factor * t * k / e), min(t, 16), 1)
    if hinted:
        # perf variant: token-major assignment order keeps the flattened
        # (T*k,) axis sharded like T (k is the minor reshape dim so GSPMD
        # propagates the batch sharding); capacity priority becomes
        # position-in-batch — GShard's original — instead of rank-major
        e_flat = constrain(idx.reshape(t * k), "batch")
        tok = jnp.repeat(jnp.arange(t), k)
        gate_flat = gates.reshape(t * k)
    else:
        # rank-major assignment order: rank-0 choices of all tokens first, so
        # a token's primary expert wins capacity over another's secondary.
        e_flat = idx.T.reshape(t * k)  # (k*T,)
        tok = jnp.tile(jnp.arange(t), k)
        gate_flat = gates.T.reshape(t * k)
    onehot = jax.nn.one_hot(e_flat, e, dtype=jnp.int32)  # (kT, E)
    pos = jnp.cumsum(onehot, axis=0) * onehot - 1
    pos = jnp.max(pos, axis=-1)  # (kT,) position in chosen expert
    keep = pos < cap
    slot = jnp.where(keep, pos, cap)  # cap = trash column

    # dispatch: (E, cap+1, D); trash column absorbs dropped tokens
    buf = jnp.zeros((e, cap + 1, d), x.dtype)
    buf = buf.at[e_flat, slot].set(xf[tok], mode="drop")
    expert_in = buf[:, :cap]
    if hinted:
        # experts-only sharding: the embed dim must stay unsharded because
        # 'data' is already carrying the token dim of the scatter updates
        # (iteration-2 refutation: ('experts',None,'embed') blew memory up)
        expert_in = constrain(expert_in, "experts", None, None)

    # grouped expert GEMMs: one GemmOp with G = E covers the whole stack —
    # exactly the skinny-M (M = capacity) grouped shapes where Stream-K's
    # work-centric decomposition matters most; activations fuse into the
    # kernel epilogue instead of running as separate XLA ops
    dg = div.get("model", 1)
    if cfg.mlp_act == "swiglu":
        g = gemm_grouped(expert_in, p["w_gate"], g_divisor=dg, tag="moe.gate")
        h = gemm_grouped(
            expert_in,
            p["w_in"],
            g_divisor=dg,
            tag="moe.in",
            epilogue=Epilogue(binary="mul_silu"),
            operand=g,
        )
    else:
        h = gemm_grouped(
            expert_in, p["w_in"], g_divisor=dg, tag="moe.in", epilogue="gelu"
        )
    out_e = gemm_grouped(h, p["w_out"], g_divisor=dg, tag="moe.out")  # (E, cap, D)
    if hinted:
        out_e = constrain(out_e, "experts", None, None)

    # combine: gather back per assignment, weight, sum over ranks
    gathered = out_e[e_flat, jnp.minimum(slot, cap - 1)]  # (kT, D)
    w = (gate_flat * keep).astype(jnp.float32)
    if hinted:
        gathered = constrain(gathered, "batch", None)
        combined = (gathered.astype(jnp.float32) * w[:, None]).reshape(t, k, d).sum(1)
    else:
        combined = (gathered.astype(jnp.float32) * w[:, None]).reshape(k, t, d).sum(0)

    # load-balance aux loss (Switch): E * sum_e f_e * p_e
    frac = jnp.mean(
        onehot.reshape((t, k, e) if hinted else (k, t, e))
        .sum(1 if hinted else 0)
        .astype(jnp.float32),
        axis=0,
    )
    mean_p = jnp.mean(probs, axis=0)
    aux = cfg.router_aux_coef * e * jnp.sum(frac * mean_p)
    return combined.reshape(b, s, d).astype(x.dtype), aux


def moe_apply_sharded(
    p: Params, x: jax.Array, cfg: ModelConfig, *, div: Dict[str, int]
) -> Tuple[jax.Array, jax.Array]:
    """Perf variant (``moe_impl="sharded"``): shard-local capacity dispatch.

    The baseline routes over the *global* token space: the cumsum that
    assigns capacity slots spans all tokens, so under GSPMD it serialises
    across data shards (collective-permute chains) and the dispatch scatter
    gathers activations globally. Here every data shard routes its own
    tokens into its own (E, cap_local) buffer — routing math is embarrassingly
    parallel over shards — and only the expert computation crosses the mesh
    (tokens meet model-sharded experts: the canonical MoE all-to-all).
    Capacity semantics per shard are identical to GShard with per-shard
    groups (the standard formulation at scale)."""
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    t = b * s
    groups = div.get("batch", 1)
    if t % groups:
        groups = 1
    tl = t // groups
    xg = constrain(x.reshape(groups, tl, d), "batch", None, None)

    logits = jnp.einsum(
        "gtd,de->gte", xg.astype(jnp.float32), p["router"].astype(jnp.float32)
    )
    probs = jax.nn.softmax(logits, axis=-1)  # (G, Tl, E)
    gates, idx = jax.lax.top_k(probs, k)  # (G, Tl, k)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)

    cap = max(int(cfg.capacity_factor * tl * k / e), min(tl, 16), 1)
    # rank-major within each shard (primary choices win capacity)
    e_flat = idx.transpose(0, 2, 1).reshape(groups, tl * k)  # (G, kTl)
    onehot = jax.nn.one_hot(e_flat, e, dtype=jnp.int32)  # (G, kTl, E)
    pos = jnp.cumsum(onehot, axis=1) * onehot - 1
    pos = jnp.max(pos, axis=-1)  # (G, kTl)
    keep = pos < cap
    slot = jnp.where(keep, pos, cap)

    tok = jnp.tile(jnp.arange(tl), k)[None, :].repeat(groups, 0)  # (G, kTl)
    gidx = jnp.arange(groups)[:, None]
    buf = jnp.zeros((groups, e, cap + 1, d), x.dtype)
    buf = buf.at[gidx, e_flat, slot].set(
        jnp.take_along_axis(xg, tok[..., None], axis=1), mode="drop"
    )
    expert_in = constrain(buf[:, :, :cap], "batch", "experts", None, None)

    # fold the shard-group dim into M: each expert contracts (G*cap, d) in
    # one grouped GemmOp (G = E), keeping the expert GEMMs on the Stream-K++
    # dispatch layer under the shard-local formulation too
    e_in = expert_in.transpose(1, 0, 2, 3).reshape(e, groups * cap, d)
    dg = div.get("model", 1)
    if cfg.mlp_act == "swiglu":
        g_ = gemm_grouped(e_in, p["w_gate"], g_divisor=dg, tag="moe.gate")
        h = gemm_grouped(
            e_in,
            p["w_in"],
            g_divisor=dg,
            tag="moe.in",
            epilogue=Epilogue(binary="mul_silu"),
            operand=g_,
        )
    else:
        h = gemm_grouped(e_in, p["w_in"], g_divisor=dg, tag="moe.in", epilogue="gelu")
    out = gemm_grouped(h, p["w_out"], g_divisor=dg, tag="moe.out")  # (E, G*cap, D)
    out_e = out.reshape(e, groups, cap, d).transpose(1, 0, 2, 3)
    out_e = constrain(out_e, "batch", "experts", None, None)

    gathered = out_e[gidx, e_flat, jnp.minimum(slot, cap - 1)]  # (G, kTl, D)
    w = (gates.transpose(0, 2, 1).reshape(groups, tl * k) * keep).astype(jnp.float32)
    combined = (
        (gathered.astype(jnp.float32) * w[..., None])
        .reshape(groups, k, tl, d)
        .sum(1)
    )

    frac = jnp.mean(
        onehot.reshape(groups, k, tl, e).sum(1).astype(jnp.float32), axis=(0, 1)
    )
    mean_p = jnp.mean(probs, axis=(0, 1))
    aux = cfg.router_aux_coef * e * jnp.sum(frac * mean_p)
    return combined.reshape(b, s, d).astype(x.dtype), aux


def moe_apply_shard_map(
    p: Params, x: jax.Array, cfg: ModelConfig, *, div: Dict[str, int]
) -> Tuple[jax.Array, jax.Array]:
    """Perf variant (``moe_impl="shard_map"``): explicit expert-parallel MoE.

    Three GSPMD formulations failed on this dispatch (§Perf iteration log):
    data-dependent scatters with more than one sharded target axis get
    replicated. The fix is to stop asking the partitioner: under
    ``shard_map`` every (data, model) shard routes the tokens of its data
    row — which the residual stream already replicates across the model
    axis — into buffers for the E/M experts IT owns. Dispatch is therefore
    entirely local; the only communication is the combine ``psum`` over
    'model' (+ GSPMD's usual gradient handling outside).

    Capacity semantics: per data-row capacity, token-major priority — the
    same contract as ``moe_impl="hinted"``.
    """
    from jax.sharding import PartitionSpec as P

    from repro.dist.sharding import current_plan

    plan = current_plan()
    mesh = plan.mesh
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    mp = mesh.shape.get("model", 1)

    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    assert e % mp == 0, "expert count must divide the model axis"
    e_loc = e // mp

    def local(xb, router, w_in, w_gate, w_out):
        # xb: (B_loc, S, D) — this data-row's tokens (replicated over model)
        bl = xb.shape[0]
        tl = bl * s
        xf = xb.reshape(tl, d)
        logits = jnp.dot(xf.astype(jnp.float32), router.astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)
        gates, idx = jax.lax.top_k(probs, k)
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)

        cap = max(int(cfg.capacity_factor * tl * k / e), min(tl, 16), 1)
        e_flat = idx.reshape(tl * k)  # token-major priority
        onehot = jax.nn.one_hot(e_flat, e, dtype=jnp.int32)
        pos = jnp.max(jnp.cumsum(onehot, axis=0) * onehot - 1, axis=-1)
        keep = pos < cap
        slot = jnp.where(keep, pos, cap)

        # dispatch ONLY into this shard's experts: local ids [0, e_loc)
        j = jax.lax.axis_index("model") if "model" in mesh.axis_names else 0
        e_local = e_flat - j * e_loc
        in_range = jnp.logical_and(e_local >= 0, e_local < e_loc)
        e_clamped = jnp.clip(e_local, 0, e_loc - 1)
        slot_masked = jnp.where(in_range, slot, cap)  # out-of-range -> trash
        tok = jnp.repeat(jnp.arange(tl), k)
        buf = jnp.zeros((e_loc, cap + 1, d), x.dtype)
        buf = buf.at[e_clamped, slot_masked].set(xf[tok], mode="drop")
        expert_in = buf[:, :cap]

        # shapes here are already shard-local (shard_map body), so the
        # grouped dispatch runs with unit divisors; G = e_loc experts
        if cfg.mlp_act == "swiglu":
            g_ = gemm_grouped(expert_in, w_gate, tag="moe.gate")
            h = gemm_grouped(
                expert_in,
                w_in,
                tag="moe.in",
                epilogue=Epilogue(binary="mul_silu"),
                operand=g_,
            )
        else:
            h = gemm_grouped(expert_in, w_in, tag="moe.in", epilogue="gelu")
        out_e = gemm_grouped(h, w_out, tag="moe.out")  # (e_loc, cap, D)

        # combine: local assignments only, then sum partial outputs
        gathered = out_e[e_clamped, jnp.minimum(slot_masked, cap - 1)]
        w = (
            gates.reshape(tl * k)
            * keep
            * in_range
        ).astype(jnp.float32)
        combined = (gathered.astype(jnp.float32) * w[:, None]).reshape(
            tl, k, d
        ).sum(1)
        if "model" in mesh.axis_names:
            if cfg.moe_impl == "shard_map_bf16":
                # halve the combine traffic; each shard's partial is a sum
                # of <= k bf16 products — quantisation comparable to the
                # layer's own bf16 output cast
                combined = jax.lax.psum(
                    combined.astype(jnp.bfloat16), "model"
                ).astype(jnp.float32)
            else:
                combined = jax.lax.psum(combined, "model")

        frac = jnp.mean(
            onehot.reshape(tl, k, e).sum(1).astype(jnp.float32), axis=0
        )
        mean_p = jnp.mean(probs, axis=0)
        aux = cfg.router_aux_coef * e * jnp.sum(frac * mean_p)
        return combined.reshape(bl, s, d).astype(x.dtype), aux

    batch_spec = P(dp_axes if len(dp_axes) > 1 else (dp_axes[0] if dp_axes else None))
    x_spec = P(batch_spec[0], None, None)
    out = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(
            x_spec,
            P(None, None),  # router replicated
            P("model", None, None),  # experts over model
            P("model", None, None),
            P("model", None, None),
        ),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(
        x,
        p["router"],
        p["w_in"],
        p.get("w_gate", p["w_in"]),
        p["w_out"],
    )
    return out
