"""GemmOp dispatch API: grouped/batched entry points, epilogue fusion,
backend registry, selector observability, op-fingerprint keying."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import tiny
from repro.core.gemm import (
    gemm,
    gemm_batched,
    gemm_context,
    gemm_grouped,
    get_backend,
    list_backends,
    register_backend,
)
from repro.core.op import Epilogue, GemmOp, encode_key
from repro.core.policies import ALL_SK, DP, TileConfig
from repro.core.selector import KernelSelector, default_selector
from repro.core.tuner import Tuner


# ---------------------------------------------------------------------------
# grouped / batched entry points
# ---------------------------------------------------------------------------


def test_grouped_matches_einsum():
    r = np.random.default_rng(0)
    x = jnp.asarray(r.normal(size=(4, 8, 32)), jnp.float32)
    w = jnp.asarray(r.normal(size=(4, 32, 16)), jnp.float32)
    with gemm_context(selector=default_selector()) as ctx:
        got = gemm_grouped(x, w, tag="t")
    want = jnp.einsum("gmk,gkn->gmn", x, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)
    [e] = ctx.log
    assert e.op.g == 4 and e.op.kind == "grouped"
    assert e.op.local == (8, 16, 32)


def test_batched_matches_einsum():
    r = np.random.default_rng(1)
    x = jnp.asarray(r.normal(size=(3, 5, 16)), jnp.float32)
    w = jnp.asarray(r.normal(size=(3, 16, 8)), jnp.float32)
    with gemm_context(selector=default_selector()) as ctx:
        got = gemm_batched(x, w)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(jnp.einsum("gmk,gkn->gmn", x, w)), rtol=1e-6
    )
    assert ctx.log[0].op.kind == "batched"


def test_grouped_shape_validation():
    with pytest.raises(ValueError):
        gemm_grouped(jnp.ones((2, 4, 8)), jnp.ones((3, 8, 4)))  # G mismatch
    with pytest.raises(ValueError):
        gemm_grouped(jnp.ones((2, 4, 8)), jnp.ones((2, 9, 4)))  # K mismatch
    with pytest.raises(ValueError):
        gemm_grouped(jnp.ones((4, 8)), jnp.ones((8, 4)))  # not stacked


# ---------------------------------------------------------------------------
# epilogue fusion
# ---------------------------------------------------------------------------


def _ref_epilogue(acc, epi: Epilogue, bias=None, operand=None):
    acc = acc.astype(jnp.float32)
    if epi.bias:
        acc = acc + bias.astype(jnp.float32)
    if epi.activation == "gelu":
        acc = jax.nn.gelu(acc)
    elif epi.activation == "silu":
        acc = jax.nn.silu(acc)
    elif epi.activation == "relu":
        acc = jnp.maximum(acc, 0.0)
    elif epi.activation == "square":
        acc = jnp.square(jnp.maximum(acc, 0.0))
    if epi.binary == "mul_silu":
        acc = acc * jax.nn.silu(operand.astype(jnp.float32))
    elif epi.binary == "add":
        acc = acc + operand.astype(jnp.float32)
    return acc


EPILOGUES = [
    Epilogue(activation="gelu"),
    Epilogue(activation="silu"),
    Epilogue(activation="square"),
    Epilogue(bias=True),
    Epilogue(bias=True, activation="gelu"),
    Epilogue(binary="mul_silu"),
    Epilogue(binary="add"),
]


@pytest.mark.parametrize("epi", EPILOGUES, ids=lambda e: e.name)
@pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
def test_epilogue_fused_matches_unfused(epi, backend):
    r = np.random.default_rng(2)
    m, n, k = 16, 128, 64
    x = jnp.asarray(r.normal(size=(m, k)), jnp.float32)
    w = jnp.asarray(r.normal(size=(k, n)), jnp.float32)
    bias = jnp.asarray(r.normal(size=(n,)), jnp.float32) if epi.bias else None
    operand = (
        jnp.asarray(r.normal(size=(m, n)), jnp.float32)
        if epi.binary != "none"
        else None
    )
    want = _ref_epilogue(jnp.dot(x, w), epi, bias=bias, operand=operand)
    kw = dict(policy=ALL_SK, cfg=TileConfig(8, 128, 128)) if backend.startswith(
        "pallas"
    ) else {}
    with gemm_context(selector=default_selector(), backend=backend):
        got = gemm(x, w, epilogue=epi, bias=bias, operand=operand, **kw)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4
    )


def test_epilogue_fused_grouped():
    r = np.random.default_rng(3)
    x = jnp.asarray(r.normal(size=(3, 8, 32)), jnp.float32)
    w = jnp.asarray(r.normal(size=(3, 32, 16)), jnp.float32)
    gate = jnp.asarray(r.normal(size=(3, 8, 16)), jnp.float32)
    with gemm_context(selector=default_selector()):
        got = gemm_grouped(
            x, w, epilogue=Epilogue(binary="mul_silu"), operand=gate
        )
    want = jnp.einsum("gmk,gkn->gmn", x, w) * jax.nn.silu(gate)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)


def test_epilogue_operand_mismatch_raises():
    x, w = jnp.ones((4, 8)), jnp.ones((8, 4))
    with pytest.raises(ValueError):
        gemm(x, w, epilogue=Epilogue(bias=True))  # bias spec, no bias operand
    with pytest.raises(ValueError):
        gemm(x, w, bias=jnp.ones((4,)))  # bias operand, no epilogue spec
    with pytest.raises(ValueError):
        gemm(x, w, epilogue=Epilogue(binary="add"))  # missing operand


# ---------------------------------------------------------------------------
# MoE routing: the dense-expert path dispatches grouped ops
# ---------------------------------------------------------------------------


def test_moe_forward_logs_grouped_ops():
    """A MoE forward pass must route its expert GEMMs through the grouped
    dispatch layer: the SelectionLog shows G > 1 entries for every expert
    matmul (router + in/out, + gate when swiglu)."""
    from repro.dist.sharding import materialize_tree
    from repro.models import build_model

    cfg = tiny("olmoe-1b-7b")
    model = build_model(cfg)
    params = materialize_tree(model.param_specs(), jax.random.PRNGKey(0))
    toks = jnp.asarray(np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 16)))
    with gemm_context(selector=default_selector()) as ctx:
        logits, _ = model.forward(params, toks)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))
    grouped = [e for e in ctx.log if e.op.g > 1]
    assert grouped, "no grouped ops in the SelectionLog — experts bypassed dispatch"
    tags = {e.tag for e in grouped}
    assert "moe.in" in tags and "moe.out" in tags
    for e in grouped:
        assert e.op.kind == "grouped"
        assert e.op.g == cfg.n_experts
    # grouped ops key independently of the plain path, and MoE dispatch
    # defaults to the fused one-kernel form (8-part grouped_fused key)
    assert all(len(e.op.key) == 8 for e in grouped)
    assert all(e.op.key[7] == "grouped_fused" for e in grouped)
    assert all(e.op.fused for e in grouped)


def test_moe_epilogue_fusion_matches_unfused_reference():
    """The fused expert MLP (epilogue in the GEMM) equals the hand-written
    einsum + activation reference within dtype tolerance."""
    from repro.dist.sharding import materialize_tree
    from repro.models import build_model
    from repro.models.layers import moe_apply

    cfg = tiny("olmoe-1b-7b")
    model = build_model(cfg)
    params = materialize_tree(model.param_specs(), jax.random.PRNGKey(0))
    p0 = jax.tree.map(lambda a: a[0], params["layers"])["moe"]
    r = np.random.default_rng(0)
    x = jnp.asarray(r.normal(size=(2, 8, cfg.d_model)) * 0.3, jnp.float32)
    with gemm_context(selector=default_selector()):
        got, _ = moe_apply(p0, x, cfg, div={})

    # unfused reference: replicate the dispatch math with raw einsums
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    t = b * s
    xf = x.reshape(t, d)
    logits = xf.astype(jnp.float32) @ p0["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, k)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    cap = max(int(cfg.capacity_factor * t * k / e), min(t, 16), 1)
    e_flat = idx.T.reshape(t * k)
    tok = jnp.tile(jnp.arange(t), k)
    gate_flat = gates.T.reshape(t * k)
    onehot = jax.nn.one_hot(e_flat, e, dtype=jnp.int32)
    pos = jnp.max(jnp.cumsum(onehot, axis=0) * onehot - 1, axis=-1)
    keep = pos < cap
    slot = jnp.where(keep, pos, cap)
    buf = jnp.zeros((e, cap + 1, d), x.dtype)
    buf = buf.at[e_flat, slot].set(xf[tok], mode="drop")
    expert_in = buf[:, :cap]
    h = jnp.einsum("ecd,edf->ecf", expert_in, p0["w_in"])
    if cfg.mlp_act == "swiglu":
        g = jnp.einsum("ecd,edf->ecf", expert_in, p0["w_gate"])
        h = (jax.nn.silu(g.astype(jnp.float32)) * h.astype(jnp.float32)).astype(
            x.dtype
        )
    else:
        h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
    out_e = jnp.einsum("ecf,efd->ecd", h, p0["w_out"])
    gathered = out_e[e_flat, jnp.minimum(slot, cap - 1)]
    wgt = (gate_flat * keep).astype(jnp.float32)
    want = (
        (gathered.astype(jnp.float32) * wgt[:, None]).reshape(k, t, d).sum(0)
    ).reshape(b, s, d)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4
    )


# ---------------------------------------------------------------------------
# backend registry
# ---------------------------------------------------------------------------


def test_unknown_backend_raises_with_registered_list():
    with pytest.raises(ValueError) as ei:
        with gemm_context(backend="cuda"):
            pass
    msg = str(ei.value)
    assert "cuda" in msg
    for name in ("xla", "pallas", "pallas_interpret"):
        assert name in msg


def test_builtin_backends_registered():
    names = list_backends()
    assert {"xla", "pallas", "pallas_interpret"} <= set(names)
    assert get_backend("xla") is not None


def test_register_backend_pluggable():
    calls = []

    def counting_backend(x, w, *, op, policy, cfg, g, bias, operand):
        calls.append(op)
        return jnp.einsum("gmk,gkn->gmn", x, w).astype(op.out_dtype)

    register_backend("counting_test", counting_backend, overwrite=True)
    x, w = jnp.ones((2, 32)), jnp.ones((32, 8))
    with gemm_context(selector=default_selector(), backend="counting_test"):
        out = gemm(x, w)
    assert out.shape == (2, 8)
    assert len(calls) == 1 and calls[0].global_mnk == (2, 8, 32)
    with pytest.raises(ValueError):
        register_backend("counting_test", counting_backend)  # no overwrite


# ---------------------------------------------------------------------------
# selector observability + op-fingerprint keying
# ---------------------------------------------------------------------------


def test_selector_counts_cache_hits_and_forced():
    sel = default_selector()
    x, w = jnp.ones((4, 32)), jnp.ones((32, 8))
    with gemm_context(selector=sel):
        gemm(x, w)
        gemm(x, w)  # same fingerprint -> memoised
        gemm(x, w, policy=ALL_SK, cfg=TileConfig(8, 128, 128))  # fully forced
        gemm(x, w, cfg=TileConfig(8, 128, 128))  # partial override
    s = sel.stats
    assert s.lookups == 4
    assert s.cache_hits == 1
    assert s.forced == 2  # full + partial overrides both categorise as forced
    # exactly one category per lookup — per-source fractions stay <= 100%
    assert s.tuned_hits + s.sieve_hits + s.fallbacks + s.cache_hits + s.forced == s.lookups


def test_partial_override_logs_what_actually_ran():
    sel = default_selector()
    x, w = jnp.ones((16, 64)), jnp.ones((64, 128))
    with gemm_context(selector=sel) as ctx:
        gemm(x, w, policy=ALL_SK)  # cfg filled from selection
    [e] = ctx.log
    assert e.selection.policy == ALL_SK  # never the selector's own pick
    assert e.selection.source == "forced"


def test_cached_selection_is_same_object():
    sel = default_selector()
    s0 = sel.select(64, 64, 64)
    assert sel.select(64, 64, 64) is s0
    assert sel.stats.cache_hits == 1


def test_plain_and_grouped_keys_independent():
    plain = GemmOp.plain(64, 128, 256)
    grouped = GemmOp(64, 128, 256, g=8, kind="grouped")
    fused = GemmOp.plain(64, 128, 256, epilogue="gelu")
    assert plain.key == (64, 128, 256)
    assert len(grouped.key) == 7 and len(fused.key) == 7
    keys = {encode_key(plain.key), encode_key(grouped.key), encode_key(fused.key)}
    assert len(keys) == 3  # distinct Bloom encodings


def test_plain_op_encodes_as_legacy_mnk():
    from repro.core.bloom import encode_mnk

    assert GemmOp.plain(8, 16, 32).encode() == encode_mnk(8, 16, 32)


def test_grouped_op_tunes_and_selects_independently():
    op = GemmOp(256, 512, 1024, g=8, kind="grouped")
    db = Tuner().tune([op, (256, 512, 1024)])
    assert op.key in db.records and (256, 512, 1024) in db.records
    sel = KernelSelector(sieve=db.build_sieve(), db=db)
    assert sel.select_op(op).source == "tuned"
    assert sel.select(256, 512, 1024).source == "tuned"
    # roundtrip through the JSON codec keeps both key forms
    import os
    import tempfile

    from repro.core.tuner import TuningDatabase

    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "db.json")
        db.save(path)
        db2 = TuningDatabase.load(path)
    assert set(db2.records) == set(db.records)


def test_non_f32_plain_ops_read_mnk_artifacts():
    """A bf16 model GEMM must still benefit from dtype-agnostic (M, N, K)
    tuning artifacts (the paper's DBs carry no dtype), while keying its own
    records separately from f32."""
    size = (256, 512, 128)
    db = Tuner().tune([size])
    sel = KernelSelector(sieve=db.build_sieve(), db=db)
    bf16_op = GemmOp.plain(*size, in_dtype="bfloat16", out_dtype="bfloat16")
    assert bf16_op.key != size and bf16_op.mnk_compatible
    got = sel.select_op(bf16_op)
    assert got.source == "tuned"
    assert got.policy.name == db.records[size].policy
    # end-to-end: dispatching bf16 operands of the tuned shape hits the DB
    x = jnp.ones((256, 128), jnp.bfloat16)
    w = jnp.ones((128, 512), jnp.bfloat16)
    sel2 = KernelSelector(sieve=db.build_sieve(), db=db)
    with gemm_context(selector=sel2) as ctx:
        gemm(x, w)
    assert ctx.log[0].selection.source == "tuned"
    # but an exact dtype-specific record takes precedence when present
    db2 = Tuner().tune([bf16_op, size])
    assert bf16_op.key in db2.records
    sel3 = KernelSelector(db=db2)
    assert sel3.select_op(bf16_op).source == "tuned"
