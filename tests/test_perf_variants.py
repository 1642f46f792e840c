"""Perf-variant implementations must be numerically equivalent to their
baselines (the §Perf contract: scheduling/sharding changes, never semantics).
"""

import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_batch, tiny
from repro.dist.sharding import materialize_tree
from repro.models import build_model


def test_moe_hinted_equals_global():
    cfg = tiny("olmoe-1b-7b")
    model = build_model(cfg)
    params = materialize_tree(model.param_specs(), jax.random.PRNGKey(0))
    batch = make_batch(cfg, 4, 32)
    l1, _ = model.loss_fn(params, batch)
    m2 = build_model(dataclasses.replace(cfg, moe_impl="hinted"))
    l2, _ = m2.loss_fn(params, batch)
    assert abs(float(l1) - float(l2)) < 1e-4


def test_mha_expand_equals_gqa():
    cfg = tiny("llava-next-34b")
    model = build_model(cfg)
    params = materialize_tree(model.param_specs(), jax.random.PRNGKey(0))
    batch = make_batch(cfg, 2, 16)
    f1, _ = model.forward(params, batch["tokens"], patch_embeds=batch["patch_embeds"])
    m2 = build_model(dataclasses.replace(cfg, attn_impl="mha_expand"))
    f2, _ = m2.forward(params, batch["tokens"], patch_embeds=batch["patch_embeds"])
    np.testing.assert_allclose(np.asarray(f1), np.asarray(f2), rtol=1e-4, atol=1e-4)


def test_attn_remat_bitwise_grads():
    cfg = tiny("granite-8b")
    model = build_model(cfg)
    params = materialize_tree(model.param_specs(), jax.random.PRNGKey(0))
    batch = make_batch(cfg, 2, 32)
    g1 = jax.grad(lambda p: model.loss_fn(p, batch)[0])(params)
    m2 = build_model(dataclasses.replace(cfg, attn_remat=True))
    g2 = jax.grad(lambda p: m2.loss_fn(p, batch)[0])(params)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_attn_chunk_invariance():
    cfg = tiny("granite-8b")
    model = build_model(cfg)
    params = materialize_tree(model.param_specs(), jax.random.PRNGKey(0))
    batch = make_batch(cfg, 2, 64)
    f1, _ = model.forward(params, batch["tokens"])
    m2 = build_model(dataclasses.replace(cfg, attn_chunk=16))
    f2, _ = m2.forward(params, batch["tokens"])
    np.testing.assert_allclose(np.asarray(f1), np.asarray(f2), rtol=2e-4, atol=2e-4)


SHARD_MAP_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "@SRC@")
import dataclasses, jax, jax.numpy as jnp, numpy as np
from repro.configs import get_reduced
from repro.models import build_model
from repro.dist.sharding import ShardingPlan, materialize_tree, use_plan
from repro.models.layers import moe_apply

cfg = dataclasses.replace(get_reduced("olmoe-1b-7b"), dtype="float32")
model = build_model(cfg)
params = materialize_tree(model.param_specs(), jax.random.PRNGKey(0))
p0 = jax.tree.map(lambda a: a[0], params["layers"])["moe"]
r = np.random.default_rng(0)
x = jnp.asarray(r.normal(size=(8, 16, cfg.d_model)) * 0.3, jnp.float32)
ref, _ = moe_apply(p0, x, cfg, div={})
mesh = jax.make_mesh((4, 2), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,) * 2)
cfg2 = dataclasses.replace(cfg, moe_impl="shard_map")
with use_plan(ShardingPlan(mesh)):
    got, _ = jax.jit(lambda p, x: moe_apply(p, x, cfg2, div={"batch": 4, "model": 2}))(p0, x)
    g1 = jax.grad(lambda p: jnp.sum(moe_apply(p, x, cfg, div={})[0] ** 2))(p0)
    g2 = jax.jit(jax.grad(lambda p: jnp.sum(moe_apply(p, x, cfg2, div={"batch": 4, "model": 2})[0] ** 2)))(p0)
err = float(jnp.max(jnp.abs(got - ref)))
gerr = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)))
assert err < 2e-3, err
assert gerr < 1e-3, gerr
print("OK", err, gerr)
"""


def test_shard_map_moe_on_8dev_mesh():
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
    r = subprocess.run(
        [sys.executable, "-c", SHARD_MAP_SCRIPT.replace("@SRC@", src)],
        capture_output=True,
        text=True,
        timeout=560,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


def test_int8_kv_cache_decode_close_to_fp():
    """Quantized KV cache (decode memory-term optimization): decode logits
    within 5% relative of the fp cache path."""
    cfg = tiny("granite-8b")
    model = build_model(cfg)
    params = materialize_tree(model.param_specs(), jax.random.PRNGKey(0))
    r = np.random.default_rng(0)
    b, s = 2, 16
    toks = jnp.asarray(r.integers(0, cfg.vocab_size, (b, s)))
    full, _ = model.forward(params, toks)
    m8 = build_model(dataclasses.replace(cfg, kv_cache_dtype="int8"))
    _, cache = m8.prefill(params, toks[:, : s - 1], max_seq=s)
    ld, _ = m8.decode_step(params, cache, toks[:, s - 1 : s], jnp.full((b,), s - 1))
    rel = float(jnp.max(jnp.abs(ld[:, 0] - full[:, -1]))) / float(
        jnp.max(jnp.abs(full[:, -1]))
    )
    assert rel < 0.05, rel


def test_windowed_cache_decode_exact():
    """gemma3-style windowed ring caches: decode chain from an empty cache
    must reproduce the teacher-forced forward exactly (window masking ==
    ring buffer semantics)."""
    from repro.dist.sharding import ArraySpec

    cfg = tiny("gemma3-27b")
    model = build_model(cfg)
    params = materialize_tree(model.param_specs(), jax.random.PRNGKey(0))
    mw = build_model(dataclasses.replace(cfg, window_cache=True))
    b, s = 2, 16
    r = np.random.default_rng(0)
    toks = jnp.asarray(r.integers(0, cfg.vocab_size, (b, s)))
    full, _ = model.forward(params, toks)
    cache = jax.tree.map(
        lambda sp: jnp.zeros(sp.shape, sp.dtype),
        mw.cache_specs(b, s),
        is_leaf=lambda x: isinstance(x, ArraySpec),
    )
    for t in range(s):
        logits, cache = mw.decode_step(
            params, cache, toks[:, t : t + 1], jnp.full((b,), t)
        )
        np.testing.assert_allclose(
            np.asarray(logits[:, 0]), np.asarray(full[:, t]), rtol=2e-3, atol=2e-3
        )


def test_windowed_cache_prefill_handoff():
    """Uniform prefill -> windowed_cache_from_uniform -> windowed decode
    must equal teacher-forced logits (the production serving handoff)."""
    cfg = tiny("gemma3-27b")
    model = build_model(cfg)
    params = materialize_tree(model.param_specs(), jax.random.PRNGKey(0))
    mw = build_model(dataclasses.replace(cfg, window_cache=True))
    b, s = 2, 16
    r = np.random.default_rng(0)
    toks = jnp.asarray(r.integers(0, cfg.vocab_size, (b, s)))
    full, _ = model.forward(params, toks)
    p0 = s - 4
    _, ucache = model.prefill(params, toks[:, :p0], max_seq=s)
    wcache = mw.windowed_cache_from_uniform(ucache, p0)
    for t in range(p0, s):
        logits, wcache = mw.decode_step(
            params, wcache, toks[:, t : t + 1], jnp.full((b,), t)
        )
        np.testing.assert_allclose(
            np.asarray(logits[:, 0]), np.asarray(full[:, t]), rtol=2e-3, atol=2e-3
        )
