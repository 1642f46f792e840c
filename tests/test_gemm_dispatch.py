"""GEMM dispatch API: correctness, selection logging, backend routing."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.gemm import gemm, gemm_context, platform_backend
from repro.core.policies import ALL_SK, DP, TileConfig
from repro.core.selector import KernelSelector, default_selector
from repro.core.tuner import Tuner


@pytest.mark.parametrize(
    "backend, seed, x_shape, w_shape, kw, atol, rtol",
    [
        # M=21, N=16: not a multiple of any tile
        ("xla", 0, (3, 7, 32), (32, 16), {}, 0.0, 1e-6),
        (
            "pallas_interpret",
            1,
            (16, 64),
            (64, 128),
            dict(policy=ALL_SK, cfg=TileConfig(8, 128, 128)),
            1e-4,
            1e-4,
        ),
    ],
    ids=["xla", "pallas_interpret"],
)
def test_gemm_matches_dot(backend, seed, x_shape, w_shape, kw, atol, rtol):
    r = np.random.default_rng(seed)
    x = jnp.asarray(r.normal(size=x_shape), jnp.float32)
    w = jnp.asarray(r.normal(size=w_shape), jnp.float32)
    with gemm_context(selector=default_selector(), backend=backend):
        got = gemm(x, w, **kw)
    want = jnp.dot(x, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=rtol, atol=atol)


def test_default_backend_follows_the_platform():
    # this process runs on the CPU: XLA's dot, never interpret mode
    assert platform_backend() == "xla"
    with gemm_context() as ctx:
        assert ctx.backend == "xla"


def test_pallas_backend_refuses_a_non_tpu_platform():
    with pytest.raises(ValueError, match="pallas_interpret"):
        with gemm_context(backend="pallas"):
            pass


@pytest.mark.parametrize(
    "divisors, local", [((4, 2, 1), (8, 32, 32)), ((2, 1, 4), (16, 64, 8))]
)
def test_dispatch_logs_local_shape(divisors, local):
    # without an installed plan the divisors only key selection: no shard_map
    x = jnp.ones((4, 8, 32), jnp.float32)
    w = jnp.ones((32, 64), jnp.float32)
    with gemm_context(selector=default_selector()) as ctx:
        gemm(x, w, divisors=divisors, tag="t")
    [e] = ctx.log
    assert e.global_mnk == (32, 64, 32)
    assert e.local_mnk == e.op.local == local
    assert e.op.key == local  # plain op -> legacy key
    assert e.tag == "t"


def test_forced_policy_bypasses_selector():
    x = jnp.ones((2, 32), jnp.float32)
    w = jnp.ones((32, 8), jnp.float32)
    with gemm_context(selector=default_selector()) as ctx:
        gemm(x, w, policy=ALL_SK, cfg=TileConfig(8, 128, 128))
    assert ctx.log[0].selection.source == "forced"
    assert ctx.log[0].selection.policy == ALL_SK


def test_pallas_backend_uses_tuned_selection():
    sizes = [(16, 128, 64)]
    db = Tuner().tune(sizes)
    sel = KernelSelector(sieve=db.build_sieve(), db=db)
    r = np.random.default_rng(2)
    x = jnp.asarray(r.normal(size=(16, 64)), jnp.float32)
    w = jnp.asarray(r.normal(size=(64, 128)), jnp.float32)
    with gemm_context(selector=sel, backend="xla") as ctx:
        got = gemm(x, w)
    assert ctx.log[0].selection.source == "tuned"
    np.testing.assert_allclose(np.asarray(got), np.asarray(jnp.dot(x, w)), rtol=1e-5)


def test_contraction_mismatch_raises():
    with pytest.raises(ValueError):
        gemm(jnp.ones((4, 8)), jnp.ones((9, 2)))


def test_gemm_under_jit_traces_once():
    sel = default_selector()

    @jax.jit
    def f(x, w):
        with gemm_context(selector=sel):
            return gemm(x, w)

    x = jnp.ones((4, 32))
    w = jnp.ones((32, 8))
    f(x, w)
    lookups = sel.stats.lookups
    f(x * 2, w)  # cached trace: no new selection
    assert sel.stats.lookups == lookups


SHARDED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
sys.path.insert(0, "@SRC@")
import importlib
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType
from repro.dist.sharding import ShardingPlan, use_plan
gm = importlib.import_module("repro.core.gemm")

backend = sys.argv[1]
mesh = jax.make_mesh((1, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
plan = ShardingPlan(mesh)
seen = []
inner = gm.get_backend(backend)
def probe(x, w, *, op, **kw):
    seen.append((x.shape, w.shape, op))
    return inner(x, w, op=op, **kw)
gm.register_backend("probe", probe)

r = np.random.default_rng(0)
x = jnp.asarray(r.normal(size=(2, 4, 256)), jnp.float32)
w1 = jnp.asarray(r.normal(size=(256, 512)) / 16, jnp.float32)
w2 = jnp.asarray(r.normal(size=(512, 256)) / 16, jnp.float32)
xg = jnp.asarray(r.normal(size=(8, 16, 256)), jnp.float32)
wg = jnp.asarray(r.normal(size=(8, 256, 128)) / 16, jnp.float32)

def mlp(x, w1, w2):
    h = gm.gemm(x, w1, divisors=(1, 4, 1), tag="col", epilogue="gelu")  # N over model
    return gm.gemm(h, w2, divisors=(1, 1, 4), tag="row")  # K over model, psum

with gm.gemm_context(backend="probe"), use_plan(plan):
    got = jax.jit(mlp)(x, w1, w2)
    got_g = jax.jit(lambda a, b: gm.gemm_grouped(a, b, g_divisor=4))(xg, wg)
    if backend == "xla":
        loss = lambda *w: jnp.sum(jnp.sin(mlp(x, *w)))
        grads = jax.jit(jax.grad(loss, argnums=(0, 1)))(w1, w2)
want = jax.nn.gelu(x @ w1) @ w2
np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
np.testing.assert_allclose(got_g, jnp.einsum("gmk,gkn->gmn", xg, wg), rtol=1e-4, atol=1e-4)
if backend == "xla":
    ref = lambda *w: jnp.sum(jnp.sin(jax.nn.gelu(x @ w[0]) @ w[1]))
    for g, gr in zip(grads, jax.grad(ref, argnums=(0, 1))(w1, w2)):
        np.testing.assert_allclose(g, gr, rtol=1e-4, atol=1e-4)
# every backend call saw exactly the shard its fingerprint names
for xs, ws, op in seen:
    assert (xs[0], xs[1], ws[2], xs[2]) == (op.g_local, *op.local), (xs, ws, op)
assert [s[:2] for s in seen[:3]] == [
    ((1, 8, 256), (1, 256, 128)),
    ((1, 8, 128), (1, 128, 256)),
    ((2, 16, 256), (2, 256, 128)),
], seen
print("OK")
"""


@pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
def test_sharded_dispatch_runs_each_shard_on_its_local_problem(backend):
    """Under a (data=1, model=4) plan, column-parallel (N), row-parallel (K,
    then psum) and expert-parallel (G) GEMMs run per shard and equal the
    unsharded product; gradients flow through the psum."""
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
    r = subprocess.run(
        [sys.executable, "-c", SHARDED_SCRIPT.replace("@SRC@", src), backend],
        capture_output=True,
        text=True,
        timeout=560,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout
