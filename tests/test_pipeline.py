"""Pipeline parallelism: the GPipe schedule must equal sequential layer
application (4-stage pipeline on an 8-device subprocess mesh) and be
differentiable."""

import os
import subprocess
import sys

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "@SRC@")
import jax, jax.numpy as jnp, numpy as np
from repro.dist.pipeline import pipeline_apply, split_stages

L, D, M, MB = 8, 16, 6, 4  # layers, width, microbatches, microbatch size
r = np.random.default_rng(0)
params = {"w": jnp.asarray(r.normal(size=(L, D, D)) * 0.3, jnp.float32),
          "b": jnp.asarray(r.normal(size=(L, D)) * 0.1, jnp.float32)}
x = jnp.asarray(r.normal(size=(M, MB, D)), jnp.float32)

def layer(p, h):
    return jnp.tanh(h @ p["w"] + p["b"])

def stage_fn(stage_params, h):
    def body(h, p):
        return layer(p, h), None
    h, _ = jax.lax.scan(body, h, stage_params)
    return h

# sequential reference
def seq_apply(params, x):
    def body(h, p):
        return layer(p, h), None
    h, _ = jax.lax.scan(body, x, params)
    return h

ref = jax.vmap(lambda xb: seq_apply(params, xb))(x.reshape(M * MB // MB, MB, D).reshape(M, MB, D))
ref = jnp.stack([seq_apply(params, x[m]) for m in range(M)])

mesh = jax.make_mesh((4, 2), ("pod", "data"), axis_types=(jax.sharding.AxisType.Auto,) * 2)
staged = split_stages(params, 4)
got = jax.jit(lambda sp, x: pipeline_apply(stage_fn, sp, x, mesh=mesh, axis="pod"))(staged, x)
err = float(jnp.max(jnp.abs(got - ref)))
assert err < 1e-5, err

# differentiability: grads vs sequential
def loss_pipe(sp, x):
    return jnp.sum(pipeline_apply(stage_fn, sp, x, mesh=mesh, axis="pod") ** 2)

def loss_seq(p, x):
    return sum(jnp.sum(seq_apply(p, x[m]) ** 2) for m in range(M))

g_pipe = jax.jit(jax.grad(loss_pipe))(staged, x)
g_seq = jax.grad(loss_seq)(params, x)
g_pipe_flat = jax.tree.map(lambda a: a.reshape(-1, *a.shape[2:]), g_pipe)
gerr = max(float(jnp.max(jnp.abs(a - b))) for a, b in
           zip(jax.tree.leaves(g_pipe_flat), jax.tree.leaves(g_seq)))
assert gerr < 1e-4, gerr
print("PIPELINE OK", err, gerr)
"""


def test_gpipe_matches_sequential_and_differentiates():
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
    r = subprocess.run(
        [sys.executable, "-c", SCRIPT.replace("@SRC@", src)],
        capture_output=True,
        text=True,
        timeout=560,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    assert "PIPELINE OK" in r.stdout
