"""Elastic scaling end-to-end: train sharded on mesh A, checkpoint, resume
sharded on a different mesh B — losses must continue identically (the
mesh-agnostic checkpoint contract at fleet scale)."""

import os
import subprocess
import sys

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "@SRC@")
import dataclasses, json
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_reduced
from repro.checkpoint import CheckpointManager
from repro.data import SyntheticLMData
from repro.dist.sharding import ShardingPlan, materialize_tree, use_plan
from repro.models import build_model
from repro.optim import make_optimizer, constant
from repro.train import init_train_state, make_train_step

mode, ckdir, mesh_spec = sys.argv[1], sys.argv[2], sys.argv[3]
d_sz, m_sz = (int(x) for x in mesh_spec.split("x"))
mesh = jax.make_mesh((d_sz, m_sz), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,) * 2)
plan = ShardingPlan(mesh)

cfg = dataclasses.replace(get_reduced("granite-8b"), dtype="float32")
model = build_model(cfg)
opt = make_optimizer("sgd", constant(1e-2))
data = SyntheticLMData(cfg, batch=8, seq_len=32, seed=5)
step_fn = jax.jit(make_train_step(model, opt))

def shard_state(state):
    param_sh = plan.tree_shardings(model.param_specs())
    put = lambda tree, sh: jax.tree.map(lambda a, s: jax.device_put(a, s), tree, sh)
    state["params"] = put(state["params"], param_sh)
    return state

with use_plan(plan):
    mgr = CheckpointManager(ckdir)
    if mode == "phase1":
        params = materialize_tree(model.param_specs(), jax.random.PRNGKey(0))
        state = shard_state(init_train_state(model, opt, params))
        losses = []
        for step in range(6):
            batch = {k: jnp.asarray(v) for k, v in data.batch_at(step).items()}
            state, m = step_fn(state, batch)
            losses.append(float(m["loss"]))
        mgr.save(6, state, extra={"data": {"seed": 5, "step": 6}})
        print("PHASE1", json.dumps(losses))
    else:
        params = materialize_tree(model.param_specs(), jax.random.PRNGKey(0))
        target = init_train_state(model, opt, params)
        state, at = mgr.restore(target)
        state = shard_state(state)
        losses = []
        for step in range(6, 12):
            batch = {k: jnp.asarray(v) for k, v in data.batch_at(step).items()}
            state, m = step_fn(state, batch)
            losses.append(float(m["loss"]))
        print("PHASE2", json.dumps(losses))
"""


def _run(mode, ckdir, mesh):
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
    r = subprocess.run(
        [sys.executable, "-c", SCRIPT.replace("@SRC@", src), mode, ckdir, mesh],
        capture_output=True,
        text=True,
        timeout=560,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout


def test_rescale_mesh_mid_training(tmp_path):
    import json as j

    ck = str(tmp_path / "ck")
    # train 6 steps on (8 data, 1 model)
    out1 = _run("phase1", ck, "8x1")
    # resume on (2 data, 4 model) — a completely different factorisation
    out2 = _run("phase2", ck, "2x4")
    # and on (4, 2)
    out3 = _run("phase2", ck, "4x2")
    l2 = j.loads(out2.split("PHASE2 ")[1])
    l3 = j.loads(out3.split("PHASE2 ")[1])
    # same data stream + same restored state => identical trajectories
    # regardless of the mesh factorisation (f32, deterministic CPU)
    assert all(abs(a - b) < 1e-4 for a, b in zip(l2, l3)), (l2, l3)
