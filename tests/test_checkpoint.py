"""Checkpoint manager: atomic commits, retention, async writer, elastic
restore (different mesh via subprocess with 8 host devices)."""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager


def _state(seed=0):
    r = np.random.default_rng(seed)
    return {
        "params": {"w": jnp.asarray(r.normal(size=(16, 8)), jnp.float32)},
        "opt": {"mu": jnp.zeros((16, 8)), "count": jnp.asarray(3, jnp.int32)},
        "step": jnp.asarray(7, jnp.int32),
    }


def test_save_restore_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    state = _state()
    mgr.save(7, state, extra={"data": {"seed": 1, "step": 7}})
    restored, step = mgr.restore(state)
    assert step == 7
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert mgr.read_extra()["data"]["step"] == 7


def test_latest_pointer_and_retention(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    for step in (1, 2, 3, 4):
        mgr.save(step, _state(step))
    assert mgr.latest_step() == 4
    assert mgr.all_steps() == [3, 4]  # retention pruned 1, 2


def test_async_writer(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=3)
    for step in (5, 10):
        mgr.save(step, _state(step), blocking=False)
    mgr.wait()
    assert mgr.latest_step() == 10


def test_no_partial_checkpoint_visible(tmp_path):
    """A .tmp dir must never be restorable (atomic rename contract)."""
    mgr = CheckpointManager(str(tmp_path), keep=2)
    os.makedirs(os.path.join(tmp_path, "step_0000000099.tmp"))
    assert mgr.latest_step() is None
    assert 99 not in mgr.all_steps()


def test_restore_missing_raises(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    with pytest.raises(FileNotFoundError):
        mgr.restore(_state())


def test_dtype_cast_on_restore(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    state = {"w": jnp.ones((4, 4), jnp.float32)}
    mgr.save(1, state)
    target = {"w": jnp.zeros((4, 4), jnp.bfloat16)}
    restored, _ = mgr.restore(target)
    assert restored["w"].dtype == jnp.bfloat16


ELASTIC_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
sys.path.insert(0, "{src}")
from repro.checkpoint import CheckpointManager

mode, ckdir = sys.argv[1], sys.argv[2]
if mode == "save":
    mesh = jax.make_mesh((8,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
    w = jax.device_put(np.arange(64, dtype=np.float32).reshape(8, 8),
                       NamedSharding(mesh, P("data", None)))
    CheckpointManager(ckdir).save(1, {{"w": w}})
    print("SAVED")
else:
    # restore onto a DIFFERENT mesh: 2x4 with model sharding on dim 1
    mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,) * 2)
    target = {{"w": jax.ShapeDtypeStruct((8, 8), jnp.float32)}}
    sh = {{"w": NamedSharding(mesh, P("data", "model"))}}
    restored, step = CheckpointManager(ckdir).restore(target, shardings=sh)
    got = np.asarray(restored["w"])
    assert np.array_equal(got, np.arange(64, dtype=np.float32).reshape(8, 8))
    assert restored["w"].sharding.spec == P("data", "model")
    print("RESTORED", step)
"""


def test_elastic_restore_different_mesh(tmp_path):
    """Save sharded on (8,) data mesh, restore onto (2,4) data x model."""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    script = ELASTIC_SCRIPT.format(src=os.path.abspath(src))
    ckdir = str(tmp_path / "ck")
    for mode, want in (("save", "SAVED"), ("restore", "RESTORED 1")):
        r = subprocess.run(
            [sys.executable, "-c", script, mode, ckdir],
            capture_output=True,
            text=True,
            timeout=120,
        )
        assert r.returncode == 0, r.stderr
        assert want in r.stdout
