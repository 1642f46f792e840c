"""Sharding rule solver tests (divisibility demotion, axis dedup, plans)."""

import subprocess
import sys
import os

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.dist.sharding import (
    ArraySpec,
    DEFAULT_RULES,
    ShardingPlan,
    abstract_tree,
    constrain,
    materialize_tree,
    use_plan,
)


@pytest.fixture(scope="module")
def mesh():
    # 1-device mesh with named axes of size 1 — rule plumbing is mesh-size
    # independent; divisibility tests use the subprocess below.
    return jax.make_mesh((1, 1), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,) * 2)


def test_spec_for_basic(mesh):
    plan = ShardingPlan(mesh)
    spec = plan.spec_for(ArraySpec((64, 128), "float32", ("embed", "ffn")))
    # size-1 mesh axes are demoted to replication (div == 1: sharding is a
    # no-op and would only add partition metadata) — positive sharding
    # assertions live in the 8-device subprocess test below
    assert spec == P(None, None)


def test_divisibility_demotion(mesh):
    plan = ShardingPlan(mesh)
    # dim 7 not divisible by ... size-1 axes always divide; force demotion
    # with a fake rule targeting a missing axis
    plan2 = ShardingPlan(mesh, {"embed": "nonexistent_axis"})
    spec = plan2.spec_for(ArraySpec((64, 128), "float32", ("embed", None)))
    assert spec == P(None, None)


def test_axis_dedup_subprocess_covered(mesh):
    # axis dedup on a real mesh is asserted in DIVIS_SCRIPT (s3/s4); here we
    # only check the rules plumbing accepts custom rules
    plan = ShardingPlan(mesh, {"a": "model", "b": "model"})
    spec = plan.spec_for(ArraySpec((8, 8), "float32", ("a", "b")))
    assert spec == P(None, None)  # size-1 mesh -> replicated


def test_tree_shardings_and_abstract(mesh):
    plan = ShardingPlan(mesh)
    tree = {
        "w": ArraySpec((16, 32), "bfloat16", ("embed", "heads")),
        "b": ArraySpec((32,), "float32", (None,)),
    }
    sh = plan.tree_shardings(tree)
    assert sh["w"].spec == P(None, None)  # size-1 mesh -> replicated
    abs_tree = abstract_tree(tree)
    assert abs_tree["w"].shape == (16, 32)
    assert str(abs_tree["w"].dtype) == "bfloat16"
    params = materialize_tree(tree, jax.random.PRNGKey(0))
    assert params["w"].dtype.name == "bfloat16"
    assert params["b"].shape == (32,)


def test_constrain_noop_without_plan():
    import jax.numpy as jnp

    x = jnp.ones((4, 4))
    assert constrain(x, "batch", None) is x


DIVIS_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "{src}")
import jax
from jax.sharding import PartitionSpec as P
from repro.dist.sharding import ArraySpec, ShardingPlan

mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,) * 2)
plan = ShardingPlan(mesh)
# divisible: shard
s1 = plan.spec_for(ArraySpec((6, 8), "float32", ("embed", "heads")))
assert s1 == P("data", "model"), s1
# not divisible by model=4: demote dim 1
s2 = plan.spec_for(ArraySpec((6, 6), "float32", ("embed", "heads")))
assert s2 == P("data", None), s2
# batch spans (pod, data): pod missing from this mesh -> only data used
s3 = plan.spec_for(ArraySpec((4, 3), "float32", ("batch", None)))
assert s3 == P("data", None), s3
# dims smaller than the axis: replicate
s4 = plan.spec_for(ArraySpec((1, 8), "float32", ("batch", "ffn")))
assert s4 == P(None, "model"), s4
assert plan.axis_divisor("heads") == 4
assert plan.axis_divisor("batch") == 2
# axis dedup: two logical axes both ruled to 'model' -> second demoted
plan2 = ShardingPlan(mesh, {{"a": "model", "b": "model"}})
s5 = plan2.spec_for(ArraySpec((8, 8), "float32", ("a", "b")))
assert s5 == P("model", None), s5
print("OK")
"""


def test_divisibility_on_real_multidevice_mesh():
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
    r = subprocess.run(
        [sys.executable, "-c", DIVIS_SCRIPT.format(src=src)],
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert r.returncode == 0, r.stderr
    assert "OK" in r.stdout


def test_materialize_tree_places_each_leaf_on_its_sharding(mesh):
    from jax.sharding import NamedSharding

    from repro.dist.sharding import materialize_tree

    specs = {"w": ArraySpec((8, 16), "bfloat16", ("embed", "heads")),
             "b": ArraySpec((16,), "float32", (None,), init="zeros")}
    sh = NamedSharding(mesh, P())
    tree = materialize_tree(specs, jax.random.PRNGKey(0), {"w": sh, "b": sh})
    assert tree["w"].sharding == sh and tree["w"].dtype == jax.numpy.bfloat16
    assert float(jax.numpy.abs(tree["b"]).max()) == 0.0
    assert 0.1 < float(jax.numpy.std(tree["w"].astype("float32"))) < 0.5  # ~1/sqrt(8)


def test_gemm_axes_name_the_mesh_axes_behind_gemm_div(mesh):
    plan = ShardingPlan(mesh)
    # a 1x1 mesh splits nothing: every divisor is 1, so no axes
    assert plan.gemm_div() == {"batch": 1, "model": 1}
    assert plan.gemm_axes() == {"batch": (), "model": ()}
