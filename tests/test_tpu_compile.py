"""Ahead-of-time compiles for a described TPU v5e (no chip attached).

The TPU compiler refuses here what interpret mode accepts: misaligned
blocks, too much VMEM, a kernel that cannot be partitioned. Each case
compiles the main path at real widths: every kernel family at granite-8b
decode and prefill shapes on each dtype rung, the fused grouped kernel at
olmoe-1b-7b expert widths, a granite-8b decode step on one chip, and the
same step tensor-parallel on a (data=1, model=4) mesh.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library.
"""

import dataclasses
import importlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.op import GemmOp
from repro.core.policies import ALL_SK, HYBRIDS, TileConfig
from repro.core.selector import KernelSelector
from repro.dist.sharding import ShardingPlan, abstract_tree, use_plan
from repro.kernels.dp import ops as dp_ops
from repro.kernels.splitk import ops as splitk_ops
from repro.kernels.streamk import ops as sk_ops
from repro.kernels.streamk.grouped import gemm_grouped_streamk
from repro.models import build_model
from repro.serve import serve_gemm_div

gemm_mod = importlib.import_module("repro.core.gemm")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the cache but cannot be
    # read back without one: keep the cache off around these compiles
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    """Dispatch as the chip would: ``jax.default_backend()`` still says
    cpu here, so steer the platform the gemm dispatcher sees."""
    monkeypatch.setattr(gemm_mod, "_platform", lambda: "tpu")


GRANITE_SHAPES = {
    # (M, N, K) of mlp.in at decode (8 slots) and one 512-token prefill chunk
    "decode": ((8, 14336, 4096), TileConfig(8, 256, 1024)),
    "prefill": ((512, 4096, 14336), TileConfig(512, 512, 256)),
}
FAMILIES = {
    "dp": lambda **kw: dp_ops.gemm.lower(**kw, g=8),
    "splitk": lambda **kw: splitk_ops.gemm.lower(**kw, s=2, g=8),
    "all_sk": lambda **kw: sk_ops.gemm.lower(**kw, policy=ALL_SK, g=8),
    "sk2dp": lambda **kw: sk_ops.gemm.lower(**kw, policy=HYBRIDS[1], g=8),
}
RUNGS = ("bfloat16", "bfloat16*int8", "bfloat16*int4", "int8*int8")


def _operands(sharding, rung, m, n, k, g=None):
    """Abstract (a, b, kwargs) of one dtype rung; ``g`` stacks groups."""
    lead = () if g is None else (g,)
    s = lambda shape, dt: jax.ShapeDtypeStruct(lead + shape, dt, sharding=sharding)  # noqa: E731
    a_dt = jnp.int8 if rung == "int8*int8" else jnp.bfloat16
    b_dt = jnp.bfloat16 if rung == "bfloat16" else jnp.int8
    kw = {}
    if rung != "bfloat16":
        kw["scale"] = s((n,), jnp.float32)
    if rung == "int8*int8":
        kw["scale_a"] = s((m,), jnp.float32)
    if rung.endswith("int4"):
        kw["b_bits"] = 4
    kb = k // 2 if rung.endswith("int4") else k
    return s((m, k), a_dt), s((kb, n), b_dt), kw


@pytest.mark.parametrize("rung", RUNGS)
@pytest.mark.parametrize("family", sorted(FAMILIES))
@pytest.mark.parametrize("shape", sorted(GRANITE_SHAPES))
def test_kernel_family_compiles_for_v5e(one_chip, shape, family, rung):
    (m, n, k), cfg = GRANITE_SHAPES[shape]
    a, b, kw = _operands(one_chip, rung, m, n, k)
    compiled = FAMILIES[family](a=a, b=b, cfg=cfg, **kw).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("rung", ("bfloat16", "bfloat16*int8"))
def test_fused_grouped_kernel_compiles_at_olmoe_widths(one_chip, rung):
    g, m, k, n = 64, 80, 2048, 1024  # 64 experts, capacity 80, d_model 2048, d_ff 1024
    op = GemmOp(
        m, n, k, g=g, kind="grouped", in_dtype=rung, out_dtype="bfloat16", fused=True
    )
    sel = KernelSelector().select_op(op)
    a, b, kw = _operands(one_chip, rung, m, n, k, g=g)
    compiled = gemm_grouped_streamk.lower(
        a, b, policy=sel.policy, cfg=sel.cfg, g=sel.g, **kw
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _granite(n_layers=2):
    return build_model(dataclasses.replace(get_config("granite-8b"), n_layers=n_layers))


def _decode_args(model, shardings, batch=8, max_seq=256):
    """Abstract (params, cache, tokens, pos) of a decode step, each leaf on
    ``shardings(spec_tree)``'s sharding."""

    def place(specs):
        return jax.tree.map(
            lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
            abstract_tree(specs),
            shardings(specs),
        )

    tok_sh, pos_sh = shardings(None)
    return (
        place(model.param_specs()),
        place(model.cache_specs(batch, max_seq)),
        jax.ShapeDtypeStruct((batch, 1), jnp.int32, sharding=tok_sh),
        jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=pos_sh),
    )


def test_granite_decode_step_compiles_under_pallas(one_chip, on_tpu):
    model = _granite()

    def shardings(specs):
        if specs is None:
            return one_chip, one_chip
        return jax.tree.map(lambda _: one_chip, abstract_tree(specs))

    args = _decode_args(model, shardings)
    with gemm_mod.gemm_context(backend="pallas") as ctx:
        compiled = jax.jit(model.decode_step).lower(*args).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 8  # 7 per layer + lm_head
    assert {e.tag for e in ctx.log} >= {"attn.q", "attn.o", "mlp.in", "mlp.out", "lm_head"}


def test_tagged_decode_step_compiles_with_a_kernel_per_tag(one_chip, on_tpu):
    """Under ``tagged_kernels`` (the paged engine's jitted steps, with
    ``tag_kernels``) every Mosaic kernel of the compiled step is named by
    its GEMM's tag and tile."""
    model = _granite()

    def shardings(specs):
        if specs is None:
            return one_chip, one_chip
        return jax.tree.map(lambda _: one_chip, abstract_tree(specs))

    args = _decode_args(model, shardings)
    with gemm_mod.gemm_context(backend="pallas") as ctx, gemm_mod.tagged_kernels():
        text = jax.jit(model.decode_step).lower(*args).compile().as_text()
    for e in ctx.log:
        assert f"{e.tag.replace('.', '_')}__" in text and e.selection.cfg.name in text, e.tag


def test_tensor_parallel_decode_step_runs_each_kernel_on_its_shard(
    topo, on_tpu, monkeypatch
):
    """On a (data=1, model=4) mesh every kernel is handed exactly the local
    problem its fingerprint names; without the per-shard dispatch the
    compile fails ('Mosaic kernels cannot be automatically partitioned')."""
    mesh = Mesh(
        np.array(topo.devices).reshape(1, 4),
        ("data", "model"),
        axis_types=(AxisType.Auto,) * 2,
    )
    plan = ShardingPlan(mesh)
    model = _granite()
    seen = []
    pallas = gemm_mod.get_backend("pallas")

    def probe(x, w, *, op, **kw):
        seen.append((x.shape, w.shape, op))
        return pallas(x, w, op=op, **kw)

    monkeypatch.setitem(gemm_mod._BACKENDS, "pallas", probe)

    def shardings(specs):
        if specs is None:
            return NamedSharding(mesh, P()), NamedSharding(mesh, P())
        return plan.tree_shardings(specs)

    args = _decode_args(model, shardings)
    with use_plan(plan), gemm_mod.gemm_context(backend="pallas"):
        div = serve_gemm_div(model, batch=8)
        assert div == {"batch": 1, "model": 4}
        step = jax.jit(lambda p, c, t, pos: model.decode_step(p, c, t, pos, div=div))
        compiled = step.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert seen
    for x_shape, w_shape, op in seen:
        g, m, k = x_shape
        assert (g, m, w_shape[2], k) == (op.g_local, *op.local)
    # attn.q: 32 heads x 128 split 4 ways, d_model whole
    assert ((1, 8, 4096), (1, 4096, 1024)) in {(x, w) for x, w, _ in seen}
