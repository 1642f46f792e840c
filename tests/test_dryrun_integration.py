"""Integration: the dry-run path end-to-end on an 8-device host mesh with a
reduced architecture (fast analogue of the 512-device production dry-run,
exercised in CI per commit; the production sweep writes artifacts/)."""

import json
import os
import subprocess
import sys

import pytest

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "@SRC@")
import dataclasses, json
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_reduced
from repro.dist.sharding import ArraySpec, ShardingPlan, abstract_tree, use_plan
from repro.dist.hlo_cost import analyze
from repro.models import build_model
from repro.optim import AdamW, constant
from repro.train import make_train_step

arch, kind = sys.argv[1], sys.argv[2]
cfg = get_reduced(arch)
mesh = jax.make_mesh((4, 2), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,) * 2)
plan = ShardingPlan(mesh, {"seq": "model"} if kind == "train" else {})
model = build_model(cfg)
specs = model.param_specs()
params_abs = abstract_tree(specs)
param_sh = plan.tree_shardings(specs)
repl = NamedSharding(mesh, P())
b, s = 8, 32

with use_plan(plan):
    if kind == "train":
        ins = {
            "tokens": jax.ShapeDtypeStruct((b, s), jnp.int32),
            "labels": jax.ShapeDtypeStruct((b, s), jnp.int32),
        }
        if cfg.family == "vlm":
            ins["patch_embeds"] = jax.ShapeDtypeStruct((b, cfg.n_patches, cfg.d_model), jnp.float32)
        if cfg.family == "encdec":
            ins["frames"] = jax.ShapeDtypeStruct((b, cfg.enc_frames, cfg.d_model), jnp.float32)
        in_sh = {k: NamedSharding(mesh, P("data") if v.ndim == 2 else P("data", None, None))
                 for k, v in ins.items()}
        opt = AdamW(schedule=constant(1e-4))
        step = make_train_step(model, opt, div={"batch": 4, "model": 2})
        state_abs = {"params": params_abs, "opt": jax.eval_shape(opt.init, params_abs),
                     "step": jax.ShapeDtypeStruct((), jnp.int32)}
        state_sh = {"params": param_sh,
                    "opt": {"mu": param_sh, "nu": param_sh, "master": param_sh, "count": repl},
                    "step": repl}
        out_struct = jax.eval_shape(step, state_abs, ins)
        out_sh = (state_sh, jax.tree.map(lambda _: repl, out_struct[1]))
        lowered = jax.jit(step, in_shardings=(state_sh, in_sh), out_shardings=out_sh).lower(state_abs, ins)
    else:
        cache_specs = model.cache_specs(b, s)
        cache_abs = abstract_tree(cache_specs)
        cache_sh = plan.tree_shardings(cache_specs)
        toks = jax.ShapeDtypeStruct((b, 1), jnp.int32)
        pos = jax.ShapeDtypeStruct((b,), jnp.int32)
        def decode_fn(p, c, t, cp):
            return model.decode_step(p, c, t, cp, div={"batch": 4, "model": 2})
        lowered = jax.jit(
            decode_fn,
            in_shardings=(param_sh, cache_sh, NamedSharding(mesh, P("data", None)), NamedSharding(mesh, P("data"))),
        ).lower(params_abs, cache_abs, toks, pos)

    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    cost = analyze(compiled.as_text())
    print(json.dumps({
        "temp": int(mem.temp_size_in_bytes),
        "flops": cost.flops,
        "coll_bytes": cost.coll_bytes,
    }))
"""


def _run(arch, kind):
    r = subprocess.run(
        [sys.executable, "-c", SCRIPT.replace("@SRC@", SRC), arch, kind],
        capture_output=True,
        text=True,
        timeout=560,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("arch", ["granite-8b", "olmoe-1b-7b", "zamba2-1.2b"])
def test_reduced_train_lowers_on_8dev_mesh(arch):
    out = _run(arch, "train")
    assert out["flops"] > 0
    assert out["coll_bytes"] > 0  # sharded training must communicate


@pytest.mark.parametrize("arch", ["granite-8b", "mamba2-1.3b"])
def test_reduced_decode_lowers_on_8dev_mesh(arch):
    out = _run(arch, "decode")
    assert out["flops"] > 0
