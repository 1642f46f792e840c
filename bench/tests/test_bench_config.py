"""What a configuration file brings besides its sizes: the reference module
it names, and the factors it puts on drawn weights."""

import json
import math
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import correct, harness, peaks, run, weights

CHECKOUT = harness.CHECKOUT
FIXTURES = CHECKOUT / "bench" / "tests" / "fixtures"
CONFIG = "bench/configs/granite-8b.json"


def _root(tmp_path, **config):
    """A benchmark root with granite-8b's configuration changed as given (a
    key set to None is left out)."""
    shutil.copy(CHECKOUT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(CHECKOUT / "bench", tmp_path / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    cfg = json.loads((CHECKOUT / CONFIG).read_text())
    cfg.update(config)
    cfg = {k: v for k, v in cfg.items() if v is not None}
    (tmp_path / CONFIG).write_text(json.dumps(cfg))
    return tmp_path


def test_a_rehearsal_runs_through_the_reference_its_configuration_names(tmp_path):
    root = _root(tmp_path, reference="stub")
    shutil.copy(FIXTURES / "stub_reference.py", root / "bench" / "reference" / "stub.py")
    cell = harness.Cell.load(root, "granite-8b.decode-batch", rehearsal=True)
    cell.mix = {**cell.mix, "output": {"dist": "fixed", "value": 6}}
    out = run.run_cell(cell, 2**31 + 7, 2.0, traced=False, backend="xla", pk=peaks.peaks("TPU v5 lite"), control=True)
    stub = cell.reference
    assert stub.__file__ == str(root / "bench" / "reference" / "stub.py")
    assert len(stub.calls) == out["cmp"]["requests"] == out["finished"] > 0
    assert all(c == stub.RefConfig(cell.config["vocab_size"]) and control for _, c, control in stub.calls)
    # the stub's gaps, not the decoder's: a sound run fails the rehearsal's limit
    assert out["cmp"]["widest_gap"] == stub.GAP and out["cmp"]["control_widest_gap"] == 2 * stub.GAP
    assert not correct.correct(out["cmp"], cell.limits)


def test_granite_names_the_decoder():
    cell = harness.Cell.load(CHECKOUT, "granite-8b.prefill-poisson")
    assert cell.reference.__file__ == str(CHECKOUT / "bench" / "reference" / "decoder.py")
    assert cell.reference.RefConfig.from_config(cell.config).layers == cell.config["num_hidden_layers"]


@pytest.mark.parametrize(
    "config, match",
    [
        ({"reference": None}, "names no reference"),
        ({"weight_scale": {"lm_head": 2.0}}, "not explained under assumed"),
    ],
    ids=["no-reference", "unexplained-scale"],
)
def test_a_configuration_that_leaves_something_out_raises(tmp_path, config, match):
    root = _root(tmp_path, **config)
    with pytest.raises(ValueError, match=match):
        harness.Cell.load(root, "granite-8b.decode-batch", rehearsal=True)


def _specs():
    from repro.dist.sharding import ArraySpec
    from repro.models import build_model

    cell = harness.Cell.load(CHECKOUT, "granite-8b.decode-batch", rehearsal=True)
    return build_model(harness.model_config(cell)).param_specs(), (lambda x: isinstance(x, ArraySpec))


@pytest.mark.parametrize("path", ["layers.attn.nowhere", "layers.attn", "final_norm.scale"])
def test_a_weight_scale_that_names_no_drawn_leaf_raises(path):
    specs, is_spec = _specs()
    with pytest.raises(ValueError, match="names no drawn leaf"):
        weights.make(specs, 1, is_spec, {path: 2.0})


def _unscaled(specs, seed, is_spec):
    """The draw as it was before configurations could scale it: one normal
    draw per leaf over the square root of its fan-in, in one jitted call."""
    leaves = jax.tree.leaves(specs, is_leaf=is_spec)

    def build(key):
        out = []
        for i, s in enumerate(leaves):
            shape = tuple(s.shape)
            if s.init in ("ones", "zeros"):
                out.append((jnp.ones if s.init == "ones" else jnp.zeros)(shape, s.dtype))
            else:
                draw = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
                out.append((draw / math.sqrt(shape[-2] if len(shape) >= 2 else shape[-1])).astype(s.dtype))
        return out

    return jax.jit(build)(weights.leaf_key(seed))


@pytest.mark.parametrize("scale", [None, {}], ids=["no-scale", "empty-scale"])
def test_an_empty_weight_scale_leaves_every_leaf_bit_for_bit(scale):
    specs, is_spec = _specs()
    seed = 2**33 + 17
    got = jax.tree.leaves(weights.make(specs, seed, is_spec, scale))
    want = _unscaled(specs, seed, is_spec)
    assert len(got) == len(want)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_a_scaled_leaf_is_its_draw_times_the_factor():
    specs, is_spec = _specs()
    plain = weights.make(specs, 11, is_spec)
    scaled = weights.make(specs, 11, is_spec, {"layers.attn.wq": 8.0, "lm_head": 0.5})
    np.testing.assert_array_equal(np.asarray(scaled["layers"]["attn"]["wq"]), 8.0 * np.asarray(plain["layers"]["attn"]["wq"]))
    np.testing.assert_array_equal(np.asarray(scaled["lm_head"]), 0.5 * np.asarray(plain["lm_head"]))
    rest = lambda p: {k: v for k, v in p.items() if k != "lm_head"}  # noqa: E731
    for k in ("wk", "wv", "wo"):
        np.testing.assert_array_equal(np.asarray(scaled["layers"]["attn"][k]), np.asarray(plain["layers"]["attn"][k]))
    for a, b in zip(jax.tree.leaves(rest(scaled)["layers"]["mlp"]), jax.tree.leaves(rest(plain)["layers"]["mlp"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
