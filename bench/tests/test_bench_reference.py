"""The float32 reference agrees with the program's model at tiny widths:
``models/lm.py``'s prefill followed by decode, in float32, against the
reference's one forward pass over the same tokens."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import weights
from bench.reference import decoder


def _ref_config(cfg):
    return decoder.RefConfig.from_config(
        {
            "num_hidden_layers": cfg.n_layers, "hidden_size": cfg.d_model,
            "num_attention_heads": cfg.n_heads, "num_key_value_heads": cfg.n_kv_heads,
            "head_dim": cfg.d_head, "intermediate_size": cfg.d_ff, "vocab_size": cfg.vocab_size,
            "rope_theta": cfg.rope_theta, "rms_norm_eps": 1e-6,
            "num_experts": cfg.n_experts, "num_experts_per_tok": cfg.top_k, "norm_topk_prob": True,
        }
    )


@pytest.mark.parametrize("arch", ["granite-8b", "olmoe-1b-7b"])
def test_reference_matches_prefill_then_decode(arch):
    from repro.configs import get_reduced
    from repro.core.gemm import gemm_context
    from repro.dist.sharding import ArraySpec
    from repro.models import build_model

    cfg = dataclasses.replace(get_reduced(arch), dtype="float32")
    if cfg.n_experts:
        # dropless capacity, as the benchmark serves the MoE
        cfg = dataclasses.replace(cfg, capacity_factor=cfg.n_experts / cfg.top_k)
    model = build_model(cfg)
    params = weights.make(model.param_specs(), 5, lambda x: isinstance(x, ArraySpec))
    rng = np.random.default_rng(0)
    prompt = rng.integers(1, cfg.vocab_size, 12).astype(np.int32)
    follow = rng.integers(1, cfg.vocab_size, 6).astype(np.int32)
    total = len(prompt) + len(follow)

    with gemm_context(backend="xla"):
        logits, cache = model.prefill(params, jnp.asarray(prompt)[None], max_seq=total)
        got = [np.asarray(logits[0, -1])]
        for i, tok in enumerate(follow[:-1]):
            pos = jnp.asarray([len(prompt) + i], jnp.int32)
            logits, cache = model.decode_step(params, cache, jnp.asarray([[tok]], jnp.int32), pos)
            got.append(np.asarray(logits[0, 0]))
    seq = np.concatenate([prompt, follow[:-1]])
    ref = np.asarray(jax.jit(decoder.logits, static_argnums=(2, 3))(params, seq, _ref_config(cfg), False))
    want = ref[len(prompt) - 1:]
    scale = np.max(np.abs(want))
    # float32 on both sides; only the order of the sums differs
    np.testing.assert_allclose(np.stack(got), want, atol=1e-4 * scale, rtol=0)


def test_reference_control_departs_from_float32():
    """The int8 forward pass (the control) is further from float32 than
    rounding: its logits move by a visible share of their scale."""
    from repro.configs import get_reduced
    from repro.dist.sharding import ArraySpec
    from repro.models import build_model

    cfg = dataclasses.replace(get_reduced("granite-8b"), dtype="float32")
    model = build_model(cfg)
    params = weights.make(model.param_specs(), 6, lambda x: isinstance(x, ArraySpec))
    seq = np.random.default_rng(1).integers(1, cfg.vocab_size, 16).astype(np.int32)
    c = _ref_config(cfg)
    f32 = np.asarray(decoder.logits(params, seq, c, False))
    q8 = np.asarray(decoder.logits(params, seq, c, True))
    rel = np.sqrt(np.mean((q8 - f32) ** 2)) / np.sqrt(np.mean(f32**2))
    assert 1e-3 < rel < 0.2
