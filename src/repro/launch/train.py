"""Training launcher CLI.

Examples:
  # 100M-param LM for a few hundred steps on host devices:
  PYTHONPATH=src python -m repro.launch.train --arch granite-8b --preset 100m \
      --steps 300 --batch 8 --seq-len 256 --ckpt-dir /tmp/ckpt

  # full assigned config (reduced smoke on CPU would OOM — use --preset):
  PYTHONPATH=src python -m repro.launch.train --arch olmoe-1b-7b --preset reduced --steps 50
"""

from __future__ import annotations

import argparse
import dataclasses

import jax

from repro.configs import get_config, get_reduced, list_archs
from repro.data import SyntheticLMData
from repro.dist.sharding import materialize_tree
from repro.launch.cache import enable_compile_cache
from repro.models import build_model
from repro.optim import make_optimizer, warmup_cosine
from repro.train import Trainer, TrainerConfig, init_train_state
from repro.utils.logging import get_logger

log = get_logger("launch.train")


def preset_config(arch: str, preset: str):
    if preset == "full":
        return get_config(arch)
    if preset == "reduced":
        return get_reduced(arch)
    if preset == "100m":
        # ~100M-param member of the arch's family (end-to-end driver scale)
        base = get_reduced(arch)
        kw = dict(
            n_layers=8,
            d_model=512,
            d_ff=2048 if base.d_ff else 0,
            vocab_size=32768,
            d_head=64,
        )
        if base.n_heads:
            kw.update(n_heads=8, n_kv_heads=max(1, min(base.n_kv_heads, 8)))
        if base.n_experts:
            kw.update(n_experts=8, top_k=2, d_ff=1024)
        if base.ssm_state:
            kw.update(ssm_state=64, ssm_head_dim=64, ssm_chunk=64)
        if base.family == "encdec":
            kw.update(n_enc_layers=4, enc_frames=128)
        return dataclasses.replace(base, **kw)
    raise ValueError(f"unknown preset {preset}")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list_archs())
    ap.add_argument("--preset", default="100m", choices=["full", "reduced", "100m"])
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--optimizer", default="adamw", choices=["adamw", "sgd", "adafactor"])
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--grad-compression", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--dtype", default=None, help="override the config's dtype (default: keep it)"
    )
    args = ap.parse_args()
    enable_compile_cache()

    cfg = preset_config(args.arch, args.preset)
    if args.dtype:
        cfg = dataclasses.replace(cfg, dtype=args.dtype)
    model = build_model(cfg)
    log.info(
        "arch=%s preset=%s params=%.1fM", args.arch, args.preset, cfg.param_count() / 1e6
    )

    params = materialize_tree(model.param_specs(), jax.random.PRNGKey(args.seed))
    opt = make_optimizer(
        args.optimizer, warmup_cosine(args.lr, args.warmup, args.steps)
    )
    data = SyntheticLMData(cfg, batch=args.batch, seq_len=args.seq_len, seed=args.seed)
    trainer = Trainer(
        model,
        opt,
        data,
        TrainerConfig(
            total_steps=args.steps,
            ckpt_dir=args.ckpt_dir,
            ckpt_every=args.ckpt_every,
            log_every=10,
            microbatches=args.microbatches,
            grad_compression=args.grad_compression,
            handle_sigterm=args.ckpt_dir is not None,
        ),
    )
    state = init_train_state(model, opt, params, args.grad_compression)
    trainer.fit(state)
    log.info("final loss %.4f (first %.4f)", trainer.history[-1], trainer.history[0])
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
