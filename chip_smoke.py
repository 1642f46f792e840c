#!/usr/bin/env python3
"""Smoke run of the Stream-K++ serving path on a TPU v5e.

Serves granite-8b at its published widths (d_model 4096, 32 query / 8 KV
heads, d_ff 14336, vocab 49152) in bfloat16, with random weights made from
``--seed``, through the same selector and paged-engine construction as
``python -m repro.launch.serve``. Every GEMM is selected by the Stream-K++
selector and runs on the Pallas kernels (``backend="pallas"``).

  python chip_smoke.py                one chip: 18 of the 36 layers (one
                                      stage of a two-stage pipeline); each
                                      kernel family against an f32
                                      reference, then 8 requests served to
                                      completion, checked against the XLA
                                      backend on the same chip
  python chip_smoke.py --four-chips   four chips: all 36 layers on a
                                      (data=1, model=4) mesh, each kernel on
                                      its shard; checked against the XLA
                                      backend on the same mesh
  python chip_smoke.py --cpu-rehearsal [--four-chips]
                                      the same phases on the CPU at a tiny
                                      size with interpret-mode kernels

Everything runs in this one process, which holds the chip. The lines before
the last are a smoke run's readings, not metrics. Any failed check exits
non-zero; on success the last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
A platform other than TPU is an error, except under ``--cpu-rehearsal``,
which never reports a device result.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

#: rtol = atol of one GEMM on bf16 activations (README "Tolerances"); here
#: the bound on rms(pallas - xla) / rms(xla) of the first served chunk
BF16_TOL = 2e-2
#: max |kernel - f32 reference| on bf16 operands with f32 output of unit
#: scale. Accumulating in f32 in another order reads ~1e-6 on the chip;
#: bf16 partial sums read ~1e-2 (the control in ``kernel_phase``).
KERNEL_TOL = 1e-4
#: Pallas may sit at most this much further from the f32-activation logits
#: than the XLA backend does (rms over rms, first served chunk)
WITNESS_RATIO = 1.25
#: every served step: rms(pallas - xla) / rms(xla) at most this many times
#: the XLA backend's own distance from f32 activations at this depth. Two
#: correct bf16 runs each sit that far from f32, about sqrt(2) times apart;
#: a faulty kernel is off by the logits' own scale.
STEP_RATIO = 2.0


@dataclasses.dataclass(frozen=True)
class Sizes:
    """One run's shapes: model depth, engine geometry, request stream, and
    the kernel-check problems."""

    layers: int
    max_seq: int
    page_size: int
    prefill_chunk: int
    prompt_lens: tuple
    max_new: int
    gemm_decode: tuple  # (M, N, K): mlp.in at decode
    gemm_prefill: tuple  # (M, N, K): mlp.in at one prefill chunk
    grouped: tuple  # (G, M, K, N): olmoe-1b-7b experts


# Prompt lengths are page-table sizes in (max_seq/2, max_seq], so every
# gathered KV view has one width, and chunk remainders are 0 or chunk/2:
# the served programs are one decode step and two chunk shapes.
CHIP = Sizes(
    layers=18,
    max_seq=2048,
    page_size=16,
    prefill_chunk=512,
    prompt_lens=(1280, 1536, 1792, 1280, 1536, 1792, 1280, 1536),
    max_new=16,
    gemm_decode=(8, 14336, 4096),
    gemm_prefill=(512, 14336, 4096),
    grouped=(64, 80, 2048, 1024),
)
FOUR_CHIPS = dataclasses.replace(CHIP, layers=36, prompt_lens=CHIP.prompt_lens[:4], max_new=8)
REHEARSAL = Sizes(
    layers=2,
    max_seq=128,
    page_size=16,
    prefill_chunk=32,
    prompt_lens=(80, 96, 112, 80),
    max_new=4,
    gemm_decode=(8, 512, 256),
    gemm_prefill=(64, 512, 256),
    grouped=(4, 16, 256, 128),
)


def say(msg: str) -> None:
    print(f"smoke: {msg}", flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise RuntimeError(f"smoke check failed: {msg}")


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true", help="the 4-chip phase only")
    ap.add_argument("--cpu-rehearsal", action="store_true", help="tiny CPU run")
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def max_err(got, want) -> float:
    import numpy as np

    return float(np.max(np.abs(np.asarray(got, np.float32) - np.asarray(want, np.float32))))


def rms(d) -> float:
    import numpy as np

    return float(np.sqrt(np.mean(np.square(d))))


def kernel_phase(sizes: Sizes, interpret: bool, seed: int) -> None:
    """Each kernel family once against ``jnp.dot`` at HIGHEST precision in
    f32: DP, split-K and Stream-K under every policy at the selector's tile
    pick, and the fused grouped kernel at expert widths. A control with bf16
    partial sums must fail the same limit."""
    import jax
    import jax.numpy as jnp

    from repro.core.op import GemmOp
    from repro.core.policies import ALL_POLICIES
    from repro.core.selector import KernelSelector
    from repro.kernels.dp import ops as dp_ops
    from repro.kernels.splitk import ops as splitk_ops
    from repro.kernels.streamk import ops as sk_ops
    from repro.kernels.streamk.grouped import gemm_grouped_streamk

    sel = KernelSelector()
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 8))

    def operands(ga, gb):
        a = jax.random.normal(next(keys), ga, jnp.float32).astype(jnp.bfloat16)
        b = jax.random.normal(next(keys), gb, jnp.float32) / gb[-2] ** 0.5
        return a, b.astype(jnp.bfloat16)

    def ref(a, b):
        return jnp.matmul(
            a.astype(jnp.float32), b.astype(jnp.float32), precision="highest"
        )

    def report(name, got, want):
        err = max_err(got, want)
        say(f"kernel {name}: max|err| {err:.3e} (limit {KERNEL_TOL})")
        check(err <= KERNEL_TOL, f"kernel {name} off the f32 reference by {err:.3e}")

    kw = dict(interpret=interpret, out_dtype=jnp.float32)
    m, n, k = sizes.gemm_decode
    a, b = operands((m, k), (k, n))
    want = ref(a, b)
    halves = (slice(0, k // 2), slice(k // 2, k))
    control = sum(
        ref(a[:, h], b[h]).astype(jnp.bfloat16).astype(jnp.float32) for h in halves
    )
    err = max_err(control, want)
    say(f"control: two bf16 partial sums {(m, n, k)}: max|err| {err:.3e}")
    check(err > KERNEL_TOL, f"the kernel limit {KERNEL_TOL} passes bf16 partial sums")
    pick = sel.select_op(GemmOp.plain(m, n, k, in_dtype="bfloat16"))
    for policy in ALL_POLICIES:
        got = sk_ops.gemm(a, b, policy=policy, cfg=pick.cfg, g=pick.g, **kw)
        report(f"streamk {policy.name} {pick.cfg.name} g={pick.g} {(m, n, k)}", got, want)
    got = splitk_ops.gemm(a, b, cfg=pick.cfg, s=2, g=pick.g, **kw)
    report(f"splitk s=2 {pick.cfg.name} {(m, n, k)}", got, want)

    m, n, k = sizes.gemm_prefill
    a, b = operands((m, k), (k, n))
    pick = sel.select_op(GemmOp.plain(m, n, k, in_dtype="bfloat16"))
    got = dp_ops.gemm(a, b, cfg=pick.cfg, g=pick.g, **kw)
    report(f"dp {pick.cfg.name} g={pick.g} {(m, n, k)}", got, ref(a, b))

    g, m, k, n = sizes.grouped
    a, b = operands((g, m, k), (g, k, n))
    op = GemmOp(
        m, n, k, g=g, kind="grouped", in_dtype="bfloat16",
        out_dtype="bfloat16", fused=True,
    )
    pick = sel.select_op(op)
    got = gemm_grouped_streamk(a, b, policy=pick.policy, cfg=pick.cfg, g=pick.g, **kw)
    report(f"grouped {pick.policy.name} {pick.cfg.name} G={g} {(m, n, k)}", got, ref(a, b))


@dataclasses.dataclass
class Served:
    """What one engine served: the finished requests, the logits of every
    served step in order (keyed by program), the token the sampler picked
    at each sample, and the ``tpu_custom_call`` count of each program."""

    done: list
    steps: list
    picks: list
    programs: dict
    selections: dict
    engine_steps: int
    seconds: float
    div: dict


def serve_once(args, model, params, prompts, forced=None) -> Served:
    """Serve ``prompts`` to completion through ``serve.make_engine`` under
    the ambient gemm context and plan, recording what each served program
    returns. Every chunk of a prompt, the first included, runs through the
    engine's jitted chunk step; decode through its jitted decode step,
    whose padding rows are dropped.
    With ``forced``, the engine samples those tokens instead of its own
    picks, so every step sees the inputs of the run that made them."""
    import numpy as np

    from repro.launch import serve

    engine = serve.make_engine(args, model, params)
    steps, picks, programs = [], [], {}

    def tap(name, fn, rows):
        def call(*a):
            key = f"{name} {tuple(a[3].shape)}"  # a[3]: the step's tokens
            if key not in programs:
                programs[key] = fn.lower(*a).as_text().count("tpu_custom_call")
            logits, pool = fn(*a)
            steps.append((key, np.asarray(logits, np.float32)[rows(a)]))
            return logits, pool

        return call

    engine._decode = tap("decode", engine._decode, lambda a: np.asarray(a[4]) > 0)
    engine._chunk_step = tap("chunk", engine._chunk_step, lambda a: slice(None))

    sample = engine._sample

    def pick(logits, temperature):
        picks.append(sample(logits, temperature))
        return picks[-1] if forced is None else forced[len(picks) - 1]

    engine._sample = pick
    for p in prompts:
        engine.submit(p, max_new_tokens=args.max_new_tokens)
    t0 = time.perf_counter()
    done = engine.run()
    seconds = time.perf_counter() - t0
    selections = {}
    for e in engine.selection_log:
        selections.setdefault((e.tag, e.local_mnk), e.selection)
    return Served(
        done, steps, picks, programs, selections, engine.metrics()["steps"], seconds, engine.div
    )


def serve_phase(sizes: Sizes, *, mesh_model: int, backend: str, rehearsal: bool, seed: int):
    """Serve the request stream through the paged engine on ``backend``,
    then again on the XLA backend fed the same tokens, and compare the
    logits of every served step; check the served programs hold the Mosaic
    kernels, and hold the first chunk to the f32-activation logits."""
    import gc

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.core.gemm import gemm_context
    from repro.dist.sharding import use_plan
    from repro.launch import serve
    from repro.models import build_model

    argv = [
        "--arch", "granite-8b", "--preset", "full", "--paged",
        "--slots", "8", "--max-seq", str(sizes.max_seq),
        "--page-size", str(sizes.page_size),
        "--prefill-chunk", str(sizes.prefill_chunk),
        "--max-new-tokens", str(sizes.max_new), "--seed", str(seed),
    ]
    if mesh_model:
        argv += ["--mesh-model", str(mesh_model)]
    args = serve.parse_args(argv)
    cfg = dataclasses.replace(serve.load_config(args), n_layers=sizes.layers)
    if rehearsal:
        # tiny widths that still divide a 4-way model axis
        cfg = dataclasses.replace(
            get_config("granite-8b"), n_layers=sizes.layers, d_model=256,
            n_heads=8, n_kv_heads=4, d_head=32, d_ff=512, vocab_size=1024,
        )
    say(
        f"model granite-8b {cfg.n_layers}/36 layers d_model={cfg.d_model} "
        f"heads={cfg.n_heads}/{cfg.n_kv_heads} d_ff={cfg.d_ff} "
        f"vocab={cfg.vocab_size} {cfg.dtype}, backend {backend}"
    )
    model = build_model(cfg)
    plan = serve.make_plan(args)
    t0 = time.perf_counter()
    params = serve.init_params(model, args, plan)
    jax.block_until_ready(params)
    say(f"weights made in {time.perf_counter() - t0:.1f} s")
    mach = serve.load_machine(args)
    selector, _ = serve.build_worker(
        args, 0, mach=mach, grid_sizes=serve.parse_grid_sizes(args), arch_cls=serve.DEFAULT_ARCH
    )
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, cfg.vocab_size, size=n) for n in sizes.prompt_lens]

    with use_plan(plan), gemm_context(selector=selector, backend=backend):
        run = serve_once(args, model, params, prompts)
    gc.collect()  # the engine and its KV pool
    ntok = sum(len(r.out_tokens) for r in run.done)
    say(
        f"served {len(run.done)}/{len(prompts)} requests, {ntok} tokens in "
        f"{run.seconds:.1f} s (compiles included); engine steps {run.engine_steps}"
    )
    check(len(run.done) == len(prompts), "not every request completed")
    check(
        all(len(r.out_tokens) == args.max_new_tokens and not r.truncated for r in run.done),
        "a request ended short of its max_new_tokens",
    )
    say(f"selection log: {len(run.selections)} distinct (tag, local MNK)")
    for (tag, mnk), s in sorted(run.selections.items()):
        say(f"  {tag:10s} {mnk} -> {s.policy.name}/{s.cfg.name} g={s.g} ({s.source})")
    # the jitted programs the engine served hold the Mosaic kernels, not
    # an XLA stand-in
    for key, n in sorted(run.programs.items()):
        say(f"served program {key}: {n} tpu_custom_call sites")
        check(rehearsal or n > 0, f"the served program {key} has no Pallas kernel")

    with use_plan(plan), gemm_context(selector=selector, backend="xla"):
        xla = serve_once(args, model, params, prompts, forced=run.picks)
        gc.collect()
        # the first served chunk with f32 activations over the same weights
        chunk = min(sizes.prefill_chunk, sizes.prompt_lens[0])
        tokens = jnp.asarray(prompts[0][:chunk])[None, :]
        f32 = build_model(dataclasses.replace(cfg, dtype="float32"))
        with jax.default_matmul_precision("highest"):
            ref = np.asarray(
                f32.prefill(params, tokens, max_seq=chunk, div=run.div)[0], np.float32
            )
    say(f"xla backend: {len(xla.done)} requests served the same tokens in {xla.seconds:.1f} s")
    check(
        [k for k, _ in xla.steps] == [k for k, _ in run.steps],
        "the xla engine served other steps than the pallas engine",
    )
    check(
        all(np.all(np.isfinite(v)) for _, v in run.steps), "non-finite logits in a served step"
    )

    (key, p), (_, x) = run.steps[0], xla.steps[0]
    check(key.startswith("chunk"), f"the first served step is {key}")
    gap = rms(p - x) / rms(x)
    sig_p, sig_x = rms(p - ref) / rms(ref), rms(x - ref) / rms(ref)
    say(
        f"{key} logits vs the xla backend: max|err| {max_err(p, x):.3e}, "
        f"max|logit| {np.max(np.abs(x)):.3e}, rms error / rms logit {gap:.3e} "
        f"(limit {BF16_TOL})"
    )
    say(
        f"{key} logits vs f32 activations, rms error / rms logit: pallas "
        f"{sig_p:.3e}, xla {sig_x:.3e} (pallas limit {WITNESS_RATIO} x xla)"
    )
    check(gap <= BF16_TOL, f"pallas logits differ from the xla backend's by {gap:.3e} (rms)")
    check(
        sig_p <= WITNESS_RATIO * sig_x,
        f"pallas sits {sig_p:.3e} from f32 activations, xla {sig_x:.3e}",
    )

    limit = STEP_RATIO * sig_x
    worst = {}
    for (key, p), (_, x) in zip(run.steps, xla.steps):
        n, w = worst.get(key, (0, 0.0))
        worst[key] = (n + 1, max(w, rms(p - x) / rms(x)))
    for key, (n, w) in sorted(worst.items()):
        say(f"{key}: {n} served steps, worst rms error / rms logit vs xla {w:.3e} (limit {limit:.3e})")
        check(w <= limit, f"{key} logits differ from the xla backend's by {w:.3e} (rms)")
    agree = float(np.mean(np.asarray(run.picks) == np.asarray(xla.picks)))
    say(f"greedy picks: the xla backend picks the served token at {agree:.3f} of {len(run.picks)}")


def main(argv=None) -> int:
    args = parse_args(argv)
    if args.cpu_rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if args.four_chips:
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, os.path.join(HERE, "src"))
    from repro.launch.cache import enable_compile_cache

    import jax

    cache = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}
    say(f"device {json.dumps(device)}; compile cache {cache}")
    if not args.cpu_rehearsal and dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (platform {dev.platform!r})", file=sys.stderr)
        return 1
    chips = 4 if args.four_chips else 1
    if len(devices) < chips:
        print(f"chip_smoke: needs {chips} devices, found {len(devices)}", file=sys.stderr)
        return 1

    compile_s = [0.0]

    def on_duration(event, seconds, **_):
        if "backend_compile" in event:
            compile_s[0] += seconds

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    backend = "pallas_interpret" if args.cpu_rehearsal else "pallas"
    sizes = REHEARSAL if args.cpu_rehearsal else (FOUR_CHIPS if args.four_chips else CHIP)
    if args.cpu_rehearsal and args.four_chips:
        sizes = dataclasses.replace(sizes, layers=4)
    t0 = time.perf_counter()
    if not args.four_chips:
        kernel_phase(sizes, args.cpu_rehearsal, args.seed)
    serve_phase(
        sizes,
        mesh_model=4 if args.four_chips else 0,
        backend=backend,
        rehearsal=args.cpu_rehearsal,
        seed=args.seed,
    )
    say(f"wall {time.perf_counter() - t0:.1f} s, of it compiling {compile_s[0]:.1f} s")
    stats = dev.memory_stats() or {}
    say(f"peak_bytes_in_use {stats.get('peak_bytes_in_use', 'not reported')}")
    if args.cpu_rehearsal:
        print(json.dumps({"rehearsal": True, "device": device}))
        return 0
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
