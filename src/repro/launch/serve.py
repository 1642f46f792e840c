"""Serving launcher CLI: batched decode with continuous batching.

Example:
  PYTHONPATH=src python -m repro.launch.serve --arch granite-8b --preset 100m \
      --requests 16 --max-new-tokens 32

Quantized serving (int8 weights, fused dequant epilogues):
  PYTHONPATH=src python -m repro.launch.serve --arch granite-8b --requests 16 \
      --quantize int8 --adapt --journal artifacts/tuning_journal.jsonl

``--quantize int8`` converts every projection weight to a QuantizedTensor at
load; decode GEMMs dispatch under mixed ``'<act>*int8'`` fingerprints, so
they tune/journal/warm-start independently of the f32 ops at the same MNK.

Online adaptation (miss-driven autotuning in the decode loop):
  PYTHONPATH=src python -m repro.launch.serve --arch granite-8b --requests 16 \
      --adapt --adapt-every 4 --adapt-budget 0.05 \
      --db artifacts/tuning_db.json --journal artifacts/tuning_journal.jsonl

``--db`` warm-starts the selector from an offline snapshot; ``--journal`` is
replayed on top at startup and appended to as serving traffic teaches the
tuner new fingerprints, so the next run starts where this one left off.

Federated serving (simulated K-process fleet):
  PYTHONPATH=src python -m repro.launch.serve --arch granite-8b --requests 32 \
      --adapt --workers 4 --merge-journals --journal artifacts/tuning_journal.jsonl

``--workers K`` serves the request stream through K engines with fully
separate selector/tuner/database state (what K serving processes would
hold), each appending to its own journal shard ``<journal>.shard<i>``;
``--merge-journals`` federates every existing shard into each worker's
warm-start database (``repro.core.federate``), so a fingerprint one worker
tuned yesterday is a database hit in every worker today. ``--mesh-model N``
installs a host-mesh sharding plan so dispatch fingerprints key on the
per-shard local MNK (mesh-aware federation across identically-sharded
hosts).

Streaming gossip and heterogeneous fleets:
  PYTHONPATH=src python -m repro.launch.serve --arch granite-8b --requests 32 \
      --adapt --workers 2 --gossip-every 8 --arch-class auto \
      --journal artifacts/tuning_journal.jsonl

``--gossip-every N`` keeps federation continuous: every N engine steps each
worker tails its siblings' journal shards (``repro.core.gossip``) and folds
fresh commits into its live selector via an atomic hot-swap — no restart
between learning and benefiting. ``--arch-class auto`` stamps records with
the machine's architecture class; same-class records federate as direct
database hits while other-class records only seed selection as re-ranked
``"xarch"`` candidates (never applied verbatim).

Paged serving with admission control and traffic replay:
  PYTHONPATH=src python -m repro.launch.serve --arch granite-8b --requests 32 \
      --paged --page-size 16 --max-pages 64 --replay poisson

``--paged`` swaps in the block/paged-KV engine (``repro.serve.scheduler``):
KV memory is a page pool, residency is bounded by actual sequence lengths,
and admission is oldest-first under a watermark reserve. ``--max-pages 0``
(the default) sizes the pool to exactly the dense engine's KV rows
(``slots * max_seq / page_size``) so the two modes compare at equal memory.
``--replay poisson|bursty`` schedules submissions on a synthetic arrival
process (one engine step per clock tick) instead of enqueueing everything
up front, and logs the SLO summary (p50/p99 latency, TTFT, page occupancy,
admission counters) the paged engine tracks per request.
"""

from __future__ import annotations

import argparse
import dataclasses
import glob
import json
import os
import time

import jax
import numpy as np

from repro.configs import list_archs
from repro.core import costmodel
from repro.core.adaptive import AdaptiveConfig, AdaptiveTuner
from repro.core.arch import DEFAULT_ARCH, append_arch, detect_arch
from repro.core.calibrate import (
    CalibrationError,
    append_calibration,
    calibrate_db,
    machine_from_json,
)
from repro.core.federate import apply_journal_db, merge_journal_shards
from repro.core.gemm import gemm_context
from repro.core.gossip import GossipExchange
from repro.core.selector import KernelSelector, SelectorState
from repro.core.tuner import TuningDatabase
from repro.dist.sharding import ShardingPlan, materialize_tree, use_plan
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.launch.train import preset_config
from repro.models import build_model
from repro.serve import (
    AdmissionError,
    PagedServeConfig,
    PagedServeEngine,
    ServeConfig,
    ServeEngine,
)
from repro.utils.logging import get_logger

log = get_logger("launch.serve")


def shard_journal_path(journal: str, worker: int, n_workers: int) -> str:
    """Worker ``worker``'s private journal shard (the base path itself for a
    single-worker run, preserving the PR-2 CLI contract)."""
    return journal if n_workers <= 1 else f"{journal}.shard{worker}"


def existing_journal_shards(journal: str) -> list:
    """Every journal shard a previous (possibly differently-sized) fleet
    left behind, base journal included."""
    paths = sorted(glob.glob(f"{journal}.shard*"))
    if os.path.exists(journal):
        paths.insert(0, journal)
    return paths


def replay_arrivals(n: int, pattern: str, rate: float, seed: int) -> list:
    """Arrival step index per request: ``poisson`` draws exponential
    inter-arrival gaps at ``rate`` requests/step; ``bursty`` emits
    back-to-back bursts of 4-12 separated by long idle gaps."""
    rng = np.random.default_rng(seed + 1)
    if pattern == "poisson":
        return [int(t) for t in np.floor(np.cumsum(rng.exponential(1.0 / rate, n)))]
    steps: list = []
    t = 0.0
    while len(steps) < n:
        burst = int(rng.integers(4, 13))
        steps.extend(int(t) for _ in range(min(burst, n - len(steps))))
        t += rng.exponential(burst / rate) + 1.0
    return steps


def replay_stream(
    engine,
    prompts,
    *,
    pattern,
    rate,
    seed,
    max_new,
    temperature,
    gossip=None,
    gossip_every=0,
):
    """Drive ``engine`` on a synthetic arrival process: one engine step per
    clock tick, submissions offered as they come due, queue backpressure
    (:class:`~repro.serve.AdmissionError`) re-offered next tick. With a
    :class:`~repro.core.gossip.GossipExchange`, sibling journal shards are
    polled every ``gossip_every`` clock ticks (plus once at drain), so the
    worker absorbs fleet commits mid-stream. Returns the finished request
    objects."""
    arrivals = replay_arrivals(len(prompts), pattern, rate, seed)
    tracked = []
    i = 0
    step = 0
    while i < len(prompts) or engine.outstanding():
        while i < len(prompts) and arrivals[i] <= step:
            try:
                engine.submit(
                    prompts[i], max_new_tokens=max_new, temperature=temperature
                )
            except AdmissionError:
                break  # queue full: this and younger requests wait a tick
            tracked.append(engine._queue[-1])
            i += 1
        engine.step()
        step += 1
        if gossip is not None and gossip_every > 0 and step % gossip_every == 0:
            gossip.exchange()
    if gossip is not None:
        gossip.exchange()
    return [r for r in tracked if r.done]


def run_with_gossip(engine, gossip, every, max_steps: int = 10_000):
    """``EngineCore.run`` with a gossip exchange every ``every`` steps.

    Mirrors the drain loop exactly (queue + resident tracking, adaptive
    end-of-run flush, exhaustion accounting) and folds sibling journal
    shards in mid-run — the live-fleet path where a worker picks up what a
    sibling tuned moments ago without restarting. A final exchange runs
    after the drain so nothing a sibling committed during our last steps is
    left for the next process lifetime."""
    finished = []
    seen = {}
    steps = 0
    for _ in range(max_steps):
        for r in list(engine._queue):
            seen[r.uid] = r
        for r in engine.outstanding():
            seen[r.uid] = r
        if not engine.step():
            break
        steps += 1
        if every > 0 and steps % every == 0:
            gossip.exchange()
    if engine.adaptive is not None and engine.adapt_every > 0:
        engine.adaptive.drain()
    gossip.exchange()
    for r in seen.values():
        if r.done:
            finished.append(r)
    engine.unfinished = engine.outstanding()
    engine.exhausted = bool(engine.unfinished)
    return finished


def build_parser() -> argparse.ArgumentParser:
    """The serving CLI's flags (see the module doc)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list_archs())
    ap.add_argument("--preset", default="100m", choices=["full", "reduced", "100m"])
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--max-seq", type=int, default=512)
    ap.add_argument("--max-new-tokens", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--dtype", default=None, help="override the config's dtype (default: keep it)"
    )
    ap.add_argument(
        "--paged",
        action="store_true",
        help="serve through the paged-KV engine (page-pool memory, "
        "admission control, optional chunked prefill) instead of the "
        "dense slot engine",
    )
    ap.add_argument(
        "--page-size",
        type=int,
        default=16,
        help="KV rows per page (with --paged)",
    )
    ap.add_argument(
        "--max-pages",
        type=int,
        default=0,
        help="page-pool size; 0 sizes it to the dense engine's KV rows "
        "(slots * max-seq / page-size) for an equal-memory comparison",
    )
    ap.add_argument(
        "--prefill-chunk",
        type=int,
        default=0,
        help="prefill long prompts in chunks of this many tokens, one "
        "chunk per engine step (0: whole-prompt prefill; with --paged)",
    )
    ap.add_argument(
        "--replay",
        default="off",
        choices=["off", "poisson", "bursty"],
        help="schedule submissions on a synthetic arrival process instead "
        "of enqueueing everything up front, and log the per-request SLO "
        "summary",
    )
    ap.add_argument(
        "--replay-rate",
        type=float,
        default=1.0,
        help="mean arrivals per engine step for --replay",
    )
    ap.add_argument(
        "--quantize",
        default="none",
        choices=["none", "int8", "int8-dynamic", "int4"],
        help="one-shot weight quantization at load: projection weights "
        "become QuantizedTensors (per-output-channel symmetric scales, "
        "dequant fused into the GEMM kernels). 'int8' keeps float "
        "activations ('<act>*int8' fingerprints); 'int8-dynamic' also "
        "quantizes activations per row at dispatch, running the int8xint8 "
        "MXU path ('int8*int8'); 'int4' packs weights two nibbles per byte "
        "along K ('<act>*int4', B traffic 0.5 bytes/element)",
    )
    ap.add_argument(
        "--adapt",
        action="store_true",
        help="enable online miss-driven autotuning in the decode loop",
    )
    ap.add_argument(
        "--adapt-every",
        type=int,
        default=4,
        help="decode steps between adaptation rounds (with --adapt)",
    )
    ap.add_argument(
        "--adapt-budget",
        type=float,
        default=None,
        help="wallclock seconds per adaptation round (default: uncapped)",
    )
    ap.add_argument(
        "--adapt-threshold",
        type=int,
        default=1,
        help="trace-time misses before a fingerprint is tuned (selection "
        "runs at trace time, so jit-cached repeats don't re-count: a "
        "fingerprint that traces at all will serve many dispatches)",
    )
    ap.add_argument(
        "--grid-sweep",
        default=None,
        help="comma-separated grid sizes the selector/tuner sweep jointly "
        "with (policy, tile), e.g. '4,8,16' (default: {lanes/2, lanes, "
        "2*lanes} for the machine model)",
    )
    ap.add_argument(
        "--calibrate",
        action="store_true",
        help="fit a CalibratedMachine from the warm-start records before "
        "serving (robust least-squares per dtype profile over journaled "
        "wall clocks); unseen fingerprints then dispatch from the model's "
        "argmin ('model' source) and the fit is journaled for the next run",
    )
    ap.add_argument(
        "--top-k",
        type=int,
        default=None,
        help="budgeted adaptation sweeps: measure only the cost model's "
        "top-k ranked candidates per hot fingerprint instead of the "
        "exhaustive (policy x tile x grid) sweep",
    )
    ap.add_argument(
        "--mach-json",
        default=None,
        help="JSON file of Machine field overrides (e.g. "
        '\'{"peak_flops": 1.5e14, "lanes": 4}\') — the nominal machine '
        "scoring/tuning/calibration run against",
    )
    ap.add_argument(
        "--db",
        default=None,
        help="tuning database snapshot to warm-start the selector from",
    )
    ap.add_argument(
        "--journal",
        default=None,
        help="append-only tuning journal: replayed on start, appended to by "
        "--adapt commits (per-worker shards <journal>.shard<i> when "
        "--workers > 1)",
    )
    ap.add_argument(
        "--workers",
        type=int,
        default=1,
        help="simulate K serving processes with fully separate "
        "selector/tuner state, each journaling to its own shard",
    )
    ap.add_argument(
        "--merge-journals",
        action="store_true",
        help="federate every existing journal shard (<journal> + "
        "<journal>.shard*) into each worker's warm-start database",
    )
    ap.add_argument(
        "--mesh-model",
        type=int,
        default=0,
        help="install a (data, model=N) host-mesh sharding plan so dispatch "
        "fingerprints key on per-shard local MNK (0: no plan)",
    )
    ap.add_argument(
        "--gossip-every",
        type=int,
        default=0,
        help="poll sibling workers' journal shards every N engine steps and "
        "fold fresh commits into the live selector (streaming federation; "
        "0: off; requires --journal)",
    )
    ap.add_argument(
        "--arch-class",
        default="off",
        choices=["off", "auto"],
        help="stamp tuning records with an architecture class: 'auto' "
        "derives an ArchProfile from the (possibly overridden) machine and "
        "live backend, so records only federate as direct hits within the "
        "same device class ('off': the legacy single-class 'default')",
    )
    return ap


def parse_args(argv=None) -> argparse.Namespace:
    """Parse and cross-check the serving flags."""
    args = build_parser().parse_args(argv)
    if args.workers < 1:
        raise SystemExit(f"--workers must be >= 1, got {args.workers}")
    if args.merge_journals and not args.journal:
        raise SystemExit("--merge-journals requires --journal")
    if args.gossip_every < 0:
        raise SystemExit(f"--gossip-every must be >= 0, got {args.gossip_every}")
    if args.gossip_every and not args.journal:
        raise SystemExit("--gossip-every requires --journal")
    return args


def load_config(args):
    """The model config ``args`` select (preset, optional dtype override)."""
    cfg = preset_config(args.arch, args.preset)
    if args.dtype:
        cfg = dataclasses.replace(cfg, dtype=args.dtype)
    if cfg.family == "encdec":
        raise SystemExit("serve CLI drives decoder-only archs; see examples/ for enc-dec")
    return cfg


def make_plan(args):
    """A (data, model=N) host-mesh sharding plan for ``--mesh-model N``."""
    if not args.mesh_model:
        return None
    mesh = make_host_mesh(model=args.mesh_model)
    plan = ShardingPlan(mesh)
    log.info(
        "mesh plan installed: %s -> gemm divisors %s",
        dict(mesh.shape),
        plan.gemm_div(),
    )
    return plan


def init_params(model, args, plan=None):
    """Random weights from ``--seed`` (sharded over ``plan``'s mesh when one
    is given), quantized at load per ``--quantize``."""
    specs = model.param_specs()
    shardings = plan.tree_shardings(specs) if plan is not None else None
    params = materialize_tree(specs, jax.random.PRNGKey(args.seed), shardings)
    if args.quantize == "none":
        return params
    # every decoder-only arch serves through LM, which owns the
    # quantization entry point (enc-dec was rejected by load_config)
    bits = 4 if args.quantize == "int4" else 8
    act_bits = 8 if args.quantize == "int8-dynamic" else None
    params, n_quant, n_skipped = model.quantize_weights(
        params, bits=bits, act_bits=act_bits
    )
    log.info(
        "quantized %d weight leaves to int%d (per-output-channel "
        "scales%s); %d float leaves skipped",
        n_quant,
        bits,
        ", dynamic int8 activations" if act_bits else "",
        n_skipped,
    )
    return params


def parse_grid_sizes(args):
    """``--grid-sweep`` as a sorted tuple (None: the machine's default)."""
    if not args.grid_sweep:
        return None
    try:
        grid_sizes = tuple(
            sorted({int(x) for x in args.grid_sweep.split(",") if x.strip()})
        )
    except ValueError:
        raise SystemExit(f"bad --grid-sweep {args.grid_sweep!r}") from None
    if not grid_sizes or min(grid_sizes) < 1:
        raise SystemExit(f"bad --grid-sweep {args.grid_sweep!r}")
    return grid_sizes


def load_machine(args):
    """The machine selection scores against: the device's published peaks
    (:func:`~repro.core.costmodel.device_machine`), or ``--mach-json``
    overrides on top of the modeled v5e."""
    if not args.mach_json:
        return costmodel.device_machine()
    try:
        with open(args.mach_json) as f:
            mach = machine_from_json(json.load(f))
    except (OSError, ValueError, TypeError) as e:
        raise SystemExit(f"bad --mach-json {args.mach_json!r}: {e}") from None
    log.info(
        "machine overrides: peak=%.1f TF/s bw=%.0f GB/s lanes=%d",
        mach.peak_flops / 1e12,
        mach.hbm_bw / 1e9,
        mach.lanes,
    )
    return mach


def warm_db(args, w: int, arch_cls: str) -> TuningDatabase:
    """Worker ``w``'s warm-start database — each simulated process
    loads its own copy, exactly as K real processes would: the snapshot,
    then (without --merge-journals) the base journal plus the worker's
    OWN shard from the previous fleet run, or (with --merge-journals)
    the federation of every shard the whole fleet ever wrote."""
    if args.db and os.path.exists(args.db):
        db = TuningDatabase.load(args.db, arch=arch_cls)
    else:
        db = TuningDatabase(arch=arch_cls)
    if not args.journal:
        return db
    if args.merge_journals:
        shards = existing_journal_shards(args.journal)
        if shards:
            # last-writer-wins among the peer shards, then applied
            # ON TOP of the snapshot (journals post-date it; their
            # producer clocks are not comparable to the snapshot's)
            merged, rep = merge_journal_shards(
                shards,
                into=TuningDatabase(arch=arch_cls),
                missing_ok=True,
            )
            apply_journal_db(db, merged)
            log.info(
                "federated warm start: %d shards -> %d records "
                "(%d conflicts, %d superseded, %d load errors)",
                rep.sources,
                len(db.records),
                rep.conflicts,
                rep.superseded,
                rep.load_errors,
            )
        return db
    db.replay_journal(args.journal, missing_ok=True)
    own = shard_journal_path(args.journal, w, args.workers)
    if own != args.journal:
        # a repeat fleet run must not silently cold-start: each
        # worker at least replays what IT learned last time
        db.replay_journal(own, missing_ok=True)
        siblings = [
            p
            for p in existing_journal_shards(args.journal)
            if p not in (args.journal, own)
        ]
        if siblings:
            log.info(
                "worker %d: %d sibling journal shards exist but "
                "--merge-journals is off; pass it to warm-start "
                "from the whole fleet",
                w,
                len(siblings),
            )
    return db


def build_worker(args, w: int, *, mach, grid_sizes, arch_cls, arch_profile=None):
    """Worker ``w``'s (selector, adaptive tuner or None)."""
    use_artifacts = bool(args.db or args.journal or args.adapt or args.calibrate)
    if use_artifacts:
        db = warm_db(args, w, arch_cls)
        # a calibration replayed from the journal/snapshot warm-starts
        # model-first dispatch even without --calibrate
        calibration = db.calibration
        if args.calibrate:
            try:
                db.set_calibration(calibrate_db(db, base=mach))
            except CalibrationError as e:
                log.warning("worker %d: calibration skipped: %s", w, e)
            else:
                calibration = db.calibration
                if args.journal:
                    append_calibration(
                        shard_journal_path(args.journal, w, args.workers),
                        calibration,
                    )
        sieve = db.build_sieve() if db.n_records() else None
        selector = KernelSelector(
            state=SelectorState(
                db=db, sieve=sieve, calibration=calibration, arch=arch_cls
            ),
            mach=mach,
            grid_sizes=grid_sizes,
        )
        log.info(
            "worker %d warm-start: %d tuned records + %d cross-arch "
            "(%d dropped at load), calibration %s, arch %s",
            w,
            len(db.records),
            db.n_records() - len(db.records),
            db.load_errors,
            "installed" if calibration is not None else "absent",
            arch_cls,
        )
    else:
        selector = KernelSelector(
            mach=mach,
            grid_sizes=grid_sizes,
            state=SelectorState(arch=arch_cls),
        )
    if arch_profile is not None and args.journal:
        # declare this producer's coordinates in its shard, so every
        # consumer of the journal knows the machine behind the class
        append_arch(
            shard_journal_path(args.journal, w, args.workers), arch_profile
        )
    adaptive = None
    if args.adapt:
        adaptive = AdaptiveTuner(
            selector,
            config=AdaptiveConfig(
                budget_s=args.adapt_budget,
                hot_threshold=args.adapt_threshold,
                top_k=args.top_k,
            ),
            journal=shard_journal_path(args.journal, w, args.workers)
            if args.journal
            else None,
        )
    return selector, adaptive


def make_engine(args, model, params, adaptive=None):
    """The serving engine ``args`` select: the paged engine with ``--paged``,
    else the dense slot engine. It dispatches under the ambient gemm
    context (and sharding plan) it is built and run in."""
    adapt_every = args.adapt_every if args.adapt else 0
    if args.paged:
        max_pages = args.max_pages or (args.slots * args.max_seq // args.page_size)
        return PagedServeEngine(
            model,
            params,
            PagedServeConfig(
                page_size=args.page_size,
                max_pages=max_pages,
                max_active=args.slots,
                max_seq=args.max_seq,
                prefill_chunk=args.prefill_chunk,
                eos=-1,
                seed=args.seed,
            ),
            adaptive=adaptive,
            adapt_every=adapt_every,
        )
    return ServeEngine(
        model,
        params,
        ServeConfig(n_slots=args.slots, max_seq=args.max_seq, eos=-1),
        adaptive=adaptive,
        adapt_every=adapt_every,
    )


def phase_totals(m) -> str:
    """One line of a paged engine's ``metrics()``: host seconds and count of
    each span name, the compiles and compile-cache loads charged to it, and
    the decode rows per decode batch."""
    parts = []
    for name in sorted(k[: -len(".count")] for k in m if k.endswith(".count")):
        part = f"{name} {m[name + '.s']:.3f} s/{m[name + '.count']}"
        if m[name + ".compiles"] or m[name + ".cache_loads"]:
            part += (
                f" ({m[name + '.compiles']} compiles, {m[name + '.cache_loads']} "
                f"cache loads {m[name + '.cache_load_s']:.3f} s)"
            )
        parts.append(part)
    rows = m["decode_rows"] / m["decode_ticks"] if m["decode_ticks"] else 0.0
    parts.append(f"{rows:.2f} rows per decode batch")
    return ", ".join(parts)


def main(argv=None) -> int:
    args = parse_args(argv)
    enable_compile_cache()
    cfg = load_config(args)
    model = build_model(cfg)
    plan = make_plan(args)
    params = init_params(model, args, plan)
    grid_sizes = parse_grid_sizes(args)
    mach = load_machine(args)
    arch_profile = None
    arch_cls = DEFAULT_ARCH
    if args.arch_class == "auto":
        arch_profile = detect_arch(mach)
        arch_cls = arch_profile.cls
        log.info("arch class: %s", arch_cls)

    # deterministic request stream, dealt round-robin across the workers
    rng = np.random.default_rng(args.seed)
    # prompt lengths must respect the engine's cache bound: submit()
    # rejects len > max_seq
    p_hi = min(64, args.max_seq + 1)
    p_lo = min(8, p_hi - 1)
    prompts = [
        rng.integers(1, cfg.vocab_size, size=int(rng.integers(p_lo, p_hi)))
        for _ in range(args.requests)
    ]

    done = []
    engines = []
    # build every worker's state BEFORE any engine serves: a real fleet's
    # processes all start from the pre-run artifacts, so worker 1 must not
    # warm-start from what worker 0 journaled moments ago in this same run
    worker_state = [
        build_worker(
            args,
            w,
            mach=mach,
            grid_sizes=grid_sizes,
            arch_cls=arch_cls,
            arch_profile=arch_profile,
        )
        for w in range(args.workers)
    ]
    t0 = time.time()
    with use_plan(plan):
        for w in range(args.workers):
            selector, adaptive = worker_state[w]
            gossip = None
            if args.gossip_every and args.workers > 1:
                # each worker tails every OTHER worker's shard: its own
                # commits are already in its database
                peers = [
                    shard_journal_path(args.journal, x, args.workers)
                    for x in range(args.workers)
                    if x != w
                ]
                gossip = GossipExchange(selector, peers)
            with gemm_context(selector=selector) as ctx:
                engine = make_engine(args, model, params, adaptive)
                wprompts = prompts[w :: args.workers]
                if args.replay != "off":
                    done.extend(
                        replay_stream(
                            engine,
                            wprompts,
                            pattern=args.replay,
                            rate=args.replay_rate,
                            seed=args.seed + w,
                            max_new=args.max_new_tokens,
                            temperature=args.temperature,
                            gossip=gossip,
                            gossip_every=args.gossip_every,
                        )
                    )
                    if adaptive is not None:
                        # replay drives step() directly; flush what run()
                        # would have committed at end of drain
                        adaptive.drain()
                else:
                    for prompt in wprompts:
                        engine.submit(
                            prompt,
                            max_new_tokens=args.max_new_tokens,
                            temperature=args.temperature,
                        )
                    if gossip is not None:
                        done.extend(
                            run_with_gossip(engine, gossip, args.gossip_every)
                        )
                    else:
                        done.extend(engine.run())
                if gossip is not None:
                    log.info(
                        "worker %d gossip: %d rounds, %d sibling entries "
                        "absorbed over %d hot-swaps (%d load errors)",
                        w,
                        gossip.stats.rounds,
                        gossip.stats.entries,
                        gossip.stats.swaps,
                        gossip.stats.load_errors,
                    )
                engines.append((w, engine, adaptive, ctx))
    dt = time.time() - t0
    ntok = sum(len(r.out_tokens) for r in done)
    log.info(
        "served %d requests, %d tokens in %.2fs (%.1f tok/s) across %d worker(s)",
        len(done),
        ntok,
        dt,
        ntok / max(dt, 1e-9),
        args.workers,
    )
    if args.paged:
        for w, engine, _, _ in engines:
            m = engine.metrics()
            log.info(
                "worker %d paged pool: peak %d/%d pages, peak %d resident, "
                "%d admitted / %d rejected / %d truncated, %d stall events",
                w,
                m["peak_used_pages"],
                m["n_pages"],
                m["peak_resident"],
                m["admitted"],
                m["rejected"],
                m["truncated"],
                m["stall_events"],
            )
            log.info(
                "worker %d phases: %s; selection %.3f s",
                w,
                phase_totals(m),
                engine.dispatch_stats.select_s,
            )
        if args.replay != "off" and done:
            lat = sorted(r.done_step - r.submit_step for r in done)
            ttft = sorted(r.first_token_step - r.submit_step for r in done)
            pct = lambda a, q: a[min(len(a) - 1, int(q / 100 * len(a)))]  # noqa: E731
            log.info(
                "SLO (steps): latency p50=%d p99=%d, ttft p50=%d p99=%d "
                "over %d completed requests",
                pct(lat, 50),
                pct(lat, 99),
                pct(ttft, 50),
                pct(ttft, 99),
                len(done),
            )
    for w, engine, adaptive, _ in engines:
        if adaptive is not None:
            st = engine.dispatch_stats
            log.info(
                "worker %d adaptation: %d misses (%d model-warm, %d "
                "xarch-seeded) -> %d records committed (sieve generation "
                "%d, %d pending, db=%d records)",
                w,
                st.misses,
                st.model_warm,
                st.xarch_seeds,
                st.adaptations,
                st.sieve_generation,
                st.pending_hot,
                st.db_records,
            )
    if args.workers > 1 and args.journal:
        # federation summary: what the fleet collectively learned this run
        shard_paths = [
            shard_journal_path(args.journal, w, args.workers)
            for w in range(args.workers)
        ]
        merged, rep = merge_journal_shards(
            shard_paths, into=TuningDatabase(arch=arch_cls), missing_ok=True
        )
        log.info(
            "fleet journals federate to %d records (%d shards, %d conflicts); "
            "re-run with --merge-journals to warm-start every worker from them",
            merged.n_records(),
            rep.sources,
            rep.conflicts,
        )
    # show the Stream-K++ dispatch decisions the decode GEMMs triggered
    # (each engine mirrors its traces' selections whether it served under
    # the ambient context or its own selector-scoped one)
    seen = {}
    for _, engine, _, ctx in engines:
        for e in engine.selection_log or ctx.log:
            seen.setdefault((e.tag, e.local_mnk), e.selection)
    log.info("distinct GEMM dispatches: %d", len(seen))
    for (tag, mnk), sel in sorted(seen.items())[:20]:
        log.info(
            "  %-12s M,N,K=%s -> %s/%s g=%d (%s)",
            tag, mnk, sel.policy.name, sel.cfg.name, sel.g, sel.source,
        )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
