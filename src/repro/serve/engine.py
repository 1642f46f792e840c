"""Batched serving engine with slot-based continuous batching.

The engine holds a fixed pool of ``n_slots`` sequences sharing one stacked
KV cache (the shape the decode_32k / long_500k dry-run cells lower). New
requests are admitted into free slots between decode steps — continuous
batching — so the decode GEMMs stay at a steady M = n_slots, exactly the
skinny-M regime where the paper's Stream-K++ policies matter most (the
dispatch log in ``repro.core.gemm`` records every selection the engine
triggers).

Decode is greedy or temperature sampling; finished sequences (EOS or length)
free their slot. Per-slot position counters make the shared cache correct
for requests of different lengths.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from contextlib import contextmanager

from repro.core.adaptive import AdaptiveTuner
from repro.core.gemm import current_log, current_selector, gemm_context
from repro.core.selector import KernelSelector, SelectorStats
from repro.dist.sharding import current_plan
from repro.utils.logging import get_logger
from repro.utils.timing import SpanStats, span

log = get_logger("serve")


def serve_gemm_div(model, batch: Optional[int] = None) -> Dict[str, int]:
    """Per-array-aware ambient GEMM divisor table for the serve path.

    ``ShardingPlan.gemm_div`` is mesh-level: it cannot see the per-array
    divisibility demotion ``spec_for`` applies (an odd vocab on a model=4
    mesh executes replicated while the mesh table still claims the split).
    The engine call site is where both halves are known — the installed
    plan AND the concrete model whose weights it will shard — so this probes
    every parameter spec through the plan's own solver
    (:meth:`ShardingPlan.demoted_dims`) and demotes the table's ``model``
    entry to 1 when any tensor-parallel weight dim would be demoted to
    replication. Likewise ``batch`` is demoted when the engine's decode
    width is not divisible by the data-parallel factor. The result: dispatch
    fingerprints never claim a local shape the arrays don't execute, in
    either regime — the resolution of ROADMAP item 6 for serving.
    """
    plan = current_plan()
    if plan is None:
        return {}
    div = dict(plan.gemm_div())
    tp = div.get("model", 1)
    if tp > 1:
        offenders = plan.demoted_dims(model.param_specs(), mesh_axis="model")
        if offenders:
            shown = ", ".join(
                f"dim {d} ({ax or '?'}) of {sh}" for sh, ax, _, d in offenders[:3]
            )
            log.warning(
                "serve fingerprints demote model divisor %d -> 1: %d weight "
                "dim(s) fail the plan's divisibility solver and execute "
                "replicated (e.g. %s); a mesh-level divisor would fingerprint "
                "local shapes the kernels never see",
                tp,
                len(offenders),
                shown,
            )
            div["model"] = 1
    db = div.get("batch", 1)
    if batch is not None and db > 1 and batch % db:
        log.warning(
            "serve fingerprints demote batch divisor %d -> 1: decode width "
            "%d is not divisible, so decode activations execute replicated",
            db,
            batch,
        )
        div["batch"] = 1
    return div


@dataclass(frozen=True)
class DispatchStats:
    """Point-in-time view of the engine's dispatch health: the selector's
    counters plus the online-adaptation loop's. Selector fields
    (``tuned_hits``, ``lookups``, ...) are reachable directly via attribute
    delegation."""

    selector: SelectorStats
    misses: int  # untuned dispatches observed (adaptive) or cold non-DB hits
    adaptations: int  # tuning records committed online
    sieve_generation: int  # build version of the live sieve
    db_records: int  # tuning database size
    pending_hot: int  # promoted fingerprints awaiting an adaptation round
    #: unseen fingerprints served from the calibrated model's argmin (the
    #: "model" selection source) — analytical warm starts, still counted as
    #: misses by the adaptive loop so hot ones get measured and promoted
    model_warm: int = 0
    #: dispatches seeded from a foreign arch class's record (the "xarch"
    #: selection source) — re-ranked warm starts, still adaptive misses so
    #: local measurements supersede the import
    xarch_seeds: int = 0

    def __getattr__(self, name):
        return getattr(self.selector, name)


@dataclass
class Request:
    uid: int
    prompt: np.ndarray  # (P,) int32
    max_new_tokens: int = 32
    temperature: float = 0.0
    out_tokens: List[int] = field(default_factory=list)
    done: bool = False
    truncated: bool = False  # retired early (e.g. paged-pool anti-deadlock)


@dataclass
class ServeConfig:
    n_slots: int = 8
    max_seq: int = 512
    eos: int = 0
    seed: int = 0


class EngineCore:
    """Shared substrate of the serving engines: dispatch-context threading
    (selector/backend scoping + selection-log mirroring), adaptive-tuner
    hooks, sampling, request admission validation, and the run() drain loop
    with exhaustion accounting. Subclasses implement :meth:`step` (one
    scheduling quantum) and :meth:`outstanding` (requests still queued or
    resident)."""

    def __init__(
        self,
        model,
        params,
        *,
        max_seq: int,
        seed: int = 0,
        div=None,
        batch_hint: Optional[int] = None,
        selector: Optional[KernelSelector] = None,
        backend: Optional[str] = None,
        adaptive: Optional[AdaptiveTuner] = None,
        adapt_every: int = 0,
    ):
        self.model = model
        self.params = params
        # Mesh-aware dispatch fingerprints: when the caller installed a
        # ShardingPlan (dist.sharding.use_plan) but passed no explicit div,
        # derive the per-shard GEMM divisors from the plan — every decode
        # GEMM then fingerprints the *local* per-device MNK, so tuning
        # records federate across identically-sharded serving processes.
        # serve_gemm_div additionally demotes the table's tensor-parallel
        # divisor when any serve-path weight dim would be demoted to
        # replication by the plan's own solver (per-array divisibility),
        # so fingerprints never claim a split the arrays don't execute.
        self.div = div if div is not None else serve_gemm_div(model, batch_hint)
        # Online adaptation: an AdaptiveTuner rides the decode loop — every
        # ``adapt_every`` engine steps it gets one budgeted round to tune the
        # hottest untuned fingerprints the serving traffic produced. The
        # tuner is bound to a selector; if the caller did not pass one
        # explicitly, the engine serves through the tuner's.
        if adaptive is not None and selector is None:
            selector = adaptive.selector
        self.adaptive = adaptive
        self.adapt_every = adapt_every
        # host seconds, counts and compiles of each span name the engine
        # opens (see ``repro.utils.timing.span``)
        self.counters: Dict[str, SpanStats] = {}
        self._steps = 0
        self._max_seq = max_seq
        # Dispatch threading: when the caller hands the engine a selector
        # and/or backend, every prefill/decode trace runs under that
        # dedicated context; otherwise traces use the ambient context (so
        # wrapping the engine in ``gemm_context`` keeps working). Either
        # way the selections the engine triggers mirror into
        # ``selection_log`` for serving-side introspection.
        self.selector = selector
        self.backend = backend
        self.selection_log: List = []
        self.rng = np.random.default_rng(seed)
        self._queue: List[Request] = []
        self._uid = 0
        # run()-exhaustion accounting: requests still queued or resident
        # when the step budget ran out (None until the first run())
        self.unfinished: List[Request] = []
        self.exhausted: bool = False

    @contextmanager
    def _dispatch_ctx(self):
        if self.selector is not None or self.backend is not None:
            with gemm_context(selector=self.selector, backend=self.backend) as ctx:
                # backend-only construction inherits the ambient selector;
                # remember it so dispatch_stats reads the one that served
                self._ambient_selector = ctx.selector
                start = len(ctx.log)
                try:
                    yield
                finally:
                    # a failing trace still recorded selections before it
                    # raised — keep them observable
                    self.selection_log.extend(ctx.log[start:])
        else:
            # remember which ambient selector served this traffic, so
            # dispatch_stats reads the right counters even after the
            # caller's gemm_context has exited
            self._ambient_selector = current_selector()
            amb_log = current_log()
            start = len(amb_log)
            try:
                yield
            finally:
                self.selection_log.extend(amb_log[start:])

    @property
    def dispatch_stats(self) -> DispatchStats:
        sel = self.selector
        if sel is None:
            sel = getattr(self, "_ambient_selector", None) or current_selector()
        ad = self.adaptive
        if ad is not None:
            misses = ad.stats.misses
            adaptations = ad.stats.adaptations
            pending = ad.pending_hot
            db_records = len(ad.db.records)
        else:
            # without an adaptive loop, "miss" degrades to the cold
            # non-database selections the selector itself counted
            misses = (
                sel.stats.sieve_hits
                + sel.stats.model_warm
                + sel.stats.xarch_seeds
                + sel.stats.fallbacks
            )
            adaptations = 0
            pending = 0
            db_records = len(sel.db.records) if sel.db is not None else 0
        return DispatchStats(
            selector=sel.stats,
            misses=misses,
            adaptations=adaptations,
            sieve_generation=sel.sieve_generation,
            db_records=db_records,
            pending_hot=pending,
            model_warm=sel.stats.model_warm,
            xarch_seeds=sel.stats.xarch_seeds,
        )

    def _sample(self, logits: np.ndarray, temperature: float) -> int:
        if temperature <= 0.0:
            return int(np.argmax(logits))
        p = np.exp((logits - logits.max()) / temperature)
        p /= p.sum()
        return int(self.rng.choice(len(p), p=p))

    def _validate_prompt(self, prompt) -> np.ndarray:
        prompt = np.asarray(prompt, np.int32)
        if len(prompt) == 0:
            # an empty prefill would scatter a meaningless KV row and
            # sample from garbage logits — refuse it at the front door
            raise ValueError("empty prompt (0 tokens) cannot be served")
        if len(prompt) > self._max_seq:
            raise ValueError(
                f"prompt length {len(prompt)} exceeds max_seq {self._max_seq}"
            )
        return prompt

    def _maybe_adapt(self):
        self._steps += 1
        if (
            self.adaptive is not None
            and self.adapt_every > 0
            and self._steps % self.adapt_every == 0
        ):
            with span("engine.adapt", self.counters):
                self.adaptive.adapt()

    # -- drain loop --------------------------------------------------------
    def step(self) -> bool:  # pragma: no cover - abstract
        raise NotImplementedError

    def outstanding(self) -> List[Request]:  # pragma: no cover - abstract
        raise NotImplementedError

    def run(self, max_steps: int = 10_000) -> List[Request]:
        """Drain queue + resident requests; returns finished requests.

        When ``max_steps`` runs out first, the unserved remainder is NOT
        silently dropped: it stays queued/resident on the engine, and is
        additionally flagged on ``self.exhausted`` / listed in
        ``self.unfinished`` so callers can distinguish "drained" from
        "budget ran out" without diffing uid sets."""
        finished: List[Request] = []
        seen: Dict[int, Request] = {}
        for _ in range(max_steps):
            for r in list(self._queue):
                seen[r.uid] = r
            for r in self.outstanding():
                seen[r.uid] = r
            if not self.step():
                break
        if self.adaptive is not None and self.adapt_every > 0:
            # end-of-run flush: short traces must still commit what they
            # learned (and journal it) before the process goes away
            self.adaptive.drain()
        for r in seen.values():
            if r.done:
                finished.append(r)
        self.unfinished = self.outstanding()
        self.exhausted = bool(self.unfinished)
        if self.exhausted:
            log.warning(
                "run(max_steps=%d) exhausted with %d request(s) still "
                "queued/active; they remain resident (see engine.unfinished)",
                max_steps,
                len(self.unfinished),
            )
        return finished


class ServeEngine(EngineCore):
    """Dense slot engine: ``n_slots`` sequences share one stacked KV cache
    out to ``max_seq`` (see module doc)."""

    def __init__(
        self,
        model,
        params,
        cfg: ServeConfig,
        *,
        div=None,
        selector: Optional[KernelSelector] = None,
        backend: Optional[str] = None,
        adaptive: Optional[AdaptiveTuner] = None,
        adapt_every: int = 0,
    ):
        super().__init__(
            model,
            params,
            max_seq=cfg.max_seq,
            seed=cfg.seed,
            div=div,
            batch_hint=cfg.n_slots,
            selector=selector,
            backend=backend,
            adaptive=adaptive,
            adapt_every=adapt_every,
        )
        self.cfg = cfg
        self.cache = model.init_cache(cfg.n_slots, cfg.max_seq)
        self.pos = np.zeros((cfg.n_slots,), np.int32)  # next write position
        self.slot_req: List[Optional[Request]] = [None] * cfg.n_slots
        self._decode = jax.jit(
            lambda p, c, t, pos: model.decode_step(p, c, t, pos, div=self.div),
            donate_argnums=(1,),
        )

    # -- request admission -------------------------------------------------
    def submit(self, prompt, max_new_tokens: int = 32, temperature: float = 0.0) -> int:
        prompt = self._validate_prompt(prompt)
        self._uid += 1
        self._queue.append(
            Request(self._uid, prompt, max_new_tokens, temperature)
        )
        return self._uid

    def outstanding(self) -> List[Request]:
        return list(self._queue) + [r for r in self.slot_req if r is not None]

    def _admit(self):
        for slot in range(self.cfg.n_slots):
            # a request can finish AT prefill (EOS / max_new_tokens == 1 /
            # prompt exactly fills the cache) and free its slot immediately;
            # keep admitting into the same slot so a run() whose every
            # request prefill-finishes still drains the queue instead of
            # abandoning it (step() would otherwise see no active slots)
            while self.slot_req[slot] is None and self._queue:
                req = self._queue.pop(0)
                self._prefill_slot(slot, req)
            if not self._queue:
                break

    def _prefill_slot(self, slot: int, req: Request):
        """Prefill one slot. Single-sequence prefill then scatter its cache
        into the shared pool at the slot index. Prompts longer than the
        cache are rejected here too (defense in depth for direct callers —
        ``submit`` already refuses them): prefilling one would silently
        scatter KV entries out of bounds."""
        if len(req.prompt) > self.cfg.max_seq:
            raise ValueError(
                f"prompt length {len(req.prompt)} exceeds max_seq "
                f"{self.cfg.max_seq}; cannot prefill without scattering out "
                "of bounds"
            )
        prompt = jnp.asarray(req.prompt)[None, :]
        with self._dispatch_ctx():
            logits, cache1 = self.model.prefill(
                self.params, prompt, max_seq=self.cfg.max_seq, div=self.div
            )

        def place(pool, fresh):
            return jax.lax.dynamic_update_index_in_dim(pool, fresh[:, 0], slot, 1)

        self.cache = jax.tree.map(place, self.cache, cache1)
        self.pos[slot] = len(req.prompt)
        self.slot_req[slot] = req
        tok = self._sample(np.asarray(logits)[0, -1], req.temperature)
        req.out_tokens.append(int(tok))
        # the prefill-sampled token can already terminate the request; a
        # prompt that exactly fills the cache leaves no decode room, so it
        # finishes with the one prefill-sampled token
        full = self.pos[slot] >= self.cfg.max_seq
        if tok == self.cfg.eos or len(req.out_tokens) >= req.max_new_tokens or full:
            req.done = True
            self.slot_req[slot] = None
            self.pos[slot] = 0

    # -- decode loop ---------------------------------------------------------
    def step(self):
        """One decode step for every active slot."""
        self._admit()
        active = [i for i, r in enumerate(self.slot_req) if r is not None]
        if not active:
            return False
        tokens = np.zeros((self.cfg.n_slots, 1), np.int32)
        for i in active:
            tokens[i, 0] = self.slot_req[i].out_tokens[-1]
        cur_pos = jnp.asarray(self.pos)
        with self._dispatch_ctx():
            logits, self.cache = self._decode(
                self.params, self.cache, jnp.asarray(tokens), cur_pos
            )
        logits_np = np.asarray(logits)[:, 0]
        for i in active:
            req = self.slot_req[i]
            self.pos[i] += 1
            tok = self._sample(logits_np[i], req.temperature)
            req.out_tokens.append(tok)
            length_done = len(req.out_tokens) >= req.max_new_tokens
            eos_done = tok == self.cfg.eos
            # the cache is full when the *next* write position is out of
            # bounds; pos was already advanced above, so compare pos itself
            # (pos + 1 retired slots one usable token early)
            full = self.pos[i] >= self.cfg.max_seq
            if length_done or eos_done or full:
                req.done = True
                self.slot_req[i] = None
                self.pos[i] = 0
        self._maybe_adapt()
        return True
