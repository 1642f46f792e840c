"""Async request scheduling over paged KV: admission control + chunked
prefill interleaved with decode.

The dense :class:`~repro.serve.engine.ServeEngine` couples three things the
paged engine decouples:

* **capacity** — KV memory is a page pool (``paged_kv``), so how many
  sequences are *resident* is bounded by the sum of their actual lengths,
  not ``n_slots * max_seq``;
* **admission** — ``submit`` is an asynchronous enqueue with queue-depth
  backpressure (:class:`AdmissionError` when the queue is full — callers
  retry later), and the scheduler admits *oldest-first* under a page-budget
  watermark: a request enters only when its whole prompt fits AND a
  configurable reserve stays free for the decode growth of sequences
  already resident. Nothing is ever evicted to make room — admission is the
  only throttle;
* **prefill** — long prompts prefill in chunks of ``prefill_chunk`` tokens,
  at most one chunk per engine step, so a 10k-token prompt contributes one
  bounded unit of work between decode batches instead of head-of-line
  blocking every resident decode for its full prefill latency.

Decode runs at a fixed batch width (``max_active``) over a gathered,
position-contiguous page view (see ``paged_kv``), so the decode GEMM
fingerprints — and therefore tuned dispatch, the adaptive tuner, and the
journal/sieve hot-swap machinery threaded through ``EngineCore`` — are
identical to the dense engine's. Page exhaustion mid-decode *stalls* the
affected sequence (it simply skips steps until a page frees); if every
resident sequence is stalled and no other progress is possible, the oldest
is retired early with ``truncated=True`` rather than deadlocking the loop.
"""

from __future__ import annotations

import math
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.adaptive import AdaptiveTuner
from repro.core.gemm import tagged_kernels
from repro.core.selector import KernelSelector
from repro.serve.engine import EngineCore, Request
from repro.serve.paged_kv import PagedKVCache, PageTable
from repro.utils.logging import get_logger
from repro.utils.timing import span

log = get_logger("serve.paged")


class AdmissionError(RuntimeError):
    """Queue-depth backpressure: the request queue is full; retry later."""


@dataclass
class PagedServeConfig:
    page_size: int = 16
    max_pages: int = 64
    max_active: int = 8  # decode batch width (fixed; padded with scratch rows)
    max_seq: int = 512  # per-sequence logical cap (prompt + decoded tokens)
    max_queue: int = 0  # queued-request cap; 0 = unbounded (no backpressure)
    watermark: float = 0.1  # fraction of the pool reserved at admission time
    prefill_chunk: int = 0  # tokens per prefill tick; 0 = whole-prompt prefill
    eos: int = 0
    seed: int = 0

    @property
    def reserve_pages(self) -> int:
        return math.ceil(self.watermark * self.max_pages)


@dataclass
class PagedRequest(Request):
    """Request + paged lifecycle state + SLO timestamps (``*_wall`` on
    ``time.perf_counter``, the clock of the engine's span counters)."""

    table: PageTable = field(default_factory=PageTable)
    prefilled: int = 0  # prompt tokens already prefilled
    pos: int = 0  # next KV write position (== prompt + decoded so far)
    stalled: bool = False  # waiting on a free page to keep decoding
    submit_step: int = -1
    first_token_step: int = -1
    done_step: int = -1
    submit_wall: float = 0.0
    admit_wall: float = 0.0
    first_token_wall: float = 0.0
    done_wall: float = 0.0


class PagedServeEngine(EngineCore):
    """Continuous batching over a paged KV pool with admission control."""

    def __init__(
        self,
        model,
        params,
        cfg: PagedServeConfig,
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
            batch_hint=cfg.max_active,
            selector=selector,
            backend=backend,
            adaptive=adaptive,
            adapt_every=adapt_every,
        )
        if cfg.max_active < 1:
            raise ValueError(f"max_active must be >= 1, got {cfg.max_active}")
        self.cfg = cfg
        self.kv = PagedKVCache(
            model, page_size=cfg.page_size, n_pages=cfg.max_pages
        )
        self.active: List[PagedRequest] = []  # admission order
        # admission/SLO counters
        self.admitted = 0
        self.rejected = 0  # queue-depth backpressure refusals
        self.truncated = 0  # anti-deadlock early retirements
        self.stall_events = 0  # decode ticks skipped for want of a page
        self.peak_resident = 0
        self.decode_ticks = 0  # decode batches run
        self.decode_rows = 0  # rows that decoded a token, over those batches
        # name each kernel of the jitted decode and chunk steps after its
        # GEMM's tag, for a profile; set before the first step. Off by
        # default: a kernel per tag traces and compiles more at set-up.
        self.tag_kernels = False
        self._decode = jax.jit(self._decode_impl, donate_argnums=(1,))
        self._chunk_step = jax.jit(self._chunk_impl, donate_argnums=(1,))

    # -- jitted paged steps ------------------------------------------------
    def _decode_impl(self, params, pool, pages_2d, tokens, pos):
        """gather view -> unchanged model.decode_step -> scatter the one new
        row per sequence back into its page."""
        view = self.kv.gather_view(pool, pages_2d)
        with tagged_kernels(self.tag_kernels):
            logits, new_view = self.model.decode_step(
                params, view, tokens, pos, div=self.div
            )
        rows = self.kv.rows_at(new_view, pos)
        b = pos.shape[0]
        pg = pages_2d[jnp.arange(b), pos // self.kv.page_size]
        pool = self.kv.scatter_rows(pool, pg, pos % self.kv.page_size, rows)
        return logits, pool

    def _chunk_impl(self, params, pool, pages_2d, chunk, start):
        """One prompt chunk for one sequence (B == 1): gather its pages,
        run model.prefill_chunk, scatter the chunk's rows back."""
        view = self.kv.gather_view(pool, pages_2d)
        with tagged_kernels(self.tag_kernels):
            logits, new_view = self.model.prefill_chunk(
                params, view, chunk, start, div=self.div
            )
        c = chunk.shape[1]
        pos_block = start[0] + jnp.arange(c)  # (C,)
        rows = jax.tree.map(lambda a: a[:, 0, pos_block], new_view)
        pg = pages_2d[0, pos_block // self.kv.page_size]
        pool = self.kv.scatter_rows(
            pool, pg, pos_block % self.kv.page_size, rows
        )
        return logits, pool

    # -- submission --------------------------------------------------------
    def submit(
        self, prompt, max_new_tokens: int = 32, temperature: float = 0.0
    ) -> int:
        """Asynchronous enqueue. Raises :class:`AdmissionError` when the
        queue is at ``max_queue`` (backpressure — the caller retries), and
        ``ValueError`` for prompts that could never be admitted (empty,
        over ``max_seq``, or needing more pages than the pool can ever
        spare past the watermark reserve)."""
        prompt = self._validate_prompt(prompt)
        need = self.kv.pages_for(len(prompt))
        budget = self.cfg.max_pages - self.cfg.reserve_pages
        if need > budget:
            raise ValueError(
                f"prompt needs {need} pages; admissible budget is {budget} "
                f"({self.cfg.max_pages} pages minus {self.cfg.reserve_pages} "
                "watermark reserve)"
            )
        if self.cfg.max_queue and len(self._queue) >= self.cfg.max_queue:
            self.rejected += 1
            raise AdmissionError(
                f"queue full ({len(self._queue)}/{self.cfg.max_queue}); "
                "retry after the engine drains"
            )
        self._uid += 1
        req = PagedRequest(self._uid, prompt, max_new_tokens, temperature)
        req.submit_step = self._steps
        req.submit_wall = time.perf_counter()
        self._queue.append(req)
        return self._uid

    def outstanding(self) -> List[Request]:
        return list(self._queue) + [r for r in self.active if not r.done]

    # -- admission ---------------------------------------------------------
    def _admit(self) -> int:
        """Oldest-first admission under the page watermark: the queue head
        enters only when its whole prompt's pages fit with the reserve left
        over. No skipping ahead (a younger short request must not starve an
        older long one) and no eviction."""
        n = 0
        with span("engine.admit", self.counters) as sp:
            while self._queue and len(self.active) < self.cfg.max_active:
                head = self._queue[0]
                need = self.kv.pages_for(len(head.prompt))
                if self.kv.free_pages - need < self.cfg.reserve_pages:
                    break
                self._queue.pop(0)
                head.table = PageTable(self.kv.alloc(need), 0)
                head.admit_wall = time.perf_counter()
                self.active.append(head)
                self.admitted += 1
                n += 1
            self.peak_resident = max(self.peak_resident, len(self.active))
            sp.annotate(admitted=n)
        return n

    # -- prefill -----------------------------------------------------------
    def _pending_prefill(self) -> Optional[PagedRequest]:
        for r in self.active:
            if r.prefilled < len(r.prompt):
                return r
        return None

    def _prefill_tick(self) -> bool:
        """Advance the oldest prefilling request by one chunk (or its whole
        prompt when ``prefill_chunk`` is 0). Returns True if work ran."""
        req = self._pending_prefill()
        if req is None:
            return False
        remaining = len(req.prompt) - req.prefilled
        chunk = remaining
        if self.cfg.prefill_chunk > 0:
            chunk = min(self.cfg.prefill_chunk, remaining)
        start = req.prefilled
        tokens = jnp.asarray(req.prompt[start : start + chunk])[None, :]
        with self._dispatch_ctx():
            if self.cfg.prefill_chunk == 0:
                # the whole prompt: the dense engine's model.prefill call and
                # numerics, outside jit at its own pages' length, scattered
                # into this sequence's pages
                n_pages = self.kv.pages_for(chunk)
                with span("engine.prefill.first", self.counters, uid=req.uid, tokens=chunk):
                    logits, fresh = self.model.prefill(
                        self.params,
                        tokens,
                        max_seq=n_pages * self.kv.page_size,
                        div=self.div,
                    )
                    self.kv.pool = self.kv.scatter_prefill(
                        self.kv.pool,
                        jnp.asarray(req.table.pages[:n_pages], jnp.int32),
                        fresh,
                    )
            else:
                # every chunk, the first at start 0, through the jitted
                # chunk step: prefill_chunk masks each key past the query's
                # position, so the pages not written yet are never read
                if start == 0:
                    sp = span("engine.prefill.first", self.counters, uid=req.uid, tokens=chunk)
                else:
                    sp = span("engine.prefill.chunk", self.counters, uid=req.uid, start=start, size=chunk)
                with sp:
                    logits, self.kv.pool = self._chunk_step(
                        self.params,
                        self.kv.pool,
                        self.kv.padded_tables([req.table]),
                        tokens,
                        jnp.asarray([start], jnp.int32),
                    )
        req.prefilled += chunk
        req.table.length = req.prefilled
        if req.prefilled < len(req.prompt):
            return True
        # prompt complete: sample the first token (same contract as the
        # dense engine's _prefill_slot)
        with span("engine.sample", self.counters, rows=1):
            req.pos = len(req.prompt)
            tok = self._sample(np.asarray(logits)[0, -1], req.temperature)
            req.out_tokens.append(int(tok))
            req.first_token_step = self._steps
            req.first_token_wall = time.perf_counter()
            full = req.pos >= self.cfg.max_seq
            if (
                tok == self.cfg.eos
                or len(req.out_tokens) >= req.max_new_tokens
                or full
            ):
                self._retire(req)
        return True

    # -- decode ------------------------------------------------------------
    def _decode_candidates(self) -> List[PagedRequest]:
        return [
            r
            for r in self.active
            if not r.done and r.prefilled == len(r.prompt)
        ]

    def _ensure_page(self, req: PagedRequest) -> bool:
        """Guarantee ``req.pos`` has a page to write to; stall on exhaustion."""
        if req.pos < req.table.capacity * self.kv.page_size:
            req.stalled = False
            return True
        got = self.kv.try_alloc(1)
        if got is None:
            if not req.stalled:
                self.stall_events += 1
            req.stalled = True
            return False
        req.table.pages.extend(got)
        req.stalled = False
        return True

    def _decode_tick(self) -> bool:
        with span("engine.decode.prepare", self.counters) as sp:
            cand = self._decode_candidates()
            runnable = [r for r in cand if self._ensure_page(r)]
            if not runnable:
                return False
            b = self.cfg.max_active
            runnable = runnable[:b]
            sp.annotate(rows=len(runnable))
            tokens = np.zeros((b, 1), np.int32)
            pos = np.zeros((b,), np.int32)
            tables = []
            for i, r in enumerate(runnable):
                tokens[i, 0] = r.out_tokens[-1]
                pos[i] = r.pos
                tables.append(r.table)
            # pad the batch to the fixed decode width with scratch-page rows
            tables.extend(PageTable() for _ in range(b - len(runnable)))
            pages_2d = self.kv.padded_tables(tables)
            tokens, pos = jnp.asarray(tokens), jnp.asarray(pos)
        with span("engine.decode.dispatch", self.counters), self._dispatch_ctx():
            logits, self.kv.pool = self._decode(
                self.params, self.kv.pool, pages_2d, tokens, pos
            )
        with span("engine.decode.wait", self.counters):
            logits_np = np.asarray(logits)[:, 0]
        self.decode_ticks += 1
        self.decode_rows += len(runnable)
        with span("engine.sample", self.counters, rows=len(runnable)):
            for i, req in enumerate(runnable):
                req.pos += 1
                req.table.length = req.pos
                tok = self._sample(logits_np[i], req.temperature)
                req.out_tokens.append(tok)
                if (
                    tok == self.cfg.eos
                    or len(req.out_tokens) >= req.max_new_tokens
                    or req.pos >= self.cfg.max_seq
                ):
                    self._retire(req)
        return True

    def _retire(self, req: PagedRequest, truncated: bool = False):
        req.done = True
        req.truncated = truncated
        req.done_step = self._steps
        req.done_wall = time.perf_counter()
        if truncated and req.first_token_wall == 0.0:
            req.first_token_step = self._steps
            req.first_token_wall = req.done_wall
        self.kv.free(req.table.pages)
        req.table = PageTable()
        self.active.remove(req)

    # -- one scheduling quantum --------------------------------------------
    def step(self) -> bool:
        with span("engine.step", self.counters, step_num=self._steps):
            return self._step()

    def _step(self) -> bool:
        progress = 0
        if self.cfg.prefill_chunk > 0:
            # chunked mode: ONE bounded prefill quantum per step — long
            # prompts interleave with the decode batch below
            progress += self._admit()
            progress += int(self._prefill_tick())
        else:
            # whole-prompt mode: admit/prefill until the pool or the queue
            # is exhausted (retire-at-prefill frees pages mid-loop, exactly
            # like the dense engine's _admit slot reuse)
            while True:
                a = self._admit()
                w = int(self._prefill_tick())
                progress += a + w
                if not (a or w):
                    break
        decoded = int(self._decode_tick())
        progress += decoded
        if not progress:
            if self.active:
                # every resident sequence is stalled on page exhaustion and
                # nothing else can move: retire the oldest (truncated) so
                # its pages unblock the rest — never deadlock the loop
                victim = self.active[0]
                log.warning(
                    "page pool gridlock (%d resident, 0 free of %d pages): "
                    "truncating request %d at %d tokens",
                    len(self.active),
                    self.kv.n_pages,
                    victim.uid,
                    len(victim.out_tokens),
                )
                self.truncated += 1
                self._retire(victim, truncated=True)
                self._maybe_adapt()
                return True
            return False  # drained (submit() rejects never-admissible work)
        self._maybe_adapt()
        return True

    # -- observability -----------------------------------------------------
    def metrics(self) -> Dict[str, float]:
        """Pool occupancy, admission counters, decode batches, and per span
        name its count, host seconds (``.s``), compiles, compile-cache loads
        and their seconds."""
        occ = self.kv.occupancy()
        occ.update(
            admitted=self.admitted,
            rejected=self.rejected,
            truncated=self.truncated,
            stall_events=self.stall_events,
            peak_resident=self.peak_resident,
            resident=len(self.active),
            queued=len(self._queue),
            steps=self._steps,
            decode_ticks=self.decode_ticks,
            decode_rows=self.decode_rows,
        )
        for name, st in self.counters.items():
            occ.update({
                f"{name}.count": st.count,
                f"{name}.s": st.seconds,
                f"{name}.compiles": st.compiles,
                f"{name}.cache_loads": st.cache_loads,
                f"{name}.cache_load_s": st.cache_load_s,
            })
        return occ
