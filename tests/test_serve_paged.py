"""Paged serving engine: token identity with the dense engine, page
exhaustion / stall / gridlock behavior, admission control, chunked prefill
(including across an adaptation round), run() exhaustion accounting, and the
per-array-aware serve divisor table."""

import jax
import numpy as np
import pytest

from conftest import tiny
from repro.core.adaptive import AdaptiveConfig, AdaptiveTuner
from repro.core.selector import KernelSelector
from repro.core.tuner import TuningDatabase
from repro.dist.sharding import materialize_tree
from repro.models import build_model
from repro.serve import (
    AdmissionError,
    PagedServeConfig,
    PagedServeEngine,
    ServeConfig,
    ServeEngine,
    serve_gemm_div,
)


@pytest.fixture(scope="module")
def served():
    cfg = tiny("granite-8b")
    model = build_model(cfg)
    params = materialize_tree(model.param_specs(), jax.random.PRNGKey(0))
    return cfg, model, params


def mixed_prompts(cfg, n=6, lo=4, hi=13, seed=0):
    rng = np.random.default_rng(seed)
    return [
        rng.integers(1, cfg.vocab_size, size=int(rng.integers(lo, hi))).astype(
            np.int32
        )
        for _ in range(n)
    ]


# -- token identity ----------------------------------------------------------


def run_dense(model, params, prompts, max_new=6, n_slots=4, max_seq=64):
    eng = ServeEngine(
        model, params, ServeConfig(n_slots=n_slots, max_seq=max_seq, eos=-1)
    )
    uids = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
    done = eng.run()
    return {u: r.out_tokens for u, r in zip(uids, sorted(done, key=lambda r: r.uid))}


def run_paged(model, params, prompts, max_new=6, max_seq=64, **over):
    cfg = PagedServeConfig(
        page_size=8,
        max_pages=32,
        max_active=4,
        max_seq=max_seq,
        eos=-1,
        **over,
    )
    eng = PagedServeEngine(model, params, cfg)
    uids = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
    done = eng.run()
    return (
        {u: r.out_tokens for u, r in zip(uids, sorted(done, key=lambda r: r.uid))},
        eng,
    )


def test_paged_tokens_identical_to_dense(served):
    """Greedy decode through the page pool must be bit-identical to the
    dense slot engine: same prefill numerics (whole-prompt fast path), same
    fixed decode batch width, garbage page tails masked to exact zeros."""
    cfg, model, params = served
    prompts = mixed_prompts(cfg)
    dense = run_dense(model, params, prompts)
    paged, eng = run_paged(model, params, prompts)
    assert paged == dense
    assert eng.kv.used_pages == 0  # every retirement returned its pages


@pytest.mark.parametrize("chunk", [5, 32], ids=["straddling", "whole-prompt"])
def test_chunked_prefill_tokens_identical_to_dense(served, chunk):
    """Chunked prefill must produce the same first token and decode chain:
    with a chunk that straddles page boundaries over prompts not
    chunk-aligned, and with a chunk longer than every prompt, where the
    jitted chunk step at start 0 alone gives the first token."""
    cfg, model, params = served
    prompts = mixed_prompts(cfg, n=4, lo=11, hi=21, seed=3)
    assert chunk == 5 or chunk >= max(map(len, prompts))
    dense = run_dense(model, params, prompts)
    paged, eng = run_paged(model, params, prompts, prefill_chunk=chunk)
    assert paged == dense


def test_page_exhaustion_mid_decode_stalls_then_recovers(served):
    """A sequence that outgrows its pages while the pool is empty must
    stall (skip decode ticks) and resume once a retirement frees a page —
    completing untruncated with its full token budget."""
    cfg, model, params = served
    eng = PagedServeEngine(
        model,
        params,
        PagedServeConfig(
            page_size=4,
            max_pages=2,
            max_active=2,
            max_seq=12,
            watermark=0.0,
            eos=-1,
        ),
    )
    short = eng.submit(np.array([3, 1], np.int32), max_new_tokens=3)
    grower = eng.submit(np.array([2, 7, 5], np.int32), max_new_tokens=6)
    done = {r.uid: r for r in eng.run()}
    assert set(done) == {short, grower}
    assert not done[grower].truncated and not done[short].truncated
    assert len(done[grower].out_tokens) == 6  # full budget despite the stall
    assert eng.stall_events >= 1
    assert eng.truncated == 0
    assert eng.kv.free_pages == eng.kv.n_pages


def test_gridlock_truncates_oldest_instead_of_deadlocking(served):
    """When every resident sequence is stalled and nothing can be admitted,
    the engine must retire the oldest with truncated=True (freeing its
    pages for the rest) rather than spin forever."""
    cfg, model, params = served
    eng = PagedServeEngine(
        model,
        params,
        PagedServeConfig(
            page_size=4,
            max_pages=2,
            max_active=2,
            max_seq=16,
            watermark=0.0,
            eos=-1,
        ),
    )
    uids = [
        eng.submit(np.arange(1, 5, dtype=np.int32), max_new_tokens=12)
        for _ in range(2)
    ]
    done = {r.uid: r for r in eng.run(max_steps=200)}
    assert set(done) == set(uids)  # drained: nothing silently dropped
    assert not eng.exhausted
    assert eng.truncated >= 1
    assert done[uids[0]].truncated  # the oldest was the victim
    for r in done.values():
        assert len(r.out_tokens) >= 1  # partial output survives truncation


def test_admission_rejection_then_retry_succeeds(served):
    """Queue-depth backpressure: a full queue raises AdmissionError (counted
    in rejected), and the same request submits cleanly once the scheduler
    drains the queue — no eviction, no lost work."""
    cfg, model, params = served
    eng = PagedServeEngine(
        model,
        params,
        PagedServeConfig(
            page_size=8, max_pages=16, max_active=2, max_seq=32,
            max_queue=1, eos=-1,
        ),
    )
    prompt = np.array([1, 2, 3], np.int32)
    eng.submit(prompt, max_new_tokens=3)
    with pytest.raises(AdmissionError):
        eng.submit(prompt, max_new_tokens=3)
    assert eng.rejected == 1
    eng.step()  # the scheduler admits the queue head, freeing queue depth
    retry = eng.submit(prompt, max_new_tokens=3)  # succeeds now
    done = eng.run()
    assert retry in {r.uid for r in done}
    assert all(len(r.out_tokens) == 3 for r in done)


def test_never_admissible_prompt_rejected_at_submit(served):
    """A prompt needing more pages than the pool can ever spare past the
    watermark reserve is a caller error, not backpressure."""
    cfg, model, params = served
    eng = PagedServeEngine(
        model,
        params,
        PagedServeConfig(page_size=4, max_pages=4, max_seq=64, eos=-1),
    )
    with pytest.raises(ValueError, match="watermark reserve"):
        eng.submit(np.arange(1, 17, dtype=np.int32))  # 16 tokens = 4 pages
    assert eng.rejected == 0  # ValueError is not the backpressure counter


def test_empty_prompt_rejected_by_both_engines(served):
    cfg, model, params = served
    dense = ServeEngine(model, params, ServeConfig(n_slots=1, max_seq=16, eos=-1))
    paged = PagedServeEngine(
        model, params, PagedServeConfig(page_size=4, max_pages=4, max_seq=16)
    )
    for eng in (dense, paged):
        with pytest.raises(ValueError, match="empty"):
            eng.submit(np.array([], np.int32))
        with pytest.raises(ValueError, match="empty"):
            eng.submit([])


def test_run_exhaustion_flags_unfinished_both_engines(served):
    """run(max_steps) running out of budget must not silently drop work:
    the remainder stays resident, engine.exhausted is set, and a follow-up
    run() finishes exactly the flagged requests."""
    cfg, model, params = served
    dense = ServeEngine(model, params, ServeConfig(n_slots=1, max_seq=32, eos=-1))
    paged = PagedServeEngine(
        model,
        params,
        PagedServeConfig(page_size=8, max_pages=8, max_active=1, max_seq=32),
    )
    for eng in (dense, paged):
        uids = [
            eng.submit(np.array([1, 2, 3], np.int32), max_new_tokens=8)
            for _ in range(3)
        ]
        first = eng.run(max_steps=2)
        assert eng.exhausted
        left = {r.uid for r in eng.unfinished}
        assert left and left <= set(uids)
        assert {r.uid for r in first} | left == set(uids)
        rest = eng.run()
        assert not eng.exhausted and eng.unfinished == []
        assert {r.uid for r in rest} >= left


def test_chunked_prefill_spans_adaptation_round(served):
    """A prompt whose chunked prefill straddles an AdaptiveTuner adaptation
    round must decode to the same tokens as the dense engine: adaptation
    swaps dispatch tables between steps, never numerics."""
    cfg, model, params = served
    prompts = mixed_prompts(cfg, n=2, lo=13, hi=17, seed=5)
    dense = run_dense(model, params, prompts, max_new=4)

    db = TuningDatabase()
    sel = KernelSelector(sieve=db.build_sieve(), db=db)
    adaptive = AdaptiveTuner(
        sel, config=AdaptiveConfig(hot_threshold=1, rebuild_every=1)
    )
    eng = PagedServeEngine(
        model,
        params,
        PagedServeConfig(
            page_size=8, max_pages=16, max_active=4, max_seq=64,
            prefill_chunk=4, eos=-1,
        ),
        adaptive=adaptive,
        adapt_every=1,  # adapt between every chunk/decode quantum
    )
    uids = [eng.submit(p, max_new_tokens=4) for p in prompts]
    done = {u: r.out_tokens for u, r in zip(uids, sorted(eng.run(), key=lambda r: r.uid))}
    assert done == dense
    assert adaptive.stats.adaptations > 0  # rounds actually fired mid-prefill
    assert eng.dispatch_stats.db_records > 0


# -- per-array-aware serve divisors (ROADMAP item 6) -------------------------


def test_serve_gemm_div_no_plan_is_empty(served):
    cfg, model, params = served
    assert serve_gemm_div(model) == {}


@pytest.mark.skipif(
    jax.device_count() < 2, reason="needs a >=2-device mesh (multi-device lane)"
)
def test_serve_gemm_div_demotes_indivisible_weight_dims():
    """On a model=N mesh, a weight dim the sharding solver demotes to
    replication must demote the serve table's model divisor to 1 — the
    fingerprints must describe the local shapes the kernels execute."""
    from repro.dist.sharding import ShardingPlan, use_plan
    from repro.launch.mesh import make_host_mesh

    tp = 2
    mesh = make_host_mesh(model=tp)
    plan = ShardingPlan(mesh)
    clean = build_model(tiny("granite-8b"))
    with use_plan(plan):
        div = serve_gemm_div(clean)
        assert div["model"] == tp  # every tensor-parallel dim divides

        # an odd vocab cannot split over the model axis: spec_for demotes
        # the lm_head/vocab dim, so the serve table must drop to 1
        odd = build_model(tiny("granite-8b", vocab_size=2049))
        assert plan.demoted_dims(odd.param_specs(), mesh_axis="model")
        assert serve_gemm_div(odd)["model"] == 1

        # a decode width indivisible by the batch factor demotes "batch"
        dp = plan.gemm_div()["batch"]
        if dp > 1:
            assert serve_gemm_div(clean, batch=dp + 1)["batch"] == 1
            assert serve_gemm_div(clean, batch=2 * dp)["batch"] == dp
