"""The program's spans and counters: the span helper (counts, host seconds,
nesting, compiles charged to the innermost counted span), the serving
engine's spans through ``engine.metrics()``, the request stamps on the span
counters' clock, and kernel names: the GEMM's tag in front of the tile in
the engine's jitted steps when asked, one kernel per shape and tile
otherwise."""

import importlib
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import tiny
from repro.core.adaptive import AdaptiveConfig, AdaptiveTuner
from repro.core.policies import ALL_SK, DP, HYBRIDS, TileConfig
from repro.core.selector import KernelSelector, SelectorState
from repro.core.tuner import TuningDatabase
from repro.dist.sharding import materialize_tree
from repro.models import build_model
from repro.serve import PagedServeConfig, PagedServeEngine
from repro.utils import timing
from repro.utils.timing import SpanStats, span

gemm_mod = importlib.import_module("repro.core.gemm")

# -- the span helper -----------------------------------------------------------


def test_span_counts_host_seconds_and_nests():
    counters = {}
    with span("outer", counters, step_num=3) as outer:
        with span("inner", counters, rows=2) as inner:
            time.sleep(0.002)
        with span("inner", counters):
            pass
        with span("uncounted", None) as bare:
            pass
    assert set(counters) == {"outer", "inner"}
    assert counters["outer"].count == 1 and counters["inner"].count == 2
    assert counters["inner"].seconds >= inner.seconds >= 0.002
    assert counters["outer"].seconds == outer.seconds >= counters["inner"].seconds
    assert bare.seconds >= 0.0
    assert timing._stack() == []  # every span closed


def test_span_closes_on_error():
    counters = {}
    with pytest.raises(ValueError):
        with span("failing", counters):
            raise ValueError("boom")
    assert counters["failing"].count == 1
    assert timing._stack() == []


def test_compiles_are_charged_to_the_innermost_counted_span():
    counters = {}
    x = jnp.arange(7.0)
    with span("outer", counters):
        with span("compiling", counters):
            # an uncounted span passes its compiles to the one around it
            with span("gemm.select", None):
                jax.jit(lambda v: v * 3.0 + 1.0)(x).block_until_ready()
    assert counters["compiling"].compile_requests >= 1
    assert counters["outer"].compile_requests == 0


def test_cache_loads_count_against_compile_requests():
    counters = {}
    with span("loading", counters):
        timing._on_event("/jax/core/compile/backend_compile_duration", 0.5)
        timing._on_event("/jax/compilation_cache/cache_retrieval_time_sec", 0.25)
        timing._on_event("/jax/core/compile/jaxpr_trace_duration", 9.0)  # not counted
    st = counters["loading"]
    assert (st.compile_requests, st.cache_loads, st.compiles) == (1, 1, 0)
    assert st.cache_load_s == pytest.approx(0.25)
    # outside every span nothing is charged
    timing._on_event("/jax/compilation_cache/cache_retrieval_time_sec", 1.0)
    assert st.cache_loads == 1 and SpanStats().compiles == 0


# -- the serving engine's spans ------------------------------------------------

ENGINE_SPANS = {
    "engine.step", "engine.admit", "engine.prefill.first", "engine.prefill.chunk",
    "engine.decode.prepare", "engine.decode.dispatch", "engine.decode.wait", "engine.sample",
}


@pytest.fixture(scope="module")
def served():
    cfg = tiny("granite-8b")
    model = build_model(cfg)
    params = materialize_tree(model.param_specs(), jax.random.PRNGKey(0))
    return cfg, model, params


def _paged(model, params, **kw):
    cfg = PagedServeConfig(page_size=8, max_pages=32, max_active=3, max_seq=64, prefill_chunk=6, eos=-1)
    return PagedServeEngine(model, params, cfg, **kw)


def _prompts(cfg, n=4, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, size=int(rng.integers(4, 15))).astype(np.int32) for _ in range(n)]


def test_engine_metrics_export_every_span_and_the_decode_batches(served):
    cfg, model, params = served
    eng = _paged(model, params)
    prompts = _prompts(cfg)
    for p in prompts:
        eng.submit(p, max_new_tokens=5)
    eng.run()
    m = eng.metrics()
    assert {k[: -len(".count")] for k in m if k.endswith(".count")} == ENGINE_SPANS
    for name in ENGINE_SPANS:
        for field in ("s", "compiles", "cache_loads", "cache_load_s"):
            assert f"{name}.{field}" in m
    # run() steps until a step finds nothing to do, which counts no step
    assert m["engine.step.count"] == m["steps"] + 1
    assert m["engine.prefill.first.count"] == len(prompts)  # one first chunk a request
    chunks = sum(-(-len(p) // 6) - 1 for p in prompts)
    assert m["engine.prefill.chunk.count"] == chunks
    assert m["engine.decode.dispatch.count"] == m["engine.decode.wait.count"] == m["decode_ticks"]
    # every request decodes all but the token its prompt completion sampled
    assert m["decode_rows"] == len(prompts) * 4
    assert 1 <= m["decode_rows"] / m["decode_ticks"] <= 3
    assert m["engine.step.s"] >= m["engine.prefill.first.s"] + m["engine.decode.wait.s"]
    # the first request's first chunk compiles the jitted chunk step at its
    # shape, or loads it from a compile cache
    assert m["engine.prefill.first.compiles"] + m["engine.prefill.first.cache_loads"] >= 1
    assert "engine.adapt.count" not in m
    assert eng.dispatch_stats.select_s > 0


def test_a_first_chunk_of_a_known_shape_compiles_nothing(served):
    """The first chunk runs the jitted chunk step: a second prompt of the
    first one's length reuses its program, with no compile or cache load."""
    cfg, model, params = served
    eng = _paged(model, params)
    p1, p2 = _prompts(cfg, n=2, seed=4)
    p2 = np.resize(p2, len(p1))
    eng.submit(p1, max_new_tokens=2)
    eng.run()
    before = eng.metrics()
    eng.submit(p2, max_new_tokens=2)
    eng.run()
    after = eng.metrics()
    assert after["engine.prefill.first.count"] == before["engine.prefill.first.count"] + 1
    for field in ("compiles", "cache_loads"):
        assert after[f"engine.prefill.first.{field}"] == before[f"engine.prefill.first.{field}"]


def test_chunked_mode_never_calls_the_whole_prompt_prefill(served, monkeypatch):
    cfg, model, params = served

    def whole_prompt(*a, **kw):
        raise AssertionError("model.prefill ran in chunked mode")

    monkeypatch.setattr(model, "prefill", whole_prompt)
    eng = _paged(model, params)
    prompts = _prompts(cfg, n=3, seed=5)
    for p in prompts:
        eng.submit(p, max_new_tokens=2)
    assert len(eng.run()) == len(prompts)
    assert eng.metrics()["engine.prefill.first.count"] == len(prompts)


def test_request_stamps_share_the_span_clock(served):
    cfg, model, params = served
    eng = _paged(model, params)
    t0 = time.perf_counter()
    uids = [eng.submit(p, max_new_tokens=3) for p in _prompts(cfg, n=5, seed=1)]
    done = {r.uid: r for r in eng.run()}
    t1 = time.perf_counter()
    assert set(done) == set(uids)
    for r in done.values():
        assert t0 <= r.submit_wall <= r.admit_wall <= r.first_token_wall <= r.done_wall <= t1


def test_adaptation_rounds_are_spans(served):
    cfg, model, params = served
    db = TuningDatabase()
    adaptive = AdaptiveTuner(
        KernelSelector(state=SelectorState(db=db, sieve=db.build_sieve())), config=AdaptiveConfig(hot_threshold=1, rebuild_every=1)
    )
    eng = _paged(model, params, adaptive=adaptive, adapt_every=2)
    for p in _prompts(cfg, n=2, seed=2):
        eng.submit(p, max_new_tokens=3)
    eng.run()
    assert eng.metrics()["engine.adapt.count"] == eng.metrics()["steps"] // 2


def test_phase_totals_line_reads_the_metrics(served):
    from repro.launch.serve import phase_totals

    cfg, model, params = served
    eng = _paged(model, params)
    eng.submit(_prompts(cfg, n=1)[0], max_new_tokens=3)
    eng.run()
    line = phase_totals(eng.metrics())
    for name in ENGINE_SPANS:
        assert name in line
    assert line.endswith("rows per decode batch")


# -- kernel names -------------------------------------------------------------

CFG = TileConfig(16, 128, 128)


@pytest.mark.parametrize("policy", [DP, ALL_SK, HYBRIDS[1]], ids=lambda p: p.name)
def test_two_tags_of_one_shape_share_one_kernel(policy):
    """The tag stays out of the kernel: two GEMMs of one shape and tile
    trace to one kernel function, so a program that is lowered again for
    every request (whole-prompt mode's eager prefill) lowers it once."""
    x, w = jnp.ones((16, 256)), jnp.ones((256, 128))
    with gemm_mod.gemm_context(backend="pallas_interpret"):
        jaxpr = jax.make_jaxpr(
            lambda a, b: gemm_mod.gemm(a, b, policy=policy, cfg=CFG, g=2, tag="attn.q")
            + gemm_mod.gemm(a, b, policy=policy, cfg=CFG, g=2, tag="attn.o")
        )(x, w)
    calls = [e.params["jaxpr"] for e in jaxpr.eqns if e.primitive.name in ("jit", "pjit")]
    assert len(calls) == 2 and calls[0] == calls[1]


def _kernel_names(jaxpr):
    """Every ``pallas_call`` name in a jaxpr, nested programs included."""
    names = []
    for e in jaxpr.eqns:
        if e.primitive.name == "pallas_call":
            names.append(e.params["name"])
        for v in e.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                inner = sub if hasattr(sub, "eqns") else getattr(sub, "jaxpr", None)
                if hasattr(inner, "eqns"):
                    names += _kernel_names(inner)
    return names


@pytest.mark.parametrize("policy", [DP, ALL_SK, HYBRIDS[1]], ids=lambda p: p.name)
def test_tagged_kernels_hold_the_tag_and_the_tile(policy):
    x, w = jnp.ones((16, 256)), jnp.ones((256, 128))
    with gemm_mod.gemm_context(backend="pallas_interpret"), gemm_mod.tagged_kernels():
        jaxpr = jax.make_jaxpr(
            lambda a, b: gemm_mod.gemm(a, b, policy=policy, cfg=CFG, g=2, tag="attn.q")
            + gemm_mod.gemm(a, b, policy=policy, cfg=CFG, g=2, tag="attn.o")
        )(x, w)
    names = _kernel_names(jaxpr.jaxpr)
    assert names and all(CFG.name in n for n in names)
    assert {n.split("__")[0] for n in names} == {"attn_q", "attn_o"}
    assert all(n.replace("__", "").replace("_", "").isalnum() for n in names)


def test_the_engines_jitted_programs_name_kernels_by_tag(served):
    """With ``tag_kernels`` the decode and chunk steps, compiled once, carry
    each GEMM's tag; without it, and in whole-prompt mode's eager prefill,
    lowered for every request, a kernel is named by its shape and tile
    alone."""
    cfg, model, params = served
    eng = _paged(model, params, backend="pallas_interpret")
    kv = eng.kv
    pages = jnp.zeros((3, 2), jnp.int32)
    decode_args = (params, kv.pool, pages, jnp.zeros((3, 1), jnp.int32), jnp.zeros((3,), jnp.int32))
    with gemm_mod.gemm_context(backend="pallas_interpret"):
        plain = jax.make_jaxpr(eng._decode_impl)(*decode_args)
    assert _kernel_names(plain.jaxpr) and not any("__" in n for n in _kernel_names(plain.jaxpr))
    eng.tag_kernels = True
    with gemm_mod.gemm_context(backend="pallas_interpret") as ctx:
        decode = jax.make_jaxpr(eng._decode_impl)(*decode_args)
        chunk = jax.make_jaxpr(eng._chunk_impl)(
            params, kv.pool, pages[:1], jnp.ones((1, 6), jnp.int32), jnp.zeros((1,), jnp.int32)
        )
        eager = jax.make_jaxpr(lambda p, t: model.prefill(p, t, max_seq=8)[0])(params, jnp.ones((1, 6), jnp.int32))
        tags = {e.tag.replace(".", "_") for e in ctx.log}
        tiles = {e.selection.cfg.name for e in ctx.log}
    for jaxpr in (decode, chunk):
        names = _kernel_names(jaxpr.jaxpr)
        assert names
        for n in names:
            tag, _, kernel = n.partition("__")
            assert tag in tags and any(t in kernel for t in tiles), n
        assert {n.split("__")[0] for n in names} == tags
    eager_names = _kernel_names(eager.jaxpr)
    assert eager_names and not any("__" in n for n in eager_names)
