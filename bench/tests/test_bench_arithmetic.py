"""The benchmark's own arithmetic: the peaks table, FLOPs and bytes from
shapes, the trace reduction, and the GEMMs each engine step runs."""

import gzip
import json
from pathlib import Path

import numpy as np
import pytest

from bench import flops, peaks, trace

FIXTURES = Path(__file__).parent / "fixtures"


def test_peaks_table_is_keyed_by_device_kind():
    v5e = peaks.peaks("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peaks("TPU v9 imaginary")


DIMS = flops.Dims(layers=2, d_model=8, heads=2, kv_heads=1, d_head=4, d_ff=16, vocab=32)


def test_chunk_flops_equal_the_sum_over_positions():
    start, size = 5, 7
    want = sum(flops.token_flops(DIMS, p + 1, logits=False) for p in range(start, start + size))
    want += 2.0 * DIMS.d_model * DIMS.vocab  # logits at the last position only
    assert flops.chunk_flops(DIMS, start, size) == pytest.approx(want)


def test_decode_flops_count_each_live_row():
    assert flops.decode_flops(DIMS, [3, 9]) == pytest.approx(
        flops.token_flops(DIMS, 3, logits=True) + flops.token_flops(DIMS, 9, logits=True)
    )
    # per token: 2 * weights multiplied; a dense layer holds q, k, v, o and 3 MLP matrices
    per_layer = 8 * 8 + 2 * 8 * 4 + 8 * 8 + 3 * 8 * 16
    assert DIMS.layer_matmul_params() == per_layer


def test_moe_flops_count_top_k_experts_only():
    moe = flops.Dims(layers=1, d_model=8, heads=2, kv_heads=2, d_head=4, d_ff=16, vocab=32, experts=64, top_k=8)
    attn = 8 * 8 + 2 * 8 * 8 + 8 * 8
    assert moe.layer_matmul_params() == attn + 8 * 64 + 8 * 3 * 8 * 16


def test_gemm_least_time_is_the_larger_bound():
    pk = peaks.peaks("TPU v5 lite")
    # decode-shaped: bound by the weight bytes
    m, n, k = 12, 14336, 4096
    t = flops.gemm_least_s(m, n, k, 1, 2, 2, 2, pk)
    assert t == pytest.approx((m * k * 2 + k * n * 2 + m * n * 2) / pk["hbm_bytes_per_s"])
    # prefill-shaped: bound by the FLOPs
    m = 4096
    assert flops.gemm_least_s(m, n, k, 1, 2, 2, 2, pk) == pytest.approx(2 * m * n * k / pk["bf16_flops"])


def _op(start, end, kernel=False, name="op"):
    return trace.Op("/device:TPU:0", name, float(start), float(end), kernel)


def test_reduction_of_a_hand_made_trace():
    # two steps of 100 ns; device busy 0-30 and 40-60 in step 0 (a kernel
    # 40-60), 120-150 in step 1 (a kernel); the host waits 200-300
    ops = [_op(0, 20), _op(10, 30), _op(40, 60, True, "k"), _op(120, 150, True, "k")]
    spans = [
        trace.Span("engine_step", 0, 100, 0),
        trace.Span("engine_step", 100, 200, 1),
        trace.Span("bench.wait", 200, 300),
        trace.Span("engine_step", 300, 400, 2),
    ]
    r = trace.reduce(ops, spans)
    assert r.window_s == pytest.approx(400e-9)
    assert r.busy_s == pytest.approx(80e-9)
    assert r.steps[0].busy_s == pytest.approx(50e-9) and r.steps[0].kernel_s == pytest.approx(20e-9)
    assert r.steps[0].kernels == 1 and r.steps[1].kernels == 1 and r.steps[2].kernels == 0
    assert r.steps[1].busy_s == pytest.approx(30e-9)
    idle = dict(r.idle_gaps)
    assert idle["bench.wait"] == pytest.approx(100e-9)
    assert idle["engine_step"] == pytest.approx(220e-9)
    assert sum(idle.values()) == pytest.approx(320e-9)
    assert dict(r.device_ops)["k"] == pytest.approx(50e-9)


def test_a_trace_without_a_tpu_plane_raises_unless_rehearsed(tmp_path):
    """A CPU profile has no /device:TPU plane: a chip run must not read the
    host's operations as the device's; a rehearsal may."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.tanh(x @ x))
    f(jnp.ones((64, 64))).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.StepTraceAnnotation(trace.STEP_SPAN, step_num=0):
        f(jnp.ones((64, 64))).block_until_ready()
    jax.profiler.stop_trace()
    path = trace.find_xplane(str(tmp_path))
    with pytest.raises(ValueError, match="no operations on a /device:TPU plane"):
        trace.events_from_xplane(path)
    ops, spans = trace.events_from_xplane(path, host_stands_in=True)
    assert ops and [s.step for s in spans if s.name == trace.STEP_SPAN] == [0]


def test_reduction_of_a_recorded_v5e_trace():
    """Three decode steps of granite-8b (18 layers, 12 slots) recorded on a
    TPU v5e (operation names cut at the HLO text's ``=``, a kernel flag
    where the text named ``tpu_custom_call``): each decode step launches one
    Pallas kernel per projection and layer and one for the head, 7 * 18 + 1,
    beside XLA's own custom calls, which are not kernels."""
    rec = json.loads(gzip.decompress((FIXTURES / "trace_v5e_decode.json.gz").read_bytes()))
    ops = [trace.Op(*o) for o in rec["ops"]]
    spans = [trace.Span(*s) for s in rec["spans"]]
    r = trace.reduce(ops, spans)
    assert 0 < r.busy_s <= r.window_s
    assert sum(s.busy_s for s in r.steps.values()) <= r.busy_s + 1e-9
    for step in rec["decode_steps"]:
        sd = r.steps[step]
        assert sd.kernels == 7 * 18 + 1
        assert 0 < sd.kernel_s <= sd.busy_s
    assert any(o.name.startswith("%custom-call") and not o.kernel for o in ops)


def test_gemms_per_step_come_from_the_selection_log():
    """The decode step of a tiny dense model traces 7 GEMMs a layer and the
    head at M = slots, and the harness counts 7 * layers + 1 launches."""
    from repro.core.gemm import gemm_context

    from bench import harness, run

    cell = harness.Cell.load(harness.CHECKOUT, "granite-8b.decode-batch", rehearsal=True)
    traffic = cell.traffic(1)
    built = harness.build(cell, 1)
    with gemm_context(selector=built.selector, backend="xla") as ctx:
        gemms = harness.warm(built, cell, traffic, ctx.log)
    slots = cell.engine["slots"]
    tags = sorted(g.tag for g in gemms["decode"])
    assert tags == sorted(["attn.q", "attn.k", "attn.v", "attn.o", "mlp.gate", "mlp.in", "mlp.out", "lm_head"])
    assert all(g.m == slots for g in gemms["decode"])
    d = cell.config["hidden_size"]
    assert {(g.n, g.k) for g in gemms["decode"] if g.tag == "mlp.in"} == {(cell.config["intermediate_size"], d)}
    rec = harness.Record(
        workload=cell.workload, config=cell.config, slots=slots, window=harness.Window(0, 1), steps=[],
        tracked=[], gemms=gemms, select_s=0.0, peaks=peaks.peaks("TPU v5 lite"), drain_end=1.0,
    )
    step = harness.Step(0, 0.0, 1.0, [20, 30], None)
    runs = rec.launches(rec.step_gemms(step))
    assert runs == 7 * cell.config["num_hidden_layers"] + 1
    # the trace's kernels match when each run has a launch named by a
    # logged tile; a Stream-K GEMM adds a fix-up launch of the same tile
    tile = gemms["decode"][0].tile
    names = [f"%dp_gemm_{g.tile}.{i}" for i, g in enumerate(rec.step_gemms(step))] * runs
    names = names[:runs] + [f"%streamk_fixup_{tile}.9"]
    assert rec.kernels_match(step, trace.StepDevice(1.0, 0.5, len(names), names))
    assert not rec.kernels_match(step, trace.StepDevice(1.0, 0.5, runs - 1, names[: runs - 1]))
    assert not rec.kernels_match(step, trace.StepDevice(1.0, 0.5, runs + 1, names[:runs] + ["%other_kernel.3"]))
    chunk = cell.engine["prefill_chunk"]
    assert all(g.m in (chunk, 1) for g in gemms[f"chunk{chunk}"])
    # a request's first chunk runs the chunk step of its size, as later ones do
    first, later = harness.Step(1, 0.0, 1.0, [], (0, chunk)), harness.Step(2, 0.0, 1.0, [], (chunk, chunk))
    assert rec.step_gemms(first) == rec.step_gemms(later) == gemms[f"chunk{chunk}"]
    widths, shapes = harness.shapes(cell, traffic)
    for n in traffic.prompt_classes():
        width = harness.pow2(harness.pages_for(n, cell.engine["page_size"]))
        assert (min(chunk, n), width) in shapes
        assert f"chunk{min(chunk, n)}" in gemms
    assert run.TRACE_MAX_S > 0 and np.isfinite(run.TRACE_MAX_S)
