"""The program's spans as the benchmark reads them: the reduction on
hand-made event lists, the recorded v5e fixture (which predates the spans),
a traced CPU rehearsal whose profile holds every engine span, and the
engine's decode-batch counters against the loop's count."""

import gzip
import json
from pathlib import Path

import pytest

from bench import harness, measure, peaks, program_spans, run, trace

FIXTURES = Path(__file__).parent / "fixtures"
ENGINE_SPANS = {
    "engine.step", "engine.admit", "engine.prefill.first", "engine.prefill.chunk",
    "engine.decode.prepare", "engine.decode.dispatch", "engine.decode.wait", "engine.sample",
}


def _op(start, end, kernel=False, name="op"):
    return trace.Op("/device:TPU:0", name, float(start), float(end), kernel)


def _ps(name, start, end, **args):
    return program_spans.ProgramSpan(name, float(start), float(end), args)


def _hand_made():
    # step 0 (0-100): a first chunk 10-60 holding a selection 20-30,
    # the device busy 50-70; step 1 (100-200): a decode step, prepare
    # 105-115, dispatch 115-120, wait 120-180 while the device runs 118-175,
    # sample 180-190
    ops = [
        _op(50, 60, True, "%dp_gemm_64x128x256.3"), _op(60, 70),
        _op(118, 150, True, "%mlp_gate__dp_gemm_64x128x256.48"), _op(150, 175, True, "%attn_q__streamk_p1_8x256x1024_g8"),
    ]
    spans = [trace.Span("engine_step", 0, 100, 0), trace.Span("engine_step", 100, 200, 1)]
    program = [
        _ps("engine.step", 2, 98, step_num=0),
        _ps("engine.prefill.first", 10, 60, uid=1, tokens=512),
        _ps("gemm.select", 20, 30, tag="attn.q", source="tuned"),
        _ps("engine.step", 102, 195, step_num=1),
        _ps("engine.decode.prepare", 105, 115, rows=12),
        _ps("engine.decode.dispatch", 115, 120),
        _ps("engine.decode.wait", 120, 180),
        _ps("engine.sample", 180, 190, rows=12),
        _ps("engine.admit", 300, 301),  # outside every step: left out
    ]
    return ops, spans, program


def test_idle_goes_to_the_innermost_program_span():
    red = program_spans.reduce(*_hand_made())
    s0, s1 = red.steps[0].idle_s, red.steps[1].idle_s
    assert s0["gemm.select"] == pytest.approx(10e-9)
    assert s0["engine.prefill.first"] == pytest.approx(30e-9)  # 10-20 and 30-50
    assert s0["engine.step"] == pytest.approx(8e-9 + 28e-9)  # 2-10 and 70-98
    assert s0["engine_step"] == pytest.approx(4e-9)  # under no program span
    assert s1["engine.decode.prepare"] == pytest.approx(10e-9)
    assert s1["engine.decode.dispatch"] == pytest.approx(3e-9)
    assert s1["engine.decode.wait"] == pytest.approx(5e-9)
    first = red.names["engine.prefill.first"]
    assert first.idle_s == pytest.approx(30e-9) and first.idle_in_s == pytest.approx(40e-9)
    assert red.step_idle_s == pytest.approx(sum(s0.values()) + sum(s1.values()))
    assert "engine.admit" not in red.names


def test_a_steps_self_times_sum_to_its_span():
    red = program_spans.reduce(*_hand_made())
    for step, width in ((0, 100e-9), (1, 100e-9)):
        assert sum(red.steps[step].self_s.values()) == pytest.approx(width)
    assert red.steps[0].self_s["engine.prefill.first"] == pytest.approx(40e-9)
    assert red.names["engine.step"].count == 2
    assert red.names["engine.step"].host_s == pytest.approx((96 + 93) * 1e-9)
    assert red.steps[0].spans[1].args == {"uid": 1, "tokens": 512}


def test_kernel_time_goes_to_the_tag_in_the_kernels_name():
    red = program_spans.reduce(*_hand_made())
    assert red.steps[0].kernel_s == pytest.approx({"untagged": 10e-9})
    assert red.steps[1].kernel_s == pytest.approx({"mlp_gate": 32e-9, "attn_q": 25e-9})
    assert program_spans.kernel_ms_by_tag(red, chunk=False) == pytest.approx({"mlp_gate": 32e-6, "attn_q": 25e-6})
    assert program_spans.kernel_ms_by_tag(red, chunk=True) == pytest.approx({"untagged": 10e-6})
    assert program_spans.kernel_tag("%dp_gemm_region_64x128x256.7") is None


def test_the_per_layer_quantities():
    red = program_spans.reduce(*_hand_made())
    assert program_spans.first_chunk_ms(red) == pytest.approx(50e-6)
    assert program_spans.first_chunk_idle_share(red) == pytest.approx(100 * 40 / 200)
    assert program_spans.decode_host_ms(red) == pytest.approx(20e-6)  # prepare + sample of step 1
    idle = dict(program_spans.idle_by_span(red))
    assert sum(idle.values()) == pytest.approx(red.step_idle_s)


def _fixture():
    rec = json.loads(gzip.decompress((FIXTURES / "trace_v5e_decode.json.gz").read_bytes()))
    return [trace.Op(*o) for o in rec["ops"]], [trace.Span(*s) for s in rec["spans"]]


def test_the_recorded_trace_reduces_as_before():
    """The v5e fixture, recorded before the program had spans, reduces to
    the numbers it always gave."""
    ops, spans = _fixture()
    r = trace.reduce(ops, spans)
    assert (r.busy_s, r.window_s, r.devices) == pytest.approx((0.267765888, 0.277784805, 1))
    assert {k: (v.busy_s, v.kernel_s, v.kernels) for k, v in r.steps.items()} == {
        39: pytest.approx((0.089185746, 0.042284629, 127)),
        40: pytest.approx((0.089270223, 0.042312687, 127)),
        41: pytest.approx((0.089269749, 0.042285824, 127)),
    }
    assert dict(r.idle_gaps) == pytest.approx({
        "engine_step": 0.009978407, "bench.record": 2.225e-05,
        "outside benchmark spans": 1.756e-05, "bench.submit": 7e-07,
    })


def test_a_trace_without_program_spans_reads_nothing():
    ops, spans = _fixture()
    red = program_spans.reduce(ops, spans, [])
    assert red.names == {} and red.step_idle_s == pytest.approx(0.009978407)
    for read in (program_spans.first_chunk_ms, program_spans.first_chunk_idle_share, program_spans.decode_host_ms):
        assert read(red) is None and read(None) is None


def test_a_traced_rehearsal_holds_every_engine_span(monkeypatch, capsys):
    """``--trace 1 --cpu-rehearsal``: the profile holds every span the
    engine opens, each inside a benchmark step, and no selection: warm-up
    has traced every program the window runs, a request's first chunk
    among them."""
    got = {}
    events = program_spans.events_from_xplane

    def keep(path, host_stands_in=False):
        got["events"] = events(path, host_stands_in)
        return got["events"]

    monkeypatch.setattr(program_spans, "events_from_xplane", keep)
    code = run.main(
        ["--workload", "granite-8b.prefill-poisson", "--seed", str(2**31 + 11), "--seconds", "3",
         "--trace", "1", "--cpu-rehearsal", "--backend", "xla"]
    )
    capsys.readouterr()
    assert code == 0
    ops, spans, program = got["events"]
    steps = [s for s in spans if s.name == trace.STEP_SPAN]
    assert ENGINE_SPANS <= {p.name for p in program}
    for p in program:
        assert any(s.start_ns <= p.start_ns and p.end_ns <= s.end_ns for s in steps), p
    first = [p for p in program if p.name == "engine.prefill.first"]
    assert not [p for p in program if p.name == "gemm.select"]
    assert all({"uid", "tokens"} <= set(f.args) for f in first)
    red = program_spans.reduce(ops, spans, program)
    assert program_spans.first_chunk_ms(red) > 0 and program_spans.first_chunk_idle_share(red) > 0
    # a loaded host may carry a prefill chunk in every step of so short a window
    if any(program_spans.decode_only(s) for s in red.steps.values()):
        assert program_spans.decode_host_ms(red) > 0
    else:
        assert program_spans.decode_host_ms(red) is None


def test_decode_counters_match_the_loops_batch_count():
    """The engine's ``decode_rows / decode_ticks`` is the loop's
    ``batch_occupancy`` count: rows that gained a token per decode step."""
    from repro.core.gemm import gemm_context

    cell = harness.Cell.load(harness.CHECKOUT, "granite-8b.decode-batch", rehearsal=True)
    traffic = cell.traffic(7)
    built = harness.build(cell, 7)
    slots = cell.engine["slots"]
    with gemm_context(selector=built.selector, backend="xla") as ctx:
        harness.warm(built, cell, traffic, ctx.log)
        loop = harness.Loop(built.engine)
        harness.fill(loop, traffic, slots)
        eng = built.engine
        rows0, ticks0, first = eng.decode_rows, eng.decode_ticks, len(loop.steps)
        for _ in range(30):
            while len(loop.waiting) < slots:
                loop.submit(traffic.next_closed(), loop.clock())
            loop.step()
    steps = loop.steps[first:]
    rec = harness.Record(
        workload=cell.workload, config=cell.config, slots=slots,
        window=harness.Window(steps[0].t0, steps[-1].t1), steps=steps, tracked=[], gemms={}, select_s=0.0,
        peaks={}, drain_end=steps[-1].t1,
    )
    ticks = eng.decode_ticks - ticks0
    assert ticks == sum(1 for s in steps if s.decode_rows)
    assert 100.0 * (eng.decode_rows - rows0) / ticks / slots == pytest.approx(measure.batch_occupancy(rec))


def test_the_program_report_reads_a_traced_rehearsal(capsys):
    """``bench/program_report.py`` runs the cell traced and reports the
    program's quantities, its idle by span, its counters and the queue split,
    and leaves the benchmark's functions as it found them."""
    from bench import program_report

    before = (harness.build, run.run_cell)
    code = program_report.main(
        ["--workload", "granite-8b.prefill-poisson", "--seed", str(2**31 + 5), "--seconds", "3",
         "--cpu-rehearsal", "--backend", "xla"]
    )
    out = capsys.readouterr().out
    assert code == 0
    assert (harness.build, run.run_cell) == before
    lines = [x for x in out.splitlines() if x.startswith("program: ")]
    text = "\n".join(lines)
    for part in ("first_chunk_ms ", "program spans hold", "idle by innermost span", "span engine.prefill.first:",
                 "Pallas kernel ms per decode-only step by tag",
                 "counters over the window:", "due to admit_wall", "span cost:"):
        assert part in text, part
    assert program_spans.first_chunk_ms(program_report._seen["rec"].program) > 0


def test_a_traced_rehearsal_fills_the_record_for_its_readers():
    """A traced run's record holds the program spans' reduction and the
    engine's counters as the window opened and closed, and the reader of
    ``decode_host_ms.decode`` finds the decode steps' host work there; an
    untraced run's record holds no reduction."""
    cell = harness.Cell.load(harness.CHECKOUT, "granite-8b.decode-batch", rehearsal=True)
    # long outputs, so that the window holds decode-only steps
    cell.mix = {**cell.mix, "output": {"dist": "fixed", "value": 16}}
    pk = peaks.peaks("TPU v5 lite")
    rec = run.run_cell(cell, 2**31 + 3, 3.0, traced=True, backend="xla", pk=pk, rehearsal=True)["rec"]
    assert isinstance(rec.program, program_spans.Reduction) and rec.program.names
    at_open, at_close = rec.engine_metrics
    window_ticks = sum(1 for s in rec.window_steps() if s.decode_rows)
    assert at_close["decode_ticks"] - at_open["decode_ticks"] == window_ticks > 0
    assert at_close["engine.step.count"] - at_open["engine.step.count"] == len(rec.window_steps())
    metric = next(m for m in cell.per_layer() if m["name"] == "decode_host_ms.decode")
    assert metric["source"] == "program_span" and metric["moves"] == "output_tok_per_s"
    reader = harness.load_module(harness.CHECKOUT / "bench" / "metrics" / "decode_host_ms.decode.py", "reader")
    assert reader.read(rec) > 0
    assert run.per_layer(cell, harness.CHECKOUT, rec)["decode_host_ms.decode"]["value"] == reader.read(rec)
    untraced = run.run_cell(cell, 2**31 + 3, 1.0, traced=False, backend="xla", pk=pk, rehearsal=True)["rec"]
    assert untraced.program is None and reader.read(untraced) is None
