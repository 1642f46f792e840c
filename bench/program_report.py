#!/usr/bin/env python3
"""Run one cell traced, as ``bench/run.py --trace 1`` does, with the engine's
jitted steps naming each kernel by its GEMM's tag (``tag_kernels``), and
report what the serving program's own spans and counters say about the
window.

  python3 bench/program_report.py --workload <name> --seed <n> --seconds <s>

``bench/run.py`` prints its readings and result line unchanged; the lines
that follow, each starting ``program:``, are this report:

- the quantities of ``bench/program_spans.py`` (``first_chunk_ms``,
  ``first_chunk_idle_share``, ``decode_host_ms``), from the run's
  ``Record.program``;
- the device's idle time inside the benchmark's steps by the innermost
  program span open meanwhile, and the share of it that program spans hold;
- per span name over the traced steps: count, mean host ms, self ms, idle;
- Pallas-kernel device ms per decode-only and per chunk step, by GEMM tag;
- the engine's own counters (``Record.engine_metrics``) over the window:
  each span's count, host seconds and compile-cache loads;
- the queue split by the engine's ``admit_wall`` stamp: due time to
  admission, the benchmark's own stamp after the admitting step, and
  admission to the first token;
- what a span costs on this host, with no profile running and under one.

``--cpu-rehearsal`` runs it at the rehearsal's tiny sizes on the CPU.
"""

from __future__ import annotations

import contextlib
import statistics
import sys
import tempfile
import time
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(CHECKOUT / "src"), str(CHECKOUT)]

from bench import harness, measure, program_spans, run  # noqa: E402

_seen = {}


def say(msg: str) -> None:
    print(f"program: {msg}", flush=True)


def span_cost(n: int = 20000):
    """Microseconds per span with no profile running, and under one."""
    import jax

    from repro.utils.timing import span

    def per_span() -> float:
        counters = {}
        t0 = time.perf_counter()
        for i in range(n):
            with span("engine.cost", counters, rows=i):
                pass
        return (time.perf_counter() - t0) / n * 1e6

    off = per_span()
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        on = per_span()
        jax.profiler.stop_trace()
    return off, on


@contextlib.contextmanager
def _kept():
    """Name the jitted steps' kernels by GEMM tag, and keep the run's record."""
    build, run_cell = harness.build, run.run_cell

    def build_kept(cell, seed):
        built = build(cell, seed)
        # before warm-up traces the jitted steps: their kernels by GEMM tag
        built.engine.tag_kernels = True
        return built

    def run_kept(*a, **k):
        out = run_cell(*a, **k)
        _seen["rec"] = out["rec"]
        return out

    _seen.clear()
    harness.build, run.run_cell = build_kept, run_kept
    try:
        yield _seen
    finally:
        harness.build, run.run_cell = build, run_cell


def _ms(values, q) -> float:
    v = measure.percentile(values, q)
    return float("nan") if v is None else 1e3 * v


def report() -> None:
    rec = _seen.get("rec")
    if rec is None:
        return
    red = rec.program
    if red is not None:
        say(
            f"first_chunk_ms {program_spans.first_chunk_ms(red)}, first_chunk_idle_share "
            f"{program_spans.first_chunk_idle_share(red)} %, decode_host_ms {program_spans.decode_host_ms(red)}"
        )
        idle = program_spans.idle_by_span(red)
        held = sum(v for k, v in idle if k != "engine_step")
        phases = sum(v for k, v in idle if k not in ("engine_step", "engine.step"))
        share = 100 * held / red.step_idle_s if red.step_idle_s else float("nan")
        phase_share = 100 * phases / red.step_idle_s if red.step_idle_s else float("nan")
        say(
            f"device idle inside steps {red.step_idle_s:.4f} s of a {red.window_s:.4f} s window; "
            f"program spans hold {share:.2f} %, phase spans {phase_share:.2f} %"
        )
        say("idle by innermost span (s): " + ", ".join(f"{k} {v:.4f}" for k, v in idle))
        for name, t in sorted(red.names.items()):
            say(
                f"span {name}: {t.count} in traced steps, host {1e3 * t.host_s / t.count:.3f} ms each, "
                f"self {1e3 * t.self_s:.1f} ms, idle {1e3 * t.idle_s:.1f} ms (inside {1e3 * t.idle_in_s:.1f} ms)"
            )
        for chunk in (False, True):
            per_tag = program_spans.kernel_ms_by_tag(red, chunk)
            say(
                f"Pallas kernel ms per {'chunk' if chunk else 'decode-only'} step by tag: "
                + ", ".join(f"{tag} {ms:.3f}" for tag, ms in per_tag.items())
            )
        decode = [s for s in red.steps.values() if program_spans.decode_only(s)]
        if decode:
            per = [sum(p.end_ns - p.start_ns for p in s.spans if p.name == "engine.step") / 1e6 for s in decode]
            say(f"decode-only steps {len(decode)}, engine.step host ms median {statistics.median(per):.3f}")
    if rec.engine_metrics is not None:
        a, b = rec.engine_metrics
        parts = []
        for key in sorted(k for k in b if k.endswith(".count")):
            name = key[: -len(".count")]
            n = b[key] - a.get(key, 0)
            if n:
                s = b[name + ".s"] - a.get(name + ".s", 0.0)
                loads = b[name + ".cache_loads"] - a.get(name + ".cache_loads", 0)
                load_s = b[name + ".cache_load_s"] - a.get(name + ".cache_load_s", 0.0)
                parts.append(f"{name} {n} x {1e3 * s / n:.3f} ms (cache loads {loads}, {1e3 * load_s:.1f} ms)")
        ticks = b["decode_ticks"] - a["decode_ticks"]
        rows = b["decode_rows"] - a["decode_rows"]
        say("counters over the window: " + "; ".join(parts) + f"; decode rows per batch {rows / max(ticks, 1):.3f}")
    tracked = [t for t in rec.tracked if t.req is not None and t.req.admit_wall]
    if tracked:
        wait = [t.req.admit_wall - t.due for t in tracked]
        loop = [t.admitted - t.due for t in tracked if t.admitted is not None]
        first = [t.token_t[0] - t.req.admit_wall for t in tracked if t.token_t]
        say(
            f"queue over {len(tracked)} requests (ms p50/p95): due to admit_wall {_ms(wait, 50):.1f}/{_ms(wait, 95):.1f}, "
            f"due to the loop's admitted stamp {_ms(loop, 50):.1f}/{_ms(loop, 95):.1f}, "
            f"admit_wall to first token {_ms(first, 50):.1f}/{_ms(first, 95):.1f}"
        )


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if "--trace" not in argv:
        argv += ["--trace", "1"]
    with _kept():
        code = run.main(argv)
    off, on = span_cost()
    report()
    say(f"span cost: {off:.3f} us with no profile, {on:.3f} us under one")
    return code


if __name__ == "__main__":
    import os

    code = main()
    sys.stdout.flush()
    os._exit(code)
