#!/usr/bin/env python3
"""Readings that set a cell's limits and rate, many seeds in one process.

  python3 bench/limits.py --workload <name> --seeds 1,2,3 --seconds <s>
      for each seed, one run of the cell (no trace) with the control on:
      the widest reference-logit gap of the served tokens, and that of the
      int8 forward pass's picks at the same positions (bench/correct.py),
      each judged against the cell's limits
  python3 bench/limits.py --workload <name> --rates 1,2,4 --seconds <s>
      the open-loop mix at each rate: whether the backlog of requests that
      wait for their first token grows over the window (the knee sweep)

Each reading is one JSON line on standard output. The benchmark's own runs
never run the control.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1] / "src"), str(Path(__file__).resolve().parents[1])]

from bench import run  # noqa: E402


def backlog_growth(rec) -> dict:
    """Requests due but without a first token, sampled at each step end:
    the mean over the window's second quarter and over its last quarter."""
    w = rec.window
    q = w.seconds / 4

    def backlog(t):
        return sum(1 for r in rec.tracked if r.due <= t and not (r.token_t and r.token_t[0] <= t))

    def mean_over(lo, hi):
        pts = [s.t1 for s in rec.steps if lo <= s.t1 < hi]
        return sum(backlog(t) for t in pts) / len(pts) if pts else 0.0

    return {"backlog_q2": mean_over(w.open + q, w.open + 2 * q), "backlog_q4": mean_over(w.open + 3 * q, w.close)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--rates", default="")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    args = ap.parse_args(argv)

    import jax

    from bench import correct, harness, measure, peaks
    from repro.launch.cache import enable_compile_cache

    root = Path(__file__).resolve().parents[1]
    cell = harness.Cell.load(root, args.workload, rehearsal=args.cpu_rehearsal)
    if args.cpu_rehearsal:
        backend, pk = "xla", peaks.peaks("TPU v5 lite")
    else:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        enable_compile_cache()
        dev = jax.devices()[0]
        if dev.platform != "tpu":
            print("limits: no TPU found", file=sys.stderr)
            return 1
        backend, pk = "pallas", peaks.peaks(dev.device_kind)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    rates = [float(r) for r in args.rates.split(",") if r]
    for i, rate in enumerate(rates or [None] * len(seeds)):
        seed = seeds[i % len(seeds)]
        if rate is not None:
            cell.mix = {**cell.mix, "arrivals": {**cell.mix["arrivals"], "rate_per_s": rate}}
        out = run.run_cell(cell, seed, args.seconds, traced=False, backend=backend, pk=pk, control=rate is None)
        rec, cmp = out["rec"], out["cmp"]
        line = {
            "workload": args.workload, "seed": seed, "rate": rate, **cmp,
            "correct": correct.correct(cmp, cell.limits), "setup_s": out["setup_s"],
            "output_tok_per_s": measure.output_tok_per_s(rec), "tpot_p95_ms": measure.tpot_p95_ms(rec),
            "window_s": rec.window.seconds, "due": len(rec.tracked), "peak": out["peak"],
        }
        if rate is None:
            line["control_correct"] = correct.correct(correct.as_control(cmp), cell.limits)
        if not cell.mix["arrivals"]["kind"] == "closed":
            line.update(
                ttft_p50_ms=measure.ttft_p50_ms(rec), ttft_p95_ms=measure.ttft_ms(rec, 95),
                queue_p95_ms=measure.queue_p95_ms(rec),
            )
            line.update(backlog_growth(rec))
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
