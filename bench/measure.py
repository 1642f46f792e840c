"""The arithmetic from a run's record to its metrics.

End-to-end metrics come from the benchmark's own host clock; the per-layer
readers in ``bench/metrics/`` call the functions below. A function that
finds nothing to read returns None, and the metric is left out.
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import numpy as np

from bench import flops
from bench.harness import Record


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (linear between order statistics), or None."""
    return float(np.percentile(np.asarray(values, np.float64), q)) if len(values) else None


def p95(values: Sequence[float]) -> Optional[float]:
    return percentile(values, 95)


# -- end to end -----------------------------------------------------------


def output_tokens(rec: Record) -> int:
    return sum(1 for t in rec.tracked for x in t.token_t if rec.window.holds(x))


def output_tok_per_s(rec: Record) -> Optional[float]:
    n = output_tokens(rec)
    return n / rec.window.seconds if n else None


def token_gaps(rec: Record) -> List[float]:
    """Every gap between successive tokens of a request inside the window."""
    w = rec.window
    return [
        b - a for t in rec.tracked for a, b in zip(t.token_t, t.token_t[1:]) if a >= w.open and b <= w.close
    ]


def tpot_p95_ms(rec: Record) -> Optional[float]:
    v = p95(token_gaps(rec))
    return None if v is None else v * 1e3


def ttft_s(rec: Record) -> List[float]:
    """From each request's due time to its first token; a request with no
    first token by the end of the drain counts until then (a lower bound,
    and a failure)."""
    return [(t.token_t[0] if t.token_t else rec.drain_end) - t.due for t in rec.tracked]


def ttft_ms(rec: Record, q: float) -> Optional[float]:
    """The ``q``-th percentile of the time to first token over all requests
    due in the window (ms)."""
    v = percentile(ttft_s(rec), q)
    return None if v is None else v * 1e3


def ttft_p50_ms(rec: Record) -> Optional[float]:
    return ttft_ms(rec, 50)


# -- per layer: counted by the loop -----------------------------------------


def batch_occupancy(rec: Record) -> Optional[float]:
    rows = [s.decode_rows for s in rec.window_steps() if s.decode_rows]
    return 100.0 * float(np.mean(rows)) / rec.slots if rows else None


def queue_p95_ms(rec: Record) -> Optional[float]:
    """From each request's due time to the step after which it is admitted
    (``engine.active``); one never admitted counts until the loop stopped."""
    v = p95([(t.admitted if t.admitted is not None else rec.drain_end) - t.due for t in rec.tracked])
    return None if v is None else v * 1e3


def selection_ms(rec: Record) -> Optional[float]:
    return rec.select_s * 1e3 if rec.select_s > 0 else None


# -- per layer: from the trace ----------------------------------------------


def _traced(rec: Record, chunk: bool):
    """Traced steps that carry a prefill chunk (``chunk``) or decode only."""
    return [(s, d) for s, d in rec.traced() if (s.chunk is not None) == chunk and (chunk or s.decode_rows)]


def step_ms(rec: Record, chunk: bool) -> Optional[float]:
    busy = [d.busy_s for _, d in _traced(rec, chunk)]
    return 1e3 * float(np.mean(busy)) if busy else None


def step_flops(rec: Record, s) -> float:
    dims = flops.Dims.from_config(rec.config)
    f = flops.decode_flops(dims, s.decode_ctx)
    if s.chunk is not None:
        f += flops.chunk_flops(dims, *s.chunk)
    return f


def mfu(rec: Record, chunk: bool) -> Optional[float]:
    """Model FLOPs the steps needed over their device busy time, as a share
    of the bf16 peak."""
    steps = _traced(rec, chunk)
    busy = sum(d.busy_s for _, d in steps)
    if not steps or busy <= 0:
        return None
    need = sum(step_flops(rec, s) for s, _ in steps)
    return 100.0 * need / busy / rec.peaks["bf16_flops"]


def gemm_roofline(rec: Record, chunk: bool) -> Optional[float]:
    """Least time of the Pallas GEMMs the steps ran over the device time of
    the Pallas kernels in those steps. Only steps whose kernels in the trace
    match the GEMMs the selection log says they ran count."""
    least = kernel = 0.0
    for s, d in _traced(rec, chunk):
        if not rec.kernels_match(s, d):
            continue
        gemms = rec.step_gemms(s)
        layers = rec.config["num_hidden_layers"]
        for g in gemms:
            t = flops.gemm_least_s(g.m, g.n, g.k, g.g, g.a_bytes, g.b_bytes, g.out_bytes, rec.peaks)
            least += t * (1 if g.tag == "lm_head" else layers)
        kernel += d.kernel_s
    return 100.0 * least / kernel if kernel > 0 else None


def matched_steps(rec: Record, chunk: bool) -> tuple:
    """(steps whose kernel launches match the selection log, traced steps)."""
    steps = _traced(rec, chunk)
    return sum(1 for s, d in steps if rec.kernels_match(s, d)), len(steps)


def idle_share(rec: Record) -> Optional[float]:
    if rec.trace is None or rec.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - rec.trace.busy_s / rec.trace.window_s)
