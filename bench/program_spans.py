"""From a profiler trace to the serving program's own spans, step by step.

The engine marks its phases with ``repro.utils.timing.span``: host spans
named ``engine.*`` and ``gemm.*``, with their arguments, on the profiler's
clock. ``events_from_xplane`` reads them from the same ``.xplane.pb`` as
``bench.trace`` reads the device's operations and the benchmark's spans.
``reduce`` takes those plain lists, with nothing of JAX, and gives:

- each program span assigned to the benchmark's ``engine_step`` span that
  encloses it (spans outside every step are left out);
- per name: count, host seconds, self seconds (not under an inner program
  span), the device's idle seconds while the name was the innermost
  program span open, and those inside its spans, inner spans included;
  time in a step under no program span goes to the step's own name, so a
  step's self seconds sum to its span;
- per step, the device seconds of its Pallas kernels by the GEMM tag in
  front of the kernel's name (``%mlp_gate__dp_gemm_64x128x256.48``; the
  engine's jitted programs name their kernels so).

The functions at the end read the per-layer quantities from a reduction.
Each returns None when the trace holds no program spans, as a profile of a
program without them does.
"""

from __future__ import annotations

import bisect
import dataclasses
import statistics
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

from bench import trace

#: prefixes of the program's span names
PREFIXES = ("engine.", "gemm.")
#: the span around a request's first prefill chunk
FIRST_CHUNK = "engine.prefill.first"
#: spans that only a step carrying a prefill chunk opens
PREFILL = ("engine.prefill.first", "engine.prefill.chunk")
#: the host work of a decode step around its device program
DECODE_HOST = ("engine.decode.prepare", "engine.sample")


@dataclasses.dataclass
class ProgramSpan:
    name: str
    start_ns: float
    end_ns: float
    args: Dict = dataclasses.field(default_factory=dict)


def events_from_xplane(path: str, host_stands_in: bool = False):
    """(device operations, benchmark spans, program spans) of one trace file;
    the first two as ``bench.trace.events_from_xplane`` gives them."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops, spans = trace.events(data, path, host_stands_in)
    program: List[ProgramSpan] = []
    for plane in data.planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIXES):
                    program.append(ProgramSpan(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats)))
    return ops, spans, program


@dataclasses.dataclass
class NameTotals:
    count: int = 0
    host_s: float = 0.0
    self_s: float = 0.0
    idle_s: float = 0.0  # while the innermost program span
    idle_in_s: float = 0.0  # inside the spans, inner spans included


@dataclasses.dataclass
class StepProgram:
    spans: List[ProgramSpan]
    self_s: Dict[str, float]
    idle_s: Dict[str, float]
    kernel_s: Dict[str, float]  # Pallas-kernel device seconds by GEMM tag


def kernel_tag(name: str) -> Optional[str]:
    """The GEMM tag in front of a Pallas kernel's name, or None for a
    kernel named by its tile alone."""
    base = name.lstrip("%")
    return base.split("__", 1)[0] if "__" in base else None


@dataclasses.dataclass
class Reduction:
    names: Dict[str, NameTotals]
    steps: Dict[int, StepProgram]
    window_s: float
    step_idle_s: float  # device idle inside the benchmark's step spans


def _innermost(spans: Sequence[ProgramSpan], a: float, b: float) -> Optional[ProgramSpan]:
    """The latest-opened span that covers ``[a, b]`` (spans of one thread
    nest, so that is the innermost)."""
    best = None
    for s in spans:
        if s.start_ns <= a and s.end_ns >= b and (best is None or s.start_ns >= best.start_ns):
            best = s
    return best


def reduce(ops: Sequence[trace.Op], spans: Sequence[trace.Span], program: Sequence[ProgramSpan]) -> Reduction:
    """Reduce one traced window (see the module doc). The window is
    ``bench.trace.reduce``'s: from the first benchmark span's start to the
    last one's end."""
    steps = sorted((s for s in spans if s.name == trace.STEP_SPAN and s.step is not None), key=lambda s: s.start_ns)
    if not steps or not ops:
        raise ValueError(f"trace holds {len(steps)} step spans and {len(ops)} device ops")
    lo = min(s.start_ns for s in spans)
    hi = max(s.end_ns for s in spans)
    by_dev: Dict[str, List[trace.Op]] = defaultdict(list)
    for o in ops:
        by_dev[o.device].append(o)
    merged = [trace.union([(o.start_ns, o.end_ns) for o in v]) for v in by_dev.values()]
    n_dev = len(by_dev)

    def idle(a: float, b: float) -> float:
        return sum((b - a) - trace.covered(m, a, b) for m in merged) / n_dev / 1e9

    launches = sorted((o.start_ns, i) for i, o in enumerate(ops) if o.kernel)
    starts = [t for t, _ in launches]
    program = sorted(program, key=lambda p: (p.start_ns, -p.end_ns))
    names: Dict[str, NameTotals] = defaultdict(NameTotals)
    per_step: Dict[int, StepProgram] = {}
    step_idle = 0.0
    for st in steps:
        inside = [p for p in program if p.start_ns >= st.start_ns and p.end_ns <= st.end_ns]
        for p in inside:
            names[p.name].count += 1
            names[p.name].host_s += (p.end_ns - p.start_ns) / 1e9
            names[p.name].idle_in_s += idle(p.start_ns, p.end_ns)
        cuts = sorted({st.start_ns, st.end_ns, *(t for p in inside for t in (p.start_ns, p.end_ns))})
        self_s: Dict[str, float] = defaultdict(float)
        idle_s: Dict[str, float] = defaultdict(float)
        for a, b in zip(cuts, cuts[1:]):
            inner = _innermost(inside, a, b)
            name = inner.name if inner is not None else trace.STEP_SPAN
            self_s[name] += (b - a) / 1e9
            gap = idle(a, b)
            idle_s[name] += gap
            step_idle += gap
        for name, v in self_s.items():
            if name != trace.STEP_SPAN:
                names[name].self_s += v
        for name, v in idle_s.items():
            if name != trace.STEP_SPAN:
                names[name].idle_s += v
        kernel_s: Dict[str, float] = defaultdict(float)
        for _, i in launches[bisect.bisect_left(starts, st.start_ns) : bisect.bisect_left(starts, st.end_ns)]:
            o = ops[i]
            kernel_s[kernel_tag(o.name) or "untagged"] += (o.end_ns - o.start_ns) / 1e9
        per_step[st.step] = StepProgram(inside, dict(self_s), dict(idle_s), dict(kernel_s))
    return Reduction(dict(names), per_step, (hi - lo) / 1e9, step_idle)


# -- the per-layer quantities ------------------------------------------------


def _has_spans(red: Optional[Reduction]) -> bool:
    return red is not None and bool(red.names)


def first_chunk_ms(red: Optional[Reduction]) -> Optional[float]:
    """Mean host milliseconds of the traced first prefill chunks."""
    if not _has_spans(red) or FIRST_CHUNK not in red.names:
        return None
    t = red.names[FIRST_CHUNK]
    return 1e3 * t.host_s / t.count


def first_chunk_idle_share(red: Optional[Reduction]) -> Optional[float]:
    """Device idle inside the first prefill chunks, as a share of the traced
    window (percent)."""
    if not _has_spans(red) or red.window_s <= 0:
        return None
    t = red.names.get(FIRST_CHUNK)
    return 100.0 * (t.idle_in_s if t is not None else 0.0) / red.window_s


def decode_only(step: StepProgram) -> bool:
    names = {p.name for p in step.spans}
    return "engine.decode.dispatch" in names and not names.intersection(PREFILL)


def decode_host_ms(red: Optional[Reduction]) -> Optional[float]:
    """Median host milliseconds per traced decode-only step in the decode
    step's host work: preparing the batch and sampling its tokens."""
    if not _has_spans(red):
        return None
    per = [
        sum((p.end_ns - p.start_ns) for p in s.spans if p.name in DECODE_HOST) / 1e6
        for s in red.steps.values()
        if decode_only(s)
    ]
    return statistics.median(per) if per else None


def idle_by_span(red: Reduction) -> List[Tuple[str, float]]:
    """Device idle seconds inside the steps by the innermost program span
    open meanwhile, largest first (``engine_step``: under none)."""
    total: Dict[str, float] = defaultdict(float)
    for s in red.steps.values():
        for name, v in s.idle_s.items():
            total[name] += v
    return sorted(total.items(), key=lambda kv: -kv[1])


def kernel_ms_by_tag(red: Reduction, chunk: bool) -> Dict[str, float]:
    """Mean Pallas-kernel device milliseconds per traced step by GEMM tag:
    over the decode-only steps, or over the steps that carry a prefill
    chunk (``chunk``); kernels named by their tile alone are ``untagged``."""
    if chunk:
        steps = [s for s in red.steps.values() if {p.name for p in s.spans}.intersection(PREFILL)]
    else:
        steps = [s for s in red.steps.values() if decode_only(s)]
    total: Dict[str, float] = defaultdict(float)
    for s in steps:
        for tag, v in s.kernel_s.items():
            total[tag] += v
    return {tag: 1e3 * v / len(steps) for tag, v in sorted(total.items(), key=lambda kv: -kv[1])}
