"""From a profiler trace to device time per engine step.

``events_from_xplane`` reads the ``.xplane.pb`` that ``jax.profiler`` writes
into plain event lists: the device's operations, and the benchmark's own
host spans (``engine_step`` with its ``step_num``, and ``bench.*``).
``reduce`` takes those lists, with nothing of JAX, and gives:

- ``busy_s`` and ``window_s``: the union of device-operation intervals inside
  the traced window, averaged over the chips, and the window's length;
- per step: device busy seconds, Pallas-kernel (``tpu_custom_call``)
  seconds, and the kernel launches that started inside the step;
- ``breakdown``: the operations that took most device time, and the device's
  idle time split by the benchmark's host span that was open meanwhile.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

#: the host span the benchmark puts around every ``engine.step()``
STEP_SPAN = "engine_step"
#: device lines that hold one event per operation
OP_LINES = ("XLA Ops",)
#: markers of a Pallas (Mosaic) kernel in an operation's name or stats
KERNEL_MARKERS = ("tpu_custom_call",)


@dataclasses.dataclass
class Op:
    device: str
    name: str
    start_ns: float
    end_ns: float
    kernel: bool


@dataclasses.dataclass
class Span:
    name: str
    start_ns: float
    end_ns: float
    step: Optional[int] = None


def _is_kernel(name: str, stats: Dict) -> bool:
    text = " ".join([name, *(str(v) for v in stats.values())])
    return any(m in text for m in KERNEL_MARKERS)


def events_from_xplane(path: str, host_stands_in: bool = False) -> Tuple[List[Op], List[Span]]:
    """Device operations and benchmark host spans of one trace file. A trace
    with no TPU operations raises, unless ``host_stands_in`` (a CPU
    rehearsal): then the host's XLA operations stand in for the device's."""
    from jax.profiler import ProfileData

    return events(ProfileData.from_file(path), path, host_stands_in)


def events(data, path: str, host_stands_in: bool = False) -> Tuple[List[Op], List[Span]]:
    """:func:`events_from_xplane` of a trace already read (``ProfileData``)."""
    ops: List[Op] = []
    host_ops: List[Op] = []
    spans: List[Span] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU"):
            for line in plane.lines:
                if line.name not in OP_LINES:
                    continue
                for e in line.events:
                    stats = dict(e.stats)
                    ops.append(Op(plane.name, e.name, e.start_ns, e.start_ns + e.duration_ns, _is_kernel(e.name, stats)))
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == STEP_SPAN or e.name.startswith("bench."):
                        step = dict(e.stats).get("step_num")
                        spans.append(
                            Span(e.name, e.start_ns, e.start_ns + e.duration_ns, None if step is None else int(step))
                        )
                    elif e.duration_ns > 0:
                        stats = dict(e.stats)
                        if "hlo_op" in stats:
                            host_ops.append(
                                Op(plane.name, e.name, e.start_ns, e.start_ns + e.duration_ns, _is_kernel(e.name, stats))
                            )
    if not ops:
        if not host_stands_in:
            raise ValueError(f"{path} holds no operations on a /device:TPU plane")
        ops = host_ops
    return ops, spans


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged, sorted intervals."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def covered(merged: Sequence[Tuple[float, float]], lo: float, hi: float) -> float:
    """Length of ``merged`` (sorted and disjoint) inside ``[lo, hi]``."""
    i = max(0, bisect.bisect_right(merged, (lo, float("inf"))) - 1)
    total = 0.0
    for a, b in merged[i:]:
        if a >= hi:
            break
        total += max(0.0, min(b, hi) - max(a, lo))
    return total


@dataclasses.dataclass
class StepDevice:
    busy_s: float = 0.0
    kernel_s: float = 0.0
    kernels: int = 0
    kernel_names: List[str] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Reduction:
    busy_s: float
    window_s: float
    steps: Dict[int, StepDevice]
    device_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]
    devices: int


def reduce(ops: Sequence[Op], spans: Sequence[Span], top: int = 10) -> Reduction:
    """Reduce one traced window (see the module doc). The window runs from
    the first benchmark span's start to the last one's end, so that time the
    loop spent waiting for arrivals counts as the device's idle time."""
    steps = [s for s in spans if s.name == STEP_SPAN and s.step is not None]
    if not steps or not ops:
        raise ValueError(f"trace holds {len(steps)} step spans and {len(ops)} device ops")
    lo = min(s.start_ns for s in spans)
    hi = max(s.end_ns for s in spans)
    by_dev: Dict[str, List[Op]] = defaultdict(list)
    for o in ops:
        by_dev[o.device].append(o)
    merged = {d: union([(o.start_ns, o.end_ns) for o in v]) for d, v in by_dev.items()}
    kernels = {d: union([(o.start_ns, o.end_ns) for o in v if o.kernel]) for d, v in by_dev.items()}
    n_dev = len(by_dev)
    busy = sum(covered(m, lo, hi) for m in merged.values()) / n_dev

    launches = sorted((o.start_ns, o.name) for o in ops if o.kernel)
    starts = [t for t, _ in launches]
    per_step: Dict[int, StepDevice] = {}
    for s in steps:
        sd = StepDevice()
        sd.busy_s = sum(covered(m, s.start_ns, s.end_ns) for m in merged.values()) / n_dev / 1e9
        sd.kernel_s = sum(covered(m, s.start_ns, s.end_ns) for m in kernels.values()) / n_dev / 1e9
        first, last = bisect.bisect_left(starts, s.start_ns), bisect.bisect_left(starts, s.end_ns)
        sd.kernels = (last - first) // n_dev
        sd.kernel_names = [name for _, name in launches[first:last]]
        per_step[s.step] = sd

    totals: Dict[str, float] = defaultdict(float)
    for o in ops:
        if o.end_ns > lo and o.start_ns < hi:
            totals[o.name] += (min(o.end_ns, hi) - max(o.start_ns, lo)) / 1e9 / n_dev
    device_ops = sorted(totals.items(), key=lambda kv: -kv[1])[:top]

    # the device's idle time inside the window, split by the benchmark's
    # host spans (which do not overlap); the rest lies outside them
    idle: Dict[str, float] = defaultdict(float)
    for sp in spans:
        a, b = max(sp.start_ns, lo), min(sp.end_ns, hi)
        if b > a:
            idle[sp.name] += sum((b - a) - covered(m, a, b) for m in merged.values()) / n_dev / 1e9
    outside = (hi - lo - busy) / 1e9 - sum(idle.values())
    if outside > 0:
        idle["outside benchmark spans"] += outside
    idle_gaps = sorted(((k, v) for k, v in idle.items() if v > 0), key=lambda kv: -kv[1])[:top]
    return Reduction(busy / 1e9, (hi - lo) / 1e9, per_step, device_ops, idle_gaps, n_dev)
