"""Wall-clock helpers: a context-manager timer, an EWMA used by the
straggler monitor, and the serving program's spans.

:class:`span` marks one stretch of host work twice: as a
``jax.profiler.TraceAnnotation``, so that a profile shows it on the same
clock as the device's operations, and in a counters dict
(``name -> SpanStats``), which it charges one count and the host seconds
it took. A process-wide ``jax.monitoring`` listener charges each backend
compile and persistent-cache load to the innermost open span that keeps
counters. Spans have no switch: with no profile running a span costs a
few microseconds of host time. Open them on the host, never inside a
jitted function, where they would time the trace only.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax


class Timer:
    """``with Timer() as t: ...; t.seconds``"""

    def __enter__(self):
        self._t0 = time.perf_counter()
        self.seconds = 0.0
        return self

    def __exit__(self, *exc):
        self.seconds = time.perf_counter() - self._t0
        return False


@dataclass
class EWMA:
    """Exponentially-weighted moving average + variance (for straggler
    detection: flag samples > mean + k*std)."""

    alpha: float = 0.1
    mean: float = 0.0
    var: float = 0.0
    count: int = 0

    def update(self, x: float) -> None:
        if self.count == 0:
            self.mean = x
            self.var = 0.0
        else:
            delta = x - self.mean
            self.mean += self.alpha * delta
            self.var = (1 - self.alpha) * (self.var + self.alpha * delta * delta)
        self.count += 1

    @property
    def std(self) -> float:
        return self.var**0.5

    def is_outlier(self, x: float, k: float = 3.0, min_samples: int = 5) -> bool:
        if self.count < min_samples:
            return False
        return x > self.mean + k * max(self.std, 1e-9)


@dataclass
class SpanStats:
    """What the spans of one name cost, over the life of their counters."""

    count: int = 0
    seconds: float = 0.0  # host seconds, inner spans included
    compile_requests: int = 0  # programs JAX asked the backend for, loads included
    cache_loads: int = 0  # of those, loaded from the persistent compile cache
    cache_load_s: float = 0.0

    @property
    def compiles(self) -> int:
        return self.compile_requests - self.cache_loads


#: the spans open on each thread, innermost last
_open = threading.local()
_listening = False


def _stack() -> List[Tuple[str, Optional[SpanStats]]]:
    stack = getattr(_open, "spans", None)
    if stack is None:
        stack = _open.spans = []
    return stack


def _on_event(event: str, seconds: float, **_) -> None:
    # JAX reports a backend compile around every program it needs, also one
    # it then loads from the persistent cache (see ``SpanStats.compiles``)
    compile_ = "backend_compile" in event
    if not compile_ and "cache_retrieval" not in event:
        return
    for _, st in reversed(_stack()):
        if st is not None:
            if compile_:
                st.compile_requests += 1
            else:
                st.cache_loads += 1
                st.cache_load_s += seconds
            return


def _listen() -> None:
    global _listening
    if not _listening:
        # JAX keeps every listener it is given: register one per process
        jax.monitoring.register_event_duration_secs_listener(_on_event)
        _listening = True


class span:
    """``with span("engine.decode.wait", counters, rows=12) as s: ...``

    ``counters`` (``name -> SpanStats``, filled on first use) is charged
    when the span closes; ``None`` leaves the span in the profile only.
    ``s.seconds`` holds the span's host seconds after it closes, and
    ``s.annotate(key=value)`` adds arguments known only inside it."""

    __slots__ = ("name", "counters", "_trace", "_t0", "seconds")

    def __init__(self, name: str, counters: Optional[Dict[str, SpanStats]], **args):
        self.name = name
        self.counters = counters
        self._trace = jax.profiler.TraceAnnotation(name, **args)
        self.seconds = 0.0

    def annotate(self, **args) -> None:
        self._trace.set_metadata(**args)

    def __enter__(self) -> "span":
        _listen()
        st = None
        if self.counters is not None:
            st = self.counters.get(self.name)
            if st is None:
                st = self.counters[self.name] = SpanStats()
        _stack().append((self.name, st))
        self._trace.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.seconds = time.perf_counter() - self._t0
        self._trace.__exit__(*exc)
        _, st = _stack().pop()
        if st is not None:
            st.count += 1
            st.seconds += self.seconds
        return False
