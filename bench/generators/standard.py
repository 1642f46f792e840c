"""The general traffic generator: lengths and arrivals from a mix's data file.

A mix file (``bench/traffic/<mix>.json``) names this module under
``"generator"`` and gives:

- ``prompt`` and ``output``: a length distribution each, ``{"dist":
  "lognormal", "median": m, "sigma": s, "min": a, "max": b, "round_up": r}``,
  ``{"dist": "uniform", "min": a, "max": b}`` or ``{"dist": "fixed",
  "value": v}``. A length is drawn, clipped to ``[min, max]``, then rounded up
  to a multiple of ``round_up`` (default 1) and clipped again.
- ``arrivals``: ``{"kind": "closed"}`` (the queue is refilled whenever fewer
  than ``slots`` requests wait), ``{"kind": "poisson", "rate_per_s": r}`` or
  ``{"kind": "bursty", "rate_per_s": r, "burst_min": a, "burst_max": b}``
  (bursts of a..b requests at once, gaps drawn so the mean rate is r). A
  Poisson window of T seconds holds exactly ``round(r * T)`` requests at
  uniform random times: a Poisson process given its count, so that every
  seed offers the same amount of work.
- ``pool_size``: how many requests a closed loop cycles through. Their
  lengths are the distribution's quantiles at ``(i + 1/2) / pool_size``, not
  draws; an open loop's window takes its own count of quantiles.
- ``order_seed``: the order of the prompt lengths and of the output lengths,
  the Poisson times and a bursty mix's gaps are drawn from it, not from the
  run's seed. Every seed then offers the same work at the same times: the
  order of the lengths decides which requests share a decode batch, and so
  the table widths and the step times. The run's seed gives the token ids
  (and the benchmark's weights).

Every request is greedy; its output runs to its drawn length.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Dict, List, Optional

import numpy as np


@dataclasses.dataclass(frozen=True)
class Request:
    """One request as offered: when it is due (seconds after the window
    opens; 0 for a closed loop), its prompt, and how many tokens to serve."""

    index: int
    due_s: float
    prompt: np.ndarray
    max_new: int


def quantiles(spec: Dict, n: int) -> np.ndarray:
    """The distribution's lengths at the quantiles ``(i + 1/2) / n``."""
    q = (np.arange(n) + 0.5) / n
    dist = spec["dist"]
    if dist == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(p)) for p in q])
        x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    elif dist == "uniform":
        x = spec["min"] + np.floor(q * (spec["max"] - spec["min"] + 1))
    elif dist == "fixed":
        x = np.full(n, float(spec["value"]))
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    return round_lengths(spec, x)


def round_lengths(spec: Dict, x: np.ndarray) -> np.ndarray:
    """Clip to ``[min, max]``, round up to ``round_up``, clip again."""
    lo = spec.get("min", spec.get("value"))
    hi = spec.get("max", spec.get("value"))
    r = int(spec.get("round_up", 1))
    x = np.clip(np.ceil(np.asarray(x, np.float64)), lo, hi)
    x = np.ceil(x / r) * r
    return np.clip(x, lo, hi).astype(np.int64)


def length_classes(spec: Dict) -> List[int]:
    """Every length the spec can produce, ascending (warm-up uses these)."""
    lo = spec.get("min", spec.get("value"))
    hi = spec.get("max", spec.get("value"))
    return sorted({int(v) for v in round_lengths(spec, np.arange(lo, hi + 1))})


class Traffic:
    """The request stream of one mix under one seed."""

    def __init__(self, mix: Dict, seed: int, vocab: int):
        self.mix = mix
        self.arrivals = mix["arrivals"]
        self.closed = self.arrivals["kind"] == "closed"
        self.vocab = int(vocab)
        self.order = int(mix.get("order_seed", 0))
        self._tokens = np.random.default_rng([seed, 3])
        self._times = np.random.default_rng([self.order, 4])
        self._next = 0
        self._pool(int(mix.get("pool_size", 64)))

    def _pool(self, n: int) -> None:
        """``n`` requests' lengths, each list in an order from ``order_seed``."""
        self.prompt_lens = quantiles(self.mix["prompt"], n)[np.random.default_rng([self.order, 1]).permutation(n)]
        self.output_lens = quantiles(self.mix["output"], n)[np.random.default_rng([self.order, 2]).permutation(n)]

    @property
    def rate_per_s(self) -> Optional[float]:
        return None if self.closed else float(self.arrivals["rate_per_s"])

    def _gaps(self, n: int) -> List[float]:
        """A bursty mix's first ``n`` gaps: bursts of ``burst_min`` to
        ``burst_max`` requests at once, spaced so the mean rate holds."""
        rng = np.random.default_rng([self.order, 5])
        lo, hi = int(self.arrivals["burst_min"]), int(self.arrivals["burst_max"])
        rate = float(self.arrivals["rate_per_s"])
        gaps: List[float] = []
        while len(gaps) < n:
            burst = int(rng.integers(lo, hi + 1))
            gaps.append(float(rng.exponential(burst / rate)))
            gaps.extend([0.0] * (burst - 1))
        return gaps[:n]

    def _make(self, i: int, due: float) -> Request:
        k = i % len(self.prompt_lens)
        plen = int(self.prompt_lens[k])
        prompt = self._tokens.integers(1, self.vocab, size=plen).astype(np.int32)
        return Request(i, due, prompt, int(self.output_lens[k]))

    def next_closed(self) -> Request:
        """The next request of a closed loop."""
        r = self._make(self._next, 0.0)
        self._next += 1
        return r

    def schedule(self, seconds: float) -> List[Request]:
        """Every open-loop request due in ``[0, seconds)``, in due order."""
        kind = self.arrivals["kind"]
        if kind == "poisson":
            n = int(round(self.rate_per_s * seconds))
            due = list(np.sort(self._times.uniform(0.0, seconds, n)))
        elif kind == "bursty":
            due = list(np.cumsum(self._gaps(int(self.rate_per_s * seconds) + 64)))
            due = [t for t in due if t < seconds]
        else:
            raise ValueError(f"a {kind} loop has no schedule")
        self._pool(max(1, len(due)))
        return [self._make(i, float(t)) for i, t in enumerate(due)]

    def prompt_classes(self) -> List[int]:
        return length_classes(self.mix["prompt"])

    def max_output(self) -> int:
        return length_classes(self.mix["output"])[-1]


def make(mix: Dict, seed: int, vocab: int) -> Traffic:
    return Traffic(mix, seed, vocab)
