"""The generator and the end-to-end arithmetic of the loop's records."""

import json
from pathlib import Path

import numpy as np
import pytest

from bench import harness, measure
from bench.generators import standard

TRAFFIC = Path(harness.CHECKOUT) / "bench" / "traffic"


def _mix(name):
    return json.loads((TRAFFIC / f"{name}.json").read_text())


@pytest.mark.parametrize("name", ["decode-batch", "prefill-poisson"])
def test_generator_is_deterministic_per_seed(name):
    mix = _mix(name)
    a, b, c = (standard.make(mix, s, 49152) for s in (2**31 + 11, 2**31 + 11, 7))
    ra = [a.next_closed() if a.closed else None for _ in range(5)] if a.closed else a.schedule(30.0)
    rb = [b.next_closed() for _ in range(5)] if b.closed else b.schedule(30.0)
    rc = [c.next_closed() for _ in range(5)] if c.closed else c.schedule(30.0)
    assert [(r.due_s, r.max_new, r.prompt.tolist()) for r in ra] == [(r.due_s, r.max_new, r.prompt.tolist()) for r in rb]
    assert [r.prompt.tolist() for r in ra] != [r.prompt.tolist() for r in rc]
    # another seed: other token ids, the same lengths at the same times
    assert [(r.due_s, r.max_new, len(r.prompt)) for r in ra] == [(r.due_s, r.max_new, len(r.prompt)) for r in rc]


@pytest.mark.parametrize("name", ["decode-batch", "prefill-poisson"])
def test_lengths_round_as_the_mix_says(name):
    mix = _mix(name)
    t = standard.make(mix, 3, 49152)
    r = mix["prompt"]["round_up"]
    assert all(n % r == 0 for n in t.prompt_lens)
    assert mix["prompt"]["min"] <= t.prompt_lens.min() and t.prompt_lens.max() <= mix["prompt"]["max"]
    o = mix["output"]
    assert o["min"] <= t.output_lens.min() and t.output_lens.max() <= o["max"]
    # another order seed: the same lengths in another order
    u = standard.make({**mix, "order_seed": mix["order_seed"] + 1}, 3, 49152)
    if not t.closed:
        t.schedule(30.0), u.schedule(30.0)
    assert sorted(t.prompt_lens) == sorted(u.prompt_lens) and list(t.prompt_lens) != list(u.prompt_lens)
    assert sorted(t.output_lens) == sorted(u.output_lens)
    assert t.prompt_classes() == sorted(set(range(mix["prompt"]["min"], mix["prompt"]["max"] + 1, r)))


def test_lengths_are_the_distribution_quantiles():
    spec = {"dist": "lognormal", "median": 256, "sigma": 0.7, "min": 64, "max": 1024}
    x = standard.quantiles(spec, 25)
    assert x[12] == 256 and x[0] < 256 < x[-1]
    assert np.all(np.diff(x) >= 0)
    u = standard.quantiles({"dist": "uniform", "min": 16, "max": 64}, 49)
    assert u[0] == 16 and u[-1] == 64


def test_open_loop_schedule_holds_the_rate():
    mix = {**_mix("prefill-poisson"), "arrivals": {"kind": "poisson", "rate_per_s": 3.0}}
    sched = standard.make(mix, 9, 100).schedule(400.0)
    assert all(0 <= a.due_s < b.due_s < 400.0 for a, b in zip(sched, sched[1:]))
    # every order seed: the same count, at other times
    other = standard.make({**mix, "order_seed": 5}, 9, 100).schedule(400.0)
    assert len(sched) == len(other) == 1200
    assert [r.due_s for r in sched] != [r.due_s for r in other]
    gaps = np.diff([r.due_s for r in sched])
    assert np.mean(gaps) == pytest.approx(1 / 3.0, rel=0.05)
    assert np.std(gaps) == pytest.approx(np.mean(gaps), rel=0.15)  # exponential-like


def test_bursty_arrivals_come_in_bursts():
    mix = {**_mix("prefill-poisson"), "arrivals": {"kind": "bursty", "rate_per_s": 2.0, "burst_min": 4, "burst_max": 12}}
    sched = standard.make(mix, 9, 100).schedule(200.0)
    gaps = np.diff([r.due_s for r in sched])
    assert np.mean(gaps == 0.0) > 0.6


def _record(token_times, window=(0.0, 100.0)):
    tracked = []
    for i, ts in enumerate(token_times):
        t = harness.Tracked(i, None, due=0.0, submitted=0.0)
        t.token_t = list(ts)
        tracked.append(t)
    return harness.Record(
        workload="w", config={}, slots=4, window=harness.Window(*window), steps=[], tracked=tracked,
        gemms={}, select_s=0.0, peaks={}, drain_end=window[1],
    )


def test_the_tail_of_all_gaps_sees_one_stalled_step():
    """Four requests decode in lock step every 10 ms for 10 s; one step
    stalls for a second. The p95 of all gaps moves; a median of per-chunk
    medians would not."""
    steps = list(np.arange(1.0, 11.0, 0.01))
    stalled = steps[:500] + [s + 1.0 for s in steps[500:]]
    calm = measure.tpot_p95_ms(_record([steps] * 4))
    gaps = measure.token_gaps(_record([stalled] * 4))
    assert calm == pytest.approx(10.0, rel=1e-6)
    assert max(gaps) == pytest.approx(1.01)
    # a window where a tenth of the steps stall shows it at the p95
    slow = [s + 0.05 * (i // 10) for i, s in enumerate(steps)]
    assert measure.tpot_p95_ms(_record([slow] * 4)) == pytest.approx(60.0, rel=1e-3)
    chunks = np.array_split(np.diff(slow), 20)
    assert float(np.median([np.median(c) for c in chunks])) * 1e3 == pytest.approx(10.0, rel=1e-3)


def test_ttft_median_is_over_all_requests_due():
    """Twenty requests due at 0 s, first tokens at 1..20 s: the median is
    taken over every request, so one late request moves the tail and
    leaves the median."""
    firsts = [float(i) for i in range(1, 21)]
    assert measure.ttft_p50_ms(_record([[t] for t in firsts])) == pytest.approx(10_500.0)
    late = firsts[:-1] + [60.0]
    assert measure.ttft_p50_ms(_record([[t] for t in late])) == pytest.approx(10_500.0)
    assert measure.ttft_ms(_record([[t] for t in late]), 95) > measure.ttft_ms(_record([[t] for t in firsts]), 95)


def test_failed_requests_count_until_the_drain_ends():
    rec = _record([[5.0], []], window=(0.0, 50.0))
    rec.drain_end = 80.0
    assert sorted(measure.ttft_s(rec)) == [5.0, 80.0]
