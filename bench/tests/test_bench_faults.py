"""A run with the timed path broken underneath comes out not correct.

Each test drives the whole of a run but the look for a chip (the CPU
rehearsal sizes of ``granite-8b.decode-batch``), with one fault planted in
the program that the window drives: a decode step that returns the KV pool
unchanged, half of the decode batch left out, a served token altered where
it is sampled. The exchange between chips does not exist in a one-chip
cell. A sound run of the same seed passes.
"""

import numpy as np
import pytest

from bench import correct, harness, peaks, run

SEED = 3


def _run(control=False):
    cell = harness.Cell.load(harness.CHECKOUT, "granite-8b.decode-batch", rehearsal=True)
    # short outputs, so that requests finish inside a few seconds of window
    # on a loaded CPU; every finished request is compared, as in a run
    cell.mix = {**cell.mix, "output": {"dist": "fixed", "value": 10}}
    out = run.run_cell(cell, SEED, 4.0, traced=False, backend="xla", pk=peaks.peaks("TPU v5 lite"), control=control)
    # every finished request is compared
    assert out["cmp"]["requests"] == out["finished"] > 0, out["cmp"]
    return out["cmp"], cell.limits


def test_a_sound_run_is_correct_and_the_control_is_not():
    cmp, limits = _run(control=True)
    assert correct.correct(cmp, limits), cmp
    # the int8 forward pass, put in the program's place, is not correct
    assert not correct.correct(correct.as_control(cmp), limits), cmp


def _stale_pool(monkeypatch):
    from repro.serve.scheduler import PagedServeEngine

    impl = PagedServeEngine._decode_impl

    def decode(self, params, pool, pages_2d, tokens, pos):
        logits, _ = impl(self, params, pool, pages_2d, tokens, pos)
        return logits, pool  # the step's KV rows never reach the pool

    monkeypatch.setattr(PagedServeEngine, "_decode_impl", decode)


def _half_batch(monkeypatch):
    from repro.serve.scheduler import PagedServeEngine

    impl = PagedServeEngine._decode_impl

    def decode(self, params, pool, pages_2d, tokens, pos):
        logits, pool = impl(self, params, pool, pages_2d, tokens, pos)
        half = logits.shape[0] // 2
        return logits.at[half:].set(logits[:1]), pool  # rows past half get row 0's

    monkeypatch.setattr(PagedServeEngine, "_decode_impl", decode)


def _altered_token(monkeypatch):
    from repro.serve.engine import EngineCore

    sample = EngineCore._sample
    calls = [0]

    def altered(self, logits, temperature):
        calls[0] += 1
        tok = sample(self, logits, temperature)
        return (tok + 1) % len(logits) if calls[0] % 3 == 0 else tok

    monkeypatch.setattr(EngineCore, "_sample", altered)


@pytest.mark.parametrize("fault", [_stale_pool, _half_batch, _altered_token])
def test_a_planted_fault_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    cmp, limits = _run()
    assert not correct.correct(cmp, limits), cmp
    assert np.isfinite(cmp["widest_gap"])
