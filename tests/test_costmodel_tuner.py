"""Cost model, tuner (ckProfiler analogue) and selector tests."""

import os

import pytest

from repro.core import costmodel
from repro.core.policies import ALL_POLICIES, ALL_SK, DP, HYBRIDS, TileConfig
from repro.core.selector import KernelSelector, default_selector
from repro.core.tuner import Tuner, TuningDatabase
from repro.core.workpart import GemmShape


def test_dp_optimal_on_divisible_big_gemm():
    """No quantization pathology -> Stream-K adds only overhead."""
    s = GemmShape(8192, 8192, 4096)
    dp = costmodel.best_config(s, DP)[1]
    for pol in (ALL_SK, *HYBRIDS):
        assert costmodel.best_config(s, pol)[1] <= dp + 1e-9


def test_streamk_wins_on_quantized_shape():
    """T mod C pathological -> SK-based schedule beats DP (the paper's
    headline mechanism)."""
    s = GemmShape(1152, 1152, 8192)  # 81 tiles over 8 lanes with 512-tiles
    dp = costmodel.best_config(s, DP)[1]
    best_sk = max(costmodel.best_config(s, p)[1] for p in (ALL_SK, *HYBRIDS))
    assert best_sk > dp * 1.05


def test_costmodel_monotone_in_flops():
    t1 = costmodel.gemm_time_s(GemmShape(1024, 1024, 1024), TileConfig(128, 128, 128), DP)
    t2 = costmodel.gemm_time_s(GemmShape(2048, 2048, 2048), TileConfig(128, 128, 128), DP)
    assert t2 > t1


def test_vmem_guard():
    mach = costmodel.Machine(vmem_bytes=100)  # nothing fits
    with pytest.raises(AssertionError):
        costmodel.best_config(GemmShape(256, 256, 256), DP, mach)


def test_tuner_and_db_roundtrip(tmp_path):
    sizes = [(64, 64, 64), (1152, 1152, 8192), (1, 4096, 65536), (8192, 8192, 512)]
    db = Tuner().tune(sizes)
    assert set(db.records) == set(sizes)
    for s, rec in db.records.items():
        assert rec.tflops >= rec.runner_up_tflops > 0
        assert rec.dp_best_tflops > 0
    path = os.path.join(tmp_path, "db.json")
    db.save(path)
    db2 = TuningDatabase.load(path)
    assert db2.records.keys() == db.records.keys()
    for s in sizes:
        assert db2.records[s].policy == db.records[s].policy
        assert db2.per_policy[s] == db.per_policy[s]


def test_selector_paths():
    sizes = [(64, 64, 64), (1152, 1152, 8192), (640, 768, 32768)]
    db = Tuner().tune(sizes)
    sieve = db.build_sieve()
    sel = KernelSelector(sieve=sieve, db=db)

    # tuned hit
    s0 = sel.select(*sizes[0])
    assert s0.source == "tuned"
    # sieve path: drop the db so it must consult the filters
    sel2 = KernelSelector(sieve=sieve, db=None)
    s1 = sel2.select(*sizes[1])
    assert s1.source in ("sieve", "fallback")
    # unknown size -> fallback (with high probability all filters miss)
    s2 = sel2.select(31, 77, 1023)
    assert s2.source in ("fallback", "sieve")
    # caching: same selection object
    assert sel.select(*sizes[0]) is s0


def test_selector_matches_tuner_winner():
    """Selection through the sieve must recover the tuned winner's policy
    for sizes the tuner saw (modulo Bloom false positives, which can only
    ADD candidates, never remove the winner)."""
    sizes = [(1152, 1152, 8192), (8192, 8192, 4096), (1, 64, 16)]
    db = Tuner().tune(sizes)
    sieve = db.build_sieve()
    sel = KernelSelector(sieve=sieve, db=None)
    for s in sizes:
        got = sel.select(*s)
        assert got.policy.name == db.records[s].policy


def test_default_selector_scores_all():
    sel = default_selector()
    out = sel.select(256, 256, 256)
    assert out.source == "fallback"
    assert sel.stats.evals >= len(ALL_POLICIES)


# -- the selector's machine per device kind ----------------------------------


def test_device_machine_keys_tpu_peaks_by_device_kind():
    from types import SimpleNamespace

    v5e = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    mach = costmodel.device_machine(v5e)
    assert (mach.peak_flops, mach.hbm_bw) == (197e12, 819e9)
    with pytest.raises(ValueError, match="TPU v9"):
        costmodel.device_machine(SimpleNamespace(platform="tpu", device_kind="TPU v9"))
    # off the chip, scoring keeps the modeled v5e
    assert costmodel.device_machine(SimpleNamespace(platform="cpu", device_kind="cpu")) is costmodel.V5E
    assert KernelSelector().mach is costmodel.V5E
