"""Arch-class federation: profile classification, per-class partitioning,
cross-arch warm seeding, the SelectorState install path, and the tagged
journal-entry registry's forward compatibility.

The multi-device CI lane also runs this file (arch classes exist for
heterogeneous fleets); every test here is device-count-agnostic."""

import dataclasses
import json
import logging

import jax
import pytest

from repro.core.arch import DEFAULT_ARCH, ArchProfile, append_arch, detect_arch
from repro.core.adaptive import AdaptiveConfig, AdaptiveTuner
from repro.core.bloom import optimal_params
from repro.core.calibrate import CalibratedMachine
from repro.core.costmodel import V5E
from repro.core.federate import federate_selector, merge_databases
from repro.core.selector import KernelSelector, SelectorState
from repro.core.tuner import (
    Tuner,
    TuningDatabase,
    TuningRecord,
    journal_entry,
)

SIZES = [(64, 512, 256), (128, 256, 512), (32, 1024, 128)]


def _rec(size=(64, 512, 256), policy="dp", tflops=1.0, arch=DEFAULT_ARCH, wall=0.0):
    return TuningRecord(
        size=size,
        policy=policy,
        cfg="128x128x128",
        tflops=tflops,
        runner_up_policy="all_sk",
        runner_up_tflops=tflops * 0.9,
        dp_best_tflops=tflops,
        g=8,
        wall=wall,
        arch=arch,
    )


# -- ArchProfile classification ---------------------------------------------


def test_arch_profile_cls_is_stable_and_readable():
    p = ArchProfile(backend="tpu", lanes=8, vmem_bytes=16 << 20, flops_per_byte=250)
    assert p.cls == "tpu:l8:v16m:r250"


def test_from_machine_quantizes_roofline_ratio():
    # two hosts of one generation with slightly different calibrated
    # constants must land in the same class (ratio centered in a bin so
    # the perturbation exercises quantization, not a bin boundary)
    base = dataclasses.replace(V5E, hbm_bw=V5E.peak_flops / 250.0)
    a = dataclasses.replace(base, hbm_bw=base.hbm_bw * 1.02)
    b = dataclasses.replace(base, hbm_bw=base.hbm_bw * 0.98)
    assert ArchProfile.from_machine(a).cls == ArchProfile.from_machine(b).cls
    assert ArchProfile.from_machine(a).flops_per_byte == 250


def test_arch_profile_json_roundtrip_rederives_cls():
    p = detect_arch()
    d = p.to_json()
    assert d["cls"] == p.cls
    d["cls"] = "hand:edited"  # redundant field must not desynchronize
    assert ArchProfile.from_json(d) == p
    assert ArchProfile.from_json(d).cls == p.cls


def test_default_arch_record_serializes_without_arch_field():
    # byte-compat: a default-class journal line is identical to pre-arch
    line = journal_entry(_rec())
    assert "arch" not in json.loads(line)["record"]
    stamped = journal_entry(_rec(arch="tpu:l8:v16m:r275"))
    assert json.loads(stamped)["record"]["arch"] == "tpu:l8:v16m:r275"


# -- legacy artifacts land in the "default" class ---------------------------


def test_archless_journal_federates_into_default_class(tmp_path):
    shard = str(tmp_path / "legacy.jsonl")
    Tuner().tune(SIZES, journal=shard)  # default Tuner: arch-less lines

    sel = KernelSelector()  # default class
    state = federate_selector(sel, journals=[shard])
    assert state.merged == len(SIZES)
    # every record landed in the own-class partition under "default"...
    assert set(sel.db.records) == {tuple(s) for s in SIZES}
    assert all(r.arch == DEFAULT_ARCH for r in sel.db.records.values())
    assert not sel.db.xarch
    # ...and dispatches identically to a direct database hit
    for m, n, k in SIZES:
        chosen = sel.select(m, n, k)
        rec = sel.db.records[(m, n, k)]
        assert chosen.source == "tuned"
        assert (chosen.policy.name, chosen.g) == (rec.policy, rec.g)


def test_archless_calibration_parses_into_default_class():
    cm = CalibratedMachine(wall=1.0)
    assert cm.arch == DEFAULT_ARCH
    db = TuningDatabase()
    assert db.set_calibration(cm, stamp=False)
    assert db.calibration is cm
    assert not db.xarch_calibrations


def test_foreign_class_calibration_routes_to_side_table():
    cm = CalibratedMachine(wall=1.0, arch="tpu:l8:v16m:r275")
    db = TuningDatabase()  # default class
    db.set_calibration(cm, stamp=False)
    assert db.calibration is None  # never steers local model-first dispatch
    assert db.xarch_calibrations["tpu:l8:v16m:r275"] is cm


# -- tagged journal registry: forward compatibility -------------------------


def test_unknown_tag_lines_skip_and_count_without_warning(tmp_path, caplog):
    shard = tmp_path / "mixed.jsonl"
    lines = [
        journal_entry(_rec()),
        json.dumps({"telemetry": {"qps": 1200}}),  # a future producer's type
        journal_entry(_rec(size=(128, 256, 512))),
    ]
    shard.write_text("\n".join(lines) + "\n")
    db = TuningDatabase()
    with caplog.at_level(logging.DEBUG, logger="repro.tuner"):
        applied = db.replay_journal(str(shard))
    assert applied == 2
    assert len(db.records) == 2
    assert db.load_errors == 1  # the skip stays visible...
    warnings_seen = [r for r in caplog.records if r.levelno >= logging.WARNING]
    assert not warnings_seen  # ...but is NOT warned as malformed


def test_arch_entry_replays_into_profile_table(tmp_path):
    shard = str(tmp_path / "arch.jsonl")
    profile = detect_arch()
    append_arch(shard, profile)
    db = TuningDatabase()
    assert db.replay_journal(shard) == 1
    assert db.arch_profiles[profile.cls] == profile
    assert db.load_errors == 0


# -- cross-arch dispatch: seeds, never direct hits --------------------------


def test_cross_arch_record_is_xarch_seed_never_direct_hit():
    foreign = _rec(policy="sk2dp", arch="tpu:l8:v16m:r275", wall=1.0)
    db = TuningDatabase(arch="tpu:l8:v16m:r225")
    db.add_record(foreign, stamp=False)
    assert not db.records  # routed to the foreign-class partition
    assert db.xarch["tpu:l8:v16m:r275"][foreign.size] is foreign

    sel = KernelSelector(state=SelectorState(db=db, arch="tpu:l8:v16m:r225"))
    chosen = sel.select(*foreign.size)
    assert chosen.source == "xarch"
    assert sel.stats.xarch_seeds == 1
    # the seed set is the foreign winner + runner-up, re-ranked locally
    assert chosen.policy.name in (foreign.policy, foreign.runner_up_policy)


def test_xarch_seed_superseded_by_local_adaptation():
    foreign = _rec(arch="tpu:l8:v16m:r275", wall=1.0)
    db = TuningDatabase(arch=DEFAULT_ARCH)
    db.add_record(foreign, stamp=False)
    sel = KernelSelector(state=SelectorState(db=db))
    adaptive = AdaptiveTuner(sel, config=AdaptiveConfig(hot_threshold=1))

    assert sel.select(*foreign.size).source == "xarch"  # still a miss
    assert adaptive.stats.misses == 1
    assert adaptive.drain() == 1
    after = sel.select(*foreign.size)
    assert after.source == "tuned"
    assert sel.db.records[foreign.size].arch == DEFAULT_ARCH
    # the foreign copy survives as provenance, not as the dispatch source
    assert sel.db.xarch["tpu:l8:v16m:r275"][foreign.size] is foreign


def test_same_class_merge_is_direct_hit_other_class_is_not(tmp_path):
    cls = "tpu:l8:v16m:r275"
    same = TuningDatabase(arch=cls)
    same.add_record(_rec(policy="sk2dp", arch=cls, wall=1.0), stamp=False)
    other = TuningDatabase(arch="tpu:l8:v16m:r225")
    other.add_record(
        _rec(size=(128, 256, 512), arch="tpu:l8:v16m:r225", wall=1.0), stamp=False
    )
    into = TuningDatabase(arch=cls)
    merge_databases([same, other], into=into)
    assert set(into.records) == {(64, 512, 256)}  # same class: direct
    assert set(into.xarch["tpu:l8:v16m:r225"]) == {(128, 256, 512)}


# -- SelectorState install path ---------------------------------------------


def test_legacy_artifact_kwargs_emit_deprecation_warning():
    db = TuningDatabase()
    with pytest.warns(DeprecationWarning, match="SelectorState"):
        KernelSelector(db=db)
    sel = KernelSelector()
    with pytest.warns(DeprecationWarning, match="hot_swap"):
        sel.hot_swap(db=db)
    assert sel.db is db


def test_state_path_and_bare_calls_do_not_warn(recwarn):
    sel = KernelSelector(state=SelectorState(db=TuningDatabase()))
    sel.hot_swap(state=SelectorState())
    sel.hot_swap(keys=[(64, 512, 256)])  # keys-only invalidation
    sel.hot_swap()  # bare full invalidation
    KernelSelector()
    deprecations = [w for w in recwarn if w.category is DeprecationWarning]
    assert not deprecations


def test_state_mixed_with_legacy_kwargs_raises():
    with pytest.raises(TypeError, match="not both"):
        KernelSelector(state=SelectorState(), db=TuningDatabase())
    sel = KernelSelector()
    with pytest.raises(TypeError, match="not both"):
        sel.hot_swap(state=SelectorState(), sieve=None or TuningDatabase())


def test_hot_swap_state_installs_all_artifacts_atomically():
    db = TuningDatabase()
    db.add_record(_rec())
    sieve = db.build_sieve(generation=3)
    cm = CalibratedMachine(wall=1.0)
    sel = KernelSelector()
    sel.select(64, 512, 256)
    state = SelectorState(db=db, sieve=sieve, calibration=cm, arch=DEFAULT_ARCH)
    dropped = sel.hot_swap(state=state)
    assert dropped == 1  # new calibration identity drops the whole memo
    assert sel.state is state
    assert (sel.db, sel.sieve, sel.calibration) == (db, sieve, cm)
    assert sel.sieve_generation == 3
    assert sel.select(64, 512, 256).source == "tuned"


def test_federate_selector_returns_installed_state_with_report(tmp_path):
    shard = str(tmp_path / "s.jsonl")
    Tuner().tune(SIZES, journal=shard)
    sel = KernelSelector()
    state = federate_selector(sel, journals=[shard])
    assert isinstance(state, SelectorState)
    assert sel.state is state  # what it returned is what it installed
    assert state.merged == len(SIZES)  # MergeReport rides on the state
    assert state.conflicts == 0


# -- federate_selector sieve-geometry bugfix --------------------------------


def test_federate_inherits_installed_sieve_geometry(tmp_path):
    shard = str(tmp_path / "s.jsonl")
    Tuner().tune(SIZES, journal=shard)
    db = TuningDatabase()
    db.add_record(_rec(size=(8, 8, 8)))
    sel = KernelSelector(
        state=SelectorState(db=db, sieve=db.build_sieve(capacity=512, fp_rate=0.05))
    )
    state = federate_selector(sel, journals=[shard])
    # the rebuilt sieve keeps the worker's installed geometry, not the
    # historical fixed (10_000, 0.01) defaults
    n_bits, n_hashes = optimal_params(512, 0.05)
    got = next(iter(state.sieve.filters.values()))
    # BloomFilter pads n_bits up to a whole byte
    assert (got.n_bits, got.n_hashes) == (n_bits + (-n_bits % 8), n_hashes)
    assert (state.sieve.capacity, state.sieve.fp_rate) == (512, 0.05)


def test_federate_explicit_mismatched_geometry_raises_early(tmp_path):
    shard = str(tmp_path / "s.jsonl")
    Tuner().tune(SIZES, journal=shard)
    db = TuningDatabase()
    db.add_record(_rec(size=(8, 8, 8)))
    sel = KernelSelector(
        state=SelectorState(db=db, sieve=db.build_sieve(capacity=512, fp_rate=0.05))
    )
    before = sel.state
    with pytest.raises(ValueError, match="mismatched parameters") as ei:
        federate_selector(sel, journals=[shard], capacity=10_000, fp_rate=0.01)
    # both configurations are named, and nothing was installed
    assert "10000" in str(ei.value).replace("10_000", "10000")
    assert sel.state is before


def test_federate_explicit_matching_geometry_is_accepted(tmp_path):
    shard = str(tmp_path / "s.jsonl")
    Tuner().tune(SIZES, journal=shard)
    db = TuningDatabase()
    db.add_record(_rec(size=(8, 8, 8)))
    sel = KernelSelector(
        state=SelectorState(db=db, sieve=db.build_sieve(capacity=512, fp_rate=0.05))
    )
    state = federate_selector(sel, journals=[shard], capacity=512, fp_rate=0.05)
    assert state.merged == len(SIZES) + 1


def test_detect_arch_raises_when_the_backend_fails(monkeypatch):
    # a backend that fails to start is an error, never a "cpu" class
    def broken():
        raise RuntimeError("backend down")

    monkeypatch.setattr(jax, "devices", broken)
    with pytest.raises(RuntimeError, match="backend down"):
        detect_arch()
