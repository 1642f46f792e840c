"""Federated tuning: sharded sweep + merge equivalence, last-writer-wins
semantics, cross-worker database hits after federation, torn-write journal
recovery, Bloom/sieve merge validation, and mesh-local fingerprints.

The multi-device CI lane runs this file under
``XLA_FLAGS=--xla_force_host_platform_device_count=8``; the mesh tests
skip themselves on fewer devices so the plain tier-1 run stays green."""

import dataclasses
import json

import jax
import pytest

from repro.core.adaptive import AdaptiveConfig, AdaptiveTuner
from repro.core.bloom import BloomFilter
from repro.core.federate import (
    MergeReport,
    apply_journal_db,
    federate_selector,
    merge_databases,
    merge_journal_shards,
    merge_records,
    merge_sieves,
    record_payload,
    selection_table,
)
from repro.core.op import Epilogue, GemmOp
from repro.core.opensieve import OpenSieve
from repro.core.selector import KernelSelector
from repro.core.tuner import (
    Tuner,
    TuningDatabase,
    TuningRecord,
    journal_entry,
    shard_targets,
)
from repro.core.policies import ALL_POLICIES

TARGETS = [
    (64, 512, 256),
    (128, 256, 512),
    (32, 1024, 128),
    (48, 640, 320),
    (256, 256, 256),
    (8, 2048, 512),
    GemmOp.plain(96, 384, 256, in_dtype="bfloat16"),
    GemmOp.plain(16, 1536, 896, in_dtype="bfloat16"),
    GemmOp(64, 256, 128, g=8, kind="grouped"),
    GemmOp(8, 768, 640, g=4, kind="grouped"),
    GemmOp.plain(128, 128, 512, epilogue=Epilogue(activation="gelu")),
    GemmOp.plain(32, 640, 256, epilogue=Epilogue(bias=True, activation="silu")),
]


def _key(t):
    return t.key if isinstance(t, GemmOp) else tuple(t)


def _rec(size=(64, 512, 256), policy="dp", tflops=1.0, version=0, g=8, wall=0.0):
    return TuningRecord(
        size=size,
        policy=policy,
        cfg="128x128x128",
        tflops=tflops,
        runner_up_policy="sk_one_tile",
        runner_up_tflops=tflops * 0.9,
        dp_best_tflops=tflops,
        g=g,
        version=version,
        wall=wall,
    )


# -- sharded sweeps ----------------------------------------------------------


def test_shard_targets_disjoint_cover():
    for n in (1, 2, 3, 4, 5):
        slices = [shard_targets(TARGETS, i, n) for i in range(n)]
        seen = [_key(t) for sl in slices for t in sl]
        assert sorted(map(str, seen)) == sorted(str(_key(t)) for t in TARGETS)
        flat = set()
        for sl in slices:
            keys = {str(_key(t)) for t in sl}
            assert not (flat & keys)  # disjoint
            flat |= keys


def test_shard_targets_validates():
    with pytest.raises(ValueError):
        shard_targets(TARGETS, 2, 2)
    with pytest.raises(ValueError):
        shard_targets(TARGETS, -1, 2)
    with pytest.raises(ValueError):
        shard_targets(TARGETS, 0, 0)


@pytest.mark.parametrize("workers", [2, 4])
def test_sharded_sweep_merge_equals_full_sweep(workers, tmp_path):
    """The acceptance bar: N sharded sweeps, journals merged, must yield a
    database, per-fingerprint Selection, and sieve identical to the
    single-worker full sweep."""
    tuner = Tuner()
    full = tuner.tune(TARGETS)
    full_sieve = full.build_sieve()

    paths = []
    shard_dbs = []
    for i in range(workers):
        p = str(tmp_path / f"shard{i}.jsonl")
        shard_dbs.append(tuner.tune(TARGETS, shard=(i, workers), journal=p))
        paths.append(p)
    merged, report = merge_journal_shards(paths)

    # records identical modulo the producers' local commit clocks
    assert set(merged.records) == set(full.records)
    for key in full.records:
        assert record_payload(merged.records[key]) == record_payload(
            full.records[key]
        )
    assert report.conflicts == 0 and report.load_errors == 0
    assert report.examined == len(TARGETS)

    # per-fingerprint Selection (policy, cfg, g) identical through a selector
    merged_sieve = merge_sieves([db.build_sieve() for db in shard_dbs])
    sel_m = KernelSelector(sieve=merged_sieve, db=merged)
    sel_f = KernelSelector(sieve=full_sieve, db=full)
    assert selection_table(sel_m, full.records) == selection_table(
        sel_f, full.records
    )
    for t in TARGETS:
        op = t if isinstance(t, GemmOp) else GemmOp.plain(*t)
        a, b = sel_m.select_op(op), sel_f.select_op(op)
        assert (a.policy, a.cfg, a.g, a.source) == (b.policy, b.cfg, b.g, b.source)
        assert a.source == "tuned"

    # sieve union is byte-identical to the full rebuild: every filter's bits
    # (and therefore every elimination decision) matches exactly
    assert merged_sieve.to_bytes() == full_sieve.to_bytes()
    # the Bloom contract survives the merge: winners never pruned
    assert merged_sieve.validate_true_negative_rate(merged.winners()) == 1.0


def test_merged_sieve_generation_past_every_input():
    s1 = OpenSieve(generation=3)
    s2 = OpenSieve(generation=7)
    assert s1.merge(s2).generation == 8
    assert merge_sieves([s1, s2]).generation == 8
    assert merge_sieves([s1, s2], generation=42).generation == 42


def test_merge_sieves_does_not_alias_inputs():
    db = Tuner().tune(TARGETS[:2])
    s = db.build_sieve()
    before = s.to_bytes()
    out = merge_sieves([s])
    out.insert_winner((9, 9, 9), ALL_POLICIES[0])
    assert s.to_bytes() == before  # input untouched by mutating the union


# -- last-writer-wins --------------------------------------------------------


def test_lww_higher_version_wins_either_order():
    old = _rec(policy="dp", tflops=5.0, version=1)
    new = _rec(policy="sk_one_tile", tflops=4.0, version=2)
    for pair in ([old, new], [new, old]):
        db = TuningDatabase()
        report = merge_records(db, ((r, None) for r in pair))
        assert db.records[old.size].policy == "sk_one_tile"
        assert report.superseded == 1
        assert report.conflicts == 0  # versions differ: ordinary supersede


def test_lww_version_tie_counts_conflict_and_is_deterministic():
    a = _rec(policy="dp", tflops=5.0, version=3)
    b = _rec(policy="sk_one_tile", tflops=6.0, version=3)
    winners = []
    for pair in ([a, b], [b, a]):
        db = TuningDatabase()
        report = merge_records(db, ((r, None) for r in pair))
        assert report.conflicts == 1
        winners.append(db.records[a.size].policy)
    assert winners[0] == winners[1] == "sk_one_tile"  # higher tflops breaks tie


def test_identical_payloads_are_not_conflicts():
    a = _rec(version=2)
    b = _rec(version=2)
    db = TuningDatabase()
    report = merge_records(db, ((r, None) for r in (a, b)))
    assert report.conflicts == 0 and report.superseded == 0


def test_merge_databases_report_and_version_clock():
    d1 = TuningDatabase()
    d1.add_record(_rec(size=(1, 2, 3)))
    d2 = TuningDatabase()
    d2.add_record(_rec(size=(4, 5, 6)))
    d2.add_record(_rec(size=(7, 8, 9)))
    out, report = merge_databases([d1, d2])
    assert isinstance(report, MergeReport)
    assert report.sources == 2 and report.examined == 3 and report.merged == 3
    assert len(out.records) == 3
    # merged clock is past every input, so a post-merge local commit wins LWW
    assert out.version >= max(d1.version, d2.version)
    late = _rec(size=(1, 2, 3), policy="sk_one_tile")
    out.add_record(late)
    assert late.version > d1.records[(1, 2, 3)].version


def test_legacy_versionless_journal_lines_always_lose_merge(tmp_path):
    """Regression: replay used to stamp legacy version-less lines with
    fresh clock values, letting a stale pre-federation shard outrank a
    modern record in last-writer-wins. Legacy lines must stay at version 0
    — same as legacy snapshot records — and lose to any stamped record."""
    key = (64, 512, 256)
    legacy_path = tmp_path / "legacy.jsonl"
    lines = []
    for i, policy in enumerate(["dp", "all_sk"]):
        entry = json.loads(journal_entry(_rec(size=key, policy=policy, tflops=99.0)))
        del entry["record"]["version"]  # pre-federation journal format
        lines.append(json.dumps(entry))
    legacy_path.write_text("\n".join(lines) + "\n")
    legacy = TuningDatabase()
    legacy.replay_journal(str(legacy_path))
    assert legacy.records[key].version == 0  # not promoted to a fresh commit
    assert legacy.records[key].policy == "all_sk"  # later line still wins

    modern = TuningDatabase()
    modern.add_record(_rec(size=key, policy="sk_one_tile", tflops=1.0))
    assert modern.records[key].version == 1
    for order in ([legacy, modern], [modern, legacy]):
        out, _ = merge_databases(order)
        assert out.records[key].policy == "sk_one_tile"  # stamped beats legacy


def test_merge_never_keeps_stale_per_policy_for_new_winner():
    """Regression: the per-policy table must describe the stored record —
    a winner without its own table drops the superseded record's, rather
    than leaving measurements that belong to a different winner."""
    loser = _rec(policy="dp", tflops=1.0, version=1)
    winner = _rec(policy="all_sk", tflops=2.0, version=2)
    db = TuningDatabase()
    merge_records(db, [(loser, {"dp": 1.0})])
    assert db.per_policy[loser.size] == {"dp": 1.0}
    merge_records(db, [(winner, None)])
    assert db.records[winner.size].policy == "all_sk"
    assert winner.size not in db.per_policy  # stale table dropped
    # and a winner WITH a table installs it
    newer = _rec(policy="sk_one_tile", tflops=3.0, version=3)
    merge_records(db, [(newer, {"sk_one_tile": 3.0})])
    assert db.per_policy[newer.size] == {"sk_one_tile": 3.0}


def test_journal_supersedes_snapshot_whatever_the_clocks_say():
    """Regression: version stamps are per-producer counters, so a large
    offline snapshot's clock (resumed at max record version) must NOT
    outrank a fresh worker's low-numbered online commits. A journal
    post-dates the snapshot it accompanies: apply_journal_db overwrites
    unconditionally, the load(path, journal=...) contract."""
    key = (64, 512, 256)
    snapshot = TuningDatabase()
    snapshot.add_record(_rec(size=key, policy="dp", tflops=9.0, version=500))
    assert snapshot.version == 500
    journal_db = TuningDatabase()
    journal_db.add_record(_rec(size=key, policy="all_sk", tflops=3.0, version=3))
    apply_journal_db(snapshot, journal_db)
    assert snapshot.records[key].policy == "all_sk"  # journal wins
    assert snapshot.records[key].version == 3  # producer stamp preserved
    assert snapshot.version >= 500  # clock never rewinds


def test_add_record_preserves_producer_stamp_on_replay():
    db = TuningDatabase()
    stamped = _rec(version=9)
    db.add_record(stamped)
    assert db.records[stamped.size].version == 9
    assert db.version == 9  # clock fast-forwarded, not reset


# -- hybrid (wall, version) commit stamp -------------------------------------


def test_add_record_stamps_hybrid_wall_clock():
    """Fresh commits get both halves of the hybrid stamp; replay
    (stamp=False) preserves whatever the producer wrote — including the
    legacy wall-less 0.0."""
    db = TuningDatabase()
    db.add_record(_rec())
    fresh = db.records[(64, 512, 256)]
    assert fresh.version == 1 and fresh.wall > 0.0
    replayed = TuningDatabase()
    legacy = _rec(size=(1, 2, 3), version=0, wall=0.0)
    replayed.add_record(legacy, stamp=False)
    assert replayed.records[(1, 2, 3)].wall == 0.0
    carried = _rec(size=(4, 5, 6), version=7, wall=123.5)
    replayed.add_record(carried, stamp=False)
    assert replayed.records[(4, 5, 6)].wall == 123.5


def test_lww_newer_wall_beats_higher_version_either_order():
    """The ROADMAP follow-up this stamp exists for: version is a
    per-producer counter, so a long-lived producer's huge clock must not
    outrank a sibling's genuinely newer commit. Wall time orders
    cross-producer merges; merge order never changes the winner."""
    long_lived = _rec(policy="dp", tflops=9.0, version=500, wall=100.0)
    fresh = _rec(policy="all_sk", tflops=3.0, version=3, wall=200.0)
    for order in ([long_lived, fresh], [fresh, long_lived]):
        db = TuningDatabase()
        report = merge_records(db, [(r, None) for r in order])
        assert db.records[fresh.size].policy == "all_sk"
        assert report.conflicts == 0  # stamps differ: ordinary supersede
        assert report.superseded == 1


def test_lww_wall_tie_falls_back_to_producer_version():
    a = _rec(policy="dp", tflops=9.0, version=2, wall=150.0)
    b = _rec(policy="all_sk", tflops=3.0, version=5, wall=150.0)
    for order in ([a, b], [b, a]):
        db = TuningDatabase()
        merge_records(db, [(r, None) for r in order])
        assert db.records[b.size].policy == "all_sk"  # higher version wins


def test_legacy_wall_less_records_lose_to_any_wall_stamped():
    legacy = _rec(policy="dp", tflops=9.0, version=10**6, wall=0.0)
    stamped = _rec(policy="all_sk", tflops=0.5, version=1, wall=1.0)
    for order in ([legacy, stamped], [stamped, legacy]):
        db = TuningDatabase()
        merge_records(db, [(r, None) for r in order])
        assert db.records[stamped.size].policy == "all_sk"


def test_full_stamp_tie_counts_conflict_and_is_deterministic():
    a = _rec(policy="dp", tflops=5.0, version=3, wall=42.0)
    b = _rec(policy="sk_one_tile", tflops=6.0, version=3, wall=42.0)
    winners = set()
    for order in ([a, b], [b, a]):
        db = TuningDatabase()
        report = merge_records(db, [(r, None) for r in order])
        assert report.conflicts == 1
        winners.add(db.records[a.size].policy)
    assert winners == {"sk_one_tile"}  # higher tflops, whatever the order


def test_record_payload_ignores_hybrid_stamp():
    """Sharded-sweep identity: the same tuning result committed by two
    workers at different times is the SAME record — differing stamps must
    not read as a conflict."""
    a = _rec(version=1, wall=10.0)
    b = _rec(version=4, wall=99.0)
    assert record_payload(a) == record_payload(b)
    db = TuningDatabase()
    report = merge_records(db, [(a, None), (b, None)])
    assert report.conflicts == 0


def test_journal_beats_snapshot_with_newer_wall_stamp(tmp_path):
    """Merge-ordering regression at the snapshot/journal boundary: the
    precedence is structural — a snapshot regenerated later (newer wall,
    bigger producer clock) must still lose to the journal records that
    post-date it logically, via both apply_journal_db and the
    load(path, journal=...) path."""
    key = (64, 512, 256)
    snap_rec = _rec(size=key, policy="dp", tflops=9.0, version=500, wall=2e9)
    journal_rec = _rec(size=key, policy="all_sk", tflops=3.0, version=3, wall=1.0)

    snapshot = TuningDatabase()
    snapshot.add_record(snap_rec, stamp=False)
    journal_db = TuningDatabase()
    journal_db.add_record(journal_rec, stamp=False)
    apply_journal_db(snapshot, journal_db)
    assert snapshot.records[key].policy == "all_sk"
    assert snapshot.records[key].wall == 1.0  # producer stamp preserved

    snap_path = tmp_path / "db.json"
    journal_path = tmp_path / "journal.jsonl"
    fresh = TuningDatabase()
    fresh.add_record(snap_rec, stamp=False)
    fresh.save(str(snap_path))
    journal_path.write_text(journal_entry(journal_rec) + "\n")
    loaded = TuningDatabase.load(str(snap_path), journal=str(journal_path))
    assert loaded.records[key].policy == "all_sk"
    # but a *federated* merge of unrelated producers DOES order on wall
    db = TuningDatabase()
    merge_records(db, [(snap_rec, None), (journal_rec, None)])
    assert db.records[key].policy == "dp"


# -- cross-worker federation (the serving-path acceptance criterion) ---------


def _cold_worker():
    db = TuningDatabase()
    sel = KernelSelector(sieve=db.build_sieve(), db=db)
    ad = AdaptiveTuner(sel, config=AdaptiveConfig(hot_threshold=1))
    return sel, ad


def test_fingerprint_tuned_in_worker_a_hits_in_worker_b_after_merge():
    """A fingerprint tuned in worker A's process state must dispatch as a
    DB hit — no miss, no re-tune — in worker B's selector after the merge."""
    op = GemmOp.plain(40, 768, 384, in_dtype="bfloat16")
    sel_a, ad_a = _cold_worker()
    sel_a.select_op(op)  # miss promotes (threshold 1)...
    ad_a.adapt()  # ...and A tunes it online
    assert sel_a.select_op(op).source == "tuned"

    sel_b, ad_b = _cold_worker()
    assert sel_b.select_op(op).source != "tuned"  # B is cold for it
    misses_before = ad_b.stats.misses
    tunes_before = ad_b.stats.adaptations
    gen_before = sel_b.sieve_generation

    report = federate_selector(sel_b, dbs=[ad_a.db], sieves=[sel_a.sieve])
    assert report.merged >= 1

    got = sel_b.select_op(op)
    assert got.source == "tuned"  # DB hit, not sieve/fallback
    assert ad_b.stats.misses == misses_before  # no miss fed the tuner
    ad_b.adapt()
    assert ad_b.stats.adaptations == tunes_before  # nothing re-tuned
    assert sel_b.sieve_generation > gen_before  # generation bumped
    # and B's pick is exactly the record A committed
    rec = ad_a.db.records[op.key]
    assert (got.policy.name, got.cfg.name, got.g) == (rec.policy, rec.cfg, rec.g)


def test_federate_via_journal_shards_only(tmp_path):
    """Journal shards alone (no shared db/sieve objects) are enough to
    federate: the transport is files, as between real hosts."""
    journal = str(tmp_path / "a.jsonl")
    db_a = TuningDatabase()
    sel_a = KernelSelector(sieve=db_a.build_sieve(), db=db_a)
    ad_a = AdaptiveTuner(
        sel_a, config=AdaptiveConfig(hot_threshold=1), journal=journal
    )
    ops = [GemmOp.plain(24, 512, 256), GemmOp(16, 256, 128, g=4, kind="grouped")]
    for op in ops:
        sel_a.select_op(op)
    ad_a.drain()

    sel_b, ad_b = _cold_worker()
    federate_selector(sel_b, journals=[journal])
    for op in ops:
        assert sel_b.select_op(op).source == "tuned"
    assert ad_b.stats.misses == 0


def test_local_commit_beats_stale_fleet_copy():
    """The worker's own (newer) commit survives a federation that carries a
    sibling's older record for the same key."""
    op = GemmOp.plain(56, 896, 448)
    sel_b, ad_b = _cold_worker()
    sel_b.select_op(op)
    ad_b.adapt()
    mine = ad_b.db.records[op.key]
    stale = dataclasses.replace(mine, policy="dp", tflops=0.1, version=0)
    foreign = TuningDatabase()
    foreign.records[stale.size] = stale
    federate_selector(sel_b, dbs=[foreign])
    assert sel_b.db.records[op.key].policy == mine.policy


# -- torn-write journal recovery (regression: crash during append) -----------


def _journal_bytes(n=3):
    tuner = Tuner()
    lines = []
    for t in TARGETS[:n]:
        rec, pp = tuner.tune_size(t)
        lines.append((journal_entry(rec, pp) + "\n").encode())
    return lines


def test_replay_tolerates_truncated_ascii_final_line(tmp_path):
    lines = _journal_bytes(3)
    path = tmp_path / "torn.jsonl"
    path.write_bytes(b"".join(lines[:2]) + lines[2][:-15])  # no trailing \n
    db = TuningDatabase()
    assert db.replay_journal(str(path)) == 2
    assert db.load_errors == 1
    assert len(db.records) == 2


def test_replay_tolerates_torn_multibyte_final_line(tmp_path):
    """A crash can land mid-UTF-8-sequence; text-mode iteration used to
    raise UnicodeDecodeError before any per-line handler ran."""
    lines = _journal_bytes(2)
    path = tmp_path / "torn_utf8.jsonl"
    path.write_bytes(b"".join(lines) + b'{"key": "1,2,3", "rec\xe2')
    db = TuningDatabase()
    assert db.replay_journal(str(path)) == 2  # must not raise
    assert db.load_errors == 1


def test_replay_warns_final_line_distinctly(tmp_path):
    import logging

    class Collect(logging.Handler):
        def __init__(self):
            super().__init__()
            self.messages = []

        def emit(self, record):
            self.messages.append(record.getMessage())

    lines = _journal_bytes(2)
    path = tmp_path / "torn.jsonl"
    path.write_bytes(lines[0] + lines[1][:20])
    handler = Collect()
    logger = logging.getLogger("repro.tuner")
    logger.addHandler(handler)
    try:
        TuningDatabase().replay_journal(str(path))
    finally:
        logger.removeHandler(handler)
    assert any("crash during append" in m for m in handler.messages)


def test_merge_journal_shards_surfaces_torn_lines(tmp_path):
    lines = _journal_bytes(3)
    good = tmp_path / "good.jsonl"
    torn = tmp_path / "torn.jsonl"
    good.write_bytes(lines[0] + lines[1])
    torn.write_bytes(lines[2][: len(lines[2]) // 2])
    merged, report = merge_journal_shards([str(good), str(torn)])
    assert len(merged.records) == 2
    assert report.load_errors == 1


# -- Bloom/sieve merge validation (regression: silent mismatch accept) -------


def test_bloom_merge_rejects_mismatched_bit_width():
    a = BloomFilter.for_capacity(1_000, 0.01, seed=1)
    b = BloomFilter.for_capacity(4_000, 0.01, seed=1)
    with pytest.raises(ValueError) as ei:
        a.merge(b)
    msg = str(ei.value)
    assert str(a.n_bits) in msg and str(b.n_bits) in msg  # names both configs


def test_bloom_merge_rejects_mismatched_hash_count_and_seed():
    a = BloomFilter(n_bits=1024, n_hashes=5, seed=1)
    with pytest.raises(ValueError, match="n_hashes=5.*n_hashes=3"):
        a.merge(BloomFilter(n_bits=1024, n_hashes=3, seed=1))
    with pytest.raises(ValueError, match="seed=1.*seed=2"):
        a.merge(BloomFilter(n_bits=1024, n_hashes=5, seed=2))


def test_bloom_merge_rejects_truncated_bit_array():
    a = BloomFilter(n_bits=1024, n_hashes=5, seed=1)
    b = BloomFilter(n_bits=1024, n_hashes=5, seed=1)
    b.bits = b.bits[:-4]  # a from_bytes of a truncated blob used to do this
    with pytest.raises(ValueError, match="mismatched bit arrays"):
        a.merge(b)


def test_bloom_from_bytes_rejects_truncated_blob():
    f = BloomFilter.for_capacity(1_000, 0.01, seed=3)
    blob = f.to_bytes()
    with pytest.raises(ValueError, match="bytes"):
        BloomFilter.from_bytes(blob[:-8])
    assert BloomFilter.from_bytes(blob).to_bytes() == blob  # intact roundtrip


def test_sieve_merge_rejects_mismatched_policy_registries():
    s1 = OpenSieve(ALL_POLICIES)
    s2 = OpenSieve(ALL_POLICIES[:3])
    with pytest.raises(ValueError, match="policy registries"):
        s1.merge(s2)


def test_sieve_merge_rejects_mismatched_capacity():
    s1 = OpenSieve(capacity=1_000)
    s2 = OpenSieve(capacity=10_000)
    with pytest.raises(ValueError, match="n_bits"):
        s1.merge(s2)


# -- mesh-aware fingerprints (multi-device CI lane) --------------------------


def test_gemm_div_without_plan_is_empty():
    from repro.dist.sharding import ambient_gemm_div

    assert ambient_gemm_div() == {}


@pytest.mark.skipif(
    jax.device_count() < 8, reason="needs 8 devices (multi-device CI lane)"
)
def test_mesh_local_fingerprints_federate_across_hosts():
    """Under a (data=2, model=4) mesh plan, two identically-sharded 'hosts'
    produce the same local-MNK fingerprint for the same global problem, so
    a record tuned on host A is an exact DB hit on host B."""
    from repro.dist.sharding import ShardingPlan, ambient_gemm_div, use_plan

    mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,) * 2)
    plan = ShardingPlan(mesh)
    with use_plan(plan):
        div = ambient_gemm_div()
        assert div == {"batch": 2, "model": 4}
        # what models do with the div table: shard M over batch, N over model
        op_host_a = GemmOp.plain(
            64, 2048, 512, divisors=(div["batch"], div["model"], 1)
        )
        op_host_b = GemmOp.plain(
            64, 2048, 512, divisors=(div["batch"], div["model"], 1)
        )
    assert op_host_a.local == (32, 512, 512)  # the per-device problem
    assert op_host_a.key == op_host_b.key

    sel_a, ad_a = _cold_worker()
    sel_a.select_op(op_host_a)
    ad_a.adapt()
    sel_b, ad_b = _cold_worker()
    federate_selector(sel_b, dbs=[ad_a.db])
    assert sel_b.select_op(op_host_b).source == "tuned"
    assert ad_b.stats.misses == 0


@pytest.mark.skipif(
    jax.device_count() < 8, reason="needs 8 devices (multi-device CI lane)"
)
def test_serve_engine_derives_div_from_ambient_plan():
    from conftest import tiny
    from repro.dist.sharding import ShardingPlan, materialize_tree, use_plan
    from repro.models import build_model
    from repro.serve import ServeConfig, ServeEngine

    cfg = tiny("granite-8b")
    model = build_model(cfg)
    params = materialize_tree(model.param_specs(), jax.random.PRNGKey(0))
    mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,) * 2)
    with use_plan(ShardingPlan(mesh)):
        eng = ServeEngine(model, params, ServeConfig(n_slots=2, max_seq=32, eos=-1))
        assert eng.div == {"batch": 2, "model": 4}
    # explicit div still wins over the ambient plan
    with use_plan(ShardingPlan(mesh)):
        eng2 = ServeEngine(
            model, params, ServeConfig(n_slots=2, max_seq=32, eos=-1), div={}
        )
        assert eng2.div == {}


# -- serve CLI shard helpers -------------------------------------------------


def test_shard_journal_paths_roundtrip(tmp_path):
    from repro.launch.serve import existing_journal_shards, shard_journal_path

    base = str(tmp_path / "j.jsonl")
    assert shard_journal_path(base, 0, 1) == base
    paths = [shard_journal_path(base, w, 3) for w in range(3)]
    assert len(set(paths)) == 3
    for p in paths:
        with open(p, "w") as f:
            f.write(json.dumps({"key": "1,2,3", "record": {}}) + "\n")
    found = existing_journal_shards(base)
    assert found == sorted(paths)
    with open(base, "w") as f:
        f.write("")
    assert existing_journal_shards(base)[0] == base
