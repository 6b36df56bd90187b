"""Offline autotuner: scan space, crash-safe store, Pareto table, prior.

Contracts under test (ISSUE 7 acceptance):
  * Pareto edge cases: dominance ties, single-point frontiers, duplicate
    non-dominated trials collapsing deterministically
  * scan resume-from-partial completes the grid with no duplicate/missing
    trials and a BIT-IDENTICAL frontier artifact
  * worker-process fan-out measures the same deterministic metrics as the
    inline path
  * prior-vs-calibrated parity on an in-bucket profile (provenance="prior",
    adherence within the bar, plan is a first-class bit-identical spec)
  * with no table or an out-of-bucket profile, planning is bit-identical
    to the table-less calibrated path
  * tuning provenance rides the v4 persistence manifest (v3 still loads)
"""

import dataclasses
import json
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (
    BoundedSpace,
    Index,
    IndexConfig,
    Planner,
    QualitySpec,
    QuerySpec,
)
from repro.tuner import (
    DataProfile,
    ScanSpace,
    TrialStore,
    TrialSpec,
    TuningTable,
    build_table,
    grid,
    log_range,
    pareto_front,
    run_scan,
    run_trial,
    scan_is_complete,
    seeded_choice,
)
from repro.tuner.pareto import dominates
from repro.tuner.space import AUTO_WIDTH

# one small space shared by the scan/table/prior tests: 6 trials at n=400
SPACE = ScanSpace(
    profiles=(DataProfile(n=400, d=6),),
    families=("theta", "l2"),
    K=(3, 4),
    L=(8,),
    W=(AUTO_WIDTH,),
    n_probes=(1, 2),
    window=(64,),
    k=3,
    queries=8,
)
QUALITY = QualitySpec(k=3, recall_target=0.6, calibration_queries=8)


def _rec(trial_id, recall, cost, mem=100, **kw):
    return {
        "trial_id": trial_id, "status": "ok", "recall": recall, "cost": cost,
        "mem_bytes": mem, **kw,
    }


@pytest.fixture(scope="module")
def scanned(tmp_path_factory):
    """One full single-shot scan + its table (the reference artifact)."""
    store = tmp_path_factory.mktemp("tuner") / "trials.jsonl"
    records = run_scan(SPACE, store)
    return store, records, build_table(records, SPACE)


# ---------------------------------------------------------------------------
# space: axis helpers + enumeration rules
# ---------------------------------------------------------------------------


def test_axis_helpers():
    assert grid(3, 1, 3, 2) == (3, 1, 2)
    assert log_range(4, 64, 3) == (4, 16, 64)
    assert log_range(8, 8, 1) == (8,)
    with pytest.raises(ValueError, match="log_range"):
        log_range(0, 8, 2)
    picked = seeded_choice(range(100), 5, seed=3)
    assert picked == seeded_choice(range(100), 5, seed=3)  # deterministic
    assert len(picked) == 5 and set(picked) <= set(range(100))
    assert picked != seeded_choice(range(100), 5, seed=4)
    assert seeded_choice((1, 2), 9) == (1, 2)  # num covers the axis


def test_profile_and_space_validation():
    with pytest.raises(ValueError, match="source"):
        DataProfile(n=10, d=2, source="mystery")
    with pytest.raises(ValueError, match="skew"):
        DataProfile(n=10, d=2, skew=0.0)
    with pytest.raises(ValueError, match="profiles"):
        ScanSpace(profiles=())
    with pytest.raises(ValueError, match="unknown hash family"):
        ScanSpace(profiles=(DataProfile(n=10, d=2),), families=("nope",))


def test_trial_enumeration_collapses_duplicates():
    # theta ignores W: two W values must not double the theta grid
    s = dataclasses.replace(SPACE, families=("theta",), W=(2.0, 8.0))
    trials = s.trials()
    assert len(trials) == 4  # 2 K x 1 L x 2 probes
    assert all(t.W == 4.0 for t in trials)
    # l2 has no probing: n_probes collapses to 1
    s = dataclasses.replace(SPACE, families=("l2",))
    trials = s.trials()
    assert len(trials) == 2 and all(t.n_probes == 1 for t in trials)
    # theta's K cap (31) drops oversized K; window < k drops the point
    s = dataclasses.replace(SPACE, families=("theta",), K=(3, 40), window=(2, 64))
    assert all(t.K == 3 and t.window == 64 for t in s.trials())


def test_trial_ids_content_addressed():
    t = SPACE.trials()[0]
    again = TrialSpec.from_dict(t.to_dict())
    assert again == t and again.trial_id == t.trial_id
    assert t.seed == again.seed
    other = dataclasses.replace(t, L=t.L + 1)
    assert other.trial_id != t.trial_id
    # space round-trips (and its id with it)
    assert ScanSpace.from_dict(SPACE.to_dict()).space_id == SPACE.space_id


# ---------------------------------------------------------------------------
# pareto: dominance edge cases
# ---------------------------------------------------------------------------


def test_dominates_edge_cases():
    a = _rec("a", recall=0.9, cost=10)
    b = _rec("b", recall=0.8, cost=20)
    tie = _rec("t", recall=0.9, cost=10)
    assert dominates(a, b) and not dominates(b, a)
    assert not dominates(a, tie) and not dominates(tie, a)  # full tie: neither
    assert not dominates(a, a)  # irreflexive


def test_pareto_single_point_frontier():
    only = _rec("x", recall=0.5, cost=99)
    assert pareto_front([only]) == [only]
    assert pareto_front([]) == []


def test_pareto_duplicate_nondominated_collapse():
    """Exact objective duplicates collapse to the smallest trial_id — the
    frontier cannot depend on store insertion order."""
    r1 = _rec("bbbb", recall=0.9, cost=10)
    r2 = _rec("aaaa", recall=0.9, cost=10)
    for order in ([r1, r2], [r2, r1]):
        front = pareto_front(order)
        assert [r["trial_id"] for r in front] == ["aaaa"]


def test_pareto_partial_ties_both_survive():
    a = _rec("a", recall=0.9, cost=10, mem=100)
    b = _rec("b", recall=0.9, cost=20, mem=50)  # worse cost, better memory
    c = _rec("c", recall=0.8, cost=25, mem=60)  # dominated by b
    bad = _rec("d", recall=1.0, cost=0, mem=0, status="skipped")
    front = pareto_front([a, b, c, bad])
    assert [r["trial_id"] for r in front] == ["a", "b"]


# ---------------------------------------------------------------------------
# scan: store crash-safety + resume bit-identity
# ---------------------------------------------------------------------------


def test_store_tolerates_torn_trailing_line(tmp_path, scanned):
    src, records, _ = scanned
    store = TrialStore(tmp_path / "torn.jsonl")
    store.write_header(SPACE)
    store.append(records[0])
    with open(store.path, "a") as f:
        f.write('{"trial_id": "abc", "trunc')  # mid-write crash artifact
    loaded = store.load(SPACE)
    assert set(loaded) == {records[0]["trial_id"]}


def test_store_rejects_interior_corruption_and_alien_space(tmp_path, scanned):
    _, records, _ = scanned
    store = TrialStore(tmp_path / "corrupt.jsonl")
    store.write_header(SPACE)
    with open(store.path, "a") as f:
        f.write("not json\n")
    store.append(records[0])
    with pytest.raises(ValueError, match="corrupt"):
        store.load(SPACE)

    other = TrialStore(tmp_path / "alien.jsonl")
    other.write_header(dataclasses.replace(SPACE, base_seed=9))
    with pytest.raises(ValueError, match="fresh store"):
        other.load(SPACE)
    # alien trial ids behind a matching header fail in run_scan
    bad = TrialStore(tmp_path / "alien_ids.jsonl")
    bad.write_header(SPACE)
    bad.append({"trial_id": "f" * 16, "status": "ok"})
    with pytest.raises(ValueError, match="not in this scan space"):
        run_scan(SPACE, bad.path)


def test_resume_completes_grid_bit_identically(tmp_path, scanned):
    """Kill-and-resume drill: a partial store (budget-stopped, then torn)
    resumes to the full grid with no duplicate/missing trials and a
    byte-identical tuning table."""
    _, _, reference = scanned
    store = tmp_path / "partial.jsonl"
    first = run_scan(SPACE, store, max_trials=2)
    assert len(first) == 2 and not scan_is_complete(SPACE, store)
    with open(store, "a") as f:
        f.write('{"torn')  # the crash artifact resume must shrug off

    records = run_scan(SPACE, store)
    assert scan_is_complete(SPACE, store)
    want_ids = [t.trial_id for t in SPACE.trials()]
    assert [r["trial_id"] for r in records] == want_ids
    # store file holds each trial exactly once (no duplicate work recorded)
    # and the resume truncated the torn line instead of burying it
    with open(store) as f:
        lines = [ln for ln in f.read().splitlines() if ln.strip()]
    stored = [json.loads(ln)["trial_id"] for ln in lines[1:]]
    assert sorted(stored) == sorted(want_ids)

    resumed_table = build_table(records, SPACE)
    assert json.dumps(resumed_table.to_dict(), sort_keys=True) == json.dumps(
        reference.to_dict(), sort_keys=True
    )


def test_rerun_trial_is_deterministic(scanned):
    _, records, _ = scanned
    again = run_trial(records[0]["trial"])
    for key in ("recall", "cand_frac", "cost", "mem_bytes", "W"):
        assert again[key] == records[0][key], key


def test_worker_pool_matches_inline(tmp_path):
    """Spawned workers (fresh jax runtimes) must reproduce the inline
    metrics — the store is content-addressed, not process-addressed."""
    tiny = ScanSpace(
        profiles=(DataProfile(n=64, d=4),), families=("theta",),
        K=(3, 4), L=(4,), n_probes=(1,), window=(16,), k=2, queries=4,
    )
    inline = run_scan(tiny, tmp_path / "inline.jsonl")
    pooled = run_scan(tiny, tmp_path / "pooled.jsonl", workers=2)
    for a, b in zip(inline, pooled):
        for key in ("trial_id", "recall", "cand_frac", "cost", "mem_bytes"):
            assert a[key] == b[key], key


def test_worker_pool_refused_on_tpu(tmp_path, monkeypatch):
    """A TPU belongs to one process: the parent holds it, so spawned
    workers could not reach it. workers > 1 is refused there, by name,
    before any store is touched."""
    from repro.tuner.scan import WorkersOnChipError

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(WorkersOnChipError, match="workers=2"):
        run_scan(SPACE, tmp_path / "trials.jsonl", workers=2)
    assert not (tmp_path / "trials.jsonl").exists()


# ---------------------------------------------------------------------------
# table: artifact + lookup
# ---------------------------------------------------------------------------


def test_table_roundtrip_and_version_gate(tmp_path, scanned):
    _, _, table = scanned
    path = table.save(tmp_path / "tuning_table.json")
    loaded = TuningTable.load(path)
    assert loaded.to_dict() == table.to_dict()
    assert loaded.provenance()["space_id"] == SPACE.space_id

    doc = loaded.to_dict()
    doc["version"] = 99
    (tmp_path / "bad.json").write_text(json.dumps(doc))
    with pytest.raises(ValueError, match="version"):
        TuningTable.load(tmp_path / "bad.json")
    doc["format"] = "something.else"
    (tmp_path / "worse.json").write_text(json.dumps(doc))
    with pytest.raises(ValueError, match="not a tuning table"):
        TuningTable.load(tmp_path / "worse.json")


def test_nearest_bucket_tolerances(scanned):
    _, _, table = scanned
    assert table.nearest_bucket("theta", 400, 6) is not None
    assert table.nearest_bucket("theta", 700, 6) is not None  # within 2x rows
    assert table.nearest_bucket("theta", 4000, 6) is None  # log2 gap > 1
    assert table.nearest_bucket("theta", 400, 7) is None  # d must match
    assert table.nearest_bucket("theta", 400, 6, skew=2.0) is None
    assert table.nearest_bucket(None, 400, 6) is not None  # family=auto

    bucket = table.nearest_bucket("theta", 400, 6)
    assert TuningTable.best_entry(bucket, recall_target=2.0) is None
    best = TuningTable.best_entry(bucket, recall_target=0.0)
    assert best == min(bucket["entries"], key=lambda e: (e["cost"], e["trial_id"]))


# ---------------------------------------------------------------------------
# planner integration: prior vs calibrated
# ---------------------------------------------------------------------------


def _workload(rng, n=400, d=6, b=4, salt=200):
    data = jax.random.uniform(jax.random.fold_in(rng, salt), (n, d))
    q = jax.random.uniform(jax.random.fold_in(rng, salt + 1), (b, d))
    w = jnp.abs(jax.random.normal(jax.random.fold_in(rng, salt + 2), (b, d))) + 0.2
    return data, q, w


def test_prior_plan_parity_in_bucket(scanned, rng):
    _, _, table = scanned
    data, q, w = _workload(rng)
    key = jax.random.fold_in(rng, 210)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        prior_ix = Index.build(key, data, QUALITY, planner=Planner(table=table))
        calib_ix = Index.build(key, data, QUALITY)
    p_plan, c_plan = prior_ix.plans[QUALITY], calib_ix.plans[QUALITY]
    assert p_plan.provenance == "prior"
    assert c_plan.provenance == "calibrated"
    assert prior_ix.tuning == table.provenance()
    assert calib_ix.tuning is None
    # parity: both paths meet the stated target within the adherence bar on
    # their own calibration evidence
    bar = QUALITY.recall_target - 0.02
    assert p_plan.predicted_recall >= bar
    assert c_plan.predicted_recall >= bar
    # a prior plan is a first-class spec: quality-spec and resolved-plan
    # queries are bit-identical
    via_quality = prior_ix.query(q, w, QUALITY)
    via_plan = prior_ix.query(q, w, p_plan)
    np.testing.assert_array_equal(np.asarray(via_quality.ids), np.asarray(via_plan.ids))
    np.testing.assert_array_equal(np.asarray(via_quality.dists), np.asarray(via_plan.dists))


def test_explain_stamps_provenance_and_plan_time(scanned, rng):
    _, _, table = scanned
    data, q, w = _workload(rng, salt=230)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        index = Index.build(
            jax.random.fold_in(rng, 231), data, QUALITY,
            planner=Planner(table=table),
        )
    report = index.explain(q, w, QUALITY)
    assert report.provenance == "prior"
    assert report.plan_build_s is not None and report.plan_build_s > 0.0
    assert report.to_dict()["provenance"] == "prior"
    # mechanism specs carry no planning metadata
    raw = index.explain(q, w, QuerySpec(k=3))
    assert raw.provenance is None and raw.plan_build_s is None


def test_out_of_bucket_falls_back_bit_identically(scanned, rng):
    """With the profile outside every bucket (d mismatch) the table-backed
    planner must resolve the SAME plan a table-less planner does."""
    _, _, table = scanned
    data, _, _ = _workload(rng, d=5, salt=240)
    cfg = IndexConfig(
        d=5, M=8, K=4, L=8, family="theta", max_candidates=64,
        space=BoundedSpace(0.0, 1.0, 8.0),
    )
    index = Index.build(jax.random.fold_in(rng, 241), data, cfg)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        with_table = Planner(table=table).plan_query(index, QUALITY)
        bare = Planner().plan_query(index, QUALITY)
    assert with_table == bare
    assert with_table.provenance == "calibrated"
    # build-time geometry derivation falls back identically too
    cfg_a = Planner(table=table).plan_config(data, QUALITY)
    cfg_b = Planner().plan_config(data, QUALITY)
    assert cfg_a == cfg_b


def test_no_table_is_the_default_path(rng):
    """Planner() with no table is exactly yesterday's planner (guards the
    bit-identical-fallback acceptance criterion at the API level)."""
    data, _, _ = _workload(rng, salt=250)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        a = Index.build(jax.random.fold_in(rng, 251), data, QUALITY)
        b = Index.build(jax.random.fold_in(rng, 251), data, QUALITY,
                        planner=Planner(table=None))
    assert a.plans[QUALITY] == b.plans[QUALITY]
    assert a.config == b.config


# ---------------------------------------------------------------------------
# persistence: tuning provenance in the v4 manifest
# ---------------------------------------------------------------------------


def test_tuning_provenance_survives_save_load(scanned, rng, tmp_path):
    _, _, table = scanned
    data, q, w = _workload(rng, salt=260)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        index = Index.build(
            jax.random.fold_in(rng, 261), data, QUALITY,
            planner=Planner(table=table),
        )
    assert index.plans[QUALITY].provenance == "prior"
    index.save(str(tmp_path))

    meta = json.loads((tmp_path / "index.json").read_text())
    assert meta["version"] == 5
    assert meta["tuning"] == table.provenance()

    restored = Index.load(str(tmp_path))
    assert restored.tuning == table.provenance()
    assert restored.plans[QUALITY] == index.plans[QUALITY]  # provenance too
    want = index.query(q, w, QUALITY)
    got = restored.query(q, w, QUALITY)
    np.testing.assert_array_equal(np.asarray(got.ids), np.asarray(want.ids))


def test_v3_directories_load_without_tuning(scanned, rng, tmp_path):
    _, _, table = scanned
    data, _, _ = _workload(rng, salt=270)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        index = Index.build(
            jax.random.fold_in(rng, 271), data, QUALITY,
            planner=Planner(table=table),
        )
    index.save(str(tmp_path))
    meta_path = tmp_path / "index.json"
    meta = json.loads(meta_path.read_text())
    meta["version"] = 3
    del meta["tuning"]
    meta_path.write_text(json.dumps(meta))
    restored = Index.load(str(tmp_path))
    assert restored.tuning is None
    assert restored.plans == index.plans
