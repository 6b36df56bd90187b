"""Declarative planning: QualitySpec -> Planner -> PlannedSpec.

Contracts under test (ISSUE 4 acceptance):
  * query(q, w, QualitySpec) is BIT-IDENTICAL to query(q, w, resolved plan)
  * planning is deterministic given (index, sample seed)
  * plans survive save/load (v3 manifest) and shard()
  * spec validation (QualitySpec fields, PlannedSpec fields, the
    n_probes-reachability gap, legacy shim deprecation)
  * explain() returns per-query diagnostics without changing the answer
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
    PlannedSpec,
    Planner,
    QualitySpec,
    QuerySpec,
)
from repro.distance import recall_at_k
from repro.launch.mesh import make_mesh

QUALITY = QualitySpec(k=5, recall_target=0.8, calibration_queries=16)


@pytest.fixture(scope="module")
def rng_module():
    return jax.random.PRNGKey(20260714)


def _cfg(d=8, M=8, K=6, L=12, family="theta", **kw):
    kw.setdefault("max_candidates", 64)
    kw.setdefault("space", BoundedSpace(0.0, 1.0, float(M)))
    return IndexConfig(d=d, M=M, K=K, L=L, family=family, **kw)


def _problem(rng, n=600, d=8, b=4, salt=0):
    data = jax.random.uniform(jax.random.fold_in(rng, salt), (n, d))
    q = jax.random.uniform(jax.random.fold_in(rng, salt + 1), (b, d))
    w = jnp.abs(jax.random.normal(jax.random.fold_in(rng, salt + 2), (b, d))) + 0.2
    return data, q, w


@pytest.fixture(scope="module")
def planned_index(rng_module):
    """One quality-built index shared by the read-only planning tests."""
    data, _, _ = _problem(rng_module, salt=0)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # tiny n: best-effort plans are fine
        return Index.build(jax.random.fold_in(rng_module, 9), data, QUALITY)


# ---------------------------------------------------------------------------
# spec validation
# ---------------------------------------------------------------------------


def test_qualityspec_validation():
    with pytest.raises(ValueError, match=r"QualitySpec\.k"):
        QualitySpec(k=0)
    with pytest.raises(ValueError, match="recall_target"):
        QualitySpec(recall_target=0.0)
    with pytest.raises(ValueError, match="approx_c"):
        QualitySpec(approx_c=1.0)
    with pytest.raises(ValueError, match="fail_prob"):
        QualitySpec(fail_prob=1.0)
    with pytest.raises(ValueError, match="latency_budget_ms"):
        QualitySpec(latency_budget_ms=0.0)
    with pytest.raises(ValueError, match="calibration_queries"):
        QualitySpec(calibration_queries=0)
    assert QualitySpec() == QualitySpec()  # frozen + hashable value object
    assert hash(QualitySpec()) == hash(QualitySpec())


def test_plannedspec_validation_and_conversion():
    with pytest.raises(ValueError, match=r"PlannedSpec\.mode"):
        PlannedSpec(k=5, mode="exact")
    with pytest.raises(ValueError, match=r"PlannedSpec\.n_probes"):
        PlannedSpec(k=5, mode="multiprobe", n_probes=0)
    with pytest.raises(ValueError, match=r"PlannedSpec\.max_flips"):
        PlannedSpec(k=5, mode="multiprobe", max_flips=-1)

    plan = PlannedSpec(k=5, mode="multiprobe", n_probes=4, max_flips=2,
                       max_candidates=32)
    qs = plan.to_query_spec()
    assert qs == QuerySpec(k=5, mode="multiprobe", n_probes=4, max_flips=2)
    cfg = _cfg(max_candidates=64)
    assert plan.effective_config(cfg).max_candidates == 32
    assert PlannedSpec(k=5, mode="probe", max_candidates=64).effective_config(cfg) is cfg
    with pytest.raises(ValueError, match="exceeds the built"):
        PlannedSpec(k=5, mode="probe", max_candidates=128).effective_config(cfg)


def test_query_rejects_unreachable_n_probes(rng):
    """Satellite: n_probes beyond the (K, max_flips) enumeration must be
    rejected, not silently probe duplicate buckets."""
    data, q, w = _problem(rng, salt=10)
    index = Index.build(jax.random.fold_in(rng, 19), data, _cfg(K=4))
    # reachable with K=4, max_flips=1: 1 + 4 = 5 keys
    index.query(q, w, QuerySpec(k=3, mode="multiprobe", n_probes=5, max_flips=1))
    with pytest.raises(ValueError, match="distinct probe keys reachable"):
        index.query(q, w, QuerySpec(k=3, mode="multiprobe", n_probes=6, max_flips=1))


def test_query_rejects_unknown_spec_type(rng):
    data, q, w = _problem(rng, salt=15)
    index = Index.build(jax.random.fold_in(rng, 18), data, _cfg())
    with pytest.raises(TypeError, match="spec must be"):
        index.query(q, w, {"k": 3})


def test_legacy_shims_warn():
    """Satellite: the package-level legacy shims deprecate toward the facade
    (the defining modules stay warning-free — the facade runs through them)."""
    from repro import core

    key = jax.random.PRNGKey(0)
    data = jax.random.uniform(key, (64, 8))
    cfg = _cfg(L=4)
    with pytest.warns(DeprecationWarning, match="repro.api.Index"):
        legacy = core.build_index(key, data, cfg)
    q = jax.random.uniform(jax.random.fold_in(key, 1), (2, 8))
    w = jnp.abs(jax.random.normal(jax.random.fold_in(key, 2), (2, 8))) + 0.1
    with pytest.warns(DeprecationWarning, match="repro.api.Index"):
        core.query_index(legacy, q, w, cfg, k=2)
    with pytest.warns(DeprecationWarning, match="multiprobe"):
        core.query_multiprobe(legacy, q, w, cfg, k=2, n_probes=2)
    # the facade executes the same engine without tripping the shims
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        Index.build(key, data, cfg).query(q, w, QuerySpec(k=2))


# ---------------------------------------------------------------------------
# the tentpole contracts
# ---------------------------------------------------------------------------


def test_quality_query_bit_identical_to_planned(planned_index, rng_module):
    _, q, w = _problem(rng_module, salt=0)
    res_q = planned_index.query(q, w, QUALITY)
    plan = planned_index.plan(QUALITY)  # memo hit — resolved during build
    res_p = planned_index.query(q, w, plan)
    np.testing.assert_array_equal(np.asarray(res_q.ids), np.asarray(res_p.ids))
    np.testing.assert_array_equal(np.asarray(res_q.dists), np.asarray(res_p.dists))
    np.testing.assert_array_equal(
        np.asarray(res_q.n_candidates), np.asarray(res_p.n_candidates)
    )
    # and the planned spec is an honest mechanism spec: replaying it through
    # the knob path (QuerySpec + effective window) is also bit-identical
    knob = planned_index.query(
        q, w,
        dataclasses.replace(
            plan, predicted_recall=float("nan"),
            predicted_success=float("nan"), expected_candidates=float("nan"),
        ),
    )
    np.testing.assert_array_equal(np.asarray(res_q.ids), np.asarray(knob.ids))


def test_planning_is_deterministic(planned_index, rng_module):
    data, _, _ = _problem(rng_module, salt=0)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        rebuilt = Index.build(jax.random.fold_in(rng_module, 9), data, QUALITY)
    assert rebuilt.config == planned_index.config
    assert rebuilt.plan(QUALITY) == planned_index.plan(QUALITY)
    # a different sample seed may give a different plan object, but planning
    # stays a pure function of (index, seed)
    seeded = dataclasses.replace(QUALITY, seed=7)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        assert rebuilt.plan(seeded) == planned_index.plan(seeded)


def test_plan_is_memoized(planned_index):
    p1 = planned_index.plan(QUALITY)
    assert planned_index.plans[QUALITY] is p1
    assert planned_index.plan(QUALITY) is p1  # no second calibration


def test_planned_fields_are_calibrated(planned_index):
    plan = planned_index.plan(QUALITY)
    assert plan.mode in ("probe", "multiprobe")
    assert 0.0 <= plan.predicted_recall <= 1.0
    assert 0.0 <= plan.predicted_success <= 1.0
    assert plan.expected_candidates > 0
    assert plan.max_candidates <= planned_index.config.max_candidates


def test_latency_budget_prefers_cheaper_plans(rng):
    """A tight candidate budget must never pick a MORE expensive plan than
    the unconstrained resolution."""
    data, _, _ = _problem(rng, n=800, salt=20)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        index = Index.build(jax.random.fold_in(rng, 29), data, QUALITY)
        free = index.plan(QUALITY)
        tight = index.plan(
            dataclasses.replace(QUALITY, latency_budget_ms=0.001)
        )
    assert tight.expected_candidates <= free.expected_candidates + 1e-6


def test_plan_memo_survives_jit_crossing(planned_index, rng_module):
    _, q, w = _problem(rng_module, salt=0)

    @jax.jit
    def serve(ix, q, w):
        return ix.query(q, w, QUALITY).dists  # must resolve from the memo

    got = serve(planned_index, q, w)
    want = planned_index.query(q, w, QUALITY).dists
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


def test_unplanned_quality_under_jit_raises(rng):
    data, q, w = _problem(rng, salt=30)
    index = Index.build(jax.random.fold_in(rng, 39), data, _cfg())

    @jax.jit
    def serve(ix, q, w):
        return ix.query(q, w, QUALITY).dists

    with pytest.raises(ValueError, match="cannot calibrate under jit"):
        serve(index, q, w)


# ---------------------------------------------------------------------------
# persistence (v3) and sharding
# ---------------------------------------------------------------------------


def test_plans_survive_save_load(planned_index, rng_module, tmp_path):
    _, q, w = _problem(rng_module, salt=0)
    want = planned_index.query(q, w, QUALITY)
    planned_index.save(str(tmp_path))

    meta = json.loads((tmp_path / "index.json").read_text())
    assert meta["version"] == 5
    assert len(meta["plans"]) == len(planned_index.plans)

    restored = Index.load(str(tmp_path))
    assert restored.plans == planned_index.plans  # exact float round trip
    got = restored.query(q, w, QUALITY)  # memo hit, no re-calibration
    np.testing.assert_array_equal(np.asarray(got.ids), np.asarray(want.ids))
    np.testing.assert_array_equal(np.asarray(got.dists), np.asarray(want.dists))


def test_v2_directories_still_load(rng, tmp_path):
    """A pre-plan directory (v2 layout) must restore with an empty memo."""
    data, q, w = _problem(rng, salt=40)
    index = Index.build(jax.random.fold_in(rng, 49), data, _cfg())
    index.save(str(tmp_path))
    meta_path = tmp_path / "index.json"
    meta = json.loads(meta_path.read_text())
    meta["version"] = 2
    del meta["plans"]
    meta_path.write_text(json.dumps(meta))
    restored = Index.load(str(tmp_path))
    assert restored.plans == {}
    got = restored.query(q, w, QuerySpec(k=3))
    want = index.query(q, w, QuerySpec(k=3))
    np.testing.assert_array_equal(np.asarray(got.ids), np.asarray(want.ids))


def test_plans_survive_shard(planned_index, rng_module):
    _, q, w = _problem(rng_module, salt=0)
    mesh = make_mesh((1,), ("data",))
    sharded = planned_index.shard(mesh)
    assert sharded.plans == planned_index.plans
    res_q = sharded.query(q, w, QUALITY)
    res_p = sharded.query(q, w, planned_index.plan(QUALITY))
    np.testing.assert_array_equal(np.asarray(res_q.ids), np.asarray(res_p.ids))


def test_sharded_rejects_unplanned_quality(rng):
    data, q, w = _problem(rng, salt=50)
    index = Index.build(jax.random.fold_in(rng, 59), data, _cfg())
    sharded = index.shard(make_mesh((1,), ("data",)))
    with pytest.raises(ValueError, match="BEFORE index.shard"):
        sharded.query(q, w, QUALITY)


def test_sharded_rejects_unreachable_n_probes(rng):
    """The sharded facade applies the same probe-reach gate as Index.query."""
    data, q, w = _problem(rng, salt=55)
    index = Index.build(jax.random.fold_in(rng, 58), data, _cfg(K=4))
    sharded = index.shard(make_mesh((1,), ("data",)))
    with pytest.raises(ValueError, match="distinct probe keys reachable"):
        sharded.query(q, w, QuerySpec(k=3, mode="multiprobe", n_probes=6, max_flips=1))


# ---------------------------------------------------------------------------
# explain / QueryReport
# ---------------------------------------------------------------------------


def test_explain_matches_query_and_reports(planned_index, rng_module):
    _, q, w = _problem(rng_module, salt=0)
    b = q.shape[0]
    report = planned_index.explain(q, w, QUALITY)
    res = planned_index.query(q, w, QUALITY)
    np.testing.assert_array_equal(
        np.asarray(report.result.ids), np.asarray(res.ids)
    )
    assert report.quality == QUALITY
    assert report.spec == planned_index.plan(QUALITY)
    for field in ("predicted_success", "n_candidates", "truncated_tables", "n_invalid"):
        assert getattr(report, field).shape == (b,)
    assert np.all((report.predicted_success >= 0) & (report.predicted_success <= 1))
    assert np.all(report.n_invalid >= 0)
    d = report.to_dict()
    json.dumps(d)  # loggable
    assert d["quality"]["recall_target"] == QUALITY.recall_target


def test_explain_mechanism_spec_and_exact(rng):
    data, q, w = _problem(rng, salt=60)
    index = Index.build(jax.random.fold_in(rng, 69), data, _cfg())
    rep = index.explain(q, w, QuerySpec(k=3, mode="exact"))
    assert rep.quality is None
    np.testing.assert_array_equal(rep.truncated_tables, 0)
    np.testing.assert_array_equal(rep.n_candidates, index.n)
    rep_mp = index.explain(q, w, QuerySpec(k=3, mode="multiprobe", n_probes=4))
    assert rep_mp.spec == QuerySpec(k=3, mode="multiprobe", n_probes=4)


# ---------------------------------------------------------------------------
# build-time planning (plan_config)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("family", ["theta", "l2"])
def test_plan_config_families(rng, family):
    data, _, _ = _problem(rng, n=500, salt=70)
    cfg = Planner().plan_config(data, QUALITY, family=family)
    assert cfg.family == family
    assert cfg.d == data.shape[1]
    assert 1 <= cfg.K and 1 <= cfg.L
    if family == "l2":
        assert cfg.W > 0
    # the derived geometry must pass its own validation round trip
    assert dataclasses.replace(cfg) == cfg


def test_plan_config_auto_picks_lower_rho(rng):
    data, _, _ = _problem(rng, n=500, salt=80)
    planner = Planner()
    cfg = planner.plan_config(data, QUALITY, family="auto")
    assert cfg.family in ("theta", "l2")


def test_quality_build_meets_target_or_warns(rng):
    """The escalation loop either reaches the calibrated target or leaves
    the best-effort warning trail."""
    data, q, w = _problem(rng, n=800, b=16, salt=90)
    quality = QualitySpec(k=5, recall_target=0.85, calibration_queries=24)
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        index = Index.build(jax.random.fold_in(rng, 99), data, quality)
    plan = index.plan(quality)
    warned = any("recall_target" in str(x.message) for x in rec)
    assert plan.predicted_recall >= quality.recall_target - 1e-9 or warned
    # held-out sanity: the planned path beats a deliberately starved spec
    res = index.query(q, w, quality)
    ref = index.query(q, w, QuerySpec(k=5, mode="exact"))
    assert recall_at_k(res.ids, ref.ids, 5) >= 0.5
