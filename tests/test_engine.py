"""Candidate-stream execution engine: parity, kernels, and contracts.

The load-bearing claim of the engine refactor is that ONE pipeline
(key enumeration → candidate sources → merge/dedupe/mask → fused
gather/rerank/top-k) reproduces every pre-refactor query path BIT FOR BIT.
``_legacy_query`` below reimplements the superseded pipeline verbatim —
per-mode probe front-ends, the dense (b, L, P, cap) delta key match, the
per-batch (n_main + cap, d) concatenated row table, the single-table fused
tail — and the suite asserts the engine matches it exactly across
probe/multiprobe/exact × fresh/segmented/tombstoned × both hash families,
plus the sharded service against its single-host twin.

Also pinned here: the two-segment gather kernels against the concatenated
table on every backend schedule, the chunked delta match against the dense
formulation, the sentinel contract (ids == -1 ⇔ dists == +inf), and the
no-retrace-across-fill-levels jit guarantee carried over from
tests/test_lifecycle.py.
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine
from repro.api import (
    BoundedSpace,
    Index,
    IndexConfig,
    QuerySpec,
    UpdateSpec,
)
from repro.core import transforms
from repro.core.index import (
    DeltaSegment,
    QueryResult,
    _dedupe_candidates,
    _delta_candidates,
    _keys_for,
    _mask_dead,
    _probe_one_table,
    delta_live_mask,
)
from repro.core.multiprobe import multiprobe_keys_for
from repro.kernels import ops
from repro.launch.mesh import make_mesh

N = 400
D = 8
CAP = 64


def _cfg(family="theta", **kw):
    kw.setdefault("max_candidates", N + CAP)  # no window truncation (parity)
    kw.setdefault("space", BoundedSpace(0.0, 1.0, 8.0))
    kw.setdefault("W", 8.0)
    return IndexConfig(d=D, M=8, K=6, L=10, family=family, **kw)


def _problem(rng, salt=0, m=37, b=5):
    data = jax.random.uniform(jax.random.fold_in(rng, salt), (N, D))
    extra = jax.random.uniform(jax.random.fold_in(rng, salt + 1), (m, D))
    q = jax.random.uniform(jax.random.fold_in(rng, salt + 2), (b, D))
    w = jnp.abs(jax.random.normal(jax.random.fold_in(rng, salt + 3), (b, D))) + 0.2
    return data, extra, q, w


def _index_for(rng, data, extra, family, lifecycle):
    """fresh (immutable) | delta (inserts only) | churn (inserts + deletes
    in both segments)."""
    bkey = jax.random.fold_in(rng, 9)
    if lifecycle == "fresh":
        return Index.build(bkey, data, _cfg(family=family))
    index = Index.build(
        bkey, data, _cfg(family=family), update=UpdateSpec(delta_capacity=CAP)
    )
    index, ids = index.insert(extra)
    if lifecycle == "churn":
        index = index.delete(jnp.asarray([0, 5, 17, int(ids[3]), int(ids[11])], jnp.int32))
    return index


def _legacy_query(index: Index, queries, weights, spec: QuerySpec) -> QueryResult:
    """The PRE-REFACTOR pipeline, reimplemented verbatim: this is what
    query_index / query_multiprobe / query_*_segmented / the facade
    computed before the engine existed. The engine must match bit for bit."""
    state, cfg = index.state, index.config
    n_main = state.n
    b = queries.shape[0]
    if index.mutable:
        cap = index.delta.capacity
        n_tot = n_main + cap
        table = jnp.concatenate(
            [state.data, index.delta.data.astype(state.data.dtype)], axis=0
        )
        tombstones = index.tombstones
    else:
        cap, n_tot, table, tombstones = 0, n_main, state.data, None

    if spec.mode == "exact":
        if not index.mutable:
            dists, ids = ops.wl1_scan_topk(state.data, queries, weights, spec.k)
            return QueryResult(dists, ids, jnp.full(b, n_main, jnp.int32))
        live = ~tombstones[:n_main]
        if cap:
            live = jnp.concatenate(
                [live, delta_live_mask(index.delta, tombstones, n_main)]
            )
        ids_row = jnp.where(live, jnp.arange(n_tot, dtype=jnp.int32), n_tot)
        cand = jnp.broadcast_to(jnp.sort(ids_row)[None, :], (b, n_tot))
        dists, ids = ops.gather_rerank_topk(table, cand, queries, weights, spec.k)
        n_candidates = jnp.broadcast_to(jnp.sum(live).astype(jnp.int32), (b,))
        return QueryResult(dists, ids, n_candidates)

    if spec.mode == "multiprobe":
        keys = multiprobe_keys_for(
            state, queries, weights, cfg, spec.n_probes, spec.max_flips
        )  # (b, L, P)
    else:
        qlevels = transforms.discretize(queries, cfg.space)
        keys = _keys_for(qlevels, weights, state.tables, cfg, state.mixers)[:, :, None]

    probe = jax.vmap(
        jax.vmap(
            jax.vmap(_probe_one_table, in_axes=(None, None, 0, None)),
            in_axes=(0, 0, 0, None),
        ),
        in_axes=(None, None, 0, None),
    )
    cand = probe(state.sorted_keys, state.perm, keys, cfg.max_candidates)
    cand = cand.reshape(b, -1)
    if index.mutable:
        cand = _mask_dead(cand, tombstones, n_main, n_tot)
        if cap:
            live = delta_live_mask(index.delta, tombstones, n_main)
            # the DENSE (b, L, P, cap) key match the chunked engine replaced
            match = jnp.any(
                keys[:, :, :, None] == index.delta.keys[None, :, None, :], axis=(1, 2)
            )
            slot_ids = n_main + jnp.arange(cap, dtype=jnp.int32)
            dcand = jnp.where(match & live[None, :], slot_ids[None, :], n_tot).astype(
                jnp.int32
            )
            cand = jnp.concatenate([cand, dcand], axis=1)
    cand, n_candidates = _dedupe_candidates(cand, n_tot)
    dists, ids = ops.gather_rerank_topk(table, cand, queries, weights, spec.k)
    return QueryResult(dists, ids, n_candidates)


def _assert_bit_identical(got: QueryResult, want: QueryResult):
    np.testing.assert_array_equal(np.asarray(got.ids), np.asarray(want.ids))
    np.testing.assert_array_equal(np.asarray(got.dists), np.asarray(want.dists))
    np.testing.assert_array_equal(
        np.asarray(got.n_candidates), np.asarray(want.n_candidates)
    )


# ---------------------------------------------------------------------------
# engine == pre-refactor pipeline, the full matrix
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("family", ["theta", "l2"])
@pytest.mark.parametrize("mode", ["probe", "multiprobe", "exact"])
@pytest.mark.parametrize("lifecycle", ["fresh", "delta", "churn"])
def test_engine_matches_legacy_pipeline(rng, family, mode, lifecycle):
    if family == "l2" and mode == "multiprobe":
        pytest.skip("l2 family does not support multiprobe")
    data, extra, q, w = _problem(rng)
    index = _index_for(rng, data, extra, family, lifecycle)
    spec = QuerySpec(k=7, mode=mode)
    _assert_bit_identical(
        index.query(q, w, spec), _legacy_query(index, q, w, spec)
    )


@pytest.mark.parametrize("family", ["theta", "l2"])
def test_legacy_entry_points_are_engine_backed(rng, family):
    """The five core entry points are thin wrappers: their results must be
    bit-identical to the facade (same compiled engine underneath)."""
    from repro.core.index import (
        query_exact_segmented,
        query_index,
        query_index_segmented,
    )
    from repro.core.multiprobe import query_multiprobe, query_multiprobe_segmented

    data, extra, q, w = _problem(rng)
    cfg = _cfg(family=family)
    imm = _index_for(rng, data, extra, family, "fresh")
    mut = _index_for(rng, data, extra, family, "churn")
    k = 7
    _assert_bit_identical(
        query_index(imm.state, q, w, cfg, k=k),
        imm.query(q, w, QuerySpec(k=k)),
    )
    _assert_bit_identical(
        query_index_segmented(mut.state, mut.delta, mut.tombstones, q, w, cfg, k=k),
        mut.query(q, w, QuerySpec(k=k)),
    )
    _assert_bit_identical(
        query_exact_segmented(mut.state, mut.delta, mut.tombstones, q, w, k=k),
        mut.query(q, w, QuerySpec(k=k, mode="exact")),
    )
    if family == "theta":
        _assert_bit_identical(
            query_multiprobe(imm.state, q, w, cfg, k=k),
            imm.query(q, w, QuerySpec(k=k, mode="multiprobe")),
        )
        _assert_bit_identical(
            query_multiprobe_segmented(
                mut.state, mut.delta, mut.tombstones, q, w, cfg, k=k
            ),
            mut.query(q, w, QuerySpec(k=k, mode="multiprobe")),
        )


def test_core_deprecation_shims_still_warn(rng):
    """Satellite contract: the repro.core package-level shims now reach the
    engine-backed facade paths but must keep their DeprecationWarning."""
    import repro.core as core

    data, _, q, w = _problem(rng)
    cfg = _cfg()
    with pytest.warns(DeprecationWarning, match="repro.api.Index.build"):
        state = core.build_index(jax.random.fold_in(rng, 9), data, cfg)
    with pytest.warns(DeprecationWarning, match="repro.api.Index.query"):
        res = core.query_index(state, q, w, cfg, k=3)
    assert res.ids.shape == (5, 3)
    with pytest.warns(DeprecationWarning, match="multiprobe"):
        core.query_multiprobe(state, q, w, cfg, k=3)


# ---------------------------------------------------------------------------
# chunked delta key match == dense formulation
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cap", [1, 64, 130, 1500])
@pytest.mark.parametrize("P", [1, 4])
def test_delta_chunked_match_equals_dense(rng, cap, P):
    """The fori_loop-chunked key match (any block size, capacity not a
    block multiple) reproduces the dense (b, L, P, cap) comparison."""
    L, b, n_main = 6, 7, 100
    kk = jax.random.fold_in(rng, cap * 10 + P)
    # draw keys from a small alphabet so real collisions occur
    dkeys = jax.random.randint(jax.random.fold_in(kk, 0), (L, cap), 0, 13, dtype=jnp.int32)
    pk = jax.random.randint(jax.random.fold_in(kk, 1), (b, L, P), 0, 13, dtype=jnp.int32)
    live = jax.random.bernoulli(jax.random.fold_in(kk, 2), 0.8, (cap,))
    delta = DeltaSegment(
        data=jnp.zeros((cap, D)),
        levels=jnp.zeros((cap, D), jnp.int32),
        keys=dkeys,
        fill=jnp.asarray(cap, jnp.int32),
    )
    sentinel = n_main + cap
    dense_match = jnp.any(pk[:, :, :, None] == dkeys[None, :, None, :], axis=(1, 2))
    slot_ids = n_main + jnp.arange(cap, dtype=jnp.int32)
    want = jnp.where(dense_match & live[None, :], slot_ids[None, :], sentinel)
    for block in (32, 1024):
        got = _delta_candidates(pk, delta, live, n_main, sentinel, block=block)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert np.asarray(dense_match).any(), "degenerate test: no collisions"


# ---------------------------------------------------------------------------
# two-segment fused gather == concatenated-table gather, every schedule
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("force", ["auto", "chunked", "ref", "interpret"])
@pytest.mark.parametrize("shape", [(100, 40, 64, 3), (600, 300, 777, 10)])
def test_segmented_gather_matches_concat_table(rng, force, shape):
    """ops.gather_rerank_topk(main, ids, ..., delta=delta) must be
    bit-identical to the single-table call over concat([main, delta]) on
    every backend schedule (incl. the Pallas kernel in interpret mode) —
    ids mixing both segments, duplicates-as-sentinels, and k > #valid."""
    n_main, cap, P, k = shape
    d, b = 16, 4
    kk = jax.random.fold_in(rng, n_main)
    main = jax.random.uniform(jax.random.fold_in(kk, 0), (n_main, d))
    delta = jax.random.uniform(jax.random.fold_in(kk, 1), (cap, d))
    q = jax.random.uniform(jax.random.fold_in(kk, 2), (b, d))
    w = jax.random.normal(jax.random.fold_in(kk, 3), (b, d))  # negative weights too
    n_tot = n_main + cap
    ids = jax.random.randint(
        jax.random.fold_in(kk, 4), (b, P), 0, n_tot + n_tot // 3, dtype=jnp.int32
    )  # ~25% sentinels
    ids, _ = _dedupe_candidates(ids, n_tot)  # production contract: deduped input
    got = ops.gather_rerank_topk(main, ids, q, w, k, force=force, delta=delta)
    want = ops.gather_rerank_topk(
        jnp.concatenate([main, delta]), ids, q, w, k, force=force
    )
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))


def test_segmented_gather_all_invalid_rows(rng):
    """A query whose every candidate is a sentinel returns (+inf, -1) on
    the segmented path exactly like the single-table path."""
    main = jax.random.uniform(jax.random.fold_in(rng, 0), (20, D))
    delta = jax.random.uniform(jax.random.fold_in(rng, 1), (8, D))
    q = jnp.zeros((2, D))
    w = jnp.ones((2, D))
    ids = jnp.full((2, 16), 28, jnp.int32)  # all == n_tot sentinel
    for force in ("auto", "chunked", "ref", "interpret"):
        dists, got_ids = ops.gather_rerank_topk(main, ids, q, w, 5, force=force, delta=delta)
        np.testing.assert_array_equal(np.asarray(got_ids), -1)
        assert not np.isfinite(np.asarray(dists)).any()


# ---------------------------------------------------------------------------
# big-delta capacity: the chunked match unblocks cap >> 4096
# ---------------------------------------------------------------------------


def test_large_delta_capacity_queries(rng):
    """A delta_capacity=16384 index (4x the old dense-match comfort zone)
    builds, inserts, and queries; inserted rows are retrievable and the
    two-segment result matches the exact oracle at non-truncating budgets."""
    cap = 16384
    data, extra, q, w = _problem(rng, m=64)
    index = Index.build(
        jax.random.fold_in(rng, 9),
        data,
        _cfg(),
        update=UpdateSpec(delta_capacity=cap),
    )
    index, ids = index.insert(extra)
    res = index.query(extra[:4], jnp.ones((4, D)), QuerySpec(k=1))
    np.testing.assert_array_equal(np.asarray(res.ids[:, 0]), np.asarray(ids[:4]))
    for mode in ("probe", "exact"):
        spec = QuerySpec(k=5, mode=mode)
        _assert_bit_identical(
            index.query(q, w, spec), _legacy_query(index, q, w, spec)
        )


# ---------------------------------------------------------------------------
# contracts carried from test_lifecycle: sentinels + no retrace
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mutable", [False, True])
@pytest.mark.parametrize("mode", ["probe", "multiprobe", "exact"])
def test_engine_sentinels_minus_one_iff_inf(rng, mutable, mode):
    data = jax.random.uniform(jax.random.fold_in(rng, 0), (5, D)) * 0.1
    cfg = _cfg(max_candidates=16)
    if mutable:
        index = Index.build(
            jax.random.fold_in(rng, 9), data, cfg, update=UpdateSpec(delta_capacity=8)
        )
        index = index.delete(jnp.asarray([2], jnp.int32))
    else:
        index = Index.build(jax.random.fold_in(rng, 9), data, cfg)
    q = jnp.ones((2, D)) * 0.95
    w = jnp.ones((2, D))
    res = index.query(q, w, QuerySpec(k=9, mode=mode))
    ids = np.asarray(res.ids)
    dists = np.asarray(res.dists)
    assert ((ids == -1) == ~np.isfinite(dists)).all()
    assert ids.max() < 5 + 8 and ids.min() >= -1  # internal sentinels never escape


def test_mode_irrelevant_static_args_share_compiled_program(rng):
    """Static args a mode does not read (n_probes/max_flips in probe mode,
    cfg in exact mode) are normalized before the compile-key lookup — the
    facade and the legacy shims hit ONE executable per traced program."""
    from repro.analysis import RetraceGuard
    from repro.core.index import query_exact_segmented

    data, extra, q, w = _problem(rng)
    imm = _index_for(rng, data, extra, "theta", "fresh")
    r1 = imm.query(q, w, QuerySpec(k=3))  # spec default n_probes=8/max_flips=3
    with RetraceGuard() as guard:
        r2 = imm.query(q, w, QuerySpec(k=3, n_probes=4, max_flips=1))
        guard.assert_no_retrace(context="probe-mode n_probes variant")
    _assert_bit_identical(r1, r2)

    mut = _index_for(rng, data, extra, "theta", "delta")
    mut.query(q, w, QuerySpec(k=3, mode="exact"))  # facade passes real cfg
    with RetraceGuard() as guard:
        query_exact_segmented(mut.state, mut.delta, mut.tombstones, q, w, k=3)  # cfg=None
        guard.assert_no_retrace(context="legacy exact shim vs facade")


def test_engine_no_retrace_across_fill_levels(rng):
    """One compiled program per (geometry, spec) across the index's whole
    mutable life — probe AND multiprobe."""
    data, extra, q, w = _problem(rng)
    index = Index.build(
        jax.random.fold_in(rng, 9),
        data,
        _cfg(),
        update=UpdateSpec(delta_capacity=CAP),
    )
    jq = jax.jit(lambda ix, q, w: ix.query(q, w, QuerySpec(k=5)))
    jmp = jax.jit(lambda ix, q, w: ix.query(q, w, QuerySpec(k=5, mode="multiprobe")))
    jins = jax.jit(lambda ix, rows: ix.insert(rows))
    jdel = jax.jit(lambda ix, ids: ix.delete(ids))
    for i in range(4):
        index, _ = jins(index, extra[i * 8 : (i + 1) * 8])
        index = jdel(index, jnp.asarray([i * 3], jnp.int32))
        jq(index, q, w)
        jmp(index, q, w)
    from repro.analysis import cache_size

    assert cache_size(jq) == 1
    assert cache_size(jmp) == 1
    assert cache_size(jins) == 1
    assert cache_size(jdel) == 1


# ---------------------------------------------------------------------------
# engine internals: source/block contract
# ---------------------------------------------------------------------------


def test_sources_emit_fixed_shape_blocks(rng):
    """Block contract: static (b, P_src) shapes, sentinel >= n_valid for
    empty slots, global ids across sources."""
    data, extra, q, w = _problem(rng)
    index = _index_for(rng, data, extra, "theta", "churn")
    cfg = index.config
    keys = engine.probe_keys(index.state, q, w, cfg)
    assert keys.shape == (5, cfg.L, 1)
    srcs = engine.sources_for(index.state, index.delta, index.tombstones, cfg, keys)
    assert len(srcs) == 2  # sorted-table + delta-match
    n_tot = index.state.n + index.delta.capacity
    table_block = srcs[0].emit(q, w)
    delta_block = srcs[1].emit(q, w)
    assert table_block.shape == (5, cfg.L * 1 * cfg.max_candidates)
    assert delta_block.shape == (5, CAP)
    # live delta ids are global (>= n_main), sentinels >= n_tot
    db = np.asarray(delta_block)
    assert ((db >= index.state.n) | (db >= n_tot)).all()
    # a multiprobe enumeration feeds the SAME sources
    mkeys = engine.probe_keys(
        index.state, q, w, cfg, mode="multiprobe", n_probes=4, max_flips=2
    )
    assert mkeys.shape[:2] == (5, cfg.L) and mkeys.shape[2] <= 4
    srcs_mp = engine.sources_for(index.state, index.delta, index.tombstones, cfg, mkeys)
    assert srcs_mp[0].emit(q, w).shape == (5, cfg.L * mkeys.shape[2] * cfg.max_candidates)


# ---------------------------------------------------------------------------
# sharded facade: validation parity (satellite) + engine parity
# ---------------------------------------------------------------------------


def test_sharded_query_validates_like_single_host(rng):
    """ShardedIndex.query runs the same _validate_query_args checks as
    Index.query — malformed inputs raise the named ValueError, not a
    shard_map trace error."""
    data, _, q, w = _problem(rng)
    mesh = make_mesh((1,), ("data",))
    sharded = Index.build(jax.random.fold_in(rng, 9), data, _cfg()).shard(mesh)
    with pytest.raises(ValueError, match="queries"):
        sharded.query(q[:, :-1], w, QuerySpec(k=3))
    with pytest.raises(ValueError, match="weights"):
        sharded.query(q, w[:, :-1], QuerySpec(k=3))
    with pytest.raises(ValueError, match="batch dims disagree"):
        sharded.query(q, w[:-1], QuerySpec(k=3))
    with pytest.raises(ValueError, match="queries"):
        sharded.query(q[0], w[0], QuerySpec(k=3))


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_sharded_engine_matches_single_host():
    """Per-shard engine dispatch + hierarchical merge == single-host engine,
    bit for bit, for both families across probe/multiprobe/exact on a
    mutable (delta + tombstones) index (8 fake CPU devices, subprocess)."""
    code = """
        import jax, jax.numpy as jnp, numpy as np
        from repro.api import Index, IndexConfig, QuerySpec, UpdateSpec, BoundedSpace

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        n, d, k = 512, 8, 7
        key = jax.random.PRNGKey(0)
        data = jax.random.uniform(jax.random.fold_in(key, 0), (n, d))
        extra = jax.random.uniform(jax.random.fold_in(key, 1), (37, d))
        q = jax.random.uniform(jax.random.fold_in(key, 2), (5, d))
        w = jnp.abs(jax.random.normal(jax.random.fold_in(key, 3), (5, d))) + 0.2
        for family in ("theta", "l2"):
            cfg = IndexConfig(d=d, M=8, K=6, L=10, family=family, W=8.0,
                              max_candidates=n + 64, space=BoundedSpace(0., 1., 8.))
            local = Index.build(jax.random.fold_in(key, 9), data, cfg,
                                update=UpdateSpec(delta_capacity=64))
            local, ids = local.insert(extra)
            local = local.delete(jnp.asarray([3, 77, int(ids[4])], jnp.int32))
            sharded = local.shard(mesh)
            modes = ("probe", "exact") + (("multiprobe",) if family == "theta" else ())
            for mode in modes:
                a = local.query(q, w, QuerySpec(k=k, mode=mode))
                b = sharded.query(q, w, QuerySpec(k=k, mode=mode))
                np.testing.assert_array_equal(np.asarray(a.ids), np.asarray(b.ids))
                np.testing.assert_array_equal(np.asarray(a.dists), np.asarray(b.dists))
                np.testing.assert_array_equal(np.asarray(a.n_candidates),
                                              np.asarray(b.n_candidates))
        print("OK")
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=900,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr[-3000:]}"
    assert "OK" in out.stdout
