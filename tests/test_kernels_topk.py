"""Fused streaming-top-k kernels vs the materializing ref oracles.

Covers (interpret=True Pallas bodies + chunked jnp production paths):
  * shape/padding sweeps — non-multiple n, d, C; C > n; k > candidates;
  * all-invalid candidate rows;
  * dedupe correctness with candidate ids duplicated across tables;
  * the fused query_index tail vs a hand-built unfused gather → rerank →
    top-k reference.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import BoundedSpace, IndexConfig, build_index, query_index
from repro.core.index import _dedupe_candidates
from repro.kernels import ops, ref
from repro.kernels.gather_rerank import (
    gather_rerank_topk_chunked,
    gather_rerank_topk_pallas,
)
from repro.kernels.wl1_topk import wl1_scan_topk_chunked, wl1_scan_topk_pallas

# (n, b, d, k): block-exact, off-by-one, sub-block, k > n
SCAN_TOPK_SHAPES = [
    (1, 1, 1, 1),
    (33, 3, 7, 5),
    (128, 8, 256, 128),  # exact blocks, k = lane width
    (129, 9, 257, 10),  # off-by-one everywhere
    (300, 5, 16, 3),
    (4, 2, 2, 8),  # k > n ⇒ (+inf, -1) tail
]


@pytest.mark.parametrize("n,b,d,k", SCAN_TOPK_SHAPES)
@pytest.mark.parametrize("impl", ["interpret", "chunked"])
def test_scan_topk_matches_ref(n, b, d, k, impl):
    key = jax.random.PRNGKey(n * 31 + b * 7 + d + k)
    k1, k2, k3 = jax.random.split(key, 3)
    data = jax.random.normal(k1, (n, d))
    q = jax.random.normal(k2, (b, d))
    w = jax.random.normal(k3, (b, d))
    want_d, want_i = ref.wl1_scan_topk(data, q, w, k)
    if impl == "interpret":
        got_d, got_i = wl1_scan_topk_pallas(data, q, w, k, interpret=True)
    else:
        got_d, got_i = wl1_scan_topk_chunked(data, q, w, k, chunk=64)
    np.testing.assert_allclose(np.asarray(got_d), np.asarray(want_d), rtol=1e-5, atol=1e-5)
    assert np.array_equal(np.asarray(got_i), np.asarray(want_i))


# (n, b, P, d, k): P is the candidate-slot count (L·C in the index)
GATHER_SHAPES = [
    (50, 3, 17, 7, 5),
    (200, 2, 64, 128, 10),  # d exactly one chunk
    (8, 2, 40, 5, 3),  # C > n: more slots than database rows
    (10, 2, 16, 300, 4),  # d spans multiple chunks with padding
    (5, 1, 1, 1, 1),
]


@pytest.mark.parametrize("n,b,P,d,k", GATHER_SHAPES)
@pytest.mark.parametrize("impl", ["interpret", "chunked"])
def test_gather_rerank_topk_matches_ref(n, b, P, d, k, impl):
    key = jax.random.PRNGKey(n + P * 13 + d + k)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    data = jax.random.normal(k1, (n, d))
    q = jax.random.normal(k2, (b, d))
    w = jax.random.normal(k3, (b, d))
    raw = jax.random.randint(k4, (b, P), 0, n + max(2, n // 3))
    ids = jnp.minimum(raw, n).astype(jnp.int32)  # >= n ⇒ invalid sentinel
    want_d, want_i = ref.gather_rerank_topk(data, ids, q, w, k)
    if impl == "interpret":
        got_d, got_i = gather_rerank_topk_pallas(data, ids, q, w, k, interpret=True)
    else:
        got_d, got_i = gather_rerank_topk_chunked(data, ids, q, w, k, chunk=16)
    np.testing.assert_allclose(np.asarray(got_d), np.asarray(want_d), rtol=1e-5, atol=1e-5)
    assert np.array_equal(np.asarray(got_i), np.asarray(want_i))


@pytest.mark.parametrize("storage", ["f32", "two_segment", "int8", "bf16"])
@pytest.mark.parametrize("max_ids", [64, 200])
def test_gather_rerank_id_tiles_match_one_call(storage, max_ids):
    """Ids split into bounded (query, candidate) tiles — what keeps a large
    batch's scalar prefetch inside SMEM — carry the top-k buffer from tile
    to tile: bit-identical to one call over all ids, and equal to the ref."""
    from repro import quant

    key = jax.random.PRNGKey(max_ids)
    n, b, P, d, k = 90, 11, 70, 20, 6
    data = jax.random.normal(key, (n, d))
    q = jax.random.normal(jax.random.fold_in(key, 1), (b, d))
    w = jax.random.normal(jax.random.fold_in(key, 2), (b, d))
    kw = {}
    if storage == "two_segment":
        kw["delta"] = jax.random.normal(jax.random.fold_in(key, 4), (13, d))
        hi = n + 13 + 3
    else:
        hi = n + 20
    if storage in ("int8", "bf16"):
        data, kw["scales"] = quant.get_codec(storage).encode(data)
    ids = jax.random.randint(jax.random.fold_in(key, 3), (b, P), 0, hi)
    ids = jnp.minimum(ids, hi - 3).astype(jnp.int32)  # some invalid sentinels
    assert b * P > max_ids  # the tiled path runs
    got = gather_rerank_topk_pallas(data, ids, q, w, k, interpret=True, max_ids=max_ids, **kw)
    whole = gather_rerank_topk_pallas(data, ids, q, w, k, interpret=True, max_ids=b * P, **kw)
    assert np.array_equal(np.asarray(got[0]), np.asarray(whole[0]))
    assert np.array_equal(np.asarray(got[1]), np.asarray(whole[1]))
    want = ops.gather_rerank_topk(data, ids, q, w, k, force="ref", **kw)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]), rtol=1e-5, atol=1e-5)
    assert np.array_equal(np.asarray(got[1]), np.asarray(want[1]))


@pytest.mark.parametrize("impl", ["interpret", "chunked", "ref"])
def test_gather_rerank_all_invalid(impl):
    """A query whose every candidate slot is padding returns (+inf, -1)."""
    key = jax.random.PRNGKey(0)
    n, b, P, d, k = 12, 3, 9, 6, 4
    data = jax.random.normal(key, (n, d))
    q = jax.random.normal(jax.random.fold_in(key, 1), (b, d))
    w = jnp.abs(jax.random.normal(jax.random.fold_in(key, 2), (b, d)))
    ids = jnp.full((b, P), n, jnp.int32)
    got_d, got_i = ops.gather_rerank_topk(data, ids, q, w, k, force=impl)
    assert np.all(np.isinf(np.asarray(got_d)))
    assert np.all(np.asarray(got_i) == -1)


@pytest.mark.parametrize("impl", ["interpret", "chunked"])
def test_gather_rerank_duplicate_ids_after_dedupe(impl):
    """Ids duplicated across tables: dedupe marks repeats invalid, and the
    fused top-k must not return the same id twice."""
    key = jax.random.PRNGKey(7)
    n, b, d, k = 30, 2, 8, 6
    data = jax.random.normal(key, (n, d))
    q = jax.random.normal(jax.random.fold_in(key, 1), (b, d))
    w = jnp.abs(jax.random.normal(jax.random.fold_in(key, 2), (b, d))) + 0.1
    # every id appears in "both tables" (two copies), plus window padding
    half = jax.random.randint(jax.random.fold_in(key, 3), (b, 10), 0, n)
    cand = jnp.concatenate([half, half, jnp.full((b, 4), n + 3)], axis=1)
    deduped, n_cand = _dedupe_candidates(cand.astype(jnp.int32), n)
    # counts only unique real ids
    for i in range(b):
        assert int(n_cand[i]) == len(set(np.asarray(half[i]).tolist()))
    got_d, got_i = ops.gather_rerank_topk(data, deduped, q, w, k, force=impl)
    want_d, want_i = ref.gather_rerank_topk(data, deduped, q, w, k)
    np.testing.assert_allclose(np.asarray(got_d), np.asarray(want_d), rtol=1e-5, atol=1e-5)
    assert np.array_equal(np.asarray(got_i), np.asarray(want_i))
    for i in range(b):
        real = [int(x) for x in np.asarray(got_i[i]) if x >= 0]
        assert len(real) == len(set(real)), f"duplicate id returned: {real}"


def test_query_index_matches_unfused_reference(rng):
    """End-to-end: the fused query tail returns exactly what the old 3-step
    (gather → wl1_rerank → lax.top_k) path returned."""
    n, d, M, k = 600, 10, 8, 5
    space = BoundedSpace(0.0, 1.0, float(M))
    data = jax.random.uniform(jax.random.fold_in(rng, 80), (n, d))
    cfg = IndexConfig(d=d, M=M, K=6, L=12, max_candidates=32, space=space)
    idx = build_index(jax.random.fold_in(rng, 81), data, cfg)
    q = jax.random.uniform(jax.random.fold_in(rng, 82), (6, d))
    w = jax.random.normal(jax.random.fold_in(rng, 83), (6, d))  # mixed signs
    res = query_index(idx, q, w, cfg, k=k)

    # unfused reference tail over the same probe set
    from repro.core import transforms
    from repro.core.index import _keys_for, _probe_one_table

    qlevels = transforms.discretize(q, cfg.space)
    qkeys = _keys_for(qlevels, w, idx.tables, cfg, idx.mixers)
    probe = jax.vmap(
        jax.vmap(_probe_one_table, in_axes=(0, 0, 0, None)), in_axes=(None, None, 0, None)
    )
    cand = probe(idx.sorted_keys, idx.perm, qkeys, cfg.max_candidates)
    cand, _ = _dedupe_candidates(cand.reshape(6, -1), n)
    valid = cand < n
    pts = data[jnp.minimum(cand, n - 1)]
    dists = jnp.where(valid, ref.wl1_rerank(pts, q, w), jnp.inf)
    neg, sel = jax.lax.top_k(-dists, k)
    want_d = -neg
    want_i = jnp.where(
        jnp.isfinite(want_d), jnp.take_along_axis(cand, sel, axis=1), -1
    )
    np.testing.assert_allclose(
        np.asarray(res.dists), np.asarray(want_d), rtol=1e-5, atol=1e-5
    )
    assert np.array_equal(np.asarray(res.ids), np.asarray(want_i))


@pytest.mark.parametrize("impl", ["interpret", "chunked"])
def test_scan_topk_positive_weights_ascending(impl, rng):
    """Sanity: ascending order, non-negative dists under positive weights."""
    data = jax.random.normal(rng, (70, 9))
    q = jax.random.normal(jax.random.fold_in(rng, 1), (4, 9))
    w = jnp.abs(jax.random.normal(jax.random.fold_in(rng, 2), (4, 9)))
    d, i = ops.wl1_scan_topk(data, q, w, 10, force=impl)
    d = np.asarray(d)
    assert np.all(np.diff(d, axis=1) >= -1e-6)
    assert np.all(d >= -1e-6)
    assert np.all(np.asarray(i) >= 0)


# The exact scan's gated merge: a (query block, row block) tile merges only
# when some block distance is below its query's k-th. Integer-valued rows,
# queries and weights make every distance exact in float32, so the kernel
# must match the oracle bit for bit whatever the summation order.
GATE_N, GATE_B, GATE_D = 700, 12, 20  # 6 row blocks, the last partial; 2 query blocks


def _gate_inputs(order, n=GATE_N, b=GATE_B, d=GATE_D, seed=0):
    """Rows ordered by distance to every query: ``ascending``, ``descending``
    or ``random``. Row r of the ascending table is r in every coordinate and
    each query lies below 0, so each row is strictly farther than the last."""
    rng = np.random.default_rng(seed)
    q = -rng.integers(0, 6, (b, d)).astype(np.float32)
    w = rng.integers(1, 4, (b, d)).astype(np.float32)
    rows = np.repeat(np.arange(n, dtype=np.float32)[:, None], d, axis=1)
    if order == "descending":
        rows = rows[::-1]
    elif order == "random":
        rows = rows[rng.permutation(n)]
    return jnp.asarray(np.ascontiguousarray(rows)), jnp.asarray(q), jnp.asarray(w)


def _assert_bit_identical(got, want):
    assert np.array_equal(np.asarray(got[0]), np.asarray(want[0]))
    assert np.array_equal(np.asarray(got[1]), np.asarray(want[1]))


@pytest.mark.parametrize("order", ["random", "ascending", "descending"])
def test_scan_topk_gate_row_order(order):
    data, q, w = _gate_inputs(order)
    got = wl1_scan_topk_pallas(data, q, w, 10, interpret=True)
    _assert_bit_identical(got, ref.wl1_scan_topk(data, q, w, 10))


@pytest.mark.parametrize("copy_to", [1, 3, 5])  # a later row block; 5 is the partial last
def test_scan_topk_gate_ties_keep_earlier_ids(copy_to):
    """Exact duplicates of the top-k rows in a later row block tie with
    their originals and must not displace the earlier ids."""
    rng = np.random.default_rng(copy_to)
    n, b, d, k = GATE_N, GATE_B, GATE_D, 10
    data = rng.integers(8, 16, (n, d)).astype(np.float32)
    data[:128] = rng.integers(0, 4, (128, d))  # block 0 holds every top-k row
    lo = 128 * copy_to
    data[lo : min(lo + 128, n)] = data[: min(128, n - lo)]
    q = rng.integers(0, 4, (b, d)).astype(np.float32)
    w = rng.integers(1, 4, (b, d)).astype(np.float32)
    data, q, w = jnp.asarray(data), jnp.asarray(q), jnp.asarray(w)
    got = wl1_scan_topk_pallas(data, q, w, k, interpret=True)
    _assert_bit_identical(got, ref.wl1_scan_topk(data, q, w, k))
    for ids in np.asarray(got[1]).tolist():  # a copy comes only after its original
        for pos, i in enumerate(ids):
            assert i < 128 or (i - lo) in ids[:pos]


@pytest.mark.parametrize("k,n", [(1, GATE_N), (128, GATE_N), (200, 150)])  # LANE; k > n
@pytest.mark.parametrize("order", ["random", "descending"])
def test_scan_topk_gate_k(k, n, order):
    data, q, w = _gate_inputs(order, n=n)
    got = wl1_scan_topk_pallas(data, q, w, k, interpret=True)
    _assert_bit_identical(got, ref.wl1_scan_topk(data, q, w, k))


@pytest.mark.parametrize("k", [1, 10, 128])
@pytest.mark.parametrize("order", ["ascending", "descending"])
def test_scan_topk_merge_count(order, k):
    """Ascending rows merge once per query block (the first row block fills
    the top-k, and nothing after it is nearer); descending rows merge in
    every row block. The counting variant returns the served answer."""
    data, q, w = _gate_inputs(order)
    d_, i_, merges = wl1_scan_topk_pallas(data, q, w, k, interpret=True, count_merges=True)
    row_blocks = -(-GATE_N // 128)
    want = 1 if order == "ascending" else row_blocks
    assert np.asarray(merges).tolist() == [want] * (-(-GATE_B // 8))
    _assert_bit_identical((d_, i_), wl1_scan_topk_pallas(data, q, w, k, interpret=True))
    share = ops.wl1_scan_topk_merge_share(data, q, w, k, force="interpret")
    assert float(share) == pytest.approx(want / row_blocks)
