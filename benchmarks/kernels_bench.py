"""Kernel microbenchmarks: CPU production path (jnp oracle) timings + Pallas
interpret-mode validation cost, plus the fused-vs-unfused probe-tail rows
that track the PR-over-PR perf trajectory (benchmarks/run.py snapshots them
into BENCH_kernels.json). On TPU the ops.py dispatcher switches to the
compiled Pallas kernels.

Fused-tail methodology: the "3-step path" is the seed's candidate tail as
separately dispatched kernel stages — gather the (b, P, d) candidate tensor,
``wl1_rerank`` it, ``lax.top_k`` the result — each materializing its output
(exactly how this file benchmarks every other kernel). The fused row is one
``ops.gather_rerank_topk`` call on the same deduped candidate ids. Candidate
ids come from REAL probes of a built index (planted near-neighbour queries,
the paper's R1-NNS regime) so the padding/duplicate structure the fused
kernel exploits is the production one.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.common import row, time_fn
from repro.kernels import ops, ref


def _probe_candidates(key, data, queries, weights, L: int, C: int, M: int):
    """Real probe → dedupe ids for a (L, C) budget over the given table."""
    from repro.api import BoundedSpace, Index, IndexConfig
    from repro.core import transforms
    from repro.core.index import _dedupe_candidates, _keys_for, _probe_one_table

    n, d = data.shape
    b = queries.shape[0]
    cfg = IndexConfig(
        d=d, M=M, K=14, L=L, family="theta", max_candidates=C,
        space=BoundedSpace(0.0, 1.0, float(M)),
    )
    idx = Index.build(key, data, cfg).state  # engine pytree for kernel-level rows
    qlevels = transforms.discretize(queries, cfg.space)
    qkeys = _keys_for(qlevels, weights, idx.tables, cfg, idx.mixers)
    probe = jax.vmap(
        jax.vmap(_probe_one_table, in_axes=(0, 0, 0, None)), in_axes=(None, None, 0, None)
    )
    cand = probe(idx.sorted_keys, idx.perm, qkeys, C).reshape(b, L * C)
    ids, n_cand = jax.jit(_dedupe_candidates, static_argnums=1)(cand, n)
    return ids, float(jnp.mean(n_cand))


def _fused_tail_rows(key):
    """Fused gather+rerank+topk vs the unfused 3-step path, b=64 d=128."""
    n, b, d, k, M = 65536, 64, 128, 10, 16
    data = jax.random.uniform(jax.random.fold_in(key, 0), (n, d))
    base = jax.random.randint(jax.random.fold_in(key, 1), (b,), 0, n)
    q = jnp.clip(
        data[base] + 0.01 * jax.random.normal(jax.random.fold_in(key, 2), (b, d)), 0, 1
    )
    w = jnp.abs(jax.random.normal(jax.random.fold_in(key, 3), (b, d))) + 0.1

    gather = jax.jit(lambda data, ids: data[jnp.minimum(ids, n - 1)])
    rerank = jax.jit(ops.wl1_rerank)

    @jax.jit
    def topk_step(dists, ids):
        dists = jnp.where(ids < n, dists, jnp.inf)
        neg, sel = jax.lax.top_k(-dists, k)
        outd = -neg
        return outd, jnp.where(
            jnp.isfinite(outd), jnp.take_along_axis(ids, sel, axis=1), -1
        )

    def unfused(data, ids, q, w):
        # three separate dispatches, each materializing its output; ordering
        # is enforced by data dependence (no artificial host syncs) and
        # time_fn blocks on the final result.
        pts = gather(data, ids)
        dists = rerank(pts, q, w)
        return topk_step(dists, ids)

    # the seed's compiled behavior: same 3 steps inside ONE jit region
    # (what query_index actually traced pre-fusion) — reported alongside so
    # the trajectory records both comparators.
    seed_jit = jax.jit(functools.partial(ref.gather_rerank_topk, k=k))

    fused = jax.jit(functools.partial(ops.gather_rerank_topk, k=k))

    out = []
    for P in (512, 1024, 2048, 4096):
        ids, uniq = _probe_candidates(
            jax.random.fold_in(key, 100 + P), data, q, w, L=8, C=P // 8, M=M
        )
        t_un = time_fn(unfused, data, ids, q, w)
        t_jit = time_fn(seed_jit, data, ids, q, w)
        t_f = time_fn(fused, data, ids, q, w)
        out.append(
            row(
                f"kernel_fused_tail_P{P}",
                t_f,
                f"b={b},d={d},k={k},uniq={uniq:.0f};unfused_us={t_un:.1f};"
                f"seedjit_us={t_jit:.1f};speedup={t_un / t_f:.2f}x;"
                f"speedup_vs_seedjit={t_jit / t_f:.2f}x",
            )
        )
    return out


def _segmented_tail_rows(key):
    """Engine two-segment tail: fused per-segment gather (``delta=``) vs
    the superseded concat-table path (materialize [main; delta], single
    gather) — same deduped candidate ids addressing both segments."""
    from repro.core.index import _dedupe_candidates

    n, cap, b, d, k = 65536, 4096, 64, 128, 10
    main = jax.random.uniform(jax.random.fold_in(key, 0), (n, d))
    delta = jax.random.uniform(jax.random.fold_in(key, 1), (cap, d))
    q = jax.random.uniform(jax.random.fold_in(key, 2), (b, d))
    w = jnp.abs(jax.random.normal(jax.random.fold_in(key, 3), (b, d))) + 0.1
    n_tot = n + cap

    fused = jax.jit(
        lambda m, dl, ids, q, w: ops.gather_rerank_topk(m, ids, q, w, k, delta=dl)
    )
    concat = jax.jit(
        lambda m, dl, ids, q, w: ops.gather_rerank_topk(
            jnp.concatenate([m, dl]), ids, q, w, k
        )
    )
    out = []
    for P in (1024, 4096):
        # ~1/8 of candidates land in the delta segment, ~20% sentinels —
        # the id mix a full delta produces after dedupe
        km = jax.random.fold_in(key, 100 + P)
        ids_m = jax.random.randint(jax.random.fold_in(km, 0), (b, (P * 7) // 8), 0, n)
        ids_d = jax.random.randint(
            jax.random.fold_in(km, 1), (b, P - (P * 7) // 8), n, n_tot + n_tot // 4
        )
        ids, _ = jax.jit(_dedupe_candidates, static_argnums=1)(
            jnp.concatenate([ids_m, ids_d], axis=1).astype(jnp.int32), n_tot
        )
        t_f = time_fn(fused, main, delta, ids, q, w)
        t_c = time_fn(concat, main, delta, ids, q, w)
        out.append(
            row(
                f"kernel_fused_tail_two_segment_P{P}",
                t_f,
                f"b={b},d={d},k={k},cap={cap};concat_us={t_c:.1f};"
                f"speedup={t_c / t_f:.2f}x",
            )
        )
    return out


def _scan_topk_rows(key):
    """Streaming top-k scan vs materializing scan + top_k baseline."""
    n, b, d, k = 65536, 64, 128, 10
    data = jax.random.normal(jax.random.fold_in(key, 0), (n, d))
    q = jax.random.normal(jax.random.fold_in(key, 1), (b, d))
    w = jax.random.normal(jax.random.fold_in(key, 2), (b, d))

    scan = jax.jit(ops.wl1_scan)

    @jax.jit
    def topk_step(dists):
        neg, ids = jax.lax.top_k(-dists, k)
        return -neg, ids

    def unfused(data, q, w):
        dists = jax.block_until_ready(scan(data, q, w))
        return topk_step(dists)

    fused = jax.jit(functools.partial(ops.wl1_scan_topk, k=k))
    t_un = time_fn(unfused, data, q, w)
    t_f = time_fn(fused, data, q, w)
    return [
        row(
            "kernel_wl1_scan_topk",
            t_f,
            f"n={n},b={b},d={d},k={k};unfused_us={t_un:.1f};"
            f"speedup={t_un / t_f:.2f}x",
        )
    ]


def run():
    key = jax.random.PRNGKey(0)
    n, d, H, M = 4096, 64, 256, 32
    levels = jax.random.randint(key, (n, d), 0, M + 1)
    folded = jax.random.normal(jax.random.fold_in(key, 1), (H, d, M + 1))
    weights = jax.random.normal(jax.random.fold_in(key, 2), (n, d))

    proj = jax.jit(lambda l, f: ops.alsh_project(l, f))
    proj_w = jax.jit(lambda l, f, w: ops.alsh_project(l, f, w))
    out = [
        row("kernel_alsh_project_data", time_fn(proj, levels, folded),
            f"n={n},d={d},H={H},M={M}"),
        row("kernel_alsh_project_query", time_fn(proj_w, levels, folded, weights),
            "weighted"),
    ]

    nd, b, dd = 65536, 64, 128
    data = jax.random.normal(jax.random.fold_in(key, 3), (nd, dd))
    q = jax.random.normal(jax.random.fold_in(key, 4), (b, dd))
    w = jax.random.normal(jax.random.fold_in(key, 5), (b, dd))
    scan = jax.jit(ops.wl1_scan)
    out.append(row("kernel_wl1_scan", time_fn(scan, data, q, w),
                   f"n={nd},b={b},d={dd} ({nd*b*dd*3/1e9:.1f} GOP)"))

    pts = jax.random.normal(jax.random.fold_in(key, 6), (b, 512, dd))
    rer = jax.jit(ops.wl1_rerank)
    out.append(row("kernel_wl1_rerank", time_fn(rer, pts, q, w), f"b={b},C=512,d={dd}"))

    out.extend(_scan_topk_rows(jax.random.fold_in(key, 7)))
    out.extend(_fused_tail_rows(jax.random.fold_in(key, 8)))
    out.extend(_segmented_tail_rows(jax.random.fold_in(key, 9)))
    return out
