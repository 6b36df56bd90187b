"""Chip smoke test: the paper's weighted-l1 ALSH service, driven once on a TPU.

    python chip_smoke.py [--seed 0]     # one chip
    python chip_smoke.py --four-chips   # Index.shard over four chips only

One chip. The deployment is the service config of ``repro.configs.paper_alsh``
(d=128, M=32, K=12, L=32, theta family, max_candidates=128, top-10) over
1,000,000 rows: the shape of ann-benchmarks' SIFT1M (``sift-128-euclidean``),
drawn from ``--seed`` uniformly in the config's space [0, 1]^128. Through
``repro.api.Index`` it

  * builds the index;
  * serves probe batches at b=64 and at the config's b=1024;
  * runs one exact batch, whose weights include negative entries;
  * inserts 8,192 rows, deletes some, and queries the two-segment view;
  * builds with int8 storage and queries it with ``screen_alpha=2.0``;
  * runs one ``early_exit=True`` batch.

Checks, against references outside the query path:

  * the exact batch equals a NumPy brute force sum(w·|o − q|) on the host
    for 16 queries, ties allowed;
  * every returned distance equals the NumPy distance of the returned row;
  * one batch's candidates, re-ranked by the pure-jnp oracle
    (``ops.gather_rerank_topk(force="ref")``), give the Pallas top-k;
  * every compiled program holds a Pallas kernel (``tpu_custom_call``), so
    no jnp schedule stands in for one.

Four chips (``--four-chips``, this phase only). 4,000,000 rows of the same
config; ``Index.shard`` over a ("data",) mesh of four is compared with the
single-device Index over the same rows on chip 0. Exact batches must agree bit
for bit. Probe batches must agree bit for bit on every query whose bucket
windows all fit max_candidates; where a bucket was cut, each shard keeps its
own max_candidates rows of it, so the sharded candidates are a superset and
its distances may only be smaller. So the probe batch is also compared, bit
for bit, with four one-device Indexes over the shards' rows, merged on the
host.

Prints per-phase compile and run times and recall@10, then as its last line
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Any failed check, or a JAX that finds no TPU, ends it with a non-zero exit
and no such line.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

N_ONE_CHIP = 1_000_000
N_FOUR_CHIPS = 4_000_000
K = 10
SMALL_B, LARGE_B = 64, 1024
N_INSERT = 8192
N_CHECK = 16  # queries checked against the host brute force
TOL = 1e-4  # distance tolerance, relative to sum(|w|): f32 sums of 128 terms
KERNEL_MARK = "tpu_custom_call"  # how a Pallas kernel shows in compiled HLO


class SmokeError(RuntimeError):
    """A phase returned a wrong result."""


def check(ok, msg: str) -> None:
    if not ok:
        raise SmokeError(msg)


def run(name: str, fn, *args, kernel: bool = True):
    """Compile ``fn`` for ``args``, check that the program holds a Pallas
    kernel, run it once. Returns (compiled, output)."""
    import jax

    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    t1 = time.perf_counter()
    if kernel:
        check(
            KERNEL_MARK in compiled.as_text(),
            f"{name}: the compiled program holds no Pallas kernel",
        )
    out = jax.block_until_ready(compiled(*args))
    print(f"[{name}] compile {t1 - t0:.3f} s  run {time.perf_counter() - t1:.4f} s",
          flush=True)
    return compiled, out


def rerun(name: str, compiled, *args):
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    print(f"[{name}] run {time.perf_counter() - t0:.4f} s", flush=True)
    return out


# -- data, from the seed ------------------------------------------------------
def make_queries(key, rows, b: int):
    """Jittered copies of random rows, so queries lie near the data."""
    import jax
    import jax.numpy as jnp

    k_src, k_noise = jax.random.split(key)
    src = jax.random.randint(k_src, (b,), 0, rows.shape[0])
    noise = 0.02 * jax.random.normal(k_noise, (b, rows.shape[1]))
    return jnp.clip(rows[src] + noise, 0.0, 1.0), src


def make_weights(key, b: int, d: int, signed: bool = False):
    import jax

    lo = -0.5 if signed else 0.1  # signed: a third of the entries negative
    return jax.random.uniform(key, (b, d), minval=lo, maxval=1.0)


# -- host references ----------------------------------------------------------
def np_dists(rows: np.ndarray, q: np.ndarray, w: np.ndarray) -> np.ndarray:
    """sum(w·|o − q|) of every row, in float64."""
    return np.abs(rows - q).astype(np.float64) @ w.astype(np.float64)


def check_distances(name, res, rows: np.ndarray, q, w, dead=None) -> None:
    """Every returned distance is the NumPy distance of its row; ids are
    unique, ascending by distance, and never a deleted row."""
    ids, dists = np.asarray(res.ids), np.asarray(res.dists)
    q, w = np.asarray(q), np.asarray(w)
    valid = ids >= 0
    check(np.array_equal(valid, np.isfinite(dists)), f"{name}: id/distance sentinels disagree")
    check(np.all(ids < rows.shape[0]), f"{name}: id beyond the table")
    picked = rows[np.where(valid, ids, 0)]  # (b, k, d)
    want = np.einsum("bkd,bd->bk", np.abs(picked - q[:, None, :]).astype(np.float64),
                     w.astype(np.float64))
    tol = TOL * np.abs(w).sum(axis=1, keepdims=True)
    err = np.where(valid, np.abs(dists - want), 0.0)
    check(np.all(err <= tol), f"{name}: distance off its row by {err.max():.3g}")
    fin = np.where(valid, dists, np.inf)
    check(np.all(fin[:, 1:] >= fin[:, :-1]), f"{name}: results not ascending")
    for r in range(ids.shape[0]):
        real = ids[r][valid[r]]
        check(len(set(real.tolist())) == real.size, f"{name}: duplicate id in row {r}")
    if dead is not None:
        check(not np.any(dead[np.where(valid, ids, 0)] & valid), f"{name}: deleted row returned")


def check_exact(name, res, rows: np.ndarray, q, w) -> None:
    """The first N_CHECK queries' top-k equals the NumPy brute force, ties
    allowed: each returned distance is its row's, and the sorted distances
    are the k smallest."""
    ids, dists = np.asarray(res.ids), np.asarray(res.dists)
    q, w = np.asarray(q), np.asarray(w)
    for r in range(N_CHECK):
        d = np_dists(rows, q[r], w[r])
        tol = TOL * np.abs(w[r]).sum()
        want = np.sort(np.partition(d, K - 1)[:K])
        check(np.all(ids[r] >= 0), f"{name}: query {r} returned fewer than {K} rows")
        check(np.all(np.abs(dists[r] - d[ids[r]]) <= tol),
              f"{name}: query {r} distance is not its row's")
        check(np.all(np.abs(dists[r] - want) <= tol),
              f"{name}: query {r} top-{K} is not the brute force's "
              f"(max gap {np.abs(dists[r] - want).max():.3g})")


def check_same_topk(name, a, b, w) -> None:
    """Two top-k results agree up to ties at the k-th distance."""
    ad, ai = np.asarray(a[0]), np.asarray(a[1])
    bd, bi = np.asarray(b[0]), np.asarray(b[1])
    tol = TOL * np.abs(np.asarray(w)).sum(axis=1)
    check(np.array_equal(np.isfinite(ad), np.isfinite(bd)), f"{name}: sentinels differ")
    gap = np.where(np.isfinite(ad), np.abs(ad - bd), 0.0).max(axis=1)
    check(np.all(gap <= tol), f"{name}: distances differ by {gap.max():.3g}")
    for r in range(ai.shape[0]):
        swapped = set(ai[r].tolist()) ^ set(bi[r].tolist())
        if swapped:
            kth = ad[r][np.isfinite(ad[r])].max()
            dist_of = dict(zip(ai[r].tolist(), ad[r])) | dict(zip(bi[r].tolist(), bd[r]))
            check(all(abs(dist_of[i] - kth) <= tol[r] for i in swapped),
                  f"{name}: row {r} ids differ beyond a tie")


def identical(a, b) -> bool:
    return all(np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(a, b))


def recall(ids, truth) -> float:
    from repro.distance import recall_at_k

    return recall_at_k(np.asarray(ids), np.asarray(truth), K)


# -- phases ---------------------------------------------------------------------
def one_chip(seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro.api import Index, QuerySpec, UpdateSpec
    from repro.configs.paper_alsh import SERVICE
    from repro.core.index import _dedupe_candidates
    from repro.engine.pipeline import probe_keys, sources_for
    from repro.kernels import ops

    cfg = SERVICE.index_config
    d = cfg.d
    key = jax.random.PRNGKey(seed)
    ks = iter(jax.random.split(key, 32))
    data = jax.random.uniform(next(ks), (N_ONE_CHIP, d))
    rows = np.asarray(data)
    print(f"data: {N_ONE_CHIP} x {d} f32 rows ({rows.nbytes / 2**20:.0f} MiB), "
          f"config {cfg}", flush=True)

    def query(spec):
        return lambda ix, q, w: ix.query(q, w, spec)

    probe, exact = QuerySpec(k=K), QuerySpec(k=K, mode="exact")
    build_key = next(ks)
    _, index = run("build f32", lambda k, x: Index.build(k, x, cfg), build_key, data)

    # exact batch: weights with negative entries, against the host brute force
    qx, _ = make_queries(next(ks), data, SMALL_B)
    wx = make_weights(next(ks), SMALL_B, d, signed=True)
    exact_prog, res = run(f"exact b={SMALL_B}", query(exact), index, qx, wx)
    check_exact(f"exact b={SMALL_B}", res, rows, qx, wx)
    check_distances(f"exact b={SMALL_B}", res, rows, qx, wx)

    # probe batches at b=64; exact results of the same queries are the truth
    small = []
    for i in range(3):
        q, _ = make_queries(next(ks), data, SMALL_B)
        w = make_weights(next(ks), SMALL_B, d)
        name = f"probe b={SMALL_B} #{i}"
        if i == 0:
            probe_prog, res = run(name, query(probe), index, q, w)
        else:
            res = rerun(name, probe_prog, index, q, w)
        check_distances(name, res, rows, q, w)
        truth = rerun(f"exact b={SMALL_B} (truth for {name})", exact_prog, index, q, w)
        print(f"[{name}] recall@{K} {recall(res.ids, truth.ids):.4f}  mean candidates "
              f"{float(np.mean(np.asarray(res.n_candidates))):.1f}", flush=True)
        small.append((q, w, res, truth))

    # probe batches at the config's b=1024; recall on the first 64 queries
    for i in range(2):
        q, _ = make_queries(next(ks), data, LARGE_B)
        w = make_weights(next(ks), LARGE_B, d)
        name = f"probe b={LARGE_B} #{i}"
        if i == 0:
            big_prog, res = run(name, query(probe), index, q, w)
        else:
            res = rerun(name, big_prog, index, q, w)
        check_distances(name, res, rows, q, w)
        truth = exact_prog(index, q[:SMALL_B], w[:SMALL_B])
        print(f"[{name}] recall@{K} (first {SMALL_B}) "
              f"{recall(np.asarray(res.ids)[:SMALL_B], truth.ids):.4f}", flush=True)

    # one batch's candidates: Pallas rerank vs the jnp oracle, and vs the engine
    q, w, res, _ = small[0]

    def candidates(ix, q, w):
        keys = probe_keys(ix.state, q, w, ix.config, mode="probe", n_probes=1,
                          max_flips=0, impl="auto")
        blocks = [s.emit(q, w) for s in sources_for(ix.state, None, None, ix.config, keys)]
        return _dedupe_candidates(jnp.concatenate(blocks, axis=1), ix.state.n)[0]

    cand = jax.jit(candidates)(index, q, w)
    _, pallas = run("rerank pallas", lambda x, c, q, w: ops.gather_rerank_topk(x, c, q, w, K),
                    index.state.data, cand, q, w)
    oracle = jax.jit(lambda x, c, q, w: ops.gather_rerank_topk(x, c, q, w, K, force="ref"))(
        index.state.data, cand, q, w)
    check_same_topk("rerank pallas vs ref", pallas, oracle, w)
    check_same_topk("rerank pallas vs engine", pallas, (res.dists, res.ids), w)
    print(f"[rerank] {cand.shape[1]} candidate slots: Pallas top-{K} == jnp oracle; "
          f"bit-identical to the engine's: {identical(pallas, (res.dists, res.ids))}",
          flush=True)

    # early exit on the first b=64 batch: the same top-k at exit_slack=0
    _, ee = run(f"probe early_exit b={SMALL_B}", query(QuerySpec(k=K, early_exit=True)),
                index, q, w)
    check(ee.tables_probed is not None, "early exit: the engine ran the monolithic tail")
    check_distances("early exit", ee, rows, q, w)
    check_same_topk("early exit vs probe", (ee.dists, ee.ids), (res.dists, res.ids), w)
    print(f"[probe early_exit b={SMALL_B}] recall@{K} {recall(ee.ids, small[0][3].ids):.4f}  "
          f"mean tables probed {float(np.mean(np.asarray(ee.tables_probed))):.2f} "
          f"of {cfg.L}; bit-identical to probe: "
          f"{identical((ee.dists, ee.ids), (res.dists, res.ids))}", flush=True)

    # int8 rows, proxy screen at alpha=2, exact rerank of the decoded rows
    cfg8 = dataclasses.replace(cfg, storage="int8")
    _, ix8 = run("build int8", lambda k, x: Index.build(k, x, cfg8), build_key, data)
    decoded = np.asarray(ix8.state.data, np.float32) * np.asarray(ix8.state.scales)
    _, res8 = run(f"probe int8 screen_alpha=2 b={SMALL_B}",
                  query(QuerySpec(k=K, screen_alpha=2.0)), ix8, q, w)
    check_distances("int8", res8, decoded, q, w)
    print(f"[probe int8 screen_alpha=2 b={SMALL_B}] recall@{K} vs f32 exact "
          f"{recall(res8.ids, small[0][3].ids):.4f}  table "
          f"{ix8.table_bytes / 2**20:.0f} MiB (f32 {index.table_bytes / 2**20:.0f} MiB)",
          flush=True)
    del ix8, decoded

    # two-segment view: insert N_INSERT rows, delete some of both segments
    _, mix = run("build mutable", lambda k, x: Index.build(
        k, x, cfg, update=UpdateSpec(delta_capacity=N_INSERT)), build_key, data)
    new = jax.random.uniform(next(ks), (N_INSERT, d))
    _, (mix, new_ids) = run(f"insert {N_INSERT}", lambda ix, r: ix.insert(r), mix, new)
    new_ids = np.asarray(new_ids)
    check(np.array_equal(new_ids, N_ONE_CHIP + np.arange(N_INSERT)), "insert: unexpected ids")
    all_rows = np.concatenate([rows, np.asarray(new)])
    # queries near inserted rows and near main rows that get deleted
    q_new, src_new = make_queries(next(ks), new, SMALL_B // 2)
    q_old, src_old = make_queries(next(ks), data, SMALL_B // 2)
    q2 = jnp.concatenate([q_old, q_new])
    w2 = make_weights(next(ks), SMALL_B, d)
    rng = np.random.default_rng(seed)
    dead_ids = np.unique(np.concatenate([
        np.asarray(src_old),
        rng.choice(N_ONE_CHIP, N_ONE_CHIP // 256, replace=False),
        N_ONE_CHIP + rng.choice(N_INSERT, N_INSERT // 8, replace=False),
    ])).astype(np.int32)
    _, mix = run("delete", lambda ix, i: ix.delete(i), mix, jnp.asarray(dead_ids), kernel=False)
    dead = np.zeros(all_rows.shape[0], bool)
    dead[dead_ids] = True
    _, res2 = run(f"probe two-segment b={SMALL_B}", query(probe), mix, q2, w2)
    check_distances("two-segment", res2, all_rows, q2, w2, dead=dead)
    truth2 = np.stack([
        np.argsort(np.where(dead, np.inf, np_dists(all_rows, np.asarray(q2)[r],
                                                   np.asarray(w2)[r])))[:K]
        for r in range(N_CHECK)
    ])
    found = np.mean(np.asarray(res2.ids)[SMALL_B // 2:, 0] ==
                    N_ONE_CHIP + np.asarray(src_new))
    print(f"[probe two-segment b={SMALL_B}] recall@{K} (first {N_CHECK}, host brute force) "
          f"{recall(np.asarray(res2.ids)[:N_CHECK], truth2):.4f}  inserted source row "
          f"ranked first for {found:.3f} of the queries near inserts", flush=True)


def four_chips(seed: int) -> None:
    import jax

    from repro.api import Index, QuerySpec
    from repro.configs.paper_alsh import SERVICE
    from repro.core.distributed import merge_topk_host
    from repro.launch.mesh import make_mesh

    check(len(jax.devices()) == 4, f"--four-chips needs 4 devices, found {len(jax.devices())}")
    cfg = SERVICE.index_config
    key = jax.random.PRNGKey(seed)
    k_data, k_build, k_q, k_w, k_wx = jax.random.split(key, 5)
    data = jax.random.uniform(k_data, (N_FOUR_CHIPS, cfg.d))  # on chip 0
    _, index = run(f"build f32 n={N_FOUR_CHIPS} on chip 0",
                   lambda k, x: Index.build(k, x, cfg), k_build, data)
    del data  # the index holds its own copy

    t0 = time.perf_counter()
    sharded = index.shard(make_mesh((4,), ("data",)))
    jax.block_until_ready(sharded.index_sharded)
    n_dev = len(sharded.index_sharded.data.sharding.device_set)
    check(n_dev == 4, f"sharded rows live on {n_dev} devices, not 4")
    print(f"[shard over 4 chips] {time.perf_counter() - t0:.3f} s (compile + run)", flush=True)

    q, _ = make_queries(k_q, index.state.data, SMALL_B)
    w = make_weights(k_w, SMALL_B, cfg.d)
    wx = make_weights(k_wx, SMALL_B, cfg.d, signed=True)
    for name, spec, ww in (("probe", QuerySpec(k=K), w),
                           ("exact", QuerySpec(k=K, mode="exact"), wx)):
        report = index.explain(q, ww, spec)
        one = report.result
        for call in (1, 2):
            t0 = time.perf_counter()
            got = jax.block_until_ready(sharded.query(q, ww, spec))
            print(f"[sharded {name} b={SMALL_B} call {call}] {time.perf_counter() - t0:.4f} s",
                  flush=True)
        od, oi = np.asarray(one.dists), np.asarray(one.ids)
        gd, gi = np.asarray(got.dists), np.asarray(got.ids)
        cut = np.asarray(report.truncated_tables) > 0
        same = np.array_equal(od[~cut], gd[~cut]) and np.array_equal(oi[~cut], gi[~cut])
        check(same, f"sharded {name}: differs from the single-device index on a query "
                    f"with no truncated bucket")
        check(np.all(gd[cut] <= od[cut]),
              f"sharded {name}: a truncated query came back worse than single-device")
        print(f"[sharded {name} b={SMALL_B}] bit-identical to chip 0 on {int((~cut).sum())} "
              f"queries with whole buckets; {int(cut.sum())} truncated queries "
              f"no worse ({int((gi[cut] == oi[cut]).all(axis=1).sum())} identical)",
              flush=True)

    # Where buckets are cut, the sharded probe is by design the one-device
    # Index over each shard's rows, merged: check that bit for bit.
    n_local = N_FOUR_CHIPS // 4
    parts_d, parts_i, parts_n = [], [], []
    for s in range(4):
        part = Index.build(index.build_key, index.state.data[s * n_local:(s + 1) * n_local], cfg)
        res = part.query(q, w, QuerySpec(k=K))
        ids = np.asarray(res.ids)
        parts_d.append(np.asarray(res.dists))
        parts_i.append(np.where(ids >= 0, ids + s * n_local, -1))
        parts_n.append(np.asarray(res.n_candidates))
        del part
    want_d, want_i = merge_topk_host(np.stack(parts_d), np.stack(parts_i), K)
    got = sharded.query(q, w, QuerySpec(k=K))
    check(np.array_equal(want_d, np.asarray(got.dists)) and
          np.array_equal(want_i, np.asarray(got.ids)),
          "sharded probe: differs from the per-shard one-device indexes, merged")
    check(np.array_equal(sum(parts_n), np.asarray(got.n_candidates)),
          "sharded probe: candidate counts differ from the per-shard indexes'")
    print(f"[sharded probe b={SMALL_B}] bit-identical, ids, distances and candidate "
          f"counts, to 4 one-device indexes over the shards' rows on chip 0, merged",
          flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the Index.shard phase, on four chips")
    ap.add_argument("--seed", type=int, default=0, help="seed of rows, queries, weights")
    args = ap.parse_args(argv)

    import jax

    from repro.launch.compile_cache import use_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (device 0 is {dev.platform})")
    print(f"compile cache: {use_compile_cache()}", flush=True)
    t0 = time.perf_counter()
    if args.four_chips:
        four_chips(args.seed)
    else:
        one_chip(args.seed)
    print(f"total {time.perf_counter() - t0:.1f} s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
