"""Serving launcher — four modes:

  ALSH vector-search service (the paper's workload), served end-to-end
  through the ``repro.api`` Index facade on the shared ``repro.engine``
  pipeline (key enumeration → candidate sources → dedupe →
  gather_rerank_topk kernels; the exactness spot-check is the same facade
  with QuerySpec(mode="exact") — the oracle runs the identical tail it
  validates). Configuration is
  QUALITY-FIRST: state a recall target and the planner resolves the
  execution knobs (and prints its resolution + per-batch diagnostics):
    python -m repro.launch.serve --mode alsh --recall-target 0.9
  The legacy knob path is untouched — give explicit knobs and no planning
  happens (bit-identical to previous releases):
    python -m repro.launch.serve --mode alsh [--n 100000 --d 64 --batches 4]
    python -m repro.launch.serve --mode alsh --multiprobe --probes 8

  Streaming-ingest service — the mutable lifecycle under live traffic:
  every tick interleaves an insert batch and a retire batch with the query
  batches, all on one jit-compiled program (fixed delta capacity ⇒ no
  retrace), compacting when the delta fills past the policy threshold.
  The engine's chunked delta key match keeps per-query memory independent
  of the capacity, so large deltas (16k+, fewer compaction stalls) are a
  plain flag away:
    python -m repro.launch.serve --mode stream --ingest 512 --retire 128 \
        --delta-capacity 16384

  Fault-tolerant broker service — the full serving tier (repro.serving):
  dynamic batching over an arrival trace, SLO admission control with the
  calibrated degradation ladder, and optional shard chaos (mid-stream
  kill, survivors-only answers with labeled coverage, backoff recovery):
    python -m repro.launch.serve --mode broker --recall-target 0.9 \
        --slo-p99-ms 50 --arrival bursty --rate 500 --requests 2000
    python -m repro.launch.serve --mode broker --shards 4 --kill-shard 1 \
        --kill-at 0.5

  LM decode service with optional ALSH retrieval augmentation:
    python -m repro.launch.serve --mode lm --arch gemma3-1b --reduced --retrieval

All run real batched requests on local devices; the production mesh path is
exercised by the dry-run.
"""

from __future__ import annotations

import argparse
import time

from repro.launch.compile_cache import use_compile_cache


def serve_alsh(args):
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.api import Index, QualitySpec, QuerySpec
    from repro.configs.paper_alsh import ALSHServiceConfig
    from repro.distance import recall_at_k

    svc = ALSHServiceConfig(
        n_per_shard=args.n, d=args.d, K=args.K, L=args.L,
        query_batch=args.query_batch, topk=args.topk,
    )
    key = jax.random.PRNGKey(0)
    data = jax.random.uniform(jax.random.fold_in(key, 1), (svc.n_per_shard, svc.d))

    # quality-first: a stated recall target plans BOTH the geometry and the
    # serving policy; explicit knobs (the legacy path) skip planning entirely
    quality = None
    if args.recall_target is not None:
        quality = QualitySpec(k=svc.topk, recall_target=args.recall_target,
                              latency_budget_ms=args.latency_budget_ms)
    build_cfg = quality if quality is not None else svc.index_config
    if args.storage != "f32" and quality is None:
        build_cfg = dataclasses.replace(build_cfg, storage=args.storage)
    t0 = time.time()
    index = Index.build(jax.random.fold_in(key, 2), data, build_cfg)
    jax.block_until_ready(index.state.sorted_keys)
    cfg = index.config
    print(f"[alsh] built index over n={svc.n_per_shard} d={svc.d} "
          f"family={cfg.family} K={cfg.K} L={cfg.L} storage={cfg.storage} "
          f"in {time.time()-t0:.2f}s"
          + (" (planned from QualitySpec)" if quality is not None else ""))

    # serving policy is a spec value, not a code path
    if quality is not None:
        t0 = time.time()
        spec = index.plan(quality)  # calibration pass, memoized
        print(f"[alsh] planned in {time.time()-t0:.2f}s: {spec}")
    elif args.multiprobe:
        spec = QuerySpec(k=svc.topk, mode="multiprobe", n_probes=args.probes)
    else:
        spec = QuerySpec(k=svc.topk)
    if cfg.storage != "f32" and spec.mode != "exact" and spec.screen_alpha == 0.0:
        # quantized tier: screen against compressed rows, exact-rerank the
        # top k*alpha survivors
        spec = dataclasses.replace(spec, screen_alpha=args.screen_alpha)
    if args.early_exit and spec.mode != "exact" and spec.screen_alpha == 0.0:
        # adaptive probing: stream probe windows, stop per query once the
        # running top-k clears the confidence bound (DESIGN §13)
        spec = dataclasses.replace(
            spec, early_exit=True, exit_group=args.exit_group,
            exit_slack=args.exit_slack,
        )
    exact = QuerySpec(k=svc.topk, mode="exact")
    print(f"[alsh] serving policy: {spec}")

    for b in range(args.batches):
        kq = jax.random.fold_in(key, 100 + b)
        q = jax.random.uniform(kq, (svc.query_batch, svc.d))
        w = jnp.abs(jax.random.normal(jax.random.fold_in(kq, 1), (svc.query_batch, svc.d))) + 0.1
        t0 = time.time()
        res = index.query(q, w, spec)
        jax.block_until_ready(res.dists)
        dt = time.time() - t0
        # spot-check recall on the first 16 queries (exact mode = the oracle)
        ref = index.query(q[:16], w[:16], exact)
        rec = recall_at_k(res.ids[:16], ref.ids, svc.topk)
        line = (f"[alsh] batch {b}: {svc.query_batch} queries in {dt*1e3:.1f} ms "
                f"({dt/svc.query_batch*1e6:.1f} us/query) "
                f"cand_frac={float(jnp.mean(res.n_candidates))/svc.n_per_shard:.4f} "
                f"recall@{svc.topk}~{rec:.2f}")
        if quality is not None:
            # per-query diagnostics: predicted success + truncation pressure
            rep = index.explain(q[:16], w[:16], spec)
            line += (f" pred_success~{float(rep.predicted_success.mean()):.2f} "
                     f"truncated={int((rep.truncated_tables > 0).sum())}/16")
        print(line)
        if args.stats:
            # storage-tier accounting: bytes moved by the gather tail
            import numpy as np
            rep = index.explain(q[:16], w[:16], spec)
            print(f"[alsh]   stats: storage={rep.storage} "
                  f"table_bytes={rep.table_bytes} "
                  f"rows_screened~{float(np.mean(rep.rows_screened)):.1f} "
                  f"rows_reranked~{float(np.mean(rep.rows_reranked)):.1f} "
                  f"bytes_gathered~{float(np.mean(rep.bytes_gathered)):.0f}")
            if rep.tables_probed is not None:
                # adaptive-probing accounting: windows visited + stop mix
                d = rep.to_dict()
                n_win = cfg.L * (spec.n_probes if spec.mode == "multiprobe"
                                 else 1)
                print(f"[alsh]   stats: tables_probed~"
                      f"{d['mean_tables_probed']:.1f}/{n_win} "
                      f"stop_reasons={d['stop_reasons']}")


def serve_alsh_stream(args):
    """Mutable-index service: rows arrive and retire while queries flow."""
    import jax
    import jax.numpy as jnp

    from repro.api import Index, QuerySpec, UpdateSpec
    from repro.configs.paper_alsh import ALSHServiceConfig
    from repro.distance import recall_at_k

    svc = ALSHServiceConfig(
        n_per_shard=args.n, d=args.d, K=args.K, L=args.L,
        query_batch=args.query_batch, topk=args.topk,
    )
    key = jax.random.PRNGKey(0)
    data = jax.random.uniform(jax.random.fold_in(key, 1), (svc.n_per_shard, svc.d))
    update = UpdateSpec(delta_capacity=args.delta_capacity,
                        compact_threshold=args.compact_threshold)
    t0 = time.time()
    index = Index.build(jax.random.fold_in(key, 2), data, svc.index_config,
                        update=update)
    jax.block_until_ready(index.state.sorted_keys)
    print(f"[stream] built mutable index n={svc.n_per_shard} d={svc.d} "
          f"delta_capacity={args.delta_capacity} in {time.time()-t0:.2f}s")

    spec = QuerySpec(k=svc.topk)
    exact = QuerySpec(k=svc.topk, mode="exact")
    # one compiled program each for the whole service life (static shapes)
    jquery = jax.jit(lambda ix, q, w: ix.query(q, w, spec))
    jinsert = jax.jit(lambda ix, rows: ix.insert(rows))
    jdelete = jax.jit(lambda ix, ids: ix.delete(ids))

    next_retire = 0  # retire oldest main rows first (FIFO churn)
    for b in range(args.batches):
        kb = jax.random.fold_in(key, 100 + b)
        # ingest: new rows enter the delta segment
        rows = jax.random.uniform(jax.random.fold_in(kb, 0),
                                  (args.ingest, svc.d))
        t0 = time.time()
        index, ids = jinsert(index, rows)
        jax.block_until_ready(ids)
        t_ins = time.time() - t0
        # retire: oldest rows tombstone out
        retire = jnp.arange(next_retire, next_retire + args.retire,
                            dtype=jnp.int32)
        next_retire += args.retire
        index = jdelete(index, retire)
        # serve queries against the live two-segment view
        q = jax.random.uniform(jax.random.fold_in(kb, 1), (svc.query_batch, svc.d))
        w = jnp.abs(jax.random.normal(jax.random.fold_in(kb, 2),
                                      (svc.query_batch, svc.d))) + 0.1
        t0 = time.time()
        res = jquery(index, q, w)
        jax.block_until_ready(res.dists)
        t_q = time.time() - t0
        ref = index.query(q[:16], w[:16], exact)
        rec = recall_at_k(res.ids[:16], ref.ids, svc.topk)
        fill = index.delta_fill
        print(f"[stream] tick {b}: +{args.ingest} rows in {t_ins*1e3:.1f} ms "
              f"({args.ingest/max(t_ins,1e-9):,.0f} rows/s), -{args.retire} retired, "
              f"{svc.query_batch} queries in {t_q*1e3:.1f} ms "
              f"({t_q/svc.query_batch*1e6:.1f} us/query) "
              f"delta={fill}/{args.delta_capacity} recall@{svc.topk}~{rec:.2f}")
        if index.needs_compact:
            t0 = time.time()
            index = index.compact()
            jax.block_until_ready(index.state.sorted_keys)
            # compact renumbers survivors to [0, n_live); everything below
            # next_retire was tombstoned, so the oldest surviving row is 0
            next_retire = 0
            print(f"[stream] compacted to n={index.n} (delta emptied) "
                  f"in {time.time()-t0:.2f}s")


def serve_broker(args):
    """Fault-tolerant broker drill: arrival trace -> batched engine calls
    under an SLO, with optional scripted shard failure."""
    import tempfile

    import jax
    import numpy as np

    from repro.api import Index, QualitySpec
    from repro.serving import (
        Broker,
        BrokerConfig,
        ChaosPlan,
        ShardSet,
        SLOConfig,
        make_trace,
        requests_from_trace,
    )

    key = jax.random.PRNGKey(0)
    data = jax.random.uniform(jax.random.fold_in(key, 1), (args.n, args.d))
    quality = QualitySpec(
        k=args.topk,
        recall_target=args.recall_target if args.recall_target is not None else 0.9,
    )
    t0 = time.time()
    index = Index.build(jax.random.fold_in(key, 2), data, quality)
    ladder = index.plan_ladder(quality)
    print(f"[broker] built+planned n={args.n} d={args.d} in {time.time()-t0:.2f}s; "
          f"ladder has {len(ladder)} rungs "
          f"(recalls {[round(float(r.predicted_recall), 3) for r in ladder]})")

    shardset = None
    tmp = None
    if args.shards > 1:
        tmp = tempfile.TemporaryDirectory(prefix="repro_shards_")
        t0 = time.time()
        shardset = ShardSet.build(index, args.shards, tmp.name)
        print(f"[broker] built {args.shards} shards (persisted for recovery) "
              f"in {time.time()-t0:.2f}s")
        if args.kill_shard is not None:
            shardset.chaos = ChaosPlan(
                kill_shard=args.kill_shard, kill_at_s=args.kill_at
            )
            print(f"[broker] chaos armed: kill shard {args.kill_shard} "
                  f"at t={args.kill_at}s")

    slo = SLOConfig(p99_ms=args.slo_p99_ms)
    broker = Broker(
        index, quality, slo,
        BrokerConfig(max_batch=args.max_batch, max_queue=args.max_queue),
        shardset=shardset,
    )
    kq = jax.random.fold_in(key, 3)
    q = np.asarray(jax.random.uniform(kq, (256, args.d)))
    w = np.abs(np.asarray(jax.random.normal(jax.random.fold_in(kq, 1), (256, args.d)))) + 0.1
    trace = make_trace(args.arrival, args.rate, args.requests, seed=0)
    reqs = requests_from_trace(trace, q, w)
    t0 = time.time()
    responses, stats = broker.run(reqs)
    broker.assert_no_retrace()
    print(f"[broker] {args.arrival} trace: {len(reqs)} requests at ~{args.rate}/s "
          f"served in {time.time()-t0:.2f}s wall")
    print(f"[broker] p50={stats.p50_ms:.2f}ms p99={stats.p99_ms:.2f}ms "
          f"(SLO {slo.p99_ms}ms) throughput={stats.throughput_rps:.0f} req/s")
    print(f"[broker] shed_rate={stats.shed_rate:.3f} "
          f"degraded_frac={stats.degraded_frac:.3f} rungs={stats.rung_counts} "
          f"mean_coverage={stats.mean_coverage:.3f}")
    if shardset is not None and args.kill_shard is not None:
        served = [r for r in responses if r.status != "shed"]
        covs = sorted({round(r.coverage, 6) for r in served})
        expect = (args.shards - 1) / args.shards
        events = [e["event"] for e in shardset.recovery_log]
        print(f"[broker] chaos: coverages seen {covs}; recovery log events {events}")
        assert any(abs(c - expect) < 1e-9 for c in covs), (
            f"expected some survivors-only answers at coverage {expect}, got {covs}"
        )
        assert "killed" in events, "scripted kill never fired"
        assert "recovered" in events, "shard never recovered within the trace"
        assert shardset.coverage == 1.0, "shard set did not return to full coverage"
        print("[broker] chaos assertions passed: labeled degraded coverage + recovery")
    if tmp is not None:
        tmp.cleanup()


def serve_lm(args):
    import jax
    import jax.numpy as jnp

    from repro import models
    from repro.configs import RetrievalConfig, get_bundle, reduced_model
    from repro.runtime import retrieval as rt
    from repro.runtime.serve_step import make_decode_step, make_prefill_step

    bundle = get_bundle(args.arch)
    mcfg = reduced_model(bundle.model) if args.reduced else bundle.model
    rcfg = None
    if args.retrieval:
        rcfg = RetrievalConfig(datastore_size=4096, d_key=16, K=6, L=8, topk=4)

    key = jax.random.PRNGKey(0)
    params = models.init_params(key, mcfg)
    B, S, gen = args.batch, args.prompt_len, args.gen_len
    prompt = jax.random.randint(jax.random.fold_in(key, 1), (B, S), 0, mcfg.vocab_size)

    prefill = jax.jit(make_prefill_step(mcfg, cache_len=S + gen))
    decode = jax.jit(make_decode_step(mcfg, rcfg))
    retr_state = None
    if rcfg is not None:
        retr_state = rt.build_datastore(jax.random.fold_in(key, 2), mcfg.d_model,
                                        mcfg.vocab_size, rcfg)

    t0 = time.time()
    logits, caches = prefill(params, {"tokens": prompt})
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    jax.block_until_ready(tok)
    print(f"[lm] prefill B={B} S={S} in {time.time()-t0:.2f}s "
          f"(retrieval={'on' if rcfg else 'off'})")

    out = [tok]
    t0 = time.time()
    for i in range(gen):
        batch = {"token": tok, "pos": jnp.full((B,), S + i, jnp.int32)}
        if rcfg is None:
            _, tok, caches = decode(params, batch, caches)
        else:
            _, tok, caches = decode(params, batch, caches, retr_state)
        out.append(tok)
    jax.block_until_ready(tok)
    dt = time.time() - t0
    print(f"[lm] generated {gen} tokens x {B} seqs in {dt:.2f}s "
          f"({dt/gen*1e3:.1f} ms/step); sample: {[int(t[0]) for t in out[:8]]}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["alsh", "stream", "broker", "lm"],
                    default="alsh")
    ap.add_argument("--arch", default="gemma3-1b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--retrieval", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--n", type=int, default=65536)
    ap.add_argument("--d", type=int, default=32)
    ap.add_argument("--K", type=int, default=12)
    ap.add_argument("--L", type=int, default=32)
    ap.add_argument("--query-batch", type=int, default=256)
    ap.add_argument("--topk", type=int, default=10)
    ap.add_argument("--batches", type=int, default=3)
    ap.add_argument("--storage", choices=["f32", "bf16", "int8"],
                    default="f32",
                    help="alsh mode: compressed table tier (explicit-knob "
                         "path; quantized rows are screened then exact-"
                         "reranked)")
    ap.add_argument("--screen-alpha", type=float, default=2.0,
                    help="alsh mode: keep k*alpha proxy-screen survivors "
                         "for exact rerank (quantized storage only)")
    ap.add_argument("--stats", action="store_true",
                    help="alsh mode: print storage-tier accounting "
                         "(table_bytes, rows screened/reranked, bytes "
                         "gathered) per batch")
    ap.add_argument("--early-exit", action="store_true",
                    help="alsh mode: adaptive probing — stream probe "
                         "windows in trace-static groups and stop per "
                         "query at the confidence bound (f32 tables only; "
                         "folds off under an active quantized screen)")
    ap.add_argument("--exit-group", type=int, default=8,
                    help="alsh mode: probe windows per streamed group "
                         "(with --early-exit)")
    ap.add_argument("--exit-slack", type=float, default=0.1,
                    help="alsh mode: acceptable miss probability for the "
                         "confidence stop; 0 disables it (geometric-only, "
                         "bit-identical results)")
    ap.add_argument("--multiprobe", action="store_true",
                    help="serve with QuerySpec(mode='multiprobe')")
    ap.add_argument("--probes", type=int, default=8,
                    help="multiprobe buckets per table")
    ap.add_argument("--recall-target", type=float, default=None,
                    help="alsh mode: quality-first serving — plan geometry "
                         "and policy for this recall@topk (overrides "
                         "--K/--L/--multiprobe/--probes)")
    ap.add_argument("--latency-budget-ms", type=float, default=None,
                    help="alsh mode: optional per-query latency budget for "
                         "the planner's cost model (with --recall-target)")
    ap.add_argument("--ingest", type=int, default=512,
                    help="stream mode: rows inserted per tick")
    ap.add_argument("--retire", type=int, default=128,
                    help="stream mode: rows tombstoned per tick")
    ap.add_argument("--delta-capacity", type=int, default=8192,
                    help="stream mode: delta-segment slots before a compact "
                         "(the chunked delta match keeps query memory flat "
                         "in this, so 16k+ capacities are fine)")
    ap.add_argument("--compact-threshold", type=float, default=0.75,
                    help="stream mode: fill fraction that triggers compact")
    ap.add_argument("--slo-p99-ms", type=float, default=50.0,
                    help="broker mode: target p99 latency; breaches walk "
                         "down the degradation ladder")
    ap.add_argument("--arrival", choices=["poisson", "bursty"],
                    default="poisson", help="broker mode: arrival trace shape")
    ap.add_argument("--rate", type=float, default=500.0,
                    help="broker mode: mean arrival rate (req/s)")
    ap.add_argument("--requests", type=int, default=1000,
                    help="broker mode: trace length")
    ap.add_argument("--shards", type=int, default=1,
                    help="broker mode: >1 serves a host-side ShardSet")
    ap.add_argument("--kill-shard", type=int, default=None,
                    help="broker mode: chaos — shard to kill mid-stream "
                         "(needs --shards > 1)")
    ap.add_argument("--kill-at", type=float, default=0.5,
                    help="broker mode: virtual time (s) of the shard kill")
    ap.add_argument("--max-batch", type=int, default=64,
                    help="broker mode: largest dynamic-batch bucket")
    ap.add_argument("--max-queue", type=int, default=256,
                    help="broker mode: admission queue bound (overflow sheds)")
    args = ap.parse_args()
    use_compile_cache()
    if args.mode == "alsh":
        serve_alsh(args)
    elif args.mode == "stream":
        serve_alsh_stream(args)
    elif args.mode == "broker":
        serve_broker(args)
    else:
        serve_lm(args)


if __name__ == "__main__":
    main()
