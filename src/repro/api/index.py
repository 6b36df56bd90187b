"""The ``repro.api`` Index facade — one object, full lifecycle.

The engine underneath (``repro.core``) is a pair: an ``ALSHIndex`` pytree of
arrays and an ``IndexConfig`` of static geometry, threaded separately
through every call. This module fuses them into a single config-carrying
:class:`Index` so consumers (serving, retrieval, examples, benchmarks)
never re-wire build/query/persist plumbing by hand:

    index = Index.build(key, data, cfg)
    res   = index.query(q, w, QuerySpec(k=10))                  # single-probe
    res   = index.query(q, w, QuerySpec(k=10, mode="multiprobe"))
    res   = index.query(q, w, QuerySpec(k=10, mode="exact"))    # oracle scan
    index.save(dir);  index = Index.load(dir)                   # dir alone
    sharded = index.shard(mesh); sharded.query(q, w, spec)      # cluster

Indexes built with ``UpdateSpec(delta_capacity=C)`` are MUTABLE — they
survive data churn without the O(H·d·n + L·n log n) rebuild:

    index = Index.build(key, data, cfg, update=UpdateSpec(delta_capacity=4096))
    index, ids = index.insert(new_rows)     # functional; ids are stable
    index = index.delete(ids[:16])          # tombstones, never re-sorts
    res = index.query(q, w, spec)           # two-segment probe, same contract
    if index.needs_compact: index = index.compact()   # the only sort

Memory model: the sealed main segment never changes; inserts land in a
fixed-capacity delta segment hashed with the SAME tables (so one set of
query keys is valid everywhere); deletes flip tombstone bits. Every shape
is static — insert/delete/query reuse one compiled program across the
index's whole life at a given capacity.

``Index`` is a registered pytree whose *config and update policy ride in
the static treedef*: it crosses jit/vmap/shard_map boundaries like any
array bundle, and two indexes with different geometry can never be confused
for one compiled program. Query execution dispatches on
:class:`~repro.api.spec.QuerySpec` fields to the same jit'd engine entry
points the legacy shims call, so facade results are bit-identical to
``query_index``/``query_multiprobe`` (and a mutable index's results are
bit-identical to a fresh build over its surviving rows — see
tests/test_lifecycle.py).
"""

from __future__ import annotations

import dataclasses
import itertools
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro import engine, obs
from repro.analysis.retrace_guard import engine_cache_size
from repro.api.spec import PlannedSpec, QualitySpec, QuerySpec, UpdateSpec
from repro.core.families import n_flip_subsets
from repro.core.index import (
    ALSHIndex,
    DeltaSegment,
    IndexConfig,
    QueryResult,
    build_index,
    delta_insert,
    tombstone_ids,
)

_query_seq = itertools.count(1)  # Index.query calls in this process: the wl1.query span's seq


def _as_key_data(key: jax.Array) -> jax.Array:
    """Normalize typed PRNG keys to raw uint32 key data (persistable)."""
    if jax.dtypes.issubdtype(key.dtype, jax.dtypes.prng_key):
        return jax.random.key_data(key)
    return key


def validate_query_args(d: int, queries: jax.Array, weights: jax.Array) -> None:
    """Shape/batch/value validation shared by BOTH query facades
    (``Index.query`` and ``ShardedIndex.query``): malformed ``(queries,
    weights)`` raise a ValueError naming the offending argument instead of
    surfacing as a trace error deep inside jit/shard_map, and NON-FINITE
    rows (NaN/Inf) raise a ValueError naming the offending row indices
    instead of silently poisoning every distance in the rerank tail (a NaN
    query compares false against every candidate, so the top-k would return
    sentinel garbage with no hint why). The finiteness scan is skipped for
    tracers — inside jit the caller has already validated the concrete
    arrays at the boundary."""
    for name, arr in (("queries", queries), ("weights", weights)):
        if arr.ndim != 2 or arr.shape[-1] != d:
            raise ValueError(
                f"{name} must be (b, d) with trailing dim config.d={d}; "
                f"got {name}.shape={tuple(arr.shape)}"
            )
    if tuple(queries.shape[:-1]) != tuple(weights.shape[:-1]):
        raise ValueError(
            f"queries and weights batch dims disagree: "
            f"queries.shape={tuple(queries.shape)} vs "
            f"weights.shape={tuple(weights.shape)}"
        )
    for name, arr in (("queries", queries), ("weights", weights)):
        if isinstance(arr, jax.core.Tracer):
            continue
        finite_rows = np.isfinite(np.asarray(arr)).all(axis=1)
        if not finite_rows.all():
            bad = np.nonzero(~finite_rows)[0]
            head = ", ".join(map(str, bad[:8])) + (", …" if bad.size > 8 else "")
            raise ValueError(
                f"{name} contains non-finite values (NaN/Inf) in "
                f"{bad.size} of {finite_rows.size} rows [{head}] — "
                f"non-finite {name} would silently produce NaN distances "
                f"through the rerank tail; filter or clamp them first"
            )


def _check_probe_reach(cfg: IndexConfig, spec: QuerySpec) -> None:
    """Reject multiprobe specs asking for more probes than the (K,
    max_flips) perturbation enumeration can reach — beyond that count every
    extra probe re-probes a duplicate bucket and buys nothing. Applied by
    BOTH the single-host and the sharded query facade."""
    if spec.mode != "multiprobe":
        return
    cap = n_flip_subsets(cfg.K, spec.max_flips)
    if spec.n_probes > cap:
        raise ValueError(
            f"QuerySpec.n_probes={spec.n_probes} exceeds the "
            f"{cap} distinct probe keys reachable with K={cfg.K} "
            f"hash bits and max_flips={spec.max_flips} — extra probes "
            f"would silently hit duplicate buckets; lower n_probes or "
            f"raise max_flips"
        )


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Index:
    """A built ALSH index that owns its static configuration and lifecycle.

    Attributes:
      state: the sealed main segment (tables, sorted keys, permutations,
        data) — never mutated after build; only ``compact()`` replaces it.
      build_key: the PRNG key the tables were drawn from — persisted so a
        restored index can be re-sharded (shard-local rebuilds re-derive
        identical tables from it, including the delta-row hashes).
      config: static geometry; lives in the pytree treedef, not the leaves.
      update: static mutability policy (delta capacity); also in the treedef.
      delta: fixed-capacity unsealed segment holding post-build inserts
        (empty, capacity 0, for immutable indexes).
      tombstones: (n_main + capacity,) bool — True marks a deleted row in
        either segment.

    Row ids are stable across mutation: main rows keep their build ids
    ``[0, n_main)``; the i-th inserted row gets id ``n_main + i`` (also
    under sharding). Only ``compact()`` renumbers — ``live_ids()`` gives
    the old-id-per-new-id mapping of the compaction that is about to
    happen (or just happened, from the pre-compact index).
    """

    state: ALSHIndex
    build_key: jax.Array
    config: IndexConfig
    update: UpdateSpec = UpdateSpec()
    delta: DeltaSegment | None = None
    tombstones: jax.Array | None = None
    # memoized QualitySpec -> PlannedSpec resolutions; static metadata (rides
    # the treedef, persists in the v3 manifest, copies through shard())
    plans: dict = dataclasses.field(default_factory=dict, compare=False)
    # memoized QualitySpec -> degradation-ladder resolutions (tuple of
    # PlannedSpec, richest first). Host-side serving metadata only: it does
    # NOT ride the treedef or the manifest — a jit/shard_map crossing or a
    # save/load drops it, and plan_ladder() re-derives it deterministically
    ladders: dict = dataclasses.field(default_factory=dict, compare=False)
    # wall seconds each QualitySpec resolution cost on THIS process (audit
    # metadata for explain/benchmarks). Host-side only: wall clocks must
    # never ride the treedef (they would fracture the jit cache) or the
    # manifest (plans are bit-reproducible, their timings are not)
    plan_times: dict = dataclasses.field(default_factory=dict, compare=False)
    # provenance stamp of the offline tuning table that backed a
    # prior-based plan (repro.tuner TuningTable.provenance()). None until a
    # table-backed planner resolves a plan here; persisted in the v4
    # manifest so shipped indexes carry their tuning lineage
    tuning: dict | None = dataclasses.field(default=None, compare=False)

    def __post_init__(self):
        # Synthesize empty mutation state when constructed without it (the
        # common case for immutable indexes and shard-local facades).
        if self.delta is None:
            self.delta = DeltaSegment.empty(
                self.config, self.update.delta_capacity, dtype=self.state.data.dtype
            )
        if self.tombstones is None:
            self.tombstones = jnp.zeros(
                (self.state.data.shape[0] + self.delta.capacity,), bool
            )

    # -- pytree protocol (config + update policy are static aux data; the
    # plan memo rides along as a hashable tuple so QualitySpec queries keep
    # resolving AFTER a jit/shard_map crossing) ------------------------------
    def tree_flatten(self):
        return (
            (self.state, self.build_key, self.delta, self.tombstones),
            (self.config, self.update, tuple(self.plans.items())),
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        state, build_key, delta, tombstones = children
        config, update, plans = aux
        return cls(
            state=state,
            build_key=build_key,
            config=config,
            update=update,
            delta=delta,
            tombstones=tombstones,
            plans=dict(plans),
        )

    # -- construction -------------------------------------------------------
    @classmethod
    def build(
        cls,
        key: jax.Array,
        data: jax.Array,
        config: "IndexConfig | QualitySpec",
        impl: str = "auto",
        update: UpdateSpec = UpdateSpec(),
        family: str = "auto",
        M: int = 32,
        planner=None,
    ) -> "Index":
        """Hash every point and sort each table — Theorem 1 preprocessing.

        ``config`` is either an explicit :class:`IndexConfig` (the classic
        knob path, unchanged) or a :class:`QualitySpec` — then the geometry
        (family, K, L, W, max_candidates, space) is DERIVED from theory
        plus a data sample by :class:`repro.api.planner.Planner`, the
        execution plan is calibrated and memoized immediately, and when
        even the best calibrated plan misses ``recall_target`` the table
        count is escalated (L doubled, bounded by the planner's caps) and
        the build retried — theory proposes, measurement disposes. All of
        it is deterministic given (data, quality.seed);
        ``family``/``M``/``planner`` tune the derivation and are ignored on
        the explicit path. ``update=UpdateSpec(delta_capacity=C)`` reserves
        C delta slots and makes the index mutable (``insert``/``delete``/
        ``compact``).
        """
        key = _as_key_data(key)
        if not isinstance(config, QualitySpec):
            return cls(
                state=build_index(key, data, config, impl=impl),
                build_key=key,
                config=config,
                update=update,
            )

        import time as _time
        import warnings

        from repro.api.planner import Planner

        quality = config
        planner = planner or Planner()
        cfg = planner.plan_config(data, quality, family=family, M=M)
        last_round = 2  # escalation attempts: L x2 each, then accept best
        for attempt in range(last_round + 1):
            index = cls(
                state=build_index(key, data, cfg, impl=impl),
                build_key=key,
                config=cfg,
                update=update,
            )
            at_cap = cfg.L >= planner.max_L
            with warnings.catch_warnings(record=True) as caught:
                warnings.simplefilter("always")
                t0 = _time.perf_counter()
                planned = planner.plan_query(index, quality)
                index._record_plan(
                    quality, planned, planner, _time.perf_counter() - t0
                )
            if planned.predicted_recall >= quality.recall_target - 1e-9 or (
                attempt == last_round or at_cap
            ):
                # this attempt's plan is the one the caller gets — its
                # warnings (budget exceeded, target unreachable) are real
                for w in caught:
                    warnings.warn(w.message, w.category, stacklevel=2)
                return index
            # recall miss with escalation headroom: the rebuild supersedes
            # this attempt's warnings, so drop them
            cfg = dataclasses.replace(cfg, L=min(2 * cfg.L, planner.max_L))
        return index

    @property
    def n(self) -> int:
        """Main-segment (sealed) rows."""
        return self.state.n

    @property
    def d(self) -> int:
        return self.config.d

    @property
    def mutable(self) -> bool:
        return self.update.mutable

    @property
    def capacity(self) -> int:
        """Total addressable rows: main + delta slots."""
        return self.state.n + self.delta.capacity

    @property
    def table_bytes(self) -> int:
        """Resident bytes of the row tables (main payload + delta payload +
        decode scales) — the memory the storage codec is compressing. Hash
        tables/permutations are excluded: they are storage-invariant."""
        total = self.state.data.nbytes + self.delta.data.nbytes
        if self.state.scales is not None:
            total += self.state.scales.nbytes
        return int(total)

    @property
    def delta_fill(self) -> int:
        """Delta slots used (device sync — don't poll inside jit)."""
        return int(self.delta.fill)

    @property
    def n_live(self) -> int:
        """Surviving rows: filled, not tombstoned (device sync)."""
        return int(self.live_ids().size)

    @property
    def needs_compact(self) -> bool:
        """Advisory: delta fill crossed ``update.compact_threshold``."""
        cap = self.delta.capacity
        if cap == 0:
            return False
        return self.delta_fill >= self.update.compact_threshold * cap

    # -- querying -----------------------------------------------------------
    def _validate_query_args(self, queries: jax.Array, weights: jax.Array) -> None:
        validate_query_args(self.config.d, queries, weights)

    def resolve(self, spec) -> tuple[QuerySpec, IndexConfig, "PlannedSpec | None"]:
        """Normalize any spec kind to (mechanism QuerySpec, effective
        config, resolved PlannedSpec-or-None). QualitySpecs go through the
        memoized planner; PlannedSpecs apply their candidate window to the
        config. The same resolution backs ``query`` and ``explain`` — which
        is what makes ``query(q, w, quality)`` bit-identical to
        ``query(q, w, index.plan(quality))``."""
        if isinstance(spec, QualitySpec):
            spec = self.plan(spec)
        if isinstance(spec, PlannedSpec):
            return spec.to_query_spec(), spec.effective_config(self.config), spec
        if not isinstance(spec, QuerySpec):
            raise TypeError(
                f"spec must be a QuerySpec, QualitySpec, or PlannedSpec; "
                f"got {type(spec).__name__}"
            )
        return spec, self.config, None

    def plan(self, quality: QualitySpec, planner=None) -> PlannedSpec:
        """Resolve ``quality`` to a concrete :class:`PlannedSpec`, memoized
        on this index (and on every index derived from it by insert/delete —
        they share the memo; ``compact``/fresh builds re-plan).

        Planning is deterministic given (index, ``quality.seed``): a
        calibration sample is drawn from the build key, the plan ladder is
        executed on it, and the cheapest plan meeting
        ``quality.recall_target`` wins. The resolved plan rides the pytree
        treedef, persists through ``save``/``load`` (v3 manifest), and
        copies into ``shard()``-ed service handles.
        """
        planned = self.plans.get(quality)
        if planned is None:
            import time

            if planner is None:
                from repro.api.planner import Planner

                planner = Planner()
            t0 = time.perf_counter()
            planned = planner.plan_query(self, quality)
            self._record_plan(quality, planned, planner, time.perf_counter() - t0)
        return planned

    def _record_plan(self, quality, planned, planner, elapsed: float) -> None:
        """Memoize a resolution + its audit metadata: wall time (host-side,
        surfaces as ``QueryReport.plan_build_s``) and — for prior-based
        plans — the provenance stamp of the tuning table that shipped it."""
        self.plans[quality] = planned
        self.plan_times[quality] = elapsed
        if planned.provenance == "prior" and getattr(planner, "table", None) is not None:
            self.tuning = planner.table.provenance()

    def plan_ladder(self, quality: QualitySpec, planner=None) -> tuple:
        """Resolve ``quality`` to the full DEGRADATION ladder (memoized):
        a tuple of :class:`PlannedSpec` rungs, rung 0 being exactly what
        ``plan(quality)`` returns (the contract-meeting operating point) and
        every later rung strictly cheaper — fewer probes, then single-probe,
        then shrinking candidate windows. Each rung carries its calibrated
        ``predicted_recall``/``predicted_success``, which is what lets a
        serving broker under SLO pressure step down the ladder and LABEL
        each degraded response with the recall it traded away (see
        :mod:`repro.serving`). One calibration pass scores every rung, and
        the rung-0 resolution seeds the ``plans`` memo, so
        ``plan_ladder`` + ``query(quality)`` costs one calibration total."""
        ladder = self.ladders.get(quality)
        if ladder is None:
            if planner is None:
                from repro.api.planner import Planner

                planner = Planner()
            ladder = planner.plan_ladder(self, quality)
            self.ladders[quality] = ladder
            self.plans.setdefault(quality, ladder[0])
        return ladder

    def _screen_keep(self, qspec: QuerySpec, cfg: IndexConfig) -> int:
        """Survivors of the quantized screen a query under ``qspec`` runs,
        as the engine sizes it; 0 where no screen runs (f32 storage, an
        exact scan, α = 0, or survivors covering every candidate slot)."""
        from repro import quant

        if qspec.mode == "exact" or self.state.data.dtype == jnp.float32:
            return 0
        p_slots = qspec.n_probes if qspec.mode == "multiprobe" else 1
        n_slots = cfg.L * p_slots * cfg.max_candidates + (
            self.delta.capacity if self.mutable else 0
        )
        return quant.screen_keep(qspec.k, qspec.screen_alpha, n_slots)

    def query(self, queries: jax.Array, weights: jax.Array, spec=QuerySpec()) -> QueryResult:
        """Batched k-NN under d_w^l1; ``spec`` picks the execution strategy.

        Args:
          queries: (b, d) float query points.
          weights: (b, d) per-query weight vectors (the paper's w — may be
            negative).
          spec: policy — a mechanism :class:`QuerySpec` (exact | probe |
            multiprobe), a resolved :class:`PlannedSpec`, or a declarative
            :class:`QualitySpec` (planned on first use, memoized after).

        Every mode runs the one :mod:`repro.engine` pipeline — a mutable
        index adds the delta key-match source and the tombstone mask to the
        sealed-table window source; an immutable index probes the sealed
        source alone (bit-identical to the legacy shims, which wrap the
        same engine). Invalid result slots are ``ids == -1`` /
        ``dists == +inf`` in every mode.
        """
        with obs.span(obs.QUERY, seq=next(_query_seq)) as outer:
            with obs.span(obs.VALIDATE):
                self._validate_query_args(queries, weights)
            with obs.span(obs.PLAN):  # the probe reach needs the resolved config
                qspec, cfg, _ = self.resolve(spec)
                _check_probe_reach(cfg, qspec)
            outer.set_metadata(mode=qspec.mode, b=queries.shape[0], k=qspec.k,
                               storage=self.config.storage,
                               screen_keep=self._screen_keep(qspec, cfg))
            with obs.span(obs.DISPATCH) as dispatch:
                before = engine_cache_size()
                result = engine.query(
                    self.state,
                    self.delta if self.mutable else None,
                    self.tombstones if self.mutable else None,
                    queries,
                    weights,
                    cfg,
                    k=qspec.k,
                    mode=qspec.mode,
                    n_probes=qspec.n_probes,
                    max_flips=qspec.max_flips,
                    impl=qspec.impl,
                    screen_alpha=qspec.screen_alpha,
                    early_exit=qspec.early_exit,
                    exit_group=qspec.exit_group,
                    exit_slack=qspec.exit_slack,
                )
                dispatch.set_metadata(compiled=engine_cache_size() - before)
        return result

    def explain(self, queries: jax.Array, weights: jax.Array, spec=QuerySpec()):
        """Run ``query`` and return a :class:`~repro.api.planner.QueryReport`
        wrapping the result with per-query diagnostics: the resolved
        parameters, the Thm 1 success probability predicted from Eq 25/27
        at each query's own weight vector, candidate counts, and
        truncation/sentinel flags. The answer arrays are bit-identical to a
        plain ``query`` with the same spec — explain only adds the probe
        bookkeeping (an extra pass over the sorted keys, host-side).
        """
        from repro.api.planner import QueryReport
        from repro.core import theory
        from repro.core.index import query_keys_for, table_window_sizes

        self._validate_query_args(queries, weights)
        quality = spec if isinstance(spec, QualitySpec) else None
        qspec, cfg, planned = self.resolve(spec)
        res = self.query(queries, weights, planned if planned is not None else qspec)

        b = queries.shape[0]
        if qspec.mode == "exact":
            truncated = np.zeros((b,), np.int32)
        else:
            if qspec.mode == "multiprobe":
                from repro.core.multiprobe import multiprobe_keys_for

                keys = multiprobe_keys_for(
                    self.state, queries, weights, cfg,
                    qspec.n_probes, qspec.max_flips,
                )  # (b, L, P)
            else:
                keys = query_keys_for(self.state, queries, weights, cfg)  # (b, L)
            wins = table_window_sizes(self.state.sorted_keys, keys)
            over = wins > cfg.max_candidates
            truncated = np.asarray(
                jnp.sum(over.reshape(b, -1), axis=1), dtype=np.int32
            )

        # Thm 1 success bound per query at its OWN w and observed top-1 r
        # (result distances are raw-unit; Eq 25/27 want lattice units — x t)
        top1 = res.dists[:, 0]
        valid1 = jnp.isfinite(top1)
        r1 = jnp.where(valid1, top1, 0.0) * cfg.space.t
        if cfg.family == "l2":
            p1 = theory.collision_prob_l2(r1, cfg.M, cfg.d, weights, cfg.W)
        else:
            p1 = theory.collision_prob_theta(r1, cfg.M, cfg.d, weights)
        p1 = jnp.clip(p1, 1e-12, 1.0 - 1e-12)
        success = jnp.where(valid1, 1.0 - (1.0 - p1**cfg.K) ** cfg.L, 0.0)

        # storage-tier accounting: what the fused tail actually moved.
        # Screening gathers every unique candidate once at the ENCODED row
        # width; the exact rerank then re-gathers only the survivors (all
        # candidates when the screen is statically off).
        n_cand = np.asarray(res.n_candidates, dtype=np.int64)
        row_bytes = self.state.data.dtype.itemsize * cfg.d
        keep = self._screen_keep(qspec, cfg)
        rows_screened = n_cand if keep else np.zeros_like(n_cand)
        rows_reranked = np.minimum(n_cand, keep) if keep else n_cand
        bytes_gathered = (rows_screened + rows_reranked) * row_bytes

        return QueryReport(
            spec=planned if planned is not None else qspec,
            quality=quality,
            result=res,
            predicted_success=np.asarray(success),
            n_candidates=np.asarray(res.n_candidates),
            truncated_tables=truncated,
            n_invalid=np.asarray(jnp.sum(res.ids < 0, axis=1), dtype=np.int32),
            provenance=planned.provenance if planned is not None else None,
            plan_build_s=(
                self.plan_times.get(quality) if quality is not None else None
            ),
            storage=self.config.storage,
            rows_screened=rows_screened,
            rows_reranked=rows_reranked,
            bytes_gathered=bytes_gathered,
            table_bytes=self.table_bytes,
            tables_probed=(
                np.asarray(res.tables_probed, dtype=np.int32)
                if res.tables_probed is not None else None
            ),
            stop_reason=(
                np.asarray(res.stop_reason, dtype=np.int32)
                if res.stop_reason is not None else None
            ),
        )

    # -- mutation (functional: every method returns a new Index) ------------
    def _require_mutable(self, op: str) -> None:
        if not self.mutable:
            raise ValueError(
                f"Index.{op}() requires a mutable index — build with "
                f"update=UpdateSpec(delta_capacity=...) (this index was built "
                f"with delta_capacity=0)"
            )

    def insert(self, rows: jax.Array) -> tuple["Index", jax.Array]:
        """Append rows to the delta segment.

        Args:
          rows: (m, d) new data points (hashed with the index's own tables).

        Returns:
          (new index, (m,) int32 assigned ids). Ids are stable until the
          next ``compact()``; ``-1`` marks rows that did not fit (delta at
          capacity — compact and retry). jit/vmap-safe, no retrace across
          fill levels.
        """
        self._require_mutable("insert")
        if rows.ndim != 2 or rows.shape[-1] != self.config.d:
            raise ValueError(
                f"insert rows must be (m, d) with trailing dim "
                f"config.d={self.config.d}; got rows.shape={tuple(rows.shape)}"
            )
        delta, ids = delta_insert(self.state, self.delta, rows, self.config)
        return dataclasses.replace(self, delta=delta), ids

    def delete(self, ids: jax.Array) -> "Index":
        """Tombstone rows by id (either segment). Unknown ids — negative or
        not yet assigned by any insert — are ignored; deleted ids never
        appear in query results. Functional and jit-safe; space is
        reclaimed by ``compact()``."""
        self._require_mutable("delete")
        ts = tombstone_ids(
            self.tombstones, jnp.asarray(ids), self.state.n, self.delta.fill
        )
        return dataclasses.replace(self, tombstones=ts)

    def live_ids(self):
        """(n_live,) int64 numpy array: surviving row ids in compaction
        order — ``live_ids()[new_id] == old_id`` after ``compact()``."""
        tomb = np.asarray(self.tombstones)
        n_main = self.state.n
        fill = int(self.delta.fill)
        main_keep = np.nonzero(~tomb[:n_main])[0]
        delta_keep = n_main + np.nonzero(~tomb[n_main : n_main + fill])[0]
        return np.concatenate([main_keep, delta_keep])

    def compact(self) -> "Index":
        """Merge delta + surviving main rows into a fresh sealed segment.

        The ONLY lifecycle operation that sorts. Hashes are NOT recomputed:
        main-row keys are recovered by inverting each table's permutation
        and delta-row keys were computed at insert time — the merge is a
        gather + L argsorts, bit-identical to ``Index.build`` over the
        surviving rows (same ``build_key``). Returns a new index with an
        empty delta and a clear tombstone bitmap; ids are renumbered per
        ``live_ids()``. Host-side (dynamic output shape) — do not call
        under jit.
        """
        self._require_mutable("compact")
        state, cfg = self.state, self.config
        n_main = state.n
        fill = int(self.delta.fill)
        tomb = np.asarray(self.tombstones)
        main_keep = jnp.asarray(np.nonzero(~tomb[:n_main])[0], jnp.int32)
        delta_keep = jnp.asarray(
            np.nonzero(~tomb[n_main : n_main + fill])[0], jnp.int32
        )

        # recover per-table keys of main rows at their original positions by
        # inverting the sort: keys[l, perm[l, i]] = sorted_keys[l, i]
        perm = state.perm[:, :n_main]
        keys_main = jnp.zeros((cfg.L, n_main), jnp.int32)
        keys_main = keys_main.at[
            jnp.arange(cfg.L, dtype=jnp.int32)[:, None], perm
        ].set(state.sorted_keys)

        # survivors are decoded to f32 and RE-ENCODED as a fresh segment —
        # int8 scales are refit to the surviving rows (the delta rows were
        # saturating against the OLD segment's range; the new sealed segment
        # gets its own). f32 storage: decode and encode are both the
        # identity, bit-identical to concatenating the raw arrays.
        from repro import quant
        from repro.core.index import get_codec

        data = jnp.concatenate(
            [
                quant.decode_table(state.data[main_keep], state.scales),
                quant.decode_table(
                    self.delta.data[delta_keep].astype(state.data.dtype),
                    state.scales,
                ),
            ]
        )
        levels = jnp.concatenate(
            [state.levels[main_keep], self.delta.levels[delta_keep]]
        )
        keys_ln = jnp.concatenate(
            [keys_main[:, main_keep], self.delta.keys[:, delta_keep]], axis=1
        )

        # the sort — identical to build_index's tail over the survivor rows
        n_new = data.shape[0]
        perm_new = jnp.argsort(keys_ln, axis=1).astype(jnp.int32)
        sorted_keys = jnp.take_along_axis(keys_ln, perm_new, axis=1)
        pad = jnp.full((cfg.L, cfg.max_candidates), n_new, dtype=jnp.int32)
        perm_new = jnp.concatenate([perm_new, pad], axis=1)
        payload, scales = get_codec(cfg.storage).encode(data)
        new_state = ALSHIndex(
            tables=state.tables,
            mixers=state.mixers,
            sorted_keys=sorted_keys,
            perm=perm_new,
            data=payload,
            levels=levels,
            scales=scales,
        )
        return Index(
            state=new_state,
            build_key=self.build_key,
            config=cfg,
            update=self.update,
        )

    # -- persistence (self-describing) --------------------------------------
    def save(self, directory: str | os.PathLike) -> str:
        """Write a directory restorable by ``Index.load(directory)`` alone.

        The manifest records every segment (main rows, delta capacity/fill,
        tombstone count) plus the resolved query plans, so a restored
        mutable index resumes its lifecycle — and its memoized planning —
        exactly where it stopped."""
        from repro.api import persist

        return persist.save_index(
            directory,
            self.state,
            self.build_key,
            self.config,
            update=self.update,
            delta=self.delta,
            tombstones=self.tombstones,
            plans=self.plans,
            tuning=self.tuning,
        )

    @classmethod
    def load(cls, directory: str | os.PathLike) -> "Index":
        """Restore an index from a directory — config, update policy,
        segment state, and resolved query plans all travel with the data."""
        from repro.api import persist

        state, build_key, cfg, update, delta, tombstones, plans, tuning = (
            persist.load_index(directory)
        )
        return cls(
            state=state,
            build_key=build_key,
            config=cfg,
            update=update,
            delta=delta,
            tombstones=tombstones,
            plans=plans,
            tuning=tuning,
        )

    # -- distribution -------------------------------------------------------
    def shard(self, mesh, merge_hierarchical: bool = True) -> "ShardedIndex":
        """Partition the database rows over ``mesh`` for cluster serving.

        Builds each shard's local index ONCE (tables re-derived from the
        persisted ``build_key``, so they match across shards and across
        save/load). A mutable index replays its delta rows through the
        sharded insert path — the same tables re-hash them to identical
        keys, ids are preserved (``n_main + i`` for the i-th insert), and
        tombstones carry over. Each shard gets its own
        ``update.delta_capacity``-slot delta. Returns a
        :class:`ShardedIndex` with the same query/insert/delete surface.
        """
        from repro.core.distributed import build_local_indexes, make_sharded_delta

        if self.config.storage != "f32":
            raise ValueError(
                f"Index.shard() supports storage='f32' only (this index was "
                f"built with storage={self.config.storage!r}) — the mesh path "
                f"re-discretizes raw rows per shard, and per-shard re-encoding "
                f"would drift the quantization grid away from the single-host "
                f"index it must answer bit-identically to. Use the host-side "
                f"serving shard set (repro.serving.chaos.ShardSet), which "
                f"re-encodes each shard self-consistently, or build with "
                f"storage='f32' before sharding"
            )
        S = mesh.devices.size
        if self.mutable and self.update.delta_capacity % S:
            raise ValueError(
                f"UpdateSpec.delta_capacity={self.update.delta_capacity} must "
                f"be a multiple of the mesh size ({S} devices) — each shard "
                f"owns an equal slice of the delta segment"
            )
        index_sharded = build_local_indexes(
            self.build_key, self.state.data, self.config, mesh
        )
        sharded = ShardedIndex(
            index_sharded=index_sharded,
            config=self.config,
            mesh=mesh,
            merge_hierarchical=merge_hierarchical,
            update=self.update,
            build_key=self.build_key,
            plans=dict(self.plans),
        )
        if self.mutable:
            sharded.delta_sharded, sharded.tombstones_sharded = make_sharded_delta(
                self.config,
                mesh,
                self.update.delta_capacity // S,
                self.state.data.dtype,
                n_local=self.state.n // S,
            )
            fill = self.delta_fill
            if fill:
                sharded, _ = sharded.insert(self.delta.data[:fill])
            gids = np.nonzero(np.asarray(self.tombstones))[0]
            if gids.size:
                sharded = sharded.delete(jnp.asarray(gids, jnp.int32))
        return sharded


@dataclasses.dataclass
class ShardedIndex:
    """Row-sharded view of an :class:`Index` for the distributed service.

    Each device owns a disjoint row range with a complete prebuilt local
    index over it; hash tables are identical across shards, so query
    hashing is computed once and is valid everywhere. ``query()`` returns
    globally-merged results with global row ids.

    Mutable lifecycles shard too: every device owns a private
    ``update.delta_capacity / n_shards``-slot delta slice, inserts are
    routed round-robin by global id (``gid % shards`` picks the owner),
    deletes tombstone on whichever shard owns the id, and the global id
    scheme matches the single-host :class:`Index` exactly (main row i ↔
    gid i; i-th inserted row ↔ gid n_main + i) — so a sharded and a
    single-host index fed the same update stream return the SAME ids.
    """

    index_sharded: ALSHIndex  # leaf layout per core.distributed.local_index_specs
    config: IndexConfig
    mesh: object
    merge_hierarchical: bool = True
    update: UpdateSpec = UpdateSpec()
    build_key: jax.Array | None = None
    delta_sharded: DeltaSegment | None = None  # leaf layout per local_delta_specs
    tombstones_sharded: jax.Array | None = None  # (S·(n_local+cap),) shard-major
    plans: dict = dataclasses.field(default_factory=dict)  # from the source Index

    @property
    def n(self) -> int:
        return self.index_sharded.data.shape[0]

    @property
    def n_shards(self) -> int:
        return self.mesh.devices.size

    @property
    def mutable(self) -> bool:
        return self.update.mutable and self.delta_sharded is not None

    @property
    def _cap_local(self) -> int:
        """Delta slots per shard (delta_capacity is the index-wide total)."""
        return self.update.delta_capacity // self.n_shards

    @property
    def delta_fill(self) -> int:
        """Total delta slots used across shards (device sync)."""
        if self.delta_sharded is None:
            return 0
        return int(jnp.sum(self.delta_sharded.fill))

    @property
    def needs_compact(self) -> bool:
        """Advisory: ANY shard's delta slice crossed the compact threshold
        (that shard starts dropping inserts first — see ``insert``)."""
        if self.delta_sharded is None:
            return False
        fills = np.asarray(self.delta_sharded.fill)
        return bool((fills >= self.update.compact_threshold * self._cap_local).any())

    def query(self, queries: jax.Array, weights: jax.Array, spec=QuerySpec()):
        """Same facade contract as ``Index.query`` — hierarchical-merge path,
        including the same argument validation (malformed ``(queries,
        weights)`` raise the named ValueError, never a shard_map trace
        error). Each shard runs the shared :mod:`repro.engine` pipeline
        over its slice; the hierarchical top-k merge composes the results.

        QualitySpecs resolve against the plan memo the source ``Index``
        carried into ``shard()`` (calibration needs the single-host view, so
        an UNPLANNED QualitySpec is rejected here with the fix spelled out).
        """
        from repro.core.distributed import sharded_index_query

        cfg = self.config
        validate_query_args(cfg.d, queries, weights)
        if isinstance(spec, QualitySpec):
            planned = self.plans.get(spec)
            if planned is None:
                raise ValueError(
                    "ShardedIndex cannot calibrate a new QualitySpec (planning "
                    "needs the single-host index) — call index.plan(quality) "
                    "BEFORE index.shard(mesh), or pass the resolved "
                    "PlannedSpec/QuerySpec explicitly"
                )
            spec = planned
        if isinstance(spec, PlannedSpec):
            cfg = spec.effective_config(cfg)
            spec = spec.to_query_spec()
        _check_probe_reach(cfg, spec)
        return sharded_index_query(
            self.index_sharded,
            queries,
            weights,
            cfg,
            self.mesh,
            spec=spec,
            merge_hierarchical=self.merge_hierarchical,
            delta_sharded=self.delta_sharded,
            tombstones_sharded=self.tombstones_sharded,
            update=self.update,
        )

    def _require_mutable(self, op: str) -> None:
        if not self.mutable:
            raise ValueError(
                f"ShardedIndex.{op}() requires a mutable index — build the "
                f"source Index with update=UpdateSpec(delta_capacity=...) "
                f"before .shard()"
            )

    def insert(self, rows: jax.Array) -> tuple["ShardedIndex", jax.Array]:
        """Insert rows across shards, routed round-robin by global id.

        Returns (new sharded index, (m,) assigned global ids; ``-1`` where
        the owning shard's delta is full). Ids match what a single-host
        mutable Index would assign for the same stream."""
        self._require_mutable("insert")
        from repro.core.distributed import sharded_delta_insert

        delta, ids = sharded_delta_insert(
            self.index_sharded, self.delta_sharded, rows, self.config, self.mesh
        )
        return dataclasses.replace(self, delta_sharded=delta), ids

    def delete(self, ids: jax.Array) -> "ShardedIndex":
        """Tombstone global ids on their owning shards (unknown ids ignored)."""
        self._require_mutable("delete")
        from repro.core.distributed import sharded_tombstone

        ts = sharded_tombstone(
            self.tombstones_sharded,
            jnp.asarray(ids, jnp.int32).reshape(-1),
            self.delta_sharded.fill,
            self.mesh,
            n_local=self.n // self.n_shards,
            cap=self._cap_local,
        )
        return dataclasses.replace(self, tombstones_sharded=ts)

    def compact(self) -> Index:
        """Host-coordinated compaction: gather surviving rows in global-id
        order, rebuild a fresh single-host sealed :class:`Index` (same
        ``build_key`` ⇒ same tables), ready to ``.shard()`` again. Returns
        the LOCAL index — re-shard explicitly, since the survivor count
        must still divide the mesh."""
        self._require_mutable("compact")
        if self.build_key is None:
            raise ValueError(
                "ShardedIndex.compact() needs build_key — this sharded index "
                "was constructed without one (build via Index.shard())"
            )
        S = self.n_shards
        n_local = self.n // S
        cap = self._cap_local
        tomb = np.asarray(self.tombstones_sharded).reshape(S, n_local + cap)
        fills = np.asarray(self.delta_sharded.fill)

        main_data = np.asarray(self.index_sharded.data)  # global-id order already
        main_keep = np.nonzero(~tomb[:, :n_local].reshape(-1))[0]
        rows = [main_data[main_keep]]
        if cap:
            delta_data = np.asarray(self.delta_sharded.data).reshape(S, cap, -1)
            e = np.arange(S * cap)  # delta gids in insertion order
            s, t = e % S, e // S
            live = (t < fills[s]) & ~tomb[s, n_local + t]
            rows.append(delta_data[s[live], t[live]])
        data = jnp.asarray(np.concatenate(rows, axis=0))
        return Index.build(self.build_key, data, self.config, update=self.update)
