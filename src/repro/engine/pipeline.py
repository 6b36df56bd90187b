"""The shared execution pipeline: key enumeration → sources → one tail.

Stage contract (DESIGN.md §8):

  1. ``probe_keys`` — (b, L, P) int32 probing sequence. P = 1 is the
     paper's single-probe lookup; P > 1 is the Lv et al. query-directed
     sequence. This is the ONLY stage where probe and multiprobe differ.
  2. ``sources_for`` — the :mod:`repro.engine.sources` composition of the
     index view: sealed table windows, plus the delta key match when a
     delta segment is present. Tombstone masking happens inside the
     sources (before merge), so a deleted row can never reach a result.
  3. ``execute`` — merge the fixed-shape blocks, dedupe by sort (unique
     ids packed first; the unique count is the paper's sublinearity
     metric), and hand the ids to the fused gather/rerank/top-k kernel,
     which gathers straight from BOTH segment tables (scalar-prefetch DMA
     on TPU, chunked streaming on CPU) — neither a (b, P, d) candidate
     tensor nor an (n_main + cap, d) concatenated table is materialized.

``dispatch`` wires the stages for one index view; inside ``shard_map``
each shard runs ``dispatch`` over its slice (the per-shard local source)
and the distributed service merges the per-shard results hierarchically.
``query`` is the jitted entry every consumer shares — the legacy
``repro.core`` wrappers, the ``repro.api`` facade, and the planner's
calibration rungs all hit one compiled-program cache.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import transforms
from repro.core.index import (
    ALSHIndex,
    DeltaSegment,
    IndexConfig,
    QueryResult,
    _dedupe_candidates,
    _keys_for,
    delta_live_mask,
)
from repro.engine.sources import (
    CandidateSource,
    DeltaMatchSource,
    ExhaustiveSource,
    SortedTableSource,
)


def probe_keys(
    state: ALSHIndex,
    queries: jax.Array,
    weights: jax.Array,
    cfg: IndexConfig,
    mode: str = "probe",
    n_probes: int = 8,
    max_flips: int = 3,
    impl: str = "auto",
    with_ranks: bool = False,
) -> jax.Array:
    """Enumerate the (b, L, P) probing sequence of a query batch.

    mode="probe": each query's own bucket key per table (P = 1).
    mode="multiprobe": the query-directed perturbation sequence (P <=
    n_probes, clamped by the family's reachable-subset count).

    ``with_ranks=True`` returns ``(keys, ranks)`` with ranks the (b, L, P)
    int32 per-window probe-quality rank (P-axis position — the family emits
    keys most-likely first; rank 0 is always the query's own bucket). The
    streamed early-exit tail consumes this contract to visit windows
    quality-major instead of table-major.
    """
    with obs.scope(obs.PROJECT):
        if mode == "multiprobe":
            from repro.core.multiprobe import multiprobe_keys_for

            return multiprobe_keys_for(
                state, queries, weights, cfg, n_probes, max_flips, with_ranks=with_ranks
            )
        qlevels = transforms.discretize(queries, cfg.space)
        keys = _keys_for(qlevels, weights, state.tables, cfg, state.mixers, impl=impl)
    keys = keys[:, :, None]  # (b, L, 1)
    if not with_ranks:
        return keys
    return keys, jnp.zeros(keys.shape, jnp.int32)  # single probe = rank 0


def sources_for(
    state: ALSHIndex,
    delta: DeltaSegment | None,
    tombstones: jax.Array | None,
    cfg: IndexConfig,
    keys: jax.Array,
) -> list[CandidateSource]:
    """The candidate-source composition of one index view (a single host,
    or one shard's slice inside ``shard_map``): the sealed sorted-table
    window probe, plus the delta key match when a delta segment is
    present. One key enumeration feeds every source."""
    n_main = state.n
    cap = delta.capacity if delta is not None else 0
    n_tot = n_main + cap
    segmented = tombstones is not None or delta is not None
    if segmented and tombstones is None:
        tombstones = jnp.zeros((n_tot,), bool)
    srcs: list[CandidateSource] = [
        SortedTableSource(
            state,
            cfg,
            keys,
            tombstones=tombstones if segmented else None,
            sentinel=n_tot,
        )
    ]
    if cap:
        live = delta_live_mask(delta, tombstones, n_main)
        srcs.append(DeltaMatchSource(delta, keys, live, n_main, n_tot))
    return srcs


def execute(
    sources: list[CandidateSource],
    main_data: jax.Array,
    delta_data: jax.Array | None,
    queries: jax.Array,
    weights: jax.Array,
    k: int,
    n_valid: int,
    scales: jax.Array | None = None,
    screen_alpha: float = 0.0,
) -> QueryResult:
    """The shared tail: merge source blocks → dedupe → [quantized screen →]
    fused gather/rerank/top-k over the (optionally two-segment) row tables.

    ``n_valid`` is the total addressable row count (main + delta
    capacity); any id >= n_valid in a block is padding. A single
    ``pre_deduped`` source skips the dedupe sort (its block is already
    ascending-unique) and counts valid entries directly.

    With quantized storage (``main_data`` non-f32) and ``screen_alpha`` > 0
    a screening stage runs between dedupe and the exact rerank: the SAME
    fused kernel ranks every candidate by the compressed-domain proxy
    distance (``quant.proxy_query`` — no decode, the gather moves encoded
    bytes) and only the top ``ceil(k·α)`` survivors reach the exact f32
    rerank. ``screen_alpha`` must be trace-static (it sets the survivor
    shape). α = 0, f32 storage, or a survivor set covering every slot all
    statically disable the stage — the tail is then exactly the pre-screen
    program.
    """
    from repro import quant
    from repro.kernels import ops

    with obs.scope(obs.WINDOW):
        blocks = [s.emit(queries, weights) for s in sources]
        cand = blocks[0] if len(blocks) == 1 else jnp.concatenate(blocks, axis=1)
    with obs.scope(obs.DEDUPE):
        if len(sources) == 1 and sources[0].pre_deduped:
            n_candidates = jnp.sum(cand < n_valid, axis=1).astype(jnp.int32)
        else:
            cand, n_candidates = _dedupe_candidates(cand, n_valid)
    keep = quant.screen_keep(k, screen_alpha, cand.shape[1])  # static int
    if keep:
        with obs.scope(obs.SCREEN):
            qp, wp = quant.proxy_query(queries, weights, main_data.dtype, scales)
            _, surv = ops.gather_rerank_topk(
                main_data, cand, qp, wp, keep, delta=delta_data
            )
            # survivors come back -1-padded; remap to the candidate sentinel
            # the rerank expects (so invalid slots stay invalid, never row 0)
            cand = jnp.where(surv >= 0, surv, n_valid).astype(jnp.int32)
    with obs.scope(obs.RERANK):
        dists, ids = ops.gather_rerank_topk(
            main_data, cand, queries, weights, k, delta=delta_data, scales=scales
        )
    return QueryResult(dists=dists, ids=ids, n_candidates=n_candidates)


def execute_streamed(
    state: ALSHIndex,
    delta: DeltaSegment | None,
    tombstones: jax.Array | None,
    queries: jax.Array,
    weights: jax.Array,
    cfg: IndexConfig,
    keys: jax.Array,
    k: int,
    exit_group: int = 8,
    exit_slack: float = 0.0,
) -> QueryResult:
    """The adaptive-probing tail: stream the (b, L, P) window lattice in
    trace-static ``exit_group``-sized groups (quality-major order) through a
    ``lax.while_loop`` that carries the running top-k heap and a per-query
    live mask, stopping each query as soon as the geometric bound or the
    Eq 25/27 confidence estimate (at ``exit_slack`` miss budget) says the
    remaining windows cannot change its answer. Stopped queries ride
    all-sentinel blocks, so shapes — and the compiled program — are
    identical across batch compositions and delta fill levels. See
    :mod:`repro.engine.stream` for the algorithm and the bit-identity
    argument; results additionally report ``tables_probed``/``stop_reason``.
    """
    from repro.engine import stream

    with obs.scope(obs.STREAM):
        return stream.stream_topk(
            state,
            delta,
            tombstones,
            queries,
            weights,
            cfg,
            keys,
            k,
            scales=state.scales,
            exit_group=exit_group,
            exit_slack=exit_slack,
        )


def dispatch(
    state: ALSHIndex,
    delta: DeltaSegment | None,
    tombstones: jax.Array | None,
    queries: jax.Array,
    weights: jax.Array,
    cfg: IndexConfig | None,
    k: int = 1,
    mode: str = "probe",
    n_probes: int = 8,
    max_flips: int = 3,
    impl: str = "auto",
    screen_alpha: float = 0.0,
    early_exit: bool = False,
    exit_group: int = 8,
    exit_slack: float = 0.0,
) -> QueryResult:
    """One query dispatch for every index view — the single-host facade,
    the legacy ``repro.core`` entry points, and each shard's body inside
    ``shard_map`` all run THIS function, so mode/segment/tombstone
    semantics cannot drift between deployments.

    ``delta``/``tombstones`` are None for an immutable (sealed-only) view;
    ``cfg`` may be None only for mode="exact" (no hashing happens).
    ``screen_alpha`` > 0 enables the quantized proxy screen of ``execute``
    (meaningful only for non-f32 storage; the jitted ``query`` wrapper
    normalizes it away everywhere else). ``early_exit=True`` routes the
    probe/multiprobe key lattice through :func:`execute_streamed` instead of
    the monolithic tail — the ``query`` wrapper folds it off whenever
    streaming cannot apply (exact mode, an active quantized screen, or a
    group covering the whole lattice). Trace-compatible: call under
    jit/shard_map freely, or use the jitted ``query`` wrapper from the
    host.
    """
    n_main = state.n
    cap = delta.capacity if delta is not None else 0
    segmented = tombstones is not None or delta is not None
    if mode == "exact":
        if not segmented:
            from repro import quant
            from repro.kernels import ops

            with obs.scope(obs.EXACT_SCAN):
                table = (
                    state.data
                    if state.data.dtype == jnp.float32
                    else quant.decode_table(state.data, state.scales)
                )
                dists, ids = ops.wl1_scan_topk(table, queries, weights, k)
                n_candidates = jnp.full(queries.shape[0], n_main, jnp.int32)
            return QueryResult(dists=dists, ids=ids, n_candidates=n_candidates)
        if tombstones is None:
            tombstones = jnp.zeros((n_main + cap,), bool)
        src = ExhaustiveSource(state, delta, tombstones)
        return execute(
            [src],
            state.data,
            delta.data if cap else None,
            queries,
            weights,
            k,
            n_valid=n_main + cap,
            scales=state.scales,
        )
    keys = probe_keys(
        state, queries, weights, cfg,
        mode=mode, n_probes=n_probes, max_flips=max_flips, impl=impl,
    )
    if early_exit:
        return execute_streamed(
            state, delta, tombstones, queries, weights, cfg, keys, k,
            exit_group=exit_group, exit_slack=exit_slack,
        )
    srcs = sources_for(state, delta, tombstones, cfg, keys)
    return execute(
        srcs,
        state.data,
        delta.data if cap else None,
        queries,
        weights,
        k,
        n_valid=n_main + cap,
        scales=state.scales,
        screen_alpha=screen_alpha,
    )


def normalize_static_args(
    cfg: IndexConfig | None,
    storage_dtype,
    k: int,
    mode: str,
    n_probes: int,
    max_flips: int,
    impl: str,
    screen_alpha: float,
    early_exit: bool = False,
    exit_group: int = 8,
    exit_slack: float = 0.0,
) -> tuple:
    """Canonicalize the static arguments of a query BEFORE the jit
    compile-key lookup: every static a mode does not read is forced to its
    neutral value, so two calls that would trace the same program always
    share one executable. This is THE retrace contract of the engine —
    ``query`` applies it on every call and the :mod:`repro.analysis`
    auditor enumerates the public entry-point lattice through this same
    function to check the compile-key cardinality against the declared
    budget (a new static axis that this normalization does not fold shows
    up there as a retrace-budget breach at review time, not as compile
    stalls in production).

    Early-exit folds: streaming never applies to exact scans (the scan
    already visits every row once) or under an active quantized screen
    (the proxy screen is a global candidate-set stage — DESIGN.md §13), and
    a group covering the whole L·P window lattice IS the monolithic tail,
    so all three cases fold to ``early_exit=False``; whenever early exit is
    off, ``exit_group``/``exit_slack`` are forced to 0 so the knobs cannot
    mint compile keys for a program that never reads them.

    Returns the normalized ``(cfg, k, mode, n_probes, max_flips, impl,
    screen_alpha, early_exit, exit_group, exit_slack)`` tuple.
    """
    if mode != "multiprobe":
        n_probes, max_flips = 1, 0
    if mode != "probe":
        impl = "auto"
    if mode == "exact":
        cfg = None
    if mode == "exact" or jnp.dtype(storage_dtype) == jnp.dtype(jnp.float32):
        screen_alpha = 0.0
    if early_exit:
        if mode == "exact" or screen_alpha > 0.0:
            early_exit = False
        else:
            from repro.core.families import n_flip_subsets

            p_eff = (
                1
                if mode == "probe"
                else min(n_probes, n_flip_subsets(cfg.K, max_flips))
            )
            if exit_group >= cfg.L * p_eff:
                early_exit = False  # one group == the monolithic tail
    if not early_exit:
        exit_group, exit_slack = 0, 0.0
    return (
        cfg,
        k,
        mode,
        n_probes,
        max_flips,
        impl,
        float(screen_alpha),
        bool(early_exit),
        int(exit_group),
        float(exit_slack),
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "cfg", "k", "mode", "n_probes", "max_flips", "impl", "screen_alpha",
        "early_exit", "exit_group", "exit_slack",
    ),
)
def _query_jit(
    state: ALSHIndex,
    delta: DeltaSegment | None,
    tombstones: jax.Array | None,
    queries: jax.Array,
    weights: jax.Array,
    cfg: IndexConfig | None,
    k: int,
    mode: str,
    n_probes: int,
    max_flips: int,
    impl: str,
    screen_alpha: float,
    early_exit: bool,
    exit_group: int,
    exit_slack: float,
) -> QueryResult:
    return dispatch(
        state, delta, tombstones, queries, weights, cfg,
        k=k, mode=mode, n_probes=n_probes, max_flips=max_flips, impl=impl,
        screen_alpha=screen_alpha, early_exit=early_exit, exit_group=exit_group,
        exit_slack=exit_slack,
    )


def query(
    state: ALSHIndex,
    delta: DeltaSegment | None,
    tombstones: jax.Array | None,
    queries: jax.Array,
    weights: jax.Array,
    cfg: IndexConfig | None,
    k: int = 1,
    mode: str = "probe",
    n_probes: int = 8,
    max_flips: int = 3,
    impl: str = "auto",
    screen_alpha: float = 0.0,
    early_exit: bool = False,
    exit_group: int = 8,
    exit_slack: float = 0.0,
) -> QueryResult:
    """Jitted ``dispatch`` — the one compiled entry point every consumer
    shares. Static args a mode does not read are normalized by
    :func:`normalize_static_args` before the compile-key lookup (probe
    ignores n_probes/max_flips, multiprobe and exact ignore impl, exact
    ignores cfg entirely, ``screen_alpha`` is forced to 0 whenever
    screening cannot apply: f32-stored tables and exact scans, and the
    early-exit knobs fold off wherever streaming cannot apply), so two
    calls that trace the same program always reuse one executable —
    facade or legacy shim alike, whatever defaults their spec happened to
    carry."""
    (
        cfg, k, mode, n_probes, max_flips, impl, screen_alpha,
        early_exit, exit_group, exit_slack,
    ) = normalize_static_args(
        cfg, state.data.dtype, k, mode, n_probes, max_flips, impl, screen_alpha,
        early_exit, exit_group, exit_slack,
    )
    return _query_jit(
        state, delta, tombstones, queries, weights, cfg,
        k=k, mode=mode, n_probes=n_probes, max_flips=max_flips, impl=impl,
        screen_alpha=screen_alpha, early_exit=early_exit, exit_group=exit_group,
        exit_slack=exit_slack,
    )
