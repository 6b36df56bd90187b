"""Distributed ALSH service: row-sharded index, replicated queries,
hierarchical top-k merge — the paper's workload at cluster scale.

Sharding contract (mesh axes ("pod","data","model")):

  * database rows: disjointly partitioned over ALL devices — each device
    builds a complete local index over its n_local rows (hash tables are
    valid per-shard because the (R1,R2)-NNS guarantee is closed under
    disjoint union: the global NN lives in exactly one shard).
  * hash tables/mixers: REPLICATED — every shard derives them from the same
    broadcast build key, so query hashing is computed once and is valid
    against every shard.
  * queries: replicated (or batch-sharded for throughput serving).
  * merge: local exact top-k per shard, then a hierarchical merge — sorted
    concat + re-top-k along "model", then "data", then "pod". Two-hop
    merging moves k·devices_per_hop entries per link instead of k·devices,
    cutting cross-pod DCN bytes by the pod fan-in (see EXPERIMENTS §Perf).

Two entry points, both under shard_map with explicit collectives:

  * ``build_local_indexes`` + ``sharded_index_query`` — build the per-shard
    indexes ONCE, query many times (what ``repro.api.Index.shard`` uses).
  * ``sharded_query`` — one-shot build+query (tests/benchmarks on small CPU
    meshes, where rebuild cost is irrelevant).

Each shard's query body is :func:`repro.engine.dispatch` over the shard's
slice — the same candidate-source composition and fused rerank tail the
single-host facade runs (the shard's sorted tables + its private delta
slice ARE its local candidate sources) — so sharded results can only
differ from single-host results by the merge, which is exact.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from repro import engine
from repro.core.hash_families import PrefixTables
from repro.core.index import (
    ALSHIndex,
    DeltaSegment,
    IndexConfig,
    build_index,
    hash_rows,
)


def _local_query(idx_local, delta_local, ts_local, q, w, cfg, spec):
    """One shard's query body: the SAME engine dispatch the single-host
    facade runs, over this shard's slice (its sorted tables + its private
    delta/tombstone slice form the shard-local candidate sources)."""
    return engine.dispatch(
        idx_local, delta_local, ts_local, q, w, cfg,
        k=spec.k, mode=spec.mode, n_probes=spec.n_probes,
        max_flips=spec.max_flips, impl=spec.impl,
    )


class ShardedQueryResult(NamedTuple):
    dists: jax.Array  # (b, k) global ascending
    ids: jax.Array  # (b, k) global ids (shard_offset + local id)
    n_candidates: jax.Array  # (b,) summed over shards


def shard_row_ranges(n: int, n_shards: int) -> list[tuple[int, int]]:
    """Contiguous equal row partition [start, stop) per shard — the id
    scheme of ``_globalize_and_merge`` (shard s owns [s·n_local, (s+1)·
    n_local)) and of the serving tier's host-side shard set. Requires
    ``n % n_shards == 0`` so every shard compiles one program shape."""
    if n_shards <= 0 or n % n_shards:
        raise ValueError(
            f"n={n} database rows cannot be split into {n_shards} equal "
            f"shards — the contiguous-partition id scheme (and the one-"
            f"compiled-program-per-bucket serving contract) needs n % "
            f"n_shards == 0"
        )
    n_local = n // n_shards
    return [(s * n_local, (s + 1) * n_local) for s in range(n_shards)]


def merge_topk_host(dists: np.ndarray, ids: np.ndarray, k: int):
    """Host-side top-k merge of per-shard results — the serving-tier mirror
    of ``_globalize_and_merge``'s on-device merge (there the shards live on
    one mesh and merge with collectives; here each shard is its own host
    process and the broker merges replies).

    Args:
      dists: (S, b, k') per-shard ascending distances. Sentinel slots
        (``+inf``, incl. ENTIRE dead-shard blocks — a killed shard
        contributes only sentinels) sink to the tail, exactly like the §8
        engine merge.
      ids: (S, b, k') matching global ids (``-1`` on sentinel slots).
      k: result width.

    Returns:
      (dists (b, k), ids (b, k)) numpy arrays, ascending per row; ids are
      ``-1`` wherever fewer than k finite candidates exist across the
      surviving shards. Deterministic (stable sort), so a recovered shard
      set answers bit-identically to the pre-failure one.
    """
    dists = np.asarray(dists)
    ids = np.asarray(ids)
    S, b, kk = dists.shape
    flat_d = np.moveaxis(dists, 0, 1).reshape(b, S * kk)
    flat_i = np.moveaxis(ids, 0, 1).reshape(b, S * kk)
    # sentinel ids must not win ties against real rows at equal distance
    order = np.argsort(
        np.where(flat_i < 0, np.inf, flat_d), axis=1, kind="stable"
    )[:, :k]
    out_d = np.take_along_axis(flat_d, order, axis=1)
    out_i = np.take_along_axis(flat_i, order, axis=1)
    out_d = np.where(out_i < 0, np.inf, out_d)
    return out_d, out_i


def local_index_specs(mesh: Mesh) -> ALSHIndex:
    """Per-leaf PartitionSpecs of a row-sharded ALSHIndex pytree.

    Tables/mixers are replicated (derived from the broadcast key); the
    point-indexed leaves shard their n-sized axis over all mesh axes.
    """
    axes = tuple(mesh.axis_names)
    return ALSHIndex(
        tables=PrefixTables(folded=P(), offsets=P()),
        mixers=P(),
        sorted_keys=P(None, axes),  # (L, n_local)
        perm=P(None, axes),  # (L, n_local + C)
        data=P(axes, None),  # (n_local, d)
        levels=P(axes, None),  # (n_local, d)
        scales=None,  # f32 storage only (Index.shard gates quantized indexes)
    )


def local_delta_specs(mesh: Mesh) -> DeltaSegment:
    """Per-leaf PartitionSpecs of a shard-private DeltaSegment bundle: each
    device owns ``cap`` delta slots; ``fill`` is one counter per shard."""
    axes = tuple(mesh.axis_names)
    return DeltaSegment(
        data=P(axes, None),  # (S·cap, d) -> local (cap, d)
        levels=P(axes, None),
        keys=P(None, axes),  # (L, S·cap) -> local (L, cap)
        fill=P(axes),  # (S,) -> local (1,)
    )


def make_sharded_delta(
    cfg: IndexConfig, mesh: Mesh, capacity: int, dtype, n_local: int
) -> tuple[DeltaSegment, jax.Array]:
    """Allocate empty per-shard delta segments + the shard-major tombstone
    bitmap ((S·(n_local+cap),): shard s owns slice [s·(n_local+cap), ...))."""
    S = mesh.devices.size
    axes = tuple(mesh.axis_names)

    def put(x, spec):
        return jax.device_put(x, NamedSharding(mesh, spec))

    delta = DeltaSegment(
        data=put(jnp.zeros((S * capacity, cfg.d), dtype), P(axes, None)),
        levels=put(jnp.zeros((S * capacity, cfg.d), jnp.int32), P(axes, None)),
        keys=put(jnp.zeros((cfg.L, S * capacity), jnp.int32), P(None, axes)),
        fill=put(jnp.zeros((S,), jnp.int32), P(axes)),
    )
    tombstones = put(jnp.zeros((S * (n_local + capacity),), bool), P(axes))
    return delta, tombstones


def _shard_rank(axes, mesh) -> jax.Array:
    """Linearized shard rank inside a shard_map body (row-major over axes)."""
    rank = jnp.zeros((), jnp.int32)
    mul = 1
    for ax in reversed(axes):
        rank = rank + jax.lax.axis_index(ax) * mul
        mul *= mesh.shape[ax]  # static size (lax.axis_size needs jax>=0.4.38)
    return rank


def build_local_indexes(
    key, data_global: jax.Array, cfg: IndexConfig, mesh: Mesh
) -> ALSHIndex:
    """Build one complete local index per shard, ONCE: (n, d) row-sharded
    data -> a sharded ALSHIndex pytree (leaf layout per ``local_index_specs``).

    All shards share the SAME hash tables (key is broadcast), so a query's
    hash keys are valid against every shard's sorted tables.
    """
    axes = tuple(mesh.axis_names)
    data_sharded = jax.device_put(data_global, NamedSharding(mesh, P(axes, None)))
    fn = shard_map(
        lambda data_local: build_index(key, data_local, cfg),
        mesh=mesh,
        in_specs=P(axes, None),
        out_specs=local_index_specs(mesh),
        check_vma=False,
    )
    return fn(data_sharded)


def _globalize_and_merge(res, axes, mesh, k, n_local, merge_hierarchical):
    """Inside a query shard_map body: local QueryResult -> merged globals.

    Maps local ids to global ids — main row i on shard s is ``s·n_local + i``
    (rows are contiguously partitioned); delta slot t on shard s is
    ``S·n_local + t·S + s`` (inserts route round-robin, so the t-th slot of
    shard s held the (t·S + s)-th insert) — then top-k-merges along each
    mesh axis innermost-first (hierarchical) or across the whole mesh at
    once.
    """
    rank = _shard_rank(axes, mesh)
    S = mesh.devices.size
    main_g = res.ids + rank * n_local
    delta_g = S * n_local + (res.ids - n_local) * S + rank
    gids = jnp.where(res.ids < 0, -1, jnp.where(res.ids < n_local, main_g, delta_g))
    d, i, nc = res.dists, gids, res.n_candidates

    def merge_axis(d, i, nc, ax):
        dg = jax.lax.all_gather(d, ax, axis=0)  # (g, b, k)
        ig = jax.lax.all_gather(i, ax, axis=0)
        g, b, kk = dg.shape
        dg = jnp.moveaxis(dg, 0, 1).reshape(b, g * kk)
        ig = jnp.moveaxis(ig, 0, 1).reshape(b, g * kk)
        neg, sel = jax.lax.top_k(-dg, k)
        return -neg, jnp.take_along_axis(ig, sel, axis=1), jax.lax.psum(nc, ax)

    if merge_hierarchical:
        for ax in reversed(axes):  # model -> data -> pod
            d, i, nc = merge_axis(d, i, nc, ax)
    else:  # flat merge across the whole mesh at once (baseline)
        d, i, nc = merge_axis(d, i, nc, axes)
    return d, i, nc


def sharded_index_query(
    index_sharded: ALSHIndex,
    queries: jax.Array,
    weights: jax.Array,
    cfg: IndexConfig,
    mesh: Mesh,
    spec=None,
    k: int = 10,
    merge_hierarchical: bool = True,
    delta_sharded: DeltaSegment | None = None,
    tombstones_sharded: jax.Array | None = None,
    update=None,
):
    """Query prebuilt shard-local indexes (from ``build_local_indexes``).

    ``spec`` (a :class:`repro.api.QuerySpec`) selects the shard-local
    execution strategy — probe, multiprobe, or exact — so the sharded
    service exposes the same policy surface as a single-host ``Index``.
    Each shard's body is :func:`repro.engine.dispatch` over its slice —
    the identical pipeline (sources, dedupe, tombstone mask, fused rerank)
    the single-host facade runs — with the hierarchical top-k merge
    composing the per-shard results.

    With ``delta_sharded``/``tombstones_sharded`` (a mutable
    ``ShardedIndex``), each shard adds the delta key-match source over its
    private delta and tombstone slice; merged ids use the global id scheme
    of ``_globalize_and_merge``. ``update`` is accepted for backward
    compatibility and unused (the engine needs only the arrays).
    """
    del update  # kept for call-site compatibility
    from repro.api import QuerySpec  # lazy: api builds on core

    if spec is None:
        spec = QuerySpec(k=k)
    axes = tuple(mesh.axis_names)
    S = mesh.devices.size
    n_local = index_sharded.data.shape[0] // S

    if delta_sharded is None:

        def local(idx_local, q, w):
            res = _local_query(idx_local, None, None, q, w, cfg, spec)
            return _globalize_and_merge(
                res, axes, mesh, spec.k, n_local, merge_hierarchical
            )

        fn = shard_map(
            local,
            mesh=mesh,
            in_specs=(local_index_specs(mesh), P(), P()),
            out_specs=(P(), P(), P()),
            check_vma=False,
        )
        d, i, nc = fn(index_sharded, queries, weights)
        return ShardedQueryResult(dists=d, ids=i, n_candidates=nc)

    def local_mut(idx_local, delta_local, ts_local, q, w):
        delta = DeltaSegment(
            data=delta_local.data,
            levels=delta_local.levels,
            keys=delta_local.keys,
            fill=delta_local.fill.reshape(()),
        )
        res = _local_query(idx_local, delta, ts_local, q, w, cfg, spec)
        return _globalize_and_merge(
            res, axes, mesh, spec.k, n_local, merge_hierarchical
        )

    fn = shard_map(
        local_mut,
        mesh=mesh,
        in_specs=(local_index_specs(mesh), local_delta_specs(mesh), P(axes), P(), P()),
        out_specs=(P(), P(), P()),
        check_vma=False,
    )
    d, i, nc = fn(index_sharded, delta_sharded, tombstones_sharded, queries, weights)
    return ShardedQueryResult(dists=d, ids=i, n_candidates=nc)


def sharded_delta_insert(
    index_sharded: ALSHIndex,
    delta_sharded: DeltaSegment,
    rows: jax.Array,
    cfg: IndexConfig,
    mesh: Mesh,
    impl: str = "auto",
) -> tuple[DeltaSegment, jax.Array]:
    """Insert rows into per-shard delta segments, routed by global id.

    The j-th row of the stream gets global id ``n_main_global + e`` (e =
    running insert count); its owner is shard ``e % S`` and its slot is
    ``e // S`` — round-robin striping, so every shard's delta fills evenly
    and the single-host id scheme is preserved. Each shard hashes its own
    rows with the replicated tables (O(H·d·m/S) per shard, no resort).

    Returns (new delta_sharded, (m,) global ids; -1 where the owning
    shard's delta was full).
    """
    S = mesh.devices.size
    axes = tuple(mesh.axis_names)
    n_local = index_sharded.data.shape[0] // S
    cap = delta_sharded.data.shape[0] // S
    n_main_global = n_local * S
    m = rows.shape[0]
    B = -(-m // S)  # rows per shard this call

    # next insert position: all shards filled round-robin from e=0, so the
    # resume phase is the total fill (drops only happen when EVERY later
    # shard is full too, keeping fills within one stripe of each other)
    phase = (jnp.sum(delta_sharded.fill) % S).astype(jnp.int32)
    rows_p = jnp.pad(rows.astype(delta_sharded.data.dtype), ((0, B * S - m), (0, 0)))
    valid = jnp.arange(B * S, dtype=jnp.int32) < m
    # J[s, t] = stream position routed to shard s, slot offset t
    s_idx = jnp.arange(S, dtype=jnp.int32)[:, None]
    t_idx = jnp.arange(B, dtype=jnp.int32)[None, :]
    J = ((s_idx - phase) % S) + t_idx * S  # (S, B)
    rows_routed = jnp.take(rows_p, J.reshape(-1), axis=0)  # (S·B, d)
    valid_routed = jnp.take(valid, J.reshape(-1))  # (S·B,)

    def local(idx_local, delta_local, rows_s, valid_s):
        rank = _shard_rank(axes, mesh)
        rows_s = rows_s.reshape(B, -1)
        valid_s = valid_s.reshape(B)
        keys, levels = hash_rows(idx_local, rows_s, cfg, impl=impl)  # (L, B), (B, d)
        fill = delta_local.fill.reshape(())
        n_valid = jnp.sum(valid_s.astype(jnp.int32))  # valid rows are a prefix
        t = jnp.arange(B, dtype=jnp.int32)
        slot = fill + t
        write = (t < n_valid) & (slot < cap)
        tgt = jnp.where(write, slot, cap)  # out-of-capacity -> dropped
        new_delta = DeltaSegment(
            data=delta_local.data.at[tgt].set(rows_s, mode="drop"),
            levels=delta_local.levels.at[tgt].set(levels, mode="drop"),
            keys=delta_local.keys.at[:, tgt].set(keys, mode="drop"),
            fill=jnp.minimum(jnp.asarray(cap, jnp.int32), fill + n_valid).reshape(1),
        )
        ids = jnp.where(write, n_main_global + slot * S + rank, -1)
        return new_delta, ids.reshape(1, B)

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(local_index_specs(mesh), local_delta_specs(mesh), P(axes), P(axes)),
        out_specs=(local_delta_specs(mesh), P(axes, None)),
        check_vma=False,
    )
    new_delta, ids_mat = fn(index_sharded, delta_sharded, rows_routed, valid_routed)
    j = jnp.arange(m, dtype=jnp.int32)
    ids = ids_mat[(phase + j) % S, j // S]  # back to stream order
    return new_delta, ids


def sharded_tombstone(
    tombstones_sharded: jax.Array,
    gids: jax.Array,
    delta_fill: jax.Array,
    mesh: Mesh,
    n_local: int,
    cap: int,
) -> jax.Array:
    """Tombstone global ids on their owning shards (others drop them).

    Owner/local-slot mapping inverts ``_globalize_and_merge``: main gid g
    lives on shard ``g // n_local`` at slot ``g % n_local``; delta gid
    ``n_main_global + e`` lives on shard ``e % S`` at slot
    ``n_local + e // S``. Unknown gids — negative, out of range, or naming
    a delta slot no insert has assigned yet (slot >= the owner's fill) —
    are ignored, matching single-host ``tombstone_ids``.
    """
    S = mesh.devices.size
    axes = tuple(mesh.axis_names)
    n_main_global = n_local * S

    def local(ts_local, g, fill_local):
        rank = _shard_rank(axes, mesh)
        fill = fill_local.reshape(())
        safe = jnp.maximum(g, 0)
        is_main = (g >= 0) & (g < n_main_global)
        in_delta = (g >= n_main_global) & (g < n_main_global + cap * S)
        e = safe - n_main_global
        in_delta = in_delta & (e // S < fill)  # unassigned slots: ignored
        owner = jnp.where(is_main, safe // n_local, e % S)
        local_slot = jnp.where(is_main, safe % n_local, n_local + e // S)
        mine = (is_main | in_delta) & (owner == rank)
        idx = jnp.where(mine, local_slot, n_local + cap)  # miss -> dropped
        return ts_local.at[idx].set(True, mode="drop")

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axes), P(), P(axes)),
        out_specs=P(axes),
        check_vma=False,
    )
    return fn(tombstones_sharded, gids, delta_fill)


def sharded_query(
    key,
    data_sharded: jax.Array,
    queries: jax.Array,
    weights: jax.Array,
    cfg: IndexConfig,
    mesh: Mesh,
    k: int = 10,
    merge_hierarchical: bool = True,
    spec=None,
):
    """One-shot build+query under shard_map (tests/benchmarks on small CPU
    meshes; serving paths prebuild via ``build_local_indexes`` instead).

    ``k`` is kept for backward compatibility and ignored when ``spec`` is
    given.
    """
    from repro.api import QuerySpec  # lazy: api builds on core

    if spec is None:
        spec = QuerySpec(k=k)
    axes = tuple(mesh.axis_names)
    n_local = data_sharded.shape[0] // mesh.devices.size

    def local(data_local, q, w):
        idx = build_index(key, data_local, cfg)
        res = _local_query(idx, None, None, q, w, cfg, spec)
        return _globalize_and_merge(res, axes, mesh, spec.k, n_local, merge_hierarchical)

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axes, None), P(), P()),
        out_specs=(P(), P(), P()),
        check_vma=False,
    )
    d, i, nc = fn(data_sharded, queries, weights)
    return ShardedQueryResult(dists=d, ids=i, n_candidates=nc)
