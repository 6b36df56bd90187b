"""Streaming top-k wl1 scan: exact k-NN without the (b, n) distance matrix.

``wl1_scan`` (wl1_distance.py) materializes every query-point distance and
leaves the top-k to XLA — O(b n) HBM writes + a second O(b n) read. For the
exact baseline and the distributed re-rank that traffic dominates, so this
kernel keeps a per-query running top-k (dists + ids) resident in VMEM across
the data-row grid axis and only ever writes the (b, k) result:

  grid (query-block i, data-block j, d-chunk kd) — kd innermost:
    * a VMEM scratch (BQ, BNV) accumulates partial weighted |diff| sums
      over d-chunks exactly like the scan kernel;
    * on the last d-chunk the finished block distances are merged into the
      running top-k output block (revisited across j — Pallas keeps it in
      VMEM) by a k-step selection: each step extracts the global argmin of
      [running top-k ‖ block] and appends it in ascending order.

Ties resolve toward earlier candidates ([prev top-k ‖ ascending block ids]),
matching ``lax.top_k`` order on exact equality. Rows padded past n enter with
+inf and id -1; queries short of k valid rows return (+inf, -1) tails —
identical semantics to the materializing oracle.

The merge is gated. Its k dependent cross-lane selections cost several
times the block's distances, and in rows stored in an order unrelated to the
query almost no block holds a row nearer than the running k-th (row block j
holds one of the top k with probability about k/j). So a second VMEM scratch
keeps each query's k-th distance, ``top_d[:, k-1]`` (+inf until k rows are
in), replicated across lanes and rewritten only by a merge; a tile (BQ
queries × BNV rows) merges only when some block distance is not >= its
query's k-th. Skipping leaves the answer bit for bit: the running top-k is
ascending, and the merge keeps ties in [prev ‖ block] order, so a block whose
every distance is >= the k-th leaves (top_d, top_i) unchanged. Queries of a
merging tile with no such row pass through the merge unchanged. The gate is
one compare and one cross-lane reduction to a scalar a tile. The input it
cannot help is rows stored by descending distance to every query of a tile:
there every tile merges and the gate is pure cost. ``count_merges=True``
adds an output that counts the merges per query block
(``ops.wl1_scan_topk_merge_share``).

``wl1_scan_topk_chunked`` is the same algorithm in pure jnp (a fori_loop over
row chunks with a top_k merge) — the CPU production path: the working set
stays cache-sized instead of a (b, n) spill.

Known cost, left for a speed PR: the wrapper pads the whole table to a
multiple of ``BDV`` columns on every call. At d=128 that doubles the width,
and a v5e compile at n=1,000,000 needs 0.95 GiB of temporaries for it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BQ = 8  # queries per block
BNV = 128  # data rows per block
BDV = 256  # coordinates per reduction step
LANE = 128  # top-k buffer lane alignment


def _merge_topk(top_d, top_i, blk_d, blk_i, k: int):
    """Selection-merge: ascending k smallest of [top ‖ blk] (pure jnp, kernel-safe).

    top_d/top_i: (BQ, KP) running top-k (ascending, +inf/-1 padded).
    blk_d/blk_i: (BQ, BN) new block distances / ids.
    Returns new (top_d, top_i) with the first k slots filled ascending.
    """
    cand_d = jnp.concatenate([top_d, blk_d], axis=1)
    cand_i = jnp.concatenate([top_i, blk_i], axis=1)
    kp = top_d.shape[1]
    out_iota = jax.lax.broadcasted_iota(jnp.int32, top_d.shape, 1)
    cand_iota = jax.lax.broadcasted_iota(jnp.int32, cand_d.shape, 1)
    init = (
        cand_d,
        cand_i,
        jnp.full(top_d.shape, jnp.inf, top_d.dtype),
        jnp.full(top_i.shape, -1, top_i.dtype),
    )

    def step(t, carry):
        cd, ci, nd, ni = carry
        pos = jnp.argmin(cd, axis=1)  # (BQ,) first-occurrence ⇒ stable ties
        sel = cand_iota == pos[:, None]
        mval = jnp.min(cd, axis=1)
        mid = jnp.sum(jnp.where(sel, ci, 0), axis=1)  # gather-free pick
        put = out_iota == t
        nd = jnp.where(put, mval[:, None], nd)
        ni = jnp.where(put, mid[:, None], ni)
        cd = jnp.where(sel, jnp.inf, cd)
        return cd, ci, nd, ni

    _, _, new_d, new_i = jax.lax.fori_loop(0, min(k, kp), step, init)
    return new_d, new_i


def _scan_topk_kernel(data_ref, q_ref, w_ref, outd_ref, outi_ref, *refs, k: int, n: int):
    # refs: [merge-count output,] block-distance accumulator, k-th distances
    cnt_ref, acc_ref, kth_ref = refs if len(refs) == 3 else (None, *refs)
    j = pl.program_id(1)
    kd = pl.program_id(2)
    nd = pl.num_programs(2)

    @pl.when((j == 0) & (kd == 0))
    def _init_topk():
        outd_ref[...] = jnp.full_like(outd_ref, jnp.inf)
        outi_ref[...] = jnp.full_like(outi_ref, -1)
        kth_ref[...] = jnp.full_like(kth_ref, jnp.inf)
        if cnt_ref is not None:
            cnt_ref[...] = jnp.zeros_like(cnt_ref)

    diff = jnp.abs(data_ref[...][None, :, :] - q_ref[...][:, None, :])  # (BQ, BNV, BDV)
    partial = jnp.sum(w_ref[...][:, None, :] * diff, axis=-1)  # (BQ, BNV)

    @pl.when(kd == 0)
    def _acc_init():
        acc_ref[...] = partial

    @pl.when(kd != 0)
    def _acc():
        acc_ref[...] += partial

    @pl.when(kd == nd - 1)
    def _last_chunk():
        row0 = j * BNV
        ids = row0 + jax.lax.broadcasted_iota(jnp.int32, acc_ref.shape, 1)  # (BQ, BNV)
        in_bounds = ids < n
        blk_d = jnp.where(in_bounds, acc_ref[...], jnp.inf)
        # the gate: skip the merge when every block distance is >= its
        # query's k-th (then the merge would change nothing)
        stays = jnp.where(blk_d >= kth_ref[...], 1.0, 0.0)

        @pl.when(jnp.min(stays) == 0.0)
        def _merge():
            blk_i = jnp.where(in_bounds, ids, -1)
            new_d, new_i = _merge_topk(outd_ref[...], outi_ref[...], blk_d, blk_i, k)
            outd_ref[...] = new_d
            outi_ref[...] = new_i
            kth_ref[...] = jnp.broadcast_to(new_d[:, k - 1 : k], kth_ref.shape)
            if cnt_ref is not None:
                cnt_ref[...] += 1


@functools.partial(jax.jit, static_argnames=("k", "interpret", "count_merges"))
def wl1_scan_topk_pallas(
    data: jax.Array,
    queries: jax.Array,
    weights: jax.Array,
    k: int,
    *,
    interpret: bool = False,
    count_merges: bool = False,
) -> tuple[jax.Array, ...]:
    """data (n, d), queries (b, d), weights (b, d) -> ((b, k) dists, (b, k) ids).

    With ``count_merges`` a third output, (ceil(b / BQ),) int32, counts the
    row blocks whose merge ran, for each query block.
    """
    n, d = data.shape
    b, _ = queries.shape
    kp = -k % LANE + k  # top-k buffer lane-aligned
    pn = -n % BNV
    pb = -b % BQ
    pd = -d % BDV
    data_p = jnp.pad(data.astype(jnp.float32), ((0, pn), (0, pd)))
    q_p = jnp.pad(queries.astype(jnp.float32), ((0, pb), (0, pd)))
    w_p = jnp.pad(weights.astype(jnp.float32), ((0, pb), (0, pd)))
    bp, dp = q_p.shape
    np_ = data_p.shape[0]
    grid = (bp // BQ, np_ // BNV, dp // BDV)
    topk_spec = pl.BlockSpec((BQ, kp), lambda i, j, kd: (i, 0))
    out_specs = [topk_spec, topk_spec]
    out_shape = [
        jax.ShapeDtypeStruct((bp, kp), jnp.float32),
        jax.ShapeDtypeStruct((bp, kp), jnp.int32),
    ]
    if count_merges:
        out_specs.append(pl.BlockSpec((BQ, LANE), lambda i, j, kd: (i, 0)))
        out_shape.append(jax.ShapeDtypeStruct((bp, LANE), jnp.int32))
    outs = pl.pallas_call(
        functools.partial(_scan_topk_kernel, k=k, n=n),
        grid=grid,
        in_specs=[
            pl.BlockSpec((BNV, BDV), lambda i, j, kd: (j, kd)),
            pl.BlockSpec((BQ, BDV), lambda i, j, kd: (i, kd)),
            pl.BlockSpec((BQ, BDV), lambda i, j, kd: (i, kd)),
        ],
        out_specs=tuple(out_specs),
        out_shape=tuple(out_shape),
        scratch_shapes=[pltpu.VMEM((BQ, BNV), jnp.float32), pltpu.VMEM((BQ, BNV), jnp.float32)],
        interpret=interpret,
        name="wl1_scan_topk_pallas",
    )(data_p, q_p, w_p)
    out_d, out_i = outs[0][:b, :k], outs[1][:b, :k]
    # invalid-slot contract (QueryResult): ids == -1 ⇔ dists == +inf — a row
    # whose distance overflowed to +inf reports "not found", matching the
    # _topk_ascending paths bit-for-bit
    out_i = jnp.where(jnp.isfinite(out_d), out_i, -1)
    if count_merges:
        return out_d, out_i, outs[2][::BQ, 0]
    return out_d, out_i


@functools.partial(jax.jit, static_argnames=("k", "chunk"))
def wl1_scan_topk_chunked(
    data: jax.Array,
    queries: jax.Array,
    weights: jax.Array,
    k: int,
    chunk: int = 2048,
) -> tuple[jax.Array, jax.Array]:
    """Pure-jnp streaming top-k scan (CPU production path).

    Processes data rows in ``chunk``-sized windows, merging each window's
    distances into a running (b, k) top-k — peak live memory is
    O(b·chunk + b·k) instead of O(b·n).
    """
    n, d = data.shape
    b, _ = queries.shape
    pn = -n % chunk
    data_p = jnp.pad(data.astype(jnp.float32), ((0, pn), (0, 0)))
    q = queries.astype(jnp.float32)
    w = weights.astype(jnp.float32)
    n_chunks = data_p.shape[0] // chunk

    def body(c, carry):
        top_d, top_i = carry
        rows = jax.lax.dynamic_slice_in_dim(data_p, c * chunk, chunk, axis=0)
        dists = jnp.sum(w[:, None, :] * jnp.abs(rows[None, :, :] - q[:, None, :]), axis=-1)
        ids = c * chunk + jnp.arange(chunk, dtype=jnp.int32)
        ids = jnp.broadcast_to(ids[None, :], dists.shape)
        dists = jnp.where(ids < n, dists, jnp.inf)
        cand_d = jnp.concatenate([top_d, dists], axis=1)
        cand_i = jnp.concatenate([top_i, jnp.where(ids < n, ids, -1)], axis=1)
        neg, sel = jax.lax.top_k(-cand_d, k)
        return -neg, jnp.take_along_axis(cand_i, sel, axis=1)

    top_d = jnp.full((b, k), jnp.inf, jnp.float32)
    top_i = jnp.full((b, k), -1, jnp.int32)
    top_d, top_i = jax.lax.fori_loop(0, n_chunks, body, (top_d, top_i))
    # invalid-slot contract (QueryResult): ids == -1 ⇔ dists == +inf
    return top_d, jnp.where(jnp.isfinite(top_d), top_i, -1)
