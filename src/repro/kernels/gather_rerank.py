"""Fused ALSH probe tail: scalar-prefetch gather + exact re-rank + top-k.

The unfused tail (`index.data[ids]` → wl1_rerank → lax.top_k) materializes a
(b, L·C, d) candidate tensor in HBM and reads it straight back — for the
standard b=64, L·C=4096, d=128 probe that is two full passes over 128 MB the
query never needed. This kernel removes it: candidate ids are handed to
Pallas as **scalar-prefetch** arguments (`pltpu.PrefetchScalarGridSpec`), so
the BlockSpec index map — evaluated ahead of the grid step — points the
pipeline's DMA engine directly at the needed `(1, d-chunk)` row of the
table in HBM. Each candidate's weighted |diff| partial sums accumulate
in a scalar scratch across d-chunks; the finished distance is folded into a
per-query VMEM top-k buffer by replace-max insertion:

  grid (query i, candidate j, d-chunk kd):
    data block  (1, BDR)  @ row  min(ids[i, j], n-1)   — the gather
    out blocks  (1, KP)   @ i                          — running top-k

Every per-row operand is an (m, 1, dp) view with the leading block dim
squeezed, so each block's last two dims are (1, 128)/(1, KP): equal to the
array's, as Mosaic requires of a block smaller than the (8, 128) tile. The
scalar-prefetched ids live in SMEM (1 MiB on v5e), so a large (b, P) id
array runs as a sequence of calls over bounded (query, candidate) tiles that
carry the top-k buffer along (`_run_id_blocks`).

Invalid candidates (padding, duplicates zapped by dedupe) carry the sentinel
id n: the index map clamps them to a readable row and the merge step drops
them. The buffer holds the KP (=128-aligned) smallest distances unsorted; the
wrapper sorts the (b, KP) result and slices (b, k) — exactly the oracle's
`ref.gather_rerank_topk` semantics ((+inf, -1) tails when fewer than k valid).

The CPU production path (`gather_rerank_topk_auto`) fuses in pure jnp and
picks its schedule by static footprint: a monolithic single-pass (one XLA
fusion region, no inter-stage materialization) while the (b, P, d) working
set is cache-resident, switching to `gather_rerank_topk_chunked` — a
fori_loop over candidate chunks (gather chunk → re-rank → top-k merge) that
keeps the live set at O(b·chunk·d) and skips all-sentinel chunks — once the
monolith would spill.

Quantized storage (`scales=` / non-f32 `data` on every entry point, see
repro.quant): the table payload may be bf16 or symmetric-int8 rows. Every
schedule gathers the ENCODED row and decodes in-register (widen to f32,
then `* scales` when the codec stored them) — the DMA stream stays
byte-bound at the compressed width and no f32 copy of the table is ever
materialized. The jnp schedules decode per gathered candidate chunk; the
Pallas path switches to `gather_rerank_topk_pallas_blocked`, which
additionally coalesces the gather: each grid step prefetches a BLOCK of
`CBLK` candidate rows as `CBLK` parallel scalar-prefetch streams (batch
DMA per candidate block instead of one row per step), accumulates their
partial sums side by side in SMEM, and folds all `CBLK` finished distances
into the top-k buffer in candidate order — bit-identical insertion order
to the per-row kernel, several row DMAs in flight instead of one.

Two-segment mode (`delta=` on every entry point): a mutable index re-ranks
against a sealed (n_main, d) main table PLUS an unsealed (cap, d) delta
table, with candidate ids addressing their virtual concatenation (id i >=
n_main is delta slot i - n_main). Rather than concatenating the tables per
query batch — an O((n_main + cap)·d) HBM copy the old two-segment tail
paid — every schedule gathers from whichever segment owns each id: the
Pallas kernel runs BOTH tables as scalar-prefetch gather streams (the
index maps clamp each id into its own segment; the kernel keeps the
partial sum of the owning segment), and the jnp schedules select per
candidate between two clamped row gathers. Bit-identical to the
concatenated-table result.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BDR = 128  # coordinates per d-chunk (gather DMA granularity)
KP_LANE = 128  # top-k buffer lane alignment
CBLK = 8  # candidate rows gathered per grid step by the blocked schedule
# Candidate ids one pallas_call scalar-prefetches into SMEM (128 KiB of
# v5e's 1 MiB). Larger (b, P) id arrays run as a sequence of calls over
# (query-block, candidate-block) tiles that carry the top-k buffer along.
MAX_PREFETCH_IDS = 32 * 1024


def _init_topk(initd_ref, initi_ref, outd_ref, outi_ref):
    outd_ref[...] = initd_ref[...]
    outi_ref[...] = initi_ref[...]


def _insert(outd_ref, outi_ref, cid, dist, valid):
    """Replace-max insertion of one finished candidate into the (1, KP)
    buffer (first-occurrence argmax ⇒ +inf slots fill in order)."""
    cur_d = outd_ref[...]  # (1, KP)
    cur_i = outi_ref[...]
    worst = jnp.max(cur_d)
    slot = jnp.argmax(cur_d)
    lane = jax.lax.broadcasted_iota(jnp.int32, cur_d.shape, 1)
    put = (lane == slot) & valid & (dist < worst)
    outd_ref[...] = jnp.where(put, dist, cur_d)
    outi_ref[...] = jnp.where(put, cid, cur_i)


def _gather_rerank_kernel(
    ids_ref, row_ref, q_ref, w_ref, initd_ref, initi_ref, outd_ref, outi_ref, acc_ref,
    *, n: int,
):
    i = pl.program_id(0)
    j = pl.program_id(1)
    kd = pl.program_id(2)
    nd = pl.num_programs(2)

    @pl.when((j == 0) & (kd == 0))
    def _():
        _init_topk(initd_ref, initi_ref, outd_ref, outi_ref)

    partial = jnp.sum(w_ref[...] * jnp.abs(row_ref[...] - q_ref[...]))  # scalar

    @pl.when(kd == 0)
    def _acc_init():
        acc_ref[0, 0] = partial

    @pl.when(kd != 0)
    def _acc():
        acc_ref[0, 0] += partial

    @pl.when(kd == nd - 1)
    def _merge():
        cid = ids_ref[i, j]
        _insert(outd_ref, outi_ref, cid, acc_ref[0, 0], cid < n)


def _gather_rerank2_kernel(
    ids_ref, main_ref, delta_ref, q_ref, w_ref, initd_ref, initi_ref,
    outd_ref, outi_ref, acc_ref, *, n_main: int, n_tot: int,
):
    """Two-segment variant: the grid pipelines BOTH segment tables as
    scalar-prefetch gather streams (each index map clamps the candidate id
    into its own segment), and the accumulator keeps whichever partial sum
    belongs to the segment that owns the id — the merge step is unchanged."""
    i = pl.program_id(0)
    j = pl.program_id(1)
    kd = pl.program_id(2)
    nd = pl.num_programs(2)

    @pl.when((j == 0) & (kd == 0))
    def _():
        _init_topk(initd_ref, initi_ref, outd_ref, outi_ref)

    cid = ids_ref[i, j]
    part_m = jnp.sum(w_ref[...] * jnp.abs(main_ref[...] - q_ref[...]))  # scalar
    part_d = jnp.sum(w_ref[...] * jnp.abs(delta_ref[...] - q_ref[...]))
    partial = jnp.where(cid < n_main, part_m, part_d)

    @pl.when(kd == 0)
    def _acc_init():
        acc_ref[0, 0] = partial

    @pl.when(kd != 0)
    def _acc():
        acc_ref[0, 0] += partial

    @pl.when(kd == nd - 1)
    def _merge():
        _insert(outd_ref, outi_ref, cid, acc_ref[0, 0], cid < n_tot)


def _make_blocked_kernel(cb: int, n_main: int, n_tot: int, two_seg: bool):
    """The block-coalesced kernel body: ``cb`` candidate rows per grid step.

    Ref layout (after the scalar-prefetch ids): ``cb`` main-row streams,
    [``cb`` delta-row streams,] scales, q, w, init top-k | outd, outi |
    (1, cb) SMEM accumulator. The per-candidate math, accumulation order
    over d-chunks, and top-k insertion order (global candidate order
    jb·cb + c) are all IDENTICAL to the per-row kernels — same buffers, bit
    for bit — only the DMA schedule changes: cb gather streams are in
    flight per step instead of one."""

    def kernel(ids_ref, *refs):
        nrow = cb * (2 if two_seg else 1)
        rows = refs[:nrow]
        sc_ref, q_ref, w_ref, initd_ref, initi_ref, outd_ref, outi_ref, acc_ref = refs[nrow:]
        i = pl.program_id(0)
        jb = pl.program_id(1)
        kd = pl.program_id(2)
        nd = pl.num_programs(2)

        @pl.when((jb == 0) & (kd == 0))
        def _():
            _init_topk(initd_ref, initi_ref, outd_ref, outi_ref)

        sc = sc_ref[...]  # (1, BDR) decode scales (exact ones when unscaled)
        for c in range(cb):
            row = rows[c][...].astype(jnp.float32) * sc
            part = jnp.sum(w_ref[...] * jnp.abs(row - q_ref[...]))  # scalar
            if two_seg:
                drow = rows[cb + c][...].astype(jnp.float32) * sc
                dpart = jnp.sum(w_ref[...] * jnp.abs(drow - q_ref[...]))
                part = jnp.where(ids_ref[i, jb * cb + c] < n_main, part, dpart)

            @pl.when(kd == 0)
            def _acc_init(c=c, part=part):
                acc_ref[0, c] = part

            @pl.when(kd != 0)
            def _acc(c=c, part=part):
                acc_ref[0, c] += part

        @pl.when(kd == nd - 1)
        def _merge():
            for c in range(cb):
                cid = ids_ref[i, jb * cb + c]
                _insert(outd_ref, outi_ref, cid, acc_ref[0, c], cid < n_tot)

    return kernel


def _row_view(x: jax.Array, dp: int) -> jax.Array:
    """(m, d) -> (m, 1, dp) zero-padded rows. The unit middle axis makes a
    single row a legal TPU block: ``(None, 1, BDR)`` has trailing dims
    (1, 128), equal to / divisible by the array's, where a ``(1, BDR)``
    block of an (m, dp) array is not (Mosaic tiles the last two dims by
    (8, 128)). For f32 rows at d % 128 == 0 the view is a bitcast; int8 and
    bf16 rows are laid out anew on each call (a speed PR's concern)."""
    m, d = x.shape
    return jnp.pad(x, ((0, 0), (0, dp - d))).reshape(m, 1, dp)


def _id_blocks(b: int, P: int, max_ids: int, mult: int) -> tuple[int, int]:
    """(query rows, candidate slots) per pallas_call: the whole (b, P) id
    array when it fits ``max_ids``, else tiles of a multiple of 8 rows by
    a multiple of ``mult`` slots holding at most ~``max_ids`` ids."""
    if b * P <= max_ids:
        return b, P
    step = 8 * mult // math.gcd(8, mult)
    pc = min(P, max(step, max_ids // 8 // step * step))
    bq = min(-(-b // 8) * 8, max(8, max_ids // pc // 8 * 8))
    return bq, pc


def _run_id_blocks(call, ids, q3, w3, kp, sentinel, max_ids, mult=1):
    """Drive ``call(ids_blk, q_blk, w_blk, initd, initi) -> (outd, outi)``
    over bounded id tiles so no call's scalar prefetch outgrows SMEM.

    Query blocks map independently (``lax.map``); within one, candidate
    blocks run in order (``lax.scan``) with the unsorted top-k buffer
    carried from call to call — every candidate meets the same buffer it
    would have met in one monolithic call, so the result is bit-identical.
    Padding slots carry ``sentinel`` (never inserted); padding queries are
    sliced away. Returns the (b, kp) buffers."""
    b, P = ids.shape
    bq, pc = _id_blocks(b, P, max_ids, mult)
    nb, nc = -(-b // bq), -(-P // pc)
    ids = jnp.pad(ids, ((0, nb * bq - b), (0, nc * pc - P)), constant_values=sentinel)
    q3 = jnp.pad(q3, ((0, nb * bq - b), (0, 0), (0, 0)))
    w3 = jnp.pad(w3, ((0, nb * bq - b), (0, 0), (0, 0)))
    init = (
        jnp.full((bq, 1, kp), jnp.inf, jnp.float32),
        jnp.full((bq, 1, kp), -1, jnp.int32),
    )
    if nb == 1 and nc == 1:
        out_d, out_i = call(ids, q3, w3, *init)
        return out_d[:b, 0], out_i[:b, 0]

    def query_block(args):
        ids_q, q_q, w_q = args  # (bq, nc·pc), (bq, 1, dp) x2

        def cand_block(buf, ids_c):
            return call(ids_c, q_q, w_q, *buf), None

        blocks = ids_q.reshape(bq, nc, pc).swapaxes(0, 1)  # (nc, bq, pc)
        buf, _ = jax.lax.scan(cand_block, init, blocks)
        return buf

    dp = q3.shape[-1]
    out_d, out_i = jax.lax.map(
        query_block,
        (ids.reshape(nb, bq, nc * pc), q3.reshape(nb, bq, 1, dp), w3.reshape(nb, bq, 1, dp)),
    )
    return out_d.reshape(nb * bq, kp)[:b], out_i.reshape(nb * bq, kp)[:b]


def _topk_call(kernel, tables, specs, grid_of, kp, acc_width, interpret, name, prefix=()):
    """One bounded-id pallas_call named ``name``: ``prefix`` operands
    (decode scales) and the row ``tables`` follow the scalar-prefetched
    ids; q, w and the carried top-k buffer come last. The buffer is
    aliased in place."""

    def call(ids, q3, w3, initd, initi):
        bq, pc = ids.shape
        vec = pl.BlockSpec((None, 1, BDR), lambda i, j, kd, ids_ref: (i, 0, kd))
        buf = pl.BlockSpec((None, 1, kp), lambda i, j, kd, ids_ref: (i, 0, 0))
        n_in = 1 + len(tables) + len(prefix)  # ids, tables, prefix
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid_of(bq, pc, q3.shape[-1] // BDR),
            in_specs=[*specs, vec, vec, buf, buf],
            out_specs=(buf, buf),
            scratch_shapes=[pltpu.SMEM((1, acc_width), jnp.float32)],
        )
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=(
                jax.ShapeDtypeStruct((bq, 1, kp), jnp.float32),
                jax.ShapeDtypeStruct((bq, 1, kp), jnp.int32),
            ),
            input_output_aliases={n_in + 2: 0, n_in + 3: 1},
            interpret=interpret,
            name=name,
        )(ids, *tables, *prefix, q3, w3, initd, initi)

    return call


@functools.partial(jax.jit, static_argnames=("k", "cb", "interpret", "max_ids"))
def gather_rerank_topk_pallas_blocked(
    data: jax.Array,
    ids: jax.Array,
    queries: jax.Array,
    weights: jax.Array,
    k: int,
    *,
    delta: jax.Array | None = None,
    scales: jax.Array | None = None,
    cb: int = CBLK,
    interpret: bool = False,
    max_ids: int = MAX_PREFETCH_IDS,
) -> tuple[jax.Array, jax.Array]:
    """Block-coalesced Pallas schedule: same contract as
    ``gather_rerank_topk_pallas`` plus quantized-storage decode.

    The table payload keeps its STORED dtype end to end — the gather DMA
    moves encoded (bf16/int8) bytes and the kernel decodes in-register
    (widen + ``* scales``), so a quantized table is read at its compressed
    width. Each grid step gathers ``cb`` candidate rows as ``cb`` parallel
    scalar-prefetch streams (batch DMA per candidate block). With f32 data
    and no scales the result is bit-identical to the per-row kernel (the
    decode multiplies by exact 1.0 and the insertion order matches)."""
    n, d = data.shape
    b, P = ids.shape
    cap = 0 if delta is None else delta.shape[0]
    n_tot = n + cap
    kp = -min(k, P) % KP_LANE + min(k, P)
    dp = d + -d % BDR
    sc = jnp.ones((d,), jnp.float32) if scales is None else scales.astype(jnp.float32)
    pc = -P % cb
    ids_p = jnp.pad(ids.astype(jnp.int32), ((0, 0), (0, pc)), constant_values=n_tot)

    def _row_map(c):
        return lambda i, jb, kd, ids_ref: (
            jnp.minimum(ids_ref[i, jb * cb + c], n - 1), 0, kd,
        )

    specs = [pl.BlockSpec((None, 1, BDR), _row_map(c)) for c in range(cb)]
    tables = (_row_view(data, dp),) * cb  # encoded dtype preserved
    if delta is not None:

        def _delta_map(c):
            return lambda i, jb, kd, ids_ref: (
                jnp.clip(ids_ref[i, jb * cb + c] - n, 0, cap - 1), 0, kd,
            )

        specs += [pl.BlockSpec((None, 1, BDR), _delta_map(c)) for c in range(cb)]
        tables += (_row_view(delta.astype(data.dtype), dp),) * cb
    specs.append(pl.BlockSpec((None, 1, BDR), lambda i, jb, kd, ids_ref: (0, 0, kd)))
    kernel = _make_blocked_kernel(cb, n_main=n, n_tot=n_tot, two_seg=delta is not None)
    call = _topk_call(
        kernel, tables, specs, lambda bq, p, nd: (bq, p // cb, nd), kp, cb, interpret,
        "gather_rerank_topk_pallas_blocked", prefix=(_row_view(sc.reshape(1, d), dp),),
    )
    out_d, out_i = _run_id_blocks(
        call, ids_p, _row_view(queries.astype(jnp.float32), dp),
        _row_view(weights.astype(jnp.float32), dp), kp, n_tot, max_ids, mult=cb,
    )
    from repro.kernels.ref import _topk_ascending

    return _topk_ascending(out_d, out_i, k)


@functools.partial(jax.jit, static_argnames=("k", "interpret", "max_ids"))
def gather_rerank_topk_pallas(
    data: jax.Array,
    ids: jax.Array,
    queries: jax.Array,
    weights: jax.Array,
    k: int,
    *,
    delta: jax.Array | None = None,
    scales: jax.Array | None = None,
    interpret: bool = False,
    max_ids: int = MAX_PREFETCH_IDS,
) -> tuple[jax.Array, jax.Array]:
    """data (n, d), ids (b, P) int32 (>= n ⇒ invalid), queries/weights (b, d)
    -> ((b, k) ascending dists, (b, k) ids). With ``delta`` (cap, d), ids
    address the virtual [data; delta] concatenation (never materialized).

    Quantized storage (non-f32 ``data`` and/or ``scales``) routes to the
    block-coalesced schedule, which gathers the encoded rows and decodes
    in-register; the f32 path below is the pre-quantization program.
    ``max_ids`` bounds the ids one call prefetches (see ``_run_id_blocks``)."""
    if data.dtype != jnp.float32 or scales is not None:
        return gather_rerank_topk_pallas_blocked(
            data, ids, queries, weights, k,
            delta=delta, scales=scales, interpret=interpret, max_ids=max_ids,
        )
    n, d = data.shape
    b, P = ids.shape
    kp = -min(k, P) % KP_LANE + min(k, P)
    dp = d + -d % BDR
    row_spec = pl.BlockSpec(
        (None, 1, BDR),
        lambda i, j, kd, ids_ref: (jnp.minimum(ids_ref[i, j], n - 1), 0, kd),
    )
    if delta is None:
        specs = [row_spec]
        kernel = functools.partial(_gather_rerank_kernel, n=n)
        tables = (_row_view(data, dp),)
        n_tot = n
    else:
        cap = delta.shape[0]
        n_tot = n + cap
        delta_spec = pl.BlockSpec(
            (None, 1, BDR),
            lambda i, j, kd, ids_ref: (jnp.clip(ids_ref[i, j] - n, 0, cap - 1), 0, kd),
        )
        specs = [row_spec, delta_spec]
        kernel = functools.partial(_gather_rerank2_kernel, n_main=n, n_tot=n_tot)
        # round delta rows through the main table's dtype first — the same
        # cast every other schedule (and the old concat path) applies, so
        # mixed-dtype segments rerank identically across backends
        tables = (
            _row_view(data, dp),
            _row_view(delta.astype(data.dtype).astype(jnp.float32), dp),
        )
    call = _topk_call(
        kernel, tables, specs, lambda bq, p, nd: (bq, p, nd), kp, 1, interpret,
        "gather_rerank_topk_pallas",
    )
    out_d, out_i = _run_id_blocks(
        call, ids.astype(jnp.int32), _row_view(queries.astype(jnp.float32), dp),
        _row_view(weights.astype(jnp.float32), dp), kp, n_tot, max_ids,
    )
    # buffer is the kp smallest, unsorted — order + trim to k outside the kernel
    from repro.kernels.ref import _topk_ascending

    return _topk_ascending(out_d, out_i, k)


# Above this candidate-tensor footprint (b·P·d·4 bytes) the one-shot XLA
# fusion starts spilling LLC on CPU and the chunked streaming schedule wins
# (measured crossover between 16 MB and 32 MB on x86; see BENCH_kernels.json).
MONOLITH_BYTES = 24 * 1024 * 1024


@functools.partial(jax.jit, static_argnames=("k",))
def _gather_rerank_topk_monolith(
    data: jax.Array,
    ids: jax.Array,
    queries: jax.Array,
    weights: jax.Array,
    k: int,
    delta: jax.Array | None = None,
    scales: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """One-shot fused tail: same math as the oracle but inside a single jit
    region, so XLA folds gather → re-rank → top-k into one pass with no
    inter-stage materialization. Best schedule while the candidate tensor
    stays cache-resident."""
    from repro.kernels import ref

    if delta is None:
        return ref.gather_rerank_topk(data, ids, queries, weights, k, scales=scales)
    return ref.gather_rerank_topk_segmented(
        data, delta, ids, queries, weights, k, scales=scales
    )


def gather_rerank_topk_auto(
    data: jax.Array,
    ids: jax.Array,
    queries: jax.Array,
    weights: jax.Array,
    k: int,
    delta: jax.Array | None = None,
    scales: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """CPU production dispatch: pick the fused schedule by static footprint —
    monolithic single-pass when the (b, P, d) working set fits on-chip,
    chunked streaming (skip-capable) when it would spill. The two-segment
    monolith materializes both per-segment gathers plus their select (~3x
    the single-segment working set), so its budget is scaled to match.
    The footprint model stays at 4 bytes/value for quantized payloads too —
    both schedules decode the gathered chunk to f32, so the DECODED
    candidate tensor is what competes for cache."""
    b, P = ids.shape
    d = data.shape[1]
    working_set = b * P * d * 4 * (3 if delta is not None else 1)
    if working_set <= MONOLITH_BYTES:
        return _gather_rerank_topk_monolith(
            data, ids, queries, weights, k, delta=delta, scales=scales
        )
    return gather_rerank_topk_chunked(
        data, ids, queries, weights, k, delta=delta, scales=scales
    )


# The streamed early-exit tail merges (b, k + G·C) blocks per group — far
# smaller than a full-plan candidate tensor, but re-ranked once per
# while_loop iteration, so the chunked fori_loop's per-chunk bookkeeping is
# paid n_groups times over. The group entry therefore prefers the monolithic
# fusion up to a 2x wider footprint before falling back to chunking.
GROUP_MONOLITH_BYTES = 2 * MONOLITH_BYTES


def gather_rerank_topk_group(
    data: jax.Array,
    ids: jax.Array,
    queries: jax.Array,
    weights: jax.Array,
    k: int,
    delta: jax.Array | None = None,
    scales: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Group-sized fused tail for the streamed early-exit loop: the same
    contract (and bit-identical selection — both schedules are tested
    equal) as :func:`gather_rerank_topk_auto`, with the monolith/chunked
    crossover moved to ``GROUP_MONOLITH_BYTES`` because the caller invokes
    it once per while_loop iteration on heap+group-sized blocks."""
    b, P = ids.shape
    d = data.shape[1]
    working_set = b * P * d * 4 * (3 if delta is not None else 1)
    if working_set <= GROUP_MONOLITH_BYTES:
        return _gather_rerank_topk_monolith(
            data, ids, queries, weights, k, delta=delta, scales=scales
        )
    return gather_rerank_topk_chunked(
        data, ids, queries, weights, k, delta=delta, scales=scales
    )


@functools.partial(jax.jit, static_argnames=("k", "chunk"))
def gather_rerank_topk_chunked(
    data: jax.Array,
    ids: jax.Array,
    queries: jax.Array,
    weights: jax.Array,
    k: int,
    chunk: int = 256,
    delta: jax.Array | None = None,
    scales: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Pure-jnp fused tail (CPU production path): chunked gather → re-rank →
    streaming top-k merge. Never materializes the (b, P, d) tensor.

    Chunks whose every id is the invalid sentinel are skipped entirely
    (a cheap predicate guards the gather + reduction) — with the dedupe
    stage packing unique ids first, the loop does O(#unique) work however
    large the L·C probe budget is. With ``delta``, each chunk gathers from
    whichever segment owns each id (virtual concatenation, never built).

    Quantized payloads stay encoded at rest: the gather moves rows in the
    STORED dtype and each chunk is decoded (widen + ``* scales``) right
    before its re-rank, so only (b, chunk, d) f32 values ever exist. For
    f32 data the decode is an identity cast — bit-identical to gathering
    from a pre-cast table."""
    n_main, d = data.shape
    cap = 0 if delta is None else delta.shape[0]
    n = n_main + cap
    b, P = ids.shape
    pc = -P % chunk
    ids_p = jnp.pad(ids.astype(jnp.int32), ((0, 0), (0, pc)), constant_values=n)
    n_chunks = ids_p.shape[1] // chunk
    q = queries.astype(jnp.float32)
    w = weights.astype(jnp.float32)
    # delta rows round through the main table's dtype (same cast every other
    # schedule applies) so mixed-dtype segments rerank identically
    delta_e = None if delta is None else delta.astype(data.dtype)

    def decode(pts):  # (b, chunk, d) stored-dtype rows -> f32 rows
        pts = pts.astype(jnp.float32)
        if scales is not None:
            pts = pts * scales
        return pts

    def gather(cid):  # (b, chunk) ids -> (b, chunk, d) encoded rows
        if delta_e is None:
            return data[jnp.minimum(cid, n - 1)]

        # dedupe packs ids ascending, so most chunks live entirely in one
        # segment — branch to a single gather there and pay the two-gather
        # select only on the (rare) boundary chunk. All branches produce
        # identical rows for every valid id (invalid ids clamp to the same
        # row and are masked to +inf downstream), so the specialization
        # cannot change results.
        def main_only(_):
            return data[jnp.minimum(cid, n_main - 1)]

        def delta_only(_):
            return delta_e[jnp.clip(cid - n_main, 0, cap - 1)]

        def mixed(_):
            return jnp.where((cid < n_main)[..., None], main_only(None), delta_only(None))

        in_main = cid < n_main
        return jax.lax.cond(
            jnp.all(in_main),
            main_only,
            lambda _: jax.lax.cond(jnp.any(in_main), mixed, delta_only, None),
            None,
        )

    def body(c, carry):
        cid = jax.lax.dynamic_slice_in_dim(ids_p, c * chunk, chunk, axis=1)  # (b, chunk)
        valid = cid < n

        def compute(carry):
            top_d, top_i = carry
            pts = decode(gather(cid))  # (b, chunk, d)
            dists = jnp.sum(w[:, None, :] * jnp.abs(pts - q[:, None, :]), axis=-1)
            dists = jnp.where(valid, dists, jnp.inf)
            cand_d = jnp.concatenate([top_d, dists], axis=1)
            cand_i = jnp.concatenate([top_i, jnp.where(valid, cid, -1)], axis=1)
            neg, sel = jax.lax.top_k(-cand_d, top_d.shape[1])
            return -neg, jnp.take_along_axis(cand_i, sel, axis=1)

        return jax.lax.cond(jnp.any(valid), compute, lambda cr: cr, carry)

    kk = max(1, min(k, P))
    top_d = jnp.full((b, kk), jnp.inf, jnp.float32)
    top_i = jnp.full((b, kk), -1, jnp.int32)
    top_d, top_i = jax.lax.fori_loop(0, n_chunks, body, (top_d, top_i))
    if top_d.shape[1] < k:
        top_d = jnp.pad(top_d, ((0, 0), (0, k - top_d.shape[1])), constant_values=jnp.inf)
        top_i = jnp.pad(top_i, ((0, 0), (0, k - top_i.shape[1])), constant_values=-1)
    return top_d[:, :k], jnp.where(jnp.isfinite(top_d[:, :k]), top_i[:, :k], -1)
