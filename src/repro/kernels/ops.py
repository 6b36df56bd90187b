"""jit'd dispatch wrappers for the Pallas kernels.

Production call sites go through these. Dispatch policy:

  * TPU backend          -> compiled Pallas kernels.
  * CPU/other backends   -> pure-jnp implementations: the ref.py oracles for
                            the elementwise kernels, and the *chunked
                            streaming* variants for the fused top-k paths
                            (same fusion, cache-sized working set); tests
                            separately exercise the Pallas bodies with
                            interpret=True to validate them on CPU.

Override with ``force="pallas" | "ref" | "interpret" | "chunked"`` for
benchmarking (``chunked`` only exists for the fused top-k ops).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import alsh_project as _proj
from repro.kernels import gather_rerank as _gr
from repro.kernels import ref as _ref
from repro.kernels import wl1_distance as _wl1
from repro.kernels import wl1_topk as _topk


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def alsh_project(
    levels: jax.Array,
    folded: jax.Array,
    weights: jax.Array | None = None,
    force: str | None = None,
) -> jax.Array:
    """§4.2.3 hash projection: (n, d) levels × (H, d, M+1) tables -> (n, H)."""
    mode = force or ("pallas" if _on_tpu() else "ref")
    if mode == "pallas":
        return _proj.alsh_project_pallas(levels, folded, weights)
    if mode == "interpret":
        return _proj.alsh_project_pallas(levels, folded, weights, interpret=True)
    return _ref.alsh_project(levels, folded, weights)


def wl1_scan(
    data: jax.Array,
    queries: jax.Array,
    weights: jax.Array,
    force: str | None = None,
) -> jax.Array:
    """Exact brute-force scan: (n, d) × (b, d) -> (b, n) (materializing)."""
    mode = force or ("pallas" if _on_tpu() else "ref")
    if mode == "pallas":
        return _wl1.wl1_scan_pallas(data, queries, weights)
    if mode == "interpret":
        return _wl1.wl1_scan_pallas(data, queries, weights, interpret=True)
    return _ref.wl1_scan(data, queries, weights)


def wl1_rerank(
    pts: jax.Array,
    queries: jax.Array,
    weights: jax.Array,
    force: str | None = None,
) -> jax.Array:
    """Candidate re-rank: (b, C, d) × (b, d) -> (b, C)."""
    mode = force or ("pallas" if _on_tpu() else "ref")
    if mode == "pallas":
        return _wl1.wl1_rerank_pallas(pts, queries, weights)
    if mode == "interpret":
        return _wl1.wl1_rerank_pallas(pts, queries, weights, interpret=True)
    return _ref.wl1_rerank(pts, queries, weights)


def wl1_scan_topk(
    data: jax.Array,
    queries: jax.Array,
    weights: jax.Array,
    k: int,
    force: str | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Streaming exact k-NN scan: (n, d) × (b, d) -> ((b, k), (b, k)) without
    the (b, n) distance matrix."""
    mode = force or ("pallas" if _on_tpu() else "chunked")
    if mode == "pallas":
        return _topk.wl1_scan_topk_pallas(data, queries, weights, k)
    if mode == "interpret":
        return _topk.wl1_scan_topk_pallas(data, queries, weights, k, interpret=True)
    if mode == "chunked":
        return _topk.wl1_scan_topk_chunked(data, queries, weights, k)
    return _ref.wl1_scan_topk(data, queries, weights, k)


def wl1_scan_topk_merge_share(
    data: jax.Array,
    queries: jax.Array,
    weights: jax.Array,
    k: int,
    force: str | None = None,
) -> jax.Array:
    """Share of (query block, row block) tiles whose top-k merge ran in the
    Pallas exact scan: merges / (query blocks × row blocks), a float32
    scalar. ``force="interpret"`` reads it off the chip."""
    mode = force or ("pallas" if _on_tpu() else "interpret")
    _, _, merges = _topk.wl1_scan_topk_pallas(
        data, queries, weights, k, interpret=mode == "interpret", count_merges=True
    )
    row_blocks = -(-data.shape[0] // _topk.BNV)
    return merges.sum() / jnp.float32(merges.shape[0] * row_blocks)


def gather_rerank_topk(
    data: jax.Array,
    ids: jax.Array,
    queries: jax.Array,
    weights: jax.Array,
    k: int,
    force: str | None = None,
    delta: jax.Array | None = None,
    scales: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Fused ALSH probe tail: (n, d) table + (b, P) candidate ids (>= n ⇒
    invalid) -> top-k ((b, k) dists, (b, k) ids) with no materialized
    (b, P, d) gather. CPU auto-dispatch picks monolithic vs chunked
    streaming by candidate-tensor footprint.

    With ``delta`` (cap, d), ids address the virtual [data; delta]
    concatenation (two-segment mutable index) — every backend gathers from
    whichever segment owns each id instead of building the concatenated
    table; results are bit-identical to the single-table call over
    ``concat([data, delta])``.

    ``data``/``delta`` may hold a quantized payload (bf16/int8 — see
    repro.quant): every backend gathers the ENCODED rows and decodes per
    candidate (widen to f32, then ``* scales`` when the codec stores them)
    before the re-rank. f32 payloads with no scales take the exact
    pre-quantization code paths."""
    mode = force or ("pallas" if _on_tpu() else "auto")
    if mode == "pallas":
        return _gr.gather_rerank_topk_pallas(
            data, ids, queries, weights, k, delta=delta, scales=scales
        )
    if mode == "interpret":
        return _gr.gather_rerank_topk_pallas(
            data, ids, queries, weights, k, delta=delta, scales=scales, interpret=True
        )
    if mode == "auto":
        return _gr.gather_rerank_topk_auto(
            data, ids, queries, weights, k, delta=delta, scales=scales
        )
    if mode == "chunked":
        return _gr.gather_rerank_topk_chunked(
            data, ids, queries, weights, k, delta=delta, scales=scales
        )
    if delta is None:
        return _ref.gather_rerank_topk(data, ids, queries, weights, k, scales=scales)
    return _ref.gather_rerank_topk_segmented(
        data, delta, ids, queries, weights, k, scales=scales
    )


def gather_rerank_topk_group(
    data: jax.Array,
    ids: jax.Array,
    queries: jax.Array,
    weights: jax.Array,
    k: int,
    force: str | None = None,
    delta: jax.Array | None = None,
    scales: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Fused tail entry for GROUP-sized candidate blocks — the per-iteration
    merge of the streamed early-exit loop (repro.engine.stream). Identical
    id/sentinel/top-k contract to :func:`gather_rerank_topk`; on CPU the
    dispatch crossover is widened (see ``gather_rerank.GROUP_MONOLITH_BYTES``)
    so the small heap+group blocks stay in the monolithic fusion instead of
    paying the chunked schedule's bookkeeping once per while_loop step."""
    mode = force or ("pallas" if _on_tpu() else "group")
    if mode == "group":
        return _gr.gather_rerank_topk_group(
            data, ids, queries, weights, k, delta=delta, scales=scales
        )
    return gather_rerank_topk(
        data, ids, queries, weights, k, force=mode, delta=delta, scales=scales
    )
