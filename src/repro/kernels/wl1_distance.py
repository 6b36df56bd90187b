"""Pallas TPU kernels for exact generalized weighted Manhattan distance.

Two entry points:

  * ``wl1_scan``   — brute-force scan: data (n, d) × queries (b, d) -> (b, n).
    The linear-scan baseline the paper's sublinear scheme is measured against,
    and the building block of the distributed exact re-rank.
  * ``wl1_rerank`` — candidate re-rank: pts (b, C, d) × queries -> (b, C).
    The tail of every ALSH probe.

|o - q| has no MXU form on raw floats, so these are VPU kernels: blocked
elementwise |diff| * w with an in-register reduction over a d-chunk grid axis.
Data tiles are reused across the query-block dimension (the data tile is
loaded once per (query-block, d-chunk) step), giving O(bq) arithmetic
intensity per byte of data traffic.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BQ = 8  # queries per block (scan)
BNV = 128  # data rows per block
BDV = 256  # coordinates per reduction step
BC = 128  # candidates per block (rerank)


def _scan_kernel(data_ref, q_ref, w_ref, out_ref):
    kd = pl.program_id(2)
    data = data_ref[...]  # (BNV, BDV)
    q = q_ref[...]  # (BQ, BDV)
    w = w_ref[...]  # (BQ, BDV)
    diff = jnp.abs(data[None, :, :] - q[:, None, :])  # (BQ, BNV, BDV)
    partial = jnp.sum(w[:, None, :] * diff, axis=-1)  # (BQ, BNV)

    @pl.when(kd == 0)
    def _init():
        out_ref[...] = partial

    @pl.when(kd != 0)
    def _accum():
        out_ref[...] += partial


@functools.partial(jax.jit, static_argnames=("interpret",))
def wl1_scan_pallas(
    data: jax.Array, queries: jax.Array, weights: jax.Array, *, interpret: bool = False
) -> jax.Array:
    """data (n, d), queries (b, d), weights (b, d) -> (b, n) float32."""
    n, d = data.shape
    b, _ = queries.shape
    pn = -n % BNV
    pb = -b % BQ
    pd = -d % BDV
    # padded d-coords get w = 0 → contribute 0; padded rows/queries sliced away.
    data_p = jnp.pad(data.astype(jnp.float32), ((0, pn), (0, pd)))
    q_p = jnp.pad(queries.astype(jnp.float32), ((0, pb), (0, pd)))
    w_p = jnp.pad(weights.astype(jnp.float32), ((0, pb), (0, pd)))
    bp, dp = q_p.shape
    np_ = data_p.shape[0]
    grid = (bp // BQ, np_ // BNV, dp // BDV)
    out = pl.pallas_call(
        _scan_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((BNV, BDV), lambda i, j, k: (j, k)),
            pl.BlockSpec((BQ, BDV), lambda i, j, k: (i, k)),
            pl.BlockSpec((BQ, BDV), lambda i, j, k: (i, k)),
        ],
        out_specs=pl.BlockSpec((BQ, BNV), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((bp, np_), jnp.float32),
        interpret=interpret,
        name="wl1_distance_scan_pallas",
    )(data_p, q_p, w_p)
    return out[:b, :n]


def _rerank_kernel(pts_ref, q_ref, w_ref, out_ref):
    kd = pl.program_id(2)
    pts = pts_ref[...]  # (BC, BDV)
    q = q_ref[...]  # (1, BDV)
    w = w_ref[...]  # (1, BDV)
    diff = jnp.abs(pts - q)  # (BC, BDV)
    partial = jnp.sum(w * diff, axis=-1)[None, :]  # (1, BC)

    @pl.when(kd == 0)
    def _init():
        out_ref[...] = partial

    @pl.when(kd != 0)
    def _accum():
        out_ref[...] += partial


@functools.partial(jax.jit, static_argnames=("interpret",))
def wl1_rerank_pallas(
    pts: jax.Array, queries: jax.Array, weights: jax.Array, *, interpret: bool = False
) -> jax.Array:
    """pts (b, C, d), queries (b, d), weights (b, d) -> (b, C) float32.

    Per-query vectors ride a unit middle axis ((b, 1, ·) views, the leading
    block dim squeezed) so every block's trailing dims are TPU-tileable."""
    b, C, d = pts.shape
    pc = -C % BC
    pd = -d % BDV
    pts_p = jnp.pad(pts.astype(jnp.float32), ((0, 0), (0, pc), (0, pd)))
    q_p = jnp.pad(queries.astype(jnp.float32), ((0, 0), (0, pd)))[:, None, :]
    w_p = jnp.pad(weights.astype(jnp.float32), ((0, 0), (0, pd)))[:, None, :]
    cp = C + pc
    dp = d + pd
    grid = (b, cp // BC, dp // BDV)
    out = pl.pallas_call(
        _rerank_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, BC, BDV), lambda i, j, k: (i, j, k)),
            pl.BlockSpec((None, 1, BDV), lambda i, j, k: (i, 0, k)),
            pl.BlockSpec((None, 1, BDV), lambda i, j, k: (i, 0, k)),
        ],
        out_specs=pl.BlockSpec((None, 1, BC), lambda i, j, k: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((b, 1, cp), jnp.float32),
        interpret=interpret,
        name="wl1_distance_rerank_pallas",
    )(pts_p, q_p, w_p)
    return out[:, 0, :C]
