"""Pallas TPU kernel for the §4.2.3 ALSH projection.

The paper's O(d) trick makes hashing a table lookup:

    proj[n, h] = sum_i  w[n, i] * folded[h, i, levels[n, i]]

GPU/CPU implementations do per-element gathers. TPU adaptation (DESIGN.md §2):
the lookup over the last axis of a VMEM-resident table is reformulated as a
**one-hot contraction on the MXU** — for each d-chunk we build the one-hot of
the levels on the fly (broadcasted-iota compare, never touching HBM), fold the
query weights into the one-hot, and issue a dense

    (bn, dc*(M+1)) @ (dc*(M+1), bh)

matmul, accumulating over d-chunks via the innermost grid dimension. Tables
tile VMEM as (bh, dc, M+1); MXU dims (bn, bh) are 128-aligned by the wrapper.

The contraction runs at ``Precision.HIGHEST``: at the default precision the
MXU rounds float32 operands to bfloat16, which moves a projection by up to
2^-8 of its terms and flips the sign bit (theta) or bucket (l2) of the ones
near a boundary — keys a float32 implementation of the same hash does not
give. The one-hot is exact in bfloat16, but the tables and the weighted
one-hot of the query side are not.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Block sizes (MXU-aligned). d-chunk keeps the one-hot tile ~ bn*dc*(M+1)*4 B
# in VMEM: with bn=128, dc=128, M+1=33 that's ~2.1 MB; folded tile
# bh*dc*(M+1)*4 = 2.1 MB. The d-chunk is the lane dimension of the levels
# and weights blocks, so it must be a multiple of 128 (Mosaic tiles the last
# two block dims by (8, 128)).
BN = 128  # points per block
BH = 128  # hash functions per block
BD = 128  # coordinates per reduction step


def _project_kernel(levels_ref, weights_ref, folded_ref, out_ref, *, weighted: bool):
    """One (bn, bh) output tile; accumulates over the d-chunk grid axis."""
    kd = pl.program_id(2)

    levels = levels_ref[...]  # (BN, BD) int32
    m1 = folded_ref.shape[-1]
    # one-hot on the fly: (BN, BD, M+1)
    iota = jax.lax.broadcasted_iota(jnp.int32, (levels.shape[0], levels.shape[1], m1), 2)
    onehot = (iota == levels[:, :, None]).astype(folded_ref.dtype)
    if weighted:
        onehot = onehot * weights_ref[...][:, :, None].astype(folded_ref.dtype)

    lhs = onehot.reshape(levels.shape[0], -1)  # (BN, BD*(M+1))
    folded = folded_ref[...]  # (BH, BD, M+1)
    rhs = folded.reshape(folded.shape[0], -1)  # (BH, BD*(M+1))
    partial = jax.lax.dot_general(
        lhs,
        rhs,
        dimension_numbers=(((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )  # (BN, BH)

    @pl.when(kd == 0)
    def _init():
        out_ref[...] = partial

    @pl.when(kd != 0)
    def _accum():
        out_ref[...] += partial


@functools.partial(jax.jit, static_argnames=("interpret",))
def alsh_project_pallas(
    levels: jax.Array,
    folded: jax.Array,
    weights: jax.Array | None = None,
    *,
    interpret: bool = False,
) -> jax.Array:
    """Pallas entry point. levels (n, d) int32, folded (H, d, M+1) -> (n, H) f32.

    The wrapper pads every dim to block multiples (padded d-coords use level 0
    with zero table columns / zero weights, so they contribute exactly 0) and
    slices the result back.
    """
    n, d = levels.shape
    H, d2, m1 = folded.shape
    assert d == d2, (d, d2)
    weighted = weights is not None
    if not weighted:
        weights = jnp.ones((1, 1), jnp.float32)  # placeholder operand

    pn = -n % BN
    ph = -H % BH
    pd = -d % BD
    levels_p = jnp.pad(levels, ((0, pn), (0, pd)))
    folded_p = jnp.pad(folded, ((0, ph), (0, pd), (0, 0)))
    if weighted:
        weights_p = jnp.pad(weights.astype(jnp.float32), ((0, pn), (0, pd)))
    else:
        # broadcast placeholder to the padded point grid (never read as values
        # beyond masking; padded coords hit zero table columns anyway)
        weights_p = jnp.zeros((n + pn, d + pd), jnp.float32)

    np_, dp_ = levels_p.shape
    hp_ = folded_p.shape[0]
    grid = (np_ // BN, hp_ // BH, dp_ // BD)

    out = pl.pallas_call(
        functools.partial(_project_kernel, weighted=weighted),
        grid=grid,
        in_specs=[
            pl.BlockSpec((BN, BD), lambda i, j, k: (i, k)),
            pl.BlockSpec((BN, BD), lambda i, j, k: (i, k)),
            pl.BlockSpec((BH, BD, m1), lambda i, j, k: (j, k, 0)),
        ],
        out_specs=pl.BlockSpec((BN, BH), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((np_, hp_), jnp.float32),
        interpret=interpret,
        name="alsh_project_pallas",
    )(levels_p, weights_p, folded_p)
    return out[:n, :H]
