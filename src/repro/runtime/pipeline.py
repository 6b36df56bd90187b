"""Pipeline parallelism (gpipe-style) over a named mesh axis.

The generic engine: ``n_stages`` devices along ``axis`` each hold one stage's
parameters (leading stage dim sharded to size 1 locally). Microbatches enter
stage 0; activations advance one stage per tick via ``ppermute``; after
``n_micro + n_stages - 1`` ticks every microbatch has exited the last stage.
Bubble fraction = (P-1)/(n_micro+P-1) — the standard gpipe trade.

Differentiable end-to-end: ppermute's transpose is the reverse permutation,
so ``jax.grad`` through ``pipeline_apply`` yields exact pipelined backward
(tested against the sequential reference in tests/test_pipeline.py).

In the production mesh the "pod" axis is configured as DP for the dry-run
cells (both lower identically); this engine is the PP alternative for
pod-crossing training where DCN bandwidth can't carry full gradient
reduce-scatters — activations-only traffic scales with microbatch size, not
model size.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map


def pipeline_apply(
    stage_fn: Callable,  # (stage_params, x) -> y   (same pytree/shape both sides)
    stage_params,  # pytree, leading dim = n_stages
    x_micro: jax.Array,  # (n_micro, mb, ...) inputs for stage 0
    mesh: Mesh,
    axis: str = "pod",
):
    """Returns (n_micro, mb, ...) outputs of the final stage (replicated)."""
    n_stages = mesh.shape[axis]
    n_micro = x_micro.shape[0]
    n_ticks = n_micro + n_stages - 1
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def local(params_local, xs):
        params0 = jax.tree.map(lambda p: p[0], params_local)  # local stage params
        stage = jax.lax.axis_index(axis)
        buf0 = jnp.zeros_like(xs[0])

        def tick(buf, t):
            # stage 0 ingests microbatch t (clipped; bubbles feed zeros)
            mb_idx = jnp.clip(t, 0, n_micro - 1)
            inj = jax.lax.dynamic_index_in_dim(xs, mb_idx, 0, keepdims=False)
            x_in = jnp.where(stage == 0, inj, buf)
            y = stage_fn(params0, x_in)
            nxt = jax.lax.ppermute(y, axis, perm)
            return nxt, y

        _, ys = jax.lax.scan(tick, buf0, jnp.arange(n_ticks))  # (ticks, mb, ...)
        # microbatch m exits the LAST stage at tick m + (P-1)
        outs = jax.lax.dynamic_slice_in_dim(ys, n_stages - 1, n_micro, axis=0)
        # replicate final-stage outputs to every pipeline rank
        all_outs = jax.lax.all_gather(outs, axis)  # (P, n_micro, mb, ...)
        return all_outs[-1]

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False,
    )
    return fn(stage_params, x_micro)


def pipeline_loss(
    stage_fn: Callable,
    loss_fn: Callable,  # (y_final, target_micro) -> scalar (mean per microbatch)
    stage_params,
    x_micro: jax.Array,
    targets_micro,
    mesh: Mesh,
    axis: str = "pod",
):
    y = pipeline_apply(stage_fn, stage_params, x_micro, mesh, axis)
    losses = jax.vmap(loss_fn)(y, targets_micro)
    return jnp.mean(losses)
