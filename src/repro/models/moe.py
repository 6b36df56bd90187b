"""Mixture-of-Experts (top-1 routing, llama4-style) with expert parallelism.

Capacity-based sorted dispatch (Switch/MaxText style, static shapes):

  1. route: top-1 expert per token (+ sigmoid gate, llama4 convention)
  2. sort tokens by expert id; position-in-expert via exclusive-cumsum offsets
  3. scatter into a (E, C, dm) buffer, C = capacity_factor * T/E — overflow
     tokens are dropped (their gate contribution is zero; the shared expert
     still sees them, so no token goes dark)
  4. batched expert FFN on (E, C, dm) with E sharded over "model" (EP) — under
     GSPMD this is the canonical all_to_all pair around expert compute
  5. gather back + unsort + gate; add the always-on shared expert

Memory: E*C*dm ≈ capacity_factor * T * dm — same order as activations,
sharded over (model, data). A shared (always-on) expert runs as a plain MLP
in parallel with the routed path (llama4's design).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec

from repro.configs.base import ModelConfig, MoEConfig
from repro.models import layers, mlp
from repro.models.sharding import BATCH, EP, FSDP, get_mesh, maybe_shard, resolve_entry


def init_moe(key, cfg: ModelConfig, mcfg: MoEConfig, dtype) -> dict:
    kr, ke1, ke2, ke3, ks = jax.random.split(key, 5)
    dm, dff, E = cfg.d_model, mcfg.d_ff_expert, mcfg.n_experts
    std_in, std_out = dm**-0.5, dff**-0.5
    p = {
        "router": layers.init_linear(kr, dm, E, dtype, std=0.02),
        "experts": {
            "w_up": layers.truncated_normal_init(ke1, (E, dm, dff), std_in, dtype),
            "w_gate": layers.truncated_normal_init(ke2, (E, dm, dff), std_in, dtype),
            "w_down": layers.truncated_normal_init(ke3, (E, dff, dm), std_out, dtype),
        },
    }
    if mcfg.n_shared:
        p["shared"] = mlp.init_mlp(ks, dm, mcfg.d_ff_expert * mcfg.n_shared, "swiglu", dtype)
    return p


def moe_specs(mcfg: MoEConfig, impl: str = "gspmd") -> dict:
    P = jax.sharding.PartitionSpec
    # Both impls STORE experts 2-D sharded (EP x FSDP): grads/moments stay
    # (E/ep)/(data)-sharded — storing EP-only would leave ~48 GB/device of
    # expert grads on llama4-maverick (measured; see EXPERIMENTS §Perf). The
    # ep_shardmap path all-gathers the weights over FSDP transiently at the
    # shard_map boundary; the gather's transpose reduce-scatters the grads.
    experts = {
        "w_up": P(EP, FSDP, None),
        "w_gate": P(EP, FSDP, None),
        "w_down": P(EP, None, FSDP),
    }
    p = {"router": layers.linear_specs(None, None), "experts": experts}
    if mcfg.n_shared:
        p["shared"] = mlp.mlp_specs("swiglu")
    return p


def _capacity(T: int, E: int, factor: float) -> int:
    c = int(factor * T / E) + 1
    return max(8, min(c, T))


def _dispatch_compute_combine(xf, router_logits, we, E, C, E_offset=0):
    """Shared core: sorted capacity dispatch -> expert FFN -> combine.

    xf (T, dm); router_logits (T, E_total) float32; we holds (E, dm, dff)
    weight stacks for the E LOCAL experts starting at global id E_offset.
    Tokens routed outside [E_offset, E_offset+E) are dropped here (handled by
    other ranks under EP). Returns (T, dm) routed output (gated).
    """
    T, dm = xf.shape
    expert_global = jnp.argmax(router_logits, axis=-1).astype(jnp.int32)  # (T,)
    gate = jax.nn.sigmoid(jnp.max(router_logits, axis=-1))  # (T,)
    local = expert_global - E_offset
    mine = (local >= 0) & (local < E)
    local = jnp.where(mine, local, E)  # foreign tokens -> virtual expert E

    sort_idx = jnp.argsort(local)  # (T,) stable; foreign tokens sort last
    sorted_expert = local[sort_idx]
    counts = jnp.sum(jax.nn.one_hot(local, E + 1, dtype=jnp.int32), axis=0)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)[:-1]])
    pos_in_expert = jnp.arange(T, dtype=jnp.int32) - offsets[jnp.minimum(sorted_expert, E)]
    keep = (pos_in_expert < C) & (sorted_expert < E)
    safe_pos = jnp.where(keep, pos_in_expert, C - 1)
    safe_exp = jnp.minimum(sorted_expert, E - 1)

    buf = jnp.zeros((E, C, dm), xf.dtype)
    xs = xf[sort_idx] * keep[:, None].astype(xf.dtype)
    buf = buf.at[safe_exp, safe_pos].add(xs)

    up = jnp.einsum("ecd,edf->ecf", buf, we["w_up"].astype(xf.dtype))
    gt = jnp.einsum("ecd,edf->ecf", buf, we["w_gate"].astype(xf.dtype))
    h = jax.nn.silu(gt) * up
    down = jnp.einsum("ecf,efd->ecd", h, we["w_down"].astype(xf.dtype))  # (E, C, dm)

    gathered = down[safe_exp, safe_pos] * keep[:, None].astype(xf.dtype)
    inv = jnp.argsort(sort_idx)
    return gathered[inv] * gate[:, None].astype(xf.dtype)


def moe_ffn_ep_shardmap(params: dict, x: jax.Array, cfg: ModelConfig,
                        mcfg: MoEConfig) -> jax.Array:
    """Explicit expert parallelism (perf lever, DESIGN.md + EXPERIMENTS §Perf).

    Activations stay replicated across the EP ("model") axis (they are batch-
    sharded over ("pod","data") only — the megatron layout); each EP rank
    dispatches the SAME token set to its local E/ep experts and a single psum
    combines partial outputs. Collectives per MoE layer: ONE all-reduce of
    (T_local, dm) — versus the GSPMD scatter/gather fallback that replicated
    full dispatch buffers (measured 5.3 TiB of all-reduce per step on
    llama4-maverick; see EXPERIMENTS §Perf).
    """
    mesh = get_mesh()
    ep_axis = resolve_entry(EP)
    if mesh is None or ep_axis not in mesh.axis_names:
        return moe_ffn_gspmd(params, x, cfg, mcfg)
    ep = mesh.shape[ep_axis]
    B, S, dm = x.shape
    E = mcfg.n_experts
    assert E % ep == 0, (E, ep)
    E_local = E // ep

    # greedy divisibility degradation (mirror of sharding.sanitize_spec):
    # keep the batch-axis prefix whose product divides B (e.g. global_batch 32
    # on a 16x16 mesh under dp_over_model -> batch over ("data",) only)
    batch_axes = []
    prod = 1
    for a in resolve_entry(BATCH) or ():
        if a in mesh.axis_names and B % (prod * mesh.shape[a]) == 0:
            batch_axes.append(a)
            prod *= mesh.shape[a]
    batch_axes = tuple(batch_axes)
    P = PartitionSpec
    # Two data layouts:
    #  * megatron (ep_axis NOT in batch): x replicated over EP — dispatch the
    #    same token set per rank, psum partial outputs.
    #  * dp_over_model (ep_axis IN batch): x batch-sharded over EP too —
    #    all_gather tokens over EP, dispatch, then psum_scatter the combined
    #    outputs back to each rank's slice (half the bytes of AG+psum).
    gather_tokens = ep_axis in batch_axes
    # tokens visible to one rank's dispatch = batch shard WITHOUT the ep axis
    n_batch_shards = 1
    for a in batch_axes:
        if a != ep_axis:
            n_batch_shards *= mesh.shape[a]
    T = max(B // n_batch_shards, 1) * S
    C = _capacity(T, E, mcfg.capacity_factor)

    def local_fn(router_w, we_up, we_gate, we_down, xl):
        if gather_tokens:
            xl = jax.lax.all_gather(xl, ep_axis, axis=0, tiled=True)  # (Bl*ep, S, dm)
        Bg = xl.shape[0]
        xf = xl.reshape(Bg * S, dm)
        router_logits = (xf @ router_w.astype(xf.dtype)).astype(jnp.float32)
        rank = jax.lax.axis_index(ep_axis)
        we = {"w_up": we_up, "w_gate": we_gate, "w_down": we_down}
        routed = _dispatch_compute_combine(
            xf, router_logits, we, E_local, C, E_offset=rank * E_local
        )
        routed = routed.reshape(Bg, S, dm)
        if gather_tokens:
            return jax.lax.psum_scatter(routed, ep_axis, scatter_dimension=0,
                                        tiled=True)  # (Bl, S, dm)
        return jax.lax.psum(routed, ep_axis)

    fn = shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(
            P(),  # router replicated
            P(ep_axis), P(ep_axis), P(ep_axis),  # experts over EP (gathered over FSDP)
            P(batch_axes, None, None),
        ),
        out_specs=P(batch_axes, None, None),
        check_vma=False,
    )
    we = params["experts"]
    # transient FSDP gather (storage stays (EP x FSDP)-sharded; see moe_specs)
    w_up = maybe_shard(we["w_up"], EP, None, None)
    w_gate = maybe_shard(we["w_gate"], EP, None, None)
    w_down = maybe_shard(we["w_down"], EP, None, None)
    routed = fn(params["router"]["w"], w_up, w_gate, w_down, x)

    out = routed
    if "shared" in params:
        xf = x.reshape(B * S, dm)
        out = out + mlp.mlp(params["shared"], xf, "swiglu").reshape(B, S, dm)
    return maybe_shard(out, BATCH, None, None)


def _dispatch_by_ids(xf, local_ids, we, E, C):
    """Expert FFN for tokens with PRE-ASSIGNED local expert ids (a2a receive
    side). local_ids (T,) in [0, E) or -1 (invalid/padding). Returns (T, dm)
    outputs (zeros for invalid/dropped)."""
    T, dm = xf.shape
    valid = local_ids >= 0
    local = jnp.where(valid, local_ids, E)
    sort_idx = jnp.argsort(local)
    sorted_expert = local[sort_idx]
    counts = jnp.sum(jax.nn.one_hot(local, E + 1, dtype=jnp.int32), axis=0)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(T, dtype=jnp.int32) - offsets[jnp.minimum(sorted_expert, E)]
    keep = (pos < C) & (sorted_expert < E)
    safe_pos = jnp.where(keep, pos, C - 1)
    safe_exp = jnp.minimum(sorted_expert, E - 1)

    buf = jnp.zeros((E, C, dm), xf.dtype)
    buf = buf.at[safe_exp, safe_pos].add(xf[sort_idx] * keep[:, None].astype(xf.dtype))
    up = jnp.einsum("ecd,edf->ecf", buf, we["w_up"].astype(xf.dtype))
    gt = jnp.einsum("ecd,edf->ecf", buf, we["w_gate"].astype(xf.dtype))
    h = jax.nn.silu(gt) * up
    down = jnp.einsum("ecf,efd->ecd", h, we["w_down"].astype(xf.dtype))
    out_sorted = down[safe_exp, safe_pos] * keep[:, None].astype(xf.dtype)
    return out_sorted[jnp.argsort(sort_idx)]


def moe_ffn_a2a_shardmap(params: dict, x: jax.Array, cfg: ModelConfig,
                         mcfg: MoEConfig) -> jax.Array:
    """TRUE all-to-all expert parallelism (beyond-paper, EXPERIMENTS §Perf).

    Tokens are batch-sharded over the EP axis too (requires dp_over_model);
    each rank routes its tokens, exchanges them with the owning expert ranks
    via all_to_all (per-peer capacity Cp), computes its local experts, and
    all_to_alls the outputs back. Expert weights never move; token traffic is
    2·capacity_factor·T_local·dm per layer — constant in model size, the
    layout that scales past the weight-gather floor of gather-EP.
    """
    mesh = get_mesh()
    ep_axis = resolve_entry(EP)
    if mesh is None or ep_axis not in mesh.axis_names:
        return moe_ffn_gspmd(params, x, cfg, mcfg)
    ep = mesh.shape[ep_axis]
    B, S, dm = x.shape
    E = mcfg.n_experts
    assert E % ep == 0, (E, ep)
    E_local = E // ep

    batch_axes = []
    prod = 1
    for a in resolve_entry(BATCH) or ():
        if a in mesh.axis_names and B % (prod * mesh.shape[a]) == 0:
            batch_axes.append(a)
            prod *= mesh.shape[a]
    batch_axes = tuple(batch_axes)
    if ep_axis not in batch_axes:
        # tokens are replicated over EP: a2a degenerates — use gather-EP path
        return moe_ffn_ep_shardmap(params, x, cfg, mcfg)

    T_l = (B // prod) * S  # tokens per rank
    Cp = max(8, int(mcfg.capacity_factor * T_l / ep) + 1)  # per-peer slots
    C2 = max(8, int(mcfg.capacity_factor * ep * Cp / E_local) + 1)  # per-expert
    P = PartitionSpec

    def local_fn(router_w, we_up, we_gate, we_down, xl):
        Bl = xl.shape[0]
        xf = xl.reshape(Bl * S, dm)
        T = xf.shape[0]
        logits = (xf @ router_w.astype(xf.dtype)).astype(jnp.float32)
        expert_global = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        gate = jax.nn.sigmoid(jnp.max(logits, axis=-1))
        target = expert_global // E_local  # owning rank per token

        # --- pack send buffers: (ep, Cp, dm) + local-expert ids -------------
        sidx = jnp.argsort(target)
        st = target[sidx]
        counts = jnp.sum(jax.nn.one_hot(target, ep, dtype=jnp.int32), axis=0)
        offs = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)[:-1]])
        pos = jnp.arange(T, dtype=jnp.int32) - offs[st]
        keep = pos < Cp
        safe_pos = jnp.where(keep, pos, Cp - 1)
        sbuf = jnp.zeros((ep, Cp, dm), xf.dtype)
        sbuf = sbuf.at[st, safe_pos].add(
            xf[sidx] * keep[:, None].astype(xf.dtype)
        )
        smeta = jnp.full((ep, Cp), -1, jnp.int32)
        smeta = smeta.at[st, safe_pos].set(
            jnp.where(keep, expert_global[sidx] % E_local, -1)
        )

        # --- exchange, compute, exchange back --------------------------------
        rbuf = jax.lax.all_to_all(sbuf, ep_axis, 0, 0, tiled=True)
        rmeta = jax.lax.all_to_all(smeta[..., None], ep_axis, 0, 0, tiled=True)[..., 0]
        we = {"w_up": we_up, "w_gate": we_gate, "w_down": we_down}
        y = _dispatch_by_ids(rbuf.reshape(ep * Cp, dm), rmeta.reshape(ep * Cp),
                             we, E_local, C2)
        ybuf = jax.lax.all_to_all(y.reshape(ep, Cp, dm), ep_axis, 0, 0, tiled=True)

        # --- unpack at source -------------------------------------------------
        back_sorted = ybuf[st, safe_pos] * keep[:, None].astype(xf.dtype)
        routed = back_sorted[jnp.argsort(sidx)] * gate[:, None].astype(xf.dtype)
        return routed.reshape(Bl, S, dm)

    fn = shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P(), P(ep_axis), P(ep_axis), P(ep_axis), P(batch_axes, None, None)),
        out_specs=P(batch_axes, None, None),
        check_vma=False,
    )
    we = params["experts"]
    w_up = maybe_shard(we["w_up"], EP, None, None)
    w_gate = maybe_shard(we["w_gate"], EP, None, None)
    w_down = maybe_shard(we["w_down"], EP, None, None)
    routed = fn(params["router"]["w"], w_up, w_gate, w_down, x)

    out = routed
    if "shared" in params:
        xf = x.reshape(B * S, dm)
        out = out + mlp.mlp(params["shared"], xf, "swiglu").reshape(B, S, dm)
    return maybe_shard(out, BATCH, None, None)


def moe_ffn(params: dict, x: jax.Array, cfg: ModelConfig, mcfg: MoEConfig) -> jax.Array:
    """x (B, S, dm) -> (B, S, dm). Top-1 routed + shared expert (impl lever)."""
    if cfg.moe_impl == "a2a_shardmap":
        return moe_ffn_a2a_shardmap(params, x, cfg, mcfg)
    if cfg.moe_impl == "ep_shardmap":
        return moe_ffn_ep_shardmap(params, x, cfg, mcfg)
    return moe_ffn_gspmd(params, x, cfg, mcfg)


def moe_ffn_gspmd(params: dict, x: jax.Array, cfg: ModelConfig, mcfg: MoEConfig) -> jax.Array:
    """GSPMD-auto dispatch (paper-faithful baseline path)."""
    B, S, dm = x.shape
    E = mcfg.n_experts
    T = B * S
    C = _capacity(T, E, mcfg.capacity_factor)
    xf = x.reshape(T, dm)

    router_logits = layers.linear(params["router"], xf).astype(jnp.float32)  # (T, E)
    expert_idx = jnp.argmax(router_logits, axis=-1).astype(jnp.int32)  # (T,)
    gate = jax.nn.sigmoid(jnp.max(router_logits, axis=-1))  # (T,) llama4 top-1 gate

    # --- sorted capacity dispatch -------------------------------------------
    sort_idx = jnp.argsort(expert_idx)  # (T,) stable
    sorted_expert = expert_idx[sort_idx]
    counts = jnp.sum(jax.nn.one_hot(expert_idx, E, dtype=jnp.int32), axis=0)  # (E,)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)[:-1]])
    pos_in_expert = jnp.arange(T, dtype=jnp.int32) - offsets[sorted_expert]  # (T,)
    keep = pos_in_expert < C
    safe_pos = jnp.where(keep, pos_in_expert, C - 1)

    buf = jnp.zeros((E, C, dm), x.dtype)
    xs = xf[sort_idx] * keep[:, None].astype(x.dtype)
    buf = buf.at[sorted_expert, safe_pos].add(xs)  # dropped tokens add 0 to slot C-1
    buf = maybe_shard(buf, EP, None, None)  # experts over model axis (EP)

    # --- expert FFN (batched over local experts) ----------------------------
    we = params["experts"]
    up = jnp.einsum("ecd,edf->ecf", buf, we["w_up"].astype(x.dtype))
    gt = jnp.einsum("ecd,edf->ecf", buf, we["w_gate"].astype(x.dtype))
    h = jax.nn.silu(gt) * up
    down = jnp.einsum("ecf,efd->ecd", h, we["w_down"].astype(x.dtype))  # (E, C, dm)
    down = maybe_shard(down, EP, None, None)

    # --- combine: gather back, unsort, gate ---------------------------------
    gathered = down[sorted_expert, safe_pos]  # (T, dm) in sorted order
    gathered = gathered * keep[:, None].astype(x.dtype)
    inv = jnp.argsort(sort_idx)
    routed = gathered[inv] * gate[:, None].astype(x.dtype)

    out = routed
    if "shared" in params:
        out = out + mlp.mlp(params["shared"], xf, "swiglu")
    out = out.reshape(B, S, dm)
    return maybe_shard(out, BATCH, None, None)


def aux_load_balance_loss(router_logits: jax.Array, E: int) -> jax.Array:
    """Switch-style load-balance auxiliary (exposed for the training loss)."""
    probs = jax.nn.softmax(router_logits, axis=-1)
    expert_idx = jnp.argmax(router_logits, axis=-1)
    frac_tokens = jnp.mean(jax.nn.one_hot(expert_idx, E), axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    return E * jnp.sum(frac_tokens * frac_probs)
