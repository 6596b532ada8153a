"""Expert-parallel MoE under shard_map — DeepSpeed-MoE §5.2-5.3 on a TPU mesh.

Parallelism layout (DESIGN.md §4), mesh (pod, data=16, model=16):

  tokens   x   : P(('pod','data'), None, None)   — batch over pod×data
  router       : replicated
  expert wi/wo : P('data', None, 'model')        — EP over 'data' (=16),
                                                   expert-*slicing* over 'model'
  y            : P(('pod','data'), None, None)

The dispatch all-to-all runs over **'data' only** — i.e. only among devices
sharing the same tensor-parallel ('model') rank.  This is precisely the
paper's *parallelism-coordinated communication* (§5.3, Fig. 9): activations
are replicated across tensor-parallel ranks, so the a2a group size is
p/L (=16) instead of p (=256), and the expert-slicing reduction is a single
psum over 'model' afterwards.  Across pods, experts are replicated (pure DP),
matching the paper's "data parallelism across nodes" for inference scaling;
the hierarchical variant (parallel/collectives.py) factors the a2a instead.
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import FFNSpec, ModelConfig
from repro.core.dispatch import combine_dense, dispatch_dense
from repro.core.gating import expert_capacity, kept_counts, load_balance_loss, top_k_gating
from repro.parallel.sharding import get_mesh

EP_AXIS = "data"
TP_AXIS = "model"


def _bwd_cast(x):
    """When the bf16-backward perf toggle is on, pin the cotangent dtype to
    the primal dtype at the communication boundaries of the MoE block —
    combine_dense does f32 math whose cotangents would otherwise flow
    through the expert-slicing psum and both all-to-alls at 4 bytes/el
    (EXPERIMENTS.md §Perf, kimi-train iteration)."""
    from repro.models.transformer import BF16_BWD

    if BF16_BWD[0]:
        from repro.models.modules import grad_cast

        return grad_cast(x)
    return x


def _axis_in_mesh(mesh, name: str) -> bool:
    return name in mesh.axis_names


# NOTE (EXPERIMENTS.md §Perf, refuted hypothesis): sharding the token dim
# over the TP axis inside the MoE block ("sequence-parallel dispatch") would
# shrink the capacity buffers 16x, but it is INCOMPATIBLE with expert-slicing:
# the F-partial outputs psum'd over 'model' must correspond to the SAME
# tokens on every TP rank.  Fixing it requires either unsliced experts
# (16x expert memory — infeasible at 1T params) or an extra all-gather that
# returns the traffic.  Kept as a negative result.


# Cross-pod expert parallelism (paper §5.3 hierarchical all-to-all, Fig. 8):
# EP spans ('pod','data') = 32 shards, expert memory per pod halves, and the
# dispatch exchange runs as intra-pod a2a (fast ICI) + layout transform +
# inter-pod a2a (slow DCI).  Enabled via launch/dryrun --train-opt ep_pod.
EP_POD = [False]


def set_ep_pod(on: bool) -> None:
    EP_POD[0] = bool(on)


def _moe_body(cfg: ModelConfig, spec: FFNSpec, mesh, hier: bool, x_loc, router, wi, wg, wo):
    """Per-device body.  x_loc: [B_loc, S, D] (replicated over 'model').
    wi: [E_loc, D, F_loc], wo: [E_loc, F_loc, D]."""
    from repro.parallel.collectives import (
        hierarchical_all_to_all,
        hierarchical_all_to_all_back,
    )

    B_loc, S, D = x_loc.shape
    E = spec.num_experts
    K = spec.top_k
    ep = jax.lax.axis_size(EP_AXIS) * (jax.lax.axis_size("pod") if hier else 1)
    E_loc = E // ep
    T_loc = B_loc * S
    cap = expert_capacity(T_loc, E, K, spec.capacity_factor)

    xs = _bwd_cast(x_loc.reshape(T_loc, D))
    logits = xs.astype(jnp.float32) @ router
    g = top_k_gating(logits, K, cap)

    # Local scatter into [E, cap, D] buffers (dense mapping table, §5.4).
    buf = dispatch_dense(xs, g, cap, E)

    if hier:
        # two-stage hierarchical exchange: intra-pod ('data') then inter-pod
        recv = hierarchical_all_to_all(buf, EP_AXIS, "pod")
    else:
        # Coordinated all-to-all over the EP axis only (groups of size p/L).
        recv = jax.lax.all_to_all(buf, EP_AXIS, split_axis=0, concat_axis=1, tiled=True)
    recv = _bwd_cast(recv)
    # recv: [E_loc, ep*cap, D]

    # Expert-sliced grouped GEMMs; psum over 'model' completes the slicing.
    h = jnp.einsum("ecd,edf->ecf", recv, wi)
    if spec.act == "swiglu":
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", recv, wg)) * h
    elif spec.act == "gelu":
        h = jax.nn.gelu(h)
    else:
        h = jax.nn.relu(h)
    out = jnp.einsum("ecf,efd->ecd", h, wo)
    if _axis_in_mesh(mesh, TP_AXIS):
        out = jax.lax.psum(out, TP_AXIS)
    out = _bwd_cast(out)

    # Return all-to-all, then local combine.
    if hier:
        back = hierarchical_all_to_all_back(out, EP_AXIS, "pod")
    else:
        back = jax.lax.all_to_all(out, EP_AXIS, split_axis=1, concat_axis=0, tiled=True)
    back = _bwd_cast(back)
    y = combine_dense(back, g, cap, E).reshape(B_loc, S, D)

    # Global-batch load balance: pmean the per-expert stats (linear in the
    # tokens) across EP shards, THEN take the product — numerically identical
    # to the single-device dense path (per-shard losses averaged would not
    # be, the loss being nonlinear in f and P).
    from repro.core.gating import load_balance_stats

    f, p = load_balance_stats(g.probs, g.expert_idx, E)
    axes = [EP_AXIS] + (["pod"] if _axis_in_mesh(mesh, "pod") else [])
    f = jax.lax.pmean(f, tuple(axes))
    p = jax.lax.pmean(p, tuple(axes))
    aux = E * jnp.sum(f * p)
    # rows each expert receives: every shard's kept assignments, summed
    counts = jax.lax.psum(kept_counts(g, E), tuple(axes))
    return y, aux, counts


def _moe_body_allgather(cfg: ModelConfig, spec: FFNSpec, mesh, x_loc, router, wi, wg, wo):
    """Small-batch (decode) schedule: all-gather the few tokens across the EP
    axis, compute local experts on the full token set, reduce-scatter the
    combined output back.  Communication is O(tokens·D) per layer instead of
    O(E·capacity·D) — the capacity-padded a2a buffers that dominate the a2a
    schedule when tokens-per-shard ≪ experts (EXPERIMENTS.md §Perf, kimi
    decode iteration 1)."""
    B_loc, S, D = x_loc.shape
    E, K = spec.num_experts, spec.top_k
    ep = jax.lax.axis_size(EP_AXIS)
    E_loc = E // ep
    my_ep = jax.lax.axis_index(EP_AXIS)

    # gather all tokens in the EP group: [T_all, D]
    xs = x_loc.reshape(B_loc * S, D)
    x_all = jax.lax.all_gather(xs, EP_AXIS, axis=0, tiled=True)
    T_all = x_all.shape[0]

    logits = x_all.astype(jnp.float32) @ router
    cap = expert_capacity(T_all, E, K, spec.capacity_factor)
    g = top_k_gating(logits, K, cap)

    # keep only assignments routed to OUR experts; everything else -> trash row
    lo, hi = my_ep * E_loc, (my_ep + 1) * E_loc
    mine = (g.expert_idx >= lo) & (g.expert_idx < hi)
    g_local = g._replace(
        expert_idx=jnp.where(mine, g.expert_idx - lo, 0),
        keep=g.keep & mine,
        combine_w=jnp.where(mine, g.combine_w, 0.0),
    )
    buf = dispatch_dense(x_all, g_local, cap, E_loc)  # [E_loc, cap, D]

    h = jnp.einsum("ecd,edf->ecf", buf, wi)
    if spec.act == "swiglu":
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg)) * h
    elif spec.act == "gelu":
        h = jax.nn.gelu(h)
    else:
        h = jax.nn.relu(h)
    out = jnp.einsum("ecf,efd->ecd", h, wo)
    if _axis_in_mesh(mesh, TP_AXIS):
        out = jax.lax.psum(out, TP_AXIS)  # expert-slicing reduction

    y_partial = combine_dense(out, g_local, cap, E_loc)  # [T_all, D], partial
    # sum expert contributions across EP shards and return each shard its slice
    y = jax.lax.psum_scatter(y_partial, EP_AXIS, scatter_dimension=0, tiled=True)

    aux = load_balance_loss(g.probs, g.expert_idx, E)
    # numerically identical on every EP shard (computed from the gathered
    # token set); the pmean just certifies replication for shard_map's vma.
    axes = [EP_AXIS] + (["pod"] if _axis_in_mesh(mesh, "pod") else [])
    aux = jax.lax.pmean(aux, tuple(axes))
    # rows each expert receives: this shard's experts' kept rows, placed at
    # their global ids and summed over the EP shards (and the pods' tokens)
    counts = jax.lax.dynamic_update_slice(
        jnp.zeros((E,), jnp.int32), kept_counts(g_local, E_loc), (lo,))
    counts = jax.lax.psum(counts, tuple(axes))
    return y.reshape(B_loc, S, D), aux, counts


def moe_layer_ep(cfg: ModelConfig, spec: FFNSpec, params: dict,
                 x: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``(y, aux, counts)``: ``counts`` [E] int32 is the rows the dispatch
    gave each expert over the whole batch (read by the routed-expert
    counter; unused, it is dropped with its psum at tracing)."""
    mesh = get_mesh()
    assert mesh is not None, "moe_impl='ep' requires an active mesh (parallel.sharding.use_mesh)"
    has_pod = _axis_in_mesh(mesh, "pod")
    has_tp = _axis_in_mesh(mesh, TP_AXIS)
    batch_axes = (("pod", EP_AXIS) if has_pod else EP_AXIS)

    sizes0 = dict(zip(mesh.axis_names, mesh.devices.shape))
    hier = (
        EP_POD[0]
        and has_pod
        and spec.num_experts % (sizes0[EP_AXIS] * sizes0.get("pod", 1)) == 0
    )
    ep_axes = ("pod", EP_AXIS) if hier else EP_AXIS

    x_spec = P(batch_axes, None, None)
    router_spec = P(None, None)
    wi_spec = P(ep_axes, None, TP_AXIS if has_tp else None)
    wo_spec = P(ep_axes, TP_AXIS if has_tp else None, None)

    wg = params.get("wg", params["wi"])  # placeholder when act != swiglu

    # Schedule selection: with few tokens per EP shard (decode), the
    # capacity-padded a2a buffers (E × cap × D) dwarf the actual token
    # traffic; switch to the all-gather/reduce-scatter schedule.
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    ep = sizes[EP_AXIS]
    dp = ep * (sizes.get("pod", 1) if has_pod else 1)
    t_loc = (x.shape[0] // max(dp, 1)) * x.shape[1]
    if t_loc * spec.top_k <= spec.num_experts:
        body = partial(_moe_body_allgather, cfg, spec, mesh)
    else:
        body = partial(_moe_body, cfg, spec, mesh, hier)

    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(x_spec, router_spec, wi_spec, wi_spec, wo_spec),
        out_specs=(x_spec, P(), P()),
        check_vma=True,
    )
    # Pin every operand to its in_spec with an explicit constraint before the
    # shard_map boundary.  Without this, older XLA SPMD partitioners can feed
    # the manual computation a mis-resharded operand when the producer is
    # itself a partitioned gather/slice (observed on the CPU backend: a
    # sharded-embedding lookup flowing straight into this shard_map produced
    # O(1)-wrong expert outputs); the constraint forces a fully materialized
    # reshard first and is a no-op where the partitioner already agrees.
    constrain = lambda v, s: jax.lax.with_sharding_constraint(
        v, jax.sharding.NamedSharding(mesh, s)
    )
    return fn(
        constrain(x, x_spec),
        constrain(params["router"], router_spec),
        constrain(params["wi"], wi_spec),
        constrain(wg, wi_spec),
        constrain(params["wo"], wo_spec),
    )
