"""Expert-parallel MoE schedules for the *serving* engines (paper §5.2-5.3).

The training EP layer (core/moe_parallel.py) shards the token batch over the
mesh — fine for large train batches, impossible for serving where a decode
tick carries `slots` tokens (2-8) and a prefill chunk a few dozen rows,
neither divisible by the mesh.  The two schedules here keep the engines'
fixed shapes and are built for *token-exact parity* with the single-device
engine (the dist tier asserts bitwise-identical greedy output):

  * **replicated-token** (decode / grouped): every shard sees the full token
    set and runs the GLOBAL gating (identical on all shards — same capacity,
    same drops), computes only its local expert slice, and the full expert
    OUTPUT buffer is reassembled with all_gather/psum *before* a replicated
    combine.  Each output row has exactly one non-zero contributor shard, so
    the reduction is exact (0 + a == a in fp) and the combine is literally
    the single-device combine on the same values — bitwise parity even under
    capacity drops.  Communication is O(E·cap·D) (dense) or O(Ct·D)
    (grouped) per layer; at decode token counts this is the all-gather
    schedule of EXPERIMENTS.md run on the output side instead of the input
    side, trading a little bandwidth for exactness.

  * **a2a** (dense kernel, chunk prefill): tokens are zero-padded at the END
    to a mesh multiple, sharded over the EP axes, and exchanged with the
    flat or (two-axis mesh) hierarchical two-hop all-to-all
    (parallel/collectives.py, paper Fig. 8) — the paper's actual serving
    dataflow.  Capacity is per-shard, so parity with the single-device
    engine is exact only when nothing is dropped (trailing zero-pad rows
    cannot displace real tokens: capacity slots are claimed in token-major
    order); the dist tier runs it with a headroom capacity_factor.

Expert weights arrive pre-sharded [E_loc, D, F] per device (serving/ep.py
placement); the grouped/quantized expert kernels run per-device inside the
shard_map body.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import FFNSpec, ModelConfig
from repro.core.dispatch import combine_dense, dispatch_dense
from repro.core.dispatch_grouped import GROUPED_TILE, grouped_layout
from repro.core.gating import (
    expert_capacity,
    kept_counts,
    load_balance_loss,
    load_balance_stats,
    top_k_gating,
)
from repro.parallel.sharding import get_mesh, get_rules

# Named scope of every cross-chip collective of a serving MoE layer (the
# expert-output psum/all_gather, the all-to-alls both ways, the routed-count
# psum and the aux pmeans): ``launch/scope_split.py`` attributes the
# exchange's device time by it.
EXCHANGE = "ep_exchange"


def serve_ep_axes(num_experts: int) -> Optional[Tuple[str, ...]]:
    """EP mesh axes for serving, or None when the ambient mesh can't shard
    this expert count.  Mirrors parallel/params._pick: the 'expert' rule's
    axes must ALL be present in the mesh (all-or-nothing) and their product
    must divide E — so the layer's dispatch agrees with the weight
    placement."""
    mesh = get_mesh()
    if mesh is None:
        return None
    axes = get_rules().get("expert")
    if axes is None:
        return None
    if isinstance(axes, str):
        axes = (axes,)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    ep = 1
    for a in axes:
        if a not in sizes:
            return None
        ep *= sizes[a]
    if ep <= 1 or num_experts % ep != 0:
        return None
    return tuple(axes)


def ep_rows(counts: jax.Array, num_experts: int) -> jax.Array:
    """[ep] int32: the rows each EP rank's experts received, from a
    layer's global per-expert ``counts`` [E] (rank r owns experts
    ``[r*E_loc, (r+1)*E_loc)``).  A sum over the counts the dispatch
    already returned: no gating is re-run and no collective is added."""
    mesh = get_mesh()
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    ep = 1
    for a in serve_ep_axes(num_experts):
        ep *= sizes[a]
    return counts.reshape(ep, num_experts // ep).sum(axis=1).astype(jnp.int32)


def _ep_rank(axes) -> jax.Array:
    """Linear rank within the EP group, major-first — the same order the
    all_gather/all_to_all collectives concatenate over a multi-axis group,
    so shard r owns experts [r*E_loc, (r+1)*E_loc)."""
    r = jnp.int32(0)
    for a in axes:
        r = r * jax.lax.axis_size(a) + jax.lax.axis_index(a)
    return r


def _ffn_params(wi, wg, wo, act):
    p = {"wi": wi, "wo": wo}
    if act == "swiglu":
        p["wg"] = wg
    return p


def _body_replicated_dense(cfg: ModelConfig, spec: FFNSpec, axes, x, router, wi, wg, wo):
    """Replicated-token schedule, capacity-dispatch kernel.  x: [B, S, D]
    replicated; wi/wo: local expert slice [E_loc, ...]."""
    from repro.core.moe import experts_ffn

    B, S, D = x.shape
    E, K = spec.num_experts, spec.top_k
    ep = 1
    for a in axes:
        ep *= jax.lax.axis_size(a)
    E_loc = E // ep
    T = B * S
    cap = expert_capacity(T, E, K, spec.capacity_factor)

    xs = x.reshape(T, D)
    logits = xs.astype(jnp.float32) @ router
    g = top_k_gating(logits, K, cap)  # GLOBAL gating — identical on every shard

    # Keep only assignments routed to OUR experts (moe_parallel all-gather
    # schedule's masking); position/keep come from the global gating, so the
    # local buffer rows are bit-identical to the corresponding rows of the
    # single-device [E, cap, D] buffer.
    lo = _ep_rank(axes) * E_loc
    mine = (g.expert_idx >= lo) & (g.expert_idx < lo + E_loc)
    g_loc = g._replace(
        expert_idx=jnp.where(mine, g.expert_idx - lo, 0),
        keep=g.keep & mine,
        combine_w=jnp.where(mine, g.combine_w, 0.0),
    )
    buf = dispatch_dense(xs, g_loc, cap, E_loc)  # [E_loc, cap, D]
    out_loc = experts_ffn(_ffn_params(wi, wg, wo, spec.act), buf, spec.act)

    # Reassemble the FULL [E, cap, D] expert-output buffer BEFORE combining:
    # major-first gather order matches lo = rank*E_loc, and each expert row
    # exists on exactly one shard, so this is exact reconstruction — the
    # combine below then runs replicated on the same values and global
    # gating as the single-device engine (bitwise parity, drops included).
    with jax.named_scope(EXCHANGE):
        out = jax.lax.all_gather(out_loc, axes, axis=0, tiled=True)  # [E, cap, D]
    y = combine_dense(out, g, cap, E).reshape(B, S, D)

    aux = load_balance_loss(g.probs, g.expert_idx, E)
    with jax.named_scope(EXCHANGE):
        aux = jax.lax.pmean(aux, axes)  # identical per shard; certifies replication
    return y, aux, kept_counts(g, E)  # global gating: the same on every shard


def _body_replicated_grouped(cfg: ModelConfig, spec: FFNSpec, axes, x, router, wi, wg, wo):
    """Replicated-token schedule, dropless grouped kernel: the global grouped
    layout is computed on every shard, non-local tiles are masked, the local
    grouped kernel runs on its tile subset, and the [Ct, D] expert-output
    buffer is psum-reassembled before the replicated scatter-add combine
    (one non-zero contributor per row → exact)."""
    from repro.core.moe import grouped_experts_ffn

    B, S, D = x.shape
    E, K = spec.num_experts, spec.top_k
    ep = 1
    for a in axes:
        ep *= jax.lax.axis_size(a)
    E_loc = E // ep
    T = B * S
    TK = T * K

    xs = x.reshape(T, D)
    logits = xs.astype(jnp.float32) @ router
    g = top_k_gating(logits, K, TK)  # dropless global gating
    layout = grouped_layout(g, E, tile=GROUPED_TILE)
    token = jnp.arange(TK, dtype=jnp.int32) // K
    Ct = layout.tile_expert.shape[0] * GROUPED_TILE
    xg = jnp.zeros((Ct, D), xs.dtype).at[layout.dst].set(xs[token])

    # Mask tiles owned by other shards: zero their rows, clamp their expert
    # id into the local window so the per-device kernel never indexes out of
    # its [E_loc] weight slice.  (Trailing padding tiles clamp to E-1 in the
    # layout; no dst row points at them, so their owner is irrelevant.)
    lo = _ep_rank(axes) * E_loc
    tile_mine = (layout.tile_expert >= lo) & (layout.tile_expert < lo + E_loc)
    te_loc = jnp.where(tile_mine, layout.tile_expert - lo, 0).astype(jnp.int32)
    row_mine = jnp.repeat(tile_mine, GROUPED_TILE)  # [Ct]
    xg_loc = jnp.where(row_mine[:, None], xg, 0)
    yg_loc = grouped_experts_ffn(_ffn_params(wi, wg, wo, spec.act), xg_loc, te_loc, spec.act)
    yg_loc = jnp.where(row_mine[:, None], yg_loc.astype(jnp.float32), 0.0)
    with jax.named_scope(EXCHANGE):
        yg = jax.lax.psum(yg_loc, axes)  # [Ct, D] f32, exact (single contributor/row)

    # Replicated combine — moe_grouped's scatter-add on the reassembled
    # buffer (already f32, matching its accumulation discipline).
    w = g.combine_w.reshape(-1).astype(jnp.float32)
    y = jnp.zeros((T, D), jnp.float32).at[token].add(w[:, None] * yg[layout.dst])
    y = y.astype(xs.dtype).reshape(B, S, D)

    aux = load_balance_loss(g.probs, g.expert_idx, E)
    with jax.named_scope(EXCHANGE):
        aux = jax.lax.pmean(aux, axes)
    return y, aux, layout.counts  # global layout: the same on every shard


def _body_a2a(cfg: ModelConfig, spec: FFNSpec, axes, x_loc, router, wi, wg, wo):
    """Token-sharded a2a schedule (paper's serving dataflow).  x_loc:
    [T_loc, D] — this shard's slice of the end-padded token set."""
    from repro.core.moe import experts_ffn
    from repro.parallel.collectives import (
        flat_all_to_all,
        flat_all_to_all_back,
        hierarchical_all_to_all,
        hierarchical_all_to_all_back,
    )

    T_loc, D = x_loc.shape
    E, K = spec.num_experts, spec.top_k
    ep = 1
    for a in axes:
        ep *= jax.lax.axis_size(a)
    E_loc = E // ep
    cap = expert_capacity(T_loc, E, K, spec.capacity_factor)

    logits = x_loc.astype(jnp.float32) @ router
    g = top_k_gating(logits, K, cap)
    buf = dispatch_dense(x_loc, g, cap, E)  # [E, cap, D]

    with jax.named_scope(EXCHANGE):
        if len(axes) == 2:
            # two-hop hierarchical exchange (Fig. 8): intra-host axis first,
            # layout transform, then the inter-host hop.  Expert ids are laid
            # out outer-major, matching _ep_rank's ordering.
            recv = hierarchical_all_to_all(buf, axes[1], axes[0])
        else:
            recv = flat_all_to_all(buf, axes)
    # recv: [E_loc, ep*cap, D]
    out = experts_ffn(_ffn_params(wi, wg, wo, spec.act), recv, spec.act)
    with jax.named_scope(EXCHANGE):
        if len(axes) == 2:
            back = hierarchical_all_to_all_back(out, axes[1], axes[0])
        else:
            back = flat_all_to_all_back(out, axes)
    y = combine_dense(back, g, cap, E)  # [T_loc, D]

    # global-batch aux: pmean the linear per-expert stats, then the product
    f, p = load_balance_stats(g.probs, g.expert_idx, E)
    with jax.named_scope(EXCHANGE):
        f = jax.lax.pmean(f, axes)
        p = jax.lax.pmean(p, axes)
    aux = E * jnp.sum(f * p)
    # rows each expert receives: every shard's kept assignments, summed
    with jax.named_scope(EXCHANGE):
        counts = jax.lax.psum(kept_counts(g, E), axes)
    return y, aux, counts


def moe_layer_ep_serve(
    cfg: ModelConfig,
    spec: FFNSpec,
    params: dict,
    x: jax.Array,  # [B, S, D]
    *,
    kernel: str = "dense",  # "dense" | "grouped"
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Serving EP layer: ``(y, aux, counts)``, ``counts`` [E] int32 the rows
    the dispatch gave each expert over the whole token set (read by the
    routed-expert counter; unused, it is dropped with its psum at tracing).
    Caller (core/moe.py) guarantees an active mesh whose 'expert' rule axes
    divide ``spec.num_experts`` (serve_ep_axes)."""
    mesh = get_mesh()
    axes = serve_ep_axes(spec.num_experts)
    assert mesh is not None and axes is not None, "moe_layer_ep_serve requires a usable EP mesh"

    B, S, D = x.shape
    T = B * S
    ep = 1
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    for a in axes:
        ep *= sizes[a]

    wg = params.get("wg", params["wi"])  # placeholder when act != swiglu
    w_spec = P(axes if len(axes) > 1 else axes[0], None, None)
    rep = P()
    constrain = lambda v, s: jax.lax.with_sharding_constraint(
        v, jax.sharding.NamedSharding(mesh, s)
    )
    operands = (
        constrain(params["router"], P(None, None)),
        constrain(params["wi"], w_spec),
        constrain(wg, w_spec),
        constrain(params["wo"], w_spec),
    )

    # Schedule selection (moe_parallel's rule): with few tokens per shard the
    # capacity-padded a2a buffers dwarf the token traffic — and the grouped
    # kernel's layout is global by construction — so both take the
    # replicated-token schedule; batched/chunked prefill with the dense
    # kernel takes the paper's a2a exchange.
    if kernel == "grouped" or T * spec.top_k <= spec.num_experts:
        body = (
            _body_replicated_grouped if kernel == "grouped" else _body_replicated_dense
        )
        fn = jax.shard_map(
            partial(body, cfg, spec, axes),
            mesh=mesh,
            in_specs=(rep, P(None, None), w_spec, w_spec, w_spec),
            out_specs=(rep, rep, rep),
            check_vma=False,
        )
        return fn(constrain(x, rep), *operands)

    # a2a schedule: flatten, zero-pad at the END to a mesh multiple (trailing
    # pads can never displace a real token's capacity slot — slots are
    # claimed in token-major order), shard tokens over the EP axes.
    xs = x.reshape(T, D)
    Tp = -(-T // ep) * ep
    if Tp != T:
        xs = jnp.concatenate([xs, jnp.zeros((Tp - T, D), xs.dtype)])
    tok_spec = P(axes if len(axes) > 1 else axes[0], None)
    fn = jax.shard_map(
        partial(_body_a2a, cfg, spec, axes),
        mesh=mesh,
        in_specs=(tok_spec, rep, w_spec, w_spec, w_spec),
        out_specs=(tok_spec, rep, rep),
        check_vma=False,
    )
    y, aux, counts = fn(constrain(xs, tok_spec), *operands)
    return y[:T].reshape(B, S, D), aux, counts
