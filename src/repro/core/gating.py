"""Top-k expert gating (DeepSpeed-MoE §3.1, §5.4).

The gating pipeline is: router logits -> softmax -> top-k expert ids ->
capacity-constrained slot assignment (position-in-expert via prefix sum) ->
combine weights.  The pure-jnp implementation here is the *oracle* for the
fused Pallas gating kernel (kernels/moe_gating.py) and is itself used by the
einsum / dense dispatch paths.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class Gating(NamedTuple):
    """T = tokens, K = top_k.

    expert_idx:  [T, K] int32 — chosen expert per (token, slot-k)
    combine_w:   [T, K] f32   — gate probability (0 where dropped)
    position:    [T, K] int32 — position within the expert's capacity buffer
    keep:        [T, K] bool  — False if dropped by capacity
    probs:       [T, E] f32   — full softmax (for aux loss)
    """

    expert_idx: jax.Array
    combine_w: jax.Array
    position: jax.Array
    keep: jax.Array
    probs: jax.Array


def expert_capacity(num_tokens: int, num_experts: int, top_k: int, capacity_factor: float) -> int:
    """Tokens each expert can accept (padded to a multiple of 8 ≥ 8)."""
    c = int(capacity_factor * num_tokens * top_k / num_experts)
    c = max(c, 8)
    return ((c + 7) // 8) * 8


def _positions_cumsum(flat_expert: jax.Array, E: int) -> jax.Array:
    """Prefix-sum over one-hot assignment matrix: O(T·K·E) work/memory.
    This is the textbook formulation (and the Pallas kernel's oracle)."""
    onehot = jax.nn.one_hot(flat_expert, E, dtype=jnp.int32)  # [K*T, E]
    positions_flat = (jnp.cumsum(onehot, axis=0) - 1) * onehot
    return jnp.sum(positions_flat, axis=-1)  # [K*T]


def _positions_sort(flat_expert: jax.Array, E: int) -> jax.Array:
    """Rank-within-expert via stable argsort: O(T·K log T·K) work, O(T·K)
    memory — used for long sequences where the one-hot matrix would be
    prohibitive.  Stable sort preserves the k-major priority order."""
    TK = flat_expert.shape[0]
    order = jnp.argsort(flat_expert, stable=True)  # [TK]
    sorted_e = flat_expert[order]
    # start index of each expert's run in the sorted array
    group_start = jnp.searchsorted(sorted_e, jnp.arange(E, dtype=flat_expert.dtype), side="left")
    rank_sorted = jnp.arange(TK, dtype=jnp.int32) - group_start[sorted_e]
    pos = jnp.zeros((TK,), jnp.int32).at[order].set(rank_sorted)
    return pos


# Above this many one-hot elements, switch to the sort-based ranking.
_SORT_THRESHOLD = 1 << 22


def top_k_gating(
    logits: jax.Array,  # [T, E]
    top_k: int,
    capacity: int,
    *,
    normalize: bool = True,
    method: str = "auto",  # "auto" | "cumsum" | "sort"
) -> Gating:
    T, E = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate_w, expert_idx = jax.lax.top_k(probs, top_k)  # [T, K]
    if normalize and top_k > 1:
        gate_w = gate_w / jnp.sum(gate_w, axis=-1, keepdims=True)

    # Position within each expert's buffer, computed in token-major order
    # (slot t*K+k): earlier tokens win capacity, and within a token the
    # primary expert wins first (Megatron/t5x convention; keeps the Pallas
    # kernel a single sequential sweep over token tiles).
    flat_expert = expert_idx.reshape(-1)  # [T*K], token-major
    if method == "auto":
        method = "sort" if T * top_k * E > _SORT_THRESHOLD else "cumsum"
    pos_flat = _positions_sort(flat_expert, E) if method == "sort" else _positions_cumsum(flat_expert, E)
    position = pos_flat.reshape(T, top_k)  # [T, K]

    keep = position < capacity
    combine_w = jnp.where(keep, gate_w, 0.0)
    position = jnp.where(keep, position, capacity - 1)  # clamped; masked out by combine_w/keep
    return Gating(expert_idx.astype(jnp.int32), combine_w, position.astype(jnp.int32), keep, probs)


def load_balance_stats(probs: jax.Array, expert_idx: jax.Array, num_experts: int):
    """Per-expert (f_e, P_e): fraction of primary (k=0) assignments and mean
    router probability.  Split out so expert-parallel shards can pmean these
    *linear* statistics across the EP axis before taking the product — the
    loss is nonlinear in (f, P), so averaging per-shard losses would NOT
    equal the global-batch loss."""
    T = probs.shape[0]
    primary = expert_idx[:, 0]
    f = jnp.bincount(primary, length=num_experts).astype(jnp.float32) / T
    p = jnp.mean(probs, axis=0)
    return f, p


class RoutingStats(NamedTuple):
    """Jit-returnable routing telemetry for one MoE layer (paper §3, §5:
    expert load balance is the MoE-specific serving/training signal).  All
    leaves have token-count-independent shapes, so the engines can return
    them from fixed-shape jitted steps and aggregate host-side.

    tokens_per_expert: [E] int32 — assignments KEPT per expert (all k slots)
    dropped_frac:      []  f32   — fraction of (token, k) assignments dropped
                                   by expert capacity
    entropy:           []  f32   — mean router-softmax entropy (nats);
                                   ln(E) = uniform, 0 = collapsed
    imbalance:         []  f32   — E · Σ_e f_e·P_e (the aux-loss statistic);
                                   1.0 = perfectly balanced, E = collapse
    f:                 [E] f32   — fraction of primary (k=0) assignments
    p:                 [E] f32   — mean router probability
    """

    tokens_per_expert: jax.Array
    dropped_frac: jax.Array
    entropy: jax.Array
    imbalance: jax.Array
    f: jax.Array
    p: jax.Array


def kept_counts(g: Gating, num_experts: int) -> jax.Array:
    """[E] int32: kept assignments per expert over ALL k slots (dropped ones
    route to a scratch bucket at index E and are cut off)."""
    kept_idx = jnp.where(g.keep, g.expert_idx, num_experts).reshape(-1)
    return jnp.bincount(kept_idx, length=num_experts + 1)[:num_experts].astype(jnp.int32)


def routing_stats(g: Gating, num_experts: int) -> RoutingStats:
    """RoutingStats from one layer's gating decision.  ``f``/``p`` (and the
    ``imbalance`` built from them) are exactly ``load_balance_stats`` — the
    parity tests/test_obs.py asserts — so telemetry can never drift from the
    loss the model trains against."""
    f, p = load_balance_stats(g.probs, g.expert_idx, num_experts)
    tokens_per_expert = kept_counts(g, num_experts)
    dropped = 1.0 - jnp.mean(g.keep.astype(jnp.float32))
    entropy = -jnp.mean(jnp.sum(g.probs * jnp.log(g.probs + 1e-9), axis=-1))
    imbalance = num_experts * jnp.sum(f * p)
    return RoutingStats(tokens_per_expert.astype(jnp.int32), dropped, entropy,
                        imbalance, f, p)


def summarize_routing(stats_tree) -> dict:
    """Host-side per-layer aggregation of a routing-stats pytree as returned
    by ``forward(..., return_routing=True)`` / the engines' decode steps:
    ``{seg: {pos: RoutingStats with [repeats, ...] leaves}}``.

    Returns plain floats/lists (JSON-ready): overall means across MoE layers
    plus a per-layer breakdown keyed ``"{seg}/{pos}[repeat]"``."""
    import numpy as np

    per_layer = {}
    for seg in sorted(stats_tree):
        for pos in sorted(stats_tree[seg]):
            st = stats_tree[seg][pos]
            reps = np.asarray(st.dropped_frac).shape[0]
            tpe = np.asarray(st.tokens_per_expert)
            for r in range(reps):
                per_layer[f"{seg}/{pos}[{r}]"] = {
                    "dropped_frac": float(np.asarray(st.dropped_frac)[r]),
                    "entropy": float(np.asarray(st.entropy)[r]),
                    "imbalance": float(np.asarray(st.imbalance)[r]),
                    "tokens_per_expert": tpe[r].tolist(),
                    "max_expert_load": (float(tpe[r].max() / max(tpe[r].sum(), 1))),
                }
    n = max(len(per_layer), 1)
    return {
        "moe_layers": len(per_layer),
        "dropped_frac": sum(v["dropped_frac"] for v in per_layer.values()) / n,
        "entropy": sum(v["entropy"] for v in per_layer.values()) / n,
        "imbalance": sum(v["imbalance"] for v in per_layer.values()) / n,
        "per_layer": per_layer,
    }


def load_balance_loss(probs: jax.Array, expert_idx: jax.Array, num_experts: int) -> jax.Array:
    """Switch-Transformer auxiliary loss: E * sum_e f_e * P_e (paper Table 1:
    'MoE loss coefficient' scales this in the total loss).  f_e counts primary
    (k=0) assignments; P_e is the mean router probability."""
    f, p = load_balance_stats(probs, expert_idx, num_experts)
    return num_experts * jnp.sum(f * p)


def router_z_loss(logits: jax.Array) -> jax.Array:
    """Router z-loss (ST-MoE): discourages large router logits. Optional."""
    z = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    return jnp.mean(z**2)
