"""Grouped "dropless" MoE dispatch (MegaBlocks-style).

Capacity-factor dispatch (``dispatch.py`` / ``dispatch_einsum.py``) pads
every expert's token buffer to a static ``expert_capacity`` — the dead
compute the graph auditor's ``capacity-padding`` finding prices, and the
token *drops* whenever routing skews past the factor.  The grouped layout
removes both at once:

  1. sort the ``T*K`` (token, k) assignment slots by expert (stable argsort,
     token-major priority preserved — the same order capacity gating ranks);
  2. pad each expert's *actual* group only up to the next multiple of
     ``tile`` (the kernel's token-block size), never to capacity;
  3. scatter tokens into one flat ``[Ct, D]`` buffer of concatenated padded
     groups, where ``Ct = round_down(T*K + E*(tile-1), tile)`` is the static
     worst case over all routings — per-expert *offsets* are data, the
     buffer shape is not;
  4. hand the kernel a ``tile_expert [Ct/tile]`` map (tile index -> expert
     id) so each token tile walks against exactly its expert's weights
     (scalar-prefetched on TPU — ``kernels/expert_mlp_grouped.py``).

Every assignment keeps its expert (``keep`` all-True by construction when
gated with ``capacity = T*K``), so routing skew costs at most ``E`` partial
tiles of padding instead of dropped tokens — the dispatch is *exact* for
any routing, which is what makes it the batched-prefill engine's MoE
implementation of choice (capacity gating couples tokens across slots
through the shared buffer; dropless keeps rows independent).
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.gating import Gating

# Default token-tile granularity of the grouped buffer.  8 matches the
# sublane granularity ``expert_capacity`` already pads to (cheap on CPU
# tests); pass 128 on TPU to keep the MXU systolic array full.
GROUPED_TILE = 8


def grouped_rows(num_tokens: int, top_k: int, num_experts: int,
                 tile: int = GROUPED_TILE) -> int:
    """Static row count of the grouped buffer: the worst case of per-expert
    tile padding over ALL routings.  Each non-empty group wastes at most
    ``tile - 1`` rows, and the total is itself a tile multiple."""
    tk = num_tokens * top_k
    return (tk + num_experts * (tile - 1)) // tile * tile


class GroupedLayout(NamedTuple):
    """Device-side routing layout for one dispatch.

    dst:         [T*K] int32 — grouped-buffer row of each (token, k) slot
                 (token-major; rows within an expert's group preserve the
                 capacity-gating priority order)
    tile_expert: [Ct/tile] int32 — expert id owning each token tile
                 (trailing unused tiles clamp to E-1; their rows stay zero
                 and no ``dst`` points at them)
    counts:      [E] int32 — real (un-padded) assignments per expert
    """

    dst: jax.Array
    tile_expert: jax.Array
    counts: jax.Array


def grouped_layout(g: Gating, num_experts: int, *,
                   tile: int = GROUPED_TILE) -> GroupedLayout:
    """Sort-free-shape layout: per-expert ragged offsets as *data* inside a
    static ``[Ct]`` index space (step 1-4 of the module docstring)."""
    T, K = g.expert_idx.shape
    TK = T * K
    flat_e = g.expert_idx.reshape(-1)  # [T*K], token-major
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    counts = jnp.bincount(flat_e, length=num_experts)
    padded = (counts + tile - 1) // tile * tile  # per-group tile padding ONLY
    # rank of each sorted slot within its expert's run (same searchsorted
    # trick as gating._positions_sort)
    group_start = jnp.searchsorted(sorted_e, jnp.arange(num_experts, dtype=flat_e.dtype),
                                   side="left")
    rank_sorted = jnp.arange(TK, dtype=jnp.int32) - group_start[sorted_e].astype(jnp.int32)
    starts = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(padded)[:-1].astype(jnp.int32)])
    dst_sorted = starts[sorted_e] + rank_sorted
    dst = jnp.zeros((TK,), jnp.int32).at[order].set(dst_sorted)
    # tile t covers rows [t*tile, (t+1)*tile): its owner is the expert whose
    # padded-prefix-sum first exceeds the tile's start row
    nt = grouped_rows(T, K, num_experts, tile) // tile
    bounds = jnp.cumsum(padded)  # [E]
    tile_expert = jnp.searchsorted(
        bounds, jnp.arange(nt, dtype=bounds.dtype) * tile, side="right")
    tile_expert = jnp.clip(tile_expert, 0, num_experts - 1).astype(jnp.int32)
    return GroupedLayout(dst=dst, tile_expert=tile_expert,
                         counts=counts.astype(jnp.int32))


def moe_grouped(x: jax.Array, g: Gating, num_experts: int,
                expert_fn: Callable[[jax.Array, jax.Array], jax.Array], *,
                tile: int = GROUPED_TILE) -> jax.Array:
    """x: [T, D]; ``g`` must be dropless gating (``capacity = T*K``).
    ``expert_fn``: (xg [Ct, D], tile_expert [Ct/tile]) -> [Ct, D], applying
    tile ``t``'s rows against expert ``tile_expert[t]``'s MLP.

    gather-by-token -> scatter into padded groups -> grouped experts ->
    gather-by-row -> weighted scatter-add combine (f32 accumulation, same
    precision discipline as the einsum path).
    """
    T, D = x.shape
    K = g.expert_idx.shape[1]
    TK = T * K
    with jax.named_scope("moe_dispatch"):
        layout = grouped_layout(g, num_experts, tile=tile)
        token = jnp.arange(TK, dtype=jnp.int32) // K  # flat slot -> source token
        Ct = layout.tile_expert.shape[0] * tile
        xg = jnp.zeros((Ct, D), x.dtype).at[layout.dst].set(x[token])
    with jax.named_scope("expert_mlp"):
        yg = expert_fn(xg, layout.tile_expert)  # [Ct, D]
    with jax.named_scope("moe_combine"):
        w = g.combine_w.reshape(-1).astype(jnp.float32)  # keep is all-True (dropless)
        y = jnp.zeros((T, D), jnp.float32).at[token].add(
            w[:, None] * yg[layout.dst].astype(jnp.float32))
        return y.astype(x.dtype)
