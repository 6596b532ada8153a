"""Communication schedules for expert parallelism (DeepSpeed-MoE §5.3).

Three all-to-all schedules over the expert dimension of a [E, C, D] dispatch
buffer (E = total experts, C = per-source capacity):

  * ``flat_all_to_all``        — one a2a over the full EP axis group
                                 (the torch.distributed baseline shape:
                                 O(p) hops at small message sizes).
  * ``coordinated``            — (in core/moe_parallel.py) a2a over the
                                 16-wide 'data' axis only; tensor-parallel
                                 ranks replicate, so group size is p/L.
  * ``hierarchical_all_to_all``— the paper's two-step intra-node/inter-node
                                 factoring: a2a over the fast inner axis
                                 (ICI within a pod), a data-layout transform,
                                 then a2a over the slow outer axis (DCI
                                 across pods).  2× communication volume but
                                 O(G + p/G) serialized hops instead of O(p),
                                 a win in the latency-bound decode regime.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp



def flat_all_to_all(x: jax.Array, axis_names) -> jax.Array:
    """x: [E, C, D] with E == prod(axis sizes) * E_loc.
    Returns [E_loc, P*C, D]."""
    return jax.lax.all_to_all(x, axis_names, split_axis=0, concat_axis=1, tiled=True)


def flat_all_to_all_back(x: jax.Array, axis_names) -> jax.Array:
    return jax.lax.all_to_all(x, axis_names, split_axis=1, concat_axis=0, tiled=True)


def hierarchical_all_to_all(x: jax.Array, inner_axis: str, outer_axis: str) -> jax.Array:
    """Two-stage a2a (paper Fig. 8).  x: [E, C, D],
    E = Go * Gi * E_loc laid out with the *outer* axis major.
    Returns [E_loc, Go*Gi*C, D] — same result as flat_all_to_all over
    (outer, inner), via intra-inner exchange + layout transform + inter-outer
    exchange."""
    Go = jax.lax.axis_size(outer_axis)
    Gi = jax.lax.axis_size(inner_axis)
    E, C, D = x.shape
    E_loc = E // (Go * Gi)

    # [Go', Gi', E_loc, C, D]: destination-indexed blocks
    xv = x.reshape(Go, Gi, E_loc, C, D)
    # Stage 1: exchange within the inner (fast, intra-pod) axis on the Gi' dim.
    # After this, member i of each inner group holds the blocks destined for
    # inner-rank i of *every* outer group, from all its inner peers.
    s1 = jax.lax.all_to_all(xv, inner_axis, split_axis=1, concat_axis=3, tiled=True)
    # s1: [Go', 1, E_loc, Gi_src*C, D] -> squeeze
    s1 = s1.reshape(Go, E_loc, Gi * C, D)
    # Data-layout transformation between the two steps (paper's explicit
    # transform): nothing to permute here because the reshape above already
    # groups by destination outer rank; the transform cost shows up as the
    # reshape/copy in HLO.
    # Stage 2: exchange across the outer (slow, inter-pod) axis.
    s2 = jax.lax.all_to_all(s1, outer_axis, split_axis=0, concat_axis=2, tiled=True)
    # s2: [1, E_loc, Go_src*Gi_src*C, D]
    return s2.reshape(E_loc, Go * Gi * C, D)


def hierarchical_all_to_all_back(y: jax.Array, inner_axis: str, outer_axis: str) -> jax.Array:
    """Inverse of hierarchical_all_to_all: [E_loc, Go*Gi*C, D] -> [E, C, D]."""
    Go = jax.lax.axis_size(outer_axis)
    Gi = jax.lax.axis_size(inner_axis)
    E_loc, PC, D = y.shape
    C = PC // (Go * Gi)
    yv = y.reshape(1, E_loc, Go, Gi * C, D)
    s1 = jax.lax.all_to_all(yv, outer_axis, split_axis=2, concat_axis=0, tiled=True)
    # s1: [Go, E_loc, 1, Gi*C, D]
    s1 = s1.reshape(Go, E_loc, Gi, C, D)
    s2 = jax.lax.all_to_all(s1, inner_axis, split_axis=2, concat_axis=1, tiled=True)
    # s2: [Go, Gi*E_loc? ...] -> [Go, Gi, E_loc, C, D]
    s2 = s2.reshape(Go, Gi, E_loc, C, D)
    return s2.reshape(Go * Gi * E_loc, C, D)
