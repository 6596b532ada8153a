"""Fused MoE gating kernel (DeepSpeed-MoE §5.4, TPU-native).

The paper fuses the gating function — top-k selection, the cumulative sum
that assigns each token its slot inside its expert's capacity buffer, and the
construction of the dense token→expert mapping table — into one kernel,
replacing a chain of sparse one-hot einsums (and, on GPU, a Blelloch-scan
cumsum across SMs).

TPU adaptation (DESIGN.md §2): the Pallas grid on TPU executes
**sequentially**, so the running per-expert token counts live in a VMEM
scratch buffer carried across grid steps — an exact, race-free prefix sum
with no tree scan.  Each grid step processes a [BT, E] tile of router logits:
softmax, k iterative masked argmaxes (k ≤ 8), a one-hot cumsum for the
intra-tile position-in-expert, plus the running-counts offset.  Priority is
token-major (slot t*K + k), matching core/gating.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_T = 128  # token tile (VPU lane aligned)


def _gating_kernel(logits_ref, eidx_ref, w_ref, pos_ref, probs_ref, counts_ref, *, top_k: int, E: int):
    tb = pl.program_id(0)

    @pl.when(tb == 0)
    def _init():
        counts_ref[...] = jnp.zeros_like(counts_ref)

    logits = logits_ref[...].astype(jnp.float32)  # [BT, E]
    probs = jax.nn.softmax(logits, axis=-1)
    probs_ref[...] = probs

    BT = logits.shape[0]
    m = probs
    eidx_cols = []
    gate_cols = []
    for _ in range(top_k):  # static unroll: iterative masked argmax
        top = jnp.argmax(m, axis=-1)
        eidx_cols.append(top.astype(jnp.int32))
        gate_cols.append(jnp.max(m, axis=-1))
        m = jnp.where(jax.nn.one_hot(top, E, dtype=jnp.bool_), -jnp.inf, m)
    eidx = jnp.stack(eidx_cols, axis=-1)  # [BT, K]
    gate = jnp.stack(gate_cols, axis=-1)  # [BT, K]

    # token-major flat assignment order within the tile: row t*K + k
    flat = eidx.reshape(BT * top_k)
    onehot = jax.nn.one_hot(flat, E, dtype=jnp.int32)  # [BT*K, E]
    intra = (jnp.cumsum(onehot, axis=0) - 1) * onehot
    pos_flat = jnp.sum(intra, axis=-1) + jnp.sum(onehot * counts_ref[...][None, :], axis=-1)

    counts_ref[...] = counts_ref[...] + jnp.sum(onehot, axis=0)

    eidx_ref[...] = eidx
    w_ref[...] = gate
    pos_ref[...] = pos_flat.reshape(BT, top_k).astype(jnp.int32)


@functools.partial(
    jax.jit, static_argnames=("top_k", "capacity", "interpret", "block_t", "normalize")
)
def gating_kernel(
    logits: jax.Array,  # [T, E]
    top_k: int,
    capacity: int,
    *,
    normalize: bool = True,
    interpret: bool,
    block_t: int = BLOCK_T,
):
    """Fused gating.  Returns (expert_idx [T,K], combine_w [T,K],
    position [T,K], keep [T,K], probs [T,E]) — the same contract as
    core.gating.top_k_gating."""
    T, E = logits.shape
    bt = min(block_t, T)
    assert T % bt == 0, f"T={T} must be a multiple of the token block {bt}"
    nb = T // bt

    out_shapes = (
        jax.ShapeDtypeStruct((T, top_k), jnp.int32),
        jax.ShapeDtypeStruct((T, top_k), jnp.float32),
        jax.ShapeDtypeStruct((T, top_k), jnp.int32),
        jax.ShapeDtypeStruct((T, E), jnp.float32),
    )
    kern = functools.partial(_gating_kernel, top_k=top_k, E=E)
    eidx, w, pos, probs = pl.pallas_call(
        kern,
        grid=(nb,),
        in_specs=[pl.BlockSpec((bt, E), lambda t: (t, 0))],
        out_specs=(
            pl.BlockSpec((bt, top_k), lambda t: (t, 0)),
            pl.BlockSpec((bt, top_k), lambda t: (t, 0)),
            pl.BlockSpec((bt, top_k), lambda t: (t, 0)),
            pl.BlockSpec((bt, E), lambda t: (t, 0)),
        ),
        out_shape=out_shapes,
        scratch_shapes=[pltpu.VMEM((E,), jnp.int32)],
        interpret=interpret,
    )(logits)

    if normalize and top_k > 1:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    keep = pos < capacity
    w = jnp.where(keep, w, 0.0)
    pos = jnp.where(keep, pos, capacity - 1)
    return eidx, w, pos, keep, probs
