"""Grouped expert-MLP Pallas kernel (the dense [E, C, D] expert GEMMs that
the mapping-table dispatch feeds — DeepSpeed-MoE §5.4 "optimized transformer
and MoE related kernels", adapted to the TPU MXU).

Per grid step (e, c, f): a [BC, D] token tile of expert e meets a [D, BF]
slice of that expert's up/gate projections; the SwiGLU'd [BC, BF] tile is
immediately multiplied by the [BF, D] down-projection slice and accumulated
into the [BC, D] output tile in VMEM (revisited across the innermost f axis,
so the intermediate [C, F] activation never exists in HBM).  Block shapes
are multiples of 128 to keep the MXU systolic array full.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_C = 128
BLOCK_F = 256


def _expert_mlp_kernel(x_ref, wi_ref, wg_ref, wo_ref, o_ref):
    f = pl.program_id(2)

    @pl.when(f == 0)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[0]  # [BC, D]
    h = jnp.dot(x, wi_ref[0], preferred_element_type=jnp.float32)  # [BC, BF]
    g = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
    act = (jax.nn.silu(g) * h).astype(x.dtype)
    o_ref[...] += jnp.dot(act, wo_ref[0], preferred_element_type=jnp.float32)[None].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "block_c", "block_f"))
def expert_mlp_kernel(
    xe: jax.Array,  # [E, C, D]
    wi: jax.Array,  # [E, D, F]
    wg: jax.Array,  # [E, D, F]
    wo: jax.Array,  # [E, F, D]
    *,
    interpret: bool,
    block_c: int = BLOCK_C,
    block_f: int = BLOCK_F,
) -> jax.Array:
    E, C, D = xe.shape
    F = wi.shape[-1]
    bc = min(block_c, C)
    bf = min(block_f, F)
    assert C % bc == 0 and F % bf == 0, (C, bc, F, bf)
    grid = (E, C // bc, F // bf)

    out = pl.pallas_call(
        _expert_mlp_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bc, D), lambda e, c, f: (e, c, 0)),
            pl.BlockSpec((1, D, bf), lambda e, c, f: (e, 0, f)),
            pl.BlockSpec((1, D, bf), lambda e, c, f: (e, 0, f)),
            pl.BlockSpec((1, bf, D), lambda e, c, f: (e, f, 0)),
        ],
        out_specs=pl.BlockSpec((1, bc, D), lambda e, c, f: (e, c, 0)),
        out_shape=jax.ShapeDtypeStruct((E, C, D), jnp.float32),
        interpret=interpret,
    )(xe, wi, wg, wo)
    return out.astype(xe.dtype)
