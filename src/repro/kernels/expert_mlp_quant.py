"""Grouped expert-MLP Pallas kernel over int8 weights, dequantized *in VMEM*
(MoQ serving path — DeepSpeed-MoE §4 compression meeting the §5.4 kernels).

Same grid/BlockSpec structure as ``kernels/expert_mlp.py``: per grid step
(e, c, f) a [BC, D] token tile of expert e meets int8 tiles of that expert's
up/gate/down projections plus their per-output-channel f32 scales.  Each
weight tile is widened and rescaled right before its MXU dot, so HBM only
ever holds (and the grid only ever streams) 1-byte weights — the bytes/step
reduction that sets decode latency in the paper's memory-bound inference
analysis.  Scales ride in tiny [1, BF] / [1, D] blocks alongside each tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.expert_mlp import BLOCK_C, BLOCK_F
from repro.quant.qarrays import QuantizedArray


def _expert_mlp_quant_kernel(x_ref, wi_ref, wis_ref, wg_ref, wgs_ref, wo_ref, wos_ref, o_ref):
    f = pl.program_id(2)

    @pl.when(f == 0)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[0]  # [BC, D]
    # Dequantize int8 tiles in VMEM right before the MXU dots: widen to f32,
    # broadcast the per-output-channel scale across the contraction dim.
    wi = wi_ref[0].astype(jnp.float32) * wis_ref[0]  # [D, BF] * [1, BF]
    wg = wg_ref[0].astype(jnp.float32) * wgs_ref[0]
    h = jnp.dot(x, wi.astype(x.dtype), preferred_element_type=jnp.float32)  # [BC, BF]
    g = jnp.dot(x, wg.astype(x.dtype), preferred_element_type=jnp.float32)
    act = (jax.nn.silu(g) * h).astype(x.dtype)
    wo = wo_ref[0].astype(jnp.float32) * wos_ref[0]  # [BF, D] * [1, D]
    o_ref[...] += jnp.dot(act, wo.astype(x.dtype), preferred_element_type=jnp.float32)[None].astype(
        o_ref.dtype
    )


@functools.partial(jax.jit, static_argnames=("interpret", "block_c", "block_f"))
def expert_mlp_quant_kernel(
    xe: jax.Array,  # [E, C, D]
    wi_q: jax.Array,  # [E, D, F] int8
    wi_s: jax.Array,  # [E, 1, F] f32
    wg_q: jax.Array,  # [E, D, F] int8
    wg_s: jax.Array,  # [E, 1, F] f32
    wo_q: jax.Array,  # [E, F, D] int8
    wo_s: jax.Array,  # [E, 1, D] f32
    *,
    interpret: bool,
    block_c: int = BLOCK_C,
    block_f: int = BLOCK_F,
) -> jax.Array:
    E, C, D = xe.shape
    F = wi_q.shape[-1]
    bc = min(block_c, C)
    bf = min(block_f, F)
    assert C % bc == 0 and F % bf == 0, (C, bc, F, bf)
    grid = (E, C // bc, F // bf)

    out = pl.pallas_call(
        _expert_mlp_quant_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bc, D), lambda e, c, f: (e, c, 0)),
            pl.BlockSpec((1, D, bf), lambda e, c, f: (e, 0, f)),
            pl.BlockSpec((1, 1, bf), lambda e, c, f: (e, 0, f)),
            pl.BlockSpec((1, D, bf), lambda e, c, f: (e, 0, f)),
            pl.BlockSpec((1, 1, bf), lambda e, c, f: (e, 0, f)),
            pl.BlockSpec((1, bf, D), lambda e, c, f: (e, f, 0)),
            pl.BlockSpec((1, 1, D), lambda e, c, f: (e, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bc, D), lambda e, c, f: (e, c, 0)),
        out_shape=jax.ShapeDtypeStruct((E, C, D), jnp.float32),
        interpret=interpret,
    )(xe, wi_q, wi_s, wg_q, wg_s, wo_q, wo_s)
    return out.astype(xe.dtype)


def _check_kernel_compat(xe, wi, wg, wo, *, block_c: int = BLOCK_C, block_f: int = BLOCK_F) -> bool:
    """Kernel path handles the plain int8 per-output-channel layout only
    (int4 / group-wise take the einsum reference path), and only shapes the
    grid tiles divide: capacity C and d_ff F must be multiples of the block
    sizes once they exceed them (expert_capacity pads to 8, not 128)."""
    qs = (wi, wg, wo)
    if wg is None or not all(isinstance(q, QuantizedArray) for q in qs):
        return False
    if not all(q.bits == 8 and q.group_size == 0 for q in qs):
        return False
    C = xe.shape[1]
    F = wi.shape[-1]
    return C % min(block_c, C) == 0 and F % min(block_f, F) == 0


def expert_mlp_quant(
    xe: jax.Array,
    wi: QuantizedArray,
    wg: QuantizedArray,
    wo: QuantizedArray,
    *,
    interpret: bool,
) -> jax.Array:
    """Kernel entry from QuantizedArray leaves (int8 per-channel layout)."""
    if not _check_kernel_compat(xe, wi, wg, wo):
        raise ValueError(
            "expert_mlp_quant kernel needs int8 per-output-channel QuantizedArrays "
            "and block-divisible shapes (C mult of 128, F mult of 256 once larger); "
            f"got C={xe.shape[1]}, F={wi.shape[-1]}"
        )
    return expert_mlp_quant_kernel(
        xe, wi.q, wi.scale, wg.q, wg.scale, wo.q, wo.scale, interpret=interpret
    )


def expert_mlp_quant_ref(
    xe: jax.Array, wi: QuantizedArray, wg: QuantizedArray, wo: QuantizedArray
) -> jax.Array:
    """Einsum reference path: dequantize whole weights into the fp oracle
    ``kernels/ref.py::expert_mlp_ref`` (correctness reference for the kernel,
    and the default CPU execution path in core/moe.py)."""
    from repro.kernels.ref import expert_mlp_ref

    return expert_mlp_ref(xe, wi.dequantize(), wg.dequantize(), wo.dequantize())
