"""Dropless grouped expert-MLP Pallas kernel (MegaBlocks-style ragged walk).

The capacity kernels (``expert_mlp.py`` / ``expert_mlp_quant.py``) iterate a
dense ``[E, C, D]`` buffer — every expert pays for ``C = expert_capacity``
rows whether routed or not.  Here the dispatch layer
(``core/dispatch_grouped.py``) has already *sorted* the tokens by expert into
one flat ``[Ct, D]`` buffer of tile-padded per-expert groups, so the grid
walks token tiles, not (expert, capacity-slot) pairs:

  grid (t, f): token tile ``t`` belongs entirely to expert ``te[t]`` — the
  scalar-prefetched tile->expert map indexes the weight BlockSpecs directly,
  so each tile streams exactly its own expert's ``[D, BF]`` / ``[BF, D]``
  weight slices from HBM.  The activation (SwiGLU, GELU or ReLU — static;
  only SwiGLU streams a gate projection) + down-projection accumulate across
  the innermost ``f`` axis in VMEM, same as the capacity kernel.

Ragged group boundaries therefore cost *zero* control flow in the kernel:
the raggedness lives in ``te`` (data) and in the zero rows padding each
group to the tile — at most ``tile - 1`` wasted rows per expert, versus
``C - count_e`` per expert for the capacity path.

Quantized variants dequantize int8 tiles in VMEM (per-output-channel f32
scales ride in ``[1, BF]`` / ``[1, D]`` blocks), and int4 additionally
unpacks two nibbles per stored byte along the contraction axis in-register —
the grouped path is where int4 weights first get a true dequant-in-kernel
execution (the capacity kernel int4 path is einsum-ref only).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.expert_mlp import BLOCK_F
from repro.quant.qarrays import QuantizedArray

# ---------------------------------------------------------------------------
# fp kernel
# ---------------------------------------------------------------------------


def _dot(a, b):
    """MXU dot with f32 accumulation.  bf16 operands pin DEFAULT precision
    (exact bf16 products): Mosaic rejects a bf16 dot at an ambient
    ``jax_default_matmul_precision`` of "highest"."""
    prec = jax.lax.Precision.DEFAULT if a.dtype == jnp.bfloat16 else None
    return jnp.dot(a, b, preferred_element_type=jnp.float32, precision=prec)


def _act(h, g, act: str):
    """The FFN nonlinearity on f32 tiles: SwiGLU gates ``h`` with ``g``;
    GELU / ReLU layers carry no gate projection (``g`` is None)."""
    if act == "swiglu":
        return jax.nn.silu(g) * h
    if act == "gelu":
        return jax.nn.gelu(h)
    if act == "relu":
        return jax.nn.relu(h)
    raise ValueError(f"grouped expert MLP: unsupported act {act!r}")


def _grouped_mlp_kernel(te_ref, x_ref, *refs, act):
    del te_ref  # consumed by the index maps
    if act == "swiglu":
        wi_ref, wg_ref, wo_ref, o_ref = refs
    else:
        (wi_ref, wo_ref, o_ref), wg_ref = refs, None
    f = pl.program_id(1)

    @pl.when(f == 0)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...]  # [BT, D] — one token tile, all rows share expert te[t]
    h = _dot(x, wi_ref[0])  # [BT, BF]
    g = None if wg_ref is None else _dot(x, wg_ref[0])
    a = _act(h, g, act).astype(x.dtype)
    o_ref[...] += _dot(a, wo_ref[0]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("act", "interpret", "block_f"))
def grouped_mlp_kernel(
    xg: jax.Array,  # [Ct, D] — tile-padded, expert-sorted token buffer
    te: jax.Array,  # [Ct / BT] int32 — tile -> expert id (scalar-prefetched)
    wi: jax.Array,  # [E, D, F]
    wg,             # [E, D, F] for act="swiglu", else None
    wo: jax.Array,  # [E, F, D]
    *,
    act: str,
    interpret: bool,
    block_f: int = BLOCK_F,
) -> jax.Array:
    Ct, D = xg.shape
    nt = te.shape[0]
    F = wi.shape[-1]
    bt = Ct // nt  # token tile == the dispatch layout's tile
    bf = min(block_f, F)
    assert Ct % nt == 0 and F % bf == 0, (Ct, nt, F, bf)
    assert (wg is not None) == (act == "swiglu"), (act, wg is None)

    up = pl.BlockSpec((1, D, bf), lambda t, f, te: (te[t], 0, f))
    ups = [up, up] if wg is not None else [up]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nt, F // bf),
        in_specs=[
            pl.BlockSpec((bt, D), lambda t, f, te: (t, 0)),
            *ups,
            pl.BlockSpec((1, bf, D), lambda t, f, te: (te[t], f, 0)),
        ],
        out_specs=pl.BlockSpec((bt, D), lambda t, f, te: (t, 0)),
    )
    ws = (wi, wg, wo) if wg is not None else (wi, wo)
    out = pl.pallas_call(
        functools.partial(_grouped_mlp_kernel, act=act),
        name="grouped_mlp_kernel",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Ct, D), jnp.float32),
        interpret=interpret,
    )(te, xg, *ws)
    return out.astype(xg.dtype)


# ---------------------------------------------------------------------------
# quantized kernel (int8 / int4, dequant in VMEM)
# ---------------------------------------------------------------------------


def _widen(tile: jax.Array, bits: int) -> jax.Array:
    """int8 tile -> f32; int4 tile additionally unpacks 2 nibbles/byte along
    axis 0 (the contraction axis — qarrays packs along ``reduce_axes[0]``),
    matching ``qarrays._unpack_int4`` bit-for-bit."""
    if bits == 8:
        return tile.astype(jnp.float32)
    qm = tile.astype(jnp.int32) & 0xFF
    lo = qm & 0xF
    hi = (qm >> 4) & 0xF
    lo = lo - 16 * (lo > 7)
    hi = hi - 16 * (hi > 7)
    n, m = tile.shape
    return jnp.stack([lo, hi], axis=1).reshape(n * 2, m).astype(jnp.float32)


def _grouped_mlp_quant_kernel(te_ref, x_ref, *refs, bits, act):
    del te_ref
    if act == "swiglu":
        wi_ref, wis_ref, wg_ref, wgs_ref, wo_ref, wos_ref, o_ref = refs
    else:
        wi_ref, wis_ref, wo_ref, wos_ref, o_ref = refs
    f = pl.program_id(1)

    @pl.when(f == 0)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...]  # [BT, D]
    wi = _widen(wi_ref[0], bits) * wis_ref[0]  # [D, BF] * [1, BF]
    h = _dot(x, wi.astype(x.dtype))
    g = None
    if act == "swiglu":
        wg = _widen(wg_ref[0], bits) * wgs_ref[0]
        g = _dot(x, wg.astype(x.dtype))
    a = _act(h, g, act).astype(x.dtype)
    wo = _widen(wo_ref[0], bits) * wos_ref[0]  # [BF, D] * [1, D]
    o_ref[...] += _dot(a, wo.astype(x.dtype)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bits", "act", "interpret", "block_f"))
def grouped_mlp_quant_kernel(
    xg: jax.Array,  # [Ct, D]
    te: jax.Array,  # [Ct / BT] int32
    wi_q: jax.Array,  # [E, D(/2), F] int8 (contraction axis packed when int4)
    wi_s: jax.Array,  # [E, 1, F] f32
    wg_q,             # like wi_q for act="swiglu", else None
    wg_s,
    wo_q: jax.Array,  # [E, F(/2), D] int8
    wo_s: jax.Array,  # [E, 1, D] f32
    *,
    bits: int,
    act: str,
    interpret: bool,
    block_f: int = BLOCK_F,
) -> jax.Array:
    Ct, D = xg.shape
    nt = te.shape[0]
    F = wi_q.shape[-1]
    bt = Ct // nt
    bf = min(block_f, F)
    assert Ct % nt == 0 and F % bf == 0, (Ct, nt, F, bf)
    pack = 2 if bits == 4 else 1
    assert D % pack == 0 and bf % pack == 0, (D, bf, pack)
    assert (wg_q is not None) == (act == "swiglu"), (act, wg_q is None)

    up = [
        pl.BlockSpec((1, D // pack, bf), lambda t, f, te: (te[t], 0, f)),
        pl.BlockSpec((1, 1, bf), lambda t, f, te: (te[t], 0, f)),
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nt, F // bf),
        in_specs=[
            pl.BlockSpec((bt, D), lambda t, f, te: (t, 0)),
            *(up * (2 if wg_q is not None else 1)),
            # wo is packed along F: block index f over packed rows of size
            # bf/pack covers exactly the unpacked slice [f*bf, (f+1)*bf)
            pl.BlockSpec((1, bf // pack, D), lambda t, f, te: (te[t], f, 0)),
            pl.BlockSpec((1, 1, D), lambda t, f, te: (te[t], 0, 0)),
        ],
        out_specs=pl.BlockSpec((bt, D), lambda t, f, te: (t, 0)),
    )
    gate = (wg_q, wg_s) if wg_q is not None else ()
    out = pl.pallas_call(
        functools.partial(_grouped_mlp_quant_kernel, bits=bits, act=act),
        name="grouped_mlp_quant_kernel",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Ct, D), jnp.float32),
        interpret=interpret,
    )(te, xg, wi_q, wi_s, *gate, wo_q, wo_s)
    return out.astype(xg.dtype)


def grouped_kernel_unsupported(wi, wg, wo, act: str, *, block_f: int = BLOCK_F):
    """Why the grouped kernels cannot take this expert layout, or None.

    They take fp weights, or QuantizedArrays at 8 or 4 bits with
    per-output-channel scales (group_size == 0; int4 nibble-unpacks in
    VMEM), with a gate projection exactly when the act is SwiGLU and a d_ff
    the ``block_f`` tile divides (even, for nibble packing).  Token-tile
    divisibility is guaranteed by the dispatch layout (Ct is a tile
    multiple by construction)."""
    if act not in ("swiglu", "gelu", "relu"):
        return f"act {act!r} is not one of swiglu/gelu/relu"
    if (wg is not None) != (act == "swiglu"):
        return f"act {act!r} with{'out' if wg is None else ''} a gate projection"
    qs = (wi, wo) if wg is None else (wi, wg, wo)
    n_quant = sum(isinstance(q, QuantizedArray) for q in qs)
    if n_quant not in (0, len(qs)):
        return "a mix of quantized and fp expert weights"
    F = wi.shape[-1]
    bf = min(block_f, F)
    if F % bf:
        return f"d_ff {F} is not a multiple of the {bf}-wide block"
    if not n_quant:
        return None
    if not all(q.bits in (8, 4) and q.group_size == 0 for q in qs):
        return (f"bits={wi.bits}, group_size={wi.group_size}: the kernel needs "
                "int8/int4 per-output-channel scales (group_size=0)")
    if any(q.bits != wi.bits for q in qs):
        return "expert weights quantized at different bit widths"
    pack = 2 if wi.bits == 4 else 1
    if wo.shape[-1] % pack or bf % pack:
        return "int4 packing needs even d_model and block widths"
    return None


def grouped_mlp_quant(
    xg: jax.Array,
    te: jax.Array,
    wi: QuantizedArray,
    wg,
    wo: QuantizedArray,
    *,
    act: str,
    interpret: bool,
) -> jax.Array:
    """Kernel entry from QuantizedArray leaves (int8/int4 per-channel);
    ``wg`` is the gate QuantizedArray for SwiGLU, None otherwise."""
    reason = grouped_kernel_unsupported(wi, wg, wo, act)
    if reason is not None:
        raise ValueError(f"grouped_mlp_quant kernel cannot take this layout: {reason}")
    return grouped_mlp_quant_kernel(
        xg, te, wi.q, wi.scale,
        wg.q if wg is not None else None, wg.scale if wg is not None else None,
        wo.q, wo.scale, bits=wi.bits, act=act, interpret=interpret,
    )


# ---------------------------------------------------------------------------
# references (pure-jnp oracles + CPU execution path)
# ---------------------------------------------------------------------------


def grouped_mlp_ref(
    xg: jax.Array,  # [Ct, D]
    te: jax.Array,  # [Ct / tile] int32
    wi: jax.Array,
    wg: jax.Array | None,
    wo: jax.Array,
    act: str = "swiglu",
) -> jax.Array:
    """Gather-einsum oracle: gather each tile's expert weights, batched GEMM
    over tiles (every tile materializes its expert's whole weights — a
    test oracle, not a serving path)."""
    Ct, D = xg.shape
    nt = te.shape[0]
    xt = xg.reshape(nt, Ct // nt, D)
    h = jnp.einsum("tcd,tdf->tcf", xt, wi[te], preferred_element_type=jnp.float32)
    if act == "swiglu":
        g = jnp.einsum("tcd,tdf->tcf", xt, wg[te], preferred_element_type=jnp.float32)
        h = jax.nn.silu(g) * h
    elif act == "gelu":
        h = jax.nn.gelu(h)
    else:
        h = jax.nn.relu(h)
    y = jnp.einsum("tcf,tfd->tcd", h.astype(xg.dtype), wo[te],
                   preferred_element_type=jnp.float32)
    return y.reshape(Ct, D).astype(xg.dtype)


def grouped_mlp_quant_ref(
    xg: jax.Array,
    te: jax.Array,
    wi: QuantizedArray,
    wg: QuantizedArray | None,
    wo: QuantizedArray,
    act: str = "swiglu",
) -> jax.Array:
    """Dequantize whole weights into the fp oracle (correctness reference for
    the quant kernel, and the CPU execution path in core/moe.py)."""
    return grouped_mlp_ref(
        xg, te, wi.dequantize(), wg.dequantize() if wg is not None else None,
        wo.dequantize(), act,
    )
