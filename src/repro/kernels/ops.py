"""jit'd public wrappers around the Pallas kernels.

The kernels themselves take ``interpret`` with no default; these wrappers
are where the mode is decided: compiled by Mosaic on a TPU backend, the
Pallas interpreter elsewhere (correctness validation against the refs).
``fused_gating()`` returns a Gating namedtuple so the kernel drops into
core/moe.py transparently.
"""
from __future__ import annotations

import jax

from repro.core.gating import Gating
from repro.kernels.expert_mlp import expert_mlp_kernel
from repro.kernels.expert_mlp_quant import expert_mlp_quant
from repro.kernels.moe_gating import gating_kernel


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def fused_gating(logits: jax.Array, top_k: int, capacity: int, *, normalize: bool = True) -> Gating:
    eidx, w, pos, keep, probs = gating_kernel(
        logits, top_k, capacity, normalize=normalize, interpret=_interpret()
    )
    return Gating(eidx, w, pos, keep, probs)


def fused_expert_mlp(xe, wi, wg, wo):
    return expert_mlp_kernel(xe, wi, wg, wo, interpret=_interpret())


def fused_expert_mlp_quant(xe, wi, wg, wo):
    """wi/wg/wo: int8 per-output-channel QuantizedArrays — tiles dequantized
    in VMEM right before each MXU dot (kernels/expert_mlp_quant.py)."""
    return expert_mlp_quant(xe, wi, wg, wo, interpret=_interpret())


def fused_expert_mlp_grouped(xg, te, wi, wg, wo, *, act):
    """Dropless grouped expert MLP: ``xg`` [Ct, D] expert-sorted tile-padded
    tokens, ``te`` the scalar-prefetched tile->expert map
    (kernels/expert_mlp_grouped.py); ``wg`` only for act="swiglu"."""
    from repro.kernels.expert_mlp_grouped import grouped_mlp_kernel

    return grouped_mlp_kernel(xg, te, wi, wg, wo, act=act, interpret=_interpret())


def fused_expert_mlp_grouped_quant(xg, te, wi, wg, wo, *, act):
    """Dropless grouped expert MLP over int8/int4 QuantizedArrays — tiles
    dequantized (int4: nibble-unpacked) in VMEM before each MXU dot."""
    from repro.kernels.expert_mlp_grouped import grouped_mlp_quant

    return grouped_mlp_quant(xg, te, wi, wg, wo, act=act, interpret=_interpret())


def fused_decode_attention_quant(q, kq, ks, vq, vs, kpos, qpos, *, scale, causal, window, softcap):
    """Decode attention over an int8 KV cache — K/V tiles dequantized in
    VMEM right before the attention dots (kernels/attention_quant.py).
    Compiles natively on TPU; interpret mode elsewhere."""
    from repro.kernels.attention_quant import decode_attention_quant

    return decode_attention_quant(
        q, kq, ks, vq, vs, kpos, qpos,
        scale=scale, causal=causal, window=window, softcap=softcap,
        interpret=_interpret(),
    )


def fused_decode_attention_paged(q, kq, ks, vq, vs, kpos, table, qpos, *, scale, causal, window, softcap):
    """Decode attention over a paged KV pool: pages gathered via the
    scalar-prefetched block table inside the kernel, int8 pages dequantized
    in VMEM when ``ks``/``vs`` scales are given (kernels/attention_paged.py).
    ``table`` must be pre-clamped (-1 entries -> trash page)."""
    from repro.kernels.attention_paged import paged_decode_attention

    return paged_decode_attention(
        q, kq, ks, vq, vs, kpos, table, qpos,
        scale=scale, causal=causal, window=window, softcap=softcap,
        interpret=_interpret(),
    )


def fused_prefill_attention_paged(q, kq, ks, vq, vs, kpos, tables, qpos, ck, cv,
                                  *, scale, causal, window, softcap):
    """Chunked-prefill attention over a paged KV pool: each row's chunk of
    prompt queries attends to that sequence's already-written pages (earlier
    chunks, shared prefix pages) via the scalar-prefetched block tables PLUS
    its own in-flight fp K/V (kernels/attention_prefill_paged.py).
    ``tables`` must be pre-clamped (-1 entries -> trash page); the pool must
    be pre-write (the chunk's own positions still carry ``pos == -1``)."""
    from repro.kernels.attention_prefill_paged import paged_prefill_attention

    return paged_prefill_attention(
        q, kq, ks, vq, vs, kpos, tables, qpos, ck, cv,
        scale=scale, causal=causal, window=window, softcap=softcap,
        interpret=_interpret(),
    )
