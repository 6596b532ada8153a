"""Decode attention over an int8-quantized KV cache (TPU Pallas, validated
in interpret mode) — the kernel half of the quantized-KV serving path.

Decode is memory-bandwidth bound (DeepSpeed-MoE §5): each step streams the
whole K/V history from HBM to score one query token.  Here the cache lives
in HBM as int8 values + f32 per-(timestep, head) scales (quant/kv.py), and
each K/V tile is widened and rescaled *in VMEM* right before its dot — HBM
only ever carries 1-byte cache entries, which is the ~4x decode-traffic
reduction that buys batch-size headroom at long context.

Grid: (batch, kv-head, k-tiles); the k-tile axis is innermost (sequential on
TPU) so the online-softmax running max / normalizer / accumulator live in
VMEM scratch across tiles, flash-attention style.  GQA is handled by loading
the G = H/H_kv query rows of a kv-head as one [G, dh] tile.  Masking
(ring-slot validity, causality, sliding window) is computed in-kernel from
the cache's absolute-position array, so ring-buffer caches work unchanged.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_T = 128
NEG_INF = -1e30


def _soft_cap(s, cap: float):
    if cap and cap > 0.0:
        return jnp.tanh(s / cap) * cap
    return s


def _decode_quant_kernel(
    q_ref, kq_ref, ks_ref, vq_ref, vs_ref, kpos_ref, qpos_ref, o_ref,
    m_ref, l_ref, acc_ref,
    *, scale, causal, window, softcap, nk, bt,
):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    G, dh = q_ref.shape[-2], q_ref.shape[-1]
    q = q_ref[...].reshape(G, dh).astype(jnp.float32)  # [G, dh]
    # Dequantize the K tile in VMEM: int8 values * per-(timestep, head) scale.
    k = kq_ref[...].reshape(bt, dh).astype(jnp.float32) * ks_ref[...].reshape(bt, 1)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # [G, bt]
    s = _soft_cap(s, softcap)

    kp = kpos_ref[...].reshape(1, bt)  # absolute positions, -1 = empty slot
    qp = qpos_ref[0, 0]
    valid = kp >= 0
    if causal:
        valid = valid & (kp <= qp)
    if window > 0:
        valid = valid & (qp - kp < window)
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])  # [G, bt]
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
    v = vq_ref[...].reshape(bt, dh).astype(jnp.float32) * vs_ref[...].reshape(bt, 1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
        p, v, preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[...] = out.reshape(o_ref.shape).astype(o_ref.dtype)


def _fit(block: int, dim: int) -> int:
    b = min(block, dim)
    while dim % b:
        b //= 2
    return max(b, 1)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "causal", "window", "softcap", "interpret", "block_t"),
)
def decode_attention_quant(
    q: jax.Array,      # [B, Hkv, G, dh] — one decode token, grouped per kv-head
    kq: jax.Array,     # [B, T, Hkv, dh] int8
    ks: jax.Array,     # [B, T, Hkv, 1]  f32
    vq: jax.Array,     # [B, T, Hkv, dh] int8
    vs: jax.Array,     # [B, T, Hkv, 1]  f32
    kpos: jax.Array,   # [B, T] int32 — absolute position per slot, -1 empty
    qpos: jax.Array,   # [B, 1] int32 — the query token's absolute position
    *,
    scale: float,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    interpret: bool,
    block_t: int = BLOCK_T,
) -> jax.Array:
    """Returns [B, Hkv, G, dh] attention output in q.dtype."""
    B, Hkv, G, dh = q.shape
    T = kq.shape[1]
    bt = _fit(block_t, T)
    nk = T // bt

    kern = functools.partial(
        _decode_quant_kernel,
        scale=scale, causal=causal, window=window, softcap=softcap, nk=nk, bt=bt,
    )
    return pl.pallas_call(
        kern,
        name="decode_attention_quant",
        grid=(B, Hkv, nk),
        in_specs=[
            pl.BlockSpec((1, 1, G, dh), lambda b, h, t: (b, h, 0, 0)),
            pl.BlockSpec((1, bt, 1, dh), lambda b, h, t: (b, t, h, 0)),
            pl.BlockSpec((1, bt, 1, 1), lambda b, h, t: (b, t, h, 0)),
            pl.BlockSpec((1, bt, 1, dh), lambda b, h, t: (b, t, h, 0)),
            pl.BlockSpec((1, bt, 1, 1), lambda b, h, t: (b, t, h, 0)),
            pl.BlockSpec((1, bt), lambda b, h, t: (b, t)),
            pl.BlockSpec((1, 1), lambda b, h, t: (b, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, dh), lambda b, h, t: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G,), jnp.float32),      # running max
            pltpu.VMEM((G,), jnp.float32),      # running normalizer
            pltpu.VMEM((G, dh), jnp.float32),   # output accumulator
        ],
        interpret=interpret,
    )(q, kq, ks, vq, vs, kpos, qpos)


def decode_attention_quant_ref(
    q, kq, ks, vq, vs, kpos, qpos, *, scale, causal=True, window=0, softcap=0.0
):
    """Pure-jnp oracle: dequantize the whole cache, masked f32 softmax."""
    B, Hkv, G, dh = q.shape
    k = kq.astype(jnp.float32) * ks  # [B, T, Hkv, dh]
    v = vq.astype(jnp.float32) * vs
    s = jnp.einsum("bhgd,bthd->bhgt", q.astype(jnp.float32), k) * scale
    s = _soft_cap(s, softcap)
    kp = kpos[:, None, None, :]  # [B, 1, 1, T]
    qp = qpos[:, :, None, None].astype(jnp.int32)  # [B, 1, 1, 1]
    valid = kp >= 0
    if causal:
        valid = valid & (kp <= qp)
    if window > 0:
        valid = valid & (qp - kp < window)
    s = jnp.where(valid, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgt,bthd->bhgd", p, v)
    return out.astype(q.dtype)
