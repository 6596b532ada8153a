"""Flash-attention Pallas kernel (TPU target, validated in interpret mode).

This is the kernel that justifies the roofline accounting's score-tensor
exclusion (EXPERIMENTS.md §Roofline): the [Bq, Bk] logit/softmax tiles live
entirely in VMEM scratch; HBM sees only Q/K/V streaming (K/V re-read once
per query block — exactly what the analyzer counts via dot operands) and a
single O write.

Grid (batch·heads, q-blocks, k-blocks), k innermost (sequential on TPU) so
the online-softmax running max / normalizer / accumulator carry across k
tiles in VMEM scratch; the output tile is written once on the last k step.
Block shapes default to 128/256 — MXU-aligned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_Q = 128
BLOCK_K = 256
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *, scale, causal, nk):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)  # [Bq, dh]
    k = k_ref[0].astype(jnp.float32)  # [Bk, dh]
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # [Bq, Bk]

    if causal:
        iq = pl.program_id(1)
        bq, bk = q.shape[0], k.shape[0]
        qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(kpos <= qpos, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])  # [Bq, Bk]
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
        p.astype(v_ref.dtype), v_ref[0], preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[:, None])[None].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "scale", "interpret", "block_q", "block_k"))
def flash_attention(
    q: jax.Array,  # [BH, S, dh]
    k: jax.Array,  # [BH, T, dh]
    v: jax.Array,  # [BH, T, dh]
    *,
    scale: float,
    causal: bool = True,
    interpret: bool,
    block_q: int = BLOCK_Q,
    block_k: int = BLOCK_K,
) -> jax.Array:
    BH, S, dh = q.shape
    T = k.shape[1]
    def _fit(block, dim):
        b = min(block, dim)
        while dim % b:
            b //= 2
        return max(b, 1)

    bq = _fit(block_q, S)
    bk = _fit(block_k, T)
    nq, nk = S // bq, T // bk

    kern = functools.partial(_flash_kernel, scale=scale, causal=causal, nk=nk)
    return pl.pallas_call(
        kern,
        name="flash_attention",
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, dh), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, bk, dh), lambda bh, iq, ik: (bh, ik, 0)),
            pl.BlockSpec((1, bk, dh), lambda bh, iq, ik: (bh, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, dh), lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),  # running max
            pltpu.VMEM((bq,), jnp.float32),  # running normalizer
            pltpu.VMEM((bq, dh), jnp.float32),  # output accumulator
        ],
        interpret=interpret,
    )(q, k, v)


def flash_attention_ref(q, k, v, *, scale: float, causal: bool = True):
    """Pure-jnp oracle."""
    s = jnp.einsum("bsd,btd->bst", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    if causal:
        S, T = s.shape[-2:]
        mask = jnp.tril(jnp.ones((S, T), bool), k=T - S)
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bst,btd->bsd", p.astype(v.dtype), v).astype(q.dtype)
