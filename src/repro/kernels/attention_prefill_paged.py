"""Chunked prefill attention over a paged KV pool (TPU Pallas, validated in
interpret mode): a page-aligned *chunk* of prompt queries attends causally to

  1. every page the sequence has already written — earlier prefill chunks
     plus any prefix pages SHARED from other sequences — gathered through the
     scalar-prefetched block table, exactly like the decode kernel
     (kernels/attention_paged.py), and
  2. the chunk's own in-flight K/V, still full precision, with the causal
     mask applied inside the chunk.

This is the kernel that removes the temp-contiguous-then-scatter admission
path: the scheduler maps the prompt's pages up front, each chunk's K/V is
written straight into its destination pages after this kernel reads the
*pre-write* pool, and a prefix-sharing admission starts its first chunk at
``shared_len`` — the shared pages are read in place, never recomputed, so
sharing saves the prefill FLOPs as well as the pages.

Composes with the int8 KV cache the same way decode does: quantized pages
are widened and rescaled by their per-(timestep, head) f32 scales in VMEM
right before the dot.  The chunk's own K/V arrives unquantized (it has not
been written yet), so intra-chunk attention is always full precision.

Grid is (row, table entry + 1), one row per mid-prefill slot: the page
axis is innermost (sequential on TPU) with the online-softmax running max /
normalizer / accumulator in VMEM scratch, flash-attention style, batched
over kv-heads like the decode kernel (one step = one whole page, every
head); the extra final step processes the in-flight chunk tile.  Unlike
decode, a prefill chunk routinely sees *fully masked* tiles before any
valid key (the pool is empty on the first chunk of an unshared admission),
so the probability tile is explicitly zeroed where masked —
``exp(NEG_INF - NEG_INF) == 1`` would otherwise pollute the normalizer
while the running max is still at its initial value.

Invariants the wrapper relies on (enforced by tests/test_chunked.py):

  * ``table`` is pre-clamped (-1 -> trash page, whose ``pos`` is pinned -1);
  * pages of not-yet-written positions carry ``pos == -1`` (freshly
    allocated or recycled via ``paged_reset_pages``), so causal masking
    falls out of the pool's position array with no extra bookkeeping;
  * pool keys at positions >= the chunk start are masked in-kernel: they can
    only be shared-prefix pages being *recomputed* (archs whose window-ring
    or SSM/LRU per-slot state forces the prefix compute) — those positions
    are in flight in the chunk tile, and each key is counted exactly once.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.attention_paged import (
    _QK,
    NEG_INF,
    _soft_cap,
    gather_pages,
    online_softmax_update,
)

# The last grid step holds a [Hkv, C*G, C] f32 score tile next to the
# [Hkv, C*G, dh] accumulator; at C = 256, 16 kv-heads that is past Mosaic's
# 16 MiB default scoped-VMEM budget (v5e has 128 MiB of VMEM).
VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def _prefill_kernel(start_ref, table_ref, *refs, scale, causal, window, softcap, nt, quantized):
    """Grid (B, nt + 1); steps 0..nt-1 stream pool pages via the prefetched
    table, step nt processes the chunk's in-flight K/V and finalizes."""
    del table_ref  # consumed by the index maps
    if quantized:
        (q_ref, qpc_ref, qpr_ref, kq_ref, ks_ref, vq_ref, vs_ref, kpos_ref,
         ck_ref, cv_ref, o_ref, m_ref, l_ref, acc_ref) = refs
    else:
        (q_ref, qpc_ref, qpr_ref, kq_ref, vq_ref, kpos_ref,
         ck_ref, cv_ref, o_ref, m_ref, l_ref, acc_ref) = refs
    b = pl.program_id(0)
    it = pl.program_id(1)

    @pl.when(it == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)  # [Hkv, C*G, dh], rows c-major
    qp = qpc_ref[0]  # [C*G, 1] per-query positions (-1 = invalid row)

    def update(k, v, kp):
        """k/v: [Hkv, T, dh] f32; kp: [1, T] absolute positions (-1 = empty)."""
        s = jax.lax.dot_general(q, k, _QK, preferred_element_type=jnp.float32) * scale
        s = _soft_cap(s, softcap)  # [Hkv, C*G, T]
        valid = kp >= 0
        if causal:
            valid = valid & (kp <= qp)
        if window > 0:
            valid = valid & (qp - kp < window)
        online_softmax_update(m_ref, l_ref, acc_ref, s, valid[None], v)

    @pl.when(it < nt)
    def _page_tile():
        k = kq_ref[0].astype(jnp.float32)  # [Hkv, ps, dh]
        v = vq_ref[0].astype(jnp.float32)
        if quantized:  # dequantize the page in VMEM
            k = k * ks_ref[0]
            v = v * vs_ref[0]
        # pool history is STRICTLY pre-chunk: when a shared-prefix admission
        # recomputes the prefix (rebuilding window-ring/SSM state), those
        # positions are live in shared pages AND in flight — mask the pool
        # copy so each key is counted exactly once
        kp = kpos_ref[0]  # [1, ps]
        kp = jnp.where(kp >= start_ref[b], -1, kp)
        update(k, v, kp)

    @pl.when(it == nt)
    def _chunk_tile_and_finalize():
        # the chunk's keys sit at the query positions themselves
        update(ck_ref[0].astype(jnp.float32), cv_ref[0].astype(jnp.float32), qpr_ref[0])
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "causal", "window", "softcap", "interpret"),
)
def paged_prefill_attention(
    q: jax.Array,       # [B, C, Hkv, G, dh] — one chunk of prompt queries per row
    kq: jax.Array,      # [Pt, Hkv, ps, dh] page pool (int8 if quantized, else fp)
    ks,                 # [Pt, Hkv, ps, 1] f32 scales, or None (fp pool)
    vq: jax.Array,      # [Pt, Hkv, ps, dh]
    vs,                 # [Pt, Hkv, ps, 1] or None
    kpos: jax.Array,    # [Pt, ps] int32 — absolute position per pool entry, -1 empty
    tables: jax.Array,  # [B, nt] int32 — each row's page ids; pre-clamped: -1 -> Pt-1
    qpos: jax.Array,    # [B, C] int32 — the chunk tokens' absolute positions, -1 invalid
    ck: jax.Array,      # [B, C, Hkv, dh] — the chunk's in-flight (fp) keys
    cv: jax.Array,      # [B, C, Hkv, dh] — the chunk's in-flight (fp) values
    *,
    scale: float,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    interpret: bool,
) -> jax.Array:
    """Returns [B, C, Hkv, G, dh] attention output in q.dtype.  Each row's
    pool history is masked at positions >= its own chunk start
    (``qpos[:, 0]``; a row whose chunk starts at -1 sees no history)."""
    B, C, Hkv, G, dh = q.shape
    Pt, _, ps, _ = kq.shape
    nt = tables.shape[1]
    CG = C * G
    quantized = ks is not None
    qpos = qpos.astype(jnp.int32)
    # pad the prefetched table with one trash entry so the chunk step's page
    # index maps stay in range (their DMA result is unused)
    tbl = jnp.concatenate(
        [tables.astype(jnp.int32), jnp.full((B, 1), Pt - 1, jnp.int32)], axis=1)
    # heads lead every block so each trails with (rows, dh)
    qh = q.transpose(0, 2, 1, 3, 4).reshape(B, Hkv, CG, dh)
    q_col = jnp.repeat(qpos, G, axis=1).reshape(B, CG, 1)
    q_row = qpos.reshape(B, 1, C)

    kern = functools.partial(
        _prefill_kernel,
        scale=scale, causal=causal, window=window, softcap=softcap,
        nt=nt, quantized=quantized,
    )
    page = lambda b, t, st, tref: (tref[b, t], 0, 0, 0)
    row4 = lambda b, t, st, tref: (b, 0, 0, 0)
    row3 = lambda b, t, st, tref: (b, 0, 0)
    in_specs = [
        pl.BlockSpec((1, Hkv, CG, dh), row4),                        # q
        pl.BlockSpec((1, CG, 1), row3),                              # query positions
        pl.BlockSpec((1, 1, C), row3),                               # chunk key positions
    ]
    args = [qh, q_col, q_row]
    for pool, scales in ((kq, ks), (vq, vs)):
        in_specs.append(pl.BlockSpec((1, Hkv, ps, dh), page))        # page
        args.append(pool)
        if quantized:
            in_specs.append(pl.BlockSpec((1, Hkv, ps, 1), page))     # scales
            args.append(scales)
    in_specs.append(pl.BlockSpec((1, 1, ps), lambda b, t, st, tref: (tref[b, t], 0, 0)))
    args.append(kpos.reshape(Pt, 1, ps))                             # pool positions
    for chunk in (ck, cv):
        in_specs.append(pl.BlockSpec((1, Hkv, C, dh), row4))         # in-flight K/V
        args.append(chunk.transpose(0, 2, 1, 3))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, nt + 1),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Hkv, CG, dh), row4),
        scratch_shapes=[
            pltpu.VMEM((Hkv, CG, 1), jnp.float32),    # running max
            pltpu.VMEM((Hkv, CG, 1), jnp.float32),    # running normalizer
            pltpu.VMEM((Hkv, CG, dh), jnp.float32),   # output accumulator
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, CG, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(qpos[:, 0], tbl, *args)
    return out.reshape(B, Hkv, C, G, dh).transpose(0, 2, 1, 3, 4)


def paged_prefill_attention_ref(
    q, kq, ks, vq, vs, kpos, tables, qpos, ck, cv,
    *, scale, causal=True, window=0, softcap=0.0,
):
    """Pure-jnp oracle: gather each row's mapped pages into a contiguous
    history, append the chunk's in-flight K/V, masked f32 softmax over the
    union."""
    gather = lambda pool: gather_pages(pool, tables)  # pre-clamped tables
    k = gather(kq).astype(jnp.float32)
    v = gather(vq).astype(jnp.float32)
    if ks is not None:
        k = k * gather(ks)
        v = v * gather(vs)
    k = jnp.concatenate([k, ck.astype(jnp.float32)], axis=1)  # [B, T, Hkv, dh]
    v = jnp.concatenate([v, cv.astype(jnp.float32)], axis=1)
    qpos = qpos.astype(jnp.int32)
    hist = gather(kpos)
    hist = jnp.where(hist >= qpos[:, :1], -1, hist)  # pool = strictly pre-chunk
    kp = jnp.concatenate([hist, qpos], axis=1)[:, None, :]  # [B, 1, T]

    s = jnp.einsum("bchgd,bthd->bhgct", q.astype(jnp.float32), k) * scale
    s = _soft_cap(s, softcap)
    qp = qpos[:, :, None]  # [B, C, 1]
    valid = kp >= 0
    if causal:
        valid = valid & (kp <= qp)
    if window > 0:
        valid = valid & (qp - kp < window)
    s = jnp.where(valid[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgct,bthd->bchgd", p, v)
    return out.astype(q.dtype)
