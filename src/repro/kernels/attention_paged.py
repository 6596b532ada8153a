"""Paged decode attention (TPU Pallas): one query token attends over K/V
*pages* gathered through a block table.

The KV cache lives in HBM as a shared page pool ``[n_pages+1, H_kv,
page_size, dh]`` (last page = trash, never mapped); each sequence's history
is the pages named by its block-table row.  The grid is (batch, table entry)
with the table **scalar-prefetched** so the BlockSpec index map can pick
each page data-dependently: one grid step DMAs one whole page, every kv-head
at once (a contiguous ``[H_kv, page_size, dh]`` block), and the kernel never
materializes a gathered contiguous copy of the cache.  The page axis is
innermost (sequential on TPU), so the online-softmax running max /
normalizer / accumulator live in VMEM scratch across pages,
flash-attention style, with the kv-heads as the batch dim of the dots.

The layout is chosen for Mosaic's tiling rule (a block's last two dims are
multiples of (8, 128) or the array's own): ``(page_size, dh)`` trails every
K/V block, positions ride as ``[Pt, 1, page_size]`` blocks, and the query
positions are scalar-prefetched with the table.

Composes with the int8 KV cache (kernels/attention_quant.py): when the pool
is quantized, each page's int8 K/V tile is widened and rescaled by its
per-(head, timestep) f32 scales *in VMEM* right before the dot — pages then
cost ~1 byte/entry of HBM traffic on top of the fragmentation win.

Masking (unmapped-page validity, causality, sliding window) is computed
in-kernel from the pool's absolute-position array: the trash page is pinned
at ``pos == -1`` so -1 table entries (pre-clamped to the trash page by the
wrapper) contribute nothing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# batched-over-heads contractions: [H, M, d] x [H, N, d] -> [H, M, N] and
# [H, M, N] x [H, N, d] -> [H, M, d]
_QK = (((2,), (2,)), ((0,), (0,)))
_PV = (((2,), (1,)), ((0,), (0,)))


def _soft_cap(s, cap: float):
    if cap and cap > 0.0:
        return jnp.tanh(s / cap) * cap
    return s


def gather_pages(pool: jax.Array, table: jax.Array) -> jax.Array:
    """Pool leaf + clamped table -> the contiguous history the table names.

    ``[Pt, Hkv, ps, ...]`` K/V/scale leaves give ``[*table.shape[:-1],
    nt*ps, Hkv, ...]``; the ``[Pt, ps]`` position leaf gives
    ``[*table.shape[:-1], nt*ps]``."""
    g = pool[table]  # [..., nt, (Hkv,) ps, ...]
    lead = table.shape[:-1]
    nt = table.shape[-1]
    if pool.ndim >= 4:
        g = jnp.swapaxes(g, table.ndim, table.ndim + 1)  # [..., nt, ps, Hkv, ...]
        return g.reshape(lead + (nt * pool.shape[2],) + g.shape[table.ndim + 1:])
    return g.reshape(lead + (nt * pool.shape[1],) + g.shape[table.ndim + 1:])


def online_softmax_update(m_ref, l_ref, acc_ref, s, valid, v):
    """One flash-attention step over a key tile, batched over kv-heads.

    s: [H, M, N] f32 scores; valid: mask broadcastable to s; v: [H, N, dh]
    f32.  Masked entries are zeroed explicitly: on a fully-masked tile seen
    before any valid key the running max is still NEG_INF, and
    exp(NEG_INF - NEG_INF) == 1 would count every masked key into the
    normalizer."""
    s = jnp.where(valid, s, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, _PV, preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def _paged_kernel(qpos_ref, table_ref, *refs, scale, causal, window, softcap, nt, quantized):
    """Grid (B, nt); refs layout depends on ``quantized`` (scales present or
    not).  Scratch: running max / normalizer [Hkv, G, 1], accumulator
    [Hkv, G, dh]."""
    del table_ref  # consumed by the index maps
    if quantized:
        (q_ref, kq_ref, ks_ref, vq_ref, vs_ref, kpos_ref,
         o_ref, m_ref, l_ref, acc_ref) = refs
    else:
        (q_ref, kq_ref, vq_ref, kpos_ref, o_ref, m_ref, l_ref, acc_ref) = refs
    b = pl.program_id(0)
    it = pl.program_id(1)

    @pl.when(it == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)  # [Hkv, G, dh]
    k = kq_ref[0].astype(jnp.float32)  # [Hkv, ps, dh]
    v = vq_ref[0].astype(jnp.float32)
    if quantized:  # dequantize the page in VMEM
        k = k * ks_ref[0]
        v = v * vs_ref[0]
    s = jax.lax.dot_general(q, k, _QK, preferred_element_type=jnp.float32) * scale
    s = _soft_cap(s, softcap)  # [Hkv, G, ps]

    kp = kpos_ref[0]  # [1, ps] absolute positions, -1 = empty
    qp = qpos_ref[b]
    valid = kp >= 0
    if causal:
        valid = valid & (kp <= qp)
    if window > 0:
        valid = valid & (qp - kp < window)
    online_softmax_update(m_ref, l_ref, acc_ref, s, valid[None], v)

    @pl.when(it == nt - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "causal", "window", "softcap", "interpret"),
)
def paged_decode_attention(
    q: jax.Array,      # [B, Hkv, G, dh] — one decode token, grouped per kv-head
    kq: jax.Array,     # [Pt, Hkv, ps, dh] page pool (int8 if quantized, else fp)
    ks,                # [Pt, Hkv, ps, 1] f32 scales, or None (fp pool)
    vq: jax.Array,     # [Pt, Hkv, ps, dh]
    vs,                # [Pt, Hkv, ps, 1] or None
    kpos: jax.Array,   # [Pt, ps] int32 — absolute position per pool entry, -1 empty
    table: jax.Array,  # [B, nt] int32 — page ids; MUST be pre-clamped: -1 -> Pt-1
    qpos: jax.Array,   # [B, 1] int32 — the query token's absolute position
    *,
    scale: float,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    interpret: bool,
) -> jax.Array:
    """Returns [B, Hkv, G, dh] attention output in q.dtype.

    ``interpret`` has no default: True runs the kernel body in the Pallas
    interpreter (any backend), False compiles it with Mosaic (TPU only).

    Caller contract (``tests/test_paged.py::TestPagedKernel`` checks the
    masking consequences against the einsum ref):

      * ``table`` is pre-clamped — -1 (unmapped) entries replaced by the
        trash page id ``Pt - 1``, whose ``kpos`` row is pinned at -1 so it
        contributes nothing;
      * ``kpos`` is -1 for every never/no-longer-valid pool entry (freshly
        allocated and recycled pages are invalidated by
        ``models.model.paged_reset_pages`` — a stale position <= the query's
        would otherwise unmask the previous occupant's K/V);
      * fully masked tiles are explicitly zeroed out of the normalizer, so
        trash-only rows (inactive slots) return garbage-but-finite output
        that the scheduler discards."""
    B, Hkv, G, dh = q.shape
    Pt, _, ps, _ = kq.shape
    nt = table.shape[1]
    quantized = ks is not None

    kern = functools.partial(
        _paged_kernel,
        scale=scale, causal=causal, window=window, softcap=softcap,
        nt=nt, quantized=quantized,
    )
    # index maps get the prefetched (qpos, table) refs appended; each (b, t)
    # step DMAs page table[b, t] of the pool straight into VMEM
    page = lambda b, t, qp, tref: (tref[b, t], 0, 0, 0)
    row = lambda b, t, qp, tref: (b, 0, 0, 0)
    in_specs = [pl.BlockSpec((1, Hkv, G, dh), row)]                        # q
    args = [q]
    for pool, scales in ((kq, ks), (vq, vs)):
        in_specs.append(pl.BlockSpec((1, Hkv, ps, dh), page))              # page
        args.append(pool)
        if quantized:
            in_specs.append(pl.BlockSpec((1, Hkv, ps, 1), page))           # scales
            args.append(scales)
    in_specs.append(pl.BlockSpec((1, 1, ps), lambda b, t, qp, tref: (tref[b, t], 0, 0)))
    args.append(kpos.reshape(Pt, 1, ps))                                   # positions

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, nt),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Hkv, G, dh), row),
        scratch_shapes=[
            pltpu.VMEM((Hkv, G, 1), jnp.float32),    # running max
            pltpu.VMEM((Hkv, G, 1), jnp.float32),    # running normalizer
            pltpu.VMEM((Hkv, G, dh), jnp.float32),   # output accumulator
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, dh), q.dtype),
        interpret=interpret,
    )(qpos.reshape(B).astype(jnp.int32), table.astype(jnp.int32), *args)


def paged_decode_attention_ref(
    q, kq, ks, vq, vs, kpos, table, qpos, *, scale, causal=True, window=0, softcap=0.0
):
    """Pure-jnp oracle: gather the mapped pages into a contiguous [B, T]
    view (T = nt * ps), dequantize if needed, masked f32 softmax."""
    gather = lambda pool: gather_pages(pool, table)  # pre-clamped table
    k = gather(kq).astype(jnp.float32)
    v = gather(vq).astype(jnp.float32)
    if ks is not None:
        k = k * gather(ks)
        v = v * gather(vs)
    s = jnp.einsum("bhgd,bthd->bhgt", q.astype(jnp.float32), k) * scale
    s = _soft_cap(s, softcap)
    kp = gather(kpos)[:, None, None, :]  # [B, 1, 1, T]
    qp = qpos[:, :, None, None].astype(jnp.int32)
    valid = kp >= 0
    if causal:
        valid = valid & (kp <= qp)
    if window > 0:
        valid = valid & (qp - kp < window)
    s = jnp.where(valid, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgt,bthd->bhgd", p, v)
    return out.astype(q.dtype)
