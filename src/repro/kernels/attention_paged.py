"""Paged decode attention (TPU Pallas): one query token attends over K/V
*pages* gathered through a block table.

The KV cache lives in HBM as one page pool per layer, stacked over the
layers of a segment position: ``[layers, n_pages+1, page_size, H_kv*dh]``
(last page = trash, never mapped); each sequence's history is the pages
named by its block-table row.  A page is ``page_size`` token rows, each row
one token's K (or V) for every kv-head side by side.  That layout is what
lets the kernel read the pool where it lies: its trailing ``(page_size,
H_kv*dh)`` dims are what the TPU tiles, so the device keeps the pool
page-major with no padding, whereas ``[..., H_kv, page_size, dh]`` pages
(dh = 64 < 128 lanes) would either double in HBM or be stored page-minor,
and every kernel call would then need a relayout copy of the pool.

The grid is (batch, table entry) with the table and the layer index
**scalar-prefetched** so the BlockSpec index map can pick each page of the
layer data-dependently: one grid step DMAs one whole page, every kv-head at
once (a contiguous ``[page_size, H_kv*dh]`` block), and the kernel never
materializes a gathered contiguous copy of the cache, nor a slice of the
layer's pool out of the stack.  The page axis is innermost (sequential on
TPU), so the online-softmax running max / normalizer / accumulator live in
VMEM scratch across pages, flash-attention style, with the kv-heads as the
batch dim of the dots (each head's ``dh`` lanes of the page rows, split in
VMEM).  The positions of each row's table pages are gathered from the
layer's position pool beside the call and ride as ``[1, page_size]``
blocks, and the query positions are scalar-prefetched with the table.

Composes with the int8 KV cache (kernels/attention_quant.py): when the pool
is quantized, each page's int8 K/V tile is widened and rescaled by its
per-(timestep, head) f32 scales (``[.., Pt, page_size, H_kv]``) *in VMEM*
right before the dot — pages then cost ~1 byte/entry of HBM traffic on top
of the fragmentation win.

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


def gather_pages(pool: jax.Array, table: jax.Array, layer=None) -> jax.Array:
    """Pool leaf + clamped table -> the contiguous history the table names.

    ``[Pt, ps, W]`` K/V/scale leaves give ``[*table.shape[:-1], nt*ps, W]``
    (``split_heads`` takes W apart); the ``[Pt, ps]`` position leaf gives
    ``[*table.shape[:-1], nt*ps]``.  With ``layer``, ``pool`` is the stack
    ``[layers, Pt, ...]`` and the pages are gathered from that layer's pool
    in one gather, with no slice of the layer's pool."""
    g = pool[table] if layer is None else pool[layer, table]  # [..., nt, ps, (W)]
    lead = table.shape[:-1]
    return g.reshape(lead + (-1,) + g.shape[table.ndim + 1:])


def page_rows(x: jax.Array) -> jax.Array:
    """Per-token ``[..., n_kv, dh]`` values (or ``[..., n_kv, 1]`` scales)
    -> the page pool's token rows ``[..., n_kv*dh]`` (``[..., n_kv]``)."""
    return x.reshape(x.shape[:-2] + (-1,))


def split_heads(rows: jax.Array, n_kv: int) -> jax.Array:
    """``[..., n_kv*dh]`` page rows -> ``[..., n_kv, dh]`` (scale rows
    ``[..., n_kv]`` -> ``[..., n_kv, 1]``)."""
    return rows.reshape(rows.shape[:-1] + (n_kv, -1))


def page_heads(rows: jax.Array, n_kv: int) -> jax.Array:
    """In-kernel ``split_heads`` of one page: ``[ps, n_kv*w]`` -> f32
    ``[n_kv, ps, w]``, heads leading for the batched dots.  Mosaic cannot
    reshape the lane dim, so each head's ``w`` lanes are sliced and
    stacked."""
    rows = rows.astype(jnp.float32)
    w = rows.shape[-1] // n_kv
    return jnp.stack([rows[:, h * w:(h + 1) * w] for h in range(n_kv)])


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


def _paged_kernel(qpos_ref, table_ref, layer_ref, *refs, scale, causal, window, softcap, nt,
                  n_kv, quantized):
    """Grid (B, nt); refs layout depends on ``quantized`` (scales present or
    not).  Scratch: running max / normalizer [Hkv, G, 1], accumulator
    [Hkv, G, dh]."""
    del table_ref, layer_ref  # consumed by the index maps
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
    k = page_heads(kq_ref[0], n_kv)  # [ps, Hkv*dh] -> [Hkv, ps, dh]
    v = page_heads(vq_ref[0], n_kv)
    if quantized:  # dequantize the page in VMEM
        k = k * page_heads(ks_ref[0], n_kv)  # [Hkv, ps, 1]
        v = v * page_heads(vs_ref[0], n_kv)
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
    kq: jax.Array,     # [L, Pt, ps, Hkv*dh] page pools (int8 if quantized, else fp)
    ks,                # [L, Pt, ps, Hkv] f32 scales, or None (fp pool)
    vq: jax.Array,     # [L, Pt, ps, Hkv*dh]
    vs,                # [L, Pt, ps, Hkv] or None
    kpos: jax.Array,   # [L, Pt, ps] int32 — absolute position per pool entry, -1 empty
    table: jax.Array,  # [B, nt] int32 — page ids; MUST be pre-clamped: -1 -> Pt-1
    qpos: jax.Array,   # [B, 1] int32 — the query token's absolute position
    layer: jax.Array,  # [] int32 — which of the L stacked pools to read
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
    L, Pt, ps, _ = kq.shape
    nt = table.shape[1]
    quantized = ks is not None

    kern = functools.partial(
        _paged_kernel,
        scale=scale, causal=causal, window=window, softcap=softcap,
        nt=nt, n_kv=Hkv, quantized=quantized,
    )
    # index maps get the prefetched (qpos, table, layer) refs appended; each
    # (b, t) step DMAs page table[b, t] of the layer's pool straight into
    # VMEM, the layer axis squeezed out of the block
    page = lambda b, t, qp, tref, lref: (lref[0], tref[b, t], 0, 0)
    row = lambda b, t, qp, tref, lref: (b, 0, 0, 0)
    in_specs = [pl.BlockSpec((1, Hkv, G, dh), row)]                        # q
    args = [q]
    for pool, scales in ((kq, ks), (vq, vs)):
        in_specs.append(pl.BlockSpec((None, 1, ps, Hkv * dh), page))       # page
        args.append(pool)
        if quantized:
            in_specs.append(pl.BlockSpec((None, 1, ps, Hkv), page))        # scales
            args.append(scales)
    # each row's table pages' positions, gathered from the layer's position
    # pool ([Pt, ps] lays out page-minor on the TPU, so a page-row block of
    # it would cost a relayout copy of the whole stack per call)
    in_specs.append(pl.BlockSpec((None, 1, 1, ps), lambda b, t, qp, tref, lref: (b, t, 0, 0)))
    args.append(kpos[layer][table].reshape(B, nt, 1, ps))                  # positions

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
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
        name="paged_decode_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, dh), q.dtype),
        interpret=interpret,
    )(qpos.reshape(B).astype(jnp.int32), table.astype(jnp.int32),
      jnp.reshape(layer, (1,)).astype(jnp.int32), *args)


def paged_decode_attention_ref(
    q, kq, ks, vq, vs, kpos, table, qpos, layer, *, scale, causal=True, window=0, softcap=0.0
):
    """Pure-jnp oracle: gather the layer's mapped pages into a contiguous
    [B, T] view (T = nt * ps), dequantize if needed, masked f32 softmax."""
    Hkv = q.shape[1]
    gather = lambda pool: gather_pages(pool, table, layer)  # pre-clamped table
    heads = lambda pool: split_heads(gather(pool), Hkv).astype(jnp.float32)
    k = heads(kq)  # [B, T, Hkv, dh]
    v = heads(vq)
    if ks is not None:
        k = k * heads(ks)
        v = v * heads(vs)
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
