"""GQA attention with global / sliding-window / cross variants, KV caches
(full and ring-buffer window), and query-chunked computation so 32k-prefill
fits device memory and *local* layers cost O(S·W) FLOPs rather than O(S²).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import AttnSpec, ModelConfig
from repro.kernels.attention_paged import gather_pages
from repro.models.modules import apply_rope, dense_init, init_rmsnorm, rmsnorm, softcap
from repro.parallel.sharding import shard_hint
from repro.quant.kv import QuantizedKV, kv_quantize_values, materialize_kv
from repro.quant.qarrays import materialize

NEG_INF = -1e30

# Query-chunk size for long-sequence attention (multiple of 128 for MXU).
Q_CHUNK = 1024


def _context_parallel_size(cfg) -> int:
    """>1 when attention must be distributed over 'model' via the query
    sequence because the head count doesn't divide the TP axis."""
    from repro.parallel.sharding import get_mesh

    mesh = get_mesh()
    if mesh is None:
        return 1
    tp = dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)
    if tp > 1 and cfg.num_heads % tp != 0:
        return tp
    return 1


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------


def init_attention(key, cfg: ModelConfig, spec: AttnSpec, dtype) -> dict:
    ks = jax.random.split(key, 5)
    H, Hkv, dh, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.d_model
    p = {
        "wq": dense_init(ks[0], d, (H, dh), dtype),
        "wk": dense_init(ks[1], d, (Hkv, dh), dtype),
        "wv": dense_init(ks[2], d, (Hkv, dh), dtype),
        "wo": dense_init(ks[3], H * dh, d, dtype).reshape(H, dh, d),
    }
    if spec.qk_norm:
        p["q_norm"] = init_rmsnorm(dh, dtype)
        p["k_norm"] = init_rmsnorm(dh, dtype)
    return p


# ---------------------------------------------------------------------------
# KV caches
# ---------------------------------------------------------------------------


def init_kv_cache(batch: int, capacity: int, n_kv: int, head_dim: int, dtype, *, kv_bits: int = 0) -> dict:
    """Ring-buffer KV cache.  ``pos`` holds the absolute position stored in
    each slot (-1 = empty), which doubles as the validity/window mask source.
    A full-context cache is simply capacity == max_seq_len.

    ``kv_bits=8`` stores K/V as :class:`~repro.quant.kv.QuantizedKV` (int8
    values + f32 per-(timestep, head) scales, quantize-on-write): ~4x fewer
    cache bytes streamed per decode step, the §5 memory-bound lever after
    MoQ expert weights.  0 = full precision."""
    shape = (batch, capacity, n_kv, head_dim)
    if kv_bits == 8:
        k = QuantizedKV.zeros(shape, dtype)
        v = QuantizedKV.zeros(shape, dtype)
    elif kv_bits == 0:
        k = jnp.zeros(shape, dtype)
        v = jnp.zeros(shape, dtype)
    else:
        raise ValueError(f"kv_bits must be 0 (fp) or 8 (int8), got {kv_bits}")
    return {"k": k, "v": v, "pos": jnp.full((batch, capacity), -1, jnp.int32)}


def spec_is_paged(spec: AttnSpec) -> bool:
    """Whether a self-attention layer's cache goes into the shared page pool
    under paged serving.  Sliding-window layers keep per-slot rings — a ring
    of ``window`` tokens is already fixed-size and fragmentation-free, and
    paging it would buy nothing; paging targets the unbounded global-context
    caches whose worst-case reservation is what strands memory."""
    return not (spec.kind == "local" and spec.window > 0)


def init_paged_kv_cache(n_pages: int, page_size: int, n_kv: int, head_dim: int, dtype, *, kv_bits: int = 0) -> dict:
    """Shared page-pool KV cache: ``[n_pages + 1, n_kv, page_size, head_dim]``
    with NO batch axis — sequences own pages through per-slot block tables
    (serving/kv_pool.py) instead of reserving a contiguous capacity row.

    The extra last page is the *trash* page: never handed out by the
    allocator, its ``pos`` stays -1 forever.  Unmapped (-1) block-table
    entries are clamped to it on read (contributing nothing, masked by
    ``pos == -1``) and inactive-slot decode writes are routed into it, which
    is what lets the jitted decode step keep fully static shapes with no
    per-row masking of the pool.

    ``kv_bits=8`` stores pages as int8 :class:`~repro.quant.kv.QuantizedKV`
    — the two serving memory levers compose: ~4x fewer bytes per cache
    token × fragmentation-free packing of those tokens.

    Heads lead the page so one page of every head is one contiguous block
    whose trailing ``(page_size, head_dim)`` dims are what the Pallas page
    kernels' BlockSpecs tile (kernels/attention_paged.py)."""
    shape = (n_pages + 1, n_kv, page_size, head_dim)
    if kv_bits == 8:
        k = QuantizedKV.zeros(shape, dtype)
        v = QuantizedKV.zeros(shape, dtype)
    elif kv_bits == 0:
        k = jnp.zeros(shape, dtype)
        v = jnp.zeros(shape, dtype)
    else:
        raise ValueError(f"kv_bits must be 0 (fp) or 8 (int8), got {kv_bits}")
    return {"k": k, "v": v, "pos": jnp.full((n_pages + 1, page_size), -1, jnp.int32)}


def _write_kv(old, new_vals, write_fn):
    """Apply ``write_fn(buffer, values)`` to a cache tensor: directly for fp
    caches, to the (q, scale) pair for QuantizedKV (quantize-on-write — each
    token's scale is self-contained, so slot overwrites need no rescaling)."""
    if isinstance(old, QuantizedKV):
        q_new, s_new = kv_quantize_values(new_vals)
        return QuantizedKV(
            write_fn(old.q, q_new), write_fn(old.scale, s_new), old.orig_dtype
        )
    return write_fn(old, new_vals.astype(old.dtype))


def _cache_write_decode(cache: dict, k_new, v_new, index) -> dict:
    """Write one token per row at ring slot ``index % capacity``.
    index: [] int32 (uniform batch) or [B] int32 (ragged / continuous
    batching — each row at its own position)."""
    cap = cache["k"].shape[1]
    B = cache["k"].shape[0]
    if jnp.ndim(index) == 0:
        slot = jnp.mod(index, cap)
        write = lambda buf, vals: jax.lax.dynamic_update_slice_in_dim(buf, vals, slot, axis=1)
        k = _write_kv(cache["k"], k_new, write)
        v = _write_kv(cache["v"], v_new, write)
        pos = jax.lax.dynamic_update_slice_in_dim(
            cache["pos"], jnp.broadcast_to(index, (B, 1)).astype(jnp.int32), slot, axis=1
        )
        return {"k": k, "v": v, "pos": pos}
    # ragged: per-row batch-indexed scatter
    rows = jnp.arange(B)
    slot = jnp.mod(index.astype(jnp.int32), cap)  # [B]
    write = lambda buf, vals: buf.at[rows, slot].set(vals[:, 0])
    k = _write_kv(cache["k"], k_new, write)
    v = _write_kv(cache["v"], v_new, write)
    pos = cache["pos"].at[rows, slot].set(index.astype(jnp.int32))
    return {"k": k, "v": v, "pos": pos}


def _cache_write_prefill(cache: dict, k, v, positions) -> dict:
    """Fill the cache from a prefill of S tokens (positions [B, S]).  If the
    cache is a window ring (capacity < S) only the last ``capacity`` tokens
    are retained, laid out so slot == pos % capacity."""
    cap = cache["k"].shape[1]
    S = k.shape[1]
    if cap >= S:
        write = lambda buf, vals: jax.lax.dynamic_update_slice_in_dim(buf, vals, 0, axis=1)
        k_ = _write_kv(cache["k"], k, write)
        v_ = _write_kv(cache["v"], v, write)
        pos_ = jax.lax.dynamic_update_slice_in_dim(cache["pos"], positions.astype(jnp.int32), 0, axis=1)
        return {"k": k_, "v": v_, "pos": pos_}
    # keep last `cap` tokens; place token p at slot p % cap
    k_tail = k[:, S - cap :]
    v_tail = v[:, S - cap :]
    p_tail = positions[:, S - cap :].astype(jnp.int32)
    slots = jnp.mod(p_tail[0], cap)  # same for every batch row
    # `slots` is a permutation of 0..cap-1, so scattering into the existing
    # ring writes every slot — same result as rebuilding via gather, but the
    # old buffer stays live in the graph and the caller's donate_argnums can
    # alias it (a gather rebuild leaves the donated input unused: jax prunes
    # it and the donation is silently dropped for every window-ring layer)
    write = lambda buf, vals: buf.at[:, slots].set(vals)
    return {
        "k": _write_kv(cache["k"], k_tail, write),
        "v": _write_kv(cache["v"], v_tail, write),
        "pos": cache["pos"].at[:, slots].set(p_tail),
    }


# Process-wide default for decode over a quantized KV cache: None = auto
# (Pallas dequant-in-kernel on TPU, dequantize-into-_sdpa reference elsewhere
# — interpret-mode Pallas is a correctness tool, far too slow to serve from).
# "kernel" / "ref" force.  Mirrors core.moe.set_quant_expert_backend.
KV_QUANT_BACKEND = [None]


def set_kv_quant_backend(mode) -> None:
    """Test/benchmark knob; read at trace time (not part of jit cache keys),
    so switching drops all cached compilations."""
    assert mode in (None, "kernel", "ref"), mode
    if KV_QUANT_BACKEND[0] == mode:
        return
    KV_QUANT_BACKEND[0] = mode
    jax.clear_caches()


def _decode_attend_quant(q, cache: dict, row_pos, spec: AttnSpec, scale: float):
    """One-token decode over a QuantizedKV cache.  q: [B, 1, H, dh]."""
    mode = KV_QUANT_BACKEND[0]
    if mode is None:
        mode = "kernel" if jax.default_backend() == "tpu" else "ref"
    window = spec.window if spec.kind == "local" else 0
    if mode == "kernel":
        from repro.kernels.ops import fused_decode_attention_quant

        B, S, H, dh = q.shape
        Hkv = cache["k"].shape[2]
        qg = q[:, 0].reshape(B, Hkv, H // Hkv, dh)
        y = fused_decode_attention_quant(
            qg,
            cache["k"].q, cache["k"].scale, cache["v"].q, cache["v"].scale,
            cache["pos"], row_pos[:, None],
            scale=scale, causal=spec.causal, window=window,
            softcap=spec.logit_softcap,
        )
        return y.reshape(B, 1, H, dh)
    mask = _window_causal_mask(row_pos[:, None], cache["pos"], window, spec.causal)
    return _sdpa(
        q, materialize_kv(cache["k"]), materialize_kv(cache["v"]),
        mask, scale, spec.logit_softcap,
    )


# Process-wide default for decode over a *paged* KV pool: None = auto
# (Pallas block-table gather kernel on TPU, gather-into-_sdpa reference
# elsewhere).  "kernel" / "ref" force.  Mirrors set_kv_quant_backend.
PAGED_BACKEND = [None]


def set_paged_backend(mode) -> None:
    """Test/benchmark knob; read at trace time (not part of jit cache keys),
    so switching drops all cached compilations."""
    assert mode in (None, "kernel", "ref"), mode
    if PAGED_BACKEND[0] == mode:
        return
    PAGED_BACKEND[0] = mode
    jax.clear_caches()


def _over_slots(kernel, slot_args: tuple, pool_args: tuple):
    """``kernel(slot_args, pool_args)`` for a Pallas page kernel.  Mosaic
    kernels are not partitioned automatically, so under a serving mesh the
    call is a shard_map: each device runs the kernel on its shard of the
    slot-major arguments (leading dim, the "batch" rule axes when they
    divide it) against the whole replicated page pool."""
    from repro.parallel.sharding import get_mesh, spec
    from jax.sharding import PartitionSpec as P

    mesh = get_mesh()
    if mesh is None:
        return kernel(slot_args, pool_args)
    rows = P(spec("batch", shape=slot_args[0].shape[:1])[0])
    return jax.shard_map(kernel, mesh=mesh, in_specs=(rows, P()), out_specs=rows,
                         check_vma=False)(slot_args, pool_args)


def _paged_clamp_table(table: jax.Array, n_pages_total: int) -> jax.Array:
    """-1 (unmapped) entries -> the trash page, whose pos is pinned at -1."""
    return jnp.where(table < 0, n_pages_total - 1, table).astype(jnp.int32)


def _paged_cache_write_decode(cache: dict, k_new, v_new, row_pos, table) -> dict:
    """Write one token per row into its block-table page.  Rows whose table
    entry for ``row_pos // page_size`` is unmapped (inactive slots, whose
    table rows the scheduler resets to -1) land in the trash page with a -1
    position — self-masking, so no post-hoc merge of the pool is needed."""
    Pt, ps = cache["pos"].shape
    B = row_pos.shape[0]
    rows = jnp.arange(B)
    entry = row_pos.astype(jnp.int32) // ps
    offs = row_pos.astype(jnp.int32) % ps
    pages = _paged_clamp_table(table[rows, entry], Pt)
    write = lambda buf, vals: buf.at[pages, :, offs].set(vals[:, 0])
    k = _write_kv(cache["k"], k_new, write)
    v = _write_kv(cache["v"], v_new, write)
    pos_val = jnp.where(pages == Pt - 1, -1, row_pos.astype(jnp.int32))
    pos = cache["pos"].at[pages, offs].set(pos_val)
    return {"k": k, "v": v, "pos": pos}


def _paged_cache_write_chunk(cache: dict, k_new, v_new, positions, table_row) -> dict:
    """Write one prefill chunk's K/V (a single sequence, C tokens) straight
    into its block-table pages — the direct-write half of chunked prefill.
    ``positions`` [C] are consecutive, so every (page, offset) target is
    distinct; unmapped entries (never produced by a correct scheduler, which
    pre-allocates the prompt's pages at admission) clamp to the trash page
    with a -1 position.  Quantized pools quantize on write, same as decode.

    SHARED prefix pages are never written, with no extra plumbing: a chunk
    position whose destination entry already holds that exact position can
    only be a prefix page shared from another admission (fresh and recycled
    pages carry ``pos == -1``, and a chunk never revisits its own earlier
    positions), so its write is routed to the trash page.  This arises when
    an arch with non-paged sequential state (window rings, SSM/LRU) must
    recompute the shared prefix to rebuild that state — the refcount>1 page
    stays bit-identical, which tests/test_prefix.py asserts."""
    Pt, ps = cache["pos"].shape
    pos = positions.astype(jnp.int32)  # [C]
    entry = pos // ps
    offs = pos % ps
    pages = _paged_clamp_table(table_row[entry], Pt)
    already = cache["pos"][pages, offs] == pos  # shared-prefix entries
    pages = jnp.where(already, Pt - 1, pages)
    write = lambda buf, vals: buf.at[pages, :, offs].set(vals[0])
    k = _write_kv(cache["k"], k_new, write)
    v = _write_kv(cache["v"], v_new, write)
    pos_val = jnp.where(pages == Pt - 1, -1, pos)
    pos_arr = cache["pos"].at[pages, offs].set(pos_val)
    return {"k": k, "v": v, "pos": pos_arr}


def _paged_cache_write_chunk_batched(cache: dict, k_new, v_new, positions, tables) -> dict:
    """Multi-slot variant of ``_paged_cache_write_chunk``: every mid-prefill
    slot's chunk lands in ONE scatter.  positions: [B, C] with -1 marking
    invalid entries (rows past their chunk end, fully inactive rows); tables:
    [B, max_pages].  Invalid entries and shared-prefix re-writes (the
    ``already`` detection, same rule as the single-slot path) route to the
    trash page with a -1 position.  Distinct valid entries never collide: a
    page written this tick cannot yet be prefix-indexed, so no two slots
    target it (the scheduler only maps shared — i.e. fully-written — pages
    into more than one table row)."""
    Pt, ps = cache["pos"].shape
    B, C = positions.shape
    pos = positions.astype(jnp.int32)
    valid = pos >= 0
    entry = jnp.where(valid, pos // ps, 0)
    offs = jnp.where(valid, pos % ps, 0)
    pages = _paged_clamp_table(jnp.take_along_axis(tables, entry, axis=1), Pt)
    already = cache["pos"][pages, offs] == pos  # shared-prefix entries
    pages = jnp.where(already | ~valid, Pt - 1, pages)
    flat_p = pages.reshape(-1)
    flat_o = offs.reshape(-1)
    write = lambda buf, vals: buf.at[flat_p, :, flat_o].set(
        vals.reshape((B * C,) + vals.shape[2:])
    )
    k = _write_kv(cache["k"], k_new, write)
    v = _write_kv(cache["v"], v_new, write)
    # every trash-page write carries -1, so colliding invalid entries are
    # order-independent: the trash page's pos stays pinned at -1
    pos_val = jnp.where(pages == Pt - 1, -1, pos)
    pos_arr = cache["pos"].at[flat_p, flat_o].set(pos_val.reshape(-1))
    return {"k": k, "v": v, "pos": pos_arr}


def _paged_prefill_chunk_attend_batched(q, k, v, cache: dict, positions, tables, spec: AttnSpec, scale: float):
    """Chunk queries attend over (already-written pool pages: earlier chunks
    + shared prefix, read in place) ++ (the chunk's own in-flight fp K/V,
    causal), one row per mid-prefill slot; ``cache`` is the PRE-write pool.
    q/k/v: [B, C, ...]; positions [B, C] (-1 invalid); tables [B, max_pages].
    Pool keys at positions >= the chunk start are masked out: when a
    shared-prefix admission recomputes the prefix (archs with window rings /
    SSM state), those positions are live in the shared pages AND in flight —
    the in-flight copy is the single source, counted once.  Rows
    mask their pool history at positions >= their OWN chunk start
    (``positions[:, 0]``); invalid queries see an all-masked score row —
    finite uniform softmax garbage that the caller's active-mask merge and
    last-valid-token logit gather never read."""
    mode = PAGED_BACKEND[0]
    if mode is None:
        mode = "kernel" if jax.default_backend() == "tpu" else "ref"
    window = spec.window if spec.kind == "local" else 0
    Pt = cache["pos"].shape[0]
    tbl = _paged_clamp_table(tables, Pt)  # [B, nt]
    quant = isinstance(cache["k"], QuantizedKV)
    B, C, H, dh = q.shape
    Hkv = k.shape[2]
    if mode == "kernel":
        from repro.kernels.ops import fused_prefill_attention_paged

        # one kernel launch covers every row (grid axis 0)
        if quant:
            pool = (cache["k"].q, cache["k"].scale, cache["v"].q, cache["v"].scale)
        else:
            pool = (cache["k"], None, cache["v"], None)

        def kernel(rows, pool):
            qg, tb, qp, kc, vc = rows
            kq, ks, vq, vs, kpos = pool
            return fused_prefill_attention_paged(
                qg, kq, ks, vq, vs, kpos, tb, qp, kc, vc,
                scale=scale, causal=spec.causal, window=window,
                softcap=spec.logit_softcap,
            )

        y = _over_slots(kernel, (q.reshape(B, C, Hkv, H // Hkv, dh), tbl, positions, k, v),
                        pool + (cache["pos"],))
        return y.reshape(B, C, H, dh)
    if quant:
        kh = materialize_kv(QuantizedKV(
            gather_pages(cache["k"].q, tbl), gather_pages(cache["k"].scale, tbl),
            cache["k"].orig_dtype,
        ))
        vh = materialize_kv(QuantizedKV(
            gather_pages(cache["v"].q, tbl), gather_pages(cache["v"].scale, tbl),
            cache["v"].orig_dtype,
        ))
    else:
        kh = gather_pages(cache["k"], tbl)
        vh = gather_pages(cache["v"], tbl)
    kcat = jnp.concatenate([kh.astype(k.dtype), k], axis=1)
    vcat = jnp.concatenate([vh.astype(v.dtype), v], axis=1)
    hist_pos = gather_pages(cache["pos"], tbl)  # [B, nt*ps]
    start = positions[:, :1]  # per-row chunk start (-1 rows mask everything)
    hist_pos = jnp.where(hist_pos >= start, -1, hist_pos)  # pool = strictly pre-chunk
    k_pos = jnp.concatenate([hist_pos, positions], axis=1)
    mask = _window_causal_mask(positions, k_pos, window, spec.causal)
    return _sdpa(q, kcat, vcat, mask, scale, spec.logit_softcap)


def _cache_write_chunk(cache: dict, k, v, positions) -> dict:
    """Append one prefill chunk into a contiguous/ring cache that already
    holds earlier chunks (chunked-prefill resume for per-slot window rings).
    For C <= cap the consecutive positions map to DISTINCT ring slots
    (``pos % cap``), so a scatter preserves the ring invariant slot ==
    pos % cap even when the chunk starts mid-ring; for C > cap the ring is
    rebuilt from the chunk's last ``cap`` tokens — everything older just
    fell out of the ring, and ``_cache_write_prefill``'s rebuild lays them
    out at slot == pos % cap too."""
    cap = cache["k"].shape[1]
    S = k.shape[1]
    if S > cap:
        return _cache_write_prefill(cache, k, v, positions)
    slots = jnp.mod(positions[0].astype(jnp.int32), cap)  # same for every row
    write = lambda buf, vals: buf.at[:, slots].set(vals)
    k_ = _write_kv(cache["k"], k, write)
    v_ = _write_kv(cache["v"], v, write)
    pos_ = cache["pos"].at[:, slots].set(positions.astype(jnp.int32))
    return {"k": k_, "v": v_, "pos": pos_}


def _cache_write_chunk_batched(cache: dict, k, v, positions) -> dict:
    """Multi-slot variant of ``_cache_write_chunk`` for per-slot window rings:
    positions [B, C] per row, -1 invalid.  Each row keeps only its last
    ``cap`` valid tokens (everything older just fell out of the ring) laid
    out at slot == pos % cap; invalid/older entries get slot index ``cap``,
    which is out of bounds and therefore DROPPED by the scatter (JAX's
    default OOB-scatter semantics) — the ring row is untouched by them."""
    cap = cache["k"].shape[1]
    B, C = positions.shape
    pos = positions.astype(jnp.int32)
    row_max = jnp.max(pos, axis=1, keepdims=True)
    keep = (pos >= 0) & (pos > row_max - cap)
    slots = jnp.where(keep, jnp.mod(pos, cap), cap)  # cap == OOB -> dropped
    rows = jnp.arange(B)[:, None]
    write = lambda buf, vals: buf.at[rows, slots].set(vals)
    k_ = _write_kv(cache["k"], k, write)
    v_ = _write_kv(cache["v"], v, write)
    pos_ = cache["pos"].at[rows, slots].set(pos)
    return {"k": k_, "v": v_, "pos": pos_}


def _paged_decode_attend(q, cache: dict, row_pos, table, spec: AttnSpec, scale: float):
    """One-token decode over a paged pool.  q: [B, 1, H, dh]."""
    mode = PAGED_BACKEND[0]
    if mode is None:
        mode = "kernel" if jax.default_backend() == "tpu" else "ref"
    window = spec.window if spec.kind == "local" else 0
    Pt = cache["pos"].shape[0]
    tbl = _paged_clamp_table(table, Pt)
    quant = isinstance(cache["k"], QuantizedKV)
    if mode == "kernel":
        from repro.kernels.ops import fused_decode_attention_paged

        B, S, H, dh = q.shape
        Hkv = cache["k"].shape[1]  # pool leaf: [Pt, Hkv, ps, dh]
        qg = q[:, 0].reshape(B, Hkv, H // Hkv, dh)
        if quant:
            pool = (cache["k"].q, cache["k"].scale, cache["v"].q, cache["v"].scale)
        else:
            pool = (cache["k"], None, cache["v"], None)

        def kernel(rows, pool):
            qg, tb, qp = rows
            kq, ks, vq, vs, kpos = pool
            return fused_decode_attention_paged(
                qg, kq, ks, vq, vs, kpos, tb, qp,
                scale=scale, causal=spec.causal, window=window,
                softcap=spec.logit_softcap,
            )

        y = _over_slots(kernel, (qg, tbl, row_pos[:, None]), pool + (cache["pos"],))
        return y.reshape(B, 1, H, dh)
    if quant:
        k = materialize_kv(QuantizedKV(
            gather_pages(cache["k"].q, tbl), gather_pages(cache["k"].scale, tbl),
            cache["k"].orig_dtype,
        ))
        v = materialize_kv(QuantizedKV(
            gather_pages(cache["v"].q, tbl), gather_pages(cache["v"].scale, tbl),
            cache["v"].orig_dtype,
        ))
    else:
        k = gather_pages(cache["k"], tbl)
        v = gather_pages(cache["v"], tbl)
    k_pos = gather_pages(cache["pos"], tbl)
    mask = _window_causal_mask(row_pos[:, None], k_pos, window, spec.causal)
    return _sdpa(q, k, v, mask, scale, spec.logit_softcap)


# ---------------------------------------------------------------------------
# Core scaled-dot-product with GQA + masking
# ---------------------------------------------------------------------------


def _sdpa(q, k, v, mask, scale: float, cap: float):
    """q: [B,S,H,dh], k/v: [B,T,Hkv,dh], mask: [B,1,1,S,T] or broadcastable.
    Returns [B,S,H,dh].  Softmax in f32."""
    B, S, H, dh = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    qg = q.reshape(B, S, Hkv, G, dh)
    logits = jnp.einsum("bskgd,btkd->bkgst", qg, k, preferred_element_type=jnp.float32)
    logits = logits * scale
    logits = softcap(logits, cap)
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", probs.astype(v.dtype), v)
    return out.reshape(B, S, H, dh)


def _window_causal_mask(q_pos, k_pos, window: int, causal: bool):
    """q_pos: [B,S] or [S]; k_pos: [B,T] or [T] -> bool [B,1,1,S,T]."""
    if q_pos.ndim == 1:
        q_pos = q_pos[None]
    if k_pos.ndim == 1:
        k_pos = k_pos[None]
    q = q_pos[:, :, None]  # [B,S,1]
    k = k_pos[:, None, :]  # [B,1,T]
    m = k >= 0  # slot validity (ring caches store -1 for empty)
    if causal:
        m = m & (k <= q)
    if window > 0:
        m = m & (q - k < window)
    return m[:, None, None]  # [B,1,1,S,T]


def attend_full(q, k, v, q_pos, k_pos, spec: AttnSpec, scale: float):
    mask = _window_causal_mask(q_pos, k_pos, spec.window if spec.kind == "local" else 0, spec.causal)
    return _sdpa(q, k, v, mask, scale, spec.logit_softcap)


def attend_chunked(q, k, v, q_pos, k_pos, spec: AttnSpec, scale: float, q_chunk: int = Q_CHUNK):
    """Query-chunked attention.  For local layers each query chunk only reads
    the K/V slice [chunk_start - window, chunk_end), so HLO FLOPs are O(S·W)."""
    B, S, H, dh = q.shape
    if S <= q_chunk or S % q_chunk != 0:
        return attend_full(q, k, v, q_pos, k_pos, spec, scale)
    n_chunks = S // q_chunk
    local = spec.kind == "local" and spec.window > 0
    if local:
        # k-slice length: window rounded up to chunk multiple + chunk
        w_pad = ((spec.window + q_chunk - 1) // q_chunk) * q_chunk
        k_len = w_pad + q_chunk

    if q_pos.ndim == 1:
        q_pos = jnp.broadcast_to(q_pos[None], (B, S))
    if k_pos.ndim == 1:
        k_pos = jnp.broadcast_to(k_pos[None], (B, k.shape[1]))

    def body(i):
        qs = jax.lax.dynamic_slice_in_dim(q, i * q_chunk, q_chunk, axis=1)
        qp = jax.lax.dynamic_slice_in_dim(q_pos, i * q_chunk, q_chunk, axis=1)
        if local:
            start = jnp.maximum(i * q_chunk - w_pad, 0)
            ks = jax.lax.dynamic_slice_in_dim(k, start, k_len, axis=1)
            vs = jax.lax.dynamic_slice_in_dim(v, start, k_len, axis=1)
            kp = jax.lax.dynamic_slice_in_dim(k_pos, start, k_len, axis=1)
            # dynamic_slice clamps at the end; mask handles any overlap dupes
            # because positions beyond the causal frontier are masked anyway.
            mask = _window_causal_mask(qp, kp, spec.window, spec.causal)
        else:
            ks, vs, kp = k, v, k_pos
            mask = _window_causal_mask(qp, kp, 0, spec.causal)
        return _sdpa(qs, ks, vs, mask, scale, spec.logit_softcap)

    out = jax.lax.map(body, jnp.arange(n_chunks))  # [n, B, c, H, dh]
    return jnp.moveaxis(out, 0, 1).reshape(B, S, H, dh)


# ---------------------------------------------------------------------------
# Layer-level apply
# ---------------------------------------------------------------------------


def attention(
    cfg: ModelConfig,
    spec: AttnSpec,
    params: dict,
    x: jax.Array,
    positions: jax.Array,
    *,
    memory: Optional[jax.Array] = None,
    memory_positions: Optional[jax.Array] = None,
    cache: Optional[dict] = None,
    mode: str = "train",
    block_table: Optional[jax.Array] = None,
):
    """Returns (y, new_cache).  mode: train | prefill | decode.

    - train:   full self-attention over x (no cache IO).
    - prefill: same as train but also fills and returns the cache.
    - decode:  x is [B, 1, d]; reads cache, writes the new token into it.
    - decode_paged: like decode_ragged, but global-context caches are shared
      page pools addressed through ``block_table`` [B, max_pages] (window
      layers keep their per-slot rings; see ``spec_is_paged``).
    - prefill_chunk: one page-aligned chunk of a resumable admission prefill
      (x is [1, C, d], positions are absolute).  Paged layers attend over
      (already-written pool pages ++ in-flight chunk K/V) and write the chunk
      STRAIGHT into its block-table pages — no temp contiguous cache; window
      rings (and any contiguous cache) resume by attending over (cache
      pre-write ++ chunk) and appending.  The cache must already hold every
      position below the chunk start (earlier chunks / shared prefix pages).
    - cross (spec.kind == 'cross'): attends to ``memory`` (no cache mutation
      for train; serving caches projected memory K/V once at prefill).
    """
    B, S, d = x.shape
    H, Hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    scale = 1.0 / math.sqrt(dh)

    # materialize: dequantizes MoQ-quantized projections, passthrough otherwise
    q = jnp.einsum("bsd,dhe->bshe", x, materialize(params["wq"]))
    if spec.qk_norm:
        q = rmsnorm(params["q_norm"], q, cfg.rms_eps)

    if spec.kind == "cross":
        if cache is not None and mode.startswith("decode"):
            k, v = materialize_kv(cache["k"]), materialize_kv(cache["v"])
            k_pos = cache["pos"]
        else:
            assert memory is not None
            k = jnp.einsum("btd,dhe->bthe", memory, materialize(params["wk"]))
            v = jnp.einsum("btd,dhe->bthe", memory, materialize(params["wv"]))
            if spec.qk_norm:
                k = rmsnorm(params["k_norm"], k, cfg.rms_eps)
            k_pos = (
                memory_positions
                if memory_positions is not None
                else jnp.arange(k.shape[1], dtype=jnp.int32)[None]
            )
        mask = _window_causal_mask(
            jnp.zeros((B, S), jnp.int32), jnp.broadcast_to(k_pos, (B, k.shape[1])), 0, causal=False
        )
        y = _sdpa(q, k, v, mask, scale, spec.logit_softcap)
        new_cache = (
            {"k": k, "v": v, "pos": jnp.broadcast_to(k_pos, (B, k.shape[1])).astype(jnp.int32)}
            if mode in ("prefill", "prefill_chunk", "prefill_chunk_batched")  # chunk re-writes: idempotent
            else cache
        )
        out = jnp.einsum("bshe,hed->bsd", y, materialize(params["wo"]))
        return out, new_cache

    k = jnp.einsum("bsd,dhe->bshe", x, materialize(params["wk"]))
    v = jnp.einsum("bsd,dhe->bshe", x, materialize(params["wv"]))
    if spec.qk_norm:
        k = rmsnorm(params["k_norm"], k, cfg.rms_eps)
    if spec.use_rope:
        q = apply_rope(q, positions, spec.rope_theta)
        k = apply_rope(k, positions, spec.rope_theta)
    q = shard_hint(q, "batch", "seq", "heads", "head_dim")
    k = shard_hint(k, "batch", "seq", "kv_heads", "head_dim")
    v = shard_hint(v, "batch", "seq", "kv_heads", "head_dim")

    # Context-parallel fallback: heads that don't divide the TP axis would
    # leave attention replicated across 'model' ranks (16x redundant compute
    # and score traffic).  Shard the *query sequence* over 'model' instead;
    # K/V stay replicated across TP (each rank attends its S/L query slice
    # against the full keys).
    cp = _context_parallel_size(cfg)
    if cp > 1 and mode != "decode" and S % cp == 0:
        q = shard_hint(q, "batch", "q_seq", None, None)

    if mode in ("prefill_chunk", "prefill_chunk_batched"):
        assert cache is not None
        batched = mode == "prefill_chunk_batched"
        pos2d = positions if positions.ndim == 2 else positions[None]
        pos2d = jnp.broadcast_to(pos2d, (B, S)).astype(jnp.int32)
        if spec_is_paged(spec) and block_table is not None:
            # paged layer: attend over the pre-write pool + in-flight chunk,
            # then write the chunk's K/V straight into its pages
            if batched:
                # block_table is [B, max_pages] — one row per mid-prefill slot
                y = _paged_prefill_chunk_attend_batched(q, k, v, cache, pos2d, block_table, spec, scale)
                new_cache = _paged_cache_write_chunk_batched(cache, k, v, pos2d, block_table)
            else:
                # one slot: the batched attend with a single table row
                table_row = block_table[0] if block_table.ndim == 2 else block_table
                y = _paged_prefill_chunk_attend_batched(
                    q, k, v, cache, pos2d, table_row[None], spec, scale)
                new_cache = _paged_cache_write_chunk(cache, k, v, pos2d[0], table_row)
        else:
            # window ring (or contiguous cache) resume: earlier chunks are in
            # the cache, the current chunk is in flight.  This attend is
            # already per-row (cache/pos/mask all carry the batch axis), so
            # the batched mode shares it — only the write-back differs
            # (-1-aware per-row scatter vs the single-row slot map).
            kcat = jnp.concatenate([materialize_kv(cache["k"]).astype(k.dtype), k], axis=1)
            vcat = jnp.concatenate([materialize_kv(cache["v"]).astype(v.dtype), v], axis=1)
            k_pos = jnp.concatenate([cache["pos"], pos2d], axis=1)
            mask = _window_causal_mask(
                pos2d, k_pos, spec.window if spec.kind == "local" else 0, spec.causal
            )
            y = _sdpa(q, kcat, vcat, mask, scale, spec.logit_softcap)
            if batched:
                new_cache = _cache_write_chunk_batched(cache, k, v, pos2d)
            else:
                new_cache = _cache_write_chunk(cache, k, v, pos2d)
        y = shard_hint(y, "batch", "seq", "heads", "head_dim")
        out = jnp.einsum("bshe,hed->bsd", y, materialize(params["wo"]))
        return out, new_cache

    if mode.startswith("decode"):
        assert cache is not None and S == 1
        # positions: [B, 1]; mode == "decode" assumes a uniform batch index
        # (dynamic-update-slice — partitions best under GSPMD);
        # "decode_ragged" supports per-row positions (continuous batching).
        row_pos = positions[:, 0] if positions.ndim == 2 else positions
        row_pos = jnp.broadcast_to(row_pos, (B,)).astype(jnp.int32)
        if mode == "decode_paged" and spec_is_paged(spec):
            assert block_table is not None, "decode_paged needs a block table"
            new_cache = _paged_cache_write_decode(cache, k, v, row_pos, block_table)
            y = _paged_decode_attend(q, new_cache, row_pos, block_table, spec, scale)
        else:
            idx = row_pos if mode in ("decode_ragged", "decode_paged") else row_pos[0]
            new_cache = _cache_write_decode(cache, k, v, idx)
            if isinstance(new_cache["k"], QuantizedKV):
                # the just-written token is read back quantized too, so decode
                # sees exactly what the Pallas kernel streams from HBM
                y = _decode_attend_quant(q, new_cache, row_pos, spec, scale)
            else:
                mask = _window_causal_mask(
                    row_pos[:, None],
                    new_cache["pos"],
                    spec.window if spec.kind == "local" else 0,
                    spec.causal,
                )
                y = _sdpa(q, new_cache["k"], new_cache["v"], mask, scale, spec.logit_softcap)
    else:
        pos2d = positions if positions.ndim == 2 else positions[None]
        pos2d = jnp.broadcast_to(pos2d, (B, S))
        if cp > 1 and S % cp == 0:
            # keep the q-seq sharding intact (query chunking would slice
            # across shard boundaries and force gathers)
            y = attend_full(q, k, v, pos2d, pos2d, spec, scale)
        else:
            y = attend_chunked(q, k, v, pos2d, pos2d, spec, scale)
        new_cache = _cache_write_prefill(cache, k, v, pos2d) if (mode == "prefill" and cache is not None) else cache

    if cp > 1 and mode != "decode" and S % cp == 0:
        y = shard_hint(y, "batch", "q_seq", None, None)
    else:
        y = shard_hint(y, "batch", "seq", "heads", "head_dim")
    out = jnp.einsum("bshe,hed->bsd", y, materialize(params["wo"]))
    return out, new_cache
