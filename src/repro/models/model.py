"""Top-level model: embeddings -> segments -> final norm -> logits.

Entry points used by training / serving / dry-run:

  * ``forward``      — teacher-forced logits (training / eval)
  * ``prefill``      — forward + build caches
  * ``decode_step``  — one token with caches
  * ``encode``       — encoder stack (enc-dec models)

Frontend-stub models (audio/vlm): callers pass precomputed frame/patch
embeddings (see ``FrontendSpec``); a learned projector maps them to d_model
and they are prepended to the token embeddings (vlm) or fed to the encoder
(audio enc-dec).
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import AttnSpec, ModelConfig
from repro.kernels.attention_paged import page_rows
from repro.models.attention import spec_is_paged
from repro.models.modules import dense_init, embed_init, init_rmsnorm, rmsnorm
from repro.models.transformer import apply_segment, init_segment, init_segment_cache
from repro.parallel.sharding import shard_hint
from repro.quant.kv import QuantizedKV
from repro.quant.qarrays import materialize


def _dtype(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16, "float16": jnp.float16}[name]


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def init_params(cfg: ModelConfig, key) -> dict:
    dt = _dtype(cfg.param_dtype)
    ks = jax.random.split(key, 8)
    p = {
        "embed": embed_init(ks[0], cfg.vocab_size, cfg.d_model, dt),
        "final_norm": init_rmsnorm(cfg.d_model, dt),
        "segments": {
            f"seg{i}": init_segment(jax.random.fold_in(ks[1], i), cfg, seg, dt)
            for i, seg in enumerate(cfg.segments)
        },
    }
    if not cfg.tie_embeddings:
        p["unembed"] = embed_init(ks[2], cfg.vocab_size, cfg.d_model, dt).T  # [D, V]
    if cfg.encoder is not None:
        p["encoder"] = {
            "segments": {
                f"seg{i}": init_segment(jax.random.fold_in(ks[3], i), cfg, seg, dt)
                for i, seg in enumerate(cfg.encoder.segments)
            },
            "final_norm": init_rmsnorm(cfg.d_model, dt),
        }
    if cfg.frontend is not None:
        p["frontend_proj"] = dense_init(ks[4], cfg.frontend.embed_dim, cfg.d_model, dt)
    return p


def init_caches(cfg: ModelConfig, batch: int, capacity: int, *, cross_len: int = 0, kv_bits: int = 0) -> dict:
    """``kv_bits=8`` allocates int8 QuantizedKV self-attention caches
    (quantize-on-write; see repro/quant/kv.py), 0 = full precision."""
    dt = _dtype(cfg.param_dtype)
    return {
        f"seg{i}": init_segment_cache(cfg, seg, batch, capacity, dt, cross_len=cross_len, kv_bits=kv_bits)
        for i, seg in enumerate(cfg.segments)
    }


def init_paged_caches(
    cfg: ModelConfig, slots: int, capacity: int, *, n_pages: int, page_size: int,
    cross_len: int = 0, kv_bits: int = 0,
) -> dict:
    """Paged serving caches: global-context self-attention K/V live in shared
    page pools ``[n_pages + 1, page_size, H_kv * dh]`` addressed through
    per-slot block tables, instead of reserving ``capacity`` tokens per slot
    (serving/kv_pool.py).  Window rings, cross caches, and SSM/LRU states
    stay per-slot (``slots`` batch rows) — they are fixed-size already.
    ``capacity`` remains the per-sequence context bound (it sizes the block
    tables: ``ceil(capacity / page_size)`` entries per slot)."""
    dt = _dtype(cfg.param_dtype)
    return {
        f"seg{i}": init_segment_cache(
            cfg, seg, slots, capacity, dt, cross_len=cross_len, kv_bits=kv_bits,
            pages=(n_pages, page_size),
        )
        for i, seg in enumerate(cfg.segments)
    }


# ---------------------------------------------------------------------------
# Embedding / logits
# ---------------------------------------------------------------------------


def embed_tokens(cfg: ModelConfig, params: dict, tokens: jax.Array) -> jax.Array:
    x = params["embed"][tokens]  # [B, S, D]
    x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)  # gemma-style scale
    return shard_hint(x, "batch", "seq", "embed")


def logits_out(cfg: ModelConfig, params: dict, x: jax.Array) -> jax.Array:
    with jax.named_scope("logits"):
        x = rmsnorm(params["final_norm"], x, cfg.rms_eps)
        w = params["embed"].T if cfg.tie_embeddings else materialize(params["unembed"])
        logits = jnp.einsum("bsd,dv->bsv", x, w.astype(x.dtype))
    return shard_hint(logits, "batch", "seq", "vocab")


# ---------------------------------------------------------------------------
# Encoder (enc-dec)
# ---------------------------------------------------------------------------


def encode(cfg: ModelConfig, params: dict, source: jax.Array) -> jax.Array:
    """source: [B, T, frontend.embed_dim] (stubbed frontend embeddings) or
    token ids [B, T] if no frontend."""
    if cfg.frontend is not None and source.ndim == 3:
        x = source.astype(_dtype(cfg.compute_dtype)) @ materialize(params["frontend_proj"])
    else:
        x = embed_tokens(cfg, params, source)
    pos = jnp.arange(x.shape[1], dtype=jnp.int32)[None]
    enc = params["encoder"]
    for i, seg in enumerate(cfg.encoder.segments):
        x, _, _ = apply_segment(cfg, seg, enc["segments"][f"seg{i}"], x, pos, mode="train")
    return rmsnorm(enc["final_norm"], x, cfg.rms_eps)


# ---------------------------------------------------------------------------
# Decoder / LM entry points
# ---------------------------------------------------------------------------


def _run_segments(cfg, params, x, positions, caches, mode, memory, remat,
                  block_table=None, collect_stats=False, count_routed=False):
    """With ``collect_stats=True`` returns a further element: ``{seg{i}:
    {pos{j}: RoutingStats[repeats, ...]}}`` for every MoE position — the
    per-layer routing telemetry tree (jit-returnable; host side aggregates
    via ``core.gating.summarize_routing``).  With ``count_routed=True`` a
    last element: int32 ``[n_moe_layers]``, in layer order, the number of
    experts each MoE layer's dispatch gave at least one row."""
    aux = jnp.zeros((), jnp.float32)
    new_caches = {}
    stats = {}
    routed = []
    for i, seg in enumerate(cfg.segments):
        c = caches.get(f"seg{i}") if caches is not None else None
        out = apply_segment(
            cfg, seg, params["segments"][f"seg{i}"], x, positions,
            caches=c, mode=mode, memory=memory, remat=remat, block_table=block_table,
            collect_stats=collect_stats, count_routed=count_routed,
        )
        if collect_stats or count_routed:
            x, c_new, a, tel = out
            moe = [f"pos{j}" for j, ls in enumerate(seg.pattern) if ls.ffn.kind == "moe"]
            if collect_stats and moe:
                stats[f"seg{i}"] = {pk: tel[pk]["stats"] for pk in moe}
            if count_routed and moe:
                # [repeats, positions] -> layer order (repeat-major)
                routed.append(jnp.stack([tel[pk]["routed"] for pk in moe], axis=1).reshape(-1))
        else:
            x, c_new, a = out
        aux = aux + a
        if caches is not None:
            new_caches[f"seg{i}"] = c_new
    res = (x, (new_caches if caches is not None else None), aux)
    if collect_stats:
        res += (stats,)
    if count_routed:
        res += (jnp.concatenate(routed) if routed else jnp.zeros((0,), jnp.int32),)
    return res


def forward(
    cfg: ModelConfig,
    params: dict,
    tokens: jax.Array,  # [B, S] int32
    *,
    positions: Optional[jax.Array] = None,
    memory: Optional[jax.Array] = None,
    prefix_embeds: Optional[jax.Array] = None,  # vlm patch embeddings [B, P, De]
    remat: bool = False,
    return_routing: bool = False,
) -> Tuple[jax.Array, ...]:
    """Teacher-forced logits [B, S(+P), V]; returns (logits, aux_loss).
    ``return_routing=True`` (static) appends the per-layer routing-stats
    tree (see ``_run_segments``) as a third element."""
    x = embed_tokens(cfg, params, tokens)
    if prefix_embeds is not None:
        pre = prefix_embeds.astype(x.dtype) @ materialize(params["frontend_proj"])
        x = jnp.concatenate([pre, x], axis=1)
    S = x.shape[1]
    if positions is None:
        positions = jnp.arange(S, dtype=jnp.int32)[None]
    if return_routing:
        x, _, aux, routing = _run_segments(
            cfg, params, x, positions, None, "train", memory, remat, collect_stats=True
        )
        return logits_out(cfg, params, x), aux, routing
    x, _, aux = _run_segments(cfg, params, x, positions, None, "train", memory, remat)
    return logits_out(cfg, params, x), aux


def prefill(
    cfg: ModelConfig,
    params: dict,
    tokens: jax.Array,
    caches: dict,
    *,
    memory: Optional[jax.Array] = None,
    prefix_embeds: Optional[jax.Array] = None,
) -> Tuple[jax.Array, dict]:
    """Returns (logits for the last position [B, V], filled caches)."""
    x = embed_tokens(cfg, params, tokens)
    if prefix_embeds is not None:
        pre = prefix_embeds.astype(x.dtype) @ materialize(params["frontend_proj"])
        x = jnp.concatenate([pre, x], axis=1)
    S = x.shape[1]
    positions = jnp.arange(S, dtype=jnp.int32)[None]
    x, new_caches, _ = _run_segments(cfg, params, x, positions, caches, "prefill", memory, False)
    logits = logits_out(cfg, params, x[:, -1:])[:, 0]
    return logits, new_caches


def decode_step(
    cfg: ModelConfig,
    params: dict,
    token: jax.Array,  # [B, 1] int32
    index: jax.Array,  # [] int32 — current absolute position
    caches: dict,
    *,
    memory: Optional[jax.Array] = None,
    return_routing: bool = False,
) -> Tuple:
    """One decode step: returns (logits [B, V], updated caches);
    ``return_routing=True`` appends the routing-stats tree."""
    x = embed_tokens(cfg, params, token)
    B = x.shape[0]
    positions = jnp.broadcast_to(index.astype(jnp.int32), (B, 1))
    if return_routing:
        x, new_caches, _, routing = _run_segments(
            cfg, params, x, positions, caches, "decode", memory, False, collect_stats=True
        )
        return logits_out(cfg, params, x)[:, 0], new_caches, routing
    x, new_caches, _ = _run_segments(cfg, params, x, positions, caches, "decode", memory, False)
    logits = logits_out(cfg, params, x)[:, 0]
    return logits, new_caches


def ragged_decode_step(
    cfg: ModelConfig,
    params: dict,
    token: jax.Array,  # [B, 1] int32
    positions: jax.Array,  # [B] int32 — PER-ROW absolute position
    active: jax.Array,  # [B] bool — rows with live requests
    caches: dict,
    *,
    memory: Optional[jax.Array] = None,
    return_routing: bool = False,
    return_routed: bool = False,
) -> Tuple:
    """Continuous-batching decode tick: each slot/row decodes at its own
    position; inactive rows' caches are left untouched (masked merge).
    ``return_routing=True`` appends the routing-stats tree (stats cover
    every slot row, active or not — padding rows route too; host side
    treats the per-tick stats as a load-shape sample, not exact counts);
    ``return_routed=True`` appends the per-MoE-layer routed-expert counts
    (``_run_segments``), which count padding rows' experts too: the step
    reads those experts' weights."""
    x = embed_tokens(cfg, params, token)
    pos2d = positions.astype(jnp.int32)[:, None]
    x, new_caches, _, *tel = _run_segments(
        cfg, params, x, pos2d, caches, "decode_ragged", memory, False,
        collect_stats=return_routing, count_routed=return_routed,
    )
    logits = logits_out(cfg, params, x)[:, 0]

    def _merge(new, old):
        # cache leaves: [layers, B, ...] — select on the batch axis
        mask = active.reshape((1, -1) + (1,) * (new.ndim - 2))
        return jnp.where(mask, new, old)

    merged = jax.tree.map(_merge, new_caches, caches)
    return (logits, merged, *tel)


def prefill_into_slot(
    cfg: ModelConfig,
    params: dict,
    tokens: jax.Array,  # [1, S] int32 — a single request's prompt
    positions: jax.Array,  # [1, S] int32
    slot: jax.Array,  # [] int32 — batch row in the pooled caches
    caches: dict,
    *,
    memory: Optional[jax.Array] = None,
) -> Tuple[jax.Array, dict]:
    """Prefill one request and write its cache state into row ``slot`` of the
    pooled slot caches (continuous batching admission)."""
    x = embed_tokens(cfg, params, tokens)
    one_caches = init_caches(cfg, 1, _pool_capacity(caches), kv_bits=_pool_kv_bits(caches))
    x, filled, _ = _run_segments(cfg, params, x, positions, one_caches, "prefill", memory, False)
    logits = logits_out(cfg, params, x[:, -1:])[:, 0]

    def _write(pool, one):
        return jax.lax.dynamic_update_slice_in_dim(pool, one.astype(pool.dtype), slot, axis=1)

    merged = jax.tree.map(_write, caches, filled)
    return logits, merged


# ---------------------------------------------------------------------------
# Paged serving entry points (shared page pool + per-slot block tables)
# ---------------------------------------------------------------------------


def _layer_entries(cfg: ModelConfig):
    """Yield (seg_key, pos_key, LayerSpec, paged_self) over the decoder."""
    for i, seg in enumerate(cfg.segments):
        for j, ls in enumerate(seg.pattern):
            paged = isinstance(ls.mixer, AttnSpec) and spec_is_paged(ls.mixer)
            yield f"seg{i}", f"pos{j}", ls, paged


def arch_fully_paged(cfg: ModelConfig) -> bool:
    """True iff every sequence-mixing layer's state lives in the shared page
    pool under paged serving — i.e. no window rings and no SSM/LRU states.

    This is the condition for prefix sharing to skip the shared prefix's
    *prefill compute* (chunked prefill reads the shared pages in place): any
    non-paged sequential state must be rebuilt by actually running the
    prefix, so mixed archs (gemma3 ring mixes, hybrids) still compute it —
    they keep the page-sharing memory win, write nothing to shared pages
    (trash-routed), and only fully-paged archs get the FLOPs win too."""
    for _, _, ls, paged in _layer_entries(cfg):
        if not paged:
            return False
    return True


def paged_ragged_decode_step(
    cfg: ModelConfig,
    params: dict,
    token: jax.Array,  # [B, 1] int32
    positions: jax.Array,  # [B] int32 — PER-ROW absolute position
    active: jax.Array,  # [B] bool — rows with live requests
    caches: dict,  # from init_paged_caches
    block_table: jax.Array,  # [B, max_pages] int32, -1 = unmapped
    *,
    memory: Optional[jax.Array] = None,
    return_routing: bool = False,
    return_routed: bool = False,
) -> Tuple:
    """Continuous-batching decode tick over paged caches.  Pool writes are
    self-masking (inactive slots' table rows are all -1, so their writes land
    in the trash page); the per-slot leaves (window rings, SSM/LRU states,
    cross caches) get the same masked merge as ``ragged_decode_step``.
    ``return_routing=True`` appends the routing-stats tree and
    ``return_routed=True`` the routed-expert counts, as there."""
    x = embed_tokens(cfg, params, token)
    pos2d = positions.astype(jnp.int32)[:, None]
    x, new_caches, _, *tel = _run_segments(
        cfg, params, x, pos2d, caches, "decode_paged", memory, False,
        block_table=block_table, collect_stats=return_routing, count_routed=return_routed,
    )
    logits = logits_out(cfg, params, x)[:, 0]

    def _merge(new, old):
        # per-slot leaves: [layers, B, ...] — select on the batch axis
        mask = active.reshape((1, -1) + (1,) * (new.ndim - 2))
        return jnp.where(mask, new, old)

    merged = {}
    for sk, pk, ls, paged in _layer_entries(cfg):
        c_new, c_old = new_caches[sk][pk], caches[sk][pk]
        out = {}
        for key in c_new:
            if key == "self" and paged:
                out[key] = c_new[key]  # pool — already masked via trash routing
            else:
                out[key] = jax.tree.map(_merge, c_new[key], c_old[key])
        merged.setdefault(sk, {})[pk] = out
    return (logits, merged, *tel)


def paged_reset_pages(cfg: ModelConfig, caches: dict, page_mask: jax.Array) -> dict:
    """Invalidate pages returned to the pool: ``page_mask`` [n_pages + 1]
    bool -> those pages' ``pos`` entries become -1 in every layer's pool.

    Required for correctness, not hygiene: page reuse only overwrites the
    entries the new sequence actually fills, so without this a recycled
    page's leftover positions (which can be <= the new sequence's query
    position) would unmask the previous occupant's K/V."""
    out = {}
    for sk, pk, ls, paged in _layer_entries(cfg):
        c = dict(caches[sk][pk])
        if paged:
            self_c = dict(c["self"])
            # pos: [repeats, n_pages + 1, page_size]
            self_c["pos"] = jnp.where(page_mask[None, :, None], -1, self_c["pos"])
            c["self"] = self_c
        out.setdefault(sk, {})[pk] = c
    return out


def _copy_axis1(buf: jax.Array, src: jax.Array, dst: jax.Array) -> jax.Array:
    """Copy one index of axis 1 — the page axis of pool leaves
    ``[repeats, n_pages + 1, page_size, ...]`` and the batch axis of
    per-slot leaves ``[repeats, slots, ...]``."""
    one = jax.lax.dynamic_slice_in_dim(buf, src, 1, axis=1)
    return jax.lax.dynamic_update_slice_in_dim(buf, one, dst, axis=1)


def paged_copy_page(cfg: ModelConfig, caches: dict, src, dst) -> dict:
    """Copy one physical page's contents ``src -> dst`` in every paged
    layer's pool (k, v, and pos; (q, scale) pairs verbatim for int8 pools) —
    the device half of copy-on-write.  The scheduler calls this after
    ``KVBlockPool.fork`` hands the diverging slot a fresh page and before the
    slot's next append, so the shared original is never written.  ``src`` /
    ``dst`` are traced scalars: every CoW hits one compilation."""
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    out = {}
    for sk, pk, ls, paged in _layer_entries(cfg):
        c = dict(caches[sk][pk])
        if paged:
            c["self"] = jax.tree.map(lambda b: _copy_axis1(b, src, dst), c["self"])
        out.setdefault(sk, {})[pk] = c
    return out


def paged_copy_slot_leaves(cfg: ModelConfig, caches: dict, src, dst) -> dict:
    """Copy every PER-SLOT cache leaf's row ``src -> dst``: window rings,
    SSM/LRU states, cross caches — everything that is not in a shared page
    pool.  Parallel sampling forks a freshly-admitted slot this way: the
    fork's block table points at the base's pages (pool ``share``), and the
    non-paged state is duplicated row-wise so both samples carry identical
    prompt context.  ``src`` / ``dst`` are traced scalars."""
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    out = {}
    for sk, pk, ls, paged in _layer_entries(cfg):
        c_old = caches[sk][pk]
        c = {}
        for key in c_old:
            if key == "self" and paged:
                c[key] = c_old[key]  # shared pool — the table carries the fork
            else:
                c[key] = jax.tree.map(lambda b: _copy_axis1(b, src, dst), c_old[key])
        out.setdefault(sk, {})[pk] = c
    return out


def paged_prefill_chunk(
    cfg: ModelConfig,
    params: dict,
    tokens: jax.Array,  # [1, C] int32 — one page-aligned chunk of the prompt
    positions: jax.Array,  # [1, C] int32 — absolute positions (chunk start..end-1)
    slot: jax.Array,  # [] int32 — batch row for the per-slot leaves
    caches: dict,  # from init_paged_caches
    table_row: jax.Array,  # [max_pages] int32 — the slot's block table, -1 unmapped
    *,
    capacity: int,
    kv_bits: int = 0,
    page_size: int,
    reset: bool = False,  # static: True for an admission's FIRST chunk
    memory: Optional[jax.Array] = None,
) -> Tuple[jax.Array, dict]:
    """One chunk of a resumable admission prefill, written DIRECTLY into pool
    pages — the chunked replacement for ``paged_prefill_into_slot``'s
    temp-contiguous-then-scatter path.  Per chunk:

      * paged self-attention layers attend over (the sequence's
        already-written pages — earlier chunks AND shared prefix pages, read
        in place through ``table_row`` — ++ the chunk's in-flight K/V) and
        write the chunk's K/V straight into its destination pages
        (models/attention.py ``prefill_chunk`` mode; Pallas kernel in
        kernels/attention_prefill_paged.py, int8 pools dequantized in VMEM);
      * per-slot leaves (window rings, SSM/LRU states, cross caches) are
        sliced out at row ``slot``, advanced by the chunk (rings append at
        ``pos % cap``; SSM/LRU resume from their carried state), and written
        back — so the state machine is fully resumable across engine ticks.

    The scheduler must have mapped every page the chunk writes into
    ``table_row`` before the first chunk, and chunks must be submitted in
    position order starting at the first non-shared position (a
    prefix-sharing admission starts AFTER the shared pages, which is what
    turns page sharing into prefill-FLOPs sharing).  Returns (last-chunk-
    position logits [1, V], updated caches); only the final chunk's logits
    seed the first sampled token.

    ``reset=True`` (an admission's FIRST chunk) starts the per-slot leaves
    from their freshly-initialized values — zero SSM/LRU state, empty conv
    prefixes, rings with ``pos == -1`` — instead of resuming row ``slot``'s
    contents: the row still holds the slot's PREVIOUS occupant's state (the
    scatter path rewrote the whole row implicitly; the chunked state machine
    must reset explicitly or a reused slot leaks its predecessor's
    recurrence into the new request's first chunk).  Later chunks resume.

    There is no temp contiguous cache anywhere in this path: peak admission
    memory is the chunk activations, not a ``capacity``-token double buffer.
    """
    x = embed_tokens(cfg, params, tokens)

    def _slice_row(leaf):
        return jax.lax.dynamic_slice_in_dim(leaf, slot, 1, axis=1)

    fresh = (
        init_paged_caches(cfg, 1, capacity, n_pages=1, page_size=page_size,
                          kv_bits=kv_bits)
        if reset else None
    )  # paged pool leaves of `fresh` are unused (DCE'd); per-slot rows are
    one = {}
    for sk, pk, ls, paged in _layer_entries(cfg):
        c = caches[sk][pk]
        o = {}
        for key in c:
            if key == "self" and paged:
                o[key] = c[key]  # shared pool — addressed via the table
            elif reset:
                o[key] = fresh[sk][pk][key]  # init-valued row (ring pos -1)
            else:
                o[key] = jax.tree.map(_slice_row, c[key])
        one.setdefault(sk, {})[pk] = o

    x, updated, _ = _run_segments(
        cfg, params, x, positions, one, "prefill_chunk", memory, False,
        block_table=table_row[None],
    )
    logits = logits_out(cfg, params, x[:, -1:])[:, 0]

    def _write_row(pool, row):
        return jax.lax.dynamic_update_slice_in_dim(pool, row.astype(pool.dtype), slot, axis=1)

    merged = {}
    for sk, pk, ls, paged in _layer_entries(cfg):
        c_pool, c_new = caches[sk][pk], updated[sk][pk]
        o = {}
        for key in c_pool:
            if key == "self" and paged:
                o[key] = c_new[key]  # pool pages were written by the chunk
            else:
                o[key] = jax.tree.map(_write_row, c_pool[key], c_new[key])
        merged.setdefault(sk, {})[pk] = o
    return logits, merged


def paged_prefill_chunk_batched(
    cfg: ModelConfig,
    params: dict,
    tokens: jax.Array,  # [S, C] int32 — one chunk per slot, 0-padded
    positions: jax.Array,  # [S, C] int32 — absolute positions, -1 at padding
    reset: jax.Array,  # [S] bool — row runs its admission's FIRST chunk
    active: jax.Array,  # [S] bool — row has a chunk this tick
    last_idx: jax.Array,  # [S] int32 — index of each row's last valid token
    caches: dict,  # from init_paged_caches
    block_tables: jax.Array,  # [S, max_pages] int32 — -1 unmapped; all -1 when inactive
    *,
    capacity: int,
    kv_bits: int = 0,
    page_size: int,
    memory: Optional[jax.Array] = None,
) -> Tuple[jax.Array, dict]:
    """ALL mid-prefill slots advance one chunk in a single jitted call — the
    batched replacement for looping ``paged_prefill_chunk`` per slot.  With N
    admissions mid-prefill, the per-slot loop issues N dispatches per engine
    tick; this issues ONE, making a tick at most {one batched prefill, one
    batched decode} (the "fused tick").  Numerics per row are identical to
    the per-slot path (tests/test_chunked.py asserts token-exact parity):

      * rows' chunks may have different lengths — each row is a valid prefix
        (positions >= 0) followed by -1 padding.  Padding is inert by
        construction, not by masking outputs: paged attention writes route
        invalid positions to the trash page, ring writes drop them via the
        scatter's out-of-bounds semantics, SSM steps use dt = 0 (identity),
        LRU gates freeze (a = 1, b = 0), and conv prefixes are extracted at
        each row's last valid input;
      * INACTIVE rows (no chunk this tick) carry all--1 table rows, so their
        pool writes also land in the trash page, and their per-slot leaves
        (rings, SSM/LRU states, cross caches) are restored from the incoming
        caches by the ``active`` masked merge below;
      * ``reset`` rows start their per-slot leaves from freshly-initialized
        values (zero recurrence state, ring pos -1) exactly as
        ``paged_prefill_chunk(reset=True)`` does — a reused slot must not
        leak its previous occupant's state.

    Distinct rows never write the same pool entry: the scheduler maps each
    page to exactly one owner, and a page written this tick cannot appear in
    another row's table as a shared prefix (sharing only covers pages
    completed on a PRIOR tick).  Trash-page collisions are order-independent
    (every trash write stores pos = -1).

    Returns (logits at each row's last valid position [S, V], updated
    caches); only rows finishing their prompt this tick use their logits (to
    seed the first sampled token) — the rest are discarded by the engine.
    """
    x = embed_tokens(cfg, params, tokens)
    S = tokens.shape[0]

    fresh = init_paged_caches(
        cfg, S, capacity, n_pages=1, page_size=page_size, kv_bits=kv_bits
    )  # pool leaves unused (DCE'd); per-slot leaves give reset rows' values

    def _reset_rows(cur, fr):
        mask = reset.reshape((1, -1) + (1,) * (cur.ndim - 2))
        return jnp.where(mask, fr.astype(cur.dtype), cur)

    one = {}
    for sk, pk, ls, paged in _layer_entries(cfg):
        c = caches[sk][pk]
        o = {}
        for key in c:
            if key == "self" and paged:
                o[key] = c[key]  # shared pool — addressed via the tables
            else:
                o[key] = jax.tree.map(_reset_rows, c[key], fresh[sk][pk][key])
        one.setdefault(sk, {})[pk] = o

    x, updated, _ = _run_segments(
        cfg, params, x, positions, one, "prefill_chunk_batched", memory, False,
        block_table=block_tables,
    )
    xe = jnp.take_along_axis(x, last_idx.astype(jnp.int32)[:, None, None], axis=1)
    logits = logits_out(cfg, params, xe)[:, 0]  # [S, V]

    def _merge(new, old):
        # per-slot leaves: [repeats, S, ...] — select on the batch axis
        mask = active.reshape((1, -1) + (1,) * (new.ndim - 2))
        return jnp.where(mask, new, old)

    merged = {}
    for sk, pk, ls, paged in _layer_entries(cfg):
        c_new, c_old = updated[sk][pk], caches[sk][pk]
        o = {}
        for key in c_new:
            if key == "self" and paged:
                o[key] = c_new[key]  # pool — inactive rows trash-routed
            else:
                o[key] = jax.tree.map(_merge, c_new[key], c_old[key])
        merged.setdefault(sk, {})[pk] = o
    return logits, merged


def paged_verify_chunk_batched(
    cfg: ModelConfig,
    params: dict,
    tokens: jax.Array,  # [S, C] int32 — cur token + k drafted tokens per slot
    positions: jax.Array,  # [S, C] int32 — absolute positions, -1 at padding
    active: jax.Array,  # [S] bool — row has a speculation window this tick
    caches: dict,  # from init_paged_caches
    block_tables: jax.Array,  # [S, max_pages] int32 — tail entries point at CoW forks
    *,
    capacity: int,
    kv_bits: int = 0,
    page_size: int,
    memory: Optional[jax.Array] = None,
) -> Tuple[jax.Array, dict]:
    """Speculative VERIFY: score all k + 1 window positions of every
    decoding slot in one batched pass — ``paged_prefill_chunk_batched``
    specialised for draft-then-verify:

      * logits are returned for EVERY chunk position (not just the last):
        position j's logits are the target model's distribution over the
        token at ``positions[:, j] + 1``, which is what accepts/rejects the
        drafted token at that position;
      * there is no ``reset`` — every verified slot is long past admission;
      * per-slot leaves (window rings, SSM/LRU states, conv prefixes) are
        returned UNCHANGED: verify is a read that must not advance recurrent
        state, because a rejection would have no way to roll it back.  Only
        pool pages are written — and the scheduler points the window's table
        entries at CoW fork pages precisely so that rejected writes can be
        rolled back by dropping pages (accepted ones commit by refcount
        handoff).  Non-fully-paged archs re-run the ACCEPTED tokens through
        a separate committed chunk pass to advance their recurrent leaves;
        its pool writes are inert (the `already`-stored guard in
        models/attention.py trash-routes rewrites of a stored position).

    Rows' windows may have different lengths (k is clamped near the budget
    end): a valid prefix followed by -1 position padding, inert exactly as
    in the batched prefill chunk.  Inactive rows carry all--1 tables.

    Returns (logits at every window position [S, C, V], updated caches).
    """
    x = embed_tokens(cfg, params, tokens)

    x, updated, _ = _run_segments(
        cfg, params, x, positions, caches, "prefill_chunk_batched", memory,
        False, block_table=block_tables,
    )
    logits = logits_out(cfg, params, x)  # [S, C, V]

    merged = {}
    for sk, pk, ls, paged in _layer_entries(cfg):
        c_new, c_old = updated[sk][pk], caches[sk][pk]
        o = {}
        for key in c_new:
            if key == "self" and paged:
                o[key] = c_new[key]  # pool — fork-page writes, trash-routed when inactive
            else:
                o[key] = c_old[key]  # recurrent state must survive rejection
        merged.setdefault(sk, {})[pk] = o
    return logits, merged


def paged_reset_page_tails(
    cfg: ModelConfig,
    caches: dict,
    pages: jax.Array,  # [S] int32 — last committed page per slot, -1 = no-op row
    start_offs: jax.Array,  # [S] int32 — first in-page offset to invalidate
) -> dict:
    """Invalidate the TAIL of each slot's last committed page: offsets
    >= ``start_offs[i]`` of page ``pages[i]`` get ``pos = -1`` in every
    layer's pool.

    Required for speculative-decoding correctness, not hygiene: a committed
    window page still carries the verify pass's writes BEYOND the accepted
    point (rejected draft positions).  Those entries would satisfy the
    `already`-stored write guard (models/attention.py) when the NEXT verify
    round writes the same positions for real, silently trash-routing the
    real K/V.  Invalidating the tail restores the invariant the guard
    depends on: a live page never stores a position >= its slot's current
    length.  One fixed-shape call per commit tick covers every slot
    (``pages[i] = -1`` rows match nothing; ``start_offs[i] = page_size`` is
    a row-level no-op)."""
    out = {}
    for sk, pk, ls, paged in _layer_entries(cfg):
        c = dict(caches[sk][pk])
        if paged:
            self_c = dict(c["self"])
            pos = self_c["pos"]  # [repeats, n_pages + 1, page_size]
            n_pages, ps = pos.shape[1], pos.shape[2]
            hit = jnp.arange(n_pages)[None, :] == pages[:, None]  # [S, P]
            offm = jnp.arange(ps)[None, :] >= start_offs[:, None]  # [S, ps]
            mask = (hit[:, :, None] & offm[:, None, :]).any(axis=0)  # [P, ps]
            self_c["pos"] = jnp.where(mask[None], -1, pos)
            c["self"] = self_c
        out.setdefault(sk, {})[pk] = c
    return out


def paged_prefill_into_slot(
    cfg: ModelConfig,
    params: dict,
    tokens: jax.Array,  # [1, S] int32 — a single request's prompt
    positions: jax.Array,  # [1, S] int32
    slot: jax.Array,  # [] int32 — batch row for the per-slot leaves
    caches: dict,  # from init_paged_caches
    table_row: jax.Array,  # [max_pages] int32 — the slot's block table, -1 unmapped
    *,
    capacity: int,
    kv_bits: int = 0,
    memory: Optional[jax.Array] = None,
    scatter_start=0,  # [] int32 (traced ok) — first position written to pages
) -> Tuple[jax.Array, dict]:
    """One-shot admission prefill via temp-contiguous-then-scatter: run the
    ordinary contiguous prefill into a temporary single-sequence cache
    (identical numerics to the non-paged path), then scatter the filled K/V
    into the slot's block-table pages and dynamic-update the per-slot leaves
    at ``slot``.  The scheduler must have mapped ``ceil(S / page_size)``
    pages into ``table_row``.

    This is no longer the default admission path — ``paged_prefill_chunk``
    writes pages directly, with no temp buffer and no recompute of shared
    prefixes.  It is retained as the *parity oracle* for chunked prefill
    (``ContinuousEngine(prefill_mode="scatter")``; tests/test_chunked.py
    asserts token-identical greedy outputs between the two) and as the
    reference for the scatter semantics below.

    ``scatter_start`` supports prefix sharing: positions below it already
    live in pages SHARED with other slots (mapped into ``table_row`` by the
    scheduler), so their writes are routed to the trash page — a shared page
    is never mutated by an admission, only read through the table.  The
    prefill compute still covers the full context here (the chunked path is
    the one that also skips the shared prefix's FLOPs).  It is a traced
    scalar, so varying prefix lengths hit one compilation per prompt
    length."""
    S = tokens.shape[1]
    assert S <= capacity, f"prompt {S} exceeds per-sequence capacity {capacity}"
    x = embed_tokens(cfg, params, tokens)
    one_caches = init_caches(cfg, 1, capacity, kv_bits=kv_bits)
    x, filled, _ = _run_segments(cfg, params, x, positions, one_caches, "prefill", memory, False)
    logits = logits_out(cfg, params, x[:, -1:])[:, 0]
    pos_vec = positions[0].astype(jnp.int32)  # [S]
    start = jnp.asarray(scatter_start, jnp.int32)

    def _write_slot(pool, one):
        return jax.lax.dynamic_update_slice_in_dim(pool, one.astype(pool.dtype), slot, axis=1)

    def _scatter_self(pool, tmp):
        # pool: {"k","v","pos"} with leading repeats axis, pool tensors
        # [R, Pt, ps, Hkv*...] and pos [R, Pt, ps]; tmp: contiguous
        # [R, 1, capacity, Hkv, ...] with the prompt written at 0..S-1
        Pt, ps = pool["pos"].shape[1], pool["pos"].shape[2]
        pages = table_row[pos_vec // ps]
        pages = jnp.where((pages < 0) | (pos_vec < start), Pt - 1, pages).astype(jnp.int32)
        offs = pos_vec % ps

        def scat(buf, vals):
            return buf.at[:, pages, offs].set(vals)

        def scat_heads(buf, vals):  # vals [R, S, Hkv, ...] -> token rows [R, S, Hkv*...]
            return scat(buf, page_rows(vals))

        def scat_kv(old, tmp_kv):
            if isinstance(old, QuantizedKV):
                # tmp was quantized on write during prefill — copy (q, scale)
                # pairs verbatim, no requantization
                return QuantizedKV(
                    scat_heads(old.q, tmp_kv.q[:, 0, :S]),
                    scat_heads(old.scale, tmp_kv.scale[:, 0, :S]),
                    old.orig_dtype,
                )
            return scat_heads(old, tmp_kv[:, 0, :S].astype(old.dtype))

        pos_val = jnp.where(pages == Pt - 1, -1, pos_vec)
        return {
            "k": scat_kv(pool["k"], tmp["k"]),
            "v": scat_kv(pool["v"], tmp["v"]),
            "pos": scat(pool["pos"], jnp.broadcast_to(pos_val, (pool["pos"].shape[0], S))),
        }

    merged = {}
    for sk, pk, ls, paged in _layer_entries(cfg):
        c_pool, c_tmp = caches[sk][pk], filled[sk][pk]
        out = {}
        for key in c_pool:
            if key == "self" and paged:
                out[key] = _scatter_self(c_pool[key], c_tmp[key])
            else:
                out[key] = jax.tree.map(_write_slot, c_pool[key], c_tmp[key])
        merged.setdefault(sk, {})[pk] = out
    return logits, merged


def _pool_capacity(caches: dict) -> int:
    """Original capacity the pooled caches were built with: the largest KV
    seq dim across layers (window layers hold smaller rings)."""
    caps = [leaf.shape[2] for leaf in jax.tree.leaves(caches) if leaf.ndim == 5]
    return max(caps) if caps else 1


def _pool_kv_bits(caches: dict) -> int:
    """KV quantization of an existing cache pool (so per-request prefill
    caches in continuous batching are allocated with a matching layout)."""
    from repro.quant.kv import QuantizedKV

    leaves = jax.tree.leaves(caches, is_leaf=lambda l: isinstance(l, QuantizedKV))
    return 8 if any(isinstance(l, QuantizedKV) for l in leaves) else 0
