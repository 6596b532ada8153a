"""Block / segment assembly.

A block = pre-norm mixer (attention | SSD | RG-LRU) [+ pre-norm cross-attn]
+ pre-norm FFN (dense | MoE | none), all residual.  Segments stack identical
patterns under ``jax.lax.scan`` with parameters (and caches) stacked on a
leading ``repeats`` axis — HLO size stays O(unique layer kinds) regardless of
depth, which is what makes 512-device dry-run compiles of 62-95 layer models
tractable, and is the natural representation for PR-MoE's pyramid segments.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import AttnSpec, FFNSpec, LayerSpec, LRUSpec, ModelConfig, Segment, SSMSpec
from repro.core.moe import init_moe, moe_layer
from repro.models.attention import (
    attention,
    init_attention,
    init_kv_cache,
    init_paged_kv_cache,
    spec_is_paged,
)
from repro.models.modules import init_mlp, init_rmsnorm, mlp, rmsnorm
from repro.models.rglru import init_lru, init_lru_cache, lru_layer
from repro.models.ssm import init_ssm, init_ssm_cache, ssm_layer
from repro.parallel.sharding import shard_hint


# ---------------------------------------------------------------------------
# Single layer
# ---------------------------------------------------------------------------


def init_layer(key, cfg: ModelConfig, ls: LayerSpec, dtype) -> dict:
    ks = jax.random.split(key, 6)
    p = {"norm_mixer": init_rmsnorm(cfg.d_model, dtype)}
    m = ls.mixer
    if isinstance(m, AttnSpec):
        p["attn"] = init_attention(ks[0], cfg, m, dtype)
    elif isinstance(m, SSMSpec):
        p["ssm"] = init_ssm(ks[0], cfg, m, dtype)
    elif isinstance(m, LRUSpec):
        p["lru"] = init_lru(ks[0], cfg, m, dtype)
    else:
        raise TypeError(m)
    if ls.cross is not None:
        p["norm_cross"] = init_rmsnorm(cfg.d_model, dtype)
        p["cross"] = init_attention(ks[1], cfg, ls.cross, dtype)
    if ls.ffn.kind == "dense":
        p["norm_ffn"] = init_rmsnorm(cfg.d_model, dtype)
        p["ffn"] = init_mlp(ks[2], cfg.d_model, ls.ffn.d_ff, ls.ffn.act, dtype)
    elif ls.ffn.kind == "moe":
        p["norm_ffn"] = init_rmsnorm(cfg.d_model, dtype)
        p["moe"] = init_moe(ks[2], cfg, ls.ffn, dtype)
    return p


def init_layer_cache(cfg: ModelConfig, ls: LayerSpec, batch: int, capacity: int, dtype, *, cross_len: int = 0, kv_bits: int = 0, pages=None):
    """Cache pytree for one layer.  ``capacity`` = full-context length for
    global attention; local layers get a ring of size window.  ``kv_bits=8``
    stores self-attention K/V as int8 QuantizedKV (cross caches and SSM/LRU
    states stay fp — they are tiny by comparison).

    ``pages = (n_pages, page_size)`` switches global-context self caches to a
    shared page pool addressed through block tables (paged serving); window
    rings, cross caches, and SSM/LRU states stay per-slot."""
    c = {}
    m = ls.mixer
    if isinstance(m, AttnSpec):
        if pages is not None and spec_is_paged(m):
            n_pages, page_size = pages
            c["self"] = init_paged_kv_cache(
                n_pages, page_size, cfg.num_kv_heads, cfg.head_dim, dtype, kv_bits=kv_bits
            )
        else:
            cap = min(m.window, capacity) if (m.kind == "local" and m.window > 0) else capacity
            c["self"] = init_kv_cache(batch, cap, cfg.num_kv_heads, cfg.head_dim, dtype, kv_bits=kv_bits)
    elif isinstance(m, SSMSpec):
        c["self"] = init_ssm_cache(batch, m, dtype)
    elif isinstance(m, LRUSpec):
        c["self"] = init_lru_cache(batch, m, dtype)
    if ls.cross is not None:
        c["cross"] = init_kv_cache(batch, max(cross_len, 1), cfg.num_kv_heads, cfg.head_dim, dtype)
    return c


def apply_layer(
    cfg: ModelConfig,
    ls: LayerSpec,
    params: dict,
    x: jax.Array,
    positions: jax.Array,
    *,
    cache: Optional[dict] = None,
    mode: str = "train",
    memory: Optional[jax.Array] = None,
    block_table: Optional[jax.Array] = None,
    layer: Optional[jax.Array] = None,
    collect_stats: bool = False,
    count_routed: bool = False,
) -> Tuple:
    """Returns (x, new_cache, aux_loss); with ``collect_stats`` or
    ``count_routed`` a 4th element — the MoE layer's telemetry dict (None
    for non-MoE layers): ``"stats"``, its jit-returnable ``RoutingStats``,
    and ``"routed"``, the int32 count of experts its dispatch gave rows
    (with ``"ep_rows"``, the rows each EP rank's experts received, on the
    serving EP paths).
    The flags are static (part of the trace), so telemetry collection is
    decided when the caller builds its jitted step.  ``layer`` (paged
    decode): the index of this layer in its stacked page pools, which
    ``cache["self"]`` then holds whole."""
    aux = jnp.zeros((), jnp.float32)
    tel = None
    h = rmsnorm(params["norm_mixer"], x, cfg.rms_eps)
    sc = cache.get("self") if cache is not None else None
    m = ls.mixer
    if isinstance(m, AttnSpec):
        with jax.named_scope("attn"):
            y, new_self = attention(
                cfg, m, params["attn"], h, positions, cache=sc, mode=mode,
                block_table=block_table, layer=layer,
            )
    elif isinstance(m, SSMSpec):
        y, new_self = ssm_layer(
            cfg, m, params["ssm"], h, cache=sc, mode=mode, positions=positions
        )
    elif isinstance(m, LRUSpec):
        y, new_self = lru_layer(
            cfg, m, params["lru"], h, cache=sc, mode=mode, positions=positions
        )
    else:
        raise TypeError(m)
    x = x + y
    new_cache = dict(cache) if cache is not None else None

    if ls.cross is not None:
        h = rmsnorm(params["norm_cross"], x, cfg.rms_eps)
        cc = cache.get("cross") if cache is not None else None
        y, new_cross = attention(
            cfg, ls.cross, params["cross"], h, positions, memory=memory, cache=cc, mode=mode
        )
        x = x + y
        if new_cache is not None:
            new_cache["cross"] = new_cross

    if ls.ffn.kind == "dense":
        h = rmsnorm(params["norm_ffn"], x, cfg.rms_eps)
        with jax.named_scope("mlp"):
            x = x + mlp(params["ffn"], h, ls.ffn.act)
    elif ls.ffn.kind == "moe":
        h = rmsnorm(params["norm_ffn"], x, cfg.rms_eps)
        y, aux, *extra = moe_layer(cfg, ls.ffn, params["moe"], h,
                                   with_stats=collect_stats, with_routed=count_routed)
        if collect_stats or count_routed:
            tel = {}
            if collect_stats:
                tel["stats"] = extra[0]
            if count_routed:
                tel.update(extra[-1])
        x = x + y

    if new_cache is not None and "self" in new_cache:
        new_cache["self"] = new_self
    x = shard_hint(x, "batch", "seq", "embed")
    if BF16_BWD[0]:
        from repro.models.modules import grad_cast

        x = grad_cast(x)
    if collect_stats or count_routed:
        return x, new_cache, aux, tel
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# Segment (scan over repeats)
# ---------------------------------------------------------------------------


def init_segment(key, cfg: ModelConfig, seg: Segment, dtype) -> dict:
    """Params: {"pos{j}": stacked-over-repeats layer params}."""
    out = {}
    for j, ls in enumerate(seg.pattern):
        keys = jax.random.split(jax.random.fold_in(key, j), seg.repeats)
        out[f"pos{j}"] = jax.vmap(lambda k: init_layer(k, cfg, ls, dtype))(keys)
    return out


def init_segment_cache(cfg: ModelConfig, seg: Segment, batch: int, capacity: int, dtype, *, cross_len: int = 0, kv_bits: int = 0, pages=None):
    out = {}
    for j, ls in enumerate(seg.pattern):
        one = init_layer_cache(cfg, ls, batch, capacity, dtype, cross_len=cross_len, kv_bits=kv_bits, pages=pages)
        out[f"pos{j}"] = jax.tree.map(lambda a: jnp.broadcast_to(a, (seg.repeats,) + a.shape), one)
    return out


def apply_segment(
    cfg: ModelConfig,
    seg: Segment,
    params: dict,
    x: jax.Array,
    positions: jax.Array,
    *,
    caches: Optional[dict] = None,
    mode: str = "train",
    memory: Optional[jax.Array] = None,
    remat: bool = False,
    block_table: Optional[jax.Array] = None,
    collect_stats: bool = False,
    count_routed: bool = False,
):
    """Scan the segment.  caches (if given) mirror the params structure with a
    leading ``repeats`` axis.  Returns (x, new_caches, aux_sum); with
    ``collect_stats`` or ``count_routed`` a 4th element — ``{pos{j}:
    telemetry dict}`` (``apply_layer``) for the pattern's MoE positions, each
    leaf stacked ``[repeats, ...]`` by the scan (per-layer telemetry falls
    out of the scan's ys stacking for free).

    ``block_table`` (paged decode) is layer-invariant: every layer's page
    pool shares one table, so it rides into the scan body as a capture.

    In ``decode_paged`` mode each paged position's ``self`` pools ride in
    the scan's *carry*, whole (``[repeats, Pt, ...]``), and the body gets
    the layer's index from ``xs``: the layer writes its tokens and reads
    its pages at that index in place, so no layer's pool is sliced out of
    the stack or written back into a stacked output.  Per-slot leaves
    (window rings, SSM/LRU states, cross caches) stay in ``xs``/``ys``."""
    has_cache = caches is not None
    collect = collect_stats or count_routed
    carried = [f"pos{j}" for j, ls in enumerate(seg.pattern)
               if has_cache and mode == "decode_paged"
               and isinstance(ls.mixer, AttnSpec) and spec_is_paged(ls.mixer)]

    def body(carry, xs):
        x, aux, pools = carry
        layer_params, per_layer, layer = xs
        new_caches = {}
        new_pools = {}
        tel_out = {}
        for j, ls in enumerate(seg.pattern):
            pkey = f"pos{j}"
            c = per_layer[pkey] if has_cache else None
            if pkey in pools:
                c = {**c, "self": pools[pkey]}
            out = apply_layer(
                cfg, ls, layer_params[pkey], x, positions, cache=c, mode=mode, memory=memory,
                block_table=block_table, layer=layer if pkey in pools else None,
                collect_stats=collect_stats, count_routed=count_routed,
            )
            if collect:
                x, c_new, a, tel = out
                if tel is not None:
                    tel_out[pkey] = tel
            else:
                x, c_new, a = out
            if pkey in pools:
                c_new = dict(c_new)
                new_pools[pkey] = c_new.pop("self")
            if has_cache:
                new_caches[pkey] = c_new
            aux = aux + a
        ys = new_caches if has_cache else 0
        if collect:
            ys = (ys, tel_out)
        return (x, aux, new_pools), ys

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)

    pools = {pk: caches[pk]["self"] for pk in carried}
    per_layer = caches and {
        pk: {k: v for k, v in c.items() if not (pk in pools and k == "self")}
        for pk, c in caches.items()}
    # scan requires every xs leaf to have leading dim == repeats
    xs = (params, per_layer, jnp.arange(seg.repeats, dtype=jnp.int32))
    (x, aux, pools), ys = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32), pools), xs, unroll=_SCAN_UNROLL[0] or 1
    )
    ys, tel = ys if collect else (ys, None)
    new_caches = {pk: ({**c, "self": pools[pk]} if pk in pools else c)
                  for pk, c in ys.items()} if has_cache else None
    return (x, new_caches, aux, tel) if collect else (x, new_caches, aux)


# Dry-run knob: setting _SCAN_UNROLL[0] = True fully unrolls segment scans so
# XLA cost_analysis counts every layer (it otherwise counts a while-loop body
# once).  Compile is slower; used by launch/dryrun.py only.
_SCAN_UNROLL = [False]


def set_scan_unroll(full: bool) -> None:
    _SCAN_UNROLL[0] = bool(full)


# Perf toggle (EXPERIMENTS.md §Perf): cast the residual-stream cotangent back
# to the activation dtype at every layer boundary — halves backward-pass
# collective and HBM traffic that JAX's f32 cotangent promotion otherwise
# doubles.  Enabled via launch/dryrun --train-opt bf16_bwd.
BF16_BWD = [False]


def set_bf16_bwd(on: bool) -> None:
    BF16_BWD[0] = bool(on)
