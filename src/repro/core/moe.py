"""The MoE FFN layer (DeepSpeed-MoE §3 + §4 + §5).

Four interchangeable dispatch implementations (``cfg.moe_impl``):

  * ``einsum``  — sparse one-hot einsum (paper's baseline, §5.4)
  * ``dense``   — dense mapping-table scatter/gather (paper's optimization)
  * ``grouped`` — dropless expert-sorted dispatch (MegaBlocks-style): no
                  ``expert_capacity``, no drops; tokens tile-pad only to the
                  kernel tile (core/dispatch_grouped.py +
                  kernels/expert_mlp_grouped.py)
  * ``ep``      — dense dispatch + explicit expert-parallel all-to-all under
                  shard_map with parallelism-coordinated communication
                  (paper §5.2-5.3); requires an active mesh.

``residual=True`` adds the fixed dense-MLP branch of Residual-MoE (§4.1.1);
combined with pyramid segments this gives PR-MoE.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import FFNSpec, ModelConfig
from repro.core import dispatch, dispatch_einsum, dispatch_grouped
from repro.core.gating import (
    expert_capacity,
    load_balance_loss,
    routing_stats,
    top_k_gating,
)
from repro.models.modules import dense_init, init_mlp, mlp
from repro.parallel.sharding import get_mesh, shard_hint
from repro.quant.qarrays import QuantizedArray


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------


def init_moe(key, cfg: ModelConfig, spec: FFNSpec, dtype) -> dict:
    d, f, e = cfg.d_model, spec.d_ff, spec.num_experts
    ks = jax.random.split(key, 5)

    def stack_init(k, in_dim, out_dim):
        return jax.vmap(lambda kk: dense_init(kk, in_dim, out_dim, dtype))(jax.random.split(k, e))

    p = {
        "router": dense_init(ks[0], d, e, jnp.float32),
        "wi": stack_init(ks[1], d, f),  # [E, D, F]
        "wo": stack_init(ks[2], f, d),  # [E, F, D]
    }
    if spec.act == "swiglu":
        p["wg"] = stack_init(ks[3], d, f)
    if spec.residual:
        p["residual"] = init_mlp(ks[4], d, spec.residual_d_ff or spec.d_ff, spec.act, dtype)
    return p


# ---------------------------------------------------------------------------
# Expert FFN over stacked buffers
# ---------------------------------------------------------------------------


def experts_ffn(params: dict, xe: jax.Array, act: str, *, backend: str | None = None) -> jax.Array:
    """xe: [E, C, D] -> [E, C, D] — per-expert (Swi)GLU MLP as grouped GEMMs.

    Quantized expert weights (MoQ, repro/quant) are handled transparently:
    the int8-per-channel SwiGLU layout takes the Pallas dequant-in-kernel
    path on TPU (weights stream HBM→VMEM at 1 byte/param); "ref" dequantizes
    into the einsum path.  Under "kernel" (the TPU default) any other
    quantized layout raises instead of widening whole experts per call.
    ``backend`` ("kernel" | "ref") pins the quantized path per call —
    prefer it over the process-wide toggle below when jit caching matters.
    """
    if isinstance(params["wi"], QuantizedArray):
        return _experts_ffn_quant(params, xe, act, backend)
    h = jnp.einsum("ecd,edf->ecf", xe, params["wi"])
    if act == "swiglu":
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, params["wg"])) * h
    elif act == "gelu":
        h = jax.nn.gelu(h)
    else:
        h = jax.nn.relu(h)
    return jnp.einsum("ecf,efd->ecd", h, params["wo"])


# Process-wide default for the quantized expert path: None = auto (Pallas
# kernel on TPU, dequant-einsum reference elsewhere — interpret-mode Pallas is
# a correctness tool, far too slow to serve from).  "kernel" / "ref" force.
QUANT_EXPERT_BACKEND = [None]


def set_quant_expert_backend(mode) -> None:
    """Test/benchmark knob.  The flag is read at trace time and is not part
    of any jit cache key, so changing it drops ALL cached compilations to
    keep already-jitted engines honest — expensive; per-call sites should
    pass ``experts_ffn(..., backend=...)`` instead."""
    assert mode in (None, "kernel", "ref"), mode
    if QUANT_EXPERT_BACKEND[0] == mode:
        return
    QUANT_EXPERT_BACKEND[0] = mode
    jax.clear_caches()


def _experts_ffn_quant(params: dict, xe: jax.Array, act: str, backend: str | None) -> jax.Array:
    from repro.kernels.expert_mlp_quant import _check_kernel_compat, expert_mlp_quant_ref

    wi, wo = params["wi"], params["wo"]
    wg = params.get("wg")
    mode = backend or QUANT_EXPERT_BACKEND[0]
    if mode is None:
        mode = "kernel" if jax.default_backend() == "tpu" else "ref"
    if mode == "kernel":
        if act != "swiglu" or not _check_kernel_compat(xe, wi, wg, wo):
            raise ValueError(
                "quantized capacity expert kernel takes int8 per-output-channel "
                f"SwiGLU experts with block-divisible shapes; got act={act}, "
                f"bits={wi.bits}, group_size={wi.group_size}, C={xe.shape[1]}, "
                f"F={wi.shape[-1]} (backend='ref' runs the dequant reference)"
            )
        from repro.kernels.ops import fused_expert_mlp_quant

        return fused_expert_mlp_quant(xe, wi, wg, wo)
    if act == "swiglu":
        return expert_mlp_quant_ref(xe, wi, wg, wo)
    h = jnp.einsum("ecd,edf->ecf", xe, wi.dequantize())
    h = jax.nn.gelu(h) if act == "gelu" else jax.nn.relu(h)
    return jnp.einsum("ecf,efd->ecd", h, wo.dequantize())


# Process-wide default for the grouped (dropless) expert path, same contract
# as QUANT_EXPERT_BACKEND: None = auto (Pallas kernel on TPU, gather-einsum
# reference elsewhere), "kernel" / "ref" force.
GROUPED_EXPERT_BACKEND = [None]


def set_grouped_expert_backend(mode) -> None:
    """Test/benchmark knob; read at trace time (not a jit cache key), so
    changing it drops ALL cached compilations — expensive; per-call sites
    should pass ``grouped_experts_ffn(..., backend=...)`` instead."""
    assert mode in (None, "kernel", "ref"), mode
    if GROUPED_EXPERT_BACKEND[0] == mode:
        return
    GROUPED_EXPERT_BACKEND[0] = mode
    jax.clear_caches()


def grouped_experts_ffn(
    params: dict, xg: jax.Array, te: jax.Array, act: str, *, backend: str | None = None
) -> jax.Array:
    """xg: [Ct, D] expert-sorted tile-padded tokens; te: [Ct/tile] tile ->
    expert map (core/dispatch_grouped.py layout) -> [Ct, D].

    "kernel" (the TPU default) runs the grouped Pallas kernel for fp and
    int8/int4 weights, with the layer's activation in the kernel, and raises
    on a layout the kernel cannot take; "ref" (the default elsewhere) runs
    the gather-einsum reference, which materializes every tile's expert
    weights and is a correctness oracle, not a serving path.
    """
    from repro.kernels import expert_mlp_grouped as gk

    wi, wo = params["wi"], params["wo"]
    wg = params.get("wg")
    quantized = isinstance(wi, QuantizedArray)
    mode = backend or GROUPED_EXPERT_BACKEND[0]
    if mode is None:
        mode = "kernel" if jax.default_backend() == "tpu" else "ref"
    if mode == "ref":
        if quantized:
            return gk.grouped_mlp_quant_ref(xg, te, wi, wg, wo, act)
        return gk.grouped_mlp_ref(xg, te, wi, wg, wo, act)
    reason = gk.grouped_kernel_unsupported(wi, wg, wo, act)
    if reason is not None:
        raise ValueError(
            f"grouped expert kernel cannot take this layer: {reason} "
            "(backend='ref' runs the gather-einsum reference)")
    from repro.kernels.ops import fused_expert_mlp_grouped, fused_expert_mlp_grouped_quant

    fused = fused_expert_mlp_grouped_quant if quantized else fused_expert_mlp_grouped
    return fused(xg, te, wi, wg, wo, act=act)


# ---------------------------------------------------------------------------
# Layer apply
# ---------------------------------------------------------------------------


def moe_layer(
    cfg: ModelConfig,
    spec: FFNSpec,
    params: dict,
    x: jax.Array,  # [B, S, D]
    *,
    impl: str | None = None,
    with_stats: bool = False,
) -> Tuple[jax.Array, ...]:
    """Returns (y [B,S,D], aux_loss scalar); with ``with_stats=True`` a
    third element — a jit-returnable ``RoutingStats`` (token-count-
    independent shapes) for per-layer telemetry (docs/OBSERVABILITY.md)."""
    impl = impl or cfg.moe_impl
    B, S, D = x.shape
    E, K = spec.num_experts, spec.top_k
    stats = None

    if impl in ("ep_serve", "ep_grouped"):
        # Serving EP (core/moe_serve.py) needs an active mesh whose 'expert'
        # rule axes divide E; otherwise degrade to the equivalent
        # single-device kernel.  Under a live multi-device mesh the dense
        # mapping-table path is guarded (dispatch.moe_dense raises), so the
        # dense-family fallback there is the einsum dispatch.
        from repro.core.moe_serve import serve_ep_axes

        if serve_ep_axes(E) is None:
            if impl == "ep_grouped":
                impl = "grouped"
            else:
                impl = "einsum" if get_mesh() is not None else "dense"

    if impl in ("ep_serve", "ep_grouped"):
        from repro.core.moe_serve import moe_layer_ep_serve

        if isinstance(params.get("wi"), QuantizedArray):
            # shard_map in_specs address raw arrays (same rule as "ep"); the
            # engines dequantize expert leaves ONCE at load time — this
            # in-jit fallback only runs when a mesh appears after tracing.
            from repro.quant.ptq import dequantize_params

            params = {**params, **dequantize_params(
                {k: params[k] for k in ("wi", "wg", "wo") if k in params}
            )}
        kernel = "grouped" if impl == "ep_grouped" else "dense"
        y, aux = moe_layer_ep_serve(cfg, spec, params, x, kernel=kernel)
        if with_stats:
            # Router + gating re-run on the replicated token set outside
            # shard_map.  For the replicated-token schedules (decode,
            # grouped) this is EXACTLY the gating the sharded dispatch used
            # (global capacity / dropless); for the a2a prefill schedule the
            # drop accounting approximates the per-shard local capacity —
            # the same documented caveat as the training "ep" path.
            xs = x.reshape(B * S, D)
            capacity = (
                B * S * K if impl == "ep_grouped"
                else expert_capacity(B * S, E, K, spec.capacity_factor)
            )
            logits = xs.astype(jnp.float32) @ params["router"]
            stats = routing_stats(top_k_gating(logits, K, capacity), E)
    elif impl == "ep" and get_mesh() is not None:
        from repro.core.moe_parallel import moe_layer_ep

        if isinstance(params.get("wi"), QuantizedArray):
            # shard_map in_specs address raw arrays.  NB this fallback runs
            # inside the caller's jit, re-widening experts every step —
            # pure overhead, no bandwidth win.  The engines avoid it by
            # dequantizing ONCE at load time when cfg.moe_impl == "ep"
            # (kernel-level dequant stays the single-host serving path).
            from repro.quant.ptq import dequantize_params

            params = {**params, **dequantize_params(
                {k: params[k] for k in ("wi", "wg", "wo") if k in params}
            )}
        y, aux = moe_layer_ep(cfg, spec, params, x)
        if with_stats:
            # Telemetry for the EP path: re-run router + gating on the full
            # (replicated) token set OUTSIDE shard_map.  probs/top-k/f/P are
            # identical to the sharded dispatch; drop accounting uses the
            # global single-device capacity, so it approximates the
            # per-shard local-capacity drops (documented caveat — the
            # router matmul is T×E, negligible next to the experts).
            xs = x.reshape(B * S, D)
            capacity = expert_capacity(B * S, E, K, spec.capacity_factor)
            logits = xs.astype(jnp.float32) @ params["router"]
            stats = routing_stats(top_k_gating(logits, K, capacity), E)
    else:
        xs = x.reshape(B * S, D)
        T = B * S
        logits = xs.astype(jnp.float32) @ params["router"]
        if impl == "grouped":
            # Dropless: gate with capacity = T*K, so every assignment keeps
            # its expert by pigeonhole (keep all-True, f/P in RoutingStats
            # still report the balance the aux loss shapes).
            g = top_k_gating(logits, K, T * K)
            y = dispatch_grouped.moe_grouped(
                xs, g, E, lambda xg, te: grouped_experts_ffn(params, xg, te, spec.act)
            )
        else:
            capacity = expert_capacity(T, E, K, spec.capacity_factor)
            g = top_k_gating(logits, K, capacity)
            ef = lambda xe: experts_ffn(params, xe, spec.act)
            if impl == "einsum":
                y = dispatch_einsum.moe_einsum(xs, g, capacity, ef)
            else:  # dense mapping-table
                y = dispatch.moe_dense(xs, g, capacity, E, ef)
        aux = load_balance_loss(g.probs, g.expert_idx, E)
        if with_stats:
            stats = routing_stats(g, E)
        y = y.reshape(B, S, D)

    if spec.residual:
        # Residual-MoE (§4.1.1): fixed dense MLP branch + gated expert branch.
        y = y + mlp(params["residual"], x, spec.act)
    y = shard_hint(y, "batch", "seq", "embed")
    if with_stats:
        return y, aux, stats
    return y, aux
