"""Grouped "dropless" MoE dispatch (MegaBlocks-style, PR 8).

Locks in the fused-tick PR's expert path:

  * layout invariants: static ``grouped_rows`` worst case, unique in-tile
    destinations, every (token, k) slot lands in a tile owned by its expert,
    total-skew routings still place every assignment (no drops by
    construction);
  * the grouped Pallas kernel (fp + int8/int4 dequant-in-VMEM) against the
    gather-einsum oracle, tile-for-tile;
  * token-exact dispatch parity: ``moe_grouped`` vs the dropless einsum
    reference ``moe_einsum_dropless`` (fp and quantized weights), INCLUDING
    a routing skew that overflows any practical ``expert_capacity`` — the
    case capacity-factor dispatch drops tokens on and dropless must not;
  * ``moe_layer(impl="grouped")`` wiring: matches ``impl="einsum"`` at a
    generous capacity factor (nothing dropped -> same math), works under
    jit, and keeps reporting RoutingStats f/P for the balance telemetry.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FFNSpec, ModelConfig
from repro.core.dispatch_einsum import moe_einsum_dropless
from repro.core.dispatch_grouped import (
    GROUPED_TILE,
    grouped_layout,
    grouped_rows,
    moe_grouped,
)
from repro.core.gating import top_k_gating
from repro.core.moe import experts_ffn, grouped_experts_ffn, init_moe, moe_layer
from repro.kernels.expert_mlp_grouped import (
    grouped_mlp_kernel,
    grouped_mlp_quant,
    grouped_mlp_quant_ref,
    grouped_mlp_ref,
)
from repro.quant.qarrays import QuantizedArray


def tiny_cfg(**kw):
    return ModelConfig(
        name="t", family="moe", source="x", d_model=32, num_heads=2,
        num_kv_heads=2, head_dim=16, vocab_size=64, segments=(),
        param_dtype="float32", compute_dtype="float32", **kw,
    )


def make(T=24, E=8, K=2, seed=0, skew=0.0):
    """(cfg, spec, params, x [T,D], dropless gating).  ``skew`` adds a router
    bias toward expert 0 — large values overflow any capacity buffer."""
    cfg = tiny_cfg()
    spec = FFNSpec(kind="moe", d_ff=64, num_experts=E, top_k=K,
                   capacity_factor=1.25)
    params = init_moe(jax.random.PRNGKey(seed), cfg, spec, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (T, cfg.d_model))
    logits = x.astype(jnp.float32) @ params["router"]
    logits = logits.at[:, 0].add(skew)
    g = top_k_gating(logits, K, T * K)  # dropless: capacity = T*K
    return cfg, spec, params, x, g


def quantize_experts(params, bits, group_size=0):
    q = dict(params)
    for name, axes in (("wi", (-2,)), ("wg", (-2,)), ("wo", (-2,))):
        if name in params:
            q[name] = QuantizedArray.quantize(
                params[name], bits=bits, group_size=group_size,
                reduce_axes=axes)
    return q


# ---------------------------------------------------------------------------
# Layout invariants
# ---------------------------------------------------------------------------


class TestLayout:
    def test_static_rows_worst_case(self):
        for T, K, E, tile in [(24, 2, 8, 8), (7, 1, 4, 8), (128, 2, 16, 8)]:
            ct = grouped_rows(T, K, E, tile)
            assert ct % tile == 0
            assert ct >= T * K
            # worst case: each non-empty group wastes < tile rows
            assert ct <= ((T * K + E * (tile - 1)) // tile + 1) * tile

    def test_every_slot_lands_in_its_experts_tile(self):
        _, _, _, _, g = make(T=24, E=8, K=2)
        lay = grouped_layout(g, 8)
        dst = np.asarray(lay.dst)
        te = np.asarray(lay.tile_expert)
        flat_e = np.asarray(g.expert_idx).reshape(-1)
        assert len(set(dst.tolist())) == dst.size  # injective: no collisions
        np.testing.assert_array_equal(te[dst // GROUPED_TILE], flat_e)
        np.testing.assert_array_equal(
            np.asarray(lay.counts), np.bincount(flat_e, minlength=8))

    def test_total_skew_keeps_every_assignment(self):
        """All T*K slots route to expert 0: capacity dispatch at any sane
        factor would drop most of them; the grouped layout places all."""
        _, _, _, _, g = make(T=24, E=8, K=2, skew=1e4)
        flat = np.asarray(g.expert_idx)
        assert np.all(flat[:, 0] == 0)  # every k=0 slot routes to expert 0
        assert np.all(np.asarray(g.keep))  # ...and dropless keeps them all
        lay = grouped_layout(g, 8)
        dst = np.asarray(lay.dst)
        assert len(set(dst.tolist())) == dst.size
        np.testing.assert_array_equal(
            np.asarray(lay.tile_expert)[dst // GROUPED_TILE], flat.reshape(-1))
        # expert 0's group holds all 24 tokens — far past the capacity
        # (1.25 * 48 / 8 = 7) the einsum path would truncate it to
        assert int(np.asarray(lay.counts)[0]) == 24


# ---------------------------------------------------------------------------
# Grouped Pallas kernel vs gather-einsum oracle (interpret mode on CPU)
# ---------------------------------------------------------------------------


class TestKernelVsRef:
    def _buffers(self, E=4, D=32, F=64, nt=6, seed=0):
        ks = jax.random.split(jax.random.PRNGKey(seed), 5)
        xg = jax.random.normal(ks[0], (nt * GROUPED_TILE, D), jnp.float32)
        te = jax.random.randint(ks[1], (nt,), 0, E, jnp.int32)
        wi = jax.random.normal(ks[2], (E, D, F), jnp.float32) * 0.1
        wg = jax.random.normal(ks[3], (E, D, F), jnp.float32) * 0.1
        wo = jax.random.normal(ks[4], (E, F, D), jnp.float32) * 0.1
        return xg, te, wi, wg, wo

    def test_fp_kernel_matches_ref(self):
        xg, te, wi, wg, wo = self._buffers()
        got = grouped_mlp_kernel(xg, te, wi, wg, wo, act="swiglu", interpret=True)
        want = grouped_mlp_ref(xg, te, wi, wg, wo)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)

    @pytest.mark.parametrize("bits", [8, 4])
    def test_quant_kernel_matches_ref(self, bits):
        xg, te, wi, wg, wo = self._buffers()
        qwi = QuantizedArray.quantize(wi, bits=bits, reduce_axes=(-2,))
        qwg = QuantizedArray.quantize(wg, bits=bits, reduce_axes=(-2,))
        qwo = QuantizedArray.quantize(wo, bits=bits, reduce_axes=(-2,))
        got = grouped_mlp_quant(xg, te, qwi, qwg, qwo, act="swiglu", interpret=True)
        want = grouped_mlp_quant_ref(xg, te, qwi, qwg, qwo)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-4)

    def test_quant_kernel_rejects_groupwise_scales(self):
        xg, te, wi, wg, wo = self._buffers()
        qwi = QuantizedArray.quantize(wi, bits=8, group_size=16,
                                      reduce_axes=(-2,))
        qwg = QuantizedArray.quantize(wg, bits=8, group_size=16,
                                      reduce_axes=(-2,))
        qwo = QuantizedArray.quantize(wo, bits=8, group_size=16,
                                      reduce_axes=(-2,))
        with pytest.raises(ValueError, match="per-output-channel"):
            grouped_mlp_quant(xg, te, qwi, qwg, qwo, act="swiglu", interpret=True)

    @pytest.mark.parametrize("act", ["gelu", "relu"])
    def test_fp_kernel_ungated_act_matches_ref(self, act):
        """GELU / ReLU layers (the paper's NLG family is GELU) run in the
        kernel with no gate projection streamed."""
        xg, te, wi, _, wo = self._buffers()
        got = grouped_mlp_kernel(xg, te, wi, None, wo, act=act, interpret=True)
        want = grouped_mlp_ref(xg, te, wi, None, wo, act)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)

    @pytest.mark.parametrize("bits", [8, 4])
    def test_quant_kernel_gelu_matches_ref(self, bits):
        xg, te, wi, _, wo = self._buffers()
        qwi = QuantizedArray.quantize(wi, bits=bits, reduce_axes=(-2,))
        qwo = QuantizedArray.quantize(wo, bits=bits, reduce_axes=(-2,))
        got = grouped_mlp_quant(xg, te, qwi, None, qwo, act="gelu", interpret=True)
        want = grouped_mlp_quant_ref(xg, te, qwi, None, qwo, "gelu")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)

    def test_kernel_mode_raises_on_incompatible_layout(self):
        """Under "kernel" the layer never reaches the gather-einsum ref in
        silence: group-wise scales and a gate on a GELU layer both raise;
        "ref" stays the explicit way to run the oracle."""
        xg, te, wi, wg, wo = self._buffers()
        grouped = [QuantizedArray.quantize(w, bits=8, group_size=16, reduce_axes=(-2,))
                   for w in (wi, wg, wo)]
        qp = dict(zip(("wi", "wg", "wo"), grouped))
        with pytest.raises(ValueError, match="group_size"):
            grouped_experts_ffn(qp, xg, te, "swiglu", backend="kernel")
        with pytest.raises(ValueError, match="gate"):
            grouped_experts_ffn({"wi": wi, "wg": wg, "wo": wo}, xg, te, "gelu",
                                backend="kernel")
        got = grouped_experts_ffn(qp, xg, te, "swiglu", backend="ref")
        assert np.isfinite(np.asarray(got)).all()


# ---------------------------------------------------------------------------
# Dispatch parity: moe_grouped vs the dropless einsum reference
# ---------------------------------------------------------------------------


class TestDispatchParity:
    @pytest.mark.parametrize("skew", [0.0, 1e4],
                             ids=["balanced", "capacity-overflow"])
    def test_fp_matches_einsum_dropless(self, skew):
        """Token-exact (to f32 reduction-order noise) against the einsum
        dropless oracle — including the skew where every token routes to one
        expert, the case any fixed expert_capacity would drop on."""
        _, spec, params, x, g = make(skew=skew)
        got = moe_grouped(
            x, g, spec.num_experts,
            lambda xg, te: grouped_experts_ffn(params, xg, te, spec.act))
        want = moe_einsum_dropless(
            x, g, lambda xe: experts_ffn(params, xe, spec.act))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-4)

    @pytest.mark.parametrize("bits", [8, 4])
    def test_quant_matches_einsum_dropless(self, bits):
        """int8/int4 expert weights through the grouped path vs the same
        quantized weights through the einsum dropless path, under the
        capacity-overflowing skew."""
        _, spec, params, x, g = make(skew=1e4)
        qp = quantize_experts(params, bits)
        got = moe_grouped(
            x, g, spec.num_experts,
            lambda xg, te: grouped_experts_ffn(qp, xg, te, spec.act))
        want = moe_einsum_dropless(
            x, g, lambda xe: experts_ffn(qp, xe, spec.act))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-4)


# ---------------------------------------------------------------------------
# Layer wiring: cfg.moe_impl="grouped"
# ---------------------------------------------------------------------------


class TestLayerWiring:
    def test_matches_einsum_when_nothing_drops(self):
        """At a capacity factor high enough that einsum drops nothing, the
        two implementations compute the same mixture."""
        cfg = tiny_cfg()
        spec = FFNSpec(kind="moe", d_ff=64, num_experts=8, top_k=2,
                       capacity_factor=8.0)  # capacity >= T*K: no drops
        params = init_moe(jax.random.PRNGKey(0), cfg, spec, jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 32))
        yg, ag = moe_layer(cfg, spec, params, x, impl="grouped")
        ye, ae = moe_layer(cfg, spec, params, x, impl="einsum")
        np.testing.assert_allclose(np.asarray(yg), np.asarray(ye), atol=2e-4)
        assert abs(float(ag) - float(ae)) < 1e-5

    def test_under_jit_and_stats(self):
        cfg, spec, params, x, _ = make()
        xb = x.reshape(2, 12, 32)

        @jax.jit
        def f(p, xin):
            return moe_layer(cfg, spec, p, xin, impl="grouped",
                             with_stats=True)

        y, aux, stats = f(params, xb)
        assert y.shape == xb.shape and np.isfinite(float(aux))
        # dropless still reports the balance telemetry (f, P per expert)
        assert stats.tokens_per_expert.shape == (spec.num_experts,)
        assert abs(float(stats.dropped_frac)) < 1e-6
