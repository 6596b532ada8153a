"""MoQ quantization subsystem (repro/quant + kernels/expert_mlp_quant):
QuantizedArray numerics/pytree behavior, PTQ policies, the Pallas
dequant-in-kernel expert MLP vs its einsum oracle, end-to-end serving parity,
and checkpoint round-trips."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.checkpoint import ckpt
from repro.configs.base import QuantConfig
from repro.core.moe import set_quant_expert_backend
from repro.core.prmoe import nlg_moe
from repro.kernels.expert_mlp_quant import expert_mlp_quant, expert_mlp_quant_ref
from repro.kernels.ref import expert_mlp_ref
from repro.models.model import forward, init_params
from repro.quant import (
    QuantizedArray,
    dequantize_params,
    materialize,
    quantize_params,
    quantized_leaf_paths,
    tree_bytes,
)
from repro.serving.continuous import ContinuousEngine
from repro.serving.engine import Engine, EngineConfig, Request


def _moe_cfg(vocab=512):
    return nlg_moe("quant-test", 4, 128, 4, 8, vocab=vocab).replace(
        param_dtype="float32", compute_dtype="float32"
    )


# ---------------------------------------------------------------------------
# QuantizedArray
# ---------------------------------------------------------------------------


class TestQuantizedArray:
    def test_int8_roundtrip_error(self):
        w = jax.random.normal(jax.random.PRNGKey(0), (3, 64, 32), jnp.float32)
        qa = QuantizedArray.quantize(w, bits=8, reduce_axes=(-2,))
        rel = float(jnp.abs(qa.dequantize() - w).max() / jnp.abs(w).max())
        assert rel < 0.01
        assert qa.q.dtype == jnp.int8
        assert qa.scale.shape == (3, 1, 32)
        assert qa.shape == w.shape and qa.dtype == w.dtype

    def test_int4_packing_and_groups(self):
        w = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 16), jnp.float32)
        qa = QuantizedArray.quantize(w, bits=4, group_size=16, reduce_axes=(-2,))
        assert qa.q.shape == (2, 32, 16)  # two nibbles per byte along axis -2
        assert qa.scale.shape == (2, 4, 16)  # 64/16 groups
        assert qa.shape == (2, 64, 16)
        rel = float(jnp.abs(qa.dequantize() - w).max() / jnp.abs(w).max())
        assert rel < 0.15
        # quantizing the dequantized values is a fixed point (exact int match)
        qa2 = QuantizedArray.quantize(qa.dequantize(), bits=4, group_size=16, reduce_axes=(-2,))
        np.testing.assert_array_equal(np.asarray(qa.q), np.asarray(qa2.q))

    def test_attention_shapes(self):
        wq = jax.random.normal(jax.random.PRNGKey(2), (32, 4, 16))
        qa = QuantizedArray.quantize(wq, bits=8, reduce_axes=(-3,))
        assert qa.scale.shape == (1, 4, 16)
        wo = jax.random.normal(jax.random.PRNGKey(3), (4, 16, 32))
        qo = QuantizedArray.quantize(wo, bits=8, reduce_axes=(-3, -2))
        assert qo.scale.shape == (1, 1, 32)

    def test_pytree_jit_and_scan_slicing(self):
        w = jax.random.normal(jax.random.PRNGKey(4), (3, 64, 32))
        qa = QuantizedArray.quantize(w)
        y = jax.jit(lambda qa, x: x @ materialize(qa)[0])(qa, jnp.ones((5, 64)))
        assert y.shape == (5, 32)
        # leading-axis slicing (what lax.scan does to stacked layer params)
        sliced = jax.tree_util.tree_map(lambda l: l[1], qa)
        np.testing.assert_allclose(
            np.asarray(sliced.dequantize()), np.asarray(qa.dequantize()[1]), rtol=1e-6
        )
        # keyed flatten exposes .q/.scale children (checkpoint manifest names)
        paths = [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_flatten_with_path(qa)[0]]
        assert paths == [".q", ".scale"]

    def test_rejects_bad_args(self):
        w = jnp.ones((8, 8))
        with pytest.raises(ValueError):
            QuantizedArray.quantize(w, bits=3)
        with pytest.raises(ValueError):
            QuantizedArray.quantize(w, bits=4, group_size=3)
        with pytest.raises(ValueError):
            QuantizedArray.quantize(w, bits=8, group_size=5)


# ---------------------------------------------------------------------------
# PTQ policies
# ---------------------------------------------------------------------------


class TestPTQ:
    def test_experts_only_policy(self):
        cfg = _moe_cfg()
        params = init_params(cfg, jax.random.PRNGKey(0))
        qp = quantize_params(params, QuantConfig(bits=8, policy="experts"))
        paths = quantized_leaf_paths(qp)
        assert paths and all("/moe/" in p for p in paths)
        # router / norms / embeddings untouched
        assert not any("router" in p or "norm" in p or "embed" in p for p in paths)

    def test_policy_widening(self):
        cfg = _moe_cfg()
        params = init_params(cfg, jax.random.PRNGKey(0))
        n_exp = len(quantized_leaf_paths(quantize_params(params, QuantConfig(policy="experts"))))
        n_attn = len(
            quantized_leaf_paths(quantize_params(params, QuantConfig(policy="experts_attn")))
        )
        n_all = len(quantized_leaf_paths(quantize_params(params, QuantConfig(policy="all"))))
        assert n_exp < n_attn < n_all
        with pytest.raises(ValueError):
            quantize_params(params, QuantConfig(policy="everything"))

    def test_expert_bytes_reduction_3x(self):
        """Acceptance: int8+scales vs fp32 expert bytes >= 3x smaller."""
        cfg = _moe_cfg()
        params = init_params(cfg, jax.random.PRNGKey(0))
        qp = quantize_params(params, QuantConfig(bits=8, policy="experts"))
        fp_expert = tree_bytes(params) - (tree_bytes(qp) - tree_bytes(qp, only_quantized=True))
        q_expert = tree_bytes(qp, only_quantized=True)
        assert fp_expert / q_expert >= 3.0

    def test_dequantize_params_restores_structure(self):
        cfg = _moe_cfg()
        params = init_params(cfg, jax.random.PRNGKey(0))
        qp = quantize_params(params, QuantConfig(bits=8, policy="all"))
        deq = dequantize_params(qp)
        assert jax.tree_util.tree_structure(deq) == jax.tree_util.tree_structure(params)


# ---------------------------------------------------------------------------
# Pallas dequant-in-kernel expert MLP
# ---------------------------------------------------------------------------


class TestQuantKernel:
    @pytest.mark.parametrize("E,C,D,F", [(2, 128, 64, 256), (4, 256, 128, 256), (3, 128, 32, 512)])
    def test_kernel_matches_einsum_ref(self, E, C, D, F):
        k = jax.random.PRNGKey(E * C + D + F)
        xe = jax.random.normal(jax.random.fold_in(k, 1), (E, C, D), jnp.float32)
        wi = jax.random.normal(jax.random.fold_in(k, 2), (E, D, F)) / np.sqrt(D)
        wg = jax.random.normal(jax.random.fold_in(k, 3), (E, D, F)) / np.sqrt(D)
        wo = jax.random.normal(jax.random.fold_in(k, 4), (E, F, D)) / np.sqrt(F)
        qi, qg, qo = (QuantizedArray.quantize(w) for w in (wi, wg, wo))
        got = expert_mlp_quant(xe, qi, qg, qo, interpret=True)
        want = expert_mlp_quant_ref(xe, qi, qg, qo)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-2)

    def test_quant_close_to_fp(self):
        k = jax.random.PRNGKey(7)
        E, C, D, F = 2, 128, 64, 256
        xe = jax.random.normal(jax.random.fold_in(k, 1), (E, C, D), jnp.float32)
        wi = jax.random.normal(jax.random.fold_in(k, 2), (E, D, F)) / np.sqrt(D)
        wg = jax.random.normal(jax.random.fold_in(k, 3), (E, D, F)) / np.sqrt(D)
        wo = jax.random.normal(jax.random.fold_in(k, 4), (E, F, D)) / np.sqrt(F)
        qi, qg, qo = (QuantizedArray.quantize(w) for w in (wi, wg, wo))
        got = expert_mlp_quant_ref(xe, qi, qg, qo)
        fp = expert_mlp_ref(xe, wi, wg, wo)
        scale = float(jnp.abs(fp).max())
        assert float(jnp.abs(got - fp).max()) < 0.05 * max(scale, 1.0)

    def test_kernel_mode_falls_back_on_nondivisible_shapes(self):
        """expert_capacity pads to 8, not 128 — C not divisible by the block
        is a layout the kernel cannot take.  "kernel" mode raises rather
        than widening the experts through the einsum ref behind the
        caller's back; the fallback is the explicit "ref" backend."""
        from repro.core.moe import experts_ffn
        from repro.kernels.expert_mlp_quant import _check_kernel_compat

        k = jax.random.PRNGKey(0)
        E, C, D, F = 2, 136, 32, 256  # C=136: block_c=128 does not divide
        xe = jax.random.normal(jax.random.fold_in(k, 1), (E, C, D), jnp.float32)
        wi = jax.random.normal(jax.random.fold_in(k, 2), (E, D, F)) / np.sqrt(D)
        wg = jax.random.normal(jax.random.fold_in(k, 3), (E, D, F)) / np.sqrt(D)
        wo = jax.random.normal(jax.random.fold_in(k, 4), (E, F, D)) / np.sqrt(F)
        qp = {"wi": QuantizedArray.quantize(wi), "wg": QuantizedArray.quantize(wg),
              "wo": QuantizedArray.quantize(wo)}
        assert not _check_kernel_compat(xe, qp["wi"], qp["wg"], qp["wo"])
        with pytest.raises(ValueError, match="block-divisible"):
            experts_ffn(qp, xe, "swiglu", backend="kernel")
        got = experts_ffn(qp, xe, "swiglu", backend="ref")
        want = expert_mlp_quant_ref(xe, qp["wi"], qp["wg"], qp["wo"])
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)

    def test_int8_group_size_is_honored(self):
        cfg = _moe_cfg()
        params = init_params(cfg, jax.random.PRNGKey(0))
        qp = quantize_params(params, QuantConfig(bits=8, group_size=32, policy="experts"))
        leaves = [l for l in jax.tree_util.tree_leaves(
            qp, is_leaf=lambda l: isinstance(l, QuantizedArray)) if isinstance(l, QuantizedArray)]
        assert leaves and all(l.group_size == 32 for l in leaves)

    def test_moe_layer_swiglu_quant_path(self):
        """Full moe_layer with quantized swiglu experts (the kernel-eligible
        layout) stays close to the fp layer output."""
        from repro.configs.base import FFNSpec, ModelConfig
        from repro.core.moe import init_moe, moe_layer

        cfg = ModelConfig(name="q", family="moe", source="t", d_model=64, num_heads=4,
                          num_kv_heads=4, head_dim=16, vocab_size=128, segments=(),
                          param_dtype="float32", compute_dtype="float32")
        spec = FFNSpec(kind="moe", d_ff=128, num_experts=4, top_k=1, act="swiglu")
        params = init_moe(jax.random.PRNGKey(0), cfg, spec, jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 64))
        y_fp, _ = moe_layer(cfg, spec, params, x)
        qparams = quantize_params({"moe": params}, QuantConfig(bits=8, policy="experts"))["moe"]
        assert isinstance(qparams["wi"], QuantizedArray) and isinstance(qparams["wg"], QuantizedArray)
        y_q, _ = moe_layer(cfg, spec, qparams, x)
        scale = float(jnp.abs(y_fp).max())
        assert float(jnp.abs(y_q - y_fp).max()) < 0.05 * max(scale, 1.0)

    def test_moe_layer_backend_toggle(self):
        """experts_ffn routes quantized weights through kernel and ref paths
        identically (the kernel is exact vs the ref in interpret mode)."""
        cfg = _moe_cfg()
        params = init_params(cfg, jax.random.PRNGKey(0))
        qp = quantize_params(params, QuantConfig(bits=8, policy="experts"))
        toks = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0, cfg.vocab_size)
        try:
            set_quant_expert_backend("ref")
            ref_logits, _ = forward(cfg, qp, toks)
        finally:
            set_quant_expert_backend(None)
        # NLG configs use gelu experts -> both modes take the dequant path;
        # just assert the default path agrees with the forced-ref path.
        logits, _ = forward(cfg, qp, toks)
        np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits), atol=1e-5)


# ---------------------------------------------------------------------------
# End-to-end serving parity (acceptance: >= 95% greedy token match)
# ---------------------------------------------------------------------------


class TestServingParity:
    def _generate(self, cfg, params, reqs):
        ec = EngineConfig(max_batch=8, max_prefill=32, max_decode=8)
        return Engine(cfg, params, ec).generate(reqs)

    def test_engine_greedy_matches_fp(self):
        cfg = _moe_cfg()
        params = init_params(cfg, jax.random.PRNGKey(0))
        qp = quantize_params(params, QuantConfig(bits=8, policy="experts"))
        rng = np.random.default_rng(0)
        reqs = [
            Request(prompt=rng.integers(1, cfg.vocab_size, size=16).tolist(), max_new_tokens=8)
            for _ in range(8)
        ]
        fp_out = self._generate(cfg, params, reqs)
        q_out = self._generate(cfg, qp, reqs)
        tot = match = 0
        for a, b in zip(fp_out, q_out):
            assert len(a.tokens) == len(b.tokens)
            tot += len(a.tokens)
            match += sum(int(x == y) for x, y in zip(a.tokens, b.tokens))
        assert match / tot >= 0.95, f"greedy match {match}/{tot}"

    def test_continuous_engine_accepts_quantized(self):
        cfg = _moe_cfg()
        params = init_params(cfg, jax.random.PRNGKey(0))
        qp = quantize_params(params, QuantConfig(bits=8, policy="experts"))
        rng = np.random.default_rng(1)
        prompts = [rng.integers(1, cfg.vocab_size, size=12).tolist() for _ in range(3)]

        def run(p):
            eng = ContinuousEngine(cfg, p, slots=2, capacity=64)
            for pr in prompts:
                eng.submit(Request(prompt=pr, max_new_tokens=6))
            return eng.run_until_done()

        fp_done, q_done = run(params), run(qp)
        assert set(fp_done) == set(q_done)
        tot = match = 0
        for rid in fp_done:
            a, b = fp_done[rid].tokens, q_done[rid].tokens
            tot += len(a)
            match += sum(int(x == y) for x, y in zip(a, b))
        assert match / tot >= 0.95


# ---------------------------------------------------------------------------
# Checkpoint round-trip
# ---------------------------------------------------------------------------


class TestCheckpointRoundtrip:
    def test_quantized_tree_roundtrips(self, tmp_path):
        cfg = _moe_cfg()
        params = init_params(cfg, jax.random.PRNGKey(0))
        qp = quantize_params(params, QuantConfig(bits=4, group_size=16, policy="experts"))
        ckpt.save(str(tmp_path / "q"), qp, step=7)
        like = quantize_params(
            init_params(cfg, jax.random.PRNGKey(1)), QuantConfig(bits=4, group_size=16)
        )
        loaded, step = ckpt.load(str(tmp_path / "q"), like)
        assert step == 7
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)), qp, loaded
        )
        # metadata survives via the like-tree
        leaves = jax.tree_util.tree_leaves(
            loaded, is_leaf=lambda l: isinstance(l, QuantizedArray)
        )
        assert any(isinstance(l, QuantizedArray) and l.bits == 4 for l in leaves)

    def test_fp_checkpoint_into_quantized_like_fails_clearly(self, tmp_path):
        cfg = _moe_cfg()
        params = init_params(cfg, jax.random.PRNGKey(0))
        ckpt.save(str(tmp_path / "fp"), params, step=0)
        like = quantize_params(params, QuantConfig(bits=8, policy="experts"))
        with pytest.raises(ValueError, match="missing"):
            ckpt.load(str(tmp_path / "fp"), like)

    def test_shape_mismatch_fails_clearly(self, tmp_path):
        ckpt.save(str(tmp_path / "c"), {"x": jnp.ones((3,))}, step=0)
        with pytest.raises(ValueError, match="shape"):
            ckpt.load(str(tmp_path / "c"), {"x": jnp.ones((4,))})

    def test_missing_dir_fails_clearly(self, tmp_path):
        with pytest.raises(ValueError, match="manifest"):
            ckpt.load(str(tmp_path / "nope"), {"x": jnp.ones((1,))})
