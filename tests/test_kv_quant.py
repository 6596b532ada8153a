"""Quantized KV cache (repro/quant/kv.py + kernels/attention_quant.py +
models/attention.py cache paths): QuantizedKV numerics/pytree behavior, the
Pallas dequant-in-kernel decode attention vs its einsum oracle, cache
write/read round-trips, end-to-end decode parity against the fp cache, the
cache-byte reduction claim, and continuous-batching slot reuse."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.base import QuantConfig
from repro.core.prmoe import nlg_moe
from repro.kernels.attention_quant import decode_attention_quant, decode_attention_quant_ref
from repro.models.attention import init_kv_cache, _cache_write_decode, _cache_write_prefill
from repro.models.model import (
    decode_step,
    init_caches,
    init_params,
    prefill,
    ragged_decode_step,
)
from repro.quant import QuantizedKV, kv_cache_bytes, kv_quantize_values, materialize_kv
from repro.serving.continuous import ContinuousEngine
from repro.serving.engine import Engine, EngineConfig, Request


def _demo_cfg(vocab=512, layers=4, d_model=192, heads=4, experts=16):
    """Same family/shape as examples/quantize_and_serve.py's demo model
    (head_dim = 48, the shape the ≥3.5x cache-byte claim is made on)."""
    return nlg_moe("kv-quant-test", layers, d_model, heads, experts, vocab=vocab).replace(
        param_dtype="float32", compute_dtype="float32"
    )


# ---------------------------------------------------------------------------
# QuantizedKV numerics + pytree behavior
# ---------------------------------------------------------------------------


class TestQuantizedKV:
    def test_roundtrip_error_bound(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 32, 4, 48))
        kv = QuantizedKV.quantize(x)
        err = jnp.max(jnp.abs(kv.dequantize() - x))
        # symmetric int8: error <= scale/2 = amax/254 per (t, h) group
        bound = jnp.max(jnp.abs(x)) / 254.0 + 1e-6
        assert float(err) <= float(bound)
        assert kv.q.dtype == jnp.int8 and kv.scale.dtype == jnp.float32
        assert kv.scale.shape == (2, 32, 4, 1)

    def test_zeros_dequantize_exact(self):
        kv = QuantizedKV.zeros((1, 8, 2, 16), jnp.float32)
        np.testing.assert_array_equal(np.asarray(kv.dequantize()), 0.0)

    def test_per_timestep_scales_are_independent(self):
        """A huge token must not degrade other timesteps' resolution."""
        x = jnp.ones((1, 4, 1, 16)) * 0.01
        x = x.at[0, 2].set(1000.0)
        kv = QuantizedKV.quantize(x)
        err_small = jnp.max(jnp.abs(kv.dequantize()[0, 0] - x[0, 0]))
        assert float(err_small) < 1e-4  # would be ~4.0 with a shared scale

    def test_pytree_flatten_keys_and_jit(self):
        kv = QuantizedKV.quantize(jax.random.normal(jax.random.PRNGKey(1), (2, 8, 2, 16)))
        kvs, treedef = jax.tree_util.tree_flatten_with_path(kv)
        names = ["".join(str(p) for p in path) for path, _ in kvs]
        assert names == [".q", ".scale"]  # checkpoint manifest names
        out = jax.jit(lambda c: c.dequantize())(kv)
        np.testing.assert_allclose(np.asarray(out), np.asarray(kv.dequantize()))

    def test_scan_slices_leading_axis_consistently(self):
        stacked = QuantizedKV.quantize(jax.random.normal(jax.random.PRNGKey(2), (3, 2, 8, 2, 16)))

        def body(c, kv):
            return c, jnp.sum(kv.dequantize())

        _, sums = jax.lax.scan(body, 0.0, stacked)
        want = [float(jnp.sum(stacked.dequantize()[i])) for i in range(3)]
        np.testing.assert_allclose(np.asarray(sums), want, rtol=1e-6)

    def test_materialize_kv_passthrough(self):
        x = jnp.ones((2, 3))
        assert materialize_kv(x) is x

    def test_nbytes_counts_ints_plus_scales(self):
        kv = QuantizedKV.zeros((1, 16, 2, 48), jnp.float32)
        assert kv.nbytes == 16 * 2 * 48 + 16 * 2 * 4


# ---------------------------------------------------------------------------
# Pallas decode kernel vs einsum oracle
# ---------------------------------------------------------------------------


class TestDecodeKernel:
    @pytest.mark.parametrize("window,softcap", [(0, 0.0), (16, 0.0), (0, 30.0)])
    def test_kernel_matches_ref(self, window, softcap):
        B, T, Hkv, G, dh = 3, 48, 2, 3, 16
        k = jax.random.normal(jax.random.PRNGKey(0), (B, T, Hkv, dh))
        v = jax.random.normal(jax.random.PRNGKey(1), (B, T, Hkv, dh))
        q = jax.random.normal(jax.random.PRNGKey(2), (B, Hkv, G, dh))
        kq, ks = kv_quantize_values(k)
        vq, vs = kv_quantize_values(v)
        kpos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
        kpos = kpos.at[:, 40:].set(-1)  # empty ring slots
        qpos = jnp.full((B, 1), 39, jnp.int32)
        args = dict(scale=0.25, window=window, softcap=softcap)
        yk = decode_attention_quant(q, kq, ks, vq, vs, kpos, qpos, interpret=True, **args)
        yr = decode_attention_quant_ref(q, kq, ks, vq, vs, kpos, qpos, **args)
        np.testing.assert_allclose(np.asarray(yk), np.asarray(yr), atol=1e-5)

    def test_kernel_tiles_nondivisible_t(self):
        """T=48 with block 128 falls back to a fitting divisor tile."""
        B, T, Hkv, G, dh = 1, 40, 1, 2, 16
        k = jax.random.normal(jax.random.PRNGKey(0), (B, T, Hkv, dh))
        q = jax.random.normal(jax.random.PRNGKey(2), (B, Hkv, G, dh))
        kq, ks = kv_quantize_values(k)
        kpos = jnp.arange(T, dtype=jnp.int32)[None]
        qpos = jnp.full((B, 1), T - 1, jnp.int32)
        yk = decode_attention_quant(q, kq, ks, kq, ks, kpos, qpos, scale=0.25, block_t=16, interpret=True)
        yr = decode_attention_quant_ref(q, kq, ks, kq, ks, kpos, qpos, scale=0.25)
        np.testing.assert_allclose(np.asarray(yk), np.asarray(yr), atol=1e-5)

    def test_ref_matches_fp_attention_closely(self):
        """Quantization error at the attention output stays ~1% scale."""
        B, T, Hkv, G, dh = 2, 32, 2, 2, 32
        k = jax.random.normal(jax.random.PRNGKey(0), (B, T, Hkv, dh))
        v = jax.random.normal(jax.random.PRNGKey(1), (B, T, Hkv, dh))
        q = jax.random.normal(jax.random.PRNGKey(2), (B, Hkv, G, dh))
        kq, ks = kv_quantize_values(k)
        vq, vs = kv_quantize_values(v)
        kpos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
        qpos = jnp.full((B, 1), T - 1, jnp.int32)
        yq = decode_attention_quant_ref(q, kq, ks, vq, vs, kpos, qpos, scale=dh**-0.5)
        # fp oracle
        s = jnp.einsum("bhgd,bthd->bhgt", q, k) * dh**-0.5
        mask = (kpos[:, None, None, :] <= qpos[:, :, None, None])
        s = jnp.where(mask, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        y_fp = jnp.einsum("bhgt,bthd->bhgd", p, v)
        assert float(jnp.max(jnp.abs(yq - y_fp))) < 0.05


# ---------------------------------------------------------------------------
# Cache write/read round-trips
# ---------------------------------------------------------------------------


class TestCacheOps:
    def test_init_rejects_bad_bits(self):
        with pytest.raises(ValueError):
            init_kv_cache(1, 8, 2, 16, jnp.float32, kv_bits=4)

    def test_quantized_layout(self):
        c = init_kv_cache(2, 16, 4, 48, jnp.float32, kv_bits=8)
        assert isinstance(c["k"], QuantizedKV) and isinstance(c["v"], QuantizedKV)
        assert c["k"].q.shape == (2, 16, 4, 48)
        assert c["k"].scale.shape == (2, 16, 4, 1)
        assert c["pos"].shape == (2, 16)

    def test_decode_write_roundtrip(self):
        """Writing one token then dequantizing equals quantize(token)."""
        c = init_kv_cache(2, 8, 2, 16, jnp.float32, kv_bits=8)
        k_new = jax.random.normal(jax.random.PRNGKey(0), (2, 1, 2, 16))
        v_new = jax.random.normal(jax.random.PRNGKey(1), (2, 1, 2, 16))
        c2 = _cache_write_decode(c, k_new, v_new, jnp.asarray(3, jnp.int32))
        got = materialize_kv(c2["k"])[:, 3:4]
        want = QuantizedKV.quantize(k_new).dequantize()
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
        # untouched slots stay zero / pos -1
        assert float(jnp.abs(materialize_kv(c2["v"])[:, :3]).max()) == 0.0
        assert int(c2["pos"][0, 3]) == 3 and int(c2["pos"][0, 0]) == -1

    def test_ragged_write_matches_uniform(self):
        c = init_kv_cache(3, 8, 2, 16, jnp.float32, kv_bits=8)
        k_new = jax.random.normal(jax.random.PRNGKey(0), (3, 1, 2, 16))
        v_new = jax.random.normal(jax.random.PRNGKey(1), (3, 1, 2, 16))
        c_u = _cache_write_decode(c, k_new, v_new, jnp.asarray(5, jnp.int32))
        c_r = _cache_write_decode(c, k_new, v_new, jnp.full((3,), 5, jnp.int32))
        for key in ("k", "v"):
            np.testing.assert_array_equal(np.asarray(c_u[key].q), np.asarray(c_r[key].q))
            np.testing.assert_allclose(np.asarray(c_u[key].scale), np.asarray(c_r[key].scale))

    def test_prefill_ring_write(self):
        """capacity < S: last `cap` tokens land at slot pos%cap, quantized."""
        cap, S = 8, 12
        c = init_kv_cache(1, cap, 2, 16, jnp.float32, kv_bits=8)
        k = jax.random.normal(jax.random.PRNGKey(0), (1, S, 2, 16))
        pos = jnp.arange(S, dtype=jnp.int32)[None]
        c2 = _cache_write_prefill(c, k, k, pos)
        got = materialize_kv(c2["k"])
        for p in range(S - cap, S):
            slot = p % cap
            want = QuantizedKV.quantize(k[:, p : p + 1]).dequantize()[0, 0]
            np.testing.assert_allclose(np.asarray(got[0, slot]), np.asarray(want), atol=1e-6)
            assert int(c2["pos"][0, slot]) == p


# ---------------------------------------------------------------------------
# End-to-end decode parity + the byte-reduction claim
# ---------------------------------------------------------------------------


class TestServingParity:
    def test_cache_byte_reduction_3_5x(self):
        """Acceptance: ≥3.5x fewer cache bytes on the demo shape (dh=48)."""
        cfg = _demo_cfg()
        fp = kv_cache_bytes(init_caches(cfg, 8, 128))
        q8 = kv_cache_bytes(init_caches(cfg, 8, 128, kv_bits=8))
        assert fp / q8 >= 3.5, f"only {fp/q8:.2f}x"

    def test_decode_logits_close_and_caches_quantized(self):
        cfg = _demo_cfg(layers=2)
        params = init_params(cfg, jax.random.PRNGKey(0))
        B, S = 2, 10
        toks = jax.random.randint(jax.random.PRNGKey(1), (B, S + 1), 0, cfg.vocab_size)
        lg_fp, c_fp = prefill(cfg, params, toks[:, :S], init_caches(cfg, B, S + 4))
        lg_q, c_q = prefill(cfg, params, toks[:, :S], init_caches(cfg, B, S + 4, kv_bits=8))
        # prefill logits identical: prefill attends over in-flight fp K/V
        np.testing.assert_allclose(np.asarray(lg_fp), np.asarray(lg_q), atol=1e-5)
        d_fp, _ = decode_step(cfg, params, toks[:, S:], jnp.asarray(S, jnp.int32), c_fp)
        d_q, _ = decode_step(cfg, params, toks[:, S:], jnp.asarray(S, jnp.int32), c_q)
        # decode reads the quantized history: close, not exact
        assert float(jnp.max(jnp.abs(d_fp - d_q))) < 0.5
        assert isinstance(c_q["seg0"]["pos0"]["self"]["k"], QuantizedKV)

    def test_uniform_ragged_matches_decode_quant(self):
        cfg = _demo_cfg(layers=2)
        params = init_params(cfg, jax.random.PRNGKey(0))
        B, S = 3, 8
        toks = jax.random.randint(jax.random.PRNGKey(2), (B, S + 1), 0, cfg.vocab_size)
        _, caches = prefill(cfg, params, toks[:, :S], init_caches(cfg, B, S + 4, kv_bits=8))
        lg_u, c_u = decode_step(cfg, params, toks[:, S:], jnp.asarray(S, jnp.int32), caches)
        lg_r, c_r = ragged_decode_step(
            cfg, params, toks[:, S:], jnp.full((B,), S, jnp.int32), jnp.ones((B,), bool), caches
        )
        np.testing.assert_allclose(np.asarray(lg_u), np.asarray(lg_r), atol=1e-5)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6),
            c_u, c_r,
        )

    def test_engine_greedy_agreement_trained(self):
        """Acceptance: 100% greedy-token agreement on a trained demo
        checkpoint (the briefly-trained analogue of the example's 80-step
        run; an untrained model's near-uniform logits would make this a
        coin-flip test of fp noise, not of the KV cache)."""
        from repro.data.pipeline import data_stream
        from repro.training.trainer import TrainConfig, train_loop

        cfg = _demo_cfg(layers=2, d_model=96, experts=4)
        it = data_stream(cfg.vocab_size, 8, 32, seed=0)
        params, _, _ = train_loop(
            cfg, TrainConfig(lr=1.5e-3, warmup_steps=5, decay_steps=40), it, 40, log_every=100
        )
        rng = np.random.default_rng(0)
        reqs = [
            Request(prompt=rng.integers(1, cfg.vocab_size, size=16).tolist(), max_new_tokens=8)
            for _ in range(8)
        ]
        ec = EngineConfig(max_batch=8, max_prefill=32, max_decode=8)
        fp_out = Engine(cfg, params, ec).generate(reqs)
        q_out = Engine(
            cfg, params, EngineConfig(max_batch=8, max_prefill=32, max_decode=8, kv_cache_bits=8)
        ).generate(reqs)
        tot = match = 0
        for a, b in zip(fp_out, q_out):
            assert len(a.tokens) == len(b.tokens)
            tot += len(a.tokens)
            match += sum(int(x == y) for x, y in zip(a.tokens, b.tokens))
        assert match == tot, f"greedy agreement {match}/{tot}"

    def test_quant_config_knob(self):
        qcfg = QuantConfig(kv_cache_bits=8)
        assert qcfg.kv_cache_bits == 8
        assert QuantConfig().kv_cache_bits == 0
        assert EngineConfig().kv_cache_bits == 0


# ---------------------------------------------------------------------------
# Continuous batching: slot reuse with a quantized pool
# ---------------------------------------------------------------------------


class TestContinuousSlotReuse:
    def test_long_context_slot_reuse_matches_fp(self):
        """5 requests through 2 slots: every slot is vacated and re-admitted
        with a fresh long prompt (prefill overwrites the previous tenant's
        quantized entries in place); outputs must track the fp-cache pool."""
        cfg = _demo_cfg(layers=2, d_model=96, experts=4)
        params = init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(3)
        prompts = [rng.integers(1, cfg.vocab_size, size=40).tolist() for _ in range(5)]

        def run(kv_bits):
            eng = ContinuousEngine(cfg, params, slots=2, capacity=48, kv_cache_bits=kv_bits)
            for pr in prompts:
                eng.submit(Request(prompt=pr, max_new_tokens=6))
            done = eng.run_until_done()
            return eng, done

        eng_q, q_done = run(8)
        _, fp_done = run(0)
        assert set(q_done) == set(fp_done) == set(range(5))
        # pooled caches stayed quantized through admission + decode + reuse
        leaf = eng_q.caches["seg0"]["pos0"]["self"]["k"]
        assert isinstance(leaf, QuantizedKV)
        tot = match = 0
        for rid in fp_done:
            a, b = fp_done[rid].tokens, q_done[rid].tokens
            assert len(a) == len(b)
            tot += len(a)
            match += sum(int(x == y) for x, y in zip(a, b))
        assert match / tot >= 0.9, f"agreement {match}/{tot}"
