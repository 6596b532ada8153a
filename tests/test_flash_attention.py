"""Flash-attention Pallas kernel vs pure-jnp oracle (interpret mode), with
hypothesis shape sweeps — the kernel behind the roofline's score-tensor
exclusion (EXPERIMENTS.md §Roofline)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st  # hypothesis, or a single-draw fallback shim

from repro.kernels.flash_attention import flash_attention, flash_attention_ref


def _qkv(BH, S, T, dh, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (
        jax.random.normal(ks[0], (BH, S, dh)),
        jax.random.normal(ks[1], (BH, T, dh)),
        jax.random.normal(ks[2], (BH, T, dh)),
    )


class TestFlashAttention:
    @pytest.mark.parametrize(
        "BH,S,T,dh,causal",
        [
            (2, 256, 256, 64, True),
            (1, 512, 512, 32, True),
            (3, 128, 384, 16, False),
            (2, 128, 128, 128, True),
        ],
    )
    def test_matches_ref(self, BH, S, T, dh, causal):
        q, k, v = _qkv(BH, S, T, dh, seed=S + T)
        got = flash_attention(q, k, v, scale=dh**-0.5, causal=causal, interpret=True)
        want = flash_attention_ref(q, k, v, scale=dh**-0.5, causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=1e-4)

    def test_block_shape_invariance(self):
        """Online softmax must be exact regardless of the k-tiling."""
        q, k, v = _qkv(1, 256, 512, 32, seed=9)
        outs = [
            flash_attention(q, k, v, scale=0.2, causal=False, block_q=bq, block_k=bk, interpret=True)
            for bq, bk in [(128, 512), (128, 128), (256, 64)]
        ]
        for o in outs[1:]:
            np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o), atol=2e-5)

    def test_bf16(self):
        q, k, v = (t.astype(jnp.bfloat16) for t in _qkv(2, 128, 128, 64, seed=4))
        got = flash_attention(q, k, v, scale=0.125, causal=True, interpret=True)
        want = flash_attention_ref(q, k, v, scale=0.125, causal=True)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32), atol=2e-2, rtol=2e-2
        )

    @settings(max_examples=8, deadline=None)
    @given(
        S=st.sampled_from([128, 256]),
        T=st.sampled_from([128, 256, 512]),
        dh=st.sampled_from([16, 64]),
        causal=st.booleans(),
        seed=st.integers(0, 50),
    )
    def test_property_sweep(self, S, T, dh, causal, seed):
        if causal:
            T = S  # kernel's causal mask assumes aligned q/k position ranges
        q, k, v = _qkv(1, S, T, dh, seed=seed)
        got = flash_attention(q, k, v, scale=dh**-0.5, causal=causal, interpret=True)
        want = flash_attention_ref(q, k, v, scale=dh**-0.5, causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5, rtol=1e-3)
