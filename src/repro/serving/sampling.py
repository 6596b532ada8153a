"""Token sampling for the serving engine: greedy, temperature, top-k and
top-p (nucleus) filtering — top-k and top-p compose (k first, then p)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

_MASKED = -1e30


def _top_k_mask(logits: jax.Array, top_k: int) -> jax.Array:
    vals, _ = jax.lax.top_k(logits, top_k)
    cut = vals[:, -1:]
    return jnp.where(logits < cut, _MASKED, logits)


def _top_p_mask(logits: jax.Array, top_p: float) -> jax.Array:
    """Keep the smallest set of tokens whose probability mass >= top_p."""
    sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # token i stays if the mass strictly before it is < top_p (so the first
    # token crossing the threshold is included)
    keep = cum - probs < top_p
    n_keep = jnp.maximum(jnp.sum(keep, axis=-1, keepdims=True), 1)
    cutoff = jnp.take_along_axis(sorted_logits, n_keep - 1, axis=-1)
    return jnp.where(logits < cutoff, _MASKED, logits)


def sample(
    logits: jax.Array,
    key,
    *,
    temperature: float = 0.0,
    top_k: int = 0,
    top_p: float = 0.0,
) -> jax.Array:
    """logits [B, V] -> tokens [B]."""
    with jax.named_scope("sample"):
        if temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        logits = logits.astype(jnp.float32) / temperature
        if top_k > 0:
            logits = _top_k_mask(logits, top_k)
        if 0.0 < top_p < 1.0:
            logits = _top_p_mask(logits, top_p)
        return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)
