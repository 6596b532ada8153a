"""``QuantizedKV``: an int8 KV-cache layout with per-head, per-timestep scales.

DeepSpeed-MoE's inference analysis (§5) treats decode as memory-bandwidth
bound; PR 1 (MoQ, §4) shrank the expert weights, and at long context / large
batch the next dominant term in decode HBM traffic is the KV cache — every
decode step streams the full ``[B, T, H_kv, dh]`` K and V history.  Storing
them as int8 with one f32 scale per (batch, timestep, kv-head) cuts those
bytes ~4x (dh/(dh+4) of the ideal 4x for an f32 cache; 48-head-dim demo
models get 3.7x) while keeping the quantization *local*: each written token
is scaled independently, so cache writes never touch earlier entries and
ring-buffer slot reuse just overwrites (q, scale) pairs in place.

Like :class:`~repro.quant.qarrays.QuantizedArray`, the class is a pytree
node with attr keys, so pooled caches flow through ``jax.jit``,
``jax.lax.scan`` over stacked layers, ``dynamic_update_slice`` slot writes,
and the masked merges of continuous batching without special-casing: ``q``
and ``scale`` both carry the same leading (layers, batch, time) dims and are
sliced/stacked consistently.

Layout (one cache tensor, e.g. K):

  * ``q``      int8  [..., T, H_kv, dh]   — symmetric values, zero-point 0
  * ``scale``  f32   [..., T, H_kv, 1]    — amax/127 per (timestep, head)

The leading dims are layout-agnostic: contiguous slot caches carry
``[..., B, T, ...]``, and the paged serving path lays the same pair out *per
page* as token rows, ``q [..., n_pages + 1, page_size, H_kv*dh]`` and
``scale [..., n_pages + 1, page_size, H_kv]`` (models/attention.py
``init_paged_kv_cache``) — per-token scales mean pages quantize, scatter,
recycle, and gather through block tables with no rescaling anywhere, and
the dequant-in-VMEM kernels stream 1-byte entries either way.

An all-zero slot quantizes to (q=0, scale≈0) and dequantizes to exact zeros,
so freshly-initialized / vacated ring slots behave like the fp cache's zero
fill (masked out by ``pos == -1`` anyway).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

_KV_QMAX = 127.0


def kv_quantize_values(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """x: [..., T, H, dh] -> (q int8 [..., T, H, dh], scale f32 [..., T, H, 1]).

    Symmetric per-(timestep, head) quantization over the head dim — the
    finest granularity that still amortizes (dh values share 4 scale bytes),
    and the one that matches decode writes: one new (q, scale) pair per head
    per step, no rescaling of history.
    """
    x32 = jnp.asarray(x, jnp.float32)
    amax = jnp.max(jnp.abs(x32), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / _KV_QMAX
    q = jnp.clip(jnp.round(x32 / scale), -_KV_QMAX, _KV_QMAX).astype(jnp.int8)
    return q, scale


@jax.tree_util.register_pytree_with_keys_class
class QuantizedKV:
    """int8 values + f32 per-(timestep, head) scales for one cache tensor."""

    __slots__ = ("q", "scale", "orig_dtype")

    def __init__(self, q, scale, orig_dtype: str):
        self.q = q
        self.scale = scale
        self.orig_dtype = orig_dtype

    # -- pytree protocol ----------------------------------------------------
    def tree_flatten_with_keys(self):
        children = (
            (jax.tree_util.GetAttrKey("q"), self.q),
            (jax.tree_util.GetAttrKey("scale"), self.scale),
        )
        return children, (self.orig_dtype,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        q, scale = children
        return cls(q, scale, *aux)

    # -- array-ish surface --------------------------------------------------
    @property
    def shape(self) -> Tuple[int, ...]:
        return tuple(jnp.shape(self.q))

    @property
    def ndim(self) -> int:
        return len(self.shape)

    @property
    def dtype(self):
        return jnp.dtype(self.orig_dtype)

    @property
    def nbytes(self) -> int:
        # via .shape/.dtype (not .size) so jax.eval_shape trees work too
        import numpy as np

        return int(
            np.prod(self.q.shape) * jnp.dtype(self.q.dtype).itemsize
            + np.prod(self.scale.shape) * jnp.dtype(self.scale.dtype).itemsize
        )

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"QuantizedKV(int8, shape={self.shape}, orig={self.orig_dtype})"

    # -- numerics -----------------------------------------------------------
    @classmethod
    def zeros(cls, shape: Tuple[int, ...], dtype) -> "QuantizedKV":
        """Empty cache tensor: q=0 / scale=0 dequantizes to exact zeros."""
        return cls(
            jnp.zeros(shape, jnp.int8),
            jnp.zeros(shape[:-1] + (1,), jnp.float32),
            str(jnp.dtype(dtype)),
        )

    @classmethod
    def quantize(cls, x: jax.Array) -> "QuantizedKV":
        q, scale = kv_quantize_values(x)
        return cls(q, scale, str(x.dtype))

    def dequantize(self) -> jax.Array:
        return (self.q.astype(jnp.float32) * self.scale).astype(self.dtype)


def materialize_kv(x):
    """Dequantize if quantized, passthrough otherwise — the KV analogue of
    :func:`repro.quant.qarrays.materialize`."""
    if isinstance(x, QuantizedKV):
        return x.dequantize()
    return x


def kv_cache_bytes(caches) -> int:
    """Total KV/state cache bytes; QuantizedKV leaves count packed ints +
    scales (the serving-memory headroom number: batch slots ∝ 1/bytes).
    Accepts concrete arrays or a ``jax.eval_shape`` tree — sizing never
    needs to allocate a cache."""
    import numpy as np

    total = 0
    for leaf in jax.tree_util.tree_leaves(
        caches, is_leaf=lambda l: isinstance(l, QuantizedKV)
    ):
        if isinstance(leaf, QuantizedKV):
            total += leaf.nbytes
        else:
            total += int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
    return total
