"""Plain float32 reference of the NLG / PR-MoE family (DeepSpeed-MoE,
Table 1 and section 4.1), written from the configuration file alone.

No kernels and no cache: each sequence's full causal forward pass, one
layer at a time, with every matmul at ``highest`` precision.  Sequences
meet only as independent rows of the same matmuls (attention is per
sequence; tokens are grouped by expert so each expert's weights are read
once per tile).  It imports nothing of the program and takes
nothing the program made: it draws its own copy of the weights from the
run's seed (the same draws, in the same order, as the served weights;
rounded to the served dtype, then widened to float32).

The layer, as the configuration states it:

    h = x + Attn(Norm(x))                     causal MHA, rotary positions
    x = h + FFN(Norm(h))                      dense GELU MLP, or
    x = h + g * Expert_e(Norm(h)) + MLP(Norm(h))   MoE: top-1 expert e with
                                              gate g = softmax(router)_e;
                                              the MLP term only with the
                                              residual branch (PR-MoE)
    logits = Norm(x_L) @ E^T                  embeddings tied

Departures from the paper, all shared with the program and stated in the
configuration's ``assumed``: rotary positions in place of learned ones,
RMSNorm with a (1 + scale) gain in place of LayerNorm, and embeddings
scaled by sqrt(d_model).

``precision="fp8"`` is the control: every weight and every matmul input
rounded to float8 e4m3 (per-tensor scale), the step below the bfloat16
the configuration serves in.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.catalog import expand_layers
from bench.seeds import weights_key

HI = jax.lax.Precision.HIGHEST


def _served(x, dtype):
    """A weight as it is served: drawn in float32, rounded to the served
    dtype, then widened back for the float32 reference."""
    return x.astype(dtype).astype(jnp.float32)


def _tn(key, shape, fan_in, dtype):
    """Truncated normal (-3, 3) with fan-in scale: the draw of every
    projection."""
    return _served(jax.random.truncated_normal(key, -3, 3, shape, jnp.float32) / np.sqrt(fan_in), dtype)


def _mlp(key, d, f, dtype):
    k = jax.random.split(key, 3)
    return {"wi": _tn(k[0], (d, f), d, dtype), "wo": _tn(k[1], (f, d), f, dtype)}


def layer_weights(conf: dict, key, ls: dict) -> dict:
    """One layer's weights from its key: attention, then the dense MLP or
    the router, the expert stacks and the residual MLP.  Norm gains are
    drawn as zeros (a gain of 1)."""
    dt = jnp.dtype(conf["param_dtype"])
    d, H, Hkv, dh = conf["d_model"], conf["num_heads"], conf["num_kv_heads"], conf["head_dim"]
    ks = jax.random.split(key, 6)
    ka = jax.random.split(ks[0], 5)
    w = {"attn": {"wq": _tn(ka[0], (d, H, dh), d, dt), "wk": _tn(ka[1], (d, Hkv, dh), d, dt),
                  "wv": _tn(ka[2], (d, Hkv, dh), d, dt),
                  "wo": _tn(ka[3], (H * dh, d), H * dh, dt).reshape(H, dh, d)}}
    f = ls["d_ff"]
    if ls["ffn"] == "dense":
        w["mlp"] = _mlp(ks[2], d, f, dt)
        return w
    E = ls["experts"]
    km = jax.random.split(ks[2], 5)
    w["router"] = jax.random.truncated_normal(km[0], -3, 3, (d, E), jnp.float32) / np.sqrt(d)
    w["wi"] = jax.vmap(lambda k: _tn(k, (d, f), d, dt))(jax.random.split(km[1], E))
    w["wo"] = jax.vmap(lambda k: _tn(k, (f, d), f, dt))(jax.random.split(km[2], E))
    if ls["residual"]:
        w["residual"] = _mlp(km[4], d, f, dt)
    return w


def layer_keys(conf: dict, seed: int) -> list:
    """The key of every layer, in order: segment i's key folds i into the
    second of eight keys split from the seed's; pattern position j folds j
    into that and splits one key per repeat."""
    seg_root = jax.random.split(weights_key(seed), 8)[1]
    keys = []
    for i, seg in enumerate(conf["segments"]):
        seg_key = jax.random.fold_in(seg_root, i)
        per_pos = [jax.random.split(jax.random.fold_in(seg_key, j), seg["repeats"])
                   for j in range(len(seg["pattern"]))]
        for r in range(seg["repeats"]):
            for j in range(len(seg["pattern"])):
                keys.append(per_pos[j][r])
    return keys


def embedding(conf: dict, seed: int):
    k = jax.random.split(weights_key(seed), 8)[0]
    e = jax.random.normal(k, (conf["vocab_size"], conf["d_model"]), jnp.float32) * 0.02
    return _served(e, jnp.dtype(conf["param_dtype"]))


def _fp8(x):
    """Round to float8 e4m3 under a per-tensor scale (the control)."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _q(precision):
    return _fp8 if precision == "fp8" else (lambda x: x)


def _norm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos[:, None].astype(jnp.float32) * freqs  # [S, half]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _mm(q, a, b, spec):
    return jnp.einsum(spec, q(a), q(b), precision=HI)


def _attention(conf, w, x, q):
    """x: [S, d] one sequence; causal MHA over all its positions."""
    S = x.shape[0]
    pos = jnp.arange(S)
    qh = _rope(_mm(q, x, w["wq"], "sd,dhe->she"), pos, conf["rope_theta"])
    kh = _rope(_mm(q, x, w["wk"], "sd,dhe->she"), pos, conf["rope_theta"])
    vh = _mm(q, x, w["wv"], "sd,dhe->she")
    s = _mm(q, qh, kh, "qhe,khe->hqk") / math.sqrt(conf["head_dim"])
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = _mm(q, p, vh, "hqk,khe->qhe")
    return _mm(q, o, w["wo"], "qhe,hed->qd")


def _gelu_mlp(q, x, wi, wo):
    return _mm(q, jax.nn.gelu(_mm(q, x, wi, "td,df->tf")), wo, "tf,fd->td")


def _experts(q, h, top, wi, wo, tile: int = 128):
    """Expert_top[t](h[t]) for every token t.  Tokens are grouped by their
    expert, each group padded to whole tiles of ``tile`` rows, and every
    tile runs against its own expert's weights, so each expert's weights
    are read once per tile instead of once per token."""
    T, d = h.shape
    E = wi.shape[0]
    order = jnp.argsort(top, stable=True)
    counts = jnp.bincount(top, length=E)
    padded = -(-counts // tile) * tile
    first = jnp.cumsum(padded) - padded
    sorted_e = top[order]
    rank = jnp.arange(T) - (jnp.cumsum(counts) - counts)[sorted_e]
    row = first[sorted_e] + rank  # padded-buffer row of the k-th sorted token
    R = (T + E * (tile - 1)) // tile * tile
    buf = jnp.zeros((R, d), h.dtype).at[row].set(h[order])
    tile_e = jnp.searchsorted(jnp.cumsum(padded), jnp.arange(R // tile) * tile, side="right")
    tile_e = jnp.minimum(tile_e, E - 1)
    out = jax.lax.map(lambda a: _gelu_mlp(q, a[0], wi[a[1]], wo[a[1]]),
                      (buf.reshape(R // tile, tile, d), tile_e)).reshape(R, d)
    return jnp.zeros_like(h).at[order].set(out[row])


@partial(jax.jit, static_argnames=("conf_items", "ls_items", "precision"))
def _apply_layer(w, x, *, conf_items, ls_items, precision):
    """x: [N, S, d] (each row one sequence, padded at its end)."""
    conf, ls, q = dict(conf_items), dict(ls_items), _q(precision)
    eps = conf["rms_eps"]
    x = x + jax.lax.map(lambda xi: _attention(conf, w["attn"], _norm(xi, eps), q), x)
    N, S, d = x.shape
    h = _norm(x, eps).reshape(N * S, d)
    if ls["ffn"] == "dense":
        y = _gelu_mlp(q, h, w["mlp"]["wi"], w["mlp"]["wo"])
    else:
        probs = jax.nn.softmax(_mm(q, h, w["router"], "td,de->te"), axis=-1)
        top = jnp.argmax(probs, axis=-1)
        gate = jnp.max(probs, axis=-1)
        y = gate[:, None] * _experts(q, h, top, w["wi"], w["wo"])
        if ls["residual"]:
            y = y + _gelu_mlp(q, h, w["residual"]["wi"], w["residual"]["wo"])
    return x + y.reshape(N, S, d)


@partial(jax.jit, static_argnames=("conf_items", "ls_items"))
def _make_layer(key, *, conf_items, ls_items):
    return layer_weights(dict(conf_items), key, dict(ls_items))


def _items(d: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in d.items() if not isinstance(v, (list, dict))))


def final_hidden(conf: dict, seed: int, seqs, precision: str = "f32", *, rows: int, length: int):
    """The last layer's normed hidden state at every position of each
    sequence in ``seqs`` (int arrays), as ``[rows, length, d]``: sequences
    padded at their end (padding never reaches a real position: attention
    is causal) and empty rows after them, so that every run compiles one
    program per kind of layer.  One layer's weights are on the device at a
    time."""
    N, S = rows, length
    tok = np.zeros((N, S), np.int32)
    for i, s in enumerate(seqs):
        tok[i, : len(s)] = s
    emb = embedding(conf, seed)
    x = emb[jnp.asarray(tok)] * math.sqrt(conf["d_model"])
    if precision == "fp8":
        x = _fp8(x)
    conf_items = _items(conf)
    for key, ls in zip(layer_keys(conf, seed), expand_layers(conf)):
        w = _make_layer(key, conf_items=conf_items, ls_items=_items(ls))
        x = _apply_layer(w, x, conf_items=conf_items, ls_items=_items(ls), precision=precision)
        del w
    return _norm(x, conf["rms_eps"]), emb


@partial(jax.jit, static_argnames=("precision", "block"))
def judge(h, emb, tokens, *, precision: str = "f32", block: int = 1024):
    """Logits of every position of ``h`` [N, S, d], reduced as they are
    made, ``block`` rows at a time: the best logit, the logit of
    ``tokens`` [N, S], and the argmax, each [N, S]."""
    q = _q(precision)
    N, S, d = h.shape
    hb = h.reshape(-1, block, d)
    tb = tokens.reshape(-1, block)

    def one(a):
        lg = jnp.einsum("td,vd->tv", q(a[0]), q(emb), precision=HI)
        return (jnp.max(lg, axis=-1), jnp.take_along_axis(lg, a[1][:, None], axis=-1)[:, 0],
                jnp.argmax(lg, axis=-1).astype(jnp.int32))

    best, at, arg = jax.lax.map(one, (hb, tb))
    return best.reshape(N, S), at.reshape(N, S), arg.reshape(N, S)


def served_logits(conf: dict, seed: int, samples, *, rows: int, length: int,
                  control: bool = False):
    """Teacher-forced on each sample ``(prompt, served)``, at every served
    position: ``best``, the reference's best logit, and ``at``, its logit
    of the served token (each a list of arrays, one per sample).  With
    ``control``, also the fp8 control at the same positions, as a second
    dict: ``own``, the control's best logit, and ``at``, the reference's
    logit of the token the control puts first."""
    seqs = [np.concatenate([p, s[:-1]]).astype(np.int32) for p, s in samples]
    block = min(1024, rows * length)
    nxt = np.zeros((rows, length), np.int32)
    for i, (p, s) in enumerate(samples):
        nxt[i, len(p) - 1: len(p) - 1 + len(s)] = s
    h, emb = final_hidden(conf, seed, seqs, rows=rows, length=length)

    def cut(a):
        a = np.asarray(a)
        return [a[i, len(p) - 1: len(p) - 1 + len(s)] for i, (p, s) in enumerate(samples)]

    best, at, _ = judge(h, emb, jnp.asarray(nxt), block=block)
    served = {"best": cut(best), "at": cut(at)}
    if not control:
        return served
    hc, emb_c = final_hidden(conf, seed, seqs, "fp8", rows=rows, length=length)
    own, _, picked = judge(hc, emb_c, jnp.asarray(nxt), precision="fp8", block=block)
    del hc
    return served, {"own": cut(own), "at": cut(judge(h, emb, picked, block=block)[1])}
