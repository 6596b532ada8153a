"""Operations and bytes the work needs, from the configuration's shapes:
what a decode step or a prefill chunk requires of the model, and what each
attention kernel call requires of the chip.  These count the algorithm's
work, not what the compiler emitted: no padding rows, no recomputation, no
experts that no token was routed to.

A decode row at context ``L`` (the positions it holds, the new one
included) and a prefill row computing positions ``start .. start + n - 1``
are the units.  Matmul FLOPs are 2 per multiply-add.
"""
from __future__ import annotations

from bench.catalog import expand_layers

BF16 = 2  # bytes of a served weight, activation or cache entry


def matmul_params_per_token(conf: dict) -> int:
    """Weights a token multiplies through in the layer stack (top-1 routed
    expert, residual MLP where the layer has one), without the logits."""
    d, H, Hkv, dh = conf["d_model"], conf["num_heads"], conf["num_kv_heads"], conf["head_dim"]
    n = 0
    for ls in expand_layers(conf):
        n += d * H * dh * 2 + d * Hkv * dh * 2  # q, o and k, v projections
        mlp = 2 * d * ls["d_ff"]  # GELU MLP: in and out
        if ls["ffn"] == "dense":
            n += mlp
        else:
            n += d * ls["experts"] + ls["top_k"] * mlp + (mlp if ls["residual"] else 0)
    return n


def logits_flops(conf: dict) -> int:
    return 2 * conf["vocab_size"] * conf["d_model"]


def attn_flops(conf: dict, queries_keys: int) -> int:
    """QK^T and PV over ``queries_keys`` (query, key) pairs, all layers."""
    return 4 * conf["num_heads"] * conf["head_dim"] * queries_keys * conf["num_hidden_layers"]


def causal_pairs(start: int, n: int) -> int:
    """(query, key) pairs of queries ``start .. start+n-1`` under causality."""
    return n * start + n * (n + 1) // 2


def decode_step(conf: dict, lengths) -> dict:
    """Model FLOPs of one decode step over rows at context ``lengths``, and
    the paged decode attention kernel's FLOPs and bytes (all layers): each
    row reads the K and V of every position it holds, reads its query and
    writes its output."""
    L, H, Hkv, dh = conf["num_hidden_layers"], conf["num_heads"], conf["num_kv_heads"], conf["head_dim"]
    rows, keys = len(lengths), int(sum(lengths))
    model = rows * (2 * matmul_params_per_token(conf) + logits_flops(conf)) + attn_flops(conf, keys)
    kv = keys * 2 * Hkv * dh * BF16
    qo = rows * 2 * H * dh * BF16
    return {"model_flops": model, "attn_flops": attn_flops(conf, keys), "attn_bytes": L * (kv + qo)}


def prefill_call(conf: dict, rows) -> dict:
    """Model FLOPs of one batched prefill call over ``rows`` =
    ``[(start, n, finishing), ...]`` (logits only where the row's prompt
    ends), and the chunk-prefill attention kernel's FLOPs and bytes: each
    row reads the K and V of its history and of its chunk, reads its
    queries and writes its outputs."""
    L, H, Hkv, dh = conf["num_hidden_layers"], conf["num_heads"], conf["num_kv_heads"], conf["head_dim"]
    per_tok = 2 * matmul_params_per_token(conf)
    model = attn = nbytes = 0
    for start, n, finishing in rows:
        pairs = causal_pairs(start, n)
        model += n * per_tok + attn_flops(conf, pairs) + (logits_flops(conf) if finishing else 0)
        attn += attn_flops(conf, pairs)
        nbytes += L * ((start + n) * 2 * Hkv * dh + n * 2 * H * dh) * BF16
    return {"model_flops": model, "attn_flops": attn, "attn_bytes": nbytes}


def roofline(flops: float, nbytes: float, seconds: float, peak: dict) -> tuple:
    """(share of the roofline in %, the bound that applies): the least time
    the chip could take, the larger of FLOPs over peak FLOP/s and bytes
    over peak bytes/s, over the time taken."""
    t_c, t_m = flops / peak["bf16_flop_s"], nbytes / peak["hbm_bytes_s"]
    return 100.0 * max(t_c, t_m) / seconds, ("compute" if t_c >= t_m else "memory")
