#!/usr/bin/env python3
"""Run the paged MoE serving main path once on a TPU and check its output.

One chip (no arguments): ``nlg-350m-moe128`` (DeepSpeed-MoE Table 1) at its
published widths — d_model 1024, 16 heads of 64, d_ff 4096, vocab 51,200,
128 experts with top-1 routing, GELU, MoE on every other layer — cut to 4
layers (two whole dense/MoE periods), bf16 weights drawn from ``--seed``.
``ContinuousEngine`` serves 16 requests (prompt lengths drawn from the seed
in 128..1024, 32 new tokens each) through the paged KV pool (page_size 16,
8 slots), batched chunk prefill (256-token chunks) and grouped dropless MoE
dispatch.  Each of these checks fails the run:

  * every request completes with its full token count;
  * every logit the decode and prefill steps return is finite;
  * each Pallas kernel of the path — paged decode attention, chunk-prefill
    attention, grouped expert MLP — agrees with its jnp reference on the
    chip at the smoke's shapes, within ``TOL`` of the reference's largest
    magnitude;
  * the lowered decode and prefill programs contain ``tpu_custom_call``.

``--ep 4`` (four chips) runs only the expert-parallel serving phase:
(a) the 4-layer model served on a (4,) EP mesh and on device 0 alone in
this process — greedy tokens must be identical — and (b) the full 24-layer
model served on the mesh, with every device's bytes in use printed and
required to hold its expert share.

The last line of stdout is ``{"ok": true, "device": {...}}``; any failure
exits non-zero before it is printed, and so does a run without a TPU.

    python3 chip_smoke.py [--seed N]
    python3 chip_smoke.py --ep 4
"""
from __future__ import annotations

import argparse
import functools
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# Kernel-vs-reference bound, relative to the reference's largest magnitude.
# Both sides read the same bf16 inputs and accumulate in f32; the kernel
# output is rounded to bf16 (unit roundoff 2^-8), the attention kernels feed
# f32 probabilities to the MXU, and the online softmax re-associates the
# sums.  Each of those is about one bf16 rounding; four of them bound it.
TOL = 4 * 2.0 ** -8

ARCH = dict(d_model=1024, n_heads=16, experts=128)  # nlg-350m-moe128 widths
PAGE_SIZE, SLOTS, CHUNK = 16, 8, 256
# one-chip traffic: requests, prompt-length range, new tokens per request
REQUESTS, PROMPT_LO, PROMPT_HI, NEW_TOKENS = 16, 128, 1024, 32


class Fail(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise Fail(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


def nlg_cfg(layers: int, **kw):
    from repro.core.prmoe import nlg_moe

    return nlg_moe("nlg-350m-moe128", layers, ARCH["d_model"], ARCH["n_heads"],
                   ARCH["experts"]).replace(moe_impl="grouped", **kw)


def init_params_on(cfg, seed: int, mesh=None, rules=None):
    """Random weights made on the device(s) from ``seed``: whole on the
    default device, or already in the serving layout across ``mesh`` — the
    24-layer model does not fit one chip, so it is never gathered."""
    import jax

    from repro.models.model import init_params

    make = lambda key: init_params(cfg, key)
    if mesh is None:
        return jax.jit(make)(jax.random.PRNGKey(seed))
    from jax.sharding import NamedSharding

    from repro.parallel.params import param_pspecs
    from repro.parallel.sharding import use_mesh

    shapes = jax.eval_shape(make, jax.random.PRNGKey(seed))
    with use_mesh(mesh, rules):
        specs = param_pspecs(mesh, shapes, mode="serve")
    out = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                       is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec))
    return jax.jit(make, out_shardings=out)(jax.random.PRNGKey(seed))


def make_requests(rng, n: int, lo: int, hi: int, new_tokens: int, vocab: int):
    from repro.serving.engine import Request

    lens = rng.integers(lo, hi + 1, size=n)
    return [Request(prompt=rng.integers(0, vocab, size=int(L)).tolist(),
                    max_new_tokens=new_tokens) for L in lens]


class Watch:
    """Wraps an engine's jitted decode / prefill entries: records whether
    every returned logit is finite (reduced on the device, read once), and,
    when ``keep`` is set, the decoding rows' logits of every decode call."""

    def __init__(self, engine, keep: bool = False):
        import jax.numpy as jnp

        self.flags, self.logits = [], []

        def wrap(fn, is_decode):
            def call(*a):
                out = fn(*a)
                self.flags.append(jnp.isfinite(out[0]).all())
                if keep and is_decode:
                    self.logits.append(np.asarray(out[0], np.float32)[np.asarray(a[3])])
                return out
            return call

        engine._decode = wrap(engine._decode, True)
        engine._prefill_chunk_batched = wrap(engine._prefill_chunk_batched, False)

    def all_finite(self) -> bool:
        return bool(all(bool(f) for f in self.flags))


def build_engine(cfg, params, capacity: int, slots: int = SLOTS):
    from repro.configs.base import PagedKVConfig
    from repro.serving.continuous import ContinuousEngine

    return ContinuousEngine(
        cfg, params, slots=slots, capacity=capacity,
        paged_cfg=PagedKVConfig(page_size=PAGE_SIZE, prefill_chunk=CHUNK),
        prefill_mode="batched",
    )


def serve(engine, reqs, warm_prompt_len: int, vocab: int):
    """Warm up (compiles every entry of the tick), then serve ``reqs``.
    Returns (warm seconds, serve seconds, {rid: Response}, compilations
    during the serve window)."""
    from repro.serving.engine import Request

    warm = np.random.default_rng(123).integers(0, vocab, size=warm_prompt_len).tolist()
    compiles = engine.obs.metrics.counter("serve.retraces")
    t0 = time.perf_counter()
    engine.submit(Request(prompt=warm, max_new_tokens=2))
    engine.run_until_done()
    t1 = time.perf_counter()
    before = compiles.value
    ids = [engine.submit(r) for r in reqs]
    done = engine.run_until_done()
    t2 = time.perf_counter()
    return t1 - t0, t2 - t1, {i: done[i] for i in ids}, compiles.value - before


def check_complete(done, reqs, tag: str) -> int:
    check(len(done) == len(reqs), f"{tag}: {len(done)} of {len(reqs)} requests completed")
    for (rid, resp), req in zip(sorted(done.items()), reqs):
        check(len(resp.tokens) == req.max_new_tokens,
              f"{tag}: request {rid} returned {len(resp.tokens)} of {req.max_new_tokens} tokens")
    return sum(len(r.tokens) for r in done.values())


def compare(name: str, got, want) -> None:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    check(np.isfinite(got).all(), f"{name}: kernel output not finite")
    scale = float(np.abs(want).max())
    err = float(np.abs(got - want).max())
    log(f"kernel {name}: max|kernel-ref| {err:.3e} vs bound {TOL * scale:.3e} "
        f"(TOL {TOL:.4f} x max|ref| {scale:.3e})")
    check(err <= TOL * scale, f"{name}: kernel differs from its reference by {err:.3e} "
          f"> {TOL * scale:.3e}")


def random_pool(rng, Pt: int, Hkv: int, dh: int, nt: int, mapped, written):
    """A random bf16 page pool (token rows ``[Pt, page_size, Hkv*dh]``) and
    the clamped block tables of one sequence per row: ``mapped[b]``
    positions have pages (distinct; the last page is trash), the first
    ``written[b]`` of them hold keys (``pos`` >= 0)."""
    import jax.numpy as jnp

    k = jnp.asarray(rng.standard_normal((Pt, PAGE_SIZE, Hkv * dh)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((Pt, PAGE_SIZE, Hkv * dh)), jnp.bfloat16)
    kpos = np.full((Pt, PAGE_SIZE), -1, np.int32)
    table = np.full((len(mapped), nt), Pt - 1, np.int32)
    free = list(rng.permutation(Pt - 1))
    for b, (m, n) in enumerate(zip(mapped, written)):
        for e in range(-(-int(m) // PAGE_SIZE)):
            table[b, e] = page = free.pop()
            lo = e * PAGE_SIZE
            if n > lo:
                kpos[page, : min(PAGE_SIZE, n - lo)] = np.arange(lo, min(lo + PAGE_SIZE, n))
    return k, v, jnp.asarray(kpos), jnp.asarray(table)


def kernel_checks(engine, params, cfg, seed: int) -> None:
    """Each Pallas kernel of the path against its jnp reference, compiled
    for the chip (interpret=False), at the shapes this smoke served."""
    import jax
    import jax.numpy as jnp

    from repro.core.dispatch_grouped import GROUPED_TILE, grouped_layout
    from repro.core.gating import top_k_gating
    from repro.kernels.attention_paged import paged_decode_attention, paged_decode_attention_ref
    from repro.kernels.attention_prefill_paged import (
        paged_prefill_attention,
        paged_prefill_attention_ref,
    )
    from repro.kernels.expert_mlp_grouped import grouped_mlp_kernel, grouped_mlp_ref

    rng = np.random.default_rng(seed + 1)
    Hkv, dh = cfg.num_kv_heads, cfg.head_dim
    G = cfg.num_heads // Hkv
    Pt, nt = engine.n_pages + 1, engine.max_pages
    scale = dh ** -0.5
    ref = lambda f, *a, **kw: jax.jit(functools.partial(f, **kw))(*a)

    # paged decode: one query per slot at the end of a random-length history,
    # read from the last of two stacked layer pools
    lengths = rng.integers(1, engine.capacity + 1, size=SLOTS)
    k, v, kpos, table = random_pool(rng, Pt, Hkv, dh, nt, lengths, lengths)
    stack = lambda a: jnp.stack([jnp.zeros_like(a), a])
    q = jnp.asarray(rng.standard_normal((SLOTS, Hkv, G, dh)), jnp.bfloat16)
    qpos = jnp.asarray(lengths - 1, jnp.int32)[:, None]
    args = (q, stack(k), None, stack(v), None, stack(kpos), table, qpos, jnp.int32(1))
    got = paged_decode_attention(*args, scale=scale, interpret=False)
    with jax.default_matmul_precision("highest"):
        want = ref(paged_decode_attention_ref, *args, scale=scale)
    compare("paged_decode_attention", got, want)

    # chunk prefill: each row's 256-token chunk starts after its history
    # (page-aligned, as the scheduler chunks), one row past its chunk end
    starts = rng.integers(0, (engine.capacity - CHUNK) // PAGE_SIZE + 1, size=SLOTS) * PAGE_SIZE
    # pages are mapped for the whole chunk up front; only the history is written
    k, v, kpos, table = random_pool(rng, Pt, Hkv, dh, nt, starts + CHUNK, starts)
    qpos = starts[:, None] + np.arange(CHUNK)[None]
    qpos[-1, CHUNK // 2:] = -1  # a row whose chunk ends early
    qpos = jnp.asarray(qpos, jnp.int32)
    qc = jnp.asarray(rng.standard_normal((SLOTS, CHUNK, Hkv, G, dh)), jnp.bfloat16)
    ck = jnp.asarray(rng.standard_normal((SLOTS, CHUNK, Hkv, dh)), jnp.bfloat16)
    cv = jnp.asarray(rng.standard_normal((SLOTS, CHUNK, Hkv, dh)), jnp.bfloat16)
    args = (qc, k, None, v, None, kpos, table, qpos, ck, cv)
    got = paged_prefill_attention(*args, scale=scale, interpret=False)
    with jax.default_matmul_precision("highest"):
        want = ref(paged_prefill_attention_ref, *args, scale=scale)
    valid = np.asarray(qpos >= 0)  # rows past a chunk's end are never read
    compare("paged_prefill_attention", np.asarray(got, np.float32)[valid],
            np.asarray(want, np.float32)[valid])

    # grouped expert MLP: a decode tick's routing (8 tokens over 128
    # experts) against the first MoE layer's real expert weights
    moe = params["segments"]["seg0"]["pos1"]["moe"]
    wi, wo = moe["wi"][0], moe["wo"][0]
    E = wi.shape[0]
    x = jnp.asarray(rng.standard_normal((SLOTS, cfg.d_model)), jnp.bfloat16)
    g = top_k_gating(x.astype(jnp.float32) @ moe["router"][0], 1, SLOTS)
    lay = grouped_layout(g, E)
    Ct = lay.tile_expert.shape[0] * GROUPED_TILE
    xg = jnp.zeros((Ct, cfg.d_model), x.dtype).at[lay.dst].set(x)
    got = grouped_mlp_kernel(xg, lay.tile_expert, wi, None, wo, act="gelu", interpret=False)
    with jax.default_matmul_precision("highest"):
        want = ref(grouped_mlp_ref, xg, lay.tile_expert, wi, None, wo, act="gelu")
    compare(f"grouped_mlp[Ct={Ct}]", got, want)


def check_lowered(engine) -> None:
    """The decode and prefill programs the engine runs carry the Mosaic
    kernels (no silent reference path)."""
    import jax.numpy as jnp

    S, mp = engine.n_slots, engine.max_pages
    i32 = lambda *s: jnp.zeros(s, jnp.int32)
    flags = jnp.zeros((S,), bool)
    dec = engine._jit_registry["decode"][0].lower(
        engine.params, i32(S, 1), i32(S), flags, engine.caches, i32(S, mp)).as_text()
    pre = engine._jit_registry["prefill_chunk_batched"][0].lower(
        engine.params, i32(S, CHUNK), i32(S, CHUNK), flags, flags, i32(S),
        engine.caches, i32(S, mp)).as_text()
    for name, txt in (("decode", dec), ("prefill", pre)):
        n = txt.count("tpu_custom_call")
        log(f"lowered {name}: {n} tpu_custom_call sites")
        check(n > 0, f"lowered {name} program has no tpu_custom_call")


def memory_line(devices) -> str:
    parts = []
    for d in devices:
        st = d.memory_stats() or {}
        parts.append(f"dev{d.id}: in_use {st.get('bytes_in_use', 0) / 1e9:.3f} GB, "
                     f"peak {st.get('peak_bytes_in_use', 0) / 1e9:.3f} GB")
    return "; ".join(parts)


def one_chip(seed: int) -> None:
    import jax

    cfg = nlg_cfg(4)
    t0 = time.perf_counter()
    params = init_params_on(cfg, seed)
    jax.block_until_ready(params)
    log(f"model {cfg.name} cut to 4 layers: d_model {cfg.d_model}, {cfg.num_heads} heads "
        f"of {cfg.head_dim}, vocab {cfg.vocab_size}, {ARCH['experts']} experts top-1 gelu; params "
        f"made in {time.perf_counter() - t0:.1f} s")
    rng = np.random.default_rng(seed)
    reqs = make_requests(rng, REQUESTS, PROMPT_LO, PROMPT_HI, NEW_TOKENS, cfg.vocab_size)
    engine = build_engine(cfg, params, capacity=PROMPT_HI + NEW_TOKENS)
    watch = Watch(engine)
    warm_s, serve_s, done, compiles = serve(engine, reqs, PROMPT_LO + 45, cfg.vocab_size)
    n_tok = check_complete(done, reqs, "serve")
    prompt_tok = sum(len(r.prompt) for r in reqs)
    log(f"compile+warmup {warm_s:.2f} s (1 request of {PROMPT_LO + 45} prompt tokens, "
        "2 new tokens)")
    log(f"served {len(done)} requests: {prompt_tok} prompt tokens, {n_tok} new tokens "
        f"in {serve_s:.2f} s ({compiles} compilations inside the window)")
    check(watch.all_finite(), "a decode or prefill step returned non-finite logits")
    log(f"logits finite over {len(watch.flags)} decode/prefill calls")
    check_lowered(engine)
    kernel_checks(engine, params, cfg, seed)
    log("memory: " + memory_line(jax.devices()[:1]))


def ep_phase(seed: int, ep: int) -> None:
    import jax

    from repro.serving.ep import build_serving_mesh, placed_param_bytes

    devices = jax.devices()
    check(len(devices) >= ep, f"--ep {ep} needs {ep} devices, {len(devices)} visible")

    # (a) 4 layers: EP mesh vs device 0 alone, token for token
    cfg = nlg_cfg(4)
    params = init_params_on(cfg, seed)
    rng = np.random.default_rng(seed)
    reqs = make_requests(rng, 8, 128, 1024, 32, cfg.vocab_size)
    results = {}
    for tag, c in (("device0", cfg), (f"ep{ep}", cfg.replace(ep_mesh=(ep,)))):
        engine = build_engine(c, params, capacity=1024 + 32)
        watch = Watch(engine, keep=True)
        warm_s, serve_s, done, _ = serve(engine, reqs, 300, cfg.vocab_size)
        n_tok = check_complete(done, reqs, tag)
        check(watch.all_finite(), f"{tag}: non-finite logits")
        log(f"(a) {tag}: compile+warmup {warm_s:.2f} s, served {len(done)} requests, "
            f"{n_tok} tokens in {serve_s:.2f} s")
        results[tag] = ([done[i].tokens for i in sorted(done)], watch.logits)
        del engine, watch
    (tok1, log1), (tokn, logn) = results["device0"], results[f"ep{ep}"]
    same = sum(a == b for a, b in zip(tok1, tokn))
    n = min(len(log1), len(logn))
    diff = max((float(np.abs(a - b).max()) for a, b in zip(log1[:n], logn[:n])
                if a.shape == b.shape), default=float("nan"))
    log(f"(a) greedy tokens identical for {same} of {len(tok1)} requests; largest "
        f"|logit difference| over {n} decode ticks: {diff:.4e}")
    check(same == len(tok1), f"(a) EP mesh and device 0 disagree on {len(tok1) - same} requests")
    del params, results
    gc.collect()

    # (b) the full 24 layers on the mesh, weights made in place
    cfg = nlg_cfg(24, ep_mesh=(ep,))
    mesh, rules = build_serving_mesh((ep,))
    t0 = time.perf_counter()
    params = init_params_on(cfg, seed, mesh, rules)
    jax.block_until_ready(params)
    per_dev = placed_param_bytes(params)
    log(f"(b) 24-layer params made on the mesh in {time.perf_counter() - t0:.1f} s: "
        f"{per_dev / 1e9:.3f} GB per device")
    reqs = make_requests(rng, 4, 128, 512, 16, cfg.vocab_size)
    engine = build_engine(cfg, params, capacity=512 + 16, slots=ep)
    watch = Watch(engine)
    warm_s, serve_s, done, _ = serve(engine, reqs, 200, cfg.vocab_size)
    n_tok = check_complete(done, reqs, "(b)")
    check(watch.all_finite(), "(b) non-finite logits")
    log(f"(b) 24 layers: compile+warmup {warm_s:.2f} s, served {len(done)} requests, "
        f"{n_tok} tokens in {serve_s:.2f} s")
    log("(b) memory: " + memory_line(devices[:ep]))
    for d in devices[:ep]:
        used = (d.memory_stats() or {}).get("bytes_in_use", 0)
        check(used >= 0.9 * per_dev, f"(b) device {d.id} holds {used / 1e9:.3f} GB, "
              f"less than its {per_dev / 1e9:.3f} GB parameter share")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ep", type=int, default=0,
                    help="run only the expert-parallel phase on this many chips")
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); nothing was run",
              file=sys.stderr)
        return 2
    try:
        from repro.launch.runtime import device_info, device_label, enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repository's src/ is not beside this script ({e})",
              file=sys.stderr)
        return 2
    log(f"compile cache: {enable_compile_cache()}")
    log(f"device: {device_label()}")
    try:
        if args.ep:
            ep_phase(args.seed, args.ep)
        else:
            one_chip(args.seed)
    except Fail as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device_info()}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
