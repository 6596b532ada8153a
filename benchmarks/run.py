"""Benchmark harness — one section per DeepSpeed-MoE table/figure.
Prints ``name,us_per_call,derived`` CSV rows.

  table3   — training cost: MoE-at-base-cost vs quality-equivalent dense (5x)
  fig10    — 52B MoE scaling 8→64 GPUs: latency + per-GPU throughput
             (super-linear), baseline vs DS-MoE
  fig11    — 107B→2T models: baseline vs DS-MoE latency (≤7.3x)
  fig12    — min GPUs to serve: standard vs PR-MoE vs PR-MoE+MoS (2x fewer)
  fig13    — PR-MoE/MoS latency at fixed GPUs
  fig14_15 — MoE vs quality-equivalent dense serving latency/cost
  kernel6x — sparse-einsum vs fused dense-mapping MoE kernels (>6x, §5.4)
  moe_impl — full MoE layer wall-clock, einsum vs dense dispatch (CPU)
  quant    — MoQ expert PTQ: bytes int8/int4 vs fp32, CPU overhead, and the
             projected decode-latency win at 1 byte/param (§4)
  kv_quant — int8 KV cache: cache bytes/token fp vs quantized, decode-step
             wall-clock with fp vs int8 caches (CPU ref path), batch-size
             headroom at a fixed cache-memory budget
  paged    — paged KV block pool: cache bytes + effective sequences/GiB vs
             contiguous slots (fp and int8 pages), decode-tick wall-clock,
             and a traffic-mix run with per-tick scheduler metrics (JSON)
  prefix   — prefix-sharing / copy-on-write pages: physical pages for
             shared-system-prompt traffic with vs without sharing, the
             effective sequences/GiB multiplier on top of the paged
             baseline, n-sample parallel sampling page cost, and a measured
             run with shared_pages / cow_copies telemetry (JSON)
  chunked_prefill — chunked prefill-into-pages: temp contiguous admission
             buffer eliminated (bytes), long-prompt admission wall-clock and
             TTFT head-of-line blocking chunked vs scatter under mixed
             traffic (decode progress while the long prompt prefills),
             measured prefill FLOPs saved on shared-preamble traffic, and
             per-tick prefill/decode token telemetry (JSON)
  obs      — observability layer: decode-tick overhead with instrumentation
             fully off vs default (metrics, tracer disabled) vs everything
             on (tracer + per-tick routing stats) — ASSERTS the default
             path adds <1%; raw tracer emit cost on/off; MoE routing
             telemetry from one training step and one decode tick; retrace
             watchdog warmup-vs-steady compile counts; final metrics
             snapshot as JSON
  fused_tick — one fused tick (grouped dropless MoE + batched multi-slot
             chunk prefill): >=3 concurrent admissions in ONE jitted prefill
             call (jitted calls/tick <= 2), predicted==observed compile
             counts with the batched entry compiling once, tick p50/p99
             batched vs chunked, and capacity-padding vs grouped tile-padding
             dead expert FLOPs (JSON)
  ep_serving — expert-parallel serving mesh: measured per-device parameter
             bytes with experts sharded (4x2) vs single-device, the
             aggregate expert-bandwidth multiplier, per-layer all-to-all /
             all_gather exchange volume, and flat vs hierarchical two-hop
             message counts (JSON)
  spec     — draft-then-verify speculative decoding over CoW page forks:
             accepted tokens per verify pass with a same-family drafter
             (ASSERTS > 1), the fresh-init low-accept rollback contrast
             (token-exact either way), target forward passes per emitted
             token vs the non-speculative baseline, decode-tick p50/p99
             for all three engines, and the fork-page commit/rollback
             ledger (JSON)

Run: PYTHONPATH=src python -m benchmarks.run [section ...]
"""
from __future__ import annotations

import sys

import jax
import jax.numpy as jnp

from benchmarks.common import decode_latency_model, emit, min_gpus_to_fit, time_fn
from repro.configs.base import count_active_params, count_params
from repro.configs.registry import all_configs
from repro.launch.runtime import enable_compile_cache


def table3() -> None:
    """Table 3: same quality, ~5x cheaper training.  Training cost ∝
    activated params/token; also measured wall-clock on scaled CPU proxies."""
    cfgs = all_configs()
    moe = cfgs["nlg-1.3b-moe128"]
    dense = cfgs["nlg-6.7b"]
    ratio = count_params(dense) / count_active_params(moe)
    emit("table3_flops_ratio_6.7Bdense_over_1.3B+MoE128", 0.0, f"{ratio:.2f}x_cheaper_training(paper:5x)")

    from repro.core.prmoe import nlg_dense, nlg_moe
    from repro.data.pipeline import data_stream
    from repro.models.model import init_params
    from repro.training.optimizer import init_adamw
    from repro.training.trainer import TrainConfig, make_train_step

    proxy_moe = nlg_moe("proxy-moe", 4, 256, 4, 16, vocab=2048).replace(
        param_dtype="float32", compute_dtype="float32")
    proxy_dense = nlg_dense("proxy-dense", 6, 512, 8, vocab=2048).replace(
        param_dtype="float32", compute_dtype="float32")
    it = data_stream(2048, 8, 128)
    tokens, labels = next(it)
    rows = {}
    for name, cfg in [("moe_base", proxy_moe), ("dense_equiv", proxy_dense)]:
        p = init_params(cfg, jax.random.PRNGKey(0))
        o = init_adamw(p)
        step = jax.jit(make_train_step(cfg, TrainConfig(lr=1e-3, warmup_steps=1, decay_steps=10)))
        us = time_fn(lambda p=p, o=o: step(p, o, tokens, labels), iters=5, warmup=2)
        rows[name] = us
        emit(f"table3_proxy_step_{name}", us, f"params={count_params(cfg)/1e6:.0f}M")
    emit("table3_proxy_measured_speedup", 0.0, f"{rows['dense_equiv']/rows['moe_base']:.2f}x")


def fig10() -> None:
    cfg = all_configs()["nlg-1.3b-moe128"]  # the 52B model of Fig. 10
    base_tput = None
    for g in (8, 16, 32, 64):
        lat_opt = decode_latency_model(cfg, g, optimized=True)
        lat_base = decode_latency_model(cfg, g, optimized=False)
        # weak-scaling serving: 16 tokens/GPU -> per-GPU throughput rises as
        # experts-per-GPU (and thus expert bytes) shrink — §5.5.1 locality
        tput = 16.0 / lat_opt  # tokens/s per GPU
        if base_tput is None:
            base_tput = tput
        emit(f"fig10_52B_{g}gpu_dsmoe", lat_opt * 1e6,
             f"speedup_vs_baseline={lat_base/lat_opt:.2f}x")
        emit(f"fig10_52B_{g}gpu_perGPU_tput", 0.0,
             f"superlinear_factor={tput/base_tput:.2f}(>1=superlinear)")


def fig11() -> None:
    for name in ("nlg-2.4b-moe128", "nlg-8b-moe128", "nlg-24b-moe128", "nlg-47b-moe128"):
        cfg = all_configs()[name]
        g = 256 if count_params(cfg) > 6e11 else 128
        lat_opt = decode_latency_model(cfg, g, optimized=True)
        lat_base = decode_latency_model(cfg, 128, optimized=False)
        emit(f"fig11_{name}_{g}gpu", lat_opt * 1e6,
             f"size={count_params(cfg)/1e9:.0f}B,improvement={lat_base/lat_opt:.1f}x(paper:<=7.3x)")


def fig12() -> None:
    cfgs = all_configs()
    for std, pr, mos, tag in [
        ("nlg-350m-moe128", "nlg-350m-prmoe-32-64", "nlg-350m-prmoe-mos", "13B"),
        ("nlg-1.3b-moe128", "nlg-1.3b-prmoe-64-128", "nlg-1.3b-prmoe-mos", "52B"),
    ]:
        g_std = min_gpus_to_fit(cfgs[std])
        g_mos = min_gpus_to_fit(cfgs[mos])
        emit(f"fig12_min_gpus_{tag}", 0.0,
             f"standard={g_std},prmoe={min_gpus_to_fit(cfgs[pr])},prmoe+mos={g_mos},"
             f"reduction={g_std/g_mos:.1f}x(paper:2x)")


def fig13() -> None:
    cfgs = all_configs()
    for std, pr, mos, g in [
        ("nlg-350m-moe128", "nlg-350m-prmoe-32-64", "nlg-350m-prmoe-mos", 16),
        ("nlg-1.3b-moe128", "nlg-1.3b-prmoe-64-128", "nlg-1.3b-prmoe-mos", 64),
    ]:
        l_std = decode_latency_model(cfgs[std], g, optimized=True)
        l_pr = decode_latency_model(cfgs[pr], g, optimized=True)
        l_mos = decode_latency_model(cfgs[mos], g, optimized=True)
        emit(f"fig13_{std}_{g}gpu", l_std * 1e6,
             f"prmoe={l_pr*1e6:.0f}us,prmoe+mos={l_mos*1e6:.0f}us,gain={l_std/l_mos:.2f}x")


def fig14_15() -> None:
    """Figs 14-15 compare DS-MoE-served MoE against *PyTorch-served* dense
    (that is the paper's setup), per-token GPU-seconds for the cost claim."""
    cfgs = all_configs()
    moe, dense = cfgs["nlg-1.3b-moe128"], cfgs["nlg-6.7b"]
    l_moe = decode_latency_model(moe, 128, optimized=True)
    l_dense = decode_latency_model(dense, 8, optimized=False)
    emit("fig14_52B_moe_vs_6.7B_dense", l_moe * 1e6,
         f"dense={l_dense*1e6:.0f}us,speedup={l_dense/l_moe:.2f}x(paper:2.4x+)")
    from repro.core.prmoe import nlg_dense, nlg_moe

    d175 = nlg_dense("nlg-175b", 96, 12288, 96)
    moe2t = cfgs["nlg-47b-moe128"]
    mos2t = nlg_moe("nlg-47b-prmoe-mos", 58, 8192, 64, (64, 128), residual=True,
                    student_layers=51)
    l_moe = decode_latency_model(moe2t, 256, optimized=True)
    l_mos = decode_latency_model(mos2t, 256, optimized=True)
    l_dense = decode_latency_model(d175, 16, optimized=False)
    emit("fig15_2T_moe_vs_175B_dense", l_moe * 1e6,
         f"dense={l_dense*1e6:.0f}us,speedup={l_dense/l_moe:.2f}x")
    emit("fig15_2T_prmoe_mos_vs_175B_dense", l_mos * 1e6,
         f"dense={l_dense*1e6:.0f}us,speedup={l_dense/l_mos:.2f}x(paper:4.5x)")
    # cost: GPU-seconds per token at 16 tokens/GPU weak-scaling load
    cost_dense = l_dense * 16 / (16 * 16)
    cost_mos = l_mos * 256 / (16 * 256)
    emit("fig15_cost_per_token_ratio", 0.0,
         f"dense_over_moe={cost_dense/cost_mos:.2f}x_cheaper(paper:9x)")


def kernel6x() -> None:
    """§5.4: dense mapping-table dispatch vs sparse one-hot einsum dispatch,
    wall-clock on CPU at paper-ish shape (E=128, top-1)."""
    from repro.core.dispatch import moe_dense
    from repro.core.dispatch_einsum import moe_einsum
    from repro.core.gating import expert_capacity, top_k_gating

    T, E, D = 2048, 128, 512
    cap = expert_capacity(T, E, 1, 1.25)
    logits = jax.random.normal(jax.random.PRNGKey(0), (T, E))
    x = jax.random.normal(jax.random.PRNGKey(1), (T, D))
    ident = lambda b: b  # isolate dispatch cost (identity experts)

    f_einsum = jax.jit(lambda x, lg: moe_einsum(x, top_k_gating(lg, 1, cap, method="cumsum"), cap, ident))
    f_dense = jax.jit(lambda x, lg: moe_dense(x, top_k_gating(lg, 1, cap, method="sort"), cap, E, ident))
    us_e = time_fn(f_einsum, x, logits, iters=10)
    us_d = time_fn(f_dense, x, logits, iters=10)
    emit("kernel_sparse_einsum_dispatch", us_e, f"T={T},E={E},D={D}")
    emit("kernel_dense_mapping_dispatch", us_d, f"speedup={us_e/us_d:.2f}x(paper:>6x)")


def moe_impl() -> None:
    from repro.configs.base import FFNSpec, ModelConfig
    from repro.core.moe import init_moe, moe_layer

    cfg = ModelConfig(name="b", family="moe", source="x", d_model=256, num_heads=4,
                      num_kv_heads=4, head_dim=64, vocab_size=1024, segments=(),
                      param_dtype="float32", compute_dtype="float32")
    spec = FFNSpec(kind="moe", d_ff=512, num_experts=32, top_k=1, capacity_factor=1.25)
    params = init_moe(jax.random.PRNGKey(0), cfg, spec, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 256, 256))
    us = {}
    for impl in ("einsum", "dense"):
        f = jax.jit(lambda p, x, impl=impl: moe_layer(cfg, spec, p, x, impl=impl)[0])
        us[impl] = time_fn(f, params, x, iters=10)
        emit(f"moe_layer_{impl}", us[impl], "E=32,T=1024,D=256")
    emit("moe_layer_full_speedup", 0.0, f"{us['einsum']/us['dense']:.2f}x")


def quant() -> None:
    """MoQ (§4, "up to 3.7x" smaller): expert-weight PTQ.  Reports (a) expert
    parameter bytes fp32 vs int8/int4 (+scales), (b) expert-MLP wall-clock on
    the CPU dequant-einsum path, (c) projected decode latency with 1-byte
    weights through the paper's analytic memory-bound latency model."""
    from repro.configs.base import FFNSpec, ModelConfig, QuantConfig
    from repro.core.moe import experts_ffn, init_moe
    from repro.quant import quantize_params, tree_bytes

    cfg = ModelConfig(name="q", family="moe", source="x", d_model=256, num_heads=4,
                      num_kv_heads=4, head_dim=64, vocab_size=1024, segments=(),
                      param_dtype="float32", compute_dtype="float32")
    spec = FFNSpec(kind="moe", d_ff=1024, num_experts=16, top_k=1, act="swiglu")
    params = init_moe(jax.random.PRNGKey(0), cfg, spec, jnp.float32)
    expert = {k: params[k] for k in ("wi", "wg", "wo")}
    fp_bytes = tree_bytes(expert)

    quantized = {}
    for bits, gs in ((8, 0), (4, 64)):
        qp = quantize_params({"moe": expert}, QuantConfig(bits=bits, group_size=gs))["moe"]
        quantized[bits] = qp
        qb = tree_bytes(qp)
        emit(f"quant_expert_bytes_int{bits}", 0.0,
             f"fp32={fp_bytes},int{bits}+scales={qb},reduction={fp_bytes/qb:.2f}x(paper:3.7x_model)")

    E, C, D = spec.num_experts, 128, cfg.d_model
    xe = jax.random.normal(jax.random.PRNGKey(1), (E, C, D), jnp.float32)
    f_fp = jax.jit(lambda p, xe: experts_ffn(p, xe, "swiglu"))
    us_fp = time_fn(f_fp, params, xe, iters=10)
    emit("quant_expert_mlp_fp32", us_fp, f"E={E},C={C},D={D},F={spec.d_ff}")
    for bits in (8, 4):
        us_q = time_fn(f_fp, quantized[bits], xe, iters=10)
        emit(f"quant_expert_mlp_int{bits}_dequant_einsum", us_q,
             f"overhead_vs_fp={us_q/us_fp:.2f}x(CPU_ref_path;TPU_uses_dequant-in-kernel)")

    # Projected decode latency: experts-only int8 halves ONLY the expert
    # bytes streamed from HBM (dense weights and activation/a2a traffic stay
    # bf16) — the term that dominates the paper's fig. 10/11 at low GPU
    # counts, where experts are the bulk of per-GPU bytes.
    cfg52 = all_configs()["nlg-1.3b-moe128"]
    for g in (8, 32):
        l_bf16 = decode_latency_model(cfg52, g, optimized=True)
        l_int8 = decode_latency_model(cfg52, g, optimized=True, expert_bytes_per_param=1)
        emit(f"quant_52B_{g}gpu_decode_projection", l_int8 * 1e6,
             f"bf16={l_bf16*1e6:.0f}us,experts_int8_speedup={l_bf16/l_int8:.2f}x")


def kv_quant() -> None:
    """Quantized KV cache (serving): (a) cache bytes/token fp32 vs int8 +
    per-(head, timestep) scales, (b) measured decode-step wall-clock with
    fp vs int8 caches on the CPU dequant path (TPU uses the Pallas
    dequant-in-kernel decode attention), (c) the batch-headroom implication
    at a fixed cache-memory budget — decode batch ∝ 1/cache-bytes when the
    §5 memory-bound regime is cache-dominated."""
    from repro.core.prmoe import nlg_moe
    from repro.models.model import decode_step, init_caches, init_params, prefill
    from repro.quant import kv_cache_bytes

    cfg = nlg_moe("kv-bench", 4, 256, 4, 16, vocab=1024).replace(
        param_dtype="float32", compute_dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))
    B, S, cap = 8, 64, 128
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S + 1), 0, cfg.vocab_size)

    rows = {}
    for bits in (0, 8):
        tag = f"int{bits}" if bits else "fp32"
        caches = init_caches(cfg, B, cap, kv_bits=bits)
        nbytes = kv_cache_bytes(caches)
        per_tok = nbytes / (B * cap)
        emit(f"kv_quant_cache_bytes_{tag}", 0.0,
             f"total={nbytes},per_slot_token={per_tok:.1f}B")
        rows[bits] = nbytes

        _, filled = jax.jit(lambda p, t, c: prefill(cfg, p, t, c))(params, toks[:, :S], caches)
        f_dec = jax.jit(lambda p, t, i, c: decode_step(cfg, p, t, i, c))
        us = time_fn(lambda: f_dec(params, toks[:, S:], jnp.asarray(S, jnp.int32), filled),
                     iters=10, warmup=3)
        emit(f"kv_quant_decode_step_{tag}", us, f"B={B},cap={cap}")

    red = rows[0] / rows[8]
    emit("kv_quant_byte_reduction", 0.0,
         f"{red:.2f}x_fewer_cache_bytes,batch_headroom_at_fixed_budget={red:.2f}x")


def paged() -> None:
    """Paged KV block pool (serving/kv_pool.py): (a) cache bytes for the same
    live traffic, contiguous slots x capacity vs a pool provisioned for the
    actual sequence lengths; (b) effective concurrent sequences per GiB of
    cache — the number that multiplies with int8 KV; (c) measured decode-tick
    wall-clock paged vs contiguous through the ContinuousEngine (CPU ref
    path; TPU uses the scalar-prefetch Pallas page-gather kernel); (d) a
    short traffic mix with per-tick scheduler metrics emitted as JSON."""
    import json

    from repro.core.prmoe import nlg_moe
    from repro.models.model import init_caches, init_paged_caches, init_params
    from repro.quant import kv_cache_bytes
    from repro.serving.continuous import ContinuousEngine
    from repro.serving.engine import Request

    cfg = nlg_moe("paged-bench", 4, 256, 4, 16, vocab=1024).replace(
        param_dtype="float32", compute_dtype="float32")
    slots, capacity, ps = 8, 256, 16
    avg_len = 48  # demo traffic: 32-token prompts + 16 new tokens
    pages_per_seq = -(-avg_len // ps)

    for kv_bits in (0, 8):
        tag = f"int{kv_bits}" if kv_bits else "fp32"
        contig = kv_cache_bytes(jax.eval_shape(
            lambda b=kv_bits: init_caches(cfg, slots, capacity, kv_bits=b)))
        n_pages = slots * pages_per_seq  # provisioned for the traffic, not worst case
        pool = kv_cache_bytes(jax.eval_shape(
            lambda b=kv_bits: init_paged_caches(
                cfg, slots, capacity, n_pages=n_pages, page_size=ps, kv_bits=b)))
        emit(f"paged_cache_bytes_{tag}", 0.0,
             f"contiguous={contig},pool={pool}({n_pages}x{ps}pages),"
             f"reduction={contig/pool:.2f}x")
        # effective concurrent sequences per GiB: contiguous reserves
        # `capacity` cache tokens per sequence; paged reserves only the pages
        # a sequence actually occupies
        per_tok_contig = contig / (slots * capacity)
        # denominator = ALLOCATABLE tokens only — the trash page's bytes are
        # pure overhead and stay in the numerator
        per_tok_paged = pool / (n_pages * ps)
        seqs_contig = 2**30 / (capacity * per_tok_contig)
        seqs_paged = 2**30 / (pages_per_seq * ps * per_tok_paged)
        emit(f"paged_effective_seqs_per_GiB_{tag}", 0.0,
             f"contiguous={seqs_contig:.0f},paged={seqs_paged:.0f},"
             f"gain={seqs_paged/seqs_contig:.2f}x(target:>=2x)")

    params = init_params(cfg, jax.random.PRNGKey(0))
    t_slots, t_cap = 4, 128
    rng = jax.random.PRNGKey(1)
    prompts = [jax.random.randint(jax.random.fold_in(rng, i), (32,), 0,
                                  cfg.vocab_size).tolist() for i in range(t_slots)]
    rows = {}
    for mode in ("contiguous", "paged"):
        eng = ContinuousEngine(
            cfg, params, slots=t_slots, capacity=t_cap,
            paged=(mode == "paged"), page_size=ps,
        )
        for p in prompts:
            eng.submit(Request(prompt=p, max_new_tokens=t_cap - 33))
        eng.step()  # compile
        us = time_fn(eng.step, iters=10, warmup=2)
        rows[mode] = us
        emit(f"paged_decode_tick_{mode}", us, f"slots={t_slots},cap={t_cap}")
    emit("paged_decode_tick_overhead", 0.0,
         f"{rows['paged']/rows['contiguous']:.2f}x_vs_contiguous(CPU_ref_gather)")

    # traffic mix: many short + a few long, pool at half the contiguous
    # reservation — per-tick scheduler telemetry straight from step()
    eng = ContinuousEngine(cfg, params, slots=6, capacity=128, paged=True,
                           page_size=ps, n_pages=6 * 4)
    for i in range(10):
        n = 12 if i % 3 else 48
        eng.submit(Request(prompt=prompts[i % t_slots][: 8 + (i % 3) * 8],
                           max_new_tokens=n))
    eng.run_until_done()
    occ = [m["page_occupancy"] for m in eng.metrics_log]
    emit("paged_scheduler_traffic_mix", 0.0,
         f"ticks={len(eng.metrics_log)},peak_occupancy={max(occ):.2f},"
         f"preemptions={eng.preemptions}")
    print("# paged_metrics_json:", json.dumps({
        "config": {"slots": 6, "capacity": 128, "page_size": ps, "n_pages": 24},
        "preemptions": eng.preemptions,
        "ticks": eng.metrics_log,
    }))


def prefix() -> None:
    """Prefix sharing / copy-on-write pages (serving/prefix_index.py): heavy
    shared-system-prompt traffic stores the preamble's pages ONCE.  Reports
    (a) analytic per-sequence page cost and the effective sequences/GiB
    multiplier over the PR 3 paged baseline; (b) a measured run — identical
    traffic through the paged engine with and without sharing, comparing
    peak physical pages, with per-tick shared_pages / cow_copies telemetry
    as JSON; (c) the n-sample parallel sampling page cost (all prompt pages
    shared, divergence via CoW)."""
    import json

    from repro.core.prmoe import nlg_moe
    from repro.models.model import init_params
    from repro.serving.continuous import ContinuousEngine
    from repro.serving.engine import Request

    ps = 16
    # analytic: 32-token shared preamble (2 pages), 16-token unique tail +
    # generation (1 page) per sequence, N concurrent sequences
    pre_pages, tail_pages = 2, 1
    for n_seqs in (8, 64):
        base = pre_pages + tail_pages  # PR 3 paged: every seq pays the preamble
        shared = tail_pages + pre_pages / n_seqs  # preamble amortized
        emit(f"prefix_pages_per_seq_{n_seqs}seqs", 0.0,
             f"paged={base},shared={shared:.2f},"
             f"seqs_per_GiB_multiplier={base/shared:.2f}x_on_top_of_paged")

    cfg = nlg_moe("prefix-bench", 4, 256, 4, 16, vocab=1024).replace(
        param_dtype="float32", compute_dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = jax.random.PRNGKey(1)
    preamble = jax.random.randint(rng, (32,), 0, cfg.vocab_size).tolist()
    tails = [jax.random.randint(jax.random.fold_in(rng, i), (8,), 0,
                                cfg.vocab_size).tolist() for i in range(6)]
    reqs = [Request(prompt=preamble + t, max_new_tokens=8) for t in tails]

    rows = {}
    peng = None
    for mode in ("paged", "prefix"):
        eng = ContinuousEngine(cfg, params, slots=6, capacity=128, paged=True,
                               page_size=ps, n_pages=36,
                               prefix_sharing=(mode == "prefix"))
        for r in reqs:
            eng.submit(r)
        eng.run_until_done()
        peak_used = eng.n_pages - min(m["free_pages"] for m in eng.metrics_log)
        rows[mode] = peak_used  # counters only — don't keep both engines' caches alive
        if mode == "prefix":
            peng = eng
        emit(f"prefix_peak_pages_{mode}", 0.0,
             f"peak_used={peak_used}/{eng.n_pages},min_free={eng.n_pages - peak_used}")
    used_paged, used_prefix = rows["paged"], rows["prefix"]
    emit("prefix_page_reduction", 0.0,
         f"{used_paged}/{used_prefix}={used_paged/max(used_prefix,1):.2f}x_fewer_live_pages,"
         f"hits={peng.prefix_hits},shared_tokens={peng.prefix_hit_tokens},"
         f"cow_copies={peng.cow_copies}")

    # parallel sampling: n samples off one prompt share ALL its pages
    n = 4
    eng = ContinuousEngine(cfg, params, slots=n, capacity=128, paged=True,
                           page_size=ps, n_pages=32, prefix_sharing=True)
    eng.submit_n(Request(prompt=preamble + tails[0], max_new_tokens=8), n)
    fork_pages = eng.pool.used_count
    solo_pages = eng.pool.pages_for(len(preamble) + len(tails[0]))
    eng.run_until_done()
    emit("prefix_n_sample_fork_pages", 0.0,
         f"n={n},pages_at_admission={fork_pages}(vs_independent={n * solo_pages}),"
         f"cow_copies={eng.cow_copies}")
    print("# prefix_metrics_json:", json.dumps({
        "config": {"slots": 6, "capacity": 128, "page_size": ps, "n_pages": 36},
        "prefix_hits": peng.prefix_hits,
        "prefix_hit_tokens": peng.prefix_hit_tokens,
        "cow_copies": peng.cow_copies,
        "ticks": peng.metrics_log,
    }))


def chunked_prefill() -> None:
    """Chunked prefill-into-pages (serving admission path): (a) the temp
    contiguous prefill cache the scatter path allocated per admission is
    gone — its bytes were pure double-buffering of the prompt's K/V; (b)
    head-of-line blocking under mixed traffic — a long-prompt admission's
    submit wall-clock (the blocking compute before control returns) and the
    decode tokens running slots produce while the long prompt is still
    prefilling, scatter vs chunked; (c) measured prefill-FLOPs savings on
    shared-preamble traffic (a prefix-sharing admission starts its chunks
    after the shared pages — savings = prefix_len / prompt_len); (d) per-tick
    prefill/decode token telemetry as JSON."""
    import json
    import time as _time

    from repro.core.prmoe import nlg_moe
    from repro.models.model import init_caches, init_params
    from repro.quant import kv_cache_bytes
    from repro.serving.continuous import ContinuousEngine
    from repro.serving.engine import Request

    cfg = nlg_moe("chunked-bench", 4, 256, 4, 16, vocab=1024).replace(
        param_dtype="float32", compute_dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))
    slots, capacity, ps, chunk = 4, 192, 16, 32

    # (a) temp admission buffer: scatter runs the prompt through a fresh
    # [1, capacity] contiguous cache before scattering into pages; chunked
    # writes pages directly, so those bytes vanish from the admission path
    for kv_bits in (0, 8):
        tag = f"int{kv_bits}" if kv_bits else "fp32"
        tmp = kv_cache_bytes(jax.eval_shape(
            lambda b=kv_bits: init_caches(cfg, 1, capacity, kv_bits=b)))
        emit(f"chunked_prefill_temp_buffer_bytes_{tag}", 0.0,
             f"scatter_per_admission={tmp},chunked=0,eliminated={tmp}")

    # (b) mixed traffic: short requests decoding, one long prompt arrives
    rng = jax.random.PRNGKey(1)
    shorts = [jax.random.randint(jax.random.fold_in(rng, i), (8,), 0,
                                 cfg.vocab_size).tolist() for i in range(2)]
    long_p = jax.random.randint(jax.random.fold_in(rng, 9), (128,), 0,
                                cfg.vocab_size).tolist()
    rows = {}
    for mode in ("scatter", "chunked"):
        eng = ContinuousEngine(cfg, params, slots=slots, capacity=capacity,
                               paged=True, page_size=ps, prefill_mode=mode,
                               prefill_chunk=chunk)
        # warm the compile caches so submit() timing is compute, not tracing
        w = eng.submit(Request(prompt=long_p, max_new_tokens=1))
        eng.run_until_done()
        sids = [eng.submit(Request(prompt=p, max_new_tokens=64)) for p in shorts]
        eng.step()
        t0 = _time.perf_counter()
        lid = eng.submit(Request(prompt=long_p, max_new_tokens=4))
        submit_us = (_time.perf_counter() - t0) * 1e6
        li = next(i for i, s in enumerate(eng.slots) if s.request_id == lid)
        decoded_during = 0
        ticks_to_first = 0
        while eng.slots[li].active and (eng.slots[li].prefilling
                                        or not eng.slots[li].generated):
            before = sum(len(eng.slots[i].generated) for i in range(slots) if i != li)
            eng.step()
            ticks_to_first += 1
            decoded_during += sum(
                len(eng.slots[i].generated) for i in range(slots) if i != li) - before
        eng.run_until_done()
        rows[mode] = submit_us
        emit(f"chunked_prefill_long_admit_{mode}", submit_us,
             f"prompt=128tok,decode_tokens_while_prefilling={decoded_during},"
             f"ticks_to_first_token={ticks_to_first}")
    emit("chunked_prefill_admit_blocking_reduction", 0.0,
         f"{rows['scatter']/max(rows['chunked'], 1e-9):.2f}x_shorter_submit_block"
         f"(bounded_by_chunk={chunk}tok_per_tick)")

    # (c) shared-preamble FLOPs savings: serve the preamble once, then N
    # requests that repeat it — chunked+sharing never recomputes it
    preamble = jax.random.randint(rng, (64,), 0, cfg.vocab_size).tolist()
    tails = [jax.random.randint(jax.random.fold_in(rng, 20 + i), (16,), 0,
                                cfg.vocab_size).tolist() for i in range(6)]
    stats = {}
    peng = None
    for sharing in (False, True):
        eng = ContinuousEngine(cfg, params, slots=slots, capacity=capacity,
                               paged=True, page_size=ps, prefill_chunk=chunk,
                               prefix_sharing=sharing)
        first = eng.submit(Request(prompt=preamble + tails[0], max_new_tokens=8))
        while any(s.active and s.prefilling for s in eng.slots):
            eng.step()
        for t in tails[1:]:
            eng.submit(Request(prompt=preamble + t, max_new_tokens=8))
        eng.run_until_done()
        stats[sharing] = (eng.prefill_tokens_total, eng.prefill_tokens_skipped)
        if sharing:
            peng = eng
    total_ns, _ = stats[False]
    total_s, skipped = stats[True]
    emit("chunked_prefill_shared_flops_saved", 0.0,
         f"prefill_tokens:no_sharing={total_ns},sharing={total_s},"
         f"skipped={skipped},saved={skipped/total_ns:.2%}"
         f"(analytic_prefix/prompt={len(preamble)/(len(preamble)+16):.2%}_per_hit)")
    print("# chunked_prefill_metrics_json:", json.dumps({
        "config": {"slots": slots, "capacity": capacity, "page_size": ps,
                   "prefill_chunk": chunk},
        "prefill_tokens_total": peng.prefill_tokens_total,
        "prefill_tokens_skipped": peng.prefill_tokens_skipped,
        "prefix_hits": peng.prefix_hits,
        "ticks": peng.metrics_log[-64:],
    }))


def obs() -> None:
    """Observability layer (src/repro/obs/): the contract is that telemetry
    compiled into the serving hot path is free when off.  (a) steady-state
    decode-tick wall-clock through ContinuousEngine under three Obs levels —
    ``Obs.disabled()`` (baseline), the default ``Obs()`` (metrics on, tracer
    off — what every engine runs with), and everything on (tracer + per-tick
    routing stats); asserts default-vs-disabled overhead <1%; (b) raw tracer
    emit cost per begin/end pair, enabled vs the no-op path; (c) routing
    telemetry (dropped fraction, gate entropy, f·P imbalance) from one
    jitted training step and one decode tick of the SAME model family; (d)
    retrace-watchdog compile accounting, warmup vs steady (steady retraces
    must be zero); (e) the full metrics snapshot as JSON."""
    import json
    import time as _time

    from repro.core.prmoe import nlg_moe
    from repro.models.model import init_params
    from repro.obs import Obs, Tracer
    from repro.serving.continuous import ContinuousEngine
    from repro.serving.engine import Request

    cfg = nlg_moe("obs-bench", 4, 256, 4, 16, vocab=1024).replace(
        param_dtype="float32", compute_dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))
    slots, capacity, ps = 4, 256, 16
    rng = jax.random.PRNGKey(1)
    prompts = [jax.random.randint(jax.random.fold_in(rng, i), (16,), 0,
                                  cfg.vocab_size).tolist() for i in range(slots)]

    def build(o):
        eng = ContinuousEngine(cfg, params, slots=slots, capacity=capacity,
                               paged=True, page_size=ps, obs=o)
        for p in prompts:  # long decodes: every measured tick is pure decode
            eng.submit(Request(prompt=p, max_new_tokens=capacity - 20))
        for _ in range(6):  # warmup: compile + reach watchdog steady state
            eng.step()
        return eng

    modes = {
        "disabled": build(Obs.disabled()),
        "default": build(Obs()),
        "full": build(Obs(trace=True, routing=True)),
    }
    # interleave measurement rounds so clock drift hits all modes equally;
    # min-of-ticks isolates the instrumentation cost from scheduler noise
    mins = {k: float("inf") for k in modes}
    for _ in range(5):
        for k, eng in modes.items():
            for _ in range(8):
                t0 = _time.perf_counter()
                eng.step()  # blocks on the donated caches before returning
                mins[k] = min(mins[k], _time.perf_counter() - t0)
    base = mins["disabled"] * 1e6
    for k in ("disabled", "default", "full"):
        us = mins[k] * 1e6
        emit(f"obs_decode_tick_{k}", us,
             f"overhead_vs_disabled={us/base - 1:+.2%}")
    overhead = mins["default"] / mins["disabled"] - 1
    assert overhead < 0.01, (
        f"default Obs (metrics on, tracer off) added {overhead:.2%} to the "
        "decode tick — the <1% no-op-path contract is broken")
    emit("obs_overhead_guard", 0.0, f"default_vs_disabled={overhead:+.2%}(<1%_OK)")

    # (b) raw tracer emit cost, on vs off
    for enabled in (True, False):
        tr = Tracer(enabled=enabled)
        n = 20000
        t0 = _time.perf_counter()
        for i in range(n):
            tr.begin(("bench", 0), "s")
            tr.end(("bench", 0))
        per = (_time.perf_counter() - t0) / n * 1e6
        emit(f"obs_tracer_span_pair_{'on' if enabled else 'off'}", per,
             f"events={tr.n_events}")

    # (c) routing telemetry: one training step and one decode tick
    from repro.core.gating import summarize_routing
    from repro.training.optimizer import init_adamw
    from repro.training.trainer import TrainConfig, make_train_step

    opt = init_adamw(params)
    step = jax.jit(make_train_step(cfg, TrainConfig(lr=1e-3, warmup_steps=1,
                                                    decay_steps=10),
                                   with_routing=True))
    toks = jax.random.randint(jax.random.fold_in(rng, 99), (2, 64), 0,
                              cfg.vocab_size)
    _, _, metrics = step(params, opt, toks[:, :-1], toks[:, 1:])
    train_r = summarize_routing(metrics["routing"])
    emit("obs_routing_train_step", 0.0,
         f"moe_layers={train_r['moe_layers']},drop={train_r['dropped_frac']:.3f},"
         f"entropy={train_r['entropy']:.3f},imbalance={train_r['imbalance']:.3f}")
    full = modes["full"]
    full.step()
    decode_r = full.last_metrics.get("routing")
    emit("obs_routing_decode_tick", 0.0,
         f"moe_layers={decode_r['moe_layers']},drop={decode_r['dropped_frac']:.3f},"
         f"entropy={decode_r['entropy']:.3f},imbalance={decode_r['imbalance']:.3f}")

    # (d) watchdog: warmup compiles happened, steady state holds, and the
    # measured ticks above never retraced
    wd = full.obs.watchdog.snapshot()
    assert wd["steady"] and wd["steady_retraces"] == 0, wd
    emit("obs_retrace_watchdog", 0.0,
         f"warmup_compiles={wd['total_compiles']},steady={wd['steady']},"
         f"steady_retraces={wd['steady_retraces']}(must_be_0)")

    # the static contract checker (repro.analysis) must predict exactly the
    # compiles the watchdog observed — the trace-time and runtime halves of
    # the instrument agreeing on the number
    from repro.analysis import Workload, predict_compiles

    ticks = 6 + 5 * 8 + 1  # warmup + measurement rounds + the routing tick
    pred = predict_compiles(
        slots=slots, capacity=capacity, page_size=ps,
        prefill_chunk=full.prefill_chunk,
        workload=Workload(tuple(len(p) for p in prompts),
                          capacity - 20, ticks))
    observed = {k: v for k, v in wd["per_fn"].items() if k in pred}
    assert observed == pred, (observed, pred)
    emit("obs_predicted_compiles", float(sum(pred.values())),
         "static_contract_prediction==watchdog_observation")

    print("# obs_metrics_json:", json.dumps({
        "config": {"slots": slots, "capacity": capacity, "page_size": ps},
        "tick_overhead_default_vs_disabled": overhead,
        "watchdog": wd,
        "snapshot": full.obs.metrics.snapshot(),
    }))


def fused_tick() -> None:
    """One fused tick (PR 8): grouped dropless expert dispatch + batched
    multi-slot chunk prefill.  (a) >= 3 concurrent mid-prefill admissions
    served by ONE fixed-shape jitted prefill call per tick — jitted calls
    per tick and batched-call occupancy from the engine's own metrics;
    (b) the retrace-watchdog acceptance: predicted compile counts
    (``predict_compiles(prefill_mode="batched")``) == observed per-fn counts,
    with the batched entry compiling exactly once; (c) tick p50/p99 batched
    vs per-slot chunked on the same traffic (batched must not regress p50);
    (d) dead expert FLOPs: capacity-factor padding (``[E, C]`` slots gating
    left empty) vs the grouped layout's worst-case tile padding on the same
    token counts."""
    import json
    import numpy as np

    from repro.analysis import Workload, predict_compiles
    from repro.analysis.graph import capacity_dead_compute
    from repro.core.dispatch_grouped import GROUPED_TILE, grouped_rows
    from repro.core.prmoe import nlg_moe
    from repro.models.model import init_params
    from repro.serving.continuous import ContinuousEngine
    from repro.serving.engine import Request

    cfg = nlg_moe("fused-bench", 4, 256, 4, 16, vocab=1024).replace(
        param_dtype="float32", compute_dtype="float32", moe_impl="grouped")
    params = init_params(cfg, jax.random.PRNGKey(0))
    slots, capacity, ps, chunk = 4, 256, 16, 64
    rng = jax.random.PRNGKey(1)
    # 4 long prompts admitted together: every slot stays mid-prefill for 3
    # ticks, so the batched call runs at full occupancy before decode starts
    plens = (192, 192, 160, 128)
    prompts = [jax.random.randint(jax.random.fold_in(rng, i), (n,), 0,
                                  cfg.vocab_size).tolist()
               for i, n in enumerate(plens)]
    n_new = 24

    def run(mode):
        eng = ContinuousEngine(cfg, params, slots=slots, capacity=capacity,
                               paged=True, page_size=ps, prefill_chunk=chunk,
                               prefill_mode=mode)
        for p in prompts:
            eng.submit(Request(prompt=p, max_new_tokens=n_new))
        eng.run_until_done()
        # second identical wave, fully warm: these are the measured ticks
        eng.metrics_log.clear()
        for p in prompts:
            eng.submit(Request(prompt=p, max_new_tokens=n_new))
        eng.run_until_done()
        return eng

    engines = {m: run(m) for m in ("chunked", "batched")}

    # (a) fused-tick dispatch accounting from the engine's own telemetry
    eb = engines["batched"]
    pre = [m for m in eb.metrics_log if m.get("prefill_tokens", 0)]
    occ = max(m["batched_prefill_occupancy"] for m in pre)
    calls = max(m["jitted_calls"] for m in pre)
    assert occ >= 3 / slots, f"want >=3 concurrent mid-prefill rows, occ={occ}"
    assert calls <= 2, f"fused tick issued {calls} jitted calls"
    emit("fused_tick_batched_occupancy", 0.0,
         f"peak={occ:.2f},rows={int(occ * slots)}_of_{slots}")
    emit("fused_tick_jitted_calls", float(calls),
         "max_per_prefill_tick(<=2:batched_prefill+decode)")

    # (b) predicted == observed compile counts (both waves in one workload
    # is wrong — the second wave adds no compiles, so predict the first)
    wd = eb.obs.watchdog.snapshot()
    assert wd["steady_retraces"] == 0, wd
    pred = predict_compiles(slots=slots, capacity=capacity, page_size=ps,
                            prefill_chunk=chunk, prefill_mode="batched",
                            workload=Workload(plens, n_new, 64))
    observed = {k: v for k, v in wd["per_fn"].items() if k in pred}
    assert observed == pred, (observed, pred)
    assert pred["prefill_chunk_batched"] == 1
    emit("fused_tick_predicted_compiles", float(sum(pred.values())),
         "static_prediction==watchdog_observation,batched_entry_compiles_once")

    # (c) tick latency, batched vs chunked, same traffic
    stats = {}
    for mode, eng in engines.items():
        ts = np.asarray([m["tick_s"] for m in eng.metrics_log]) * 1e6
        stats[mode] = (float(np.percentile(ts, 50)), float(np.percentile(ts, 99)))
        emit(f"fused_tick_p50_{mode}", stats[mode][0],
             f"p99={stats[mode][1]:.0f}us,ticks={len(ts)}")
    assert stats["batched"][0] <= stats["chunked"][0] * 1.25, (
        "batched tick p50 regressed vs per-slot chunked", stats)

    # (d) dead expert FLOPs on one full batched prefill call's tokens, at a
    # REALIZED routing of the same gating spec (not the analytic worst case:
    # actual tile padding is data-dependent and far below it).  Useful work
    # differs too — capacity DROPS overflowing assignments, dropless keeps
    # every one — so compare dead fraction per expert-MLP row actually run.
    from repro.core.gating import top_k_gating
    from repro.core.moe import init_moe

    f = next(ls.ffn for seg in cfg.segments for ls in seg.pattern
             if getattr(ls.ffn, "num_experts", 0))
    nt, tk = slots * chunk, slots * chunk * f.top_k
    moe_p = init_moe(jax.random.fold_in(rng, 7), cfg, f, jnp.float32)
    xs = jax.random.normal(jax.random.fold_in(rng, 8), (nt, cfg.d_model))
    g = top_k_gating(xs @ moe_p["router"], f.top_k, tk)
    counts = np.bincount(np.asarray(g.expert_idx).reshape(-1),
                         minlength=f.num_experts)
    cap = capacity_dead_compute(nt, f.num_experts, f.top_k, f.capacity_factor)
    kept = int(np.minimum(counts, cap["capacity"]).sum())
    cap_dead = 1.0 - kept / cap["slots"]
    t = GROUPED_TILE
    ct_actual = int(((counts + t - 1) // t * t).sum())
    ct_worst = grouped_rows(nt, f.top_k, f.num_experts, t)
    g_dead = 1.0 - tk / ct_actual
    emit("fused_tick_dead_flops_capacity", 0.0,
         f"dead_row_fraction={cap_dead:.1%}(E={f.num_experts},"
         f"C={cap['capacity']},dropped={tk - kept}_of_{tk})")
    emit("fused_tick_dead_flops_grouped", 0.0,
         f"dead_row_fraction={g_dead:.1%}(Ct={ct_actual},"
         f"worst_case={ct_worst},dropped=0_of_{tk})")
    assert ct_actual <= ct_worst
    assert g_dead < cap_dead, (g_dead, cap_dead)

    print("# fused_tick_metrics_json:", json.dumps({
        "config": {"slots": slots, "capacity": capacity, "page_size": ps,
                   "prefill_chunk": chunk, "moe_impl": cfg.moe_impl,
                   "prompt_lens": list(plens)},
        "batched_occupancy_peak": occ,
        "jitted_calls_max_prefill_tick": calls,
        "predicted_compiles": pred,
        "tick_us": {m: {"p50": s[0], "p99": s[1]} for m, s in stats.items()},
        "dead_flops_fraction": {"capacity": cap_dead, "grouped": g_dead,
                                "capacity_dropped": tk - kept},
        "watchdog": wd,
    }))


def ep_serving() -> None:
    """Expert-parallel serving topology (PR 9): what sharding the experts
    over a serving mesh buys vs single-device.  (a) MEASURED per-device
    parameter bytes on a (4, 2) ("pod", data) mesh — expert stacks sharded
    ep-ways, attention/router replicated — via the real placement path
    (subprocess under 8 fake CPU devices, `serving/ep.py`); (b) the
    aggregate-bandwidth ledger: expert bytes each device reads per tick,
    sharded vs single-device (the paper's §5 latency lever); (c) the
    all-to-all exchange volume the sharding costs per MoE layer — decode's
    replicated-token all_gather, prefill's token-sharded a2a — and the
    flat vs hierarchical two-hop (Fig. 8) message count per device."""
    import json
    import os
    import subprocess
    import sys as _sys

    from repro.core.gating import expert_capacity

    script = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
import jax, json
from repro.configs.registry import all_configs, make_reduced, with_moe_ffn
from repro.models.model import init_params
from repro.serving.ep import build_serving_mesh, place_params, placed_param_bytes
from repro.parallel.sharding import use_mesh

E = 8
cfg = with_moe_ffn(make_reduced(all_configs()["nlg-350m-moe128"]), num_experts=E)
params = init_params(cfg, jax.random.PRNGKey(0))
flat = jax.tree_util.tree_flatten_with_path(params)[0]
total = sum(l.size * l.dtype.itemsize for _, l in flat)
# expert stacks are the layer-stacked [L, E, d, f] moe mlp weights
expert = sum(l.size * l.dtype.itemsize for kp, l in flat
             if "moe" in jax.tree_util.keystr(kp)
             and jax.tree_util.keystr(kp).split("'")[-2] in ("wi", "wg", "wo"))
mesh, rules = build_serving_mesh((4, 2))
with use_mesh(mesh, rules):
    placed = place_params(mesh, rules, params)
print(json.dumps({"total": total, "expert": expert,
                  "per_dev": placed_param_bytes(placed)}))
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    # this process may hold the accelerator, and a chip belongs to one
    # process: the child forces 8 fake CPU devices and measures placement
    # (bytes per device), never time — keep this section out of chip runs
    print("# ep_serving: placement measured in a child process on 8 fake CPU "
          "devices (JAX_PLATFORMS=cpu)")
    r = subprocess.run([_sys.executable, "-c", script], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    m = json.loads(r.stdout.strip().splitlines()[-1])
    ep = 8
    expect = (m["total"] - m["expert"]) + m["expert"] // ep
    assert m["per_dev"] == expect, (m, expect)
    emit("ep_serving_params_per_device", 0.0,
         f"mesh=(4x2),{m['per_dev'] / 1e6:.2f}MB_of_{m['total'] / 1e6:.2f}MB,"
         f"expert_shard={m['expert'] // ep / 1e6:.2f}MB(1/{ep})")
    emit("ep_serving_expert_read_per_tick", 0.0,
         f"sharded={m['expert'] // ep / 1e6:.2f}MB/device,"
         f"single={m['expert'] / 1e6:.2f}MB:aggregate_bandwidth_x{ep}")

    # (c) exchange volume per MoE layer per device, f32 reduced config
    #     (E=8, K=2, d=128): decode = all_gather of the [E, C, d] output
    #     buffer (each device contributes its E/ep slice); prefill chunk of
    #     64 tokens = dispatch a2a out + combine a2a back
    E, K, d, bytes_el = 8, 2, 128, 4
    for T, phase in ((4, "decode_allgather"), (64, "prefill_a2a")):
        if phase == "decode_allgather":
            cap = expert_capacity(T, E, K, 8.0)
            vol = (E - E // ep) * cap * d * bytes_el  # received per device
        else:
            cap = expert_capacity(T // ep, E, K, 8.0)  # per-shard gating
            vol = 2 * (ep - 1) * (E // ep) * cap * d * bytes_el
        emit(f"ep_serving_{phase}_volume", 0.0,
             f"T={T},cap={cap},{vol / 1e3:.1f}KB/device/layer,single_device=0KB")
    for shape in ((8,), (4, 2), (2, 4)):
        n = 1
        for s in shape:
            n *= s
        flat_msgs = n - 1
        hier_msgs = sum(s - 1 for s in shape)
        emit("ep_serving_a2a_messages", 0.0,
             f"mesh={'x'.join(map(str, shape))},flat={flat_msgs},"
             f"hierarchical={hier_msgs}_per_device(Fig8_two_hop)")

    print("# ep_serving_metrics_json:", json.dumps({
        "mesh": [4, 2], "ep_degree": ep,
        "params_bytes": {"total": m["total"], "expert": m["expert"],
                         "per_device": m["per_dev"]},
    }))


def spec() -> None:
    """Draft-then-verify speculative decoding over CoW page forks (PR 10):
    (a) accepted tokens per verify pass with a same-family (self) drafter —
    ASSERTS > 1.0, i.e. each batched target forward emits more than one
    token; (b) the fresh-init drafter contrast — near-zero accept, every
    window's fork pages rolled back, output still token-exact greedy;
    (c) target forward passes per emitted token, speculative vs the
    non-speculative baseline on the same traffic (the paper-level win:
    the expensive MoE model runs once per window, not once per token);
    (d) decode-tick wall-clock p50/p99 for all three engines plus the
    fork-page commit/rollback ledger from the metrics registry (JSON)."""
    import json
    import numpy as np

    from repro.core.prmoe import nlg_moe
    from repro.models.model import init_params
    from repro.obs import Obs
    from repro.serving.continuous import ContinuousEngine
    from repro.serving.engine import Request

    # dropless grouped dispatch: capacity-factor dropping is batch-size
    # dependent (a k+1-token verify pass would route differently from the
    # baseline's one-token decode), so greedy parity needs moe_impl=grouped
    cfg = nlg_moe("spec-bench", 4, 256, 4, 16, vocab=1024).replace(
        param_dtype="float32", compute_dtype="float32", moe_impl="grouped")
    params = init_params(cfg, jax.random.PRNGKey(0))
    fresh = init_params(cfg, jax.random.PRNGKey(1))
    k, slots, n_new = 4, 3, 32
    rng = jax.random.PRNGKey(2)
    prompts = [jax.random.randint(jax.random.fold_in(rng, i), (n,), 0,
                                  cfg.vocab_size).tolist()
               for i, n in enumerate((12, 9, 17))]

    def run(spec_draft):
        kw = dict(slots=slots, capacity=64, paged=True, page_size=4,
                  obs=Obs())
        if spec_draft is not None:
            kw.update(spec_draft=spec_draft, spec_k=k)
        eng = ContinuousEngine(cfg, params, **kw)
        out = []
        for _ in range(2):  # wave 0 warms every jit, wave 1 is measured
            eng.metrics_log.clear()
            ids = [eng.submit(Request(prompt=p, max_new_tokens=n_new))
                   for p in prompts]
            done = eng.run_until_done()
            out = [done[i].tokens for i in ids]
        return eng, out

    engines = {"baseline": run(None), "self_draft": run((cfg, params)),
               "fresh_draft": run((cfg, fresh))}
    base_out = engines["baseline"][1]
    for name, (_, out) in engines.items():
        assert out == base_out, f"{name} diverged from greedy baseline"

    # (a)/(b) accept accounting from the engine's own per-tick spec metrics
    totals = {}
    for name in ("self_draft", "fresh_draft"):
        eng = engines[name][0]
        s = [m["spec"] for m in eng.metrics_log if m.get("spec")]
        t = {f: sum(m.get(f, 0) for m in s)
             for f in ("windows", "drafted", "accepted", "emitted", "resyncs")}
        totals[name] = t
        tpv = t["emitted"] / t["windows"]
        rate = t["accepted"] / max(t["drafted"], 1)
        c = eng.obs.metrics.snapshot()["counters"]
        emit(f"spec_tokens_per_verify_{name}", 0.0,
             f"{tpv:.2f}tok/verify(k={k},accept_rate={rate:.2f},"
             f"windows={t['windows']},committed_pages="
             f"{c['spec.committed_pages']},rolled_back_pages="
             f"{c['spec.rolled_back_pages']})")
    self_tpv = totals["self_draft"]["emitted"] / totals["self_draft"]["windows"]
    fresh_tpv = (totals["fresh_draft"]["emitted"]
                 / totals["fresh_draft"]["windows"])
    assert self_tpv > 1.0, (
        "same-family drafter must accept >1 token per verify pass", self_tpv)
    assert fresh_tpv < self_tpv, (fresh_tpv, self_tpv)

    # (c) target forward passes per emitted token: the baseline decodes one
    # token per (batched) tick; the speculative engine emits a whole window
    # per verify pass.  Per-slot passes = windows / emitted.
    base_ticks = [m for m in engines["baseline"][0].metrics_log
                  if m["tokens_this_tick"]]
    emit("spec_target_passes_per_token", 0.0,
         f"baseline=1.00,self_draft="
         f"{totals['self_draft']['windows'] / totals['self_draft']['emitted']:.2f},"
         f"fresh_draft="
         f"{totals['fresh_draft']['windows'] / totals['fresh_draft']['emitted']:.2f}")

    # (d) decode-tick wall-clock (spec ticks carry draft + verify + commit)
    stats = {}
    for name, (eng, _) in engines.items():
        ts = np.asarray([m["tick_s"] for m in eng.metrics_log
                         if m["tokens_this_tick"]]) * 1e6
        stats[name] = {"p50": float(np.percentile(ts, 50)),
                       "p99": float(np.percentile(ts, 99)),
                       "ticks": len(ts)}
        emit(f"spec_decode_tick_p50_{name}", stats[name]["p50"],
             f"p99={stats[name]['p99']:.0f}us,ticks={len(ts)}")
    assert len(base_ticks) > totals["self_draft"]["windows"] / slots, (
        "speculation must need fewer target passes than baseline ticks")

    print("# spec_metrics_json:", json.dumps({
        "config": {"k": k, "slots": slots, "page_size": 4,
                   "max_new_tokens": n_new,
                   "prompt_lens": [len(p) for p in prompts]},
        "totals": totals,
        "tokens_per_verify": {"self_draft": self_tpv,
                              "fresh_draft": fresh_tpv},
        "tick_us": stats,
    }))


SECTIONS = {
    "table3": table3,
    "fig10": fig10,
    "fig11": fig11,
    "fig12": fig12,
    "fig13": fig13,
    "fig14_15": fig14_15,
    "kernel6x": kernel6x,
    "moe_impl": moe_impl,
    "quant": quant,
    "kv_quant": kv_quant,
    "paged": paged,
    "prefix": prefix,
    "chunked_prefill": chunked_prefill,
    "obs": obs,
    "fused_tick": fused_tick,
    "ep_serving": ep_serving,
    "spec": spec,
}


def main() -> None:
    picks = sys.argv[1:] or list(SECTIONS)
    enable_compile_cache()
    print("name,us_per_call,derived,device")
    for p in picks:
        SECTIONS[p]()


if __name__ == "__main__":
    main()
