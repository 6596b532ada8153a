"""Serving launcher: loads (or randomly initialises) a model and runs the
DS-MoE inference engine over synthetic requests, reporting prefill and
per-token decode latency.

  PYTHONPATH=src python -m repro.launch.serve --arch nlg-350m-moe128 --reduced

``--paged`` switches to the continuous-batching engine with a paged KV block
pool (serving/kv_pool.py): cache memory becomes a shared pool of
``--page-size``-token pages, requests are admitted by free-block count, and
``--pages`` oversubscribes the pool below the contiguous worst case.
Composes with ``--kv-bits 8`` (int8 pages) and ``--quant-bits``.

``--prefix-sharing`` adds refcounted copy-on-write page sharing: admissions
whose context repeats an indexed full-page prefix point their block tables
at the existing physical pages, and ``--n-samples N`` serves N parallel
samples per prompt off one set of prompt pages (diverging via CoW).

``--prefill-mode batched`` fuses every mid-prefill slot's next chunk into ONE
fixed-shape jitted call per tick (the fused tick: at most one prefill + one
decode dispatch), and ``--moe-impl grouped`` serves the dropless
expert-sorted MoE dispatch — no expert_capacity, no token drops.  The
``serve.jitted_calls_per_tick`` and ``serve.batched_prefill_occupancy``
gauges in the rendered snapshot show both at work.

Observability (docs/OBSERVABILITY.md): the run's SLO histograms (queue-wait,
TTFT, TPOT, tick latency), lifecycle counters, and MoE routing gauges are
printed from one metrics ``snapshot()`` — ``--metrics-out`` appends the SAME
snapshot as a JSON line, so the CLI and the file can never disagree.
``--trace-out`` records the full request lifecycle (queued → prefill
chunk(s) → decode → complete, plus preemption/CoW/prefix-hit instants) as
Chrome ``trace_event`` JSON; load it at https://ui.perfetto.dev.
``--obs-routing`` adds per-decode-tick expert-routing telemetry.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.checkpoint import ckpt
from repro.configs.registry import get_config, make_reduced
from repro.launch.runtime import device_label, enable_compile_cache
from repro.models.model import init_params
from repro.obs import Obs
from repro.serving.engine import Engine, EngineConfig, Request


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--moe-impl", default=None,
                    choices=[None, "einsum", "dense", "ep", "grouped"],
                    help="MoE dispatch implementation override; 'grouped' is "
                         "the dropless expert-sorted Pallas path (no "
                         "expert_capacity, no token drops)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=0.0)
    ap.add_argument("--quant-bits", type=int, default=0, choices=[0, 4, 8],
                    help="weight-only PTQ before serving (0 = off; MoQ §4)")
    ap.add_argument("--quant-policy", default="experts",
                    choices=["experts", "experts_attn", "all"])
    ap.add_argument("--quant-group-size", type=int, default=0,
                    help="scale group size along the contraction dim, int8 or int4 "
                         "(0 = one scale per output channel)")
    ap.add_argument("--kv-bits", type=int, default=0, choices=[0, 8],
                    help="KV-cache quantization: 8 = int8 cache with per-head, "
                         "per-timestep scales (~4x fewer decode cache bytes), "
                         "0 = full precision; composes with --quant-bits")
    ap.add_argument("--paged", action="store_true",
                    help="serve via the continuous-batching engine with a "
                         "paged KV block pool (admission by free-block count, "
                         "lazy table growth, youngest-slot preemption)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="cache tokens per page for --paged")
    ap.add_argument("--pages", type=int, default=0,
                    help="total pool pages for --paged (0 = auto: "
                         "slots * ceil(capacity / page_size), no oversubscription)")
    ap.add_argument("--slots", type=int, default=0,
                    help="decode slots for --paged (default: --batch)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="with --paged: admission-prefill tokens per engine "
                         "tick (chunked prefill-into-pages; 0 = auto: "
                         "max(64, page_size)).  Long prompts prefill one "
                         "page-aligned chunk per tick interleaved with "
                         "decode, bounding time-to-first-token head-of-line "
                         "blocking; must be >= --page-size")
    ap.add_argument("--prefill-mode", default="chunked",
                    choices=["chunked", "batched", "scatter"],
                    help="with --paged: 'chunked' prefills one slot per tick "
                         "(default), 'batched' fuses ALL mid-prefill slots "
                         "into one fixed-shape jitted call per tick (fused "
                         "tick: at most one prefill + one decode dispatch), "
                         "'scatter' is the legacy non-chunked admission")
    ap.add_argument("--prefix-sharing", action="store_true",
                    help="with --paged: refcounted copy-on-write page sharing "
                         "— contexts repeating an indexed full-page prefix "
                         "point their block tables at the existing pages "
                         "(serving/prefix_index.py)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome/Perfetto trace_event JSON of the "
                         "request lifecycle (slots, requests, engine ticks) "
                         "to PATH; load in https://ui.perfetto.dev")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="append the final metrics snapshot (counters, "
                         "gauges, SLO histograms) to PATH as one JSON line")
    ap.add_argument("--obs-routing", action="store_true",
                    help="collect per-decode-tick MoE routing telemetry "
                         "(per-expert load, dropped-token fraction, gate "
                         "entropy, f*P imbalance) in the jitted step")
    ap.add_argument("--n-samples", type=int, default=1,
                    help="parallel samples per prompt (paged continuous "
                         "engine); with --prefix-sharing the samples share "
                         "ALL prompt pages and diverge via copy-on-write")
    ap.add_argument("--spec-draft", default=None, metavar="ARCH",
                    help="draft-then-verify speculative decoding "
                         "(serving/spec.py): registry arch name of the dense "
                         "drafter (randomly initialised, --reduced applies), "
                         "or 'self' for the drafter==target oracle.  The "
                         "drafter proposes --spec-k tokens per slot; the "
                         "target verifies all windows in one batched pass "
                         "over CoW page forks.  Greedy-only; needs --paged")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="with --spec-draft: drafted tokens per verify window")
    ap.add_argument("--ep-devices", default=None, metavar="N[xM]",
                    help="expert-parallel serving mesh: '8' shards experts "
                         "flat over 8 devices, '4x2' builds a (hosts, "
                         "devices-per-host) mesh whose MoE exchange is the "
                         "hierarchical two-hop all-to-all (paper Fig. 8). "
                         "Expert weights place per-device, attention runs "
                         "data-parallel over slots; the scheduler stays "
                         "host-side.  CPU testing: set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N")
    args = ap.parse_args()
    if args.prefix_sharing and not args.paged:
        ap.error("--prefix-sharing requires --paged (block tables)")
    if args.prefill_chunk and not args.paged:
        ap.error("--prefill-chunk applies to the paged admission path; pass --paged")
    if args.prefill_chunk and args.prefill_chunk < args.page_size:
        ap.error(f"--prefill-chunk {args.prefill_chunk} must be >= --page-size "
                 f"{args.page_size} (chunk boundaries are page-aligned)")
    if args.prefill_mode != "chunked" and not args.paged:
        ap.error(f"--prefill-mode {args.prefill_mode} is an admission policy "
                 "of the paged continuous engine; pass --paged")
    if args.n_samples > 1 and not args.paged:
        ap.error("--n-samples > 1 is served by the paged continuous engine; "
                 "pass --paged")
    if args.n_samples < 1:
        ap.error(f"--n-samples must be >= 1, got {args.n_samples}")
    if args.temperature <= 0.0 and (args.top_k or args.top_p):
        ap.error("--top-k/--top-p have no effect at --temperature 0 (greedy); "
                 "pass --temperature > 0")
    if args.spec_draft:
        if not args.paged:
            ap.error("--spec-draft rides the paged continuous engine "
                     "(CoW page forks); pass --paged")
        if args.temperature > 0.0:
            ap.error("--spec-draft is greedy-only: verification accepts the "
                     "longest draft prefix matching the target's argmax, "
                     "which is exact only at --temperature 0")
        if args.ep_devices:
            ap.error("--spec-draft is not implemented over an "
                     "expert-parallel serving mesh; drop --ep-devices")
        if args.spec_k < 1:
            ap.error(f"--spec-k must be >= 1, got {args.spec_k}")

    enable_compile_cache()
    print(f"device: {device_label()}")
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = make_reduced(cfg)
    if args.top_k > cfg.vocab_size:
        ap.error(f"--top-k {args.top_k} exceeds vocab_size {cfg.vocab_size}")
    if args.moe_impl:
        has_moe = any(getattr(ls.ffn, "num_experts", 0)
                      for seg in cfg.segments for ls in seg.pattern)
        if args.moe_impl == "grouped" and not has_moe:
            ap.error(f"--moe-impl grouped: arch '{cfg.name}' has no MoE "
                     "layers to dispatch — pick an MoE arch (e.g. "
                     "nlg-350m-moe128) or drop the flag")
        cfg = cfg.replace(moe_impl=args.moe_impl)
    if args.ep_devices:
        from repro.serving.ep import parse_ep_mesh

        try:
            shape = parse_ep_mesh(args.ep_devices)
        except ValueError as e:
            ap.error(str(e))
        ndev = 1
        for n in shape:
            ndev *= n
        if ndev > len(jax.devices()):
            ap.error(f"--ep-devices {args.ep_devices}: needs {ndev} devices, "
                     f"only {len(jax.devices())} visible (CPU: XLA_FLAGS="
                     f"--xla_force_host_platform_device_count={ndev})")
        cfg = cfg.replace(ep_mesh=shape)

    params = init_params(cfg, jax.random.PRNGKey(0))

    if args.quant_bits:
        from repro.configs.base import QuantConfig
        from repro.quant import quantize_params, quantized_leaf_paths, tree_bytes

        qcfg = QuantConfig(bits=args.quant_bits, group_size=args.quant_group_size,
                           policy=args.quant_policy)
        fp_bytes = tree_bytes(params)
        if args.ckpt:
            # a --ckpt may hold either an already-quantized tree (saved from
            # quantize_params output) or fp weights to PTQ after loading —
            # try the quantized structure first, fall back to fp-then-PTQ.
            try:
                params, _ = ckpt.load(args.ckpt, quantize_params(params, qcfg))
            except ValueError as q_err:
                try:
                    params, _ = ckpt.load(args.ckpt, params)
                except ValueError as fp_err:
                    raise ValueError(
                        f"--ckpt {args.ckpt!r} matches neither the quantized "
                        f"structure for {qcfg} ({q_err}) nor the fp structure "
                        f"({fp_err}); was it saved with different quant "
                        "bits/group_size/policy?"
                    ) from fp_err
                params = quantize_params(params, qcfg)
        else:
            params = quantize_params(params, qcfg)
        if not quantized_leaf_paths(params):
            print(f"WARNING: quant policy '{args.quant_policy}' matched no "
                  f"weights in {cfg.name} (dense arch with an experts-only "
                  "policy?) — serving full precision")
        print(f"PTQ int{args.quant_bits}/{args.quant_policy}: "
              f"{fp_bytes/1e6:.1f}MB -> {tree_bytes(params)/1e6:.1f}MB")
        if cfg.moe_impl == "ep":
            print("NB: under an active mesh the EP shard_map path serves "
                  "materialized fp experts (no memory win; see "
                  "repro.quant.prepare_params_for_serving)")
        if cfg.moe_impl == "grouped" and args.quant_group_size:
            print(f"NB: the grouped Pallas kernel dequantizes per-output-"
                  f"channel scales in VMEM; group_size="
                  f"{args.quant_group_size} scales run only through the "
                  "dequant reference off the TPU, and raise on it — drop "
                  "--quant-group-size to keep the kernel")
    elif args.ckpt:
        params, _ = ckpt.load(args.ckpt, params)

    ec = EngineConfig(
        max_batch=args.batch,
        max_prefill=args.prompt_len,
        max_decode=args.new_tokens,
        temperature=args.temperature,
        top_k=args.top_k,
        top_p=args.top_p,
        kv_cache_bits=args.kv_bits,
        page_size=args.page_size if args.paged else 0,
        n_pages=args.pages,
        prefix_sharing=args.prefix_sharing,
        prefill_chunk=args.prefill_chunk,
    )
    obs = Obs(trace=bool(args.trace_out), routing=args.obs_routing)
    eng = None if args.paged else Engine(cfg, params, ec, obs=obs)
    if eng is not None and eng._mesh is not None:
        from repro.serving.ep import placed_param_bytes

        print(f"EP serving mesh {dict(zip(eng._mesh.axis_names, eng._mesh.devices.shape))}: "
              f"moe_impl={eng.cfg.moe_impl}, "
              f"{placed_param_bytes(eng.params)/1e6:.1f}MB params/device")
    if args.kv_bits and eng is not None:
        from repro.models.model import init_caches
        from repro.quant import kv_cache_bytes

        # abstract shapes only — sizing the banner must not allocate caches
        sizes = {
            bits: kv_cache_bytes(jax.eval_shape(
                lambda b=bits: init_caches(cfg, args.batch, eng._capacity,
                                           cross_len=eng._cross_len, kv_bits=b)
            ))
            for bits in (0, args.kv_bits)
        }
        fp_b, q_b = sizes[0], sizes[args.kv_bits]
        print(f"KV cache int{args.kv_bits}: {fp_b/1e6:.2f}MB -> {q_b/1e6:.2f}MB "
              f"({fp_b/q_b:.2f}x fewer decode cache bytes)")

    rng = np.random.default_rng(0)
    reqs = [
        Request(prompt=rng.integers(0, cfg.vocab_size, size=args.prompt_len).tolist(),
                max_new_tokens=args.new_tokens)
        for _ in range(args.requests)
    ]

    if args.paged:
        from repro.configs.base import PagedKVConfig
        from repro.models.model import init_caches, init_paged_caches
        from repro.quant import kv_cache_bytes
        from repro.serving.continuous import ContinuousEngine

        # the page knobs ride on EngineConfig (built above) and are handed to
        # the continuous engine as a PagedKVConfig bundle
        pcfg = PagedKVConfig(page_size=ec.page_size, n_pages=ec.n_pages,
                             prefix_sharing=args.prefix_sharing,
                             prefill_chunk=ec.prefill_chunk)
        slots = args.slots or args.batch
        capacity = args.prompt_len + args.new_tokens
        spec_draft = None
        if args.spec_draft:
            if args.spec_draft == "self":
                dcfg, dparams = cfg, params
            else:
                dcfg = get_config(args.spec_draft)
                if args.reduced:
                    dcfg = make_reduced(dcfg)
                if dcfg.vocab_size != cfg.vocab_size:
                    ap.error(f"--spec-draft {args.spec_draft}: drafter vocab "
                             f"{dcfg.vocab_size} != target vocab "
                             f"{cfg.vocab_size} — greedy verification needs a "
                             "shared token space")
                dparams = init_params(dcfg, jax.random.PRNGKey(1))
            spec_draft = (dcfg, dparams)
        ceng = ContinuousEngine(
            cfg, params, slots=slots, capacity=capacity,
            temperature=ec.temperature, top_k=ec.top_k, top_p=ec.top_p,
            kv_cache_bits=ec.kv_cache_bits, paged_cfg=pcfg, obs=obs,
            prefill_mode=args.prefill_mode,
            spec_draft=spec_draft, spec_k=args.spec_k,
        )
        if spec_draft is not None:
            print(f"speculative decoding: drafter={spec_draft[0].name}"
                  f"{' (self)' if args.spec_draft == 'self' else ''}, "
                  f"k={args.spec_k} drafted tokens per verify window")
        contig_b = kv_cache_bytes(jax.eval_shape(
            lambda: init_caches(cfg, slots, capacity, kv_bits=args.kv_bits)))
        paged_b = kv_cache_bytes(jax.eval_shape(
            lambda: init_paged_caches(cfg, slots, capacity, n_pages=ceng.n_pages,
                                      page_size=ceng.page_size, kv_bits=args.kv_bits)))
        print(f"paged pool: {ceng.n_pages} pages x {ceng.page_size} tokens "
              f"({paged_b/1e6:.2f}MB) vs contiguous {slots} x {capacity} "
              f"({contig_b/1e6:.2f}MB)")
        if ceng._mesh is not None:
            from repro.serving.ep import placed_param_bytes

            print(f"EP serving mesh "
                  f"{dict(zip(ceng._mesh.axis_names, ceng._mesh.devices.shape))}: "
                  f"moe_impl={ceng.cfg.moe_impl}, "
                  f"{placed_param_bytes(ceng.params)/1e6:.1f}MB params/device")
        # warmup (compile prefill + decode; the request completes, so the
        # pool and metrics window start clean apart from the tick counter)
        ceng.submit(Request(prompt=reqs[0].prompt, max_new_tokens=2))
        ceng.run_until_done()
        ceng.done.clear()
        ceng.preemptions = 0
        ceng.prefill_tokens_total = 0
        ceng.prefill_tokens_skipped = 0
        ceng.metrics_log.clear()
        obs.metrics.reset_all()  # drop warmup/compile samples from the window
        t0 = time.time()
        if args.n_samples > 1:
            ids = [rid for r in reqs for rid in ceng.submit_n(r, args.n_samples)]
        else:
            ids = [ceng.submit(r) for r in reqs]
        done = ceng.run_until_done()
        dt = time.time() - t0
        n_tok = sum(len(done[i].tokens) for i in ids)
        print(f"served {len(ids)} requests, {n_tok} tokens in {dt:.2f}s "
              f"({n_tok/dt:.1f} tok/s, arch={cfg.name}, paged, "
              f"prefill_mode={ceng.prefill_mode})")
        if ceng.drafter is not None:
            sp = [m["spec"] for m in ceng.metrics_log if "spec" in m]
            drafted = sum(s["drafted"] for s in sp)
            accepted = sum(s["accepted"] for s in sp)
            windows = sum(s["windows"] for s in sp)
            emitted = sum(s["emitted"] for s in sp)
            print(f"speculation: {emitted} tokens / {windows} verify passes "
                  f"= {emitted/max(windows,1):.2f} tok/verify "
                  f"(accept rate {accepted/max(drafted,1):.2f}, "
                  f"k={ceng.spec_k})")
        # everything below — preemptions, page occupancy, prefix-sharing
        # hits/CoW, chunked-prefill split, SLO percentiles — renders from
        # the ONE snapshot that --metrics-out also writes
        print(obs.metrics.render(prefix="  "))
        if args.metrics_out:
            obs.metrics.write_jsonl(args.metrics_out, extra={
                "arch": cfg.name, "paged": True, "requests": len(ids),
                "tokens": n_tok, "wall_s": dt,
                "prefill_mode": ceng.prefill_mode,
            })
            print(f"metrics snapshot -> {args.metrics_out}")
        if args.trace_out:
            obs.tracer.export(args.trace_out)
            print(f"trace ({obs.tracer.n_events} events) -> {args.trace_out}; "
                  "load in https://ui.perfetto.dev")
        print("sample:", done[ids[0]].tokens[:10])
        return

    # warmup (compile)
    eng.generate(reqs[: args.batch])
    obs.metrics.reset_all()  # drop warmup/compile samples from the window
    t0 = time.time()
    responses = eng.generate(reqs)
    dt = time.time() - t0
    n_tok = sum(len(r.tokens) for r in responses)
    print(f"served {len(responses)} requests, {n_tok} tokens in {dt:.2f}s "
          f"({n_tok/dt:.1f} tok/s, arch={cfg.name}, moe_impl={cfg.moe_impl})")
    print(obs.metrics.render(prefix="  "))
    if args.metrics_out:
        obs.metrics.write_jsonl(args.metrics_out, extra={
            "arch": cfg.name, "paged": False, "requests": len(responses),
            "tokens": n_tok, "wall_s": dt,
        })
        print(f"metrics snapshot -> {args.metrics_out}")
    if args.trace_out:
        obs.tracer.export(args.trace_out)
        print(f"trace ({obs.tracer.n_events} events) -> {args.trace_out}; "
              "load in https://ui.perfetto.dev")
    print("sample:", responses[0].tokens[:10])


if __name__ == "__main__":
    main()
