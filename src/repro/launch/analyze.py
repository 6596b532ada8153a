"""Trace-time static analysis gate (`make analyze`, ci.sh `analyze` stage).

Runs the four `repro.analysis` passes over the serving engines — before
anything executes on a device — and turns the findings into an exit code:

  1. host-sync / tracer-leak lint over the whole ``src/repro`` tree;
  2. compile-shape contract check for the continuous (paged + prefix-sharing
     + chunked-prefill) and static engines of each ``--arch``: every
     declared signature abstract-traces, the chunk family is closed under
     reachable scheduler states, and the predicted compile count is reported
     (the number the PR 6 retrace watchdog verifies at runtime — see
     ``benchmarks/run.py obs``);
  3. donation/aliasing audit: every ``donate_argnums`` leaf of every jitted
     engine function produced an input-output alias in the lowered module,
     and every donating call site rebinds the donated reference;
  4. graph audit of the decode/prefill graphs: no collectives in
     single-device serving graphs, no int8/int4 -> f32 dequant upcasts, and
     the capacity-padding dead-compute fraction for MoE archs (info).

Besides the ``--arch`` targets it also analyzes a fused-tick engine
(``nlg-350m-moe128`` with ``moe_impl="grouped"`` + ``prefill_mode="batched"``)
so the grouped dropless dispatch graph and the batched-prefill contract /
compile-count prediction are gated too (``--no-fused`` skips it), and two
expert-parallel serving-mesh engines (``nlg-350m-moe128`` over a (2, 2)
hierarchical-a2a mesh, default + grouped/batched schedules) so the sharded
jit registry's contracts, donations and collective structure are gated as
well — re-exec'd under forced fake CPU devices when the host has fewer
than 4 (``--no-ep`` skips it, ``--ep-only`` runs just these).

Exit 0 = no unsuppressed errors (``--strict``: no warnings either).

  PYTHONPATH=src python -m repro.launch.analyze                 # glm4 + gemma3
  PYTHONPATH=src python -m repro.launch.analyze --arch nlg-350m-moe128
"""
from __future__ import annotations

import argparse
import os
import sys
from typing import List, Optional, Sequence

import jax

from repro.analysis import (
    Report,
    Workload,
    audit_donation,
    audit_donated_rebinds,
    audit_graph,
    check_closure,
    check_contract,
    lint_tree,
    predict_compiles,
)
from repro.configs.registry import get_config, make_reduced
from repro.models.model import init_params
from repro.serving.continuous import ContinuousEngine
from repro.serving.engine import Engine, EngineConfig

DEFAULT_ARCHS = ("glm4-9b", "gemma3-27b")

# the scenario the contract's closure/prediction passes replay: mixed prompt
# lengths (page-aligned, odd, sub-page, exactly one chunk budget)
_WORKLOAD = Workload(prompt_lens=(16, 33, 7, 64), max_new=8, ticks=24)


def _moe_ffn(cfg):
    for seg in cfg.segments:
        for ls in seg.pattern:
            if getattr(ls.ffn, "num_experts", 0):
                return ls.ffn
    return None


def _moe_spec(cfg, num_tokens: int) -> Optional[dict]:
    f = _moe_ffn(cfg)
    if f is None:
        return None
    impl = cfg.moe_impl
    # the EP serving schedules keep the reference kernels' compute shape:
    # ep_grouped is the grouped dropless layout (tile padding, no [E, C]
    # buffer) and ep_serve's per-shard dots have leading dim E_local != E,
    # so the capacity cross-check must not look for full-E buffers there.
    if impl == "ep_grouped":
        impl = "grouped"
    return {"num_tokens": num_tokens, "num_experts": f.num_experts,
            "top_k": f.top_k, "capacity_factor": f.capacity_factor,
            "impl": impl}


def build_engines(arch: str, *, reduced: bool = True, slots: int = 4,
                  capacity: int = 128, page_size: int = 16,
                  static_ec: Optional[EngineConfig] = None,
                  moe_impl: Optional[str] = None,
                  prefill_mode: str = "chunked",
                  ep_mesh: Sequence[int] = (), spec: bool = False):
    """(ContinuousEngine paged+prefix, static Engine) for ``arch``.
    ``moe_impl`` overrides the config's dispatch implementation (the grouped
    dropless target); ``prefill_mode`` selects the admission state machine
    ("chunked" default, "batched" = the fused-tick single-dispatch entry);
    ``ep_mesh`` builds the engines over an expert-parallel serving mesh
    (``(2, 2)`` = hierarchical two-hop all-to-all topology); ``spec`` arms
    draft-then-verify speculation with the self-draft oracle (drafter ==
    target), registering the verify/propose/commit jit family."""
    import dataclasses

    cfg = get_config(arch)
    if reduced:
        cfg = make_reduced(cfg)
    if moe_impl is not None:
        cfg = dataclasses.replace(cfg, moe_impl=moe_impl)
    if ep_mesh:
        cfg = dataclasses.replace(cfg, ep_mesh=tuple(ep_mesh))
    params = init_params(cfg, jax.random.PRNGKey(0))
    cont = ContinuousEngine(
        cfg, params, slots=slots, capacity=capacity,
        paged=True, page_size=page_size, prefix_sharing=True,
        prefill_mode=prefill_mode,
        spec_draft=(cfg, params) if spec else None,
    )
    ec = static_ec if static_ec is not None else EngineConfig(
        max_batch=2, max_prefill=64, max_decode=8)
    stat = Engine(cfg, params, ec)
    return cont, stat


def analyze_contracts(tag: str, engine, report: Report, *,
                      workload: Workload = _WORKLOAD) -> None:
    """Pass 2 on one engine: trace + closure + compile-count prediction."""
    entries = engine.shape_contract()
    sub = Report()
    check_contract(entries, sub)
    if isinstance(engine, ContinuousEngine) and engine.paged:
        check_closure(entries, capacity=engine.capacity,
                      page_size=engine.page_size,
                      prefill_chunk=engine.prefill_chunk,
                      workload=workload, report=sub)
        pred = predict_compiles(
            slots=engine.n_slots, capacity=engine.capacity,
            page_size=engine.page_size, prefill_chunk=engine.prefill_chunk,
            workload=workload, prefill_mode=engine.prefill_mode,
            spec=({"commit_pass": engine._spec_commit is not None}
                  if getattr(engine, "drafter", None) is not None else None))
        sub.add("predicted-compiles", "info", tag,
                f"workload {tuple(workload.prompt_lens)} x{workload.max_new} "
                f"new over {workload.ticks} ticks compiles: "
                + ", ".join(f"{k}={v}" for k, v in pred.items() if v)
                + f" (total {sum(pred.values())})")
        sub.metrics[f"contract.{tag}.predicted_compiles"] = sum(pred.values())
    # re-home the per-pass metric keys under this engine's tag
    for k in list(sub.metrics):
        if k.startswith("contract.") and not k.startswith(f"contract.{tag}"):
            sub.metrics[f"contract.{tag}.{k[len('contract.'):]}"] = sub.metrics.pop(k)
    report.extend(sub)


def analyze_donations(tag: str, engine, report: Report) -> None:
    """Pass 3a on one engine: lowered-module alias audit per jitted fn."""
    by_name = {e.name: e for e in engine.shape_contract()}
    for name, (fn, don, _primary) in engine.jitted_functions().items():
        entry = by_name.get(name)
        if entry is None or not entry.sample:
            report.add("donation-uncovered", "error", f"{tag}.{name}",
                       "jitted fn has no contract entry to audit donation at")
            continue
        args = entry.make(*entry.sample[-1])
        audit_donation(f"{tag}.{name}", fn, args, don, report,
                       location=f"{tag}.{name}")


def _pkg_root() -> str:
    import repro

    # repro is a namespace package (no __init__.py): __file__ is None
    return list(repro.__path__)[0]


def analyze_rebinds(report: Report, donated_by_file: dict) -> None:
    """Pass 3b: donated references are rebound at every call site."""
    root = _pkg_root()
    for rel, donated in donated_by_file.items():
        path = os.path.join(root, rel)
        with open(path) as f:
            audit_donated_rebinds(f.read(), rel, donated, report)


def analyze_graphs(tag: str, engine, report: Report) -> None:
    """Pass 4 on one engine: collectives / dtype drift / dead compute in the
    decode graph (the steady-state tick) and, for the continuous engine, the
    budget-length prefill chunk (the admission graph).  Engines built over an
    expert-parallel serving mesh flip the collective check: their MoE graphs
    must *contain* the shard_map token exchange (all_gather/psum/all_to_all)
    rather than be free of it."""
    by_name = {e.name: e for e in engine.shape_contract()}
    cfg = engine.cfg
    multi = getattr(engine, "_mesh", None) is not None
    coll = dict(single_device=not multi,
                expect_collectives=multi and _moe_ffn(cfg) is not None)
    dec = by_name["decode"]
    n_dec = engine.n_slots if isinstance(engine, ContinuousEngine) else engine.ec.max_batch
    audit_graph(f"{tag}.decode", dec.fn, dec.make(*dec.sample[-1]),
                moe=_moe_spec(cfg, n_dec), report=report, **coll)
    chunk = by_name.get("prefill_chunk_first")
    if chunk is not None:
        pt = chunk.sample[-1]
        audit_graph(f"{tag}.prefill_chunk", chunk.fn, chunk.make(*pt),
                    moe=_moe_spec(cfg, pt[0]), report=report, **coll)
        return
    # batched fused-tick engines build one fixed-shape prefill entry instead
    # of the first/cont chunk family; its sample point is the singleton ()
    batched = by_name.get("prefill_chunk_batched")
    if batched is not None:
        nt = engine.n_slots * engine.prefill_chunk
        audit_graph(f"{tag}.prefill_chunk_batched", batched.fn,
                    batched.make(*batched.sample[-1]),
                    moe=_moe_spec(cfg, nt), report=report, **coll)


def analyze_arch(arch: str, report: Report, *, reduced: bool = True,
                 passes: Sequence[str] = ("contract", "donation", "graph"),
                 moe_impl: Optional[str] = None,
                 prefill_mode: str = "chunked", tag: str = "",
                 ep_mesh: Sequence[int] = (), spec: bool = False) -> None:
    cont, stat = build_engines(arch, reduced=reduced, moe_impl=moe_impl,
                               prefill_mode=prefill_mode, ep_mesh=ep_mesh,
                               spec=spec)
    base = f"{arch}{tag}"
    for tag, eng in ((f"{base}.continuous", cont), (f"{base}.static", stat)):
        if "contract" in passes:
            analyze_contracts(tag, eng, report)
        if "donation" in passes:
            analyze_donations(tag, eng, report)
        if "graph" in passes:
            analyze_graphs(tag, eng, report)


def donated_call_sites() -> dict:
    """file -> {method attr -> donated argnum}: the engines' donating call
    sites, derived from the jit registries' declared donations (the paged
    continuous registry is the superset)."""
    return {
        "serving/continuous.py": {
            "_decode": 4, "_prefill": 4, "_prefill_chunk_first": 4,
            "_prefill_chunk_cont": 4, "_prefill_chunk_batched": 6,
            "_reset_pages": 0, "_copy_page": 0, "_copy_slot": 0,
            "_verify": 4, "_spec_commit": 6, "_spec_reset_tail": 0,
        },
        "serving/engine.py": {"_decode": 3, "_prefill": 2},
        "serving/spec.py": {"_prefill": 4, "_propose": 5},
    }


# the EP serving gate shards experts over this many fake CPU devices when
# the host has fewer real ones (the (2, 2) mesh exercises the hierarchical
# two-hop all-to-all topology on the reduced 4-expert configs)
_EP_DEVICES = 4
_EP_MESH = (2, 2)


def analyze_ep(report: Report, *, reduced: bool = True,
               passes: Sequence[str] = ("contract", "donation", "graph")) -> None:
    """EP serving targets: experts sharded over a (2, 2) ("pod", ep_axis)
    mesh for both the default serving schedule (replicated-token decode +
    a2a-sharded prefill) and the grouped dropless kernel with batched
    prefill.  Gates that the sharded jit registry abstract-traces, donates,
    and that its MoE graphs actually carry the token-exchange collectives."""
    analyze_arch("nlg-350m-moe128", report, reduced=reduced, passes=passes,
                 tag="+ep", ep_mesh=_EP_MESH)
    analyze_arch("nlg-350m-moe128", report, reduced=reduced, passes=passes,
                 moe_impl="grouped", prefill_mode="batched",
                 tag="+ep-grouped", ep_mesh=_EP_MESH)


def _reexec_ep(args) -> int:
    """Re-run this module with ``--ep-only`` in a subprocess that forces
    enough fake CPU devices for the EP mesh (the parent's jax backend is
    already initialized single-device, so the flag can't be set in-process)."""
    import subprocess

    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        f" --xla_force_host_platform_device_count={_EP_DEVICES}").strip()
    env["JAX_PLATFORMS"] = "cpu"
    cmd = [sys.executable, "-m", "repro.launch.analyze", "--ep-only"]
    if args.full:
        cmd.append("--full")
    if args.strict:
        cmd.append("--strict")
    if args.show_suppressed:
        cmd.append("--show-suppressed")
    if args.skip:
        cmd += ["--skip", *args.skip]
    # the parent already holds the accelerator (a chip belongs to one
    # process), so the child runs on fake CPU devices — this is a trace-time
    # gate, nothing it prints is a device measurement
    print(f"[analyze --ep-only: child process on {_EP_DEVICES} fake CPU devices "
          "(JAX_PLATFORMS=cpu)]", flush=True)
    proc = subprocess.run(cmd, env=env, capture_output=True, text=True)
    sys.stdout.write(proc.stdout)
    if proc.returncode and proc.stderr:
        sys.stderr.write(proc.stderr)
    return proc.returncode


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--arch", nargs="*", default=list(DEFAULT_ARCHS),
                    help=f"registry archs to analyze (default: {DEFAULT_ARCHS})")
    ap.add_argument("--full", action="store_true",
                    help="full-size configs (default: make_reduced)")
    ap.add_argument("--strict", action="store_true",
                    help="warnings fail the gate too")
    ap.add_argument("--show-suppressed", action="store_true")
    ap.add_argument("--skip", nargs="*", default=[],
                    choices=["lint", "contract", "donation", "rebind", "graph"],
                    help="passes to skip")
    ap.add_argument("--no-fused", action="store_true",
                    help="skip the grouped-MoE + batched-prefill fused-tick "
                         "engine target")
    ap.add_argument("--no-spec", action="store_true",
                    help="skip the speculative-decoding (self-draft) engine "
                         "target")
    ap.add_argument("--no-ep", action="store_true",
                    help="skip the expert-parallel serving-mesh engine targets")
    ap.add_argument("--ep-only", action="store_true",
                    help="run only the EP targets (used by the self-re-exec "
                         "under forced fake devices; skips lint/rebind)")
    args = ap.parse_args(argv)

    report = Report()
    engine_passes = tuple(p for p in ("contract", "donation", "graph")
                          if p not in args.skip)
    if not args.ep_only:
        if "lint" not in args.skip:
            report.extend(lint_tree(_pkg_root()))
        if "rebind" not in args.skip:
            analyze_rebinds(report, donated_call_sites())
        if engine_passes:
            for arch in args.arch:
                analyze_arch(arch, report, reduced=not args.full,
                             passes=engine_passes)
            if not args.no_fused:
                # the fused-tick configuration the PR 8 work is measured
                # against: grouped (dropless) expert dispatch + single
                # batched prefill call
                analyze_arch("nlg-350m-moe128", report, reduced=not args.full,
                             passes=engine_passes, moe_impl="grouped",
                             prefill_mode="batched", tag="+fused")
            if not args.no_spec:
                # speculative decoding with the self-draft oracle; gemma3's
                # window-ring mix also registers the committed-recurrent-state
                # pass (spec_commit), the widest spec jit family
                analyze_arch("gemma3-27b", report, reduced=not args.full,
                             passes=engine_passes, prefill_mode="batched",
                             tag="+spec", spec=True)
    ep_rc = 0
    if engine_passes and not args.no_ep:
        if jax.device_count() >= _EP_DEVICES:
            analyze_ep(report, reduced=not args.full, passes=engine_passes)
        elif args.ep_only:
            report.add("ep-devices", "error", "ep",
                       f"--ep-only needs >= {_EP_DEVICES} devices, have "
                       f"{jax.device_count()} (set XLA_FLAGS="
                       f"--xla_force_host_platform_device_count={_EP_DEVICES})")
        else:
            ep_rc = _reexec_ep(args)
    print(report.render(show_suppressed=args.show_suppressed))
    failed = report.failed(strict=args.strict)
    print("analyze:", "FAIL" if failed else "OK")
    return 1 if (failed or ep_rc) else 0


if __name__ == "__main__":
    sys.exit(main())
