"""Graph auditor: per-equation jaxpr walks over traced serving graphs.

``launch/hlo_account.py`` totals what a compiled graph *costs* (flops,
HBM traffic, collective bytes).  This pass audits what a traced graph
*contains* — the three structural defects the fused-tick / dropless-MoE
work will be measured against:

  * ``stray-collective``   — a communication primitive (psum, all_gather,
                             all_to_all, ppermute, ...) inside a graph the
                             engine declared single-device.  On one chip a
                             collective lowers to a copy at best; at worst it
                             means an ``out_shardings``/``shard_map`` leak
                             into the serving tick.
  * ``dtype-drift``        — ``convert_element_type`` from a quantized
                             integer dtype (int8 / int4) straight to float32
                             on a large buffer: the dequantize materializes a
                             4x-8x f32 copy of the weight/KV block instead of
                             staying in bf16 or fusing the scale into the
                             consuming dot.  (int32 position/index math is
                             exempt — only sub-byte and 8-bit sources count.)
  * ``capacity-padding``   — dead compute from capacity-factor gating: every
                             expert MLP dot runs over the full
                             ``[num_experts, capacity, d]`` dispatch buffer,
                             including slots gating left empty or dropped.
                             Reported as **info** with the analytic padded
                             fraction (1 - routed / (E*C)) cross-checked
                             against the actual leading-``num_experts`` dot
                             equations found in the graph.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Iterable, Iterator, List, Optional, Sequence, Tuple

import jax
from jax.extend import core as jcore

from repro.analysis.findings import Report

# primitive names of cross-device communication in jax's lax.parallel
COLLECTIVE_PRIMS = {
    "psum", "pmax", "pmin", "ppermute", "pshuffle", "all_gather",
    "all_to_all", "psum_scatter", "reduce_scatter", "pgather", "pbroadcast",
}

# dequantize sources: sub-byte + 8-bit integer storage dtypes
_QUANT_SRC = {"int8", "uint8", "int4", "uint4"}


def _subjaxprs(params: Dict[str, Any]) -> Iterator[Any]:
    """Every Jaxpr/ClosedJaxpr reachable through an eqn's params (pjit's
    ``jaxpr``, scan/while bodies, cond ``branches``, custom_jvp calls...)."""
    for v in params.values():
        vals = v if isinstance(v, (tuple, list)) else (v,)
        for x in vals:
            if isinstance(x, jcore.ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, jcore.Jaxpr):
                yield x


def iter_eqns(jaxpr) -> Iterator[Any]:
    """All equations in a (closed) jaxpr, recursing through call/control-flow
    sub-jaxprs."""
    if isinstance(jaxpr, jcore.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _subjaxprs(eqn.params):
            yield from iter_eqns(sub)


def _aval_of(var) -> Optional[Any]:
    return getattr(var, "aval", None)


def audit_collectives(jaxpr, name: str, report: Optional[Report] = None, *,
                      allowed: Sequence[str] = ()) -> Report:
    """Flag communication primitives in a graph declared single-device."""
    report = report if report is not None else Report()
    seen: Dict[str, int] = {}
    for eqn in iter_eqns(jaxpr):
        p = eqn.primitive.name
        if p in COLLECTIVE_PRIMS and p not in allowed:
            seen[p] = seen.get(p, 0) + 1
    for p, n in sorted(seen.items()):
        report.add(
            "stray-collective", "error", name,
            f"{n}x `{p}` in a single-device serving graph — a sharding or "
            "axis-env leak into the hot path (or an engine that should "
            "declare itself multi-device)",
        )
    report.metrics[f"graph.{name}.collectives"] = sum(seen.values())
    return report


def audit_dtype_drift(jaxpr, name: str, report: Optional[Report] = None, *,
                      min_elements: int = 4096) -> Report:
    """Flag int8/int4 -> f32 converts on large buffers (materialized
    dequantize instead of bf16 / fused-scale)."""
    report = report if report is not None else Report()
    hits = 0
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name != "convert_element_type":
            continue
        src = _aval_of(eqn.invars[0])
        dst = _aval_of(eqn.outvars[0])
        if src is None or dst is None:
            continue
        if str(src.dtype) in _QUANT_SRC and str(dst.dtype) == "float32" \
                and math.prod(src.shape or (1,)) >= min_elements:
            hits += 1
            if hits <= 4:  # one finding per site, capped; total in metrics
                report.add(
                    "dtype-drift", "error", name,
                    f"convert {src.dtype}{list(src.shape)} -> float32: the "
                    "dequantized copy is 4-8x the quantized buffer — keep "
                    "the wide type bf16 or fuse the scale into the consumer",
                )
    report.metrics[f"graph.{name}.quant_f32_upcasts"] = hits
    return report


def capacity_dead_compute(num_tokens: int, num_experts: int, top_k: int,
                          capacity_factor: float) -> Dict[str, float]:
    """Analytic padded-compute fraction of capacity-factor dispatch: the
    dense ``[E, C, d]`` expert buffer runs every slot through the MLP whether
    or not gating filled it."""
    cap = max(1, int(capacity_factor * num_tokens * top_k / num_experts))
    slots = num_experts * cap
    routed = min(num_tokens * top_k, slots)
    return {
        "capacity": cap,
        "slots": slots,
        "routed_upper_bound": routed,
        "padded_fraction": 1.0 - routed / slots,
    }


def audit_dead_compute(jaxpr, name: str, *, num_tokens: int, num_experts: int,
                       top_k: int, capacity_factor: float,
                       impl: str = "einsum",
                       report: Optional[Report] = None) -> Report:
    """Cross-check the analytic padding fraction against the expert dots
    actually present in the graph (operands with leading dim
    ``num_experts``), and report the dead-compute share as info.

    ``impl="grouped"`` (dropless expert-sorted dispatch): the graph carries
    no ``[E, C, d]`` capacity buffer at all — its expert dots run over
    tile-padded sorted rows — so the capacity cross-check would be a FALSE
    finding there.  The audit instead reports the dropless path's analytic
    worst-case tile padding (< one tile per expert) as the info line."""
    report = report if report is not None else Report()
    if num_experts <= 0:
        return report
    if impl == "grouped":
        from repro.core.dispatch_grouped import GROUPED_TILE, grouped_rows

        tk = num_tokens * top_k
        ct = grouped_rows(num_tokens, top_k, num_experts, GROUPED_TILE)
        frac = 1.0 - tk / ct
        report.add(
            "capacity-padding", "info", name,
            f"grouped (dropless) dispatch: no [E, C] capacity buffer in the "
            f"graph; worst-case tile padding is {ct - tk} of {ct} sorted rows "
            f"({frac:.1%}, tile={GROUPED_TILE}), and every routed token is "
            "kept regardless of skew",
        )
        report.metrics[f"graph.{name}.expert_dots"] = 0
        report.metrics[f"graph.{name}.padded_fraction"] = round(frac, 4)
        return report
    stats = capacity_dead_compute(num_tokens, num_experts, top_k, capacity_factor)
    if impl in ("ep", "ep_serve"):
        # expert-parallel dispatch: the expert dots run inside shard_map over
        # per-shard [E_local, C, d] buffers, so a leading-dim == num_experts
        # scan would only catch unrelated batch-leading dots (e.g. attention
        # over num_slots == E).  Report the analytic padding and skip the
        # graph cross-check.
        report.add(
            "capacity-padding", "info", name,
            f"expert-parallel capacity dispatch: per-shard [E_local, "
            f"C={stats['capacity']}] buffers inside shard_map, "
            f">= {stats['padded_fraction']:.1%} capacity padding (analytic); "
            "full-E graph cross-check skipped — E_local-leading dots are "
            "indistinguishable from batch dims",
        )
        report.metrics[f"graph.{name}.expert_dots"] = 0
        report.metrics[f"graph.{name}.padded_fraction"] = round(stats["padded_fraction"], 4)
        return report
    expert_dots = 0
    expert_flops = 0.0
    graph_caps: set = set()
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name != "dot_general":
            continue
        lhs = _aval_of(eqn.invars[0])
        out = _aval_of(eqn.outvars[0])
        if lhs is None or out is None or not lhs.shape:
            continue
        if lhs.shape[0] == num_experts and len(lhs.shape) >= 3:
            expert_dots += 1
            graph_caps.add(int(lhs.shape[1]))
            dims = eqn.params.get("dimension_numbers")
            contract = 1
            if dims:
                for d in dims[0][0]:
                    contract *= lhs.shape[d]
            expert_flops += 2.0 * math.prod(out.shape) * contract
    if expert_dots and graph_caps != {stats["capacity"]}:
        report.add(
            "capacity-mismatch", "error", name,
            f"expert dispatch buffers in the graph use capacity {sorted(graph_caps)} "
            f"but the config's gating arithmetic gives {stats['capacity']} — "
            "the contract and the traced graph disagree",
        )
    if expert_dots:
        report.add(
            "capacity-padding", "info", name,
            f"{expert_dots} expert dot(s) over [E={num_experts}, "
            f"C={stats['capacity']}] buffers: >= {stats['padded_fraction']:.1%} "
            f"of their {expert_flops / 1e6:.1f} MFLOP is capacity padding "
            "(slots gating left empty still run the MLP) — the dropless "
            "baseline number",
        )
    report.metrics[f"graph.{name}.expert_dots"] = expert_dots
    report.metrics[f"graph.{name}.padded_fraction"] = round(stats["padded_fraction"], 4)
    return report


def audit_graph(name: str, fn, args: Sequence, *, single_device: bool = True,
                allowed_collectives: Sequence[str] = (),
                expect_collectives: bool = False,
                moe: Optional[Dict[str, Any]] = None,
                report: Optional[Report] = None) -> Report:
    """Run all graph checks on ``fn`` traced at ``args`` (ShapeDtypeStructs
    are fine — tracing only, no compile).  ``moe`` carries the gating
    arithmetic for the dead-compute pass:
    ``{num_tokens, num_experts, top_k, capacity_factor}``.

    ``single_device=False`` flips the collective check around: instead of
    flagging strays, ``expect_collectives=True`` asserts the graph DOES
    carry communication primitives — an expert-parallel serving graph whose
    all-to-all/all-gather exchange silently traced away (mesh context lost,
    EP impl fell back to a replicated kernel) would otherwise pass every
    other audit while serving single-device math on every rank."""
    report = report if report is not None else Report()
    try:
        closed = jax.make_jaxpr(fn)(*args)
    except Exception as exc:
        report.add("graph-trace-failed", "error", name,
                   f"could not trace for graph audit: {exc!r}".replace("\n", " ")[:300])
        return report
    if single_device:
        audit_collectives(closed, name, report, allowed=allowed_collectives)
    else:
        n_coll = sum(1 for eqn in iter_eqns(closed)
                     if eqn.primitive.name in COLLECTIVE_PRIMS)
        report.metrics[f"graph.{name}.collectives"] = n_coll
        if expect_collectives and n_coll == 0:
            report.add(
                "missing-collective", "error", name,
                "multi-device EP serving graph contains no communication "
                "primitive — the shard_map exchange traced away (lost mesh "
                "context or a silent fallback to a replicated MoE kernel)",
            )
    audit_dtype_drift(closed, name, report)
    if moe:
        audit_dead_compute(closed, name, report=report, **moe)
    return report
