"""What the per-layer readers share: the traced runs of each program on each
chip, the harness's record of the calls made while the trace ran, and the
sums of kernel time, FLOPs and bytes over them.

``ctx`` (built by ``run.py`` for a traced run) carries ``conf``, ``peak``,
``chips``, ``trace`` (the extract), ``calls`` (the harness's log of the
engine's decode and prefill calls), ``trace_t0``/``trace_t1`` (host clock
of the traced span), ``busy_s``, ``window_s``, ``rec``, ``ticks`` and
``notes`` (lines the run prints on stderr).
"""
from __future__ import annotations

from bench import flops, trace


def per_device_runs(ctx, program: str) -> list:
    """``[(device extract, [program run events])]`` for each chip."""
    return [(dev, trace.program_events(dev, program)) for _, dev in sorted(ctx.trace["devices"].items())]


def traced_calls(ctx, program: str) -> list:
    """The harness's records of the calls into ``program`` made while the
    profiler ran.  Every tick is fenced, so these are exactly the runs the
    trace holds; a mismatch is noted."""
    calls = [c for c in ctx.calls[program] if ctx.trace_t0 <= c["t"] < ctx.trace_t1]
    for i, (dev, runs) in enumerate(per_device_runs(ctx, program)):
        if len(runs) != len(calls):
            ctx.notes.append(f"trace: chip {i} holds {len(runs)} {program} runs, "
                             f"the harness made {len(calls)} calls while tracing")
    return calls


def program_ms(ctx, program: str):
    vals = [sum(e - s for _, s, e in runs) / len(runs) / 1e6
            for _, runs in per_device_runs(ctx, program) if runs]
    return sum(vals) / len(vals) if vals else None


def kernel_ns(ctx, program: str, kernel: str) -> tuple:
    """(mean over chips of the kernel's total device time inside the
    program's runs, mean number of runs), or (0, 0)."""
    tot, n, chips = 0, 0, 0
    for dev, runs in per_device_runs(ctx, program):
        if not runs:
            continue
        ops = [o for o in trace.ops_within(dev, runs) if trace.is_kernel(o, kernel)]
        tot += sum(e - s for _, s, e in ops)
        n += len(runs)
        chips += 1
    return (tot / chips, n / chips) if chips else (0, 0)


def kernel_ms_per_run(ctx, program: str, kernel: str):
    t, n = kernel_ns(ctx, program, kernel)
    if not t:
        ctx.notes.append(f"trace: no {kernel} kernel found in the {program} program")
        return None
    return t / n / 1e6


def work(ctx, program: str) -> dict:
    """FLOPs and bytes summed over the traced calls of ``program``."""
    tot = {"model_flops": 0, "attn_flops": 0, "attn_bytes": 0}
    for c in traced_calls(ctx, program):
        w = (flops.decode_step(ctx.conf, c["lengths"]) if program == "decode"
             else flops.prefill_call(ctx.conf, c["rows"]))
        for k in tot:
            tot[k] += w[k]
    return tot


def kernel_roofline(ctx, program: str, kernel: str):
    """Share of the roofline of ``kernel`` over the traced calls: the work
    is split evenly over the chips (slots over the mesh), the time is the
    mean chip's."""
    t, _ = kernel_ns(ctx, program, kernel)
    w = work(ctx, program)
    if not t or not w["attn_flops"]:
        if not t:
            ctx.notes.append(f"trace: no {kernel} kernel found in the {program} program")
        return None
    share, bound = flops.roofline(w["attn_flops"] / ctx.chips, w["attn_bytes"] / ctx.chips,
                                  t / 1e9, ctx.peak)
    ctx.notes.append(f"{kernel}_roofline: {share:.2f}% ({bound}-bound; {w['attn_flops']:.4g} FLOP, "
                     f"{w['attn_bytes']:.4g} B over {t / 1e9:.4g} s of kernel time per chip)")
    return share


def step_mfu(ctx, program: str):
    runs = [sum(e - s for _, s, e in r) for _, r in per_device_runs(ctx, program) if r]
    w = work(ctx, program)
    if not runs or not w["model_flops"]:
        return None
    t = sum(runs) / len(runs) / 1e9
    return 100.0 * w["model_flops"] / (t * ctx.chips * ctx.peak["bf16_flop_s"])
