"""MoE dispatch and expert MLP: the grouped expert kernel's share of its
roofline in the decode program.  The least time is the larger of the FLOPs
and the bytes the kernel needs at the chip's peaks: each MoE layer reads the
weights of the experts the engine's counter says it routed to
(``experts_routed`` in the tick log), reads the rows and writes their
outputs (bench/spans.py), over the last n decode ticks of the window, n
being the decode runs the trace holds; the time is the kernel's device time
in those runs.  Work is split evenly over the chips, as for the attention
kernel."""
from bench import flops, layers, spans


def read(ctx):
    t, runs = layers.kernel_ns(ctx, "decode", "grouped_mlp")
    n = int(round(runs))
    ticks = [m for m in ctx.ticks if "experts_routed" in m][-n:] if n else []
    if not t or len(ticks) < n:
        return None
    work = spans.grouped_mlp_work(ctx.conf, ticks)
    if work is None or not work[1]:
        return None
    f, b = work
    share, bound = flops.roofline(f / ctx.chips, b / ctx.chips, t / 1e9, ctx.peak)
    ctx.notes.append(f"grouped_mlp_decode_roofline: {share:.2f}% ({bound}-bound; {f:.4g} FLOP, "
                     f"{b:.4g} B over {t / 1e9:.4g} s of kernel time per chip, {n} decode runs)")
    return share
