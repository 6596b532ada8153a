"""Engine host tick: the device's idle time inside a decode tick.  For each
traced ``engine.tick`` span that holds an ``engine.decode``, the time inside
it in which no operation ran on a chip; the mean over those ticks, then
over chips (bench/spans.py).  It holds two kinds of idle: while the host
runs its phases (host work the device waits for), and inside
``engine.wait``, where the device has finished and the host is blocked on
the readback (readback latency, not host work)."""
from bench import spans


def read(ctx):
    ticks = spans.decode_ticks(ctx.trace)
    devs = ctx.trace["devices"]
    if not ticks or not devs:
        return None
    per_chip = []
    for _, dev in sorted(devs.items()):
        busy = spans.busy_intervals(dev)
        per_chip.append(sum(spans.idle_ns(busy, s, e) for s, e in ticks) / len(ticks))
    return sum(per_chip) / len(per_chip) / 1e6
