"""Engine host tick: the host's time in a decode tick, outside its waits on
the device.  For each traced ``engine.tick`` span that holds an
``engine.decode``, its length less the union of the ``engine.wait`` spans
inside it; the mean over those ticks (bench/spans.py)."""
from bench import spans


def read(ctx):
    ticks = spans.decode_ticks(ctx.trace)
    if not ticks:
        return None
    return sum(spans.host_ns(ctx.trace, t) for t in ticks) / len(ticks) / 1e6
