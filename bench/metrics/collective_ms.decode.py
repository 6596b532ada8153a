"""EP exchange: device time of the collective operations per decode step,
the union of their intervals on a chip over the traced decode runs, divided
by those runs, then the mean over chips (bench/collectives.py)."""
from bench.collectives import collective_ms


def read(ctx):
    return collective_ms(ctx, "decode")
