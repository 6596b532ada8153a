"""EP exchange: the part of the decode step's collective time in which no
other operation runs on the chip, per decode step, the mean over chips
(bench/collectives.py).  Collective time that overlaps compute costs the
step nothing; this is what the exchange adds to it."""
from bench.collectives import collective_ms


def read(ctx):
    return collective_ms(ctx, "decode", exposed=True)
