"""EP exchange: device time of the collective operations per batched
prefill tick (its program's runs), the mean over chips
(bench/collectives.py).  Every decoding row waits through a prefill tick,
so this part of it lands in the inter-token gaps' tail."""
from bench.collectives import collective_ms


def read(ctx):
    return collective_ms(ctx, "prefill")
