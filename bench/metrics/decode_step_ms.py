"""Model step: device time of one run of the decode program, from the
trace (its executions' total over their count, averaged over chips)."""
from bench.layers import program_ms


def read(ctx):
    return program_ms(ctx, "decode")
