"""MoE dispatch and expert MLP: device time of the grouped expert kernel
per decode step (its events inside the decode program, averaged over
chips).  No roofline yet: the bytes a step needs are those of the experts
actually routed to, which the program does not count."""
from bench.layers import kernel_ms_per_run


def read(ctx):
    return kernel_ms_per_run(ctx, "decode", "grouped_mlp")
