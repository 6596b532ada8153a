"""Device, whole step: the model FLOPs the traced decode steps needed (2 x
the weights each row multiplies through, the logits, and attention over
the context each row holds; bench/flops.py) over the decode programs'
device time x chips x the chip's bf16 peak."""
from bench.layers import step_mfu


def read(ctx):
    return step_mfu(ctx, "decode")
