"""Attention kernels: the paged decode attention kernel's share of its
roofline.  The least time is that of the FLOPs and the KV bytes of the
positions each decoding row holds (bench/flops.py, from the per-tick
context lengths the harness noted), at the chip's peaks; the time is the
kernel's device time in the decode program."""
from bench.layers import kernel_roofline


def read(ctx):
    return kernel_roofline(ctx, "decode", "paged_decode_attn")
