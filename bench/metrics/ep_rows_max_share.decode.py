"""MoE dispatch and expert MLP: how unevenly a decode step's rows spread
over the chips of the EP mesh.  From the engine's ``ep_rows`` counter (per
decode tick and MoE layer, the rows each chip's experts received), the
busiest chip's share of the layer's rows in %, the mean over the traced
decode ticks and the MoE layers.  100 / chips is balanced; the step waits
for the busiest chip's experts.  A program without the counter (one chip,
or a program that does not count) gives nothing."""
from bench.collectives import traced_decode_ticks


def read(ctx):
    shares = [100.0 * max(rows) / sum(rows)
              for m in traced_decode_ticks(ctx, "ep_rows") for rows in m["ep_rows"] if sum(rows)]
    return sum(shares) / len(shares) if shares else None
