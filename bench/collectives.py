"""What the readers of the expert-parallel exchange share: the collective
operations each chip ran inside a traced program, and the tick log's
``ep_rows`` counter over the traced decode ticks.

A TPU trace's operation events carry the HLO instruction's name and no
``op_name`` (so not the ``ep_exchange`` scope the program puts on its MoE
exchange).  Collectives are found by their instruction names, which XLA
takes from the opcode (``all-reduce``, ``all-gather``, ``all-to-all``,
``collective-permute``, ``reduce-scatter`` and their ``-start``/``-done``
halves) or from the JAX primitive that made them (``psum.8``).  That counts
every collective of the program: the MoE layer's exchange of expert
outputs, and the gathers that move the slot-sharded attention's rows and
new K/V onto every chip.  A program without collectives (one chip) gives
nothing, and the readers then return nothing.
"""
from __future__ import annotations

import re

from bench import layers, spans, trace

COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|all-to-all|collective-permute|reduce-scatter"
    r"|psum|pmean|psum_scatter|all_gather|all_to_all|ppermute)(-start|-done)?(\.|$)")


def is_collective(name: str) -> bool:
    return bool(COLLECTIVE.match(name))


def collective_ms(ctx, program: str, exposed: bool = False):
    """Device time of the collectives per run of ``program`` (the union of
    their intervals on a chip over the program's runs there), the mean over
    chips; with ``exposed``, only the part of it in which no other operation
    ran on that chip.  ``None`` when no chip ran a collective in the
    program."""
    vals, found = [], False
    for dev, runs in layers.per_device_runs(ctx, program):
        if not runs:
            continue
        coll = [(s, e) for n, s, e in trace.ops_within(dev, runs) if is_collective(n)]
        found = found or bool(coll)
        if exposed:
            busy = spans.busy_intervals({"ops": [o for o in dev["ops"] if not is_collective(o[0])]})
            t = sum(spans.idle_ns(busy, s, e) for s, e in trace.merged(coll))
        else:
            t = trace.union_ns(coll)
        vals.append(t / len(runs) / 1e6)
    return sum(vals) / len(vals) if found else None


def traced_decode_ticks(ctx, key: str) -> list:
    """The tick log's last n decode entries that carry ``key``, n being the
    decode runs the trace holds (the mean over chips); empty when the trace
    holds none or the log has fewer."""
    runs = [len(r) for _, r in layers.per_device_runs(ctx, "decode")]
    n = int(round(sum(runs) / len(runs))) if runs else 0
    ticks = [m for m in ctx.ticks if key in m][-n:] if n else []
    return ticks if len(ticks) == n else []
