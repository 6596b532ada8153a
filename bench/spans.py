"""What the readers of the engine's host tick share: the program's own spans
in a traced run's extract, and the work its routed-expert counter implies.

The engine writes each tick and its phases as profiler annotations
(``engine.tick`` around ``engine.prefill``, ``engine.pages``,
``engine.decode``, ``engine.sample``, ``engine.commit``, ``engine.record``,
with ``engine.wait`` wherever the host blocks on the device).  They land on
the host line of the trace, on the clock the device's operations are
stamped with, so host phases and device gaps can be laid side by side.
Each decode tick's entry in the engine's tick log carries
``experts_routed``: per MoE layer, the experts the step's dispatch gave at
least one row.  A program that writes neither gives empty lists here, and
the readers then return nothing.
"""
from __future__ import annotations

import numpy as np

from bench import trace
from bench.catalog import expand_layers

TICK, DECODE, WAIT = "engine.tick", "engine.decode", "engine.wait"
DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
ACT_BYTES = 2  # a served activation (bfloat16)


def named(tr: dict, name: str) -> list:
    """``[(start_ns, end_ns)]`` of the host events called ``name``, in order."""
    return sorted((s, e) for n, s, e in tr["host"] if n == name)


def inside(spans, outer) -> list:
    """The spans that lie within ``outer = (start, end)``."""
    s0, e0 = outer
    return [(s, e) for s, e in spans if s0 <= s and e <= e0]


def decode_ticks(tr: dict) -> list:
    """``[(start_ns, end_ns)]`` of each traced ``engine.tick`` that holds an
    ``engine.decode``."""
    decodes = named(tr, DECODE)
    return [t for t in named(tr, TICK) if inside(decodes, t)]


def host_ns(tr: dict, tick) -> int:
    """The tick's host time: its length less the union of the
    ``engine.wait`` spans inside it (the host blocked on the device)."""
    return (tick[1] - tick[0]) - trace.union_ns(inside(named(tr, WAIT), tick))


def busy_intervals(dev: dict) -> tuple:
    """The device's busy time as sorted, disjoint ``(starts, ends)`` arrays."""
    iv = trace.merged((s, e) for _, s, e in dev["ops"])
    return (np.array([s for s, _ in iv], np.int64), np.array([e for _, e in iv], np.int64))


def idle_ns(busy: tuple, start: int, end: int) -> int:
    """Time in ``[start, end)`` in which no operation ran on the device."""
    starts, ends = busy
    lo = int(np.searchsorted(ends, start, side="right"))
    hi = int(np.searchsorted(starts, end, side="left"))
    covered = np.minimum(ends[lo:hi], end) - np.maximum(starts[lo:hi], start)
    return (end - start) - int(covered.clip(min=0).sum())


def grouped_mlp_work(conf: dict, ticks) -> tuple:
    """(FLOPs, bytes) the grouped expert MLP needs over decode ``ticks``
    (tick-log entries with ``experts_routed``): in each MoE layer it reads
    the weights (in and out, and the gate when gated, at the served dtype)
    of every expert routed to, reads each routed row and writes its output,
    and multiplies each row through one expert per top-k choice.  ``None``
    when the counter does not have one entry per MoE layer."""
    moe = [ls for ls in expand_layers(conf) if ls["ffn"] == "moe"]
    w_bytes = DTYPE_BYTES[conf["param_dtype"]]
    d = conf["d_model"]
    weights = [d * ls["d_ff"] * (3 if ls["act"] == "swiglu" else 2) for ls in moe]
    flops = nbytes = 0
    for m in ticks:
        routed = m["experts_routed"]
        if len(routed) != len(moe):
            return None
        rows = m["tokens_this_tick"]
        for ls, w, n in zip(moe, weights, routed):
            flops += 2 * rows * ls["top_k"] * w
            nbytes += n * w * w_bytes + rows * ls["top_k"] * 2 * d * ACT_BYTES
    return flops, nbytes
