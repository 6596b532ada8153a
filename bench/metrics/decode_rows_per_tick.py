"""Scheduler: decoding rows per decode tick, the mean over the window's
ticks that decoded (the engine's ``tokens_this_tick`` counter)."""


def read(ctx):
    rows = [m["tokens_this_tick"] for m in ctx.ticks if m.get("tokens_this_tick")]
    return sum(rows) / len(rows) if rows else None
