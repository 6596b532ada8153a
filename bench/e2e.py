"""End-to-end metrics of a window, from the host clock alone.

Every number is taken over all the work and all the time of the window: a
rate over the whole window, a mean gap over every gap, a tail over every
request or every gap.  Nothing is built from medians of chunks or from
per-request medians.
"""
from __future__ import annotations

import numpy as np


def in_window(rec, times) -> list:
    return [t for t in times if t >= rec.t0]


def gaps_s(rec) -> list:
    """Every gap between two tokens of a request that both came inside the
    window."""
    out = []
    for times in rec.tokens.values():
        out.extend(np.diff(in_window(rec, times)).tolist())
    return out


def output_tok_s(rec) -> float:
    return sum(len(in_window(rec, t)) for t in rec.tokens.values()) / rec.seconds


def tpot_ms(rec) -> float | None:
    """Sum over requests of (last token - first token) over the number of
    gaps: the mean time per output token over every gap, stalls included."""
    g = gaps_s(rec)
    return 1e3 * float(np.sum(g)) / len(g) if g else None


def itl_p95_ms(rec) -> float | None:
    g = gaps_s(rec)
    return 1e3 * float(np.percentile(g, 95)) if g else None


def ttft_s(rec) -> list:
    """First-token time minus due time, for every request whose first token
    came inside the window."""
    return [times[0] - rec.due[rid] for rid, times in rec.tokens.items()
            if times and times[0] >= rec.t0]


def ttft_p95_ms(rec) -> float | None:
    t = ttft_s(rec)
    return 1e3 * float(np.percentile(t, 95)) if t else None


END_TO_END = {"output_tok_s": output_tok_s, "tpot_ms": tpot_ms, "itl_p95_ms": itl_p95_ms,
              "ttft_p95_ms": ttft_p95_ms}
