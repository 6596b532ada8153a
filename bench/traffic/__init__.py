"""The one traffic generator: a mix file's parameters in, requests out.

A mix names an arrival kind (``arrivals.kind`` — the module
``traffic/<kind>.py``, which gives the count of requests and the gaps
between them), a prompt-length and an output-length distribution.  An
open-loop mix may start its arrivals ``arrivals.ramp_s`` seconds before
the window, so that the window finds the engine as the traffic keeps it
and not empty; the harness serves those arrivals before the window and
counts their seconds apart from the set-up.

Runs should differ in their tokens, not in their work.  So the prompt
lengths, output lengths and inter-arrival gaps are drawn once, in order,
from a fixed seed, and the run's ``--seed`` draws only the token ids (and
the weights): every seed offers requests of the same sizes at the same
times.  A request's size decides when it finishes and so how many
admissions (each a whole batched prefill tick) a window holds: sizes drawn
from the run's seed would change the work from seed to seed.
"""
from __future__ import annotations

import importlib
import math
from dataclasses import dataclass

import numpy as np

SIZES_SEED = 0  # the draw of every mix's sizes and gaps, the same in every run


@dataclass
class Arrival:
    due_s: float  # offset from the window's start (negative: during the ramp)
    prompt: np.ndarray  # int32 token ids
    max_new_tokens: int


def lengths(dist: dict, n: int, rng) -> np.ndarray:
    lo, hi = int(dist["min"]), int(dist["max"])
    if dist["dist"] == "lognormal":
        x = np.exp(rng.normal(math.log(dist["median"]), dist["sigma"], size=n))
        return np.clip(np.rint(x), lo, hi).astype(np.int64)
    if dist["dist"] == "uniform":
        return rng.integers(lo, hi + 1, size=n)
    raise ValueError(f"unknown length distribution {dist['dist']!r}")


def arrival_kind(name: str):
    return importlib.import_module(f"bench.traffic.{name}")


def generate(mix: dict, seed: int, seconds: float, vocab: int) -> list[Arrival]:
    """The requests a run offers, in order of due time."""
    kind = arrival_kind(mix["arrivals"]["kind"])
    ramp = float(mix["arrivals"].get("ramp_s", 0.0))
    n = kind.count(mix["arrivals"], seconds + ramp)
    sizes = np.random.default_rng(SIZES_SEED)
    p_len = lengths(mix["prompt"], n, sizes)
    o_len = lengths(mix["output"], n, sizes)
    gaps = kind.gaps(mix["arrivals"], n, sizes)
    due = np.cumsum(gaps) - ramp
    rng = np.random.default_rng(seed)
    return [Arrival(float(due[i]), rng.integers(0, vocab, size=int(p_len[i]), dtype=np.int32),
                    int(o_len[i])) for i in range(n)]
