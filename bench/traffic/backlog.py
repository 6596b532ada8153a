"""Backlog: every request is due at the window's start (``requests`` of
them).  The harness fails a run whose backlog drains inside the window."""
import numpy as np

drains_fail = True


def count(params: dict, seconds: float) -> int:
    return int(params["requests"])


def gaps(params: dict, n: int, rng) -> np.ndarray:
    return np.zeros(n)
