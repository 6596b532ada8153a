"""Open loop, Poisson arrivals at ``rate`` requests per second.  Enough
requests are drawn to cover the window a quarter over; those due after it
closes are never sent."""
import math

import numpy as np

drains_fail = False


def count(params: dict, seconds: float) -> int:
    return int(math.ceil(params["rate"] * seconds * 1.25)) + 8


def gaps(params: dict, n: int, rng) -> np.ndarray:
    return rng.exponential(1.0 / params["rate"], size=n)
