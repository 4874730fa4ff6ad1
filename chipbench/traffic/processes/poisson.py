"""``round(rate_hz * seconds)`` arrivals placed uniformly at random over the
window and sorted: a Poisson process given its count."""

import numpy as np

PARAMS = ()


def arrivals(params: dict, seconds: float, rng) -> np.ndarray:
    n = int(round(float(params["rate_hz"]) * seconds))
    return np.sort(rng.uniform(0.0, seconds, n))
