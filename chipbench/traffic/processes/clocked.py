"""One request every ``1 / rate_hz`` seconds (a sensor clock)."""

import math

import numpy as np

PARAMS = ()


def arrivals(params: dict, seconds: float, rng) -> np.ndarray:
    rate = float(params["rate_hz"])
    return np.arange(int(math.floor(rate * seconds))) / rate
