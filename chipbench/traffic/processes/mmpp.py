"""A two-state Markov-modulated Poisson process.

The low state runs at ``2 * rate_hz / (1 + burst_factor)`` and the high
state at ``burst_factor`` times that, so that equal time in each gives
``rate_hz``.  Each state's dwell times are the quantiles of an exponential
with mean ``dwell_s``, scaled to fill half the window and shuffled by the
seed; the states alternate, low first.  The ``round(rate_hz * seconds)``
arrivals are shared out over the segments in proportion to their expected
counts, and placed uniformly within each."""

import numpy as np

from chipbench.traffic.generate import apportion

PARAMS = ("burst_factor", "dwell_s")


def arrivals(params: dict, seconds: float, rng) -> np.ndarray:
    rate = float(params["rate_hz"])
    factor, dwell = float(params["burst_factor"]), float(params["dwell_s"])
    low = 2.0 * rate / (1.0 + factor)
    n_seg = max(1, int(round(seconds / (2.0 * dwell))))
    q = -np.log(1.0 - (np.arange(n_seg) + 0.5) / n_seg)
    q *= (seconds / 2.0) / q.sum()
    dwell_low, dwell_high = rng.permutation(q), rng.permutation(q)
    edges, rates = [0.0], []
    for lo, hi in zip(dwell_low, dwell_high):
        edges += [edges[-1] + lo, edges[-1] + lo + hi]
        rates += [low, low * factor]
    edges = np.minimum(np.asarray(edges), seconds)
    counts = apportion(np.diff(edges) * np.asarray(rates),
                       int(round(rate * seconds)))
    out = [rng.uniform(a, b, n) for a, b, n in
           zip(edges[:-1], edges[1:], counts)]
    return np.sort(np.concatenate(out))
