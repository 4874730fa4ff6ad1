"""Median time to first token (ms): from each request's scheduled arrival
to its first output token on the host, over every request the window
completed.  Nothing for a driver that records no first token."""

import numpy as np


def read(run):
    if run.records.first is None:
        return None
    ok = run.ok()
    ttft = run.records.first[ok] - run.records.due[ok]
    return float(np.percentile(ttft, 50) * 1e3) if ttft.size else None
