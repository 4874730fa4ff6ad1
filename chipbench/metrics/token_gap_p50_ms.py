"""Median gap between output tokens (ms): per completed request with two
tokens or more, the time from its first output token on the host to its
last, over its tokens less one; the median over those requests.  Nothing
for a driver that records no first token."""

import numpy as np


def read(run):
    rec = run.records
    if rec.first is None:
        return None
    sel = run.ok() & (rec.tokens >= 2)
    gap = (rec.done[sel] - rec.first[sel]) / (rec.tokens[sel] - 1)
    return float(np.percentile(gap, 50) * 1e3) if gap.size else None
