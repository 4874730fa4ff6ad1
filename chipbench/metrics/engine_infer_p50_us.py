"""Median duration (us) of the program's ``infer`` span: ``EdgeEngine.infer``
from entry to a checked output on the host (dispatch, host-to-device copy,
the forward, device-to-host copy, finiteness check).  Over the spans that
began before the profiler started."""

import numpy as np


def read(run):
    start, end = run.spans_named("infer")
    first = run.records.first_profiled
    if first < len(run.records.call):
        keep = start < run.records.call[first]
        start, end = start[keep], end[keep]
    return float(np.percentile(end - start, 50) * 1e6) if start.size \
        else None
