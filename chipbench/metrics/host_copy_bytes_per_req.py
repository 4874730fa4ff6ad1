"""Bytes copied between host and device per completed request, from the
counters the program's engine spans carry: ``h2d_bytes`` on
``engine.dispatch`` (0 for an input already on the device) plus
``d2h_bytes`` on ``engine.readback``, summed per request id; the mean over
every completed request of the window."""

import numpy as np

from chipbench import spans


def read(run):
    copied = spans.per_request(
        run.spans or [], ("engine.dispatch", "engine.readback"),
        lambda s: s.attrs.get("h2d_bytes", 0) + s.attrs.get("d2h_bytes", 0))
    return float(np.mean(list(copied.values()))) if copied else None
