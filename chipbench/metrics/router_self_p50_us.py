"""Median time (us) a request spends in the router and the layers between
it and the engine (admission, breaker, supervisor, SLO monitor): the
benchmark's span around ``Router.infer`` minus the program's ``infer``
spans inside it.  Over the requests issued before the profiler started."""

import numpy as np


def read(run):
    start, end = run.spans_named("infer")
    if not start.size:
        return None
    order = np.argsort(start)
    start, end = start[order], end[order]
    cum = np.concatenate([[0.0], np.cumsum(end - start)])
    sel = run.ok() & run.host_part()
    call, ret = run.records.call[sel], run.records.ret[sel]
    lo = np.searchsorted(start, call, "left")
    hi = np.searchsorted(start, ret, "right")
    own = (ret - call) - (cum[hi] - cum[lo])
    return float(np.percentile(own, 50) * 1e6) if own.size else None
