"""Median time (us) a request spends in the router's own code, read from
the program's spans: per completed request, its ``router.admit`` (tenant
lookup, admission, breaker) plus its ``router.account`` (metrics,
supervisor, SLO monitor, replan check), joined on the request id.  Over
the requests that began before the profiler started."""

import numpy as np

from chipbench import spans


def read(run):
    own = spans.per_request(spans.before_profile(run),
                            ("router.admit", "router.account"),
                            lambda s: s.dur_s)
    return float(np.percentile(list(own.values()), 50) * 1e6) if own \
        else None
