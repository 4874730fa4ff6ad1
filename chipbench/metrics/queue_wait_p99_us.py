"""99th-percentile wait (us) between a request's scheduled arrival and the
serving thread's call into ``Router.infer``: the queue in front of the one
serving thread.  Read on the benchmark's clock over the requests issued
before the profiler started."""

import numpy as np


def read(run):
    sel = run.ok() & run.host_part()
    wait = run.records.call[sel] - run.records.due[sel]
    return float(np.percentile(wait, 99) * 1e6) if wait.size else None
