"""99th-percentile request latency (us): from each request's scheduled
arrival to its output on the host, over the requests completed before the
profiler started.  The machine's host pauses for about a tenth of a second
now and then, and the requests queued behind one pause set this tail, so it
is read per layer and not held to a bound."""

import numpy as np


def read(run):
    sel = run.ok() & run.host_part()
    lat = run.records.done[sel] - run.records.due[sel]
    return float(np.percentile(lat, 99) * 1e6) if lat.size else None
