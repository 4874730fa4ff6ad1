"""Share (%) of the device's idle time in the traced window that
``chipbench.trace.attribute_gaps`` gives to no event inside the serving
call: to the benchmark's own ``router.infer`` annotation as the innermost
event, or to no event at all.  What the program's spans and JAX's own
events leave unexplained."""

import numpy as np

from chipbench import trace

UNATTRIBUTED = ("router.infer", trace.NO_EVENT)


def read(run):
    if run.trace is None or run.trace_window is None \
            or not run.trace.devices:
        return None
    ops = next(iter(run.trace.devices.values()))
    gaps = trace.idle_gaps(ops, *run.trace_window)
    idle = float(np.sum(gaps[:, 1] - gaps[:, 0]))
    if idle <= 0:
        return None
    host = run.trace.serving_thread
    by_event = dict(trace.attribute_gaps(gaps, host, n=len(host) + 1))
    return 100.0 * sum(by_event.get(k, 0.0) for k in UNATTRIBUTED) / idle
