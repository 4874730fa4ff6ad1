"""Share (%) of the traced window in which no operation ran on the device:
1 - (union of the device's op intervals) / (traced window), averaged over
the chips used."""

import numpy as np

from chipbench import trace


def read(run):
    if run.trace is None or run.trace_window is None \
            or not run.trace.devices:
        return None
    t0, t1 = run.trace_window
    busy = np.mean([trace.busy_s(ops, t0, t1)
                    for ops in run.trace.devices.values()])
    return 100.0 * (1.0 - busy / (t1 - t0))
