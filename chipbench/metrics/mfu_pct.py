"""Share (%) of the chip's int8 peak that the whole step's useful work
reached: the operations of the requests completed under the profiler (each
request's ``work.Work`` from its configuration's kind; for the edge nets
2 * batch * MACs), per second of the traced window, over the int8 peak in
``chipbench/peaks.json``."""

import numpy as np


def read(run):
    if run.trace_window is None or run.peak is None:
        return None
    sel = np.flatnonzero(run.profiled())
    if not sel.size:
        return None
    ops = sum(run.work[i].ops for i in sel)
    t0, t1 = run.trace_window
    return 100.0 * ops / (t1 - t0) / run.peak["int8_ops_per_s"]
