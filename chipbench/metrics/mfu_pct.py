"""Share (%) of the chip's int8 peak that the whole step's useful work
reached: the operations of the requests completed under the profiler
(2 * batch * MACs each, from the nets' widths), per second of the traced
window, over the int8 peak in ``chipbench/peaks.json``."""

import numpy as np


def read(run):
    if run.trace_window is None or run.peak is None:
        return None
    sel = run.profiled()
    if not sel.any():
        return None
    counts = np.bincount(run.tenant[sel], minlength=len(run.work))
    ops = sum(int(n) * w.ops for n, w in zip(counts, run.work))
    t0, t1 = run.trace_window
    return 100.0 * ops / (t1 - t0) / run.peak["int8_ops_per_s"]
