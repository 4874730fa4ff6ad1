"""Share (%) of the kernels' roofline: the least time the chip could take for
the work the profiled requests' forwards needed (each request's
``work.Work`` from its configuration's kind, counted from the nets' widths
by ``chipbench/work.py``), divided by the summed device time of the
``repro_*`` kernel events in the trace (``repro_gemm_int8``,
``repro_fused_mlp_x<n>``).  Nothing when no kernel event was traced."""

import numpy as np

from chipbench import trace, work


def read(run):
    if run.trace is None or run.peak is None or not run.trace.devices:
        return None
    kernel_s = sum(trace.kernel_s(ops) for ops in run.trace.devices.values())
    sel = np.flatnonzero(run.profiled())
    if kernel_s <= 0 or not sel.size:
        return None
    least = sum(work.roofline_s(run.work[i], run.peak) for i in sel)
    return 100.0 * least / kernel_s
