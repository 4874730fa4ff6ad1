"""Share (%) of the kernels' roofline: the least time the chip could take for
the work the profiled requests' forwards needed (``chipbench/work.py``,
counted from the nets' widths), divided by the summed device time of the
``repro_*`` kernel events in the trace (``repro_gemm_int8``,
``repro_fused_mlp_x<n>``).  Nothing when no kernel event was traced."""

import numpy as np

from chipbench import trace, work


def read(run):
    if run.trace is None or run.peak is None or not run.trace.devices:
        return None
    kernel_s = sum(trace.kernel_s(ops) for ops in run.trace.devices.values())
    sel = run.profiled()
    if kernel_s <= 0 or not sel.any():
        return None
    counts = np.bincount(run.tenant[sel], minlength=len(run.work))
    least = sum(int(n) * work.roofline_s(w, run.peak)
                for n, w in zip(counts, run.work))
    return 100.0 * least / kernel_s
