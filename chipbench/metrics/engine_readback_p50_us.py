"""Median duration (us) of the program's ``engine.readback`` span: the
device-to-host copy of the output (``np.asarray``) and the finiteness
check.  Over the spans that began before the profiler started."""

from chipbench import spans


def read(run):
    return spans.p50_us(run, "engine.readback")
