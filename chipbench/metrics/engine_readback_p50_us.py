"""Median duration (us) of the program's ``engine.readback`` span: the
finiteness check on the output's host copy, which ``engine.wait`` has
already brought to the host.  Over the spans that began before the
profiler started."""

from chipbench import spans


def read(run):
    return spans.p50_us(run, "engine.readback")
