"""Median duration (us) of the program's ``engine.dispatch`` span: the call
of the jitted edge forward up to its return (argument handling, the
host-to-device copy of a host input, the enqueue).  Over the spans that
began before the profiler started."""

from chipbench import spans


def read(run):
    return spans.p50_us(run, "engine.dispatch")
