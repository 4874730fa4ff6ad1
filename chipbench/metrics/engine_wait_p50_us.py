"""Median duration (us) of the program's ``engine.wait`` span:
``jax.block_until_ready`` on the forward's output, i.e. the launch latency
plus the forward on the device.  Over the spans that began before the
profiler started."""

from chipbench import spans


def read(run):
    return spans.p50_us(run, "engine.wait")
