"""Median duration (us) of the program's ``engine.wait`` span: the one
blocking wait of an edge request, ``np.asarray`` on the forward's output
while it is not yet ready.  It asks for the copy to the host and lasts
until the output is there: the launch, the forward on the device and the
device-to-host copy.  Over the spans that began before the profiler
started."""

from chipbench import spans


def read(run):
    return spans.p50_us(run, "engine.wait")
