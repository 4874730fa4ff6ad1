"""Seconds from process start to the window's start: imports, the device
check, building and compiling (or loading from the compile cache) the
cell's nets, inputs and warm-up."""


def read(run):
    return run.setup_s
