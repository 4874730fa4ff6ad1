"""Events per second: the rows of every request completed within the
window (a request of batch ``b`` carries ``b`` sensor events), divided by
the window."""

import numpy as np


def read(run):
    done = run.ok() & (run.records.done <= run.window_end)
    return float(np.sum(done) * run.batch / run.seconds)
