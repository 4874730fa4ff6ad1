"""Output tokens per second: the tokens of every request completed within
the window, divided by the window.  Nothing for a driver that records no
first token."""

import numpy as np


def read(run):
    rec = run.records
    if rec.first is None:
        return None
    done = run.ok() & (rec.done <= run.window_end)
    return float(np.sum(rec.tokens[done]) / run.seconds)
