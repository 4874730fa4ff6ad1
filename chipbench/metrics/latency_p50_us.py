"""Median request latency (us): from each request's scheduled arrival to its
output on the host, over every request the window completed."""

import numpy as np


def read(run):
    ok = run.ok()
    lat = run.records.done[ok] - run.records.due[ok]
    return float(np.percentile(lat, 50) * 1e6) if lat.size else None
