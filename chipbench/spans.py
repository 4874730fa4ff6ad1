"""The program's own spans, as the per-layer metric readers read them.

An enabled ``repro.obs.Tracer`` records each span in memory on
``perf_counter`` and, while the profiler runs, as a ``TraceAnnotation`` of
the same name.  The edge path's spans of one request carry one request id
(``Span.trace_id``): ``request`` holds ``router.admit``, ``infer`` (with
``engine.dispatch``, ``engine.wait`` and ``engine.readback`` inside) and
``router.account``.  The router books only completed requests in
``router.account``, so its ids are the completed requests.  A program
without these spans gives every reader nothing to read."""

from __future__ import annotations

import numpy as np

COMPLETED = "router.account"


def before_profile(run):
    """The program's spans that began before the profiler started (all of
    them when it never did)."""
    first = run.records.first_profiled
    cut = (run.records.call[first] if first < len(run.records.call)
           else np.inf)
    return [s for s in (run.spans or ()) if s.t0_s < cut]


def p50_us(run, name: str):
    """Median duration (us) of the spans ``name`` that began before the
    profiler; None when there are none."""
    durs = [s.dur_s for s in before_profile(run) if s.name == name]
    return float(np.percentile(durs, 50) * 1e6) if durs else None


def per_request(spans, names, value):
    """``{request id: sum of value(span)}`` over the spans named in
    ``names`` of the requests that completed."""
    done = {s.trace_id for s in spans if s.name == COMPLETED}
    out = dict.fromkeys(done, 0.0)
    for s in spans:
        if s.name in names and s.trace_id in out:
            out[s.trace_id] += value(s)
    return out
