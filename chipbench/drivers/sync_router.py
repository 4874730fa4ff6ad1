"""Open-loop driver for the synchronous edge entry, ``Router.infer``.

One thread plays the schedule: it spins on ``perf_counter`` until a request
is due (no sleep: a sleep wakes tens of microseconds late), calls
``Router.infer(net_id, x)`` with the request's own input batch, and takes the
output to the host (``np.asarray``).  ``Router.infer`` blocks, so a request
that falls due while an earlier one is in flight waits, and that wait is part
of its latency: each request is timed from its scheduled arrival, not from
when the driver reached it.

The driver stops issuing once the window has closed; requests still due are
left unserved and counted.  With ``profile`` set it starts the profiler
before the first request it reaches ``profile.start_s`` or more into the
window (by the clock, so a backlog does not delay it) and wraps every wait
and call in a ``jax.profiler.TraceAnnotation``, so that the trace shows
what the host was doing while the device sat idle.
"""

from __future__ import annotations

import time

import numpy as np

from chipbench.records import FAILED, OK, UNSERVED, Records
from repro.serve.router import TenantOverBudget


def run(router, schedule, pools, *, window_start: float, seconds: float,
        sample_slots: dict, profile=None) -> Records:
    """Play ``schedule`` from ``window_start`` (a ``perf_counter`` time).

    ``sample_slots[t][k]`` says where tenant ``t``'s ``k``-th completed
    request is kept for the correctness check (``-1``: not kept), so that
    the kept outputs are a reservoir sample drawn from the seed.
    """
    n = len(schedule)
    due = window_start + schedule.arrival_s
    call = np.zeros(n)
    ret = np.zeros(n)
    done = np.zeros(n)
    status = np.full(n, UNSERVED, np.int8)
    names = schedule.tenants
    tenant = schedule.tenant.tolist()
    pool_index = schedule.pool_index.tolist()
    completed = [0] * len(names)
    samples = {t: {} for t in range(len(names))}
    infer = router.infer
    perf = time.perf_counter
    window_end = window_start + seconds
    first_profiled = n
    annotate = None
    profile_at = float("inf") if profile is None \
        else window_start + profile.start_s
    for i in range(n):
        if annotate is None and perf() >= profile_at:
            profile.start()
            import jax
            annotate = jax.profiler.TraceAnnotation
            first_profiled = i
        t = tenant[i]
        if annotate is None:
            while perf() < due[i]:
                pass
        else:
            with annotate("wait_arrival"):
                while perf() < due[i]:
                    pass
        now = perf()
        if now >= window_end:
            break
        x = pools[names[t]][pool_index[i]]
        call[i] = now
        try:
            if annotate is None:
                y = infer(names[t], x)
                ret[i] = perf()
                y = np.asarray(y)
            else:
                with annotate("router.infer"):
                    y = infer(names[t], x)
                    ret[i] = perf()
                    y = np.asarray(y)
        except TenantOverBudget:
            ret[i] = done[i] = perf()
            status[i] = FAILED
            continue
        done[i] = perf()
        status[i] = OK
        k = completed[t]
        completed[t] = k + 1
        slots = sample_slots[t]
        if k < len(slots) and slots[k] >= 0:
            samples[t][int(slots[k])] = (i, y)
    return Records(due=due, call=call, ret=ret, done=done, status=status,
                   first_profiled=first_profiled, samples=samples)
