"""The open-loop driver, on a stand-in router that takes a fixed time."""

import time

import numpy as np

from chipbench.drivers import sync_router
from chipbench.records import OK, UNSERVED
from chipbench.traffic.generate import Schedule


class SlowRouter:
    def __init__(self, service_s):
        self.service_s = service_s

    def infer(self, net_id, x):
        end = time.perf_counter() + self.service_s
        while time.perf_counter() < end:
            pass
        return x * 2.0


def test_backlog_is_timed_from_the_due_time_and_left_unserved():
    n = 200
    sched = Schedule(arrival_s=np.linspace(0.0, 0.099, n),
                     tenant=np.zeros(n, np.int32),
                     pool_index=np.arange(n, dtype=np.int32) % 4,
                     tenants=["net"])
    pools = {"net": np.arange(4 * 8 * 3, dtype=np.float32).reshape(4, 8, 3)}
    slots = {0: np.array([0, 1, -1] + [-1] * (n - 3))}
    start = time.perf_counter() + 1e-3
    rec = sync_router.run(SlowRouter(0.002), sched, pools,
                          window_start=start, seconds=0.1,
                          sample_slots=slots)
    ok = rec.status == OK
    assert 10 < ok.sum() < n
    assert np.all(rec.status[ok.sum():] == UNSERVED)
    wait = (rec.call - rec.due)[ok]
    assert wait[-1] > 0.02 > wait[0]         # the queue grew
    assert np.all(rec.done[ok] - rec.call[ok] >= 0.002)
    assert rec.first_profiled == n
    assert sorted(rec.samples[0]) == [0, 1]
    i, y = rec.samples[0][1]
    np.testing.assert_array_equal(y, pools["net"][sched.pool_index[i]] * 2)
