"""The reduction from a profiler trace to busy time, idle share, kernel
time and idle-gap attribution."""

import gzip
import pathlib

import numpy as np
import pytest

from chipbench import trace

RECORDED = pathlib.Path(__file__).resolve().parent / "data" / \
    "recorded.xplane.pb.gz"


def events(rows):
    return trace.Events.of(rows)


def test_union_merges_overlaps_and_touching():
    u = trace.union(np.array([0.0, 1.0, 1.5, 5.0, 2.0]),
                    np.array([1.0, 2.0, 1.7, 6.0, 3.0]))
    np.testing.assert_allclose(u, [[0.0, 3.0], [5.0, 6.0]])
    assert trace.union(np.array([]), np.array([])).shape == (0, 2)


def test_busy_and_idle_clip_to_the_window():
    ops = events([("a", 0.0, 2.0), ("b", 1.0, 3.0), ("c", 5.0, 6.0),
                  ("d", 9.0, 12.0)])
    assert trace.busy_s(ops, 1.0, 10.0) == pytest.approx(2.0 + 1.0 + 1.0)
    np.testing.assert_allclose(trace.idle_gaps(ops, 1.0, 10.0),
                               [[3.0, 5.0], [6.0, 9.0]])


def test_kernel_events_by_name():
    ops = events([
        ("%repro_gemm_int8.1 = f32[32,128] custom-call(...)", 0.0, 1.0),
        ("%repro_fused_mlp_x9.1 = f32[32,640] custom-call(...)", 1.0, 4.0),
        ("%slice.23 = f32[8,640] slice(...)", 4.0, 4.5),
        ("%copy-start.11 = (f32[1,128]) copy-start(...)", 4.5, 5.0)])
    assert trace.kernel_s(ops) == pytest.approx(4.0)
    assert trace.op_name(ops.names[1]) == "repro_fused_mlp_x9"
    assert trace.top_ops(ops, 2) == [["repro_fused_mlp_x9", 3.0],
                                     ["repro_gemm_int8", 1.0]]


def test_gaps_go_to_the_innermost_host_event():
    host = events([("router.infer", 0.0, 10.0), ("DevicePut", 1.0, 3.0),
                   ("wait_arrival", 10.0, 20.0)])
    gaps = np.array([[1.5, 2.5], [4.0, 6.0], [9.0, 11.0], [12.0, 18.0],
                     [30.0, 31.0]])
    assert trace.attribute_gaps(gaps, host) == [
        ["wait_arrival", 7.0], ["router.infer", 3.0], ["DevicePut", 1.0],
        [trace.NO_EVENT, 1.0]]


def test_recorded_trace(tmp_path):
    """A profile of the served AD net on a TPU v5e, 0.5 s of back-to-back
    ``Router.infer`` calls: per request, a few microseconds of device ops
    against a host round trip of about a millisecond and a half."""
    path = tmp_path / "recorded.xplane.pb"
    path.write_bytes(gzip.decompress(RECORDED.read_bytes()))
    tr = trace.load(path)
    assert list(tr.devices) == ["/device:TPU:0"]
    ops = tr.devices["/device:TPU:0"]
    host = tr.serving_thread
    names = np.asarray(host.names)
    calls = int(np.sum(names == "router.infer"))
    ann = np.isin(names, trace.ANNOTATIONS)
    t0, t1 = host.start[ann].min(), host.end[ann].max()
    busy = trace.busy_s(ops, t0, t1)
    assert calls > 5
    assert 0 < busy < 0.05 * (t1 - t0)
    per_call_kernel = trace.kernel_s(ops) / calls
    assert 0.5e-6 < per_call_kernel < 20e-6
    assert {"repro_gemm_int8", "repro_fused_mlp_x9"} <= {
        trace.op_name(n) for n in ops.names}
    gaps = trace.attribute_gaps(trace.idle_gaps(ops, t0, t1), host)
    assert sum(s for _, s in gaps) == pytest.approx(t1 - t0 - busy)
    assert {"router.infer", "DevicePut"} <= {n for n, _ in gaps}
