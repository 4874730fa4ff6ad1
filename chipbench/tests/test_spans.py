"""The readers of the program's own spans and of the idle time they leave
unexplained, on a synthetic run and on a recorded trace."""

import gzip
import pathlib

import numpy as np
import pytest

from chipbench import trace
from chipbench.loader import load_module
from chipbench.records import FAILED, OK, Records
from repro.obs import Span

HERE = pathlib.Path(__file__).resolve().parents[1]
RECORDED = HERE / "tests" / "data" / "recorded.xplane.pb.gz"


def read(name, run):
    return load_module(HERE / "metrics" / f"{name}.py").read(run)


class Run:
    """The part of ``run.RunData`` the readers use."""

    def __init__(self, spans, call, first_profiled, tr=None, window=None):
        n = len(call)
        self.spans = spans
        self.records = Records(
            due=np.asarray(call), call=np.asarray(call),
            ret=np.asarray(call), done=np.asarray(call),
            status=np.full(n, OK, np.int8), first_profiled=first_profiled,
            samples={})
        self.trace = tr
        self.trace_window = window


def edge_request(rid, t0, *, admit, dispatch=0.0, wait=0.0, readback=0.0,
                 account=0.0, h2d=20_480, d2h=20_480, refused=False):
    """The spans of one edge request starting at ``t0`` (seconds); a
    request refused at admission has only its ``router.admit``."""
    out, t = [], t0
    for name, dur, attrs in (
            ("router.admit", admit, {}),
            ("engine.dispatch", dispatch, {"h2d_bytes": h2d}),
            ("engine.wait", wait, {}),
            ("engine.readback", readback, {"d2h_bytes": d2h}),
            ("router.account", account, {})):
        out.append(Span(name=name, t0_s=t, dur_s=dur, trace_id=rid,
                        attrs={"tenant": "ad", **attrs}))
        t += dur
        if refused:
            break
    return out


@pytest.fixture
def synthetic():
    us = 1e-6
    spans = (
        edge_request(1, 0.0, admit=10 * us, dispatch=200 * us,
                     wait=600 * us, readback=400 * us, account=30 * us)
        + edge_request(2, 0.01, admit=20 * us, dispatch=300 * us,
                       wait=700 * us, readback=500 * us, account=50 * us,
                       h2d=0)
        + edge_request(3, 0.02, admit=30 * us, dispatch=400 * us,
                       wait=800 * us, readback=600 * us, account=70 * us)
        # refused at admission: not a completed request
        + edge_request(4, 0.025, admit=5 * us, refused=True)
        # under the profiler: left out of the host-clock medians
        + edge_request(5, 0.03, admit=900 * us, dispatch=900 * us,
                       wait=900 * us, readback=900 * us, account=900 * us))
    return Run(spans, call=[0.0, 0.01, 0.02, 0.025, 0.03], first_profiled=4)


@pytest.mark.parametrize("name, want", [
    ("engine_dispatch_p50_us", 300.0),
    ("engine_wait_p50_us", 700.0),
    ("engine_readback_p50_us", 500.0),
    ("router_own_p50_us", 70.0),                  # 40, 70, 100
    ("host_copy_bytes_per_req", (40_960 * 3 + 20_480) / 4)])
def test_span_readers(synthetic, name, want):
    assert read(name, synthetic) == pytest.approx(want)


@pytest.mark.parametrize("name", [
    "engine_dispatch_p50_us", "engine_wait_p50_us",
    "engine_readback_p50_us", "router_own_p50_us",
    "host_copy_bytes_per_req", "idle_unattributed_pct"])
def test_readers_find_nothing_without_spans(name):
    """A program without the spans (or a run without a trace) gives each
    reader nothing to read: the metric is left out, nothing raises."""
    assert read(name, Run(None, call=[0.0], first_profiled=1)) is None
    assert read(name, Run([], call=[0.0], first_profiled=1)) is None


def test_idle_unattributed_reads_the_gaps_no_event_explains():
    host = trace.Events.of([
        ("wait_arrival", 0.0, 2.0), ("router.infer", 2.0, 10.0),
        ("request", 2.5, 9.5), ("engine.wait", 4.0, 6.0)])
    ops = trace.Events.of([("%repro_gemm_int8.1 = f32[8,8] x", 5.0, 5.5)])
    tr = trace.Trace(devices={"/device:TPU:0": ops}, serving_thread=host)
    run = Run([], call=[0.0], first_profiled=0, tr=tr, window=(0.0, 11.0))
    # idle 10.5 s: router.infer alone 1.0 s (2-2.5, 9.5-10), no event 1.0 s
    assert read("idle_unattributed_pct", run) == pytest.approx(
        100 * 2.0 / 10.5)


def test_idle_unattributed_on_the_recorded_trace(tmp_path):
    """The recorded AD profile has only the benchmark's annotations and
    JAX's own events: about half its idle time is unexplained."""
    path = tmp_path / "recorded.xplane.pb"
    path.write_bytes(gzip.decompress(RECORDED.read_bytes()))
    tr = trace.load(path)
    host = tr.serving_thread
    ann = np.isin(np.asarray(host.names), trace.ANNOTATIONS)
    window = (float(host.start[ann].min()), float(host.end[ann].max()))
    run = Run([], call=[0.0], first_profiled=0, tr=tr, window=window)
    # router.infer 0.2663 s + no event 0.0008 s of 0.4992 s idle
    assert read("idle_unattributed_pct", run) == pytest.approx(53.5, abs=0.1)
