"""Work counts from the configurations' widths."""

import json
import pathlib

import pytest

from chipbench import work

CONFIGS = pathlib.Path(__file__).resolve().parents[1] / "configs"


def nets(name):
    return {n["name"]: n for n in
            json.loads((CONFIGS / f"{name}.json").read_text())["nets"]}


def test_mlperf_tiny_ad_work():
    net = nets("mlperf_tiny_ad")["mlperf_tiny_ad"]
    assert work.macs(net["dims"]) == 264_192
    w = work.request_work(net["dims"], net["batch"])
    assert w.ops == 2 * 8 * 264_192
    # int8 weights + f32 scale and bias per channel + f32 input and output
    assert w.bytes == 264_192 + 8 * 1672 + 4 * 8 * (640 + 640) == 318_528


@pytest.mark.parametrize("name, macs", [
    ("jet_tagger", 4_256), ("tau_select", 1_408), ("vae", 35_968),
    ("qubit", 81_824), ("autoencoder", 113_152)])
def test_table1_fleet_work(name, macs):
    net = nets("table1_fleet")[name]
    assert work.macs(net["dims"]) == macs
    w = work.request_work(net["dims"], 8)
    assert w.ops == 16 * macs
    assert w.bytes == (macs + 8 * sum(net["dims"][1:])
                       + 32 * (net["dims"][0] + net["dims"][-1]))


def test_roofline_takes_the_larger_bound():
    peak = work.peaks("TPU v5 lite")
    w = work.request_work(nets("mlperf_tiny_ad")["mlperf_tiny_ad"]["dims"], 8)
    assert work.roofline_s(w, peak) == pytest.approx(318_528 / 819e9)
    assert work.roofline_s(work.Work(ops=393_000, bytes=1), peak) == \
        pytest.approx(1e-9)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks("TPU v9 imaginary")


def test_edge_kind_counts_each_request_as_its_tenants_net():
    """The kind's work per request is the per-net work of its tenant, so a
    sum over requests equals the per-tenant counts times each net's work."""
    from chipbench.loader import load_kind
    from chipbench.traffic import generate
    config = json.loads((CONFIGS / "table1_fleet.json").read_text())
    kind = load_kind(config)
    sched = generate.schedule(generate.load("fleet.bursty"), 2.0, 2**31 + 5)
    got = kind.request_work(config, sched, None, None)
    assert len(got) == len(sched)
    for i in (0, len(sched) // 2, len(sched) - 1):
        name = sched.tenants[sched.tenant[i]]
        assert got[i] == work.request_work(nets("table1_fleet")[name]["dims"],
                                           8)
    per_tenant = sched.per_tenant()
    assert sum(w.ops for w in got) == sum(
        n * work.request_work(nets("table1_fleet")[t]["dims"], 8).ops
        for t, n in per_tenant.items())


def test_step_and_kernel_shares_sum_the_work_of_each_request():
    """``mfu_pct`` and ``edge_mlp_roofline`` read the same totals from the
    per-request work as from per-tenant counts times each net's work."""
    import types

    import numpy as np

    from chipbench import trace
    from chipbench.loader import load_kind, load_module
    from chipbench.traffic import generate
    config = json.loads((CONFIGS / "table1_fleet.json").read_text())
    sched = generate.schedule(generate.load("fleet.saturate"), 2.0, 11)
    profiled = np.arange(len(sched)) >= 0.8 * len(sched)
    profiled[-3:] = False                    # not completed
    peak = work.peaks("TPU v5 lite")
    kernel = "%repro_fused_mlp_x4.1 = f32[8,5] custom-call(...)"
    run = types.SimpleNamespace(
        work=load_kind(config).request_work(config, sched, None, None),
        profiled=lambda: profiled, peak=peak, trace_window=(0.0, 0.4),
        trace=types.SimpleNamespace(
            devices={"/device:TPU:0": trace.Events.of([(kernel, 0.0, 2e-3)])}))
    counts = np.bincount(sched.tenant[profiled],
                         minlength=len(sched.tenants))
    per_net = [work.request_work(nets("table1_fleet")[t]["dims"], 8)
               for t in sched.tenants]
    ops = sum(int(n) * w.ops for n, w in zip(counts, per_net))
    least = sum(int(n) * work.roofline_s(w, peak)
                for n, w in zip(counts, per_net))
    metrics = CONFIGS.parent / "metrics"
    assert load_module(metrics / "mfu_pct.py").read(run) == pytest.approx(
        100.0 * ops / 0.4 / peak["int8_ops_per_s"], rel=1e-12)
    assert load_module(metrics / "edge_mlp_roofline.py").read(run) == \
        pytest.approx(100.0 * least / 2e-3, rel=1e-12)
