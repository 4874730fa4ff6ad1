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
