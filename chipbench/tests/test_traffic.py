"""The traffic generator: seeded, the same work for every seed, and mixes
found by name."""

import json
import pathlib
import shutil

import numpy as np
import pytest

from chipbench.traffic import generate

HERE = pathlib.Path(__file__).resolve().parents[1]
MIXES = ["ad.clocked", "ad.saturate", "fleet.bursty", "fleet.saturate"]


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_schedule_and_inputs(mix):
    spec = generate.load(mix)
    a = generate.schedule(spec, 2.0, 2**31 + 17)
    b = generate.schedule(spec, 2.0, 2**31 + 17)
    np.testing.assert_array_equal(a.arrival_s, b.arrival_s)
    np.testing.assert_array_equal(a.tenant, b.tenant)
    widths = {t: 4 for t in spec["tenants"]}
    pa = generate.input_pool(spec, widths, 8, 5)
    pb = generate.input_pool(spec, widths, 8, 5)
    for t in spec["tenants"]:
        np.testing.assert_array_equal(pa[t], pb[t])
        assert pa[t].dtype == np.float32
        assert pa[t].shape == (generate.INPUT_POOL, 8, 4)


@pytest.mark.parametrize("mix", MIXES)
def test_every_seed_gets_the_same_work(mix):
    spec = generate.load(mix)
    a = generate.schedule(spec, 3.0, 1)
    b = generate.schedule(spec, 3.0, 2)
    assert len(a) == len(b)
    assert a.per_tenant() == b.per_tenant()
    assert np.all(np.diff(a.arrival_s) >= 0)
    assert a.arrival_s.min() >= 0 and a.arrival_s.max() < 3.0
    if spec["arrivals"]["process"] != "clocked":
        assert not np.array_equal(a.arrival_s, b.arrival_s)


def test_rates_and_zipf_shares():
    spec = generate.load("fleet.saturate")
    s = generate.schedule(spec, 4.0, 3)
    assert len(s) == round(spec["arrivals"]["rate_hz"] * 4.0)
    shares = generate.tenant_shares(spec)
    assert shares[0] / shares[1] == pytest.approx(2.0)
    counts = np.asarray(list(s.per_tenant().values()))
    assert np.all(np.abs(counts - shares * len(s)) <= 1)
    clocked = generate.schedule(generate.load("ad.clocked"), 1.0, 3)
    np.testing.assert_allclose(np.diff(clocked.arrival_s),
                               1.0 / generate.load("ad.clocked")
                               ["arrivals"]["rate_hz"])


def test_mmpp_bursts():
    spec = generate.load("fleet.bursty")
    arr = spec["arrivals"]
    seconds = 12.0
    s = generate.schedule(spec, seconds, 9)
    assert len(s) == pytest.approx(arr["rate_hz"] * seconds, abs=2)
    # the busiest 60 ms run near the high state's rate
    counts, _ = np.histogram(s.arrival_s, bins=int(seconds / 0.06))
    high = 2 * arr["rate_hz"] * arr["burst_factor"] / (1 + arr["burst_factor"])
    assert counts.max() / 0.06 > 0.8 * high
    assert counts.min() / 0.06 < 2 * arr["rate_hz"] / (1 + arr["burst_factor"]) * 2


def test_pool_index_cycles_per_tenant():
    spec = generate.load("fleet.saturate")
    s = generate.schedule(spec, 8.0, 4)
    assert s.per_tenant()["jet_tagger"] > generate.INPUT_POOL
    for t in range(len(s.tenants)):
        idx = s.pool_index[s.tenant == t]
        np.testing.assert_array_equal(
            idx, np.arange(len(idx)) % generate.INPUT_POOL)


@pytest.mark.parametrize("mix, match", [
    ({"arrivals": {"process": "gamma", "rate_hz": 1.0}}, "gamma"),
    ({"arrivals": {"process": "mmpp", "rate_hz": 1.0}}, "burst_factor"),
    ({"arrivals": {"process": "poisson", "rate_hz": 0.0}}, "rate_hz"),
    ({"tenants": ["a", "b"]}, "zipf_s"),
    ({"tenants": []}, "no tenant"),
])
def test_bad_mix_is_refused(tmp_path, mix, match):
    good = {"driver": "sync_router",
            "arrivals": {"process": "poisson", "rate_hz": 1.0},
            "tenants": ["a"]}
    (tmp_path / "good.json").write_text(json.dumps(good))
    generate.load("good", tmp_path)
    (tmp_path / "bad.json").write_text(json.dumps(dict(good, **mix)))
    with pytest.raises(ValueError, match=match):
        generate.load("bad", tmp_path)
    with pytest.raises(FileNotFoundError):
        generate.load("absent", tmp_path)


def test_new_arrival_process_is_found_by_name(tmp_path, monkeypatch):
    """A later mix brings its own arrival process as a file of its own, and
    edits nothing that is there."""
    procs = tmp_path / "processes"
    shutil.copytree(generate.PROCESSES, procs)
    (procs / "flash_test.py").write_text(
        "import numpy as np\n"
        "PARAMS = ('spike',)\n"
        "def arrivals(params, seconds, rng):\n"
        "    n = int(params['rate_hz'] * seconds * params['spike'])\n"
        "    return np.sort(rng.uniform(0.0, seconds / 5, n))\n")
    monkeypatch.setattr(generate, "PROCESSES", procs)
    mix = {"driver": "sync_router", "tenants": ["a"],
           "arrivals": {"process": "flash_test", "rate_hz": 10.0,
                        "spike": 8}}
    (tmp_path / "flash.json").write_text(json.dumps(mix))
    s = generate.schedule(generate.load("flash", tmp_path), 2.0, 7)
    assert len(s) == 160 and s.arrival_s.max() < 0.4
    (tmp_path / "flash.json").write_text(json.dumps(
        dict(mix, arrivals={"process": "flash_test", "rate_hz": 10.0})))
    with pytest.raises(ValueError, match="spike"):
        generate.load("flash", tmp_path)


def test_new_traffic_file_is_found_by_name(tmp_path, monkeypatch):
    """A later cell adds a mix file and a workload entry, and edits
    nothing that is there."""
    import importlib.util
    import sys
    monkeypatch.setattr(sys, "path", list(sys.path))
    shutil.copytree(HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    bench = json.loads((HERE.parent / "BENCHMARK.json").read_text())
    mix = dict(generate.load("ad.clocked"),
               arrivals={"process": "poisson", "rate_hz": 123.0})
    (tmp_path / "chipbench" / "traffic" / "ad.poisson_test.json").write_text(
        json.dumps(mix))
    bench["workloads"].append(
        {"name": "ad.poisson_test", "config": "mlperf_tiny_ad",
         "traffic": "ad.poisson_test", "chips": 1, "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    spec = importlib.util.spec_from_file_location(
        "chipbench_copy_run", tmp_path / "chipbench" / "run.py")
    run = importlib.util.module_from_spec(spec)
    monkeypatch.setitem(sys.modules, spec.name, run)
    spec.loader.exec_module(run)
    cell = run.load_cell("ad.poisson_test")
    assert cell.traffic["arrivals"]["rate_hz"] == 123.0
    assert cell.config["name"] == "mlperf_tiny_ad"
    assert [m["name"] for m in cell.end_to_end] == ["setup_s"]
