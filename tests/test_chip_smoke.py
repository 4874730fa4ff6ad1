"""CPU rehearsal of ``chip_smoke.py``: the device check refuses the CPU,
and every phase after it runs here (Pallas interpreted) with the same
fleet, trace and correctness bounds the chip run uses."""

import importlib.util
import pathlib

import pytest

_PATH = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_device_check_refuses_cpu(smoke, capsys):
    with pytest.raises(smoke.SmokeFailure, match="'cpu'"):
        smoke.check_device()
    assert smoke.main() != 0
    captured = capsys.readouterr()
    assert "'cpu'" in captured.err
    assert '"ok"' not in captured.out


def test_phases_after_the_device_check(smoke, tmp_path):
    dep = smoke.build(out_dir=tmp_path)
    assert {t.net_id for t in dep.fleet.tenants} == \
        set(smoke.EDGE_NETS) | {"qwen2.5-3b-smoke"}
    assert list(tmp_path.glob("fleet_*_tpu.json"))
    router, served, _ = smoke.serve(dep)
    assert served["qwen2.5-3b-smoke"] == smoke.LM_REQUESTS
    smoke.check_health(router)
    outputs = smoke.check_outputs(dep)
    assert set(outputs) == set(smoke.EDGE_NETS)
    # The interpreter is exactly what the chip check must refuse.
    with pytest.raises(smoke.SmokeFailure, match="interpret"):
        smoke.check_compiled(dep)
