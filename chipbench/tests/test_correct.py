"""The comparison that decides ``correct``: the int4 control and each fault
the served cells can have come out not correct; a sound run comes out
correct.  On the CPU, at the cells' own widths."""

import json
import pathlib
import subprocess
import sys
import types

import numpy as np
import pytest

from chipbench import check, reference
from chipbench.traffic import generate

HERE = pathlib.Path(__file__).resolve().parents[1]
ROOT = HERE.parent
CELLS = {"ad.clocked": "mlperf_tiny_ad", "fleet.bursty": "table1_fleet"}


def config(name):
    return json.loads((HERE / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("mix, cfg", sorted(CELLS.items()))
def test_int4_control_is_not_correct(mix, cfg):
    """The reference computed in int4, put in the program's place on the
    cell's own traffic and sample size, fails every tenant's number."""
    conf = config(cfg)
    spec = generate.load(mix)
    seed = 2**31 + 101
    sched = generate.schedule(spec, 4.0, seed)
    nets = {n["name"]: n for n in conf["nets"]}
    pools = generate.input_pool(
        spec, {t: nets[t]["dims"][0] for t in spec["tenants"]}, 8, seed)
    samples = {}
    for t, name in enumerate(sched.tenants):
        idx = np.flatnonzero(sched.tenant == t)[:check.SAMPLES_PER_TENANT]
        samples[name] = [(int(i), None) for i in idx]
    got = check.compare(conf, seed, samples, sched, pools, failed=0,
                        replace=reference.forward_int4)
    assert not check.is_correct(got)
    for name in nets:
        c = got[f"{name}.err_med"]
        assert c["value"] > c["limit"], (name, c)


def test_reference_matches_the_published_init_rule():
    """The reference's weights follow the init rule without importing the
    program: first layer of seed 0, drawn again by hand."""
    import jax
    w, b = reference.init_weights(0, [16, 64, 5])[0]
    k, _ = jax.random.split(jax.random.fold_in(jax.random.PRNGKey(0), 0))
    np.testing.assert_allclose(
        np.asarray(w), np.asarray(jax.random.normal(k, (16, 64))) / 4.0,
        rtol=1e-6)
    assert not np.asarray(b).any()


def _altered(y):
    """An answer altered where it is produced: one output channel's sign."""
    y = np.array(y)
    y[:, 0] = -y[:, 0] + 1.0
    return y * 1.5


def _half_batch(y):
    """Half of the batch left out: rows 4..7 never computed."""
    y = np.array(y)
    y[y.shape[0] // 2:] = 0.0
    return y


def _stale(infer):
    """A fault on a minority of requests: every fourth call of an engine
    returns that engine's previous answer."""
    last, calls = {}, {}

    def broken(self, x):
        y = infer(self, x)
        k = calls[id(self)] = calls.get(id(self), 0) + 1
        prev, last[id(self)] = last.get(id(self)), y
        return prev if k % 4 == 0 and prev is not None else y
    return broken


FAULTS = {"sound": None,
          "answer_altered": lambda infer: lambda self, x: _altered(
              infer(self, x)),
          "half_batch": lambda infer: lambda self, x: _half_batch(
              infer(self, x)),
          "stale_minority": _stale}


def _drive(monkeypatch, capsys, cell_name, fault):
    """A whole run of a cell past the device check, on the CPU, with the
    engine's output broken by ``fault``; returns the result."""
    monkeypatch.setattr(sys, "path", list(sys.path))
    sys.path.insert(0, str(HERE))
    import jax

    import run
    from repro.serve.engine import EdgeEngine
    if fault is not None:
        monkeypatch.setattr(EdgeEngine, "infer", fault(EdgeEngine.infer))
    cell = run.load_cell(cell_name)
    args = types.SimpleNamespace(seed=2**31 + 7, seconds=0.5, trace=0)
    assert run.run_cell(cell, args, jax.devices()[0], 1) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


# On the autoencoder a stale answer lies as close to the right one as the
# int8 answers of a sound seed with an unlucky calibration batch do, so no
# limit of its numbers can tell them apart; the fleet's nets are held to it.
NOT_SEEN = {("ad.clocked", "stale_minority")}


@pytest.mark.parametrize("cell, fault", [
    (c, f) for f in sorted(FAULTS) for c in sorted(CELLS)
    if (c, f) not in NOT_SEEN])
def test_run_judges_its_answers(monkeypatch, capsys, cell, fault):
    result = _drive(monkeypatch, capsys, cell, FAULTS[fault])
    compared = result["compared"]
    assert result["correct"] is (fault == "sound"), compared
    assert result["attempted"] > 0 and result["failed"] == 0
    assert list(result)[-1] == "compared"
    assert set(result["metrics"]) == {"latency_p50_us", "setup_s"}
    if fault == "stale_minority":        # the median cannot see it
        assert all(v["value"] <= v["limit"] for k, v in compared.items()
                   if k.endswith(".err_med")), compared


def test_refuses_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, str(HERE / "run.py"), "--workload", "ad.clocked",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "HOME": str(ROOT)})
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr and "TPU" in proc.stderr
    assert proc.stdout.strip() == ""
