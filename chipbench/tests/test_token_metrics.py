"""The end-to-end metrics of token-generating cells: their readers on
synthetic records, and a token-generating cell added as new files and
entries only (a kind, a driver, a configuration, a mix, a workload entry and
the three metrics' entries), run through the harness on the CPU."""

import importlib.util
import json
import pathlib
import shutil
import sys
import types

import numpy as np
import pytest

from chipbench.loader import load_module
from chipbench.records import FAILED, OK, Records

HERE = pathlib.Path(__file__).resolve().parents[1]
TOKEN_METRICS = {"ttft_p50_ms", "token_gap_p50_ms", "tokens_per_s"}


def read(name, run):
    return load_module(HERE / "metrics" / f"{name}.py").read(run)


def run_data(records, window_start, seconds):
    """The part of ``run.RunData`` the readers use."""
    return types.SimpleNamespace(
        records=records, window_start=window_start, seconds=seconds,
        window_end=window_start + seconds,
        ok=lambda: records.status == OK)


def token_records(window_start):
    """Seven requests; times from ``window_start``, in seconds.

    ===  ======  =====  ======  =====  ========  ===================
    req  due     ttft   tokens  gap    done      status
    ===  ======  =====  ======  =====  ========  ===================
    0    0.0     0.010  1       --     0.510     one token: no gap
    1    1.0     0.020  5       0.002  1.028
    2    2.0     0.030  3       0.004  2.038
    3    3.0     0.040  9       0.001  3.048
    4    4.0     --     --      --     4.0       failed
    5    9.9     0.060  7       0.003  9.978
    6    9.95    0.050  2       0.100  10.100    done after the window
    ===  ======  =====  ======  =====  ========  ===================
    """
    due = window_start + np.array([0.0, 1.0, 2.0, 3.0, 4.0, 9.9, 9.95])
    ttft = np.array([0.010, 0.020, 0.030, 0.040, 0.0, 0.060, 0.050])
    tokens = np.array([1, 5, 3, 9, 0, 7, 2])
    gap = np.array([0.0, 0.002, 0.004, 0.001, 0.0, 0.003, 0.100])
    first = due + ttft
    done = first + gap * np.maximum(tokens - 1, 0)
    done[0] = first[0] + 0.5             # one token: any time, no gap
    status = np.array([OK, OK, OK, OK, FAILED, OK, OK], np.int8)
    return Records(due=due, call=due, ret=first, done=done, status=status,
                   first_profiled=7, samples={}, first=first, tokens=tokens)


def test_readers_on_token_records():
    """Medians over completed requests; a request of one token has no gap;
    a request done after the window adds no tokens to the rate."""
    rec = token_records(100.0)
    run = run_data(rec, 100.0, 10.0)
    # ttft of the completed requests 0-3, 5, 6: 10, 20, 30, 40, 60, 50 ms
    assert read("ttft_p50_ms", run) == pytest.approx(35.0)
    # gaps of 1-3, 5, 6 (request 0 has one token): 2, 4, 1, 3, 100 ms
    assert read("token_gap_p50_ms", run) == pytest.approx(3.0)
    # tokens of 0-3 and 5 (6 is done at 10.1 s), over 10 s
    assert read("tokens_per_s", run) == pytest.approx(
        (1 + 5 + 3 + 9 + 7) / 10.0)


def test_readers_read_nothing_of_edge_records():
    n = 4
    t = np.linspace(0.0, 0.3, n)
    rec = Records(due=t, call=t, ret=t, done=t + 0.001,
                  status=np.full(n, OK, np.int8), first_profiled=n,
                  samples={})
    assert rec.first is None and rec.tokens is None
    for name in TOKEN_METRICS:
        assert read(name, run_data(rec, 0.0, 1.0)) is None, name


# A token-generating kind: a stand-in router, prompts of 16 tokens drawn
# from the seed, and each request's work counted from its lengths.
KIND = '''
import numpy as np

from chipbench import work


class Router:
    def reset_metrics(self):
        pass

    def health(self):
        return {"tenants": {}}


def build(config, seed, tracer):
    return Router()


def input_pool(traffic, config, seed):
    rng = np.random.default_rng(seed)
    return {t: rng.integers(0, config["vocab"], (4, config["prompt"]))
            for t in traffic["tenants"]}


def warm(router, pools):
    pass


def batch(config):
    return 1


def request_work(config, sched, pools, records):
    return [work.Work(ops=2 * config["params"] * (config["prompt"] + n),
                      bytes=config["params"])
            for n in records.tokens.tolist()]


def control(config):
    return None


def compare(config, seed, samples, sched, pools, *, failed, replace=None,
            errors=None):
    kept = sum(len(v) for v in samples.values())
    return {"failed": {"value": failed, "limit": 0},
            "lm.unsampled": {"value": int(kept == 0), "limit": 0}}
'''

# Its driver stamps each request by a rule instead of serving it: request
# k has 1 + k % 4 tokens, its first 10 * (1 + k % 3) ms after it was due,
# and 100 * (1 + k % 2) ms between tokens.
DRIVER = '''
import numpy as np

from chipbench.records import OK, Records


def run(router, schedule, pools, *, window_start, seconds, sample_slots,
        profile=None):
    n = len(schedule)
    k = np.arange(n)
    due = window_start + schedule.arrival_s
    tokens = 1 + k % 4
    first = due + 0.010 * (1 + k % 3)
    done = first + 0.100 * (1 + k % 2) * (tokens - 1)
    return Records(due=due, call=due, ret=first, done=done,
                   status=np.full(n, OK, np.int8), first_profiled=n,
                   samples={0: {0: (0, np.zeros(1))}}, first=first,
                   tokens=tokens)
'''


# The entries a token-generating cell appends to ``end_to_end``, each
# listing the cells that report it; their bounds as PERF.md section 2 has
# them, until that cell's own runs set them.
TOKEN_ENTRIES = [
    {"name": "ttft_p50_ms", "unit": "ms", "better": "lower", "bound": 0.25,
     "source": "host_clock", "workloads": ["lm.stub"]},
    {"name": "token_gap_p50_ms", "unit": "ms", "better": "lower",
     "bound": 0.25, "source": "host_clock", "workloads": ["lm.stub"]},
    {"name": "tokens_per_s", "unit": "tokens/s", "better": "higher",
     "bound": 0.15, "source": "host_clock", "workloads": ["lm.stub"]},
]


def write_new(path, text):
    """Adds a file; a cell of a new kind edits none that is there."""
    assert not path.exists(), path
    path.write_text(text)


def test_token_cell_is_added_as_new_files_only(tmp_path, monkeypatch,
                                                capsys):
    import jax
    monkeypatch.setattr(sys, "path", list(sys.path))
    copy = tmp_path / "chipbench"
    shutil.copytree(HERE, copy,
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    write_new(copy / "kinds" / "token_stub.py", KIND)
    write_new(copy / "drivers" / "token_stub.py", DRIVER)
    write_new(copy / "configs" / "lm_stub.json", json.dumps(
        {"name": "lm_stub", "kind": "token_stub", "vocab": 32,
         "prompt": 16, "params": 1000}))
    write_new(copy / "traffic" / "lm.stub.json", json.dumps(
        {"driver": "token_stub", "tenants": ["lm"],
         "arrivals": {"process": "clocked", "rate_hz": 10.0}}))
    bench = json.loads((HERE.parent / "BENCHMARK.json").read_text())
    bench["configs"].append(
        {"name": "lm_stub", "source": "test", "reduced": [], "why": "test",
         "file": "chipbench/configs/lm_stub.json"})
    bench["workloads"].append(
        {"name": "lm.stub", "config": "lm_stub", "traffic": "lm.stub",
         "chips": 1, "why": "test"})
    assert not TOKEN_METRICS & {m["name"] for m in bench["end_to_end"]}
    bench["end_to_end"][-1:-1] = TOKEN_ENTRIES
    write_new(tmp_path / "BENCHMARK.json", json.dumps(bench))

    spec = importlib.util.spec_from_file_location(
        "chipbench_copy_run", copy / "run.py")
    run = importlib.util.module_from_spec(spec)
    monkeypatch.setitem(sys.modules, spec.name, run)
    spec.loader.exec_module(run)
    # The test process keeps JAX's compilation cache where it was.
    monkeypatch.setattr(run, "enable_compile_cache", lambda: "unchanged")

    edge = run.load_cell("ad.clocked")
    assert {m["name"] for m in edge.end_to_end} == {"latency_p50_us",
                                                    "setup_s"}
    cell = run.load_cell("lm.stub")
    assert pathlib.Path(cell.kind.__file__).parent == copy / "kinds"
    args = types.SimpleNamespace(seed=2**31 + 3, seconds=1.0, trace=0)
    assert run.run_cell(cell, args, jax.devices()[0], 1) == 0
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] == 10 and result["failed"] == 0
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(metrics) == {"setup_s", *TOKEN_METRICS}
    # Requests 0-9, due every 100 ms.  ttft 10, 20, 30 ms by k % 3: four
    # of 10 ms and three each of 20 and 30.
    assert metrics["ttft_p50_ms"] == pytest.approx(20.0)
    # Requests of two tokens or more (k % 4 != 0) and their gaps: 1, 3, 5,
    # 7, 9 at 200 ms, 2 and 6 at 100 ms.
    assert metrics["token_gap_p50_ms"] == pytest.approx(200.0)
    # 23 tokens in all; 7 (4 tokens, done at 0.72 + 0.6 s) and 9 (2, done
    # at 0.91 + 0.2 s) finish after the 1-s window.
    assert metrics["tokens_per_s"] == pytest.approx(17.0)
    assert result["metrics"]["tokens_per_s"]["unit"] == "tokens/s"
