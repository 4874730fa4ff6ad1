"""The harness's inputs and comparison, pinned to what they were before
configuration kinds: for two cells at two seeds, the schedule, each
tenant's input pool and the reservoir's slots hash as they did, and the
comparison reads the same numbers on the same stand-in answers, with and
without the control put in the program's place.  The literals were
recorded from the harness before the edge code moved into
``chipbench/kinds/edge_chain.py``."""

import hashlib
import pathlib
import sys

import numpy as np
import pytest

from chipbench import check, reference
from chipbench.traffic import generate

HERE = pathlib.Path(__file__).resolve().parents[1]
SECONDS = 10.0      # the benchmark's run_seconds


def digest(arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def reservoir(sched, slots) -> dict:
    """``{tenant: [request, ...]}`` the driver keeps when every request of
    the schedule completes, in slot order."""
    out = {}
    for t, name in enumerate(sched.tenants):
        kept = {}
        for k, i in enumerate(np.flatnonzero(sched.tenant == t)):
            if k < len(slots[t]) and slots[t][k] >= 0:
                kept[int(slots[t][k])] = int(i)
        out[name] = [i for _, i in sorted(kept.items())]
    return out


def served(config, seed, sched, pools, kept) -> dict:
    """Stand-in answers for the kept requests: the reference's output plus
    noise whose size varies from request to request, drawn from the seed."""
    nets = {n["name"]: n for n in config["nets"]}
    out = {}
    for t, (name, idx) in enumerate(kept.items()):
        xs = np.stack([pools[name][sched.pool_index[i]] for i in idx])
        ref = reference.forward(
            reference.init_weights(seed, nets[name]["dims"]), xs)
        rms = float(np.sqrt(np.mean(np.square(ref, dtype=np.float64))))
        rng = np.random.default_rng(np.random.SeedSequence([seed, 7, t]))
        size = rng.uniform(0.0, 1.0, (len(idx), 1, 1)) * rms
        ys = (ref + size * rng.standard_normal(ref.shape)).astype(np.float32)
        out[name] = list(zip(idx, ys))
    return out


PARENT = {
    ("ad.clocked", 2**31 + 29): {
        "requests": 5200,
        "schedule":
            "e433d2a36e1b439a1d2640eb7d3adcee19e99e6657472cdb3b68954a26a6eaaf",
        "slots":
            "e06b23d8b87f2d1c630a1fa71b47e7763d183400f648c6bdf1fd92bd554a64bb",
        "pools": {
            "mlperf_tiny_ad":
                "a79bb3ea8df601a6322600e2ecceaebc72296d9802d3a6ba06e6229b5c06bfb2",
        },
        "served": {
            "failed": 0,
            "mlperf_tiny_ad.err_med": 0.2612563894236894,
            "mlperf_tiny_ad.off_share": 0.515625,
        },
        "control": {
            "failed": 0,
            "mlperf_tiny_ad.err_med": 0.4207833014466275,
            "mlperf_tiny_ad.off_share": 0.97265625,
        },
    },
    ("ad.clocked", 5): {
        "requests": 5200,
        "schedule":
            "e433d2a36e1b439a1d2640eb7d3adcee19e99e6657472cdb3b68954a26a6eaaf",
        "slots":
            "d4fab849d3184b20d9d6b86dbcb175480ff4911658a71f6f75ae3b4520614d9a",
        "pools": {
            "mlperf_tiny_ad":
                "e3dc273cc34b457fd8e6dfeaa361e1de539556ced6e4d399cf9447afe6786112",
        },
        "served": {
            "failed": 0,
            "mlperf_tiny_ad.err_med": 0.2501839270376304,
            "mlperf_tiny_ad.off_share": 0.49609375,
        },
        "control": {
            "failed": 0,
            "mlperf_tiny_ad.err_med": 0.4447678334092634,
            "mlperf_tiny_ad.off_share": 0.98828125,
        },
    },
    ("fleet.bursty", 2**31 + 29): {
        "requests": 2800,
        "schedule":
            "50840b79e58f310e27d8ff8f771019b07cad04f1681208ee12715d6993c0cb12",
        "slots":
            "1c6a4f2f8adb248e2644e43729167a5e020bc727cf06c4c028d09f8749c2dc04",
        "pools": {
            "jet_tagger":
                "a21fe19bbb32498561d5d844a42ab0ef77c461e0660166019054a2dc6297c467",
            "tau_select":
                "0c3a879dd696270671e024c1a68bb1e38dde671e9d13fd12c03cff03072c5862",
            "qubit":
                "c02aa0eed085411c46e686a21fb2508b8116da4b396b1b28c34b4295658589bb",
            "vae":
                "9efeef42cb8992e5b7ec974168a24fedba027fc232d1c128c867a0db831713d7",
            "autoencoder":
                "d46c8158e34ddf10f09588662f5c9a98a585454b50ff8972c9156689c6cc0747",
        },
        "served": {
            "failed": 0,
            "jet_tagger.err_med": 0.2642946694823157,
            "jet_tagger.off_share": 0.5,
            "tau_select.err_med": 0.26892434628662426,
            "tau_select.off_share": 0.4921875,
            "vae.err_med": 0.2359901407177939,
            "vae.off_share": 0.44140625,
            "qubit.err_med": 0.2622424214341203,
            "qubit.off_share": 0.49609375,
            "autoencoder.err_med": 0.24240373632901824,
            "autoencoder.off_share": 0.4897959183673469,
        },
        "control": {
            "failed": 0,
            "jet_tagger.err_med": 0.2298991840633793,
            "jet_tagger.off_share": 0.03515625,
            "tau_select.err_med": 0.21585017561199424,
            "tau_select.off_share": 0.03515625,
            "vae.err_med": 0.24032854900380135,
            "vae.off_share": 0.01171875,
            "qubit.err_med": 0.3347179339629406,
            "qubit.off_share": 0.5625,
            "autoencoder.err_med": 0.36889522337066877,
            "autoencoder.off_share": 0.8734693877551021,
        },
    },
    ("fleet.bursty", 5): {
        "requests": 2800,
        "schedule":
            "edaa384372227719cab17e73789ff42d0049937d00c92827a96fbaf4a78fcbcd",
        "slots":
            "c7c8a2aca414822d3da796b078795e623a51d0a1cb074f04fda92d876c58ccf6",
        "pools": {
            "jet_tagger":
                "00ed9e679b7ab67d064b02c4f182cd12ea3b5c9419921b66e9e88ff2bfe6be3c",
            "tau_select":
                "cecfa847c169a00ac0da2c3d57a3dfb369c23784a9998908a16ab8f68f407d3d",
            "qubit":
                "5dd08251fee2c35abb36b9e1309e54fbc5fc2d93120e16f4a43c16aa8a459e70",
            "vae":
                "961f1917ee831e1d9d685254323a20fc3c6b12eac838adb06a7029ccaee10ed5",
            "autoencoder":
                "c878a1b93f1079a1385e037b2df4c1f51021b025082eee76ccc220d9dd57c524",
        },
        "served": {
            "failed": 0,
            "jet_tagger.err_med": 0.24824979048859225,
            "jet_tagger.off_share": 0.4765625,
            "tau_select.err_med": 0.24926135690903473,
            "tau_select.off_share": 0.4765625,
            "vae.err_med": 0.28003818773820893,
            "vae.off_share": 0.55078125,
            "qubit.err_med": 0.2828162609837422,
            "qubit.off_share": 0.54296875,
            "autoencoder.err_med": 0.2692003710580989,
            "autoencoder.off_share": 0.49387755102040815,
        },
        "control": {
            "failed": 0,
            "jet_tagger.err_med": 0.2703617524311732,
            "jet_tagger.off_share": 0.15625,
            "tau_select.err_med": 0.12478726540331087,
            "tau_select.off_share": 0.0,
            "vae.err_med": 0.2641265746038601,
            "vae.off_share": 0.02734375,
            "qubit.err_med": 0.26700491659300524,
            "qubit.off_share": 0.04296875,
            "autoencoder.err_med": 0.351220943822062,
            "autoencoder.off_share": 0.7836734693877551,
        },
    },
}


@pytest.fixture
def run(monkeypatch):
    monkeypatch.setattr(sys, "path", list(sys.path))
    sys.path.insert(0, str(HERE))
    import run
    return run


@pytest.mark.parametrize("cell_name, seed", sorted(PARENT))
def test_inputs_and_comparison_match_the_parent(run, cell_name, seed):
    want = PARENT[(cell_name, seed)]
    cell = run.load_cell(cell_name)
    sched = generate.schedule(cell.traffic, SECONDS, seed)
    pools = cell.kind.input_pool(cell.traffic, cell.config, seed)
    slots = run.sample_slots(sched, check.SAMPLES_PER_TENANT, seed)
    assert len(sched) == want["requests"]
    assert digest([sched.arrival_s, sched.tenant, sched.pool_index]) \
        == want["schedule"]
    assert {t: digest([p]) for t, p in pools.items()} == want["pools"]
    assert digest([slots[t] for t in range(len(sched.tenants))]) \
        == want["slots"]
    samples = served(cell.config, seed, sched, pools,
                     reservoir(sched, slots))
    # The float32 reference may round its last digits differently on
    # another CPU; 1e-6 is a few float32 ulps.
    for side, replace in (("served", None),
                          ("control", reference.forward_int4)):
        got = check.compare(cell.config, seed, samples, sched, pools,
                            failed=0, replace=replace)
        assert list(got) == list(want[side])
        for number, value in want[side].items():
            assert got[number]["value"] == pytest.approx(value, rel=1e-6), \
                (side, number)
