"""repro.deploy facade: staged pipeline, caching, serving, bench, CLI."""

import pathlib

import numpy as np
import pytest

from repro import configs
from repro import plan as plan_lib
from repro.deploy import Deployment, StageContext, resolve_configs, stages
from repro.models import edge
from repro.serve.engine import ContinuousBatcher, EdgeEngine, Request


@pytest.fixture(scope="module")
def lm_cfg():
    return configs.get("qwen2_5_3b").smoke


@pytest.fixture(scope="module")
def built(lm_cfg):
    """One full build shared by the e2e assertions: 2 edge nets + 1 LM,
    planned under the host-calibrated model, engines live."""
    cache = plan_lib.PlanCache()
    dep = Deployment.build(["jet_tagger", "tau_select", lm_cfg],
                           machine_model="auto", cache=cache)
    return dep, cache


# ---------------------------------------------------------------------------
# The e2e smoke the ISSUE asks for
# ---------------------------------------------------------------------------

def test_build_runs_all_stages(built, lm_cfg):
    dep, _ = built
    assert list(dep.stage_results) == ["characterize", "plan", "engines"]
    assert set(dep.plans) == {"jet_tagger", "tau_select", lm_cfg.name}
    assert isinstance(dep.engines["jet_tagger"], EdgeEngine)
    assert isinstance(dep.engines[lm_cfg.name], ContinuousBatcher)
    # The LM tenant's batcher is plan-driven (slots from the serve section).
    lm_plan = dep.plans[lm_cfg.name]
    assert dep.engines[lm_cfg.name].slots == lm_plan.serve["slots"]
    # machine_model="auto" resolved to a host-calibrated TpuV5e.
    from repro import hw as hwlib
    assert isinstance(dep.machine_model, hwlib.TpuV5e)
    assert dep.machine_model.kernel_overhead_s != hwlib.TPU_V5E.kernel_overhead_s


def test_second_build_hits_plan_cache(built, lm_cfg):
    _, cache = built
    dep2 = Deployment.build(["jet_tagger", "tau_select", lm_cfg],
                            machine_model="auto", cache=cache,
                            stop_after="plan")
    assert dep2.stage_results["plan"].cached
    assert dep2.stage_results["characterize"].cached    # process memo
    assert "engines" not in dep2.stage_results          # partial pipeline


def test_serve_drains_request_set(built, lm_cfg):
    dep, _ = built
    router = dep.serve()
    inputs = router.warmup()
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i,
                    prompt=rng.integers(1, lm_cfg.vocab_size,
                                        3).astype(np.int32), max_new=3)
            for i in range(3)]
    for r in reqs:
        router.submit(lm_cfg.name, r)
    router.drive(inputs, iters=4)
    router.run_until_drained(max_ticks=200)
    assert all(r.done and len(r.out) == 3 for r in reqs)
    rep = router.report()
    assert rep["jet_tagger"]["count"] >= 4
    assert rep[lm_cfg.name]["count"] == 3


def test_bench_row_shape(built):
    dep, _ = built
    rows = dep.bench(iters=3, warmup=1)
    assert [r.net_id for r in rows] == ["jet_tagger", "tau_select"]  # no LM
    for r in rows:
        rec = r.as_record()
        assert rec["name"] == f"deploy/{r.net_id}/planned-vs-measured"
        assert "src=measured" in rec["derived"]
        assert rec["us_per_call"] > 0


def test_bench_rows_within_2x():
    """A fully-characterized deployment predicts interpret-mode latency
    within the repo-wide 2x band.  Like fig10/fig11, a load shift between
    sweep and measurement is drift, not model error — re-characterize under
    the current load (up to 3 passes) before failing."""
    from repro.characterize import characterize
    for _ in range(3):
        mm = characterize(sweep="quick")
        dep = Deployment.build(["jet_tagger", "tau_select"],
                               machine_model=mm, cache=plan_lib.PlanCache())
        rows = dep.bench(iters=7, warmup=2)
        if all(r.within_2x for r in rows):
            break
    assert all(r.within_2x for r in rows), [r.as_record() for r in rows]


def test_recalibrate_adopts_measured_costs(built):
    dep, cache = built
    before = {t.net_id: t.plan.est_latency_s for t in dep.fleet.tenants}
    new_fleet = dep.recalibrate()
    assert dep.fleet is new_fleet
    for t in new_fleet.tenants:
        if t.plan.kind != "edge":
            continue
        assert "calibration" in t.plan.serve
        assert t.plan.est_latency_s != before[t.net_id]
        # Engines executed the same tiles but adopted the new cost story.
        assert dep.engines[t.net_id].plan is t.plan
        # Calibrated plans landed in the cache under their original keys.
        assert cache.get(t.plan.key).est_latency_s == t.plan.est_latency_s


# ---------------------------------------------------------------------------
# Partial pipelines + spec resolution
# ---------------------------------------------------------------------------

def test_plan_only_builds_no_engines():
    dep = Deployment.build("jet_tagger", machine_model=None,
                           stop_after="plan", cache=plan_lib.PlanCache())
    assert "engines" not in dep.stage_results
    assert dep.ctx.engines == {}
    assert dep.plan.network == "jet_tagger"
    # Stock constants: the characterize stage is an explicit no-op.
    assert dep.stage_results["characterize"].skipped
    # .engines builds lazily when asked.
    assert isinstance(dep.engines["jet_tagger"], EdgeEngine)
    assert "engines" in dep.stage_results


def test_single_net_plan_matches_direct_planner():
    """The facade's single-net plan is the planner's answer (same layers,
    same estimates) — no facade-only cost drift."""
    cfg = edge.edge_config("qubit")
    via_facade = Deployment.build(cfg, machine_model=None,
                                  stop_after="plan",
                                  cache=plan_lib.PlanCache()).plan
    direct = plan_lib.plan_deployment(cfg, target="tpu")
    assert via_facade.layers == direct.layers
    assert via_facade.est_latency_s == pytest.approx(direct.est_latency_s)
    assert via_facade.fusion_groups == direct.fusion_groups


def test_resolve_configs_specs(lm_cfg):
    out = resolve_configs(["jet_tagger", lm_cfg])
    assert out[0].name == "jet_tagger" and out[1] is lm_cfg
    assert resolve_configs("vae")[0].dims == edge.edge_config("vae").dims
    smoke = resolve_configs("lm:qwen2_5_3b")[0]
    assert smoke.family == lm_cfg.family
    with pytest.raises(ValueError):
        resolve_configs(["definitely_not_a_net"])


def test_build_rejects_bad_stop_after():
    with pytest.raises(ValueError):
        Deployment.build("jet_tagger", stop_after="quantize")


def test_artifact_dir_writes_plan(tmp_path):
    dep = Deployment.build("tau_select", machine_model=None,
                           stop_after="plan", artifact_dir=tmp_path,
                           cache=plan_lib.PlanCache())
    art = dep.stage_results["plan"].artifact
    assert art == tmp_path / "tau_select_tpu.json"
    assert plan_lib.DeploymentPlan.load(art).layers == dep.plan.layers


class _Device:
    def __init__(self, platform, device_kind):
        self.platform, self.device_kind = platform, device_kind


@pytest.mark.parametrize("platform,kind,expected", [
    ("cpu", "cpu", "host fit"),
    ("tpu", "TPU v5 lite", "stock v5e"),
    ("tpu", "TPU v9 unknown", ValueError),
    ("gpu", "NVIDIA H100", ValueError),
])
def test_auto_machine_model_follows_the_device(platform, kind, expected,
                                              monkeypatch):
    """``"auto"`` on the CPU keeps the interpreter's host fit; on a TPU it
    is that chip's stock constants, looked up by device kind, and a kind
    with no model raises instead of borrowing another chip's numbers."""
    import jax

    from repro import hw as hwlib
    if platform != "cpu":
        monkeypatch.setattr(jax, "devices",
                            lambda *a, **k: [_Device(platform, kind)])
    ctx = StageContext(machine_model="auto", cache=plan_lib.PlanCache())
    if expected is ValueError:
        with pytest.raises(ValueError, match=kind):
            stages.CharacterizeStage().run(ctx)
        return
    stages.CharacterizeStage().run(ctx)
    if expected == "stock v5e":
        assert ctx.model is hwlib.TPU_V5E
        assert ctx.plan_kw["tpu"].hbm_bw == 819e9
    else:
        assert isinstance(ctx.model, hwlib.TpuV5e)
        assert ctx.model.hbm_bw == 1e15          # the interpreter's fit
        assert ctx.model.kernel_overhead_s != hwlib.TPU_V5E.kernel_overhead_s


@pytest.mark.parametrize("env_dir", [None, "from-env"])
def test_compile_cache_dir(env_dir, monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins untouched; unset, the cache goes to
    the fixed in-checkout directory.  Every executable is kept."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    from repro import runtime
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        if env_dir is None:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            want = str(runtime.COMPILE_CACHE_DIR)
        else:
            want = str(tmp_path / env_dir)
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
            # JAX reads the variable when it starts; stand in for that.
            jax.config.update("jax_compilation_cache_dir", want)
        assert runtime.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
        assert runtime.COMPILE_CACHE_DIR.parent == \
            pathlib.Path(__file__).resolve().parents[1]
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        cc.reset_cache()


def test_stage_context_individually_invokable():
    """The stages are usable without Deployment: a hand-built context run
    through PlanStage alone is the documented plan-only pipeline."""
    ctx = StageContext(configs=resolve_configs("jet_tagger"),
                       machine_model=None, cache=plan_lib.PlanCache())
    res = stages.PlanStage().run(ctx)
    assert res.stage == "plan" and ctx.fleet is not None
    assert not res.cached
    again = stages.PlanStage()
    ctx2 = StageContext(configs=resolve_configs("jet_tagger"),
                        machine_model=None, cache=ctx.cache)
    assert again.run(ctx2).cached                   # same cache, same question


# ---------------------------------------------------------------------------
# Unified CLI
# ---------------------------------------------------------------------------

@pytest.fixture
def no_cache_side_effect(monkeypatch):
    """``cli.main`` turns on the persistent compile cache for its process;
    these tests share a worker with others and must not."""
    from repro import runtime
    monkeypatch.setattr(runtime, "enable_compile_cache", lambda: "")


def test_cli_plan_subcommand(tmp_path, capsys, no_cache_side_effect):
    from repro import cli
    rc = cli.main(["plan", "qubit", "--target", "tpu",
                   "--out", str(tmp_path)])
    assert rc == 0
    out = capsys.readouterr().out
    assert "# qubit [tpu]" in out
    assert (tmp_path / "qubit_tpu.json").exists()


def test_cli_deploy_dry_run(tmp_path, capsys, no_cache_side_effect):
    from repro import cli
    rc = cli.main(["deploy", "jet_tagger", "--dry-run",
                   "--machine-model", "stock", "--out", str(tmp_path)])
    assert rc == 0
    out = capsys.readouterr().out
    assert "dry run" in out and "jet_tagger" in out
    assert (tmp_path / "jet_tagger_tpu.json").exists()


def test_cli_legacy_shim_still_works(tmp_path, capsys):
    """python -m repro.plan keeps its exact flags + artifacts (deprecation
    shim over the unified CLI)."""
    from repro.plan import __main__ as plan_cli
    rc = plan_cli.main(["vae", "--target", "tpu", "--out", str(tmp_path)])
    assert rc == 0
    assert (tmp_path / "vae_tpu.json").exists()
    assert plan_cli.main(["nope", "--out", str(tmp_path)]) == 2
