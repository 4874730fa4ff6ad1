"""The facade's stages: characterize → plan → verify → engines, as
explicit objects.

Each stage is individually invokable: it reads its typed inputs off a
:class:`StageContext`, writes exactly one output back (plus an optional
artifact under ``ctx.artifact_dir``), and returns a :class:`StageResult`
describing what happened (output, wall time, whether it was served from
cache, where the artifact landed).  :class:`repro.deploy.Deployment` runs
them in order; partial pipelines — plan-only, serve-from-a-committed-plan —
just run (or skip) stages individually instead of copy-pasting glue.

Stage contract:

=============== =============================== =======================
stage           inputs (ctx fields)             output (ctx field)
=============== =============================== =======================
characterize    machine_model spec, target      model + plan_kw hw knobs
plan            configs, target, plan_kw, cache fleet (FleetPlan)
verify          fleet, plan_kw, verify flag     findings (design rules)
engines         fleet, configs, lm_params       engines {net_id: engine}
=============== =============================== =======================
"""

from __future__ import annotations

import dataclasses
import pathlib
import time
from typing import Any

from repro.obs import NULL_TRACER
from repro.plan import PlanCache, default_cache
from repro.plan.multinet import FleetPlan, plan_fleet

# Sweep-keyed memo for full characterization runs: every Deployment in the
# process shares one fitted MachineModel per sweep density instead of
# re-timing the microbenchmarks.
_SWEEP_MEMO: dict[str, Any] = {}

_MODEL_ARTIFACT = "machine_model.json"


@dataclasses.dataclass(frozen=True)
class StageResult:
    """What one stage did: its output, provenance and cost."""
    stage: str
    output: Any
    cached: bool = False                 # served from a cache/memo/artifact
    skipped: bool = False                # inputs made the stage a no-op
    artifact: pathlib.Path | None = None
    wall_s: float = 0.0
    detail: str = ""

    def __str__(self) -> str:
        state = ("cached" if self.cached else
                 "skipped" if self.skipped else "ran")
        art = f" -> {self.artifact}" if self.artifact else ""
        det = f" ({self.detail})" if self.detail else ""
        return f"{self.stage:<12} {state:<7} {self.wall_s:7.2f}s{det}{art}"


@dataclasses.dataclass
class StageContext:
    """Everything the stages read and write — the pipeline's typed state.

    Inputs are set by :meth:`repro.deploy.Deployment.build`; each stage
    fills in its output field (``model``/``fleet``/``engines``) and records
    its :class:`StageResult` under ``results``.
    """
    configs: list = dataclasses.field(default_factory=list)
    target: str = "tpu"
    machine_model: Any = "auto"          # spec; resolved by CharacterizeStage
    cache: PlanCache | None = None
    artifact_dir: pathlib.Path | None = None
    plan_kw: dict = dataclasses.field(default_factory=dict)
    lm_params: dict = dataclasses.field(default_factory=dict)
    batch: int | None = None
    x_scale: float = 0.05
    seed: int = 0
    tracer: Any = NULL_TRACER            # repro.obs.Tracer when tracing
    verify: bool = True                  # run the design-rule gate
    injector: Any = None                 # repro.faults.FaultInjector | None
    # stage outputs
    model: Any = None                    # MachineModel | TpuV5e | None
    fleet: FleetPlan | None = None
    findings: list = dataclasses.field(default_factory=list)
    engines: dict = dataclasses.field(default_factory=dict)
    results: dict = dataclasses.field(default_factory=dict)

    def __post_init__(self):
        if self.cache is None:
            self.cache = default_cache()
        if self.artifact_dir is not None:
            self.artifact_dir = pathlib.Path(self.artifact_dir)

    def record(self, res: StageResult) -> StageResult:
        self.results[res.stage] = res
        return res


def resolve_configs(specs) -> list:
    """Accept one or many config specs; return concrete config objects.

    A spec is an ``EdgeConfig``/``ModelConfig``/``DataflowGraph`` passed
    through as-is, or a string: an ``EDGE_NETS`` name, or an LM arch id
    (resolved to its CPU-serveable ``smoke`` config; pass the full
    ``configs.get(name).config`` object explicitly to plan at scale).
    """
    from repro.models import edge
    if specs is None:
        return []
    if not isinstance(specs, (list, tuple)):
        specs = [specs]
    out = []
    for s in specs:
        if not isinstance(s, str):
            out.append(s)
            continue
        name = s[3:] if s.startswith("lm:") else s
        if not s.startswith("lm:") and name in edge.EDGE_NETS:
            out.append(edge.edge_config(name))
            continue
        try:
            from repro import configs as configs_lib
            out.append(configs_lib.get(name).smoke)
        except ModuleNotFoundError as exc:
            # Only the registry's own lookup miss means "unknown name"; a
            # config module failing to import one of ITS dependencies must
            # surface as the real error, not a misleading name complaint.
            if exc.name is None or not exc.name.startswith("repro.configs"):
                raise
            raise ValueError(
                f"unknown network {s!r}: not an edge net "
                f"({sorted(edge.EDGE_NETS)}) and not an LM arch id") from None
    return out


class CharacterizeStage:
    """Resolve the ``machine_model`` spec into fitted planner knobs.

    Spec values:

    * ``None`` / ``"stock"`` — hand-tuned ``hw.py`` constants (skip);
    * ``"auto"`` — on the CPU, the fast host calibration
      (:func:`repro.plan.calibrated_cpu_model`, memoized per process): the
      gemm term fitted to the Pallas interpreter; on a TPU, the stock
      constants of that chip (:func:`repro.hw.device_model`, which raises
      for a device kind it does not know);
    * ``"quick"`` / ``"full"`` — the full characterization sweep at that
      density (``repro.characterize.characterize``, memoized per sweep;
      loaded from ``<artifact_dir>/machine_model.json`` when one exists);
    * a path — ``MachineModel.load(path)``;
    * a ``MachineModel`` — used as-is (``machine_model=`` planner knob);
    * a ``TpuV5e`` — used as-is (``tpu=`` planner knob).
    """

    name = "characterize"
    inputs = ("machine_model", "target")
    output = "model"

    def run(self, ctx: StageContext) -> StageResult:
        from repro import hw as hwlib
        from repro.characterize import MachineModel
        spec = ctx.machine_model
        t0 = time.perf_counter()

        def done(model, *, cached=False, skipped=False, artifact=None,
                 detail=""):
            ctx.model = model
            if model is None:
                pass
            elif isinstance(model, hwlib.TpuV5e):
                ctx.plan_kw.setdefault("tpu", model)
            else:
                ctx.plan_kw.setdefault("machine_model", model)
            return ctx.record(StageResult(
                stage=self.name, output=model, cached=cached, skipped=skipped,
                artifact=artifact, wall_s=time.perf_counter() - t0,
                detail=detail))

        if spec is None or spec == "stock":
            return done(None, skipped=True, detail="stock hw constants")
        if isinstance(spec, hwlib.TpuV5e):
            return done(spec, cached=True, detail="caller-supplied tpu model")
        if isinstance(spec, MachineModel):
            return done(spec, cached=True,
                        detail=f"caller-supplied {spec.version[:12]}")
        if spec == "auto":
            import jax
            device = jax.devices()[0]
            if device.platform != "cpu":
                return done(hwlib.device_model(device), cached=True,
                            detail=f"stock {device.device_kind} constants")
            from repro.plan import calibrate
            cached = calibrate.cpu_model_memoized(batch=ctx.batch or 8)
            model = calibrate.calibrated_cpu_model(batch=ctx.batch or 8)
            return done(model, cached=cached, detail="host gemm calibration")
        if spec in ("quick", "full"):
            artifact = None
            if ctx.artifact_dir is not None:
                artifact = ctx.artifact_dir / _MODEL_ARTIFACT
                if artifact.exists():
                    model = MachineModel.load(artifact)
                    if _artifact_matches(model, spec):
                        return done(model, cached=True, artifact=artifact,
                                    detail=f"{spec} (loaded)")
            if spec in _SWEEP_MEMO:
                return done(_SWEEP_MEMO[spec], cached=True,
                            detail=f"{spec} sweep (memo)")
            from repro.characterize import characterize
            model = characterize(sweep=spec, tracer=ctx.tracer)
            _SWEEP_MEMO[spec] = model
            if artifact is not None:
                model.save(artifact)
            return done(model, artifact=artifact, detail=f"{spec} sweep")
        if isinstance(spec, (str, pathlib.Path)):
            model = MachineModel.load(spec)
            return done(model, cached=True,
                        detail=f"loaded {pathlib.Path(spec).name}")
        if isinstance(spec, dict):           # CLI: explicit sweep options
            from repro.characterize import characterize
            model = characterize(tracer=ctx.tracer, **spec)
            artifact = None
            if ctx.artifact_dir is not None:
                artifact = ctx.artifact_dir / _MODEL_ARTIFACT
                model.save(artifact)
            return done(model, artifact=artifact,
                        detail=f"sweep={spec.get('sweep', 'quick')}")
        raise TypeError(f"cannot resolve machine_model spec {spec!r}")


def _artifact_matches(model, spec: str) -> bool:
    """Whether an on-disk MachineModel can stand in for a fresh ``spec``
    sweep: fitted at the requested density, on THIS host and jax build.
    Anything else is the staleness the drift machinery exists to catch —
    refit rather than silently adopt another machine's constants."""
    import platform

    import jax
    prov = model.provenance
    return (prov.get("sweep") == spec
            and prov.get("host") == platform.node()
            and prov.get("jax") == jax.__version__)


class PlanStage:
    """Plan the configs as one (possibly single-tenant) fleet.

    Always goes through :func:`repro.plan.plan_fleet`, so single nets and
    fleets share one code path, every LM tenant gets its serve-section
    batching policy, and the fleet cache answers repeat questions (the
    ``cached`` flag on the result tells you it did).
    """

    name = "plan"
    inputs = ("configs", "target", "plan_kw", "cache")
    output = "fleet"

    def run(self, ctx: StageContext) -> StageResult:
        t0 = time.perf_counter()
        if ctx.fleet is not None:            # serve-from-artifact pipelines
            return ctx.record(StageResult(
                stage=self.name, output=ctx.fleet, cached=True,
                wall_s=time.perf_counter() - t0,
                detail="pre-built plan supplied"))
        if not ctx.configs:
            raise ValueError("plan stage needs at least one config "
                             "(or a pre-built plan=)")
        key = fleet_key(ctx)
        cached = ctx.cache.get_fleet(key) is not None
        ctx.fleet = plan_fleet(ctx.configs, target=ctx.target,
                               batch=ctx.batch, cache=ctx.cache,
                               **ctx.plan_kw)
        artifact = None
        if ctx.artifact_dir is not None:
            if len(ctx.fleet.tenants) == 1:
                t = ctx.fleet.tenants[0]
                artifact = t.plan.save(
                    ctx.artifact_dir / f"{t.net_id}_{ctx.target}.json")
            else:
                artifact = ctx.fleet.save(
                    ctx.artifact_dir
                    / f"fleet_{ctx.fleet.name}_{ctx.target}.json")
        return ctx.record(StageResult(
            stage=self.name, output=ctx.fleet, cached=cached,
            artifact=artifact, wall_s=time.perf_counter() - t0,
            detail=f"{len(ctx.fleet.tenants)} tenant(s), "
                   f"key={ctx.fleet.key[:12]}"))


def fleet_key(ctx: StageContext) -> str:
    """The serve-scoped fleet cache key this context's plan stage will use
    (delegates to the plan layer's own key derivation)."""
    from repro.plan.multinet import fleet_store_key
    return fleet_store_key(ctx.configs, target=ctx.target, batch=ctx.batch,
                           **ctx.plan_kw)


class VerifyStage:
    """The fail-closed design-rule gate between planning and engines.

    Runs :func:`repro.check.check_fleet` — the full layer-1 plan rules plus
    the layer-2 kernel contracts — over the planned (or artifact-loaded)
    fleet BEFORE any engine is constructed.  Error-severity findings raise
    :class:`repro.check.PlanVerificationError`; warnings and info findings
    accumulate on ``ctx.findings`` and surface in ``Deployment.summary()``.

    ``Deployment.build(check=False)`` records the stage as skipped (the
    escape hatch for deliberately-out-of-spec experiments).  The stage is
    fault-injectable at the ``build`` hook site with ``tenant="verify"`` —
    chaos drills can make the gate itself fail without corrupting a plan.
    """

    name = "verify"
    inputs = ("fleet", "plan_kw", "verify")
    output = "findings"

    def run(self, ctx: StageContext) -> StageResult:
        from repro.check import PlanVerificationError, check_fleet
        t0 = time.perf_counter()
        if not ctx.verify:
            return ctx.record(StageResult(
                stage=self.name, output=[], skipped=True,
                wall_s=time.perf_counter() - t0, detail="check=False"))
        if ctx.fleet is None:
            raise ValueError("verify stage needs a planned fleet "
                             "(run the plan stage first)")
        if ctx.injector is not None:
            spec = ctx.injector.fire("build", tenant="verify")
            if spec is not None:
                from repro.faults import InjectedFault
                raise InjectedFault("verify stage: injected failure")
        ctx.findings = check_fleet(ctx.fleet, tpu=ctx.plan_kw.get("tpu"))
        errors = [f for f in ctx.findings if f.severity == "error"]
        counts = {}
        for f in ctx.findings:
            counts[f.severity] = counts.get(f.severity, 0) + 1
        detail = (", ".join(f"{n} {s}" for s, n in sorted(counts.items()))
                  or "clean")
        res = ctx.record(StageResult(
            stage=self.name, output=list(ctx.findings),
            wall_s=time.perf_counter() - t0, detail=detail))
        if errors:
            raise PlanVerificationError(ctx.findings)
        return res


class EngineStage:
    """Build one live engine per tenant: quantize + calibrate + jit.

    Edge tenants get an :class:`~repro.serve.engine.EdgeEngine` executing
    exactly the tenant's planned Pallas blocks (weights int8-quantized with
    activation scales calibrated against the float reference); LM tenants
    get a plan-driven :class:`~repro.serve.engine.ContinuousBatcher`.  LM
    weights come from ``ctx.lm_params[net_id]``; when absent they are
    seed-initialized (serving smoke — real deployments pass trained params).
    """

    name = "engines"
    inputs = ("fleet", "configs", "lm_params")
    output = "engines"

    def run(self, ctx: StageContext) -> StageResult:
        import jax

        from repro.models import api, edge as edge_lib
        from repro.serve.engine import ContinuousBatcher, EdgeEngine
        if ctx.fleet is None:
            raise ValueError("engine stage needs a planned fleet "
                             "(run the plan stage first)")
        t0 = time.perf_counter()
        by_name = {getattr(c, "name", None): c for c in ctx.configs}
        for tp in ctx.fleet.tenants:
            if tp.net_id in ctx.engines:
                continue
            plan = tp.plan
            cfg = by_name.get(plan.network)
            if plan.kind == "lm":
                if tp.net_id in ctx.lm_params:
                    cfg, params = ctx.lm_params[tp.net_id]
                else:
                    if cfg is None:
                        raise ValueError(
                            f"LM tenant {tp.net_id!r} needs its config: "
                            f"pass lm_params={{net_id: (cfg, params)}} or "
                            f"build from config objects")
                    params = api.init(cfg, jax.random.PRNGKey(ctx.seed))
                ctx.engines[tp.net_id] = ContinuousBatcher(cfg, params,
                                                           plan=plan)
            else:
                if cfg is None:
                    cfg = edge_lib.edge_config(plan.network)
                ctx.engines[tp.net_id] = EdgeEngine(
                    cfg, plan=plan, x_scale=ctx.x_scale, seed=ctx.seed)
        kinds = [tp.plan.kind for tp in ctx.fleet.tenants]
        return ctx.record(StageResult(
            stage=self.name, output=ctx.engines,
            wall_s=time.perf_counter() - t0,
            detail=f"{kinds.count('edge')} edge + {kinds.count('lm')} lm"))


PIPELINE = (CharacterizeStage(), PlanStage(), VerifyStage(), EngineStage())
STAGES = {s.name: s for s in PIPELINE}
