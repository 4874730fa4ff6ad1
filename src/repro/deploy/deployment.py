"""``Deployment`` — the one-call facade over characterize → plan →
calibrate → engines → serve.

The paper's deliverable is a *decision procedure*: characterize the target,
plan under the fitted model, deploy what fits, measure, recalibrate.  After
PRs 1–4 those pieces lived in four subsystems with four entry points; this
module is the staged pipeline that composes them:

    from repro.deploy import Deployment
    dep = Deployment.build(["jet_tagger", "tau_select"])   # chars + plans +
    router = dep.serve()                                   #   engines, wired
    router.drive(iters=20)                                 # measured traffic
    rows = dep.bench()                                     # planned-vs-meas
    dep.recalibrate()                                      # drift loop

Every step is resumable and partial pipelines are first-class:
``Deployment.build(cfgs, stop_after="plan")`` is plan-only,
``Deployment.build(plan="fleet.json")`` serves a committed artifact, and
the individual stages (:mod:`repro.deploy.stages`) can be invoked by hand
against a :class:`StageContext`.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any

from repro.deploy.stages import (PIPELINE, StageContext, StageResult,
                                 resolve_configs)
from repro.obs import NULL_TRACER, Tracer
from repro.plan.artifact import DeploymentPlan
from repro.plan.multinet import FleetPlan

_STAGE_ORDER = tuple(s.name for s in PIPELINE)


@dataclasses.dataclass(frozen=True)
class BenchRow:
    """One planned-vs-measured judgement, in the benchmark-row vocabulary."""
    net_id: str
    planned_s: float
    measured_s: float
    extra: str = ""                      # extra "k=v;" derived fields

    @property
    def ratio(self) -> float:
        return (self.planned_s / self.measured_s if self.measured_s > 0
                else float("inf"))

    @property
    def within_2x(self) -> bool:
        return 0.5 <= self.ratio <= 2.0

    @property
    def derived(self) -> str:
        return (f"planned_us={self.planned_s * 1e6:.1f};"
                f"ratio={self.ratio:.2f};within_2x={self.within_2x};"
                f"{self.extra}src=measured")

    def as_record(self, name: str | None = None) -> dict:
        """A ``benchmarks/common.emit``-shaped row for trend.py."""
        return {"name": name or f"deploy/{self.net_id}/planned-vs-measured",
                "us_per_call": round(self.measured_s * 1e6, 3),
                "derived": self.derived}


def _fault_injector(faults):
    """Coerce a ``faults=`` argument into a live ``FaultInjector``:
    an injector passes through, a ``FaultPlan`` arms fresh counters, a
    list/tuple of specs (or spec dicts) becomes an ad-hoc plan, and
    anything else is treated as a path to a saved plan artifact."""
    from repro.faults import FaultInjector, FaultPlan
    if faults is None:
        return None
    if isinstance(faults, FaultInjector):
        return faults
    if isinstance(faults, FaultPlan):
        return faults.injector()
    if isinstance(faults, (list, tuple)):
        return FaultPlan(faults=tuple(faults)).injector()
    return FaultPlan.load(faults).injector()


def _load_plan(plan) -> FleetPlan:
    """Accept a FleetPlan, a DeploymentPlan, or a path to either artifact."""
    if isinstance(plan, FleetPlan):
        return plan
    if isinstance(plan, DeploymentPlan):
        return FleetPlan.from_plan(plan)
    return FleetPlan.load(plan)          # handles v1/v2/v3 + fleet artifacts


class Deployment:
    """A built (or building) deployment: plans + engines + serving surface.

    Construct via :meth:`build`; the staged pipeline state lives on
    ``self.ctx`` and per-stage provenance (cache hits, wall time, artifact
    paths) on :attr:`stage_results`.
    """

    def __init__(self, ctx: StageContext):
        self.ctx = ctx
        self._router = None
        self._router_kw = None
        self._injector = None

    # -- construction -----------------------------------------------------
    @classmethod
    def build(cls, configs=None, *, target: str = "tpu",
              machine_model: Any = "auto", cache=None, plan=None,
              artifact_dir=None, lm_params: dict | None = None,
              stop_after: str | None = None, batch: int | None = None,
              x_scale: float = 0.05, seed: int = 0, trace=False,
              faults=None, check: bool = True, **plan_kw) -> "Deployment":
        """Run the pipeline end-to-end (or up to ``stop_after``).

        ``configs`` — one or many: edge net names, ``EdgeConfig``s,
        ``ModelConfig``s (LM arch ids resolve to their smoke config).
        ``machine_model`` — see :class:`~repro.deploy.stages.
        CharacterizeStage`: ``"auto"`` (default) calibrates the planner to
        the CPU interpreter, or takes the chip's stock constants on a TPU,
        ``None`` keeps stock constants, ``"quick"``/``"full"``
        run the characterization sweep, or pass a ``MachineModel``/path.
        ``plan`` — a committed plan artifact (path, ``DeploymentPlan`` or
        ``FleetPlan``): skips characterize+plan and serves it as-is.
        ``stop_after`` — ``"characterize"`` or ``"plan"`` for partial
        pipelines (``"plan"`` is the CLI's ``--dry-run``).
        ``trace`` — ``True`` (a fresh :class:`repro.obs.Tracer`) or a
        caller-supplied ``Tracer``: every stage emits a ``stage/<name>``
        span and the serving surface decomposes requests into
        queue/prefill/decode spans; export via :meth:`export_trace` /
        :meth:`export_prometheus`, judge via :meth:`attribution`.
        ``faults`` — a :class:`repro.faults.FaultPlan` (or injector, spec
        list, or saved-plan path): arms the plan cache's ``cache.read``
        hook during the build and is re-armed on the router by
        :meth:`replay`.
        ``check`` — ``True`` (default) runs the static design-rule
        verifier (:mod:`repro.check`) between planning and engines: a
        plan with error-severity findings raises
        :class:`repro.check.PlanVerificationError` and no engine is
        constructed.  ``check=False`` skips the gate (deliberately
        out-of-spec experiments).
        Planner knobs (``pl_budget``, ``pipeline_core_budget``, ``tpu=``,
        fleet serve knobs…) pass through ``plan_kw``.
        """
        if stop_after is not None and stop_after not in _STAGE_ORDER:
            raise ValueError(f"stop_after must be one of {_STAGE_ORDER}, "
                             f"got {stop_after!r}")
        tracer = (trace if isinstance(trace, Tracer)
                  else Tracer() if trace else NULL_TRACER)
        ctx = StageContext(
            configs=resolve_configs(configs), target=target,
            machine_model=machine_model if plan is None else None,
            cache=cache, artifact_dir=artifact_dir, plan_kw=dict(plan_kw),
            lm_params=dict(lm_params or {}), batch=batch, x_scale=x_scale,
            seed=seed, tracer=tracer, verify=check)
        if plan is not None:
            ctx.fleet = _load_plan(plan)
        dep = cls(ctx)
        dep._injector = _fault_injector(faults)
        if dep._injector is not None:
            ctx.cache.injector = dep._injector
            ctx.injector = dep._injector
            spec = dep._injector.fire("build")
            if spec is not None:
                from repro.faults import InjectedFault
                raise InjectedFault("deployment build: injected failure")
        dep._run_until(stop_after or _STAGE_ORDER[-1])
        return dep

    def _run_until(self, last: str):
        """Run pipeline stages (idempotently) through ``last``; each run
        emits a ``stage/<name>`` span carrying the cached/skipped flags."""
        for stage in PIPELINE:
            if stage.name not in self.ctx.results:
                t0 = time.perf_counter()
                res = stage.run(self.ctx)
                if self.ctx.tracer.enabled:
                    self.ctx.tracer.add(
                        f"stage/{stage.name}", t0, time.perf_counter(),
                        tenant="deploy", cached=res.cached,
                        skipped=res.skipped)
            if stage.name == last:
                break

    # -- typed views over the pipeline state ------------------------------
    @property
    def stage_results(self) -> dict[str, StageResult]:
        return dict(self.ctx.results)

    @property
    def machine_model(self):
        """The resolved model (``MachineModel``/``TpuV5e``) or None."""
        return self.ctx.model

    @property
    def fleet(self) -> FleetPlan:
        if self.ctx.fleet is None:
            raise RuntimeError("not planned yet (run the plan stage)")
        return self.ctx.fleet

    @property
    def plan(self):
        """The single-net ``DeploymentPlan``, or the ``FleetPlan`` when
        several networks were deployed together."""
        fleet = self.fleet
        return fleet.tenants[0].plan if len(fleet.tenants) == 1 else fleet

    @property
    def plans(self) -> dict[str, DeploymentPlan]:
        return {t.net_id: t.plan for t in self.fleet.tenants}

    @property
    def findings(self) -> list:
        """The design-rule findings the verify stage recorded (warnings and
        info advisories; error findings abort the build)."""
        return list(self.ctx.findings)

    @property
    def engines(self) -> dict:
        """net_id -> live engine (EdgeEngine | ContinuousBatcher), building
        them on first access if the pipeline stopped before that stage."""
        self._run_until("engines")
        return self.ctx.engines

    @property
    def tracer(self) -> Tracer:
        """The deployment's span sink (:data:`repro.obs.NULL_TRACER` unless
        built with ``trace=``)."""
        return self.ctx.tracer

    # -- serving ----------------------------------------------------------
    def serve(self, *, shed_after: int | None = None,
              drift_threshold: float | None = None,
              drift_min_samples: int = 5, slo: Any = True,
              defer_limit: int = 4, resilience: Any = True,
              fresh: bool = False):
        """The fleet behind a :class:`repro.serve.Router`, wired from the
        plan's serve section and this deployment's engines.  Memoized —
        repeated calls with the same knobs return the same live router;
        different knobs (or ``fresh=True``) rebuild it (engines and their
        compiled tiles are reused; router metrics start over).

        ``slo`` — ``True`` (default) attaches a
        :class:`repro.obs.slo.SloMonitor` with per-tenant p95/p99 budgets
        from each plan's serve section, enabling the router's SLO-aware
        priority scheduling; pass a ready monitor to customize windows and
        budgets, or ``False``/``None`` for the pre-SLO behavior.
        ``resilience`` — ``True`` (default) attaches a
        :class:`repro.serve.Supervisor` wired from each plan's
        ``serve["resilience"]`` knobs (per-tenant circuit breakers,
        bounded retries, deadline audit, the degradation ladder); pass a
        ready supervisor to customize, or ``False``/``None`` for the
        pre-supervisor behavior (fault isolation in the router remains).
        """
        from repro.obs.slo import SloMonitor
        from repro.serve import Router
        kw = {"shed_after": shed_after, "drift_threshold": drift_threshold,
              "drift_min_samples": drift_min_samples, "slo": slo,
              "defer_limit": defer_limit, "resilience": resilience}
        if self._router is None or fresh or kw != self._router_kw:
            tracer = (self.ctx.tracer
                      if self.ctx.tracer is not NULL_TRACER else None)
            monitor = slo if isinstance(slo, SloMonitor) else (
                SloMonitor.from_fleet(self.fleet, tracer=tracer)
                if slo else None)
            self._router = Router.from_fleet(
                self.fleet, engines=self.engines, cache=self.ctx.cache,
                tracer=tracer, slo=monitor, defer_limit=defer_limit,
                shed_after=shed_after, drift_threshold=drift_threshold,
                drift_min_samples=drift_min_samples,
                resilience=resilience or None)
            self._router_kw = kw
        return self._router

    @property
    def slo(self):
        """The live router's SLO monitor (None before :meth:`serve` or when
        serving with ``slo=False``)."""
        return self._router.slo if self._router is not None else None

    def health(self) -> dict:
        """The served fleet's resilience health — ``Router.health()``:
        per-tenant failure counters, breaker state, degradation-ladder
        level, plus fleet replan-failure counts.  Empty before
        :meth:`serve`."""
        return self._router.health() if self._router is not None else {}

    def replay(self, scenario: str = "steady", *, duration_s: float = 0.25,
               seed: int = 0, speed: float = 1.0, requests=None,
               json_dir=None, faults=None, **scenario_kw):
        """Open-loop traffic replay through the served fleet (see
        :mod:`repro.obs.workload`): generate (or take) a trace, warm the
        router, fire arrivals on the wall clock, and return the
        :class:`~repro.obs.workload.ReplayReport` (per-request e2e latency
        + scheduling lag).  ``requests`` overrides the generator with an
        explicit trace (e.g. :func:`repro.obs.workload.load_trace`);
        ``json_dir`` additionally writes the per-tenant
        ``BENCH_serve_<net>__<scenario>.json`` tail snapshots.

        ``faults`` — a :class:`repro.faults.FaultPlan` (or injector, spec
        list, or saved-plan path) armed on the router AFTER warmup, so
        compile-time traffic never consumes scheduled fault indices: the
        chaos replay.  Defaults to the plan given to :meth:`build`."""
        from repro.obs import workload
        router = self.serve()
        inputs = router.warmup()
        injector = (_fault_injector(faults) if faults is not None
                    else self._injector)
        if injector is not None:
            router.arm_faults(injector)
        if requests is None:
            tenants = {t.net_id: t.plan.kind for t in self.fleet.tenants}
            requests = workload.make_scenario(
                scenario, tenants, duration_s=duration_s, seed=seed,
                **scenario_kw)
        report = workload.replay(router, requests, inputs=inputs,
                                 speed=speed)
        report.scenario = scenario
        if json_dir is not None:
            workload.write_replay_snapshots(
                report, json_dir, scenario=scenario, slo=router.slo,
                meta={"source": "Deployment.replay", "seed": seed,
                      "duration_s": duration_s})
        return report

    # -- measurement ------------------------------------------------------
    def bench(self, *, iters: int = 5, warmup: int = 1) -> list[BenchRow]:
        """Planned-vs-measured rows for every edge tenant (trend.py's row
        shape via :meth:`BenchRow.as_record`): each engine is warmed up,
        timed for ``iters`` calls, and judged against its plan's estimate
        (median measured, the repo-wide robust statistic)."""
        import jax.numpy as jnp

        from repro.serve.engine import EdgeEngine
        rows = []
        for tp in self.fleet.tenants:
            eng = self.engines[tp.net_id]
            if not isinstance(eng, EdgeEngine):
                continue                 # LM latency includes queue wait
            x = jnp.ones((eng.cfg.batch, eng.cfg.dims[0]), jnp.float32)
            for _ in range(warmup):
                eng.infer(x)
            eng.reset_measurements()
            for _ in range(iters):
                eng.infer(x)
            groups = tp.plan.groups()
            rows.append(BenchRow(
                net_id=tp.net_id, planned_s=tp.plan.est_latency_s,
                measured_s=eng.measured_p50_s,
                extra=f"fuse_groups={len(groups)};"))
        return rows

    # -- the drift loop, behind one method --------------------------------
    def recalibrate(self, *, budget_factor: float | None = None) -> FleetPlan:
        """Feed measured latencies back and replan the fleet in place (the
        PR-3 drift loop): router metrics when the deployment is serving,
        engine measurements otherwise.  Costs and budgets move; tiles,
        regimes and engines stay.  Returns (and adopts) the new fleet.

        Degradation rung for the planner: when recalibration fails while a
        FITTED machine model is in play, the deployment drops to stock
        constants (``degrade/machine_model`` audit span), keeps the current
        fleet, and returns it — a sick calibration must not take down
        serving.  With stock constants already in play the failure is
        re-raised (there is no rung left)."""
        import time as _time
        try:
            return self._recalibrate(budget_factor=budget_factor)
        except Exception as exc:
            # Usage guidance ("nothing measured yet") is not a rung; with
            # stock constants already in play there is no rung left either.
            if self.ctx.model is None or "nothing measured" in str(exc):
                raise
            t0 = _time.perf_counter()
            self.ctx.model = None
            if self.ctx.tracer.enabled:
                self.ctx.tracer.add(
                    "degrade/machine_model", t0, _time.perf_counter(),
                    tenant="deploy", error=str(exc)[:160])
            return self.ctx.fleet

    def _recalibrate(self, *, budget_factor: float | None) -> FleetPlan:
        from repro.plan import calibrate
        if self._router is not None and any(
                t.metrics.count for t in self._router._tenants.values()):
            new_fleet = self._router.replan_fleet(
                budget_factor=budget_factor)
        else:
            measurements = calibrate.measurements_from_engines(self.engines)
            if not measurements:
                raise RuntimeError(
                    "nothing measured yet: serve traffic or run .bench() "
                    "before recalibrating")
            new_fleet = calibrate.recalibrate_fleet(
                self.fleet, measurements, cache=self.ctx.cache,
                budget_factor=budget_factor)
            if self._router is not None:
                # A live router must not keep serving the pre-recalibration
                # plans/budgets just because its own metrics were empty.
                self._router.adopt_fleet(new_fleet)
            else:
                for tp in new_fleet.tenants:
                    eng = self.ctx.engines.get(tp.net_id)
                    if eng is not None and hasattr(eng, "plan"):
                        eng.plan = tp.plan
        # No put_fleet: feedback already parked the calibrated tenant plans
        # in the cache, and the next fleet-cache hit re-adopts them.
        self.ctx.fleet = new_fleet
        return new_fleet

    # -- observability ----------------------------------------------------
    def export_trace(self, path="trace.json"):
        """Write the span stream as a Chrome/Perfetto ``trace.json``
        (load at https://ui.perfetto.dev); returns the path."""
        from repro.obs import write_chrome
        return write_chrome(self.tracer.spans, path,
                            dropped=self.tracer.dropped)

    def export_prometheus(self, path="metrics.prom"):
        """Write per-(tenant, kind) span aggregates as a Prometheus
        text-exposition snapshot — including the tracer's dropped-span
        counter and, once serving, the per-tenant SLO families and the
        ``repro_resilience_*`` health families; returns the path."""
        from repro.obs import aggregate, write_prometheus
        slo = self.slo
        return write_prometheus(
            aggregate(self.tracer.spans), path,
            dropped=self.tracer.dropped if self.tracer.enabled else None,
            slo=slo.snapshot() if slo is not None else None,
            profile=self.profile() or None,
            resilience=self.health() or None)

    def attribution(self):
        """Plan-vs-measured rows per (tenant, span kind) — see
        :func:`repro.obs.attribution`."""
        from repro.obs import attribution as attr
        return attr(self.plans, self.tracer.spans)

    def format_attribution(self) -> str:
        from repro.obs import format_attribution
        return format_attribution(self.attribution(), slo=self.slo,
                                  profile=self.profile())

    # -- roofline profiling -----------------------------------------------
    def profile_hw(self):
        """The roofline ceilings this deployment was planned under: the
        fitted :class:`MachineModel`'s substituted TPU terms when one was
        characterized, else the stock :data:`repro.hw.TPU_V5E` — the same
        single source of truth the planner's cost model reads."""
        from repro import hw as hwlib
        model = self.ctx.model
        if model is None:
            return hwlib.TPU_V5E
        tpu = getattr(model, "tpu", None)
        return tpu() if callable(tpu) else model

    def _profile_stats(self) -> dict:
        """Measured ``(tenant, kind)`` windows: the tracer's span stream
        when tracing is on, else the engines' always-on service-time
        windows (``span_stats()``) — profiling must not require
        ``trace=True``."""
        from repro.obs import aggregate
        if self.tracer.enabled and self.tracer.spans:
            return aggregate(self.tracer.spans)
        stats = {}
        for nid, eng in self.ctx.engines.items():
            for kind, agg in eng.span_stats().items():
                stats[(nid, kind)] = agg
        return stats

    def profile(self, *, hw=None) -> list:
        """Roofline-attributed profile rows (:func:`repro.obs.profile.
        profile`): per measured (tenant, span-kind) window and per fusion
        group — achieved FLOP/s and bytes/s, the roofline ceiling, a
        compute/memory/launch bound classification, the roofline fraction
        in (0, 1], and the per-tenant measured LARE.  Empty until traffic
        has been served (or :meth:`bench` has run)."""
        from repro.obs import profile as prof
        return prof(self.plans, self._profile_stats(),
                    hw=hw if hw is not None else self.profile_hw())

    def format_profile(self) -> str:
        from repro.obs import format_profile
        return format_profile(self.profile())

    def hlo_overhead(self) -> dict:
        """Model-FLOPs vs compiled-HLO-FLOPs per tenant, on the ACTUAL
        serving executables (:func:`repro.launch.hlo_analysis.
        hlo_overhead`): the EdgeEngine's jitted planned forward and the
        batcher's jitted decode step.  The batcher decodes all its slots
        per step, so its model FLOPs scale by the slot count."""
        from repro.launch.hlo_analysis import hlo_overhead as _overhead
        out = {}
        for nid, eng in self.engines.items():
            plan = self.plans.get(nid)
            if plan is None or not getattr(plan, "layers", None):
                continue
            model_flops = plan.work()["flops"]
            slots = getattr(eng, "slots", None)
            if slots:                    # ContinuousBatcher: vmapped slots
                model_flops *= slots
            out[nid] = _overhead(model_flops, eng)
        return out

    # -- reporting --------------------------------------------------------
    def summary(self) -> str:
        """Human-readable stage + tenant table (the CLI's deploy report)."""
        lines = ["stages:"]
        for name in _STAGE_ORDER:
            if name in self.ctx.results:
                lines.append(f"  {self.ctx.results[name]}")
        if self.ctx.fleet is not None:
            lines.append("tenants:")
            for t in self.ctx.fleet.tenants:
                lines.append(
                    f"  {t.net_id:<14} kind={t.plan.kind:<5} "
                    f"planned={t.plan.est_latency_s * 1e6:9.1f}us "
                    f"budget={t.latency_budget_s * 1e6:9.1f}us "
                    f"groups={len(t.plan.groups())}")
        if "verify" in self.ctx.results:
            res = self.ctx.results["verify"]
            if res.skipped:
                lines.append("check: skipped (check=False)")
            elif not self.ctx.findings:
                lines.append("check: clean (all design rules hold)")
            else:
                lines.append(f"check: {res.detail}")
                for f in self.ctx.findings:
                    lines.append(f"  {f}")
        if self.tracer.enabled:
            kinds: dict[str, int] = {}
            for s in self.tracer.spans:
                kinds[s.name] = kinds.get(s.name, 0) + 1
            per_kind = " ".join(f"{k}={n}" for k, n in sorted(kinds.items()))
            lines.append(f"tracing: {len(self.tracer.spans)} spans "
                         f"({self.tracer.dropped} dropped) {per_kind}")
        slo = self.slo
        if slo is not None:
            counts = slo.violation_counts()
            total = sum(counts.values())
            if total:
                per = " ".join(f"{t}={n}" for t, n in sorted(counts.items())
                               if n)
                lines.append(f"slo: {total} violation event(s) {per}")
            else:
                lines.append("slo: ok (no violation events)")
        health = self.health()
        if health:
            tenants = health.get("tenants", {})
            sick = {t: st for t, st in tenants.items()
                    if st.get("failures") or st.get("degrade_level")
                    or st.get("state", "closed") != "closed"}
            if sick:
                lines.append("health:")
                for t, st in sorted(sick.items()):
                    bits = [f"failures={st.get('failures', 0)}",
                            f"level={st.get('degrade_level', 0)}"]
                    if "state" in st:
                        bits.append(f"breaker={st['state']} "
                                    f"opens={st.get('breaker_opens', 0)} "
                                    f"recloses={st.get('breaker_recloses', 0)}")
                    lines.append(f"  {t:<14} " + " ".join(bits))
            else:
                supervised = ("supervised" if health.get("supervised")
                              else "unsupervised")
                lines.append(f"health: ok ({supervised}; no failures, "
                             f"all breakers closed, ladder at level 0)")
            if health.get("replan_failures"):
                lines.append(f"health: {health['replan_failures']} replan "
                             f"failure(s) — serving on the current fleet")
        prows = ([r for r in self.profile() if r.group is None]
                 if self.ctx.fleet is not None else [])
        if prows:
            lines.append("profile:")
            for r in prows:
                frac = (f"{r.roofline_fraction:.3f}"
                        if r.roofline_fraction is not None else "-")
                mlare = (f" mLARE={r.measured_lare:.1f}"
                         if r.measured_lare is not None else "")
                lines.append(
                    f"  {r.tenant:<14} {r.kind:<14} frac={frac} "
                    f"bound={r.bound}{mlare}")
        return "\n".join(lines)
