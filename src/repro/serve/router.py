"""Request router: multi-tenant dispatch over a co-residency FleetPlan.

The router is the runtime half of :func:`repro.plan.plan_fleet`: one
:class:`~repro.serve.tenant.Tenant` (engine + metrics + budget) per
co-resident network, dispatch by net id, and per-tenant latency-budget
enforcement.

Two dispatch surfaces, matching the two serving paths:

* **edge** — :meth:`infer` is synchronous: route to the tenant's
  :class:`EdgeEngine`, time the call, record it against the tenant's budget.
* **lm** — :meth:`submit` enqueues a request on the tenant's plan-driven
  :class:`ContinuousBatcher`; :meth:`step` ticks every LM tenant once
  (round-robin, so one tenant's burst cannot starve another) and completes
  request latencies as they drain.  The idle path blocks in
  ``queue.get(timeout=...)`` instead of spinning.

Budget enforcement is two-level: every over-budget request increments the
tenant's violation counters, and with ``shed_after=k`` the router starts
REFUSING (:class:`TenantOverBudget`) a tenant's traffic after ``k``
consecutive violations — shedding one misbehaving tenant instead of letting
it drag every co-resident net past its deadline.  Shedding is a half-open
circuit: after ``k`` consecutive refusals one probe request is admitted; a
within-budget probe resets the violation streak and re-opens the tenant, an
over-budget probe keeps it shed.  :meth:`reset_metrics` re-opens
unconditionally.

Two further plan-driven controls:

* **Queue-depth admission** — an LM tenant whose pending queue has reached
  its plan's ``serve["max_queue_depth"]`` bound is refused
  (:class:`TenantQueueFull`) at submit time, BEFORE the backlog grows past
  the point where the tail request could still meet any latency budget —
  back-pressure at admission instead of shedding after the damage.

* **Drift watcher** — with ``drift_threshold=r`` the router compares a
  tenant's measured service time against its planned latency after every
  completed request; when the ratio leaves ``[1/r, r]`` (and
  ``drift_min_samples`` observations exist) it triggers a FLEET-WIDE
  recalibration: :func:`repro.plan.calibrate.recalibrate_fleet` feeds the
  measured latencies back into the plan cache and replans the ``FleetPlan``
  in place (costs + budgets move; tiles and column assignments stay), and
  the router swaps the replanned fleet into its live tenants.  This closes
  the characterize -> plan -> serve -> drift -> replan loop fleet-wide.
  The measured quantity is chosen per tenant kind so it is the SAME
  quantity the plan estimates: edge tenants feed request p50 (their request
  IS the planned pipeline), LM tenants feed the batcher's decomposed
  **decode-step** p50 (an LM plan's graph models one decode step; an LM
  request's end-to-end latency includes queue wait, so recalibrating from
  it under a burst would bake transient load into the cost model).  The
  decode-step windows are maintained by the batcher unconditionally —
  LM drift works with tracing disabled.

* **SLO-aware priority scheduling** — with ``slo=`` (a
  :class:`repro.obs.slo.SloMonitor`) the router feeds every completed
  request into the monitor and turns its burn-rate signal into scheduling:
  LM tenants tick priority-first, and while any tenant is actively burning
  its p95 budget, strictly lower-priority tenants admit nothing
  (``admit_cap=0`` — live slots keep decoding) and have their queue-depth
  bound halved.  Deferral ages out after ``defer_limit`` consecutive ticks
  so a backlog is slowed, never starved; shedding remains the last resort.
  Every deferral lands as a ``sched/defer`` audit span.

* **Fault isolation & the supervisor** — engine exceptions during
  :meth:`infer` or an LM tick are CAUGHT: the failure is booked against
  that tenant (``TenantMetrics.failures``, a ``fault/<kind>`` audit span)
  and surfaced as :class:`TenantFaulted`, while every co-resident tenant
  keeps draining.  With ``resilience=True`` (what ``Deployment.serve``
  passes) a :class:`~repro.serve.resilience.Supervisor` additionally gives
  each tenant bounded retry-with-backoff, per-request deadlines from the
  plan's ``serve["slo"]`` budget, a circuit breaker
  (:class:`TenantBreakerOpen` while open; deterministic half-open probe),
  and the fused → per-layer → shed degradation ladder.  A drift-watcher
  replan that FAILS falls back to the current fleet plan with a
  ``degrade/replan`` audit span instead of propagating; explicit
  :meth:`replan_fleet` calls still raise.  :meth:`arm_faults` threads a
  deterministic :class:`repro.faults.FaultInjector` through every engine
  hook for chaos testing.

Pass ``tracer=`` (a :class:`repro.obs.Tracer`) to thread request-grain
spans through every tenant engine: edge requests emit ``request`` around
``router.admit``, the engine's ``infer`` (``engine.dispatch`` /
``engine.wait`` / ``engine.readback``) and ``router.account``, all keyed by
one request id the router draws from the tracer; LM requests decompose
into ``queue`` / ``prefill_chunk`` / ``decode_step`` / ``request`` spans
keyed by the request id as trace id.
``report()`` attaches each engine's per-kind service-time aggregates under
``"spans"`` regardless of tracing, so snapshots carry the decomposition.
"""

from __future__ import annotations

import time
from typing import Iterable

from repro.faults import InjectedFault, fault_kind
from repro.obs import NULL_TRACER
from repro.obs.slo import priority_rank
from repro.serve.resilience import Supervisor
from repro.serve.tenant import Tenant, edge_tenant, lm_tenant


class TenantOverBudget(RuntimeError):
    """Raised when a shedding router refuses a persistently late tenant."""


class TenantQueueFull(TenantOverBudget):
    """Raised when a tenant's backlog hits its plan's queue-depth bound."""


class TenantFaulted(TenantOverBudget):
    """Raised when a tenant's request FAILED (engine exception, non-finite
    output) rather than ran late.  The failure is already booked against
    the tenant; co-resident tenants are unaffected."""


class TenantBreakerOpen(TenantFaulted):
    """Raised while a tenant's circuit breaker refuses traffic (open state,
    between half-open probes)."""


class Router:
    def __init__(self, tenants: Iterable[Tenant], *,
                 shed_after: int | None = None, fleet=None,
                 drift_threshold: float | None = None,
                 drift_min_samples: int = 5, cache=None, tracer=None,
                 slo=None, defer_limit: int = 4, resilience=None):
        self._tenants: dict[str, Tenant] = {}
        for t in tenants:
            if t.net_id in self._tenants:
                raise ValueError(f"duplicate tenant id {t.net_id!r}")
            self._tenants[t.net_id] = t
        self.tracer = tracer if tracer is not None else NULL_TRACER
        if tracer is not None:
            # Retrofit the shared tracer onto every tenant engine, labeled
            # by NET ID (the engine's own cfg.name default can collide when
            # duplicate nets carry a '#index').
            for t in self._tenants.values():
                t.engine.tracer = tracer
                t.engine.trace_label = t.net_id
        self.shed_after = shed_after
        self.fleet = fleet
        if drift_threshold is not None and drift_threshold <= 1.0:
            raise ValueError(f"drift_threshold must be > 1 (a measured/"
                             f"planned ratio band), got {drift_threshold}")
        self.drift_threshold = drift_threshold
        self.drift_min_samples = drift_min_samples
        self._cache = cache
        self.replans = 0
        self._inflight: dict[str, list[tuple]] = {
            nid: [] for nid in self._tenants}
        self._refused: dict[str, int] = {nid: 0 for nid in self._tenants}
        # SLO-aware scheduling (see repro.obs.slo): the monitor is fed
        # every completed request and read by the tick/admission policy.
        self.slo = slo
        if defer_limit < 1:
            raise ValueError(f"defer_limit must be >= 1, got {defer_limit}")
        self.defer_limit = defer_limit
        self._defer_streak: dict[str, int] = {
            nid: 0 for nid in self._tenants}
        # Supervised dispatch (repro.serve.resilience): True builds a
        # Supervisor from each tenant's plan knobs; a Supervisor instance
        # is adopted as-is; None/False keeps raw dispatch (failures are
        # still isolated and counted — only breaker/retry/deadline/ladder
        # need the supervisor).
        if resilience is True:
            sup = Supervisor(tracer=self.tracer)
            for t in self._tenants.values():
                sup.register(t.net_id, t.plan)
        else:
            sup = resilience or None
        self.supervisor = sup
        self.replan_failures = 0

    # -- construction -----------------------------------------------------
    @classmethod
    def from_fleet(cls, fleet, *, engines: dict | None = None,
                   lm: dict | None = None, shed_after: int | None = None,
                   drift_threshold: float | None = None,
                   drift_min_samples: int = 5, cache=None, tracer=None,
                   slo=None, defer_limit: int = 4, resilience=None,
                   x_scale: float = 0.05, seed: int = 0) -> "Router":
        """Build a router from a :class:`FleetPlan`.

        Edge tenants get an :class:`EdgeEngine` automatically (fresh params
        unless ``engines[net_id]`` supplies a pre-built engine).  LM tenants
        need weights, so pass ``lm={net_id: (cfg, params)}`` (batcher built
        plan-driven) or a ready engine via ``engines``.  With
        ``drift_threshold`` set the router watches measured/planned drift and
        recalibrates + replans the fleet when it trips (see module doc);
        ``cache`` is the plan cache the recalibration writes through.
        """
        tenants = []
        for tp in fleet.tenants:
            if engines and tp.net_id in engines:
                tenants.append(Tenant(
                    net_id=tp.net_id, plan=tp.plan,
                    engine=engines[tp.net_id],
                    latency_budget_s=tp.latency_budget_s))
            elif tp.plan.kind == "lm":
                if not lm or tp.net_id not in lm:
                    raise ValueError(
                        f"LM tenant {tp.net_id!r} needs (cfg, params) via "
                        f"lm= or a pre-built engine via engines=")
                cfg, params = lm[tp.net_id]
                tenants.append(lm_tenant(tp, cfg, params))
            else:
                tenants.append(edge_tenant(tp, x_scale=x_scale, seed=seed))
        return cls(tenants, shed_after=shed_after, fleet=fleet,
                   drift_threshold=drift_threshold,
                   drift_min_samples=drift_min_samples, cache=cache,
                   tracer=tracer, slo=slo, defer_limit=defer_limit,
                   resilience=resilience)

    def arm_faults(self, injector) -> "Router":
        """Thread a :class:`repro.faults.FaultInjector` through every hook
        this router owns (each tenant engine + the supervisor's replan
        hook).  Arm AFTER warmup, so compile-time traffic doesn't consume
        scheduled fault indices.  Builds a default supervisor if none is
        attached — injected faults without a breaker would just be noise.
        Returns self for chaining."""
        if self.supervisor is None:
            sup = Supervisor(tracer=self.tracer)
            for t in self._tenants.values():
                sup.register(t.net_id, t.plan)
            self.supervisor = sup
        self.supervisor.injector = injector
        for t in self._tenants.values():
            if hasattr(t.engine, "injector"):
                t.engine.injector = injector
        return self

    # -- lookup -----------------------------------------------------------
    def tenant(self, net_id: str) -> Tenant:
        try:
            return self._tenants[net_id]
        except KeyError:
            raise KeyError(f"unknown net id {net_id!r}; tenants: "
                           f"{sorted(self._tenants)}") from None

    @property
    def net_ids(self) -> list[str]:
        return list(self._tenants)

    def over_budget(self, net_id: str) -> bool:
        """True when the tenant is currently shed (consecutive violations
        reached ``shed_after``)."""
        t = self.tenant(net_id)
        return (self.shed_after is not None
                and t.metrics.consecutive_violations >= self.shed_after)

    def queue_depth_bound(self, net_id: str) -> int | None:
        """The tenant plan's pending-queue bound (None = unbounded).  The
        fleet planner derives it from the serve policy (``queue_depth_factor
        x slots``): a backlog deeper than a few full slot generations cannot
        land within any budget derived from the planned latency."""
        t = self.tenant(net_id)
        serve = getattr(t.plan, "serve", None) or {}
        return serve.get("max_queue_depth")

    def _admission_check(self, t: Tenant):
        # Queue-depth-aware admission (LM path): refuse BEFORE the backlog
        # outgrows the plan's depth bound, not only after budget violations.
        bound = self.queue_depth_bound(t.net_id)
        if bound is not None and t.kind == "lm":
            # SLO pressure halves a lower-priority tenant's depth bound
            # while a higher-priority tenant is burning budget: its backlog
            # will drain slower under deferral, so the same depth would
            # mean strictly worse tail latency for its own requests.
            pressure = (self.slo.pressure_rank()
                        if self.slo is not None else None)
            if pressure is not None and priority_rank(t.priority) > pressure:
                bound = max(1, bound // 2)
            if t.engine.queue.qsize() >= bound:
                raise TenantQueueFull(
                    f"tenant {t.net_id!r} queue at plan depth bound "
                    f"({t.engine.queue.qsize()}/{bound}); retry after a tick")
        if self.shed_after is None \
                or t.metrics.consecutive_violations < self.shed_after:
            return
        # Half-open: after shed_after consecutive refusals, admit one probe.
        # Its measured latency decides whether the tenant re-opens (streak
        # reset on a within-budget observation) or stays shed.
        if self._refused[t.net_id] >= self.shed_after:
            self._refused[t.net_id] = 0
            return
        self._refused[t.net_id] += 1
        raise TenantOverBudget(
            f"tenant {t.net_id!r} shed: "
            f"{t.metrics.consecutive_violations} consecutive requests "
            f"over the {t.metrics.latency_budget_s * 1e6:.1f}us budget")

    # -- measurement loop (shared by benchmarks / facade / examples) ------
    def default_inputs(self) -> dict:
        """One representative input batch per edge tenant (ones at the
        plan's batch/width) — the probe traffic ``warmup``/``drive`` use
        when the caller has no real inputs."""
        import jax.numpy as jnp
        from repro.models import edge as edge_lib
        out = {}
        for nid, t in self._tenants.items():
            if t.kind != "edge":
                continue
            cfg = getattr(t.engine, "cfg", None) or \
                edge_lib.edge_config(t.plan.network)
            out[nid] = jnp.ones((cfg.batch, cfg.dims[0]), jnp.float32)
        return out

    def warmup(self, inputs: dict | None = None) -> dict:
        """One inference per edge tenant (jit compile + first dispatch),
        then zero every metric and engine measurement, so what follows is
        steady-state.  Returns the inputs used (handy for ``drive``)."""
        inputs = inputs if inputs is not None else self.default_inputs()
        for nid, x in inputs.items():
            self.infer(nid, x)
        self.reset_metrics()
        for t in self._tenants.values():
            if hasattr(t.engine, "reset_measurements"):
                t.engine.reset_measurements()
        return inputs

    def drive(self, inputs: dict | None = None, *, iters: int = 10) -> dict:
        """Interleaved multi-tenant traffic (not one net at a time): ``iters``
        rounds of one inference per edge tenant, then :meth:`report`.  The
        fig9/fig10-style measurement loop, hoisted out of the benchmarks."""
        inputs = inputs if inputs is not None else self.default_inputs()
        for _ in range(iters):
            for nid, x in inputs.items():
                self.infer(nid, x)
        return self.report()

    def _breaker_gate(self, t: Tenant):
        """Refuse while the tenant's circuit is open (half-open probes are
        admitted by the breaker itself)."""
        sup = self.supervisor
        if sup is not None and not sup.admit(t.net_id):
            br = sup.breaker(t.net_id)
            raise TenantBreakerOpen(
                f"tenant {t.net_id!r} circuit open after "
                f"{br.consecutive_failures} consecutive failures; a probe "
                f"is admitted after {br.cooldown} refusals")

    def _record_failure(self, t: Tenant, exc: BaseException,
                        t0: float | None = None, trace=None):
        """Book one failed request/tick against its tenant: the failure
        counter, the breaker (when supervised), and a ``fault/<kind>``
        audit span.  Non-finite faults already emitted their span at the
        engine that detected them — don't double-report those."""
        t.metrics.observe_failure()
        if self.tracer.enabled and fault_kind(exc) != "non_finite":
            now = time.perf_counter()
            self.tracer.add(f"fault/{fault_kind(exc)}",
                            t0 if t0 is not None else now, now, trace=trace,
                            tenant=t.net_id, error=str(exc)[:160])
        if self.supervisor is not None:
            self.supervisor.record_failure(t)

    # -- edge path (synchronous) ------------------------------------------
    def infer(self, net_id: str, x):
        """Route one edge inference; measured against the tenant's budget.
        A failing engine raises :class:`TenantFaulted` (after the
        supervisor's bounded retries, when one is attached) — the fault is
        booked against THIS tenant and co-residents are untouched.

        With tracing on, one request id from the tracer joins the
        request's spans, nested on the calling thread: ``request`` (the
        whole call) holds ``router.admit`` (tenant lookup, admission,
        breaker), the engine's ``infer`` and ``router.account``
        (metrics, supervisor, ``slo.observe``, ``router.replan_check``)."""
        tracer = self.tracer
        if not tracer.enabled:
            t = self.tenant(net_id)
            self._admission_check(t)
            self._breaker_gate(t)
            y, dt = self._call_edge(t, x, None)
            self._account(t, dt, None)
            return y
        rid = tracer.next_trace_id()
        with tracer.span("request", trace=rid, tenant=net_id):
            with tracer.span("router.admit", trace=rid, tenant=net_id):
                t = self.tenant(net_id)
                self._admission_check(t)
                self._breaker_gate(t)
            y, dt = self._call_edge(t, x, rid)
            with tracer.span("router.account", trace=rid, tenant=net_id):
                self._account(t, dt, rid)
        return y

    def _call_edge(self, t: Tenant, x, rid):
        """The engine call (through the supervisor when one is attached)
        and its duration; a failure is booked and raised as
        :class:`TenantFaulted`.  Only a traced call passes the request id,
        so an engine with a plain ``infer(x)`` still serves untraced."""
        sup = self.supervisor
        t0 = time.perf_counter()
        try:
            if sup is not None:
                y = sup.call_edge(t, x, trace=rid)
            elif rid is None:
                y = t.engine.infer(x)
            else:
                y = t.engine.infer(x, trace=rid)
        except Exception as exc:
            self._record_failure(t, exc, t0, trace=rid)
            raise TenantFaulted(
                f"tenant {t.net_id!r} request failed: {exc}") from exc
        return y, time.perf_counter() - t0

    def _account(self, t: Tenant, dt: float, rid):
        """Book one completed edge request of ``dt`` seconds."""
        t.metrics.observe_latency(dt)
        if self.supervisor is not None:
            self.supervisor.record_success(t, dt, trace=rid)
        if self.slo is not None:
            self.slo.observe(t.net_id, dt, trace=rid)
        if rid is None:
            self._maybe_replan(t)
        else:
            with self.tracer.span("router.replan_check", trace=rid,
                                  tenant=t.net_id):
                self._maybe_replan(t)

    # -- lm path (continuous batching) ------------------------------------
    def submit(self, net_id: str, request):
        """Enqueue an LM request on its tenant's batcher."""
        t = self.tenant(net_id)
        self._admission_check(t)
        self._breaker_gate(t)
        self._inflight[net_id].append((request, time.perf_counter()))
        t.engine.submit(request)
        return request

    def lm_pending(self) -> bool:
        """True while any LM tenant holds queued or in-slot work — the
        open-loop replay driver's "should I tick or sleep" predicate."""
        return any(not t.engine.queue.empty() or t.engine.n_active
                   for t in self._tenants.values() if t.kind == "lm")

    def _deferrals(self, lm_order: list[Tenant]) -> set[str]:
        """SLO-aware tick policy: while any tenant is actively burning its
        p95 budget (``slo.at_risk``), strictly LOWER-priority LM tenants
        with queued work admit nothing this tick (``admit_cap=0``) — their
        live slots keep decoding, but free capacity goes to the pressured
        class first.  Deferral is bounded: after ``defer_limit`` consecutive
        deferred ticks the tenant admits anyway (aging), so a permanently
        at-risk tenant can slow a batch-class backlog but never starve it.
        Every deferral is emitted as a zero-duration ``sched/defer`` audit
        span, so priority decisions are inspectable in the trace."""
        if self.slo is None:
            return set()
        pressure = self.slo.pressure_rank()
        if pressure is None:
            for nid in self._defer_streak:
                self._defer_streak[nid] = 0
            return set()
        deferred = set()
        for t in lm_order:
            nid = t.net_id
            if priority_rank(t.priority) <= pressure \
                    or t.engine.queue.empty():
                self._defer_streak[nid] = 0
                continue
            streak = self._defer_streak[nid]
            if streak >= self.defer_limit:
                self._defer_streak[nid] = 0      # aged out: admit this tick
                continue
            self._defer_streak[nid] = streak + 1
            deferred.add(nid)
            if self.tracer.enabled:
                now = time.perf_counter()
                self.tracer.add("sched/defer", now, now, tenant=nid,
                                priority=t.priority, pressure_rank=pressure,
                                streak=streak + 1)
        return deferred

    def step(self, wait_s: float = 0.0) -> int:
        """Tick every LM tenant's batcher once; returns total active slots.
        The blocking idle wait ``wait_s`` is applied only when EVERY LM
        tenant is idle, and at most once per router tick — one idle tenant
        must not stall a busy co-tenant's decodes.

        Tick order is priority-first (burn-rate breaks ties inside a
        class), and with an SLO monitor attached lower-priority tenants may
        have their admissions deferred for this tick — see
        :meth:`_deferrals`."""
        lm = [t for t in self._tenants.values() if t.kind == "lm"]
        if self.slo is not None:
            lm.sort(key=lambda t: (priority_rank(t.priority),
                                   -self.slo.burn_rate(t.net_id)))
        else:
            lm.sort(key=lambda t: priority_rank(t.priority))
        deferred = self._deferrals(lm)
        all_idle = all(t.engine.n_active == 0 and t.engine.queue.empty()
                       for t in lm)
        remaining_wait = wait_s if all_idle else 0.0
        total = 0
        for t in lm:
            nid = t.net_id
            steps_before = getattr(t.engine, "decode_steps_observed", 0)
            try:
                n = t.engine.step(wait_s=remaining_wait,
                                  admit_cap=0 if nid in deferred else None)
            except Exception as exc:
                # Isolation: one tenant's tick failure is booked against
                # that tenant; every co-resident keeps draining.
                n = t.engine.n_active
                self._record_failure(t, exc)
            remaining_wait = 0.0
            t.metrics.observe_occupancy(t.engine.n_active, t.slots)
            total += n
            # Complete latencies for drained requests; a request the
            # batcher FAILED (req.error, e.g. non-finite logits) books a
            # failure instead of a latency — garbage never enters the
            # window or the SLO monitor.
            now = time.perf_counter()
            still = []
            for req, t0 in self._inflight[nid]:
                if req.done:
                    if getattr(req, "error", None):
                        t.metrics.observe_failure()
                        if self.supervisor is not None:
                            self.supervisor.record_failure(t)
                    else:
                        t.metrics.observe_latency(now - t0)
                        if self.slo is not None:
                            self.slo.observe(nid, now - t0)
                        if self.supervisor is not None:
                            self.supervisor.record_success(t, now - t0)
                else:
                    still.append((req, t0))
            self._inflight[nid] = still
            # Drift check per tick that actually decoded (n_active can be 0
            # when every stepped request completed within the tick).
            if getattr(t.engine, "decode_steps_observed", 0) > steps_before:
                self._maybe_replan(t)
        return total

    def run_until_drained(self, max_ticks: int = 10_000,
                          wait_s: float = 0.0):
        """Drive all LM tenants until every queue and slot is empty."""
        for _ in range(max_ticks):
            pending = any(
                not t.engine.queue.empty() or t.engine.n_active
                for t in self._tenants.values() if t.kind == "lm")
            if not pending:
                return
            self.step(wait_s=wait_s)

    # -- drift watcher (characterize -> plan -> serve -> replan loop) -----
    def _drift_measurement(self, t: Tenant) -> tuple[float, int]:
        """(measured seconds, sample count) of the plan-comparable service
        time for one tenant: request p50 for edge (the request IS the
        planned pipeline), decode-step p50 for LM (the plan's graph models
        one decode step; request latency would fold queue wait into the
        cost model)."""
        if t.kind == "lm":
            return (getattr(t.engine, "measured_decode_p50_s", 0.0),
                    getattr(t.engine, "decode_steps_observed", 0))
        return t.metrics.p50_s, t.metrics.count

    def drift(self, net_id: str) -> float:
        """Measured/planned service-time ratio for one tenant (p50 over the
        kind-appropriate window vs the tenant plan's estimate); 1.0 when
        either side has no signal yet."""
        t = self.tenant(net_id)
        planned = getattr(t.plan, "est_latency_s", 0.0)
        measured, _ = self._drift_measurement(t)
        if planned <= 0 or measured <= 0:
            return 1.0
        return measured / planned

    def _tenant_drifted(self, t: Tenant) -> bool:
        _, samples = self._drift_measurement(t)
        if samples < self.drift_min_samples:
            return False
        r = self.drift(t.net_id)
        return r > self.drift_threshold or r < 1.0 / self.drift_threshold

    def drifted(self) -> list[str]:
        """Tenants whose drift ratio left ``[1/threshold, threshold]``
        with at least ``drift_min_samples`` observations."""
        if self.drift_threshold is None:
            return []
        return [nid for nid, t in self._tenants.items()
                if self._tenant_drifted(t)]

    def _maybe_replan(self, t: Tenant):
        """Fire the fleet replan when the tenant that just reported a
        latency has drifted past the threshold.  Checking only that tenant
        keeps the per-request cost at one percentile computation.

        A drift-triggered replan that FAILS degrades instead of
        propagating: the router keeps serving under the CURRENT fleet plan,
        counts the failure, and emits a ``degrade/replan`` audit span — the
        request that happened to trip the drift check must not die because
        the planner did.  Explicit :meth:`replan_fleet` calls still raise.
        """
        if self.drift_threshold is None or self.fleet is None \
                or not self._tenant_drifted(t):
            return None
        try:
            sup = self.supervisor
            if sup is not None and sup.injector is not None:
                spec = sup.injector.fire("replan", tenant=t.net_id)
                if spec is not None and spec.kind == "replan_failure":
                    raise InjectedFault(
                        f"injected replan failure ({t.net_id})")
            return self.replan_fleet()
        except Exception as exc:
            self.replan_failures += 1
            if self.tracer.enabled:
                now = time.perf_counter()
                self.tracer.add("degrade/replan", now, now, tenant=t.net_id,
                                error=str(exc)[:160])
            return None

    def replan_fleet(self, *, budget_factor: float | None = None):
        """Fleet-wide recalibration: feed every measured tenant's
        plan-comparable p50 (edge request / LM decode step) back into the
        plan cache (:func:`repro.plan.calibrate.recalibrate_fleet`) and
        swap the replanned :class:`FleetPlan` into the live tenants — cost
        annotations and budgets move; engines keep their compiled tiles.
        ``budget_factor`` overrides each tenant's original headroom factor
        when re-deriving budgets.  Returns the replanned fleet."""
        from repro.plan import calibrate
        measurements = {}
        for nid, t in self._tenants.items():
            measured, samples = self._drift_measurement(t)
            if samples and measured > 0:
                measurements[nid] = measured
        new_fleet = calibrate.recalibrate_fleet(self.fleet, measurements,
                                                cache=self._cache,
                                                budget_factor=budget_factor)
        self.adopt_fleet(new_fleet)
        self.replans += 1
        return new_fleet

    def adopt_fleet(self, new_fleet):
        """Swap a replanned fleet into the live tenants: plans, budgets and
        engine plan annotations move; engines keep their compiled tiles.
        Used by :meth:`replan_fleet` and by ``Deployment.recalibrate`` when
        the recalibration was driven from engine measurements."""
        for tp in new_fleet.tenants:
            t = self._tenants[tp.net_id]
            t.plan = tp.plan
            t.latency_budget_s = tp.latency_budget_s
            t.metrics.latency_budget_s = tp.latency_budget_s
            # The recalibrated budget reflects measured reality; stale
            # violation streaks (from the mis-planned budget) must not keep
            # the tenant shed under the corrected one.
            t.metrics.consecutive_violations = 0
            if hasattr(t.engine, "plan"):
                t.engine.plan = tp.plan
        self.fleet = new_fleet

    # -- reporting --------------------------------------------------------
    def health(self) -> dict:
        """Per-tenant resilience state + fleet-level counters — what
        ``Deployment.summary()`` prints as its health block and the
        ``repro_resilience_*`` Prometheus families export.  Breaker fields
        appear only when a supervisor is attached."""
        tenants = {}
        for nid, t in self._tenants.items():
            h = {"failures": t.metrics.failures,
                 "engine_faults": getattr(t.engine, "faults", 0),
                 "degrade_level": getattr(t.engine, "degrade_level", 0)}
            if self.supervisor is not None:
                h.update(self.supervisor.snapshot(nid))
                # The ladder's bottom rung is the open breaker itself:
                # while open, even the per-layer path only runs as probes.
                if h["state"] != "closed":
                    h["degrade_level"] = 2
            tenants[nid] = h
        return {"tenants": tenants, "replans": self.replans,
                "replan_failures": self.replan_failures,
                "supervised": self.supervisor is not None}

    def report(self) -> dict:
        """Per-tenant metrics + planned-vs-budget context."""
        out = {}
        slo_snap = self.slo.snapshot() if self.slo is not None else {}
        for nid, t in self._tenants.items():
            snap = t.metrics.snapshot()
            snap["planned_latency_s"] = t.plan.est_latency_s
            snap["kind"] = t.kind
            snap["priority"] = t.priority
            snap["shed"] = self.over_budget(nid)
            snap["drift"] = self.drift(nid)
            if hasattr(t.engine, "span_stats"):
                snap["spans"] = t.engine.span_stats()
            if nid in slo_snap:
                snap["slo"] = slo_snap[nid]
            out[nid] = snap
        return out

    def reset_metrics(self):
        """Zero every tenant's counters (e.g. after jit warmup)."""
        for t in self._tenants.values():
            t.metrics.reset()
        self._refused = {nid: 0 for nid in self._tenants}
        self._defer_streak = {nid: 0 for nid in self._tenants}
        if self.slo is not None:
            # Warmup samples (jit compile) must not pre-burn the budget.
            self.slo.reset()
