"""The unified CLI: ``python -m repro <subcommand>``.

One entry point over the staged facade (:mod:`repro.deploy`) — every
subcommand routes through the same pipeline stages instead of re-wiring the
subsystems by hand:

  python -m repro characterize --sweep quick --out model.json
  python -m repro plan jet_tagger tau_select --target aie
  python -m repro deploy jet_tagger tau_select          # end-to-end
  python -m repro deploy vae --dry-run                  # stop after planning
  python -m repro serve jet_tagger --lm qwen2_5_3b
  python -m repro bench jet_tagger tau_select --iters 10
  python -m repro trace jet_tagger --lm qwen2_5_3b      # spans + attribution
  python -m repro replay --scenario flash_crowd         # open-loop traffic
  python -m repro profile jet_tagger --lm qwen2_5_3b    # roofline + LARE
  python -m repro chaos --scenario flash_crowd --seed 0 # replay under faults
  python -m repro check                                 # static design rules

``python -m repro.plan`` and ``python -m repro.characterize`` remain as
deprecation shims over the matching subcommands.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys


# ---------------------------------------------------------------------------
# plan printing (shared by `plan` and `deploy --dry-run`)
# ---------------------------------------------------------------------------

def _print_plan(plan) -> None:
    print(f"\n# {plan.network} [{plan.target}]  batch={plan.batch}  "
          f"key={plan.key[:12]}…")
    hdr = (f"{'layer':<10}{'shape':>12}  {'regime':<9}{'LARE':>8}"
           f"{'P_KxP_N':>9}{'band':>5}  {'tile':<16}{'interval':>11}")
    print(hdr)
    for l in plan.layers:
        rep = f" x{l.repeat}" if l.repeat > 1 else ""
        print(f"{l.name:<10}{f'{l.n_in}->{l.n_out}{rep}':>12}  "
              f"{l.regime:<9}{l.lare:>8.1f}{f'{l.p_k}x{l.p_n}':>9}"
              f"{l.band:>5}  {str(l.api_tile):<16}"
              f"{l.est_interval_s * 1e6:>9.2f}us")
    for b in plan.boundaries:
        print(f"  boundary after layer {b.after_layer}: "
              f"{b.from_regime}->{b.to_regime} "
              f"(+{b.crossing_s * 1e6:.2f}us)")
    print(f"totals: latency={plan.est_latency_s * 1e6:.2f}us  "
          f"interval={plan.est_interval_s * 1e6:.2f}us  "
          f"rate={plan.inferences_per_s / 1e6:.2f} MHz")


def _print_fleet(fleet) -> None:
    print(f"\n# fleet {fleet.name} [{fleet.target}]  "
          f"key={fleet.key[:12]}…  band1_cols={fleet.band1_cols_used}")
    print(f"{'tenant':<14}{'cols':>10}  {'planned':>11}{'+cross':>10}"
          f"{'budget':>11}")
    for t in fleet.tenants:
        cols = (f"{t.col_offset}..{t.col_offset + t.cols - 1}"
                if t.cols else "-")
        print(f"{t.net_id:<14}{cols:>10}  "
              f"{t.plan.est_latency_s * 1e6:>9.2f}us"
              f"{t.crossing_s * 1e6:>8.2f}us"
              f"{t.latency_budget_s * 1e6:>9.2f}us")
    for t in fleet.tenants:
        _print_plan(t.plan)


def _machine_model_spec(flag: str | None, default=None):
    """Map the --machine-model flag onto a CharacterizeStage spec."""
    if flag is None:
        return default
    if flag in ("stock", "none"):
        return None
    return flag          # "auto" | "quick" | "full" | an artifact path


# ---------------------------------------------------------------------------
# characterize
# ---------------------------------------------------------------------------

def cmd_characterize(argv: list[str] | None = None) -> int:
    from repro.characterize import sweeps as sweeplib
    ap = argparse.ArgumentParser(
        prog="python -m repro characterize",
        description="Run the microbenchmark sweeps on THIS host, fit every "
                    "cost term, and write the versioned MachineModel "
                    "artifact the planner consumes.")
    ap.add_argument("--sweep", choices=sweeplib.SWEEPS, default="quick",
                    help="grid density (quick ~10s wall, full is denser)")
    ap.add_argument("--out", default="model.json",
                    help="path for the MachineModel JSON artifact")
    ap.add_argument("--terms", nargs="+", choices=sweeplib.TERMS,
                    default=list(sweeplib.TERMS),
                    help="cost terms to characterize (default: all)")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--iters", type=int, default=5,
                    help="timed iterations per sweep point (median taken)")
    args = ap.parse_args(argv)

    from repro.deploy import CharacterizeStage, StageContext
    print(f"# characterizing {len(args.terms)} cost term(s), "
          f"sweep={args.sweep}")
    ctx = StageContext(machine_model={
        "sweep": args.sweep, "batch": args.batch, "iters": args.iters,
        "terms": tuple(args.terms)})
    CharacterizeStage().run(ctx)
    mm = ctx.model

    print(f"\n{'term':<12}{'source':<10}{'residual':>10}  constants")
    for term, f in mm.fits.items():
        consts = "  ".join(_fmt_constant(k, v)
                           for k, v in f.constants.items())
        print(f"{term:<12}{f.source:<10}{f.residual_rel_rms:>9.1%}  {consts}")

    path = mm.save(args.out)
    print(f"\nversion {mm.version[:16]}…  wrote {path}")
    print(f"use it:  python -m repro plan <net> --machine-model {path}")
    return 0


def _fmt_constant(name: str, value: float) -> str:
    if name.endswith("_s"):
        return f"{name}={value * 1e6:.3g}us"
    if "penalty" in name:
        return f"{name}={value:.4f}"
    return f"{name}={value:.3g}"


# ---------------------------------------------------------------------------
# plan
# ---------------------------------------------------------------------------

def cmd_plan(argv: list[str] | None = None) -> int:
    from repro.models import edge

    ap = argparse.ArgumentParser(
        prog="python -m repro plan",
        description="Plan deployments (LARE + tiling + column/band + DR7) "
                    "and write the DeploymentPlan/FleetPlan JSON artifacts. "
                    "Naming several nets plans them as a co-resident fleet.")
    ap.add_argument("net", nargs="+",
                    help="edge net name (see EDGE_NETS), an LM arch id with "
                         "--kind lm, or 'all'; several names plan a "
                         "co-resident fleet")
    ap.add_argument("--target", choices=("aie", "tpu", "both"),
                    default="both")
    ap.add_argument("--kind", choices=("edge", "lm"), default="edge")
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--pl-budget", type=float, default=400.0,
                    help="PL DSP-equivalents per layer for the LARE decision")
    ap.add_argument("--machine-model", default=None, metavar="MODEL_JSON",
                    help="fitted MachineModel artifact (python -m repro "
                         "characterize), 'auto' for the device's model "
                         "(CPU interpreter fit, or the chip's stock "
                         "constants), "
                         "or 'quick'/'full' to characterize inline")
    ap.add_argument("--out", default="plans",
                    help="directory for the JSON artifacts")
    args = ap.parse_args(argv)

    from repro.deploy import Deployment
    mm_spec = _machine_model_spec(args.machine_model)
    if mm_spec is not None and pathlib.Path(str(mm_spec)).exists():
        from repro.characterize import MachineModel
        mm_spec = MachineModel.load(mm_spec)
        print(f"# machine model {mm_spec.version[:12]}… "
              f"(sweep={mm_spec.provenance.get('sweep')}, "
              f"host={mm_spec.provenance.get('host')})")

    if args.kind == "lm":
        from repro import configs
        cfgs = [configs.get(n).config for n in args.net]
    elif args.net == ["all"]:
        cfgs = [edge.edge_config(n) for n in edge.EDGE_NETS]
    else:
        for n in args.net:
            if n not in edge.EDGE_NETS:
                print(f"unknown net {n!r}; choose from "
                      f"{sorted(edge.EDGE_NETS)} or 'all'", file=sys.stderr)
                return 2
        cfgs = [edge.edge_config(n) for n in args.net]

    targets = ("aie", "tpu") if args.target == "both" else (args.target,)
    if args.kind == "lm":
        targets = tuple(t for t in targets if t == "tpu") or ("tpu",)

    def build(cfg_or_cfgs, target):
        return Deployment.build(
            cfg_or_cfgs, target=target, machine_model=mm_spec,
            artifact_dir=args.out, stop_after="plan", batch=args.batch,
            pl_budget=args.pl_budget)

    # Several nets named explicitly: plan them as one co-resident fleet.
    if len(args.net) > 1 and args.net != ["all"]:
        for target in targets:
            dep = build(cfgs, target)
            _print_fleet(dep.fleet)
            print(f"wrote {dep.stage_results['plan'].artifact}")
        return 0

    for cfg in cfgs:
        for target in targets:
            dep = build(cfg, target)
            _print_plan(dep.plan)
            print(f"wrote {dep.stage_results['plan'].artifact}")
    return 0


# ---------------------------------------------------------------------------
# deploy / serve / bench
# ---------------------------------------------------------------------------

_DEFAULT_NETS = ("jet_tagger", "tau_select")


def _deploy_parser(prog: str, description: str) -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(prog=prog, description=description)
    ap.add_argument("net", nargs="*", default=list(_DEFAULT_NETS),
                    help="edge net names (default: jet_tagger tau_select)")
    ap.add_argument("--lm", default=None, metavar="ARCH",
                    help="add an LM tenant (smoke config, seed weights), "
                         "e.g. qwen2_5_3b")
    ap.add_argument("--machine-model", default="auto",
                    help="'auto' (default: the CPU interpreter's fit, or "
                         "the chip's stock constants on a TPU), 'stock', "
                         "'quick'/'full' (characterize inline), or a "
                         "MachineModel artifact path")
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--iters", type=int, default=10,
                    help="measured inferences per edge tenant")
    ap.add_argument("--out", default="deployments",
                    help="directory for plan/model artifacts")
    return ap


def _build_deployment(args, *, stop_after=None, trace=False):
    from repro.deploy import Deployment
    specs = list(args.net)
    if args.lm:
        specs.append(f"lm:{args.lm}")
    return Deployment.build(
        specs, target="tpu",
        machine_model=_machine_model_spec(args.machine_model),
        artifact_dir=args.out, stop_after=stop_after, batch=args.batch,
        trace=trace)


def _serve_smoke(dep, *, iters: int, requests: int = 3) -> dict:
    """Drive the deployment end-to-end through the open-loop replay
    driver: interleaved edge traffic plus a small LM request set (the
    same deterministic smoke trace everywhere); returns the router
    report."""
    from repro.obs import workload
    router = dep.serve()
    inputs = router.warmup()
    tenants = {t.net_id: t.plan.kind for t in dep.fleet.tenants}
    trace = workload.smoke_trace(tenants, edge_iters=iters,
                                 lm_requests=requests)
    report = workload.replay(router, trace, inputs=inputs)
    bad = [r for r in report.records if r.status != "ok"]
    if bad:
        raise RuntimeError(f"smoke replay left non-ok requests: {bad[:3]}")
    return router.report()


def _print_report(report: dict) -> None:
    print("\nper-tenant report:")
    for nid, m in report.items():
        print(f"  {nid:<14} kind={m['kind']:<5} n={m['count']:<4} "
              f"p50={m['p50_s'] * 1e6:9.1f}us p95={m['p95_s'] * 1e6:9.1f}us "
              f"violations={m['budget_violations']} "
              f"drift={m['drift']:.2f}")


def cmd_deploy(argv: list[str] | None = None) -> int:
    ap = _deploy_parser(
        "python -m repro deploy",
        "End-to-end: characterize -> plan -> engines -> serve -> "
        "planned-vs-measured, through the staged facade.")
    ap.add_argument("--dry-run", action="store_true",
                    help="stop after the plan stage (no jit, no serving)")
    args = ap.parse_args(argv)
    dep = _build_deployment(
        args, stop_after="plan" if args.dry_run else None)
    print(dep.summary())
    if args.dry_run:
        print("\n(dry run: stopped after the plan stage)")
        return 0
    report = _serve_smoke(dep, iters=args.iters)
    _print_report(report)
    print("\nplanned-vs-measured (name,us_per_call,derived):")
    ok = True
    for row in dep.bench():
        rec = row.as_record()
        print(f"{rec['name']},{rec['us_per_call']:.3f},{rec['derived']}")
        ok &= row.within_2x
    verdict = ("all tenants within 2x of plan" if ok else
               "WARNING: a tenant missed the 2x planned-vs-measured band")
    print(f"\n{verdict}")
    return 0


def cmd_serve(argv: list[str] | None = None) -> int:
    ap = _deploy_parser(
        "python -m repro serve",
        "Plan (or reuse cached plans) and serve a fleet behind the "
        "multi-tenant router; drives smoke traffic and prints the report.")
    ap.add_argument("--requests", type=int, default=3,
                    help="LM smoke requests per LM tenant")
    args = ap.parse_args(argv)
    dep = _build_deployment(args)
    report = _serve_smoke(dep, iters=args.iters, requests=args.requests)
    _print_report(report)
    return 0


def cmd_bench(argv: list[str] | None = None) -> int:
    ap = _deploy_parser(
        "python -m repro bench",
        "Planned-vs-measured rows (trend.py's snapshot shape) for a "
        "deployment on this host.")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write the rows as a BENCH-style snapshot")
    args = ap.parse_args(argv)
    dep = _build_deployment(args)
    rows = [r.as_record() for r in dep.bench(iters=args.iters)]
    print("name,us_per_call,derived")
    for rec in rows:
        print(f"{rec['name']},{rec['us_per_call']:.3f},{rec['derived']}")
    if args.json:
        p = pathlib.Path(args.json)
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(json.dumps({"meta": {"source": "python -m repro bench"},
                                 "rows": rows}, indent=2, sort_keys=True)
                     + "\n")
        print(f"[wrote {p}]")
    return 0


def cmd_trace(argv: list[str] | None = None) -> int:
    ap = _deploy_parser(
        "python -m repro trace",
        "Traced end-to-end run: build + serve with spans on, then export "
        "the Chrome/Perfetto trace.json, a Prometheus metrics snapshot, "
        "per-tenant BENCH_serve_<net>.json rows (with per-span-kind "
        "percentiles), and print the plan-vs-measured attribution table.")
    ap.add_argument("--requests", type=int, default=3,
                    help="LM smoke requests per LM tenant")
    ap.add_argument("--trace-out", default=None, metavar="DIR",
                    help="directory for trace.json / metrics.prom / "
                         "BENCH_serve_*.json (default: <--out>/obs)")
    args = ap.parse_args(argv)
    dep = _build_deployment(args, trace=True)
    print(dep.summary())
    report = _serve_smoke(dep, iters=args.iters, requests=args.requests)
    _print_report(report)

    from repro.serve.metrics import write_serve_snapshots
    out = pathlib.Path(args.trace_out or pathlib.Path(args.out) / "obs")
    trace_path = dep.export_trace(out / "trace.json")
    prom_path = dep.export_prometheus(out / "metrics.prom")
    bench_paths = write_serve_snapshots(
        report, out, meta={"source": "python -m repro trace"})

    print("\nplan-vs-measured attribution:")
    print(dep.format_attribution())
    print(f"\nwrote {trace_path}   (load at https://ui.perfetto.dev)")
    print(f"wrote {prom_path}")
    for p in bench_paths:
        print(f"wrote {p}")
    return 0


def cmd_profile(argv: list[str] | None = None) -> int:
    ap = _deploy_parser(
        "python -m repro profile",
        "Roofline-attributed profiling: serve smoke traffic, then join the "
        "measured span windows with plan-derived work (MACs, bytes, launch "
        "counts) and the machine-model ceilings — achieved FLOP/s, a "
        "compute/memory/launch bound classification, the roofline fraction "
        "and the measured LARE per tenant, plus model-FLOPs vs "
        "compiled-HLO-FLOPs overhead on the actual serving executables.")
    ap.add_argument("--requests", type=int, default=3,
                    help="LM smoke requests per LM tenant")
    ap.add_argument("--json-dir", default=None, metavar="DIR",
                    help="write trend-gateable BENCH_profile_<net>.json "
                         "snapshots here")
    ap.add_argument("--no-hlo", action="store_true",
                    help="skip the compiled-executable HLO analysis "
                         "(saves the extra lower+compile per engine)")
    args = ap.parse_args(argv)
    dep = _build_deployment(args, trace=True)
    _serve_smoke(dep, iters=args.iters, requests=args.requests)
    rows = dep.profile()
    print(dep.format_profile())
    if not rows:
        print("no profiled windows — did the smoke traffic run?",
              file=sys.stderr)
        return 1
    if not args.no_hlo:
        print("\ncompiled-HLO overhead (plan model FLOPs vs executable):")
        for nid, ov in sorted(dep.hlo_overhead().items()):
            uf = ov["useful_fraction"]
            useful = f"{uf:.2f}" if uf is not None else "-"
            print(f"  {nid:<14} model={ov['model_flops']:.4g} "
                  f"hlo={ov['hlo_flops']:.4g} useful={useful}")
    if args.json_dir:
        from repro.obs import write_profile_snapshots
        paths = write_profile_snapshots(
            rows, args.json_dir,
            meta={"source": "python -m repro profile"})
        for p in paths:
            print(f"wrote {p}")
    return 0


def cmd_replay(argv: list[str] | None = None) -> int:
    from repro.obs import workload as wl
    ap = _deploy_parser(
        "python -m repro replay",
        "Open-loop traffic replay against a served fleet: generate a "
        "deterministic scenario trace (or load one), fire arrivals on the "
        "wall clock regardless of completions, and report per-tenant tail "
        "latency, scheduling lag, and the SLO verdict.")
    ap.add_argument("--scenario", choices=sorted(wl.SCENARIOS),
                    default="flash_crowd")
    ap.add_argument("--duration", type=float, default=0.25, metavar="S",
                    help="trace duration in seconds (default 0.25)")
    ap.add_argument("--rate", type=float, default=None, metavar="HZ",
                    help="edge-tenant mean arrival rate")
    ap.add_argument("--lm-rate", type=float, default=None, metavar="HZ",
                    help="LM-tenant mean arrival rate")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--speed", type=float, default=1.0,
                    help="replay speedup: 2.0 compresses arrivals 2x")
    ap.add_argument("--trace-file", default=None, metavar="JSONL",
                    help="replay this saved trace instead of generating")
    ap.add_argument("--save-trace", default=None, metavar="JSONL",
                    help="also save the generated trace for re-replay")
    ap.add_argument("--json-dir", default=None, metavar="DIR",
                    help="write BENCH_serve_<net>__<scenario>.json tail "
                         "snapshots here")
    ap.add_argument("--underbudget", default=None, metavar="NET",
                    help="shrink NET's SLO budgets to ~0 before replay "
                         "(CI fault injection: the monitor must flag it)")
    args = ap.parse_args(argv)

    dep = _build_deployment(args)
    router = dep.serve()
    if args.underbudget:
        if router.slo is None:
            print("--underbudget needs the SLO monitor (serve(slo=True))",
                  file=sys.stderr)
            return 2
        router.slo.set_budget(args.underbudget, p95_s=1e-9, p99_s=1e-9)
        print(f"# injected near-zero SLO budget for {args.underbudget}")

    requests = None
    if args.trace_file:
        requests = wl.load_trace(args.trace_file)
        print(f"# loaded {len(requests)} request(s) from {args.trace_file}")
    scenario_kw = {}
    if args.rate is not None:
        scenario_kw["rate_hz"] = args.rate
    if args.lm_rate is not None:
        scenario_kw["lm_rate_hz"] = args.lm_rate
    if requests is None and args.save_trace:
        tenants = {t.net_id: t.plan.kind for t in dep.fleet.tenants}
        requests = wl.make_scenario(args.scenario, tenants,
                                    duration_s=args.duration,
                                    seed=args.seed, **scenario_kw)
        print(f"[wrote {wl.save_trace(requests, args.save_trace)}]")

    report = dep.replay(args.scenario, duration_s=args.duration,
                        seed=args.seed, speed=args.speed,
                        requests=requests, json_dir=args.json_dir,
                        **scenario_kw)
    print(wl.format_replay(report, slo=router.slo))
    if args.json_dir:
        out = pathlib.Path(args.json_dir)
        for p in sorted(out.glob("BENCH_serve_*__*.json")):
            print(f"wrote {p}")
    return 0


def _recovery_window(records, victim: str, budget, *, window: int = 8):
    """First post-fault rolling window of ok latencies with p95 back under
    the recovery target; returns ``(requests_until_recovered, window_p95_s,
    target_s)`` (the first two None when never recovered / not judgeable).

    The target is the SLO budget when it is attainable, else 2x the
    victim's PRE-fault window p95: plan budgets are modeled accelerator
    time, and a CPU-emulation replay that never met them even before the
    fault should be judged on returning to its own baseline, not on a
    bar it never cleared."""
    from repro.obs.trace import percentile
    recs = sorted((r for r in records if r.tenant == victim),
                  key=lambda r: r.rid)
    last_bad = max((i for i, r in enumerate(recs) if r.status != "ok"),
                   default=-1)
    pre = [r.e2e_s for r in recs[:last_bad + 1]
           if r.status == "ok" and r.e2e_s is not None]
    tail = [r.e2e_s for r in recs[last_bad + 1:]
            if r.status == "ok" and r.e2e_s is not None]
    baseline = 2.0 * percentile(pre, 0.95) if pre else None
    target = budget
    if baseline is not None:
        target = max(budget, baseline) if budget is not None else baseline
    if target is None or len(tail) < window:
        return None, (percentile(tail, 0.95) if tail else None), target
    for i in range(window, len(tail) + 1):
        p95 = percentile(tail[i - window:i], 0.95)
        if p95 <= target:
            return i, p95, target
    return None, percentile(tail[-window:], 0.95), target


def cmd_chaos(argv: list[str] | None = None) -> int:
    from repro import faults as flib
    from repro.obs import workload as wl
    ap = _deploy_parser(
        "python -m repro chaos",
        "Chaos replay: serve the fleet, arm a deterministic fault burst "
        "against one tenant AFTER warmup, replay a scenario under "
        "injection, and judge isolation + time-to-recovery (the breaker "
        "re-close and the first post-fault window with p95 back under "
        "the SLO budget).  Exits non-zero when the fleet did not recover.")
    ap.add_argument("--scenario", choices=sorted(wl.SCENARIOS),
                    default="flash_crowd")
    ap.add_argument("--duration", type=float, default=0.25, metavar="S")
    ap.add_argument("--rate", type=float, default=None, metavar="HZ")
    ap.add_argument("--lm-rate", type=float, default=None, metavar="HZ")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--speed", type=float, default=1.0)
    ap.add_argument("--faults", default=None, metavar="JSON",
                    help="saved FaultPlan artifact (default: a burst of "
                         "--fault-kind faults against --victim)")
    ap.add_argument("--victim", default=None, metavar="NET",
                    help="tenant the default burst targets "
                         "(default: first edge tenant)")
    ap.add_argument("--fault-kind", choices=sorted(flib.FAULT_KINDS),
                    default="engine_exception")
    ap.add_argument("--fault-at", type=int, default=8, metavar="N",
                    help="post-warmup call index the burst starts at")
    ap.add_argument("--fault-count", type=int, default=6)
    ap.add_argument("--json-dir", default=None, metavar="DIR",
                    help="write BENCH_serve_* tail snapshots plus the "
                         "BENCH_chaos recovery snapshot here")
    args = ap.parse_args(argv)

    dep = _build_deployment(args)
    router = dep.serve()
    victim = args.victim or next(
        (t.net_id for t in dep.fleet.tenants if t.plan.kind == "edge"),
        dep.fleet.tenants[0].net_id)
    if args.faults:
        plan = flib.FaultPlan.load(args.faults)
        print(f"# loaded fault plan ({len(plan.faults)} spec(s)) "
              f"from {args.faults}")
    else:
        plan = flib.FaultPlan.burst(
            victim, kind=args.fault_kind, after=args.fault_at,
            count=args.fault_count,
            magnitude_s=0.002 if args.fault_kind == "latency_spike" else 0.0)
        print(f"# fault burst: {args.fault_count}x {args.fault_kind} "
              f"against {victim!r} from call {args.fault_at}")
    injector = plan.injector()

    scenario_kw = {}
    if args.rate is not None:
        scenario_kw["rate_hz"] = args.rate
    if args.lm_rate is not None:
        scenario_kw["lm_rate_hz"] = args.lm_rate
    report = dep.replay(args.scenario, duration_s=args.duration,
                        seed=args.seed, speed=args.speed,
                        json_dir=args.json_dir, faults=injector,
                        **scenario_kw)
    print(wl.format_replay(report, slo=router.slo))

    health = router.health()
    vh = health["tenants"].get(victim, {})
    cfg = (router.supervisor.cfg(victim) if router.supervisor is not None
           else dict(flib.RESILIENCE_DEFAULTS))
    slo_snap = router.slo.snapshot() if router.slo is not None else {}
    budget = slo_snap.get(victim, {}).get("p95_budget_s")
    fired = injector.fired(tenant=victim)
    opens = vh.get("breaker_opens", 0)
    recloses = vh.get("breaker_recloses", 0)
    ttr = vh.get("time_to_recovery_s")
    n_rec, rec_p95, target = _recovery_window(report.records, victim,
                                              budget)

    print(f"\nchaos verdict for {victim!r}:")
    print(f"  faults: scheduled={plan.scheduled(victim)} injected={fired} "
          f"failures={vh.get('failures', 0)}")
    print(f"  breaker: opens={opens} recloses={recloses} "
          f"state={vh.get('state', '-')}"
          + (f" ttr={ttr * 1e3:.1f}ms" if ttr is not None else ""))
    if n_rec is not None:
        print(f"  p95 recovery: back under target "
              f"({target * 1e6:.1f}us) after {n_rec} post-fault "
              f"request(s), window p95={rec_p95 * 1e6:.1f}us")
    elif target is not None:
        print(f"  p95 recovery: window p95 never returned under the "
              f"target ({target * 1e6:.1f}us)"
              + (f"; last window p95={rec_p95 * 1e6:.1f}us"
                 if rec_p95 is not None else ""))
    healthy = [t for t in health["tenants"] if t != victim]
    isolated = all(
        report.summary().get(t, {}).get("ok", 0) > 0 for t in healthy)
    print(f"  isolation: co-residents {healthy} "
          f"{'kept serving' if isolated else 'STARVED'}")

    recovered = (fired > 0 and opens > 0 and recloses >= opens
                 and vh.get("state") == "closed" and isolated)
    print(f"\nchaos: {'RECOVERED' if recovered else 'NOT RECOVERED'} "
          f"(injected={fired}, breaker {opens}->{recloses}, "
          f"model={cfg['breaker_cooldown'] + 1} requests open->reclose)")

    if args.json_dir:
        from repro.serve.metrics import _safe_net_name
        prefix = f"chaos/{victim}/{args.scenario}"
        model_derived = (f"src=model;scenario={args.scenario};"
                         f"kind={args.fault_kind}")
        meas_derived = (f"src=measured;scenario={args.scenario};"
                        f"opens={opens};recloses={recloses};"
                        f"state={vh.get('state', '-')}")
        rows = [
            {"name": f"{prefix}/faults_scheduled",
             "us_per_call": float(plan.scheduled(victim)),
             "derived": f"{model_derived};unit=faults"},
            {"name": f"{prefix}/breaker_k",
             "us_per_call": float(cfg["breaker_k"]),
             "derived": f"{model_derived};unit=failures"},
            {"name": f"{prefix}/recovery_model",
             "us_per_call": float(cfg["breaker_cooldown"] + 1),
             "derived": f"{model_derived};unit=requests"},
            {"name": f"{prefix}/faults_injected",
             "us_per_call": float(fired),
             "derived": f"{meas_derived};unit=faults"},
        ]
        if ttr is not None:
            rows.append({"name": f"{prefix}/time_to_recovery",
                         "us_per_call": round(ttr * 1e6, 3),
                         "derived": meas_derived})
        if n_rec is not None:
            rows.append({"name": f"{prefix}/recovery_requests",
                         "us_per_call": float(n_rec),
                         "derived": f"{meas_derived};unit=requests"})
        out = pathlib.Path(args.json_dir)
        out.mkdir(parents=True, exist_ok=True)
        p = out / (f"BENCH_chaos_{_safe_net_name(victim)}__"
                   f"{_safe_net_name(args.scenario)}.json")
        p.write_text(json.dumps(
            {"meta": {"source": "python -m repro chaos",
                      "victim": victim, "scenario": args.scenario,
                      "fault_kind": args.fault_kind, "seed": args.seed},
             "rows": rows}, indent=2, sort_keys=True, allow_nan=False)
            + "\n")
        print(f"wrote {p}")
    return 0 if recovered else 1


# ---------------------------------------------------------------------------
# check
# ---------------------------------------------------------------------------

def cmd_check(argv: list[str] | None = None) -> int:
    from repro import check as checklib
    ap = argparse.ArgumentParser(
        prog="python -m repro check",
        description="Static design-rule verification with zero execution: "
                    "lint src/repro for jax hazards, verify every plan "
                    "artifact under deployments/ against the paper's "
                    "design rules (tiles, columns, VMEM, DR7 boundaries, "
                    "serve knobs) plus the Pallas kernel contracts, and "
                    "validate every bench/ BENCH_*.json snapshot. "
                    "Exit 0 clean, 1 on error findings, 2 on an "
                    "undecodable artifact (one-line stderr).")
    ap.add_argument("artifacts", nargs="*", metavar="PLAN_JSON",
                    help="verify just these plan artifacts instead of the "
                         "whole tree")
    ap.add_argument("--root", default=".",
                    help="repo root for the tree check (default: .)")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable report on stdout")
    ap.add_argument("--no-lint", action="store_true",
                    help="skip the src/repro jax-hazard lint")
    ap.add_argument("--no-kernels", action="store_true",
                    help="skip the jax.eval_shape kernel contracts")
    args = ap.parse_args(argv)
    try:
        if args.artifacts:
            report = checklib.CheckReport()
            for p in args.artifacts:
                report.extend(checklib.check_artifact(
                    p, kernels=not args.no_kernels))
                report.checked.append(f"plan:{pathlib.Path(p).name}")
        else:
            report = checklib.check_tree(args.root,
                                         kernels=not args.no_kernels,
                                         lint=not args.no_lint)
            if not args.no_kernels:
                from repro.check import kernel_contracts
                report.extend(kernel_contracts.verify_kernel_library())
                report.checked.append("kernels:library self-check")
    except checklib.ArtifactError as e:
        print(f"check: {e}", file=sys.stderr)
        return checklib.EXIT_UNDECODABLE
    print(report.to_json() if args.json else str(report))
    return report.exit_code


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

_SUBCOMMANDS = {
    "characterize": cmd_characterize,
    "plan": cmd_plan,
    "deploy": cmd_deploy,
    "serve": cmd_serve,
    "bench": cmd_bench,
    "trace": cmd_trace,
    "replay": cmd_replay,
    "profile": cmd_profile,
    "chaos": cmd_chaos,
    "check": cmd_check,
}


def main(argv: list[str] | None = None) -> int:
    from repro.runtime import enable_compile_cache
    enable_compile_cache()
    argv = sys.argv[1:] if argv is None else list(argv)
    # Dispatch by hand (no parse_known_args): the root parser must not
    # swallow `--help` meant for a subcommand — `python -m repro plan
    # --help` has to reach cmd_plan's parser.
    ap = argparse.ArgumentParser(
        prog="python -m repro", description=__doc__, add_help=False,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("subcommand", choices=sorted(_SUBCOMMANDS),
                    help="what to run (each routes through repro.deploy's "
                         "pipeline stages)")
    if not argv or argv[0] in ("-h", "--help"):
        ap.print_help()
        return 0 if argv else 2
    if argv[0] not in _SUBCOMMANDS:
        ap.print_usage(sys.stderr)
        print(f"python -m repro: unknown subcommand {argv[0]!r} "
              f"(choose from {', '.join(sorted(_SUBCOMMANDS))})",
              file=sys.stderr)
        return 2
    return _SUBCOMMANDS[argv[0]](argv[1:])


def deprecated_main(old: str, subcommand: str, argv=None) -> int:
    """Shim for the legacy per-subsystem CLIs (``python -m repro.plan`` /
    ``python -m repro.characterize``): warn, then run the unified
    subcommand with unchanged flags."""
    print(f"[deprecated] `python -m {old}` is now "
          f"`python -m repro {subcommand}` (same flags); the shim will "
          f"keep working but new options land on the unified CLI only.",
          file=sys.stderr)
    return _SUBCOMMANDS[subcommand](argv)
