"""Run one cell of the on-chip benchmark once.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

from the root of a checkout on a machine with a TPU.  The cell, its
configuration and its traffic mix are looked up by name in
``BENCHMARK.json``; each configuration, configuration kind, traffic mix,
driver and metric reader is a file of its own under ``chipbench/``, found
by that name.

A run:

1. refuses to go on (exit 2, no result) unless JAX runs on a TPU with at
   least the chips the cell asks for;
2. builds the configuration from the seed through the entry users call, as
   its kind (``chipbench/kinds/<kind>.py``) says: for the edge nets,
   ``Deployment.build(..., target="tpu", machine_model="auto", seed=seed)``
   and ``dep.serve()`` with its defaults; and warms every shape the cell
   sends;
3. plays the traffic mix for ``--seconds`` through the router (the driver
   the mix names), timing each request from its scheduled arrival to its
   output on the host;
4. with ``--trace 1``, records the program's spans for the whole window and
   a profiler trace of its last fifth, and reads the per-layer metrics from
   them; with ``--trace 0`` it reads the end-to-end metrics;
5. frees the program, compares a sample of the window's answers, drawn from
   the seed, with the reference the configuration names (its kind's
   ``compare``; ``chipbench/check.py``), and prints
   each number compared beside its limit as the last lines of standard
   error, then one JSON result line as the last line of standard output.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench.loader import load_kind, load_module  # noqa: E402

# The profiler trace of a traced run; replaced by every traced run.
TRACE_DIR = HERE / "out" / "trace"
PROFILE_SHARE = 0.2          # last fifth of the window is profiled
SPAN_CAP = 4_000_000         # the program's span sink, per traced run


class NoChip(RuntimeError):
    """JAX runs on no TPU, or on fewer chips than the cell asks for."""


def log(*parts) -> None:
    print("[chipbench]", *parts, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict             # the configuration file
    kind: object             # its kind's module (chipbench/kinds/)
    traffic: dict            # the traffic mix file
    end_to_end: list         # BENCHMARK.json metric entries of this cell
    per_layer: list


def load_cell(workload: str, bench_path=ROOT / "BENCHMARK.json") -> Cell:
    from chipbench.traffic import generate
    bench = json.loads(pathlib.Path(bench_path).read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(known: {sorted(cells)})")
    w = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((ROOT / cfg_entry["file"]).read_text())

    def mine(metrics):
        return [m for m in metrics
                if "workloads" not in m or workload in m["workloads"]]
    return Cell(name=workload, chips=int(w["chips"]), config=config,
                kind=load_kind(config, HERE / "kinds"),
                traffic=generate.load(w["traffic"], HERE / "traffic"),
                end_to_end=mine(bench["end_to_end"]),
                per_layer=mine(bench["per_layer"]))


def check_device(chips: int):
    """The devices JAX runs on; raises :class:`NoChip` unless they are at
    least ``chips`` TPUs.  Never falls back to the CPU."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX runs on platform {devices[0].platform!r} "
                     f"({devices[0].device_kind}), not on a TPU")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} TPU chips; JAX sees "
                     f"{len(devices)}")
    return devices


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    when set (JAX reads it itself), else ``<checkout>/.jax_cache``, a fixed
    path inside the checkout.  Every executable is kept."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


class CompileCounter:
    """Counts backend compilations and persistent-cache hits and misses."""

    def __init__(self):
        from jax import monitoring
        self.counts = collections.Counter()
        monitoring.register_event_listener(self._event)
        monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        self.counts[event.rsplit("/", 1)[-1]] += 1

    def _duration(self, event, duration, **_):
        if event.endswith("backend_compile_duration"):
            self.counts["compiles"] += 1

    def snapshot(self) -> dict:
        return dict(self.counts)


class GcPauses:
    """The interpreter's garbage-collection pauses while it is open."""

    def __init__(self):
        self.pauses: list[tuple[int, float]] = []   # (generation, seconds)
        self._t0 = 0.0
        gc.callbacks.append(self._callback)

    def _callback(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t0))

    def close(self):
        gc.callbacks.remove(self._callback)

    def summary(self) -> str:
        longest = max((p for _, p in self.pauses), default=0.0)
        full = sum(1 for g, _ in self.pauses if g == 2)
        return (f"gc_pauses={len(self.pauses)} full={full} "
                f"longest_ms={longest * 1e3} "
                f"total_ms={sum(p for _, p in self.pauses) * 1e3}")


class Profile:
    """The profiler over the last part of a traced window."""

    def __init__(self, start_s: float):
        self.start_s = start_s
        self.started = False

    def start(self):
        import jax
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
        self.started = True

    def stop(self):
        if self.started:
            import jax
            jax.profiler.stop_trace()


@dataclasses.dataclass
class RunData:
    """What a metric reader reads.  Times are ``perf_counter`` seconds,
    except the profiler trace's, which are on its own clock."""
    seconds: float
    window_start: float
    setup_s: float
    records: object              # drivers' Records, one entry per request
    tenant: object               # np.ndarray: tenant index per request
    tenants: list
    batch: int
    work: list                   # work.Work per scheduled request
    peak: dict                   # peaks.json entry of this device
    spans: list | None = None    # the program's spans (traced run)
    trace: object = None         # trace.Trace (traced run)
    trace_window: tuple | None = None   # (t0, t1) on the trace's clock

    @property
    def window_end(self) -> float:
        return self.window_start + self.seconds

    def ok(self):
        from chipbench.records import OK
        return self.records.status == OK

    def host_part(self):
        """Requests issued before the profiler started (all when none)."""
        import numpy as np
        return np.arange(len(self.tenant)) < self.records.first_profiled

    def profiled(self):
        """Completed requests issued while the profiler ran."""
        import numpy as np
        return self.ok() & (np.arange(len(self.tenant))
                            >= self.records.first_profiled)

    def spans_named(self, name: str):
        """``(start, end)`` arrays of the program's spans of one kind."""
        import numpy as np
        sel = [s for s in (self.spans or []) if s.name == name]
        return (np.asarray([s.t0_s for s in sel], np.float64),
                np.asarray([s.t1_s for s in sel], np.float64))


def read_metrics(entries, run: RunData) -> dict:
    """Each metric's reader, found by name; one that finds nothing to read
    returns None and is left out."""
    out = {}
    for m in entries:
        value = load_module(HERE / "metrics" / f"{m['name']}.py").read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def sample_slots(sched, k: int, seed: int) -> dict:
    """Algorithm R, decided before the window: for tenant ``t``'s ``c``-th
    completed request, the reservoir slot it takes (``-1``: none), so that
    whatever prefix of the schedule completes, the kept requests are a
    uniform sample of it drawn from the seed."""
    import numpy as np
    out = {}
    for t, n in enumerate(np.bincount(sched.tenant,
                                      minlength=len(sched.tenants))):
        rng = np.random.default_rng(np.random.SeedSequence([seed, 3, t]))
        c = np.arange(n)
        j = np.floor(rng.random(n) * (c + 1)).astype(np.int64)
        slots = np.where(c < k, c, np.where(j < k, j, -1))
        out[t] = slots
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seconds <= 0:
        ap.error("--seconds must be > 0")

    cell = load_cell(args.workload)
    # libtpu's own logs would otherwise go to a fixed path under /tmp.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        devices = check_device(cell.chips)
    except NoChip as exc:
        print(f"chipbench: {exc}; this benchmark runs only on a TPU",
              file=sys.stderr)
        return 2
    dev = devices[0]
    return run_cell(cell, args, dev, len(devices))


@dataclasses.dataclass
class Played:
    """One window played through the program, before anything is read."""
    router: object
    tracer: object
    sched: object
    pools: dict
    batch: int
    records: object
    window_start: float
    setup_s: float
    compiles: int                # backend compilations inside the window
    gc_pauses: "GcPauses"
    profile: Profile | None


def play(cell: Cell, seed: int, seconds: float, *, trace: bool,
         counter: CompileCounter) -> Played:
    """Build the cell's configuration from ``seed``, warm every shape the
    window sends, and play ``seconds`` of the cell's traffic through it."""
    from chipbench import check
    from chipbench.traffic import generate
    from repro.obs import Tracer

    kind = cell.kind
    sched = generate.schedule(cell.traffic, seconds, seed)
    pools = kind.input_pool(cell.traffic, cell.config, seed)
    slots = sample_slots(sched, check.SAMPLES_PER_TENANT, seed)
    driver = load_module(HERE / "drivers" / f"{cell.traffic['driver']}.py")

    tracer = Tracer(maxlen=SPAN_CAP) if trace else None
    router = kind.build(cell.config, seed, tracer)
    kind.warm(router, pools)
    router.reset_metrics()
    if tracer is not None:
        tracer.clear()
    profile = Profile((1 - PROFILE_SHARE) * seconds) if trace else None
    # The heap set-up leaves behind (JAX, the program, the inputs) is
    # frozen, as a long-running server would do: a full collection inside
    # the window then scans only what serving allocated, instead of pausing
    # the serving thread for a scan of every module JAX imported.
    gc.collect()
    gc.freeze()
    pauses = GcPauses()
    before = counter.snapshot().get("compiles", 0)
    window_start = time.perf_counter() + 1e-3
    setup_s = window_start - T_PROCESS
    try:
        records = driver.run(router, sched, pools,
                             window_start=window_start, seconds=seconds,
                             sample_slots=slots, profile=profile)
    finally:
        pauses.close()
        gc.unfreeze()
    if profile is not None:
        profile.stop()
    return Played(router=router, tracer=tracer, sched=sched,
                  pools=pools, batch=kind.batch(cell.config),
                  records=records, window_start=window_start,
                  setup_s=setup_s,
                  compiles=counter.snapshot().get("compiles", 0) - before,
                  gc_pauses=pauses, profile=profile)


def samples_of(played: Played) -> dict:
    """``{tenant: [(request, output), ...]}`` kept for the comparison."""
    names = played.sched.tenants
    return {names[t]: [v for _, v in sorted(s.items())]
            for t, s in played.records.samples.items()}


def read_trace(run: RunData, device: dict):
    """Attach the profiler trace to ``run``; fill ``busy_s``/``window_s``
    and return the breakdown (None when nothing was traced)."""
    import numpy as np

    from chipbench import trace as trace_lib
    tr = trace_lib.load(TRACE_DIR)
    run.trace = tr
    host = tr.serving_thread
    ann = np.isin(np.asarray(host.names), trace_lib.ANNOTATIONS)
    if not ann.any() or not tr.devices:
        return None
    t0, t1 = float(host.start[ann].min()), float(host.end[ann].max())
    run.trace_window = (t0, t1)
    device["busy_s"] = float(np.mean([trace_lib.busy_s(ops, t0, t1)
                                      for ops in tr.devices.values()]))
    device["window_s"] = t1 - t0
    ops = next(iter(tr.devices.values()))
    return {"device_ops": trace_lib.top_ops(ops),
            "idle_gaps": trace_lib.attribute_gaps(
                trace_lib.idle_gaps(ops, t0, t1), host)}


def run_cell(cell: Cell, args, dev, n_devices: int) -> int:
    """Everything after the device check (the tests drive this on the CPU
    with the timed path broken underneath)."""
    import numpy as np

    from chipbench import check, work as work_lib
    from chipbench.records import FAILED, OK, UNSERVED

    cache_dir = enable_compile_cache()
    counter = CompileCounter()
    seed, seconds = args.seed, args.seconds
    played = play(cell, seed, seconds, trace=bool(args.trace),
                  counter=counter)
    records, sched = played.records, played.sched
    health = played.router.health()

    run = RunData(
        seconds=seconds, window_start=played.window_start,
        setup_s=played.setup_s, records=records, tenant=sched.tenant,
        tenants=sched.tenants, batch=played.batch,
        work=cell.kind.request_work(cell.config, sched, played.pools,
                                    records),
        peak=(work_lib.peaks(dev.device_kind) if dev.platform == "tpu"
              else None),
        spans=played.tracer.spans if played.tracer is not None else None)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": n_devices}
    breakdown = None
    if played.profile is not None and played.profile.started:
        breakdown = read_trace(run, device)
    metrics = read_metrics(cell.per_layer if args.trace else cell.end_to_end,
                           run)
    device["memory_peak_bytes"] = int(
        (dev.memory_stats() or {}).get("peak_bytes_in_use", 0))

    status = records.status
    attempted = int(np.sum(status != UNSERVED))
    failed = int(np.sum(status == FAILED))
    ok = status == OK
    late = records.call[ok] - records.due[ok]
    log(f"cell={cell.name} seed={seed} seconds={seconds} trace={args.trace}"
        f" compile_cache={cache_dir}")
    log(f"setup_s={played.setup_s} compiles_in_window={played.compiles} "
        f"cache={counter.snapshot()}")
    log(f"offered={len(sched)} per_tenant={sched.per_tenant()} "
        f"attempted={attempted} ok={int(ok.sum())} failed={failed} "
        f"unserved={int(np.sum(status == UNSERVED))}")
    if late.size:
        service = records.done[ok] - records.call[ok]
        worst = int(np.argmax(service))
        log(f"start_after_due_us p50={np.percentile(late, 50) * 1e6} "
            f"p99={np.percentile(late, 99) * 1e6} max={late.max() * 1e6}")
        log(f"service_us p50={np.percentile(service, 50) * 1e6} "
            f"p99={np.percentile(service, 99) * 1e6} "
            f"max={service[worst] * 1e6} at_s="
            f"{records.call[ok][worst] - played.window_start} "
            f"over_10ms={int(np.sum(service > 0.01))} "
            f"{played.gc_pauses.summary()}")
    log("health " + json.dumps(
        {nid: {k: v for k, v in h.items() if v}
         for nid, h in health["tenants"].items()}))
    if played.tracer is not None:
        log(f"spans={len(played.tracer)} dropped={played.tracer.dropped}")

    # The program's state goes before the reference runs on the chip.
    samples = samples_of(played)
    pools = played.pools
    del played, run, records
    gc.collect()
    compared = cell.kind.compare(cell.config, seed, samples, sched, pools,
                                 failed=failed)
    for name, c in compared.items():
        log(f"compared {name} {c['value']} limit {c['limit']}")
    result = {"correct": check.is_correct(compared), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = compared
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
