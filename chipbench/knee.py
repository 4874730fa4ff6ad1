"""Knee sweep: the highest rate the served path sustains.

    python3 chipbench/knee.py --workload <cell> --rates 400,500,600 \\
        --seconds 8 --seeds 1,2

For each seed, builds the cell's configuration once, then plays steady
Poisson arrivals at each rate, with the cell's tenants, Zipf mix and inputs,
through the cell's driver.  For each rate it prints what was offered and
completed per second, and the mean queue wait (scheduled arrival to the call
into the router) in the first and the second half of the window.  A rate
passes where completions keep pace with arrivals (``PACE``) and the
second half's wait is at most twice the first half's plus ``SLACK_S``.  The
knee, printed last, is the highest rate at and below which every rate
passed on every seed.  The rates of the traffic mixes were written from
it.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import run

PACE = 0.97           # completions per second over arrivals per second
SLACK_S = 100e-6      # allowed growth of the mean wait beyond doubling


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated req/s")
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seeds", default="1", help="comma-separated seeds")
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        run.check_device(cell.chips)
    except run.NoChip as exc:
        print(f"knee: {exc}", file=sys.stderr)
        return 2
    run.enable_compile_cache()
    rates = sorted(float(r) for r in args.rates.split(","))
    passed = {r: True for r in rates}
    for seed in (int(s) for s in args.seeds.split(",")):
        for row in sweep(cell, rates, args.seconds, seed):
            passed[row["rate_hz"]] &= row["passed"]
    knee = None
    for r in rates:
        if not passed[r]:
            break
        knee = r
    print(json.dumps({"workload": cell.name, "knee_hz": knee,
                      "passed": {str(r): p for r, p in passed.items()}}),
          flush=True)
    return 0


def sweep(cell, rates, seconds, seed) -> list[dict]:
    import numpy as np

    from chipbench import check
    from chipbench.records import OK
    from chipbench.traffic import generate
    pools = cell.kind.input_pool(cell.traffic, cell.config, seed)
    driver = run.load_module(run.HERE / "drivers"
                             / f"{cell.traffic['driver']}.py")
    router = cell.kind.build(cell.config, seed, None)
    cell.kind.warm(router, pools)
    gc.collect()
    gc.freeze()                     # as run.play does before its window
    rows = []
    for rate in rates:
        spec = dict(cell.traffic, arrivals={"process": "poisson",
                                            "rate_hz": rate})
        sched = generate.schedule(spec, seconds, seed)
        slots = run.sample_slots(sched, check.SAMPLES_PER_TENANT, seed)
        start = time.perf_counter() + 1e-3
        rec = driver.run(router, sched, pools, window_start=start,
                         seconds=seconds, sample_slots=slots)
        ok = rec.status == OK
        wait = rec.call - rec.due
        first = ok & (rec.due < start + seconds / 2)
        second = ok & (rec.due >= start + seconds / 2)
        lat = (rec.done - rec.due)[ok]
        row = {
            "seed": seed, "rate_hz": rate, "offered": len(sched),
            "completed_per_s": float(np.sum(ok & (rec.done <= start
                                                   + seconds)) / seconds),
            "unserved": int(np.sum(~ok)),
            "wait_mean_us_first_half": float(wait[first].mean() * 1e6),
            "wait_mean_us_second_half": float(wait[second].mean() * 1e6),
            "latency_p50_us": float(np.percentile(lat, 50) * 1e6),
            "latency_p99_us": float(np.percentile(lat, 99) * 1e6)}
        row["passed"] = bool(
            row["completed_per_s"] >= PACE * rate
            and row["wait_mean_us_second_half"]
            <= 2 * row["wait_mean_us_first_half"] + SLACK_S * 1e6)
        print(json.dumps(row), flush=True)
        rows.append(row)
    return rows


if __name__ == "__main__":
    sys.exit(main())
