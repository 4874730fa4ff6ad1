"""Readings the limits of ``correct`` are set from.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3 \\
        --seconds 3 [--out readings.json]

For each seed, in this one process: builds the cell's nets, plays a window
of the cell's own traffic through the timed path, and reads the compared
numbers on the same sampled requests

* for what the program served (the lower reading: the largest over the
  seeds);
* with the control, the configuration's reference computed one precision
  step down (for the edge nets, the float32 reference in int4), put in the
  program's place (the upper reading: the smallest over the seeds);
* for each fault a served cell can have, planted in the program's answers:
  half of every batch left out, another request's answer returned for one
  request in eight, and an answer altered where it is produced.

Prints one JSON line per seed, then the summary as the last line.  With
``--out``, also writes each tenant's per-request errors (program and
control) per seed, from which the ``req_err`` thresholds are set.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import pathlib
import sys

import numpy as np

import run


def half_batch(y):
    """Half of the batch left out: its rows never computed."""
    y = np.array(y)
    y[y.shape[0] // 2:] = 0.0
    return y


def altered(y):
    """An answer altered where it is produced."""
    y = np.array(y)
    y[:, 0] = -y[:, 0] + 1.0
    return y * 1.5


def stale(samples: dict) -> dict:
    """One request in eight gets the answer of the request before it."""
    out = {}
    for name, got in samples.items():
        out[name] = [(i, got[k - 1][1] if k % 8 == 7 else y)
                     for k, (i, y) in enumerate(got)]
    return out


def faulted(samples: dict, fault) -> dict:
    return {name: [(i, fault(y)) for i, y in got]
            for name, got in samples.items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        run.check_device(cell.chips)
    except run.NoChip as exc:
        print(f"control: {exc}", file=sys.stderr)
        return 2
    run.enable_compile_cache()
    readings(cell, [int(s) for s in args.seeds.split(",")], args.seconds,
             args.out)
    return 0


def readings(cell, seeds, seconds, out=None) -> dict:
    from chipbench import check
    from chipbench.records import FAILED
    counter = run.CompileCounter()
    values = {}                     # kind -> number -> [value per seed]
    errors = {}                     # seed -> {"program"|"control": {...}}
    for seed in seeds:
        played = run.play(cell, seed, seconds, trace=False, counter=counter)
        failed = int((played.records.status == FAILED).sum())
        samples = run.samples_of(played)
        sched, pools = played.sched, played.pools
        del played
        gc.collect()
        errs = {"program": {}, "control": {}}
        compare = cell.kind.compare
        kinds = {
            "program": compare(cell.config, seed, samples, sched, pools,
                               failed=failed, errors=errs["program"]),
            "control": compare(cell.config, seed, samples, sched, pools,
                               failed=0,
                               replace=cell.kind.control(cell.config),
                               errors=errs["control"])}
        for name, broken in (("half_batch", faulted(samples, half_batch)),
                             ("stale_1in8", stale(samples)),
                             ("altered", faulted(samples, altered))):
            kinds[name] = compare(cell.config, seed, broken, sched, pools,
                                  failed=0)
        errors[seed] = {k: {t: v.tolist() for t, v in e.items()}
                        for k, e in errs.items()}
        row = {"seed": seed, "correct": {k: check.is_correct(c)
                                         for k, c in kinds.items()}}
        for kind, compared in kinds.items():
            row[kind] = {k: v["value"] for k, v in compared.items()}
            for k, v in compared.items():
                values.setdefault(kind, {}).setdefault(k, []).append(
                    v["value"])
        print(json.dumps(row), flush=True)
    summary = {"workload": cell.name, "seeds": seeds,
               "lower": {k: max(v) for k, v in values["program"].items()},
               "upper": {k: min(v) for k, v in values["control"].items()}}
    for kind in ("half_batch", "stale_1in8", "altered"):
        summary[kind] = {k: min(v) for k, v in values[kind].items()}
    print(json.dumps(summary), flush=True)
    if out:
        pathlib.Path(out).parent.mkdir(parents=True, exist_ok=True)
        pathlib.Path(out).write_text(json.dumps(
            {"workload": cell.name, "errors": errors, "summary": summary}))
    return summary


if __name__ == "__main__":
    sys.exit(main())
