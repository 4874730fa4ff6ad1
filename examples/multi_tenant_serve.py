"""Multi-tenant serving example: co-resident networks behind one router.

  PYTHONPATH=src python examples/multi_tenant_serve.py

ONE facade call plans two extreme-edge nets AND a small LM as a fleet
(joint placement, per-tenant latency budgets, host-calibrated machine
model) and builds the engines; ``.serve()`` wires the multi-tenant router.
The example then drives mixed traffic — synchronous edge inferences
interleaved with continuous-batched LM requests — prints the per-tenant
report, and closes the loop with ``.recalibrate()`` (measured latencies
back into the plan cache, budgets re-derived).
"""

import jax
import numpy as np

from repro import configs
from repro.deploy import Deployment
from repro.models import api
from repro.serve.engine import Request


def main():
    lm_cfg = configs.get("qwen2_5_3b").smoke
    lm_params = api.init(lm_cfg, jax.random.PRNGKey(0))

    # One fleet: two edge tenants + one LM tenant.  machine_model="auto"
    # (the default) fits the planner to the CPU interpreter, or takes the
    # chip's stock constants on a TPU, so budgets are meaningful; engines
    # are quantized + calibrated + jitted behind build.
    dep = Deployment.build(
        ["jet_tagger", "tau_select", lm_cfg],
        lm_params={lm_cfg.name: (lm_cfg, lm_params)},
        serve_slots_total=3, prefill_chunk=4)
    print(dep.summary())

    router = dep.serve()
    inputs = router.warmup()      # jit compile, then zero the counters

    # Mixed traffic: submit LM requests, then interleave edge inferences
    # with batcher ticks (the LM tenant decodes while edge nets serve).
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i,
                    prompt=rng.integers(1, lm_cfg.vocab_size,
                                        3).astype(np.int32),
                    max_new=4)
            for i in range(4)]
    for r in reqs:
        router.submit(lm_cfg.name, r)
    for tick in range(40):
        for name, x in inputs.items():
            router.infer(name, x)
        if router.step() == 0 and all(r.done for r in reqs):
            break

    print("\nper-tenant report:")
    for nid, m in router.report().items():
        print(f"  {nid:<14} n={m['count']:<3} mean={m['mean_s'] * 1e6:8.1f}us "
              f"p95={m['p95_s'] * 1e6:8.1f}us "
              f"violations={m['budget_violations']} "
              f"occupancy={m['occupancy']:.2f}")
    assert all(r.done for r in reqs)
    for r in reqs:
        print(f"  lm req {r.rid}: {len(r.out)} tokens")

    # Autotune feedback, one call: measured edge latencies land in the plan
    # cache and the fleet's costs + budgets are re-derived in place.
    fleet = dep.recalibrate()
    for t in fleet.tenants:
        if t.plan.kind == "edge":
            print(f"calibrated {t.net_id}: planned -> "
                  f"{t.plan.est_latency_s * 1e6:.1f}us "
                  f"(scale {t.plan.serve['calibration']['scale']:.2f})")


if __name__ == "__main__":
    main()
