"""Bring-up smoke of the served fleet on one TPU.

Drives the main path once, in this one process, through the calls a user
makes: ``Deployment.build`` plans the five Table-I edge nets (batch 8, their
full published widths) and the ``qwen2_5_3b`` LM tenant at its smoke config
onto the chip under the chip's own machine model; ``dep.serve()`` puts them
behind the router; the deterministic smoke trace is replayed through it.
Then it checks, and fails loudly on any miss:

* every replayed request is ``ok``, and the resilience layer booked no
  failure, retry, breaker trip or degradation;
* every edge executable holds a compiled Mosaic kernel (``tpu_custom_call``)
  and the kernels are not interpreted;
* each edge net's served (fused) output equals its per-layer int8 path and
  stays within ``FLOAT_REF_BOUND`` of the float32 reference.

Run from the checkout root on a machine with a TPU::

    python chip_smoke.py

Without a TPU it exits non-zero at the device check.  Plan artifacts go to
``chiprun_out/chip_smoke/``; compiled executables go to the persistent
compile cache (:func:`repro.runtime.enable_compile_cache`).  Phase times are
host wall seconds, compilation included; no device time is measured.  The
last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import collections
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
OUT_DIR = ROOT / "chiprun_out" / "chip_smoke"

EDGE_NETS = ("jet_tagger", "tau_select", "vae", "qubit", "autoencoder")
LM_ARCH = "qwen2_5_3b"                   # served at its smoke config
EDGE_ITERS = 8                           # replayed requests per edge tenant
LM_REQUESTS = 3
SEED = 0
# Fused vs per-layer int8 path: the bound tests/test_fusion.py holds.
FUSED_TOL = 1e-5
# max|int8 served - float32 reference| / max|float32 reference| on the
# seeded input.  The CPU rehearsal (tests/test_chip_smoke.py) measured at
# most 0.1442 (jet_tagger; qubit 0.1427, vae 0.1155, autoencoder 0.0821,
# tau_select 0.0192): activations beyond the 8-row calibration batch clip
# at int8.  The int8 GEMMs are exact, so the chip should land on the same
# figures; the bound only leaves room for float rounding.
FLOAT_REF_BOUND = 0.2


class SmokeFailure(RuntimeError):
    """A check of the smoke failed."""


def log(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def check_device() -> dict:
    """The device JAX runs on; raises unless it is a TPU."""
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SmokeFailure(f"no TPU: JAX runs on platform {dev.platform!r} "
                           f"({dev.device_kind}); this smoke needs a TPU")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def build(out_dir=OUT_DIR):
    """Characterize -> plan -> verify -> engines for the whole fleet."""
    from repro.deploy import Deployment
    return Deployment.build(list(EDGE_NETS) + [f"lm:{LM_ARCH}"],
                            target="tpu", machine_model="auto",
                            artifact_dir=out_dir, seed=SEED)


def check_machine_model(dep) -> None:
    """``"auto"`` must resolve to the chip's stock constants."""
    import jax

    from repro import hw
    want = hw.device_model(jax.devices()[0])
    if dep.machine_model != want:
        raise SmokeFailure(f"machine model {dep.machine_model} is not the "
                           f"stock model of this chip ({want})")


def serve(dep) -> tuple:
    """Warm the router, replay the smoke trace, and require every record
    ``ok``.  Returns ``(router, per-tenant ok counts, phase seconds)``."""
    from repro.obs import workload
    router = dep.serve()
    t0 = time.perf_counter()
    inputs = router.warmup()
    t1 = time.perf_counter()
    tenants = {t.net_id: t.plan.kind for t in dep.fleet.tenants}
    trace = workload.smoke_trace(tenants, edge_iters=EDGE_ITERS,
                                 lm_requests=LM_REQUESTS)
    report = workload.replay(router, trace, inputs=inputs)
    t2 = time.perf_counter()
    bad = [r for r in report.records if r.status != "ok"]
    if bad:
        raise SmokeFailure(f"{len(bad)} replayed request(s) not ok: "
                           f"{bad[:3]}")
    served = collections.Counter(r.tenant for r in report.records)
    want = {nid: LM_REQUESTS if kind == "lm" else EDGE_ITERS
            for nid, kind in tenants.items()}
    if dict(served) != want:
        raise SmokeFailure(f"served {dict(served)}, expected {want}")
    return router, served, {"warmup": t1 - t0, "replay": t2 - t1}


def check_health(router) -> None:
    """No tenant may have leaned on the resilience layer."""
    health = router.health()
    if not health["supervised"]:
        raise SmokeFailure("router serves without its supervisor: retries "
                           "would go uncounted")
    sick = {}
    for nid, st in health["tenants"].items():
        bad = {k: st[k] for k in ("failures", "engine_faults",
                                  "degrade_level", "retries", "degrades",
                                  "breaker_opens") if st[k]}
        if st["state"] != "closed":
            bad["state"] = st["state"]
        if bad:
            sick[nid] = bad
    if sick or health["replan_failures"]:
        raise SmokeFailure(f"degraded serving: {sick}, replan_failures="
                           f"{health['replan_failures']}")


def check_compiled(dep) -> dict:
    """Every edge executable runs a compiled Mosaic kernel.  Returns
    ``{net_id: tpu_custom_call count}``."""
    from repro.kernels import ops
    from repro.serve.engine import EdgeEngine
    if ops.use_interpret():
        raise SmokeFailure("Pallas kernels would run in interpret mode")
    calls = {}
    for nid, eng in dep.engines.items():
        if isinstance(eng, EdgeEngine):
            calls[nid] = eng.hlo_text().count("tpu_custom_call")
            if not calls[nid]:
                raise SmokeFailure(f"{nid}: no tpu_custom_call in the "
                                   f"served executable")
    return calls


def check_outputs(dep) -> dict:
    """Served (fused) output vs the per-layer int8 path and the float32
    reference, per edge net, on a seeded input.  Returns
    ``{net_id: (max fused-vs-per-layer diff, relative float error)}``."""
    import jax
    import numpy as np

    from repro.models import edge
    from repro.serve.engine import EdgeEngine
    out = {}
    for nid, eng in dep.engines.items():
        if not isinstance(eng, EdgeEngine):
            continue
        cfg = eng.cfg
        x = jax.random.normal(jax.random.PRNGKey(SEED + 1),
                              (cfg.batch, cfg.dims[0]))
        y_fused = np.asarray(eng.infer(x))
        per_layer = jax.jit(lambda v, eng=eng: edge.edge_forward_q8(
            eng.qparams, eng.cfg, v, x_scale=eng.x_scale, plan=eng.plan,
            fused=False))
        y_layer = np.asarray(per_layer(x))
        # The engine's float weights: EdgeEngine seeds them from `SEED`.
        params = edge.init_edge(jax.random.PRNGKey(SEED), cfg)
        with jax.default_matmul_precision("highest"):
            y_ref = np.asarray(edge.edge_forward(params, cfg, x))
        if not np.allclose(y_fused, y_layer, rtol=FUSED_TOL, atol=FUSED_TOL):
            raise SmokeFailure(
                f"{nid}: fused output differs from the per-layer path by "
                f"{np.abs(y_fused - y_layer).max():.3g}")
        rel = float(np.abs(y_fused - y_ref).max() / np.abs(y_ref).max())
        if not rel <= FLOAT_REF_BOUND:
            raise SmokeFailure(f"{nid}: relative error {rel:.4f} vs the "
                               f"float32 reference exceeds "
                               f"{FLOAT_REF_BOUND}")
        out[nid] = (float(np.abs(y_fused - y_layer).max()), rel)
    return out


def main() -> int:
    try:
        device = check_device()
    except SmokeFailure as exc:
        print(f"chip_smoke: FAILED: {exc}", file=sys.stderr)
        return 1
    log("device", platform=device["platform"], kind=repr(device["kind"]),
        count=device["count"])

    sys.path.insert(0, str(ROOT / "src"))
    from jax import monitoring

    from repro.runtime import enable_compile_cache
    cache_dir = enable_compile_cache()
    cache = collections.Counter()
    monitoring.register_event_listener(
        lambda event, **_: cache.update([event.rsplit("/", 1)[-1]]))

    try:
        t0 = time.perf_counter()
        dep = build()
        build_s = time.perf_counter() - t0
        check_machine_model(dep)
        log("build", wall_s=f"{build_s:.3f}",
            machine_model=repr(dep.stage_results["characterize"].detail),
            check=repr(dep.stage_results["verify"].detail))
        router, served, phase_s = serve(dep)
        for t in dep.fleet.tenants:
            label = " (smoke config)" if t.plan.kind == "lm" else ""
            log("served", tenant=t.net_id + label, kind=t.plan.kind,
                ok=served[t.net_id])
        check_health(router)
        log("health", failures=0, retries=0, degrades=0, breaker="closed")
        t0 = time.perf_counter()
        calls = check_compiled(dep)
        for nid, n in calls.items():
            log("compiled", tenant=nid, tpu_custom_call=n)
        for nid, (diff, rel) in check_outputs(dep).items():
            log("outputs", tenant=nid, fused_vs_per_layer_max=f"{diff:.3g}",
                fused_tol=FUSED_TOL, float_ref_rel=f"{rel:.4f}",
                float_ref_bound=FLOAT_REF_BOUND)
        phase_s["checks"] = time.perf_counter() - t0
    except SmokeFailure as exc:
        print(f"chip_smoke: FAILED: {exc}", file=sys.stderr)
        return 1
    log("phases", build_s=f"{build_s:.3f}",
        **{f"{k}_s": f"{v:.3f}" for k, v in phase_s.items()})
    log("compile_cache", dir=cache_dir, hits=cache["cache_hits"],
        misses=cache["cache_misses"])
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
