"""The ``edge_chain`` kind: int8 dense chains served batch by batch through
``Router.infer``.  A configuration without a ``"kind"`` key is of this kind.

Its configuration lists ``"nets"``, each with a ``name``, its widths
``dims``, its activation ``act`` and its ``batch``; ``"reference"``, the
path, from the checkout's root, of the plain reference the answers are
compared with; and ``"limits"`` on the numbers compared.

Every kind provides these functions, which the harness calls by name:

``build(config, seed, tracer)``
    the system users get, built from ``seed``, with the program's spans
    going to ``tracer`` (``None``: spans off).  It returns the router the
    cell's driver plays the schedule through.
``input_pool(traffic, config, seed)``
    ``{tenant: inputs}`` the driver sends, indexed by a request's
    ``pool_index``, drawn from ``seed``.
``warm(router, pools)``
    runs every shape the window sends once, before the window.
``batch(config)``
    the rows one request carries.
``request_work(config, sched, pools, records)``
    a ``work.Work`` for each scheduled request, counted from its sizes.
``compare(config, seed, samples, sched, pools, *, failed, replace=None,
errors=None)``
    ``{number: {"value", "limit"}}``: the sampled answers against the
    configuration's reference (see :func:`compare`).
``control(config)``
    the reference computed one precision step down, which ``compare``
    takes as ``replace`` and has to judge not correct.
"""

from __future__ import annotations

import functools
import pathlib

import numpy as np

from chipbench import work
from chipbench.loader import load_module
from chipbench.traffic import generate

ROOT = pathlib.Path(__file__).resolve().parents[2]


def nets(config: dict) -> dict:
    return {n["name"]: n for n in config["nets"]}


def build(config: dict, seed: int, tracer):
    """The configuration's nets behind the router users get from
    ``Deployment.build(..., target="tpu", machine_model="auto", seed=seed)``
    and ``dep.serve()`` with its defaults."""
    from repro.deploy import Deployment
    from repro.models.edge import EdgeConfig
    edge = [EdgeConfig(name=n["name"], dims=tuple(n["dims"]), act=n["act"],
                       batch=n["batch"]) for n in config["nets"]]
    dep = Deployment.build(edge, target="tpu", machine_model="auto",
                           seed=seed,
                           trace=tracer if tracer is not None else False)
    return dep.serve()


def batch(config: dict) -> int:
    batches = {n["batch"] for n in config["nets"]}
    if len(batches) != 1:
        raise ValueError("every net of a configuration serves one batch")
    return next(iter(batches))


def input_pool(traffic: dict, config: dict, seed: int) -> dict:
    """Standard-normal float32 batches of each tenant's input width
    (``generate.input_pool``)."""
    held = nets(config)
    tenants = list(traffic["tenants"])
    missing = [t for t in tenants if t not in held]
    if missing:
        raise KeyError(f"traffic names nets {missing} that the "
                       f"configuration does not hold")
    return generate.input_pool(traffic,
                               {t: held[t]["dims"][0] for t in tenants},
                               batch(config), seed)


def warm(router, pools: dict) -> None:
    for t, pool in pools.items():
        for x in pool[:2]:
            np.asarray(router.infer(t, x))


def request_work(config: dict, sched, pools, records) -> list:
    """One forward of the request's tenant's net at the batch, for every
    scheduled request."""
    held, rows = nets(config), batch(config)
    per_tenant = [work.request_work(held[t]["dims"], rows)
                  for t in sched.tenants]
    return [per_tenant[t] for t in sched.tenant.tolist()]


@functools.cache
def _reference(path: str):
    return load_module(ROOT / path)


def reference(config: dict):
    """The module the configuration names as its ``"reference"``: a float32
    forward of the chain from weights it draws from the seed itself
    (``init_weights``, ``forward``) and its int4 control
    (``forward_int4``)."""
    return _reference(config["reference"])


def control(config: dict):
    return reference(config).forward_int4


def err_med(y: np.ndarray, ref: np.ndarray) -> float:
    rms = float(np.sqrt(np.mean(np.square(ref, dtype=np.float64))))
    return float(np.median(np.abs(y.astype(np.float64) - ref)) / rms)


def req_err(y: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """Per request: the RMS of ``served - reference`` over the request's
    batch, divided by the RMS of all the tenant's reference outputs."""
    rms = float(np.sqrt(np.mean(np.square(ref, dtype=np.float64))))
    diff = (y.astype(np.float64) - ref).reshape(len(y), -1)
    return np.sqrt(np.mean(np.square(diff), axis=1)) / rms


def sample_inputs(samples: dict, sched, pools) -> dict:
    """``{tenant: (inputs, outputs)}`` of the sampled requests."""
    out = {}
    for name, got in samples.items():
        if not got:
            continue
        xs = np.stack([pools[name][sched.pool_index[i]] for i, _ in got])
        ys = np.stack([y for _, y in got])
        out[name] = (xs, ys)
    return out


def compare(config: dict, seed: int, samples: dict, sched, pools, *,
            failed: int, replace=None, errors=None) -> dict:
    """Two numbers per tenant, beside ``failed`` against 0.

    ``err_med``
        the median over every output element of the tenant's sampled
        requests of ``|served - reference|``, divided by the RMS of the
        reference's outputs.  The served nets are int8 with activation
        scales calibrated on one 8-row batch, so a few rows of some
        requests clip and are off by tens of percent; the median looks past
        those and reads the rounding every element carries.  Computing the
        nets in int4, one precision step down, moves every element and
        reads several times higher.
    ``off_share``
        the share of the tenant's sampled requests whose own error
        (:func:`req_err`) exceeds the tenant's ``req_err`` threshold.  A
        median cannot see a fault that hits a minority of requests
        (another request's answer, half a batch left out on some
        requests); this counts them one by one.

    A tenant with no sampled answer reads ``None``, which is not correct.
    ``replace(weights, xs, act)`` puts another computation in the
    program's place (the control); ``errors``, when given, receives each
    tenant's ``req_err`` array."""
    ref_mod = reference(config)
    limits = config["limits"]
    out = {"failed": {"value": failed, "limit": 0}}
    got = sample_inputs(samples, sched, pools)
    for net in config["nets"]:
        name = net["name"]
        values = {"err_med": None, "off_share": None}
        if name in got:
            xs, ys = got[name]
            weights = ref_mod.init_weights(seed, net["dims"])
            if replace is not None:
                ys = replace(weights, xs, net["act"])
            ref = ref_mod.forward(weights, xs, net["act"])
            per_request = req_err(ys, ref)
            if errors is not None:
                errors[name] = per_request
            values = {"err_med": err_med(ys, ref),
                      "off_share": float(np.mean(
                          per_request > limits["req_err"][name]))}
        for number, value in values.items():
            out[f"{name}.{number}"] = {"value": value,
                                       "limit": limits[number][name]}
    return out
