"""The comparison that decides ``correct``.

After the window has closed and the program is freed, each tenant's sampled
answers (a reservoir sample of the requests the window completed, drawn
from the seed) are compared with the float32 reference run on the same
inputs.  Two numbers are compared per tenant:

``err_med``
    the median over every output element of the tenant's sampled requests
    of ``|served - reference|``, divided by the RMS of the reference's
    outputs.  The served nets are int8 with activation scales calibrated on
    one 8-row batch, so a few rows of some requests clip and are off by
    tens of percent; the median looks past those and reads the rounding
    every element carries.  Computing the nets in int4, one precision step
    down, moves every element and reads several times higher.
``off_share``
    the share of the tenant's sampled requests whose own error, the RMS of
    ``served - reference`` over the request's batch divided by the RMS of
    all the tenant's reference outputs, exceeds the tenant's ``req_err``
    threshold.  A median cannot see a fault that hits a minority of
    requests (another request's answer, half a batch left out on some
    requests); this counts them one by one.

``failed`` is compared too, against 0: a refused or faulted request is an
answer that never came.  The limits are in the configuration file;
PERF.md gives the readings each was set from.
"""

from __future__ import annotations

import numpy as np

from chipbench import reference

SAMPLES_PER_TENANT = 256


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
    """``{number: {"value", "limit"}}``.  A tenant with no sampled answer
    reads ``None``, which is not correct.  ``replace(weights, xs, act)``
    puts another computation in the program's place (the control);
    ``errors``, when given, receives each tenant's ``req_err`` array."""
    limits = config["limits"]
    out = {"failed": {"value": failed, "limit": 0}}
    got = sample_inputs(samples, sched, pools)
    for net in config["nets"]:
        name = net["name"]
        values = {"err_med": None, "off_share": None}
        if name in got:
            xs, ys = got[name]
            weights = reference.init_weights(seed, net["dims"])
            if replace is not None:
                ys = replace(weights, xs, net["act"])
            ref = reference.forward(weights, xs, net["act"])
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


def is_correct(compared: dict) -> bool:
    return all(c["value"] is not None and c["value"] <= c["limit"]
               for c in compared.values())
