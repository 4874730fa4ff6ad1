"""The one traffic generator: a mix file's parameters -> arrivals and inputs.

A mix is a JSON file in this directory, found by the name a cell gives as
its ``traffic``.  Its keys:

``driver``
    The module in ``chipbench/drivers/`` that plays the schedule.
``arrivals``
    ``{"process": <name>, "rate_hz": <mean rate>, ...}``.  The process is
    the file ``processes/<name>.py``, found by that name; it reads
    ``rate_hz`` and the further parameters it lists in ``PARAMS``, and
    returns the window's sorted arrival times.  A new process is a new file
    there, and edits nothing that is there.
``tenants``, ``zipf_s``
    The nets that receive requests, most popular first, and (with more
    than one tenant) the Zipf exponent of their popularity: the request
    share of rank ``r`` is proportional to ``1 / r**zipf_s``.

Every seed gets the same amount of work: the number of requests, the
requests per tenant, the time spent in each arrival state and the number of
arrivals in it are fixed by the parameters and the window.  The seed only
orders them and places them in time, and draws the inputs (standard normal,
float32, the distribution the served nets calibrate on): ``INPUT_POOL``
distinct batches per tenant, of which a tenant's ``k``-th request sends
batch ``k mod INPUT_POOL``.  So two seeds differ by the arrangement of the
work, never by its size.
"""

from __future__ import annotations

import dataclasses
import json
import pathlib

import numpy as np

from chipbench.loader import load_module

HERE = pathlib.Path(__file__).resolve().parent
PROCESSES = HERE / "processes"
INPUT_POOL = 2048


@dataclasses.dataclass
class Schedule:
    """A window's requests in arrival order."""
    arrival_s: np.ndarray      # float64, seconds from window start, sorted
    tenant: np.ndarray         # int32 index into ``tenants``
    pool_index: np.ndarray     # int32 index into the tenant's input pool
    tenants: list[str]

    def __len__(self) -> int:
        return len(self.arrival_s)

    def per_tenant(self) -> dict[str, int]:
        counts = np.bincount(self.tenant, minlength=len(self.tenants))
        return dict(zip(self.tenants, counts.tolist()))


def process(name: str):
    """The arrival process ``<name>.py``."""
    path = PROCESSES / f"{name}.py"
    if not path.is_file():
        raise ValueError(f"arrival process {name!r}: no file {path}")
    return load_module(path)


def load(name: str, directory=HERE) -> dict:
    """The mix file ``<name>.json``, checked."""
    path = pathlib.Path(directory) / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} at {path}")
    spec = json.loads(path.read_text())
    for key in ("driver", "arrivals", "tenants"):
        if key not in spec:
            raise ValueError(f"traffic mix {name!r} lacks {key!r}")
    if not spec["tenants"]:
        raise ValueError(f"traffic mix {name!r} names no tenant")
    if len(spec["tenants"]) > 1 and "zipf_s" not in spec:
        raise ValueError(f"traffic mix {name!r} has several tenants and "
                         f"no 'zipf_s'")
    arr = spec["arrivals"]
    proc = process(arr.get("process", ""))
    if not arr.get("rate_hz", 0) > 0:
        raise ValueError(f"traffic mix {name!r}: rate_hz must be > 0")
    lacking = [p for p in proc.PARAMS if p not in arr]
    if lacking:
        raise ValueError(f"traffic mix {name!r}: arrival process "
                         f"{arr['process']!r} needs {lacking}")
    return spec


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([seed, stream]))


def apportion(weights: np.ndarray, n: int) -> np.ndarray:
    """``n`` whole items split in proportion to ``weights`` by largest
    remainder (ties to the earlier entry)."""
    exact = weights / weights.sum() * n
    counts = np.floor(exact).astype(int)
    short = n - counts.sum()
    counts[np.argsort(-(exact - counts), kind="stable")[:short]] += 1
    return counts


def tenant_shares(spec: dict) -> np.ndarray:
    """Request share of each tenant, in the mix's order."""
    n = len(spec["tenants"])
    w = 1.0 / np.arange(1, n + 1) ** float(spec.get("zipf_s", 0.0))
    return w / w.sum()


def schedule(spec: dict, seconds: float, seed: int) -> Schedule:
    """The window's requests for ``seed``."""
    arr = spec["arrivals"]
    arrival = process(arr["process"]).arrivals(arr, seconds, _rng(seed, 1))
    n = len(arrival)
    counts = apportion(tenant_shares(spec), n)    # the same for every seed
    tenant = _rng(seed, 2).permutation(
        np.repeat(np.arange(len(counts)), counts)).astype(np.int32)
    pool_index = np.zeros(n, np.int32)
    for t in range(len(counts)):
        mask = tenant == t
        pool_index[mask] = np.arange(mask.sum()) % INPUT_POOL
    return Schedule(arrival_s=arrival, tenant=tenant, pool_index=pool_index,
                    tenants=list(spec["tenants"]))


def input_pool(spec: dict, widths: dict[str, int], batch: int,
               seed: int) -> dict[str, np.ndarray]:
    """``{tenant: (INPUT_POOL, batch, width) float32}`` for ``seed``."""
    out = {}
    for i, name in enumerate(spec["tenants"]):
        rng = _rng(seed, 100 + i)
        out[name] = rng.standard_normal((INPUT_POOL, batch, widths[name]),
                                        np.float32)
    return out
