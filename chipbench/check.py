"""The comparison that decides ``correct``.

After the window has closed and the program is freed, each tenant's sampled
answers (a reservoir sample of the requests the window completed, drawn
from the seed) are compared with the plain reference the configuration
names under ``"reference"``, run on the same inputs.  The configuration's
kind (``chipbench/kinds/<kind>.py``) makes the comparison and names the
numbers compared; ``kinds/edge_chain.py`` describes those of the edge nets.
``failed`` is compared too, against 0: a refused or faulted request is an
answer that never came.  The limits are in the configuration file;
PERF.md gives the readings each was set from.
"""

from __future__ import annotations

from chipbench.loader import load_kind

SAMPLES_PER_TENANT = 256


def compare(config: dict, seed: int, samples: dict, sched, pools, *,
            failed: int, replace=None, errors=None) -> dict:
    """``{number: {"value", "limit"}}`` from the configuration's kind (its
    ``compare``, which documents ``replace`` and ``errors``)."""
    return load_kind(config).compare(config, seed, samples, sched, pools,
                                     failed=failed, replace=replace,
                                     errors=errors)


def is_correct(compared: dict) -> bool:
    return all(c["value"] is not None and c["value"] <= c["limit"]
               for c in compared.values())
