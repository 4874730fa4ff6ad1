"""What a driver hands back: one entry per scheduled request."""

from __future__ import annotations

import dataclasses

import numpy as np

OK, FAILED, UNSERVED = 0, 1, 2


@dataclasses.dataclass
class Records:
    """Per scheduled request; times are ``perf_counter`` seconds, 0 where a
    request was not issued."""
    due: np.ndarray          # scheduled arrival
    call: np.ndarray         # entry into the system's entry point
    ret: np.ndarray          # the entry point returned
    done: np.ndarray         # output on the host
    status: np.ndarray       # OK / FAILED / UNSERVED (left when the
                             # window closed)
    first_profiled: int      # first request issued under the profiler
                             # (the number of requests when none was)
    samples: dict            # tenant index -> {slot: (request, output)}
    # Set by drivers of token-generating systems, left None by the others:
    first: np.ndarray | None = None   # first output token on the host
    tokens: np.ndarray | None = None  # output tokens of the request
