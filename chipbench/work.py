"""Operations and bytes one request's forward needs, counted from widths.

The count is of the algorithm, not of what implements it: padding to the
chip's tiles, and copies the compiler adds, are not work.  So a roofline
share computed from it reads the same work whatever kernels serve the net.

Per request of ``batch`` rows through the dense chain ``dims``:

* ops: ``2 * batch * MACs``, with ``MACs = sum(dims[i] * dims[i+1])``;
* bytes: the int8 weights (one byte per MAC of one row), a float32 scale and
  a float32 bias per output channel, the float32 input batch and the float32
  output batch, each read or written once.
"""

from __future__ import annotations

import dataclasses
import json
import pathlib

PEAKS_FILE = pathlib.Path(__file__).resolve().parent / "peaks.json"


@dataclasses.dataclass(frozen=True)
class Work:
    ops: int
    bytes: int


def macs(dims) -> int:
    return sum(a * b for a, b in zip(dims[:-1], dims[1:]))


def request_work(dims, batch: int) -> Work:
    """Work of one forward of ``batch`` rows through ``dims``."""
    channels = sum(dims[1:])
    return Work(ops=2 * batch * macs(dims),
                bytes=macs(dims) + 2 * 4 * channels
                + 4 * batch * (dims[0] + dims[-1]))


def peaks(device_kind: str, path=PEAKS_FILE) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an error."""
    table = json.loads(pathlib.Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} "
                       f"(known: {sorted(table)})")
    return table[device_kind]


def roofline_s(work: Work, peak: dict) -> float:
    """Least time the chip could take: the larger of ops over the int8 peak
    and bytes over the memory bandwidth."""
    return max(work.ops / peak["int8_ops_per_s"],
               work.bytes / peak["hbm_bytes_per_s"])
