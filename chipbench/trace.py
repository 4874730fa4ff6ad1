"""Reduction of a JAX profiler trace to device busy time, kernel time and
what the host was doing while the device sat idle.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.  On a
TPU its device planes are named ``/device:TPU:<n>``; the line ``XLA Ops``
holds one event per HLO operation run, named ``%<instruction> = <hlo text>``,
with start and duration in nanoseconds on the host's clock.  A Pallas kernel
appears there as the custom call named after the kernel (``%repro_gemm_int8.1
= ... custom-call(...)``).  Host threads are ``/host:CPU`` lines; the one
that carries the benchmark's ``TraceAnnotation``s (``wait_arrival``,
``router.infer``) is the serving thread.

Everything below works on plain arrays, so the tests check it on a small
recorded trace without a chip.
"""

from __future__ import annotations

import dataclasses
import pathlib
import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
ANNOTATIONS = ("wait_arrival", "router.infer")
NO_EVENT = "python (no traced call)"
_SUFFIX = re.compile(r"\.\d+$")


def op_name(event_name: str) -> str:
    """``%repro_gemm_int8.1 = f32[...] custom-call(...)`` -> ``repro_gemm_int8``."""
    head = event_name.split(" = ", 1)[0].lstrip("%")
    return _SUFFIX.sub("", head)


@dataclasses.dataclass
class Events:
    """One line's events: names and ``[start, end)`` in seconds."""
    names: list[str]
    start: np.ndarray
    end: np.ndarray

    @classmethod
    def of(cls, rows) -> "Events":
        rows = sorted(rows, key=lambda r: (r[1], -r[2]))  # outer first
        return cls(names=[r[0] for r in rows],
                   start=np.asarray([r[1] for r in rows], np.float64),
                   end=np.asarray([r[2] for r in rows], np.float64))

    def __len__(self) -> int:
        return len(self.names)


@dataclasses.dataclass
class Trace:
    devices: dict[str, Events]      # device plane -> its XLA Ops
    serving_thread: Events          # host events of the annotated thread


def find_xplane(directory) -> pathlib.Path:
    """The newest ``*.xplane.pb`` under ``directory``."""
    files = sorted(pathlib.Path(directory).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return files[-1]


def load(path) -> Trace:
    """Read an ``.xplane.pb`` (a file or a profile directory)."""
    import jax
    path = pathlib.Path(path)
    if path.is_dir():
        path = find_xplane(path)
    data = jax.profiler.ProfileData.from_file(str(path))
    devices, serving = {}, None
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = Events.of(
                        (e.name, e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9)
                        for e in line.events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                rows = [(e.name, e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9)
                        for e in line.events]
                if serving is None and any(r[0] in ANNOTATIONS
                                           for r in rows):
                    serving = Events.of(rows)
    return Trace(devices=devices,
                 serving_thread=serving or Events.of([]))


def union(start: np.ndarray, end: np.ndarray) -> np.ndarray:
    """Merge intervals; returns an ``(n, 2)`` array of disjoint intervals."""
    if len(start) == 0:
        return np.zeros((0, 2))
    order = np.argsort(start, kind="stable")
    s, e = start[order], end[order]
    reach = np.maximum.accumulate(e)
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > reach[:-1]
    idx = np.flatnonzero(new)
    return np.stack([s[idx], np.append(reach[idx[1:] - 1], reach[-1])], 1)


def busy_s(ops: Events, t0: float, t1: float) -> float:
    """Seconds within ``[t0, t1]`` in which some operation ran."""
    u = union(np.clip(ops.start, t0, t1), np.clip(ops.end, t0, t1))
    return float(np.sum(u[:, 1] - u[:, 0]))


def idle_gaps(ops: Events, t0: float, t1: float) -> np.ndarray:
    """``(n, 2)`` intervals within ``[t0, t1]`` in which no operation ran."""
    u = union(np.clip(ops.start, t0, t1), np.clip(ops.end, t0, t1))
    edges = np.concatenate([[t0], u.ravel(), [t1]]).reshape(-1, 2)
    return edges[edges[:, 1] > edges[:, 0]]


def kernel_s(ops: Events, prefix: str = "repro_") -> float:
    """Summed device time of the events of kernels named ``prefix*``."""
    keep = np.asarray([op_name(n).startswith(prefix) for n in ops.names],
                      bool)
    return float(np.sum((ops.end - ops.start)[keep])) if len(ops) else 0.0


def top_ops(ops: Events, n: int = 10) -> list[list]:
    """The ``n`` operations that took the most device time, by name."""
    total: dict[str, float] = {}
    for name, d in zip(ops.names, ops.end - ops.start):
        key = op_name(name)
        total[key] = total.get(key, 0.0) + float(d)
    return [[k, v] for k, v in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def attribute_gaps(gaps: np.ndarray, host: Events,
                   n: int = 10) -> list[list]:
    """Idle device seconds by what the serving thread was inside during
    them: each instant of each gap goes to the innermost host event open at
    that instant (events on one thread nest), or to ``NO_EVENT`` when none
    was.  The ``n`` largest totals."""
    keep = np.flatnonzero(host.end > host.start)
    start, end = host.start[keep], host.end[keep]
    names = [host.names[k] for k in keep]
    parent = np.full(len(start), -1)
    stack: list[int] = []
    for i in range(len(start)):
        while stack and end[stack[-1]] <= start[i]:
            stack.pop()
        parent[i] = stack[-1] if stack else -1
        stack.append(i)
    cuts = np.unique(np.concatenate([start, end, np.ravel(gaps)]))
    total: dict[str, float] = {}
    for a, b in gaps:
        pts = cuts[np.searchsorted(cuts, a):np.searchsorted(cuts, b) + 1]
        for x, y in zip(pts[:-1], pts[1:]):
            mid = 0.5 * (x + y)
            i = int(np.searchsorted(start, mid, "right")) - 1
            while i >= 0 and end[i] <= mid:
                i = int(parent[i])
            name = names[i] if i >= 0 else NO_EVENT
            total[name] = total.get(name, 0.0) + float(y - x)
    return [[k, v] for k, v in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]
