"""Span/trace primitives: the measurement substrate under the serving stack.

A :class:`Span` is one named host-side interval (``perf_counter`` based)
with an optional **trace id** — the request id that lets a request's
``queue -> prefill_chunk -> decode_step`` decomposition be reassembled from
the flat span stream — plus free-form attributes (tenant, token counts,
cache-hit flags).  A :class:`Tracer` is an append-only, bounded span sink
that the serving runtime (:mod:`repro.serve`), the deployment stages
(:mod:`repro.deploy`) and the characterization harness all emit into.

An enabled tracer's :meth:`Tracer.span` also opens a
``jax.profiler.TraceAnnotation`` of the same name for the span's extent.
With no profiler session running that costs about a microsecond; while one
runs, every program span lands in its ``.xplane.pb`` on the emitting thread,
on the same clock as the device's operations, so an idle gap on the device
can be put down to the innermost span open on the host.  Spans recorded
with :meth:`Tracer.add` after the fact stay in memory only.

Overhead discipline: every emit site in a hot path guards on
``tracer.enabled`` (one attribute read) before doing any work, and the
shared :data:`NULL_TRACER` used as the default is permanently disabled —
tracing-off dispatch costs one branch (guarded by a micro-test in
``tests/test_obs.py``).  jax is imported on the first enabled span, not
here: the module must stay cheap to import and safe to use from any layer.
"""

from __future__ import annotations

import dataclasses
import itertools
import threading
import time
from typing import Any, Iterable


@dataclasses.dataclass(frozen=True)
class Span:
    """One completed interval: ``[t0_s, t0_s + dur_s]`` on this host's
    ``perf_counter`` clock (monotonic; comparable only within a process)."""
    name: str                       # span kind: "decode_step", "queue", ...
    t0_s: float
    dur_s: float
    trace_id: int | str | None = None   # request id (None = engine-level)
    attrs: dict = dataclasses.field(default_factory=dict)

    @property
    def t1_s(self) -> float:
        return self.t0_s + self.dur_s

    def to_dict(self) -> dict:
        return {"name": self.name, "t0_s": self.t0_s, "dur_s": self.dur_s,
                "trace_id": self.trace_id, "attrs": dict(self.attrs)}


_annotation = None


def _trace_annotation():
    """``jax.profiler.TraceAnnotation``, imported on first use."""
    global _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    return _annotation


class _SpanCtx:
    """Context manager recording one span on exit (exceptions included —
    a span that died is still time the caller spent), inside a profiler
    annotation of the same name."""
    __slots__ = ("_tracer", "_name", "_trace", "_attrs", "_t0", "_ann")

    def __init__(self, tracer, name, trace, attrs):
        self._tracer, self._name = tracer, name
        self._trace, self._attrs = trace, attrs

    def __enter__(self):
        self._ann = _trace_annotation()(self._name)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def set(self, **attrs) -> None:
        """Add attributes known only inside the span (e.g. an output's
        size once the call that makes it has returned)."""
        self._attrs.update(attrs)

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        tracer = self._tracer
        if tracer.enabled:
            tracer._record(self._name, self._t0, t1, self._trace,
                           self._attrs)
        return False


class _NoopCtx:
    __slots__ = ()

    def __enter__(self):
        return self

    def set(self, **attrs) -> None:
        pass

    def __exit__(self, *exc):
        return False


_NOOP_CTX = _NoopCtx()


class Tracer:
    """Bounded, thread-safe span sink.

    ``maxlen`` caps memory for long-lived serving loops: once full, new
    spans are counted in :attr:`dropped` instead of appended (the exporters
    surface the truncation rather than silently pretending full coverage).
    """

    def __init__(self, *, enabled: bool = True, maxlen: int = 100_000):
        self.enabled = enabled
        self.maxlen = maxlen
        self.dropped = 0
        self._spans: list[tuple] = []     # Span fields, in order
        self._lock = threading.Lock()
        self._trace_ids = itertools.count(1)

    # -- emission ---------------------------------------------------------
    def span(self, name: str, *, trace=None, **attrs):
        """Context manager timing the enclosed block, also written into a
        running profiler trace.  With the tracer disabled this returns a
        shared no-op (no allocation, no clock); a hot path reads
        :attr:`enabled` itself first, so it builds no ``attrs`` either."""
        if not self.enabled:
            return _NOOP_CTX
        return _SpanCtx(self, name, trace, attrs)

    def add(self, name: str, t0_s: float, t1_s: float, *, trace=None,
            **attrs) -> None:
        """Record an explicit interval (e.g. queue wait measured between a
        submit and an admit that happen in different call frames)."""
        if self.enabled:
            self._record(name, t0_s, t1_s, trace, attrs)

    def _record(self, name, t0_s, t1_s, trace, attrs) -> None:
        # A plain tuple in the sink: a frozen Span takes microseconds to
        # build and keeps the collector busy, so :attr:`spans` builds them
        # when read, off the serving path.
        rec = (name, t0_s, max(t1_s - t0_s, 0.0), trace, attrs)
        with self._lock:
            if len(self._spans) >= self.maxlen:
                self.dropped += 1
                return
            self._spans.append(rec)

    def next_trace_id(self) -> int:
        """A fresh per-tracer trace id: the edge path's request id, drawn
        once per ``Router.infer`` (or per ``EdgeEngine.infer`` called
        directly) and carried by every span of that request."""
        return next(self._trace_ids)

    # -- access -----------------------------------------------------------
    @property
    def spans(self) -> list[Span]:
        """A snapshot copy — safe to iterate while serving continues."""
        with self._lock:
            recs = list(self._spans)
        return [Span(*r) for r in recs]

    def by_trace(self, trace_id) -> list[Span]:
        return [s for s in self.spans if s.trace_id == trace_id]

    def by_name(self, name: str) -> list[Span]:
        return [s for s in self.spans if s.name == name]

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()
            self.dropped = 0

    def __len__(self) -> int:
        return len(self._spans)

    def __bool__(self) -> bool:            # "if tracer:" == "is tracing on"
        return self.enabled


class _NullTracer(Tracer):
    """The permanently-disabled default.  Shared process-wide, so it must be
    impossible to flip on by accident (``enabled`` writes are ignored)."""

    def __init__(self):
        super().__init__(enabled=False, maxlen=0)

    @property
    def enabled(self) -> bool:
        return False

    @enabled.setter
    def enabled(self, _value) -> None:     # silently refuse: stay disabled
        pass


NULL_TRACER = _NullTracer()


def percentile(xs: Iterable[float], q: float) -> float:
    """Nearest-rank percentile over a finite sample; 0.0 on empty input.
    The same convention ``TenantMetrics`` uses, shared so span aggregates
    and tenant metrics never disagree on what "p95" means."""
    xs = sorted(xs)
    if not xs:
        return 0.0
    if q <= 0:
        return xs[0]
    import math
    return xs[min(len(xs) - 1, int(math.ceil(q * len(xs))) - 1)]


def summarize(durs: Iterable[float]) -> dict[str, Any]:
    """count/mean/p50/p95/total over a duration sample (seconds)."""
    xs = sorted(durs)
    n = len(xs)
    total = sum(xs)
    return {
        "count": n,
        "total_s": total,
        "mean_s": total / n if n else 0.0,
        "p50_s": xs[n // 2] if n else 0.0,
        "p95_s": percentile(xs, 0.95),
    }
