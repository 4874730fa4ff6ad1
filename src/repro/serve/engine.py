"""Serving engine: prefill/decode step builders + continuous batcher +
int8 weight quantization + the extreme-edge low-latency path.

Two serving surfaces:

* **LM serving** (the assigned decode/prefill shapes): jitted prefill and
  decode steps with TP-sharded weights and head/batch-sharded caches, driven
  by a continuous-batching scheduler (fixed slot count, admit-on-free).
* **Edge serving** (the paper's own regime): batch-8, weights-on-chip int8
  dense pipelines executed through a compiled :class:`DeploymentPlan`
  (``repro.plan``): LARE chooses each layer's regime, the two-level tiling
  search fixes the Pallas block shapes, and :class:`EdgeEngine` runs the
  result — no hard-coded tiles or regime flags in this module.
"""

from __future__ import annotations

import collections
import dataclasses
import queue
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro import partition, runtime
from repro.faults import InjectedFault, NonFiniteOutput
from repro.models import api
from repro.models.config import ModelConfig
from repro.obs import NULL_TRACER, summarize

F32 = jnp.float32


# ---------------------------------------------------------------------------
# int8 weight quantization (pjit path; kernels/gemm_int8 covers the TPU path)
# ---------------------------------------------------------------------------

_QUANT_MIN_SIZE = 1 << 16      # only quantize big matmul weights


# Embeddings are gathered directly; norm scales/biases must stay exact.
_QUANT_EXCLUDE = ("emb", "unemb", "pos_emb", "scale", "bias",
                  "ln0", "ln1", "ln2", "ln_x", "post_ln1", "post_ln2",
                  "final_norm", "gn", "q_norm", "kv_norm", "norm_h", "norm_e",
                  "enc_final", "dec_final")


def quantize_params(params: Any, *, min_size: int = _QUANT_MIN_SIZE) -> Any:
    """Per-output-channel symmetric int8 for >=2-D weight leaves.

    Quantized leaves become {"q8","scale"} marker dicts that
    ``runtime.maybe_dequant`` expands per layer inside the scan body, so at
    rest HBM holds int8 (the mixtral-8x22b @ TP16 fit story).  Embedding
    tables are excluded — they are index-gathered outside the dequant hook
    (and int8 embeddings measurably hurt quality anyway)."""

    def one(path, leaf):
        keys = [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]
        if any(k in _QUANT_EXCLUDE for k in keys):
            return leaf
        if (not isinstance(leaf, jnp.ndarray) and
                not hasattr(leaf, "shape")):
            return leaf
        if leaf.ndim < 2 or leaf.size < min_size or \
                not jnp.issubdtype(leaf.dtype, jnp.floating):
            return leaf
        w = leaf.astype(F32)
        scale = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 127.0 + 1e-12
        q = jnp.clip(jnp.round(w / scale), -127, 127).astype(jnp.int8)
        return {"q8": q, "scale": scale.astype(F32)}

    return jax.tree_util.tree_map_with_path(one, params)


def quantized_bytes(params: Any) -> tuple[int, int]:
    """(bytes_before_assuming_bf16, bytes_after) for reporting."""
    before = after = 0
    for leaf in jax.tree.leaves(params):
        n = int(np.prod(leaf.shape))
        before += 2 * n
        after += n if leaf.dtype == jnp.int8 else 2 * n
    return before, after


# ---------------------------------------------------------------------------
# Step builders
# ---------------------------------------------------------------------------

def prepare_params(params: Any, *, plan=None, quantize: bool = False) -> Any:
    """Apply the plan's weight-format decision (int8 vs bf16) to params."""
    if plan is not None:
        quantize = bool(plan.serve.get("quantize_weights", quantize))
    return quantize_params(params) if quantize else params


def build_serve_steps(cfg: ModelConfig, *, max_len: int,
                      quantize: bool = False, plan=None):
    """Returns (prefill_fn, decode_fn) — pure functions ready for jit.

    prefill_fn(params, tokens, state)        -> (logits_last, state)
    decode_fn(params, tokens, state, pos)    -> (logits, state)

    Execution policy comes from the :class:`DeploymentPlan` when one is
    given (``repro.plan.get_or_plan(cfg, target="tpu")``): the plan's
    ``serve`` section selects prefill chunking, and its weight-format
    decision is applied by :func:`prepare_params`, instead of ad-hoc flags
    at every call site.
    """
    chunk = None
    if plan is not None:
        chunk = plan.serve.get("prefill_chunk")

    def prefill_fn(params, tokens, state, extras=None):
        s = tokens.shape[1]
        if chunk is None or s <= chunk:
            logits, state = api.decode_step(params, cfg, tokens, state, 0,
                                            extras=extras or {})
            return logits[:, -1:], state
        logits = None
        for off in range(0, s, chunk):       # unrolled at trace time
            logits, state = api.decode_step(
                params, cfg, tokens[:, off:off + chunk], state, off,
                extras=extras or {})
        return logits[:, -1:], state

    def decode_fn(params, tokens, state, pos, extras=None):
        return api.decode_step(params, cfg, tokens, state, pos,
                               extras=extras or {})

    return prefill_fn, decode_fn


# ---------------------------------------------------------------------------
# Continuous batcher
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray               # (S,) int32
    max_new: int = 16
    out: list = dataclasses.field(default_factory=list)
    done: bool = False
    filled: int = 0                  # prompt tokens prefilled so far (chunked)
    # Trace bookkeeping (perf_counter clock).  ``rid`` doubles as the trace
    # id: every span this request produces — queue wait, prefill chunks,
    # decode steps — carries it, so the flat span stream decomposes back
    # into per-request timelines.  Stamps survive shedding retries and
    # max_new_cap eviction: the request object is the source of truth.
    t_submit: float | None = None    # stamped by ContinuousBatcher.submit
    t_admit: float | None = None     # stamped when a slot is assigned
    t_done: float | None = None      # stamped when the request completes
    # Fault disposition: set (e.g. "non_finite_output") when the request
    # FAILED rather than completed — done=True with error set means the
    # slot was freed and no further tokens are coming, but ``out`` must
    # not be trusted.  The router counts these as per-tenant failures.
    error: str | None = None


@dataclasses.dataclass(frozen=True)
class BatchPolicy:
    """Continuous-batching policy, read from a plan's ``serve`` section.

    The batcher used to hard-code all of this; now the deployment plan (and
    the fleet planner's per-tenant serve sections) decides.  ``None`` keeps
    the permissive default for that knob."""
    slots: int = 4
    prefill_chunk: int | None = None   # prompt tokens prefilled per tick
    admit_per_tick: int | None = None  # max admissions per tick
    max_new_cap: int | None = None     # evict: hard cap on generated tokens

    def __post_init__(self):
        if self.slots < 1:
            raise ValueError(f"slots must be >= 1, got {self.slots}")
        for name in ("prefill_chunk", "admit_per_tick", "max_new_cap"):
            v = getattr(self, name)
            # A zero chunk would stall prefill forever (no progress, no
            # decode, and run_until_drained's tick bound never advances).
            if v is not None and v < 1:
                raise ValueError(f"{name} must be >= 1 or None, got {v}")

    @classmethod
    def from_plan(cls, plan, **overrides) -> "BatchPolicy":
        fields = {f.name for f in dataclasses.fields(cls)}
        unknown = set(overrides) - fields
        if unknown:
            raise TypeError(
                f"unknown BatchPolicy override(s): {sorted(unknown)} "
                f"(valid: {sorted(fields)})")
        serve = dict(getattr(plan, "serve", None) or {})
        slots = serve.get("slots")
        kw = {
            # `is None`, not truthiness: an explicit 0 in a plan must reach
            # __post_init__'s validation, not silently become the default.
            "slots": cls.slots if slots is None else slots,
            "prefill_chunk": serve.get("prefill_chunk"),
            "admit_per_tick": serve.get("admit_per_tick"),
            "max_new_cap": serve.get("max_new_cap"),
        }
        kw.update(overrides)
        return cls(**kw)


class ContinuousBatcher:
    """Fixed-slot continuous batching over the jitted decode step.

    Slots hold independent sequences; finished slots admit queued requests
    (per-slot position tracking; greedy sampling).  Admission, eviction and
    chunked-prefill sizes come from a :class:`BatchPolicy` — pass ``plan=``
    (a :class:`~repro.plan.artifact.DeploymentPlan`) to read the policy from
    the plan's ``serve`` section instead of the defaults.  CPU-scale smoke
    models exercise the exact code path the TPU deployment jits.
    """

    def __init__(self, cfg: ModelConfig, params, *, slots: int | None = None,
                 max_len: int = 256, plan=None,
                 policy: "BatchPolicy | None" = None, tracer=None):
        self.cfg, self.params = cfg, params
        if policy is None:
            policy = (BatchPolicy.from_plan(plan) if plan is not None
                      else BatchPolicy())
        if slots is not None:           # explicit arg outranks the plan
            policy = dataclasses.replace(policy, slots=slots)
        self.policy = policy
        self.plan = plan
        self.slots, self.max_len = policy.slots, max_len
        # Span-decomposed service time.  The per-kind windows are ALWAYS
        # maintained (a handful of perf_counter calls per tick, invisible
        # next to a jitted decode) so decode-step p50 exists for the drift
        # watcher even with tracing off; the tracer additionally receives
        # per-request spans when one is attached (router or Deployment).
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.trace_label = cfg.name
        self._windows: dict[str, collections.deque] = {}
        self._span_totals: dict[str, int] = {}
        self.state = api.init_decode_state(cfg, self.slots, max_len)
        self.pos = np.zeros((self.slots,), np.int32)
        self.active: list[Request | None] = [None] * self.slots
        self.queue: "queue.Queue[Request]" = queue.Queue()

        # Per-slot decode: vmap over the slot axis so every slot advances at
        # ITS OWN cache position (staggered admissions must not share a
        # cursor), with `live` masking state writes so idle slots stay
        # byte-identical (recurrent families have no overwritable cache).
        # The batch axis is not uniform across state leaves (layer-stacked
        # caches carry it at axis 1, unstacked tails at axis 0): recover it
        # per leaf by diffing specs at two batch sizes.
        s1 = api.decode_state_specs(cfg, 1, max_len)
        s2 = api.decode_state_specs(cfg, 2, max_len)

        def batch_axis(a, b):
            for ax, (x, y) in enumerate(zip(a.shape, b.shape)):
                if x != y:
                    return ax
            return 0

        axes = self._axes = jax.tree.map(batch_axis, s1, s2)

        def decode_one(p, tok, state, pos, live):
            state_b = jax.tree.map(lambda v, ax: jnp.expand_dims(v, ax),
                                   state, axes)
            logits, new_state = api.decode_step(p, cfg, tok.reshape(1, 1),
                                                state_b, pos)
            new_state = jax.tree.map(
                lambda old, new, ax: jnp.where(live, jnp.squeeze(new, ax),
                                               old),
                state, new_state, axes)
            return logits[0], new_state

        self._decode = jax.jit(
            jax.vmap(decode_one, in_axes=(None, 0, axes, 0, 0),
                     out_axes=(0, axes)))
        self._steps = 0
        self._hlo_text: str | None = None
        self._reset_fn = None            # jitted slot reset, built on demand
        # Fault hooks (repro.faults): ``injector`` is armed by
        # Router.arm_faults for chaos runs; unarmed it costs one ``is not
        # None`` per tick.  ``faults`` counts failed requests/ticks
        # (injected or organic, e.g. non-finite logits).
        self.injector = None
        self.faults = 0

    def hlo_text(self) -> str:
        """Post-optimization HLO of the ACTUAL jitted decode step — the
        executable every decode tick runs, at serving shapes (slots, live
        masking, cache axes).  Feeds the loop-aware analyzer
        (:func:`repro.launch.hlo_analysis.analyze_hlo`) so the profiler can
        report model-FLOPs vs compiled-FLOPs overhead on the real
        executable instead of a stand-in.  Compiled once and cached."""
        if self._hlo_text is None:
            tok = np.zeros((self.slots,), np.int32)
            live = np.zeros((self.slots,), bool)
            self._hlo_text = self._decode.lower(
                self.params, tok, self.state, self.pos.copy(),
                live).compile().as_text()
        return self._hlo_text

    def submit(self, req: Request):
        req.t_submit = time.perf_counter()
        self.queue.put(req)

    # -- span recording ----------------------------------------------------
    def _record(self, kind: str, t0: float, t1: float, *, trace=None,
                emit: bool = True, **attrs):
        """One observed interval: window (always) + tracer (when enabled).
        ``emit=False`` keeps the window observation but skips the tracer —
        used when the caller emits finer-grained (per-request) spans for the
        same interval, so aggregates never double-count it."""
        win = self._windows.get(kind)
        if win is None:
            win = self._windows[kind] = collections.deque(maxlen=512)
            self._span_totals[kind] = 0
        win.append(t1 - t0)
        self._span_totals[kind] += 1
        if emit and self.tracer.enabled:
            self.tracer.add(kind, t0, t1, trace=trace,
                            tenant=self.trace_label, **attrs)

    def span_stats(self) -> dict:
        """Windowed per-kind service-time aggregates (count/mean/p50/p95
        over the recent window, plus the lifetime observation count)."""
        out = {}
        for kind, win in self._windows.items():
            agg = summarize(win)
            agg["total_count"] = self._span_totals[kind]
            out[kind] = agg
        return out

    @property
    def measured_decode_p50_s(self) -> float:
        """Median decode-step service time over the recent window — queue
        wait and prefill excluded, so it is directly comparable to the LM
        plan's ``est_latency_s`` (an LM plan models ONE decode step).  This
        is the statistic that lets LM tenants join drift replanning."""
        win = self._windows.get("decode_step")
        return summarize(win)["p50_s"] if win else 0.0

    @property
    def decode_steps_observed(self) -> int:
        return self._span_totals.get("decode_step", 0)

    def _decode_masked(self, tok: np.ndarray, live: np.ndarray):
        # Snapshot the host buffers: CPU device_put can alias numpy memory
        # zero-copy while dispatch is async, so handing jax the live buffers
        # (mutated by the admit/step loops) races.  The copies are local to
        # this call and never mutated.
        logits, self.state = self._decode(
            self.params,
            # Deliberate sync: sampled tokens must reach the host to detect EOS.
            np.array(tok[:, 0]),  # repro: check-ok(lint.host-sync)
            self.state,
            self.pos.copy(), live.copy())
        return logits

    def _reset_slot(self, i: int):
        """Fresh cache + position for a re-used slot (no stale KV).  One
        jitted executable (slot index traced, so every slot shares it)
        instead of 2x-layers eager ``.at[].set`` dispatches — admission
        runs before any span opens, so its cost must stay in the noise."""
        if self._reset_fn is None:
            self._reset_fn = jax.jit(
                lambda state, j: jax.tree.map(
                    lambda v, ax: v.at[(slice(None),) * ax + (j,)].set(0),
                    state, self._axes))
        self.state = self._reset_fn(self.state, jnp.int32(i))
        self.pos[i] = 0

    @property
    def n_active(self) -> int:
        """Occupied slots (the router's occupancy numerator)."""
        return sum(1 for r in self.active if r is not None)

    def _max_new(self, req: Request) -> int:
        """Eviction policy: the plan's cap bounds every request's budget."""
        cap = self.policy.max_new_cap
        return req.max_new if cap is None else min(req.max_new, cap)

    def _prefill_tick(self, i: int, req: Request):
        """Advance slot ``i``'s prefill by at most ``prefill_chunk`` tokens
        (the whole prompt when the policy sets no chunk).  Emits the first
        generated token once the prompt is fully consumed."""
        chunk = self.policy.prefill_chunk
        limit = (len(req.prompt) if chunk is None
                 else min(len(req.prompt), req.filled + chunk))
        if req.filled >= limit:
            return
        t0 = time.perf_counter()
        first = req.filled
        tok = np.zeros((self.slots, 1), np.int32)
        live = np.zeros((self.slots,), bool)
        live[i] = True
        logits = None
        for t in req.prompt[req.filled:limit]:
            tok[i, 0] = t
            logits = self._decode_masked(tok, live)
            self.pos[i] += 1
        req.filled = limit
        if req.filled == len(req.prompt):
            # Deliberate sync: the finiteness guard reads one logits row.
            row = np.asarray(logits[i, -1])  # repro: check-ok(lint.host-sync)
            if not np.isfinite(row).all():
                self._fail_request(i, req, "non_finite_output")
            else:
                req.out.append(int(row.argmax()))
        self._record("prefill_chunk", t0, time.perf_counter(), trace=req.rid,
                     tokens=limit - first, slot=i)

    def _admit(self, wait_s: float = 0.0, admit_cap: int | None = None) -> int:
        """Fill free slots from the queue.  ``wait_s > 0`` blocks on the
        FIRST pop (``queue.get(timeout=...)``) so an idle serving loop parks
        in the kernel instead of spinning on ``queue.empty()``.

        ``admit_cap`` tightens the policy's per-tick admission bound for
        THIS tick only (the router's SLO-aware deferral passes 0 to hold a
        lower-priority tenant's queue while a higher-priority tenant burns
        its budget — live slots keep decoding either way)."""
        caps = [c for c in (self.policy.admit_per_tick, admit_cap)
                if c is not None]
        cap = min(caps) if caps else None
        if cap is not None and cap <= 0:
            return 0
        admitted = 0
        for i in range(self.slots):
            if self.active[i] is not None:
                continue
            if cap is not None and admitted >= cap:
                break
            try:
                req = (self.queue.get(timeout=wait_s) if wait_s > 0
                       else self.queue.get_nowait())
            except queue.Empty:
                break
            wait_s = 0.0                 # block at most once per tick
            now = time.perf_counter()
            req.t_admit = now
            if req.t_submit is not None:
                self._record("queue", req.t_submit, now, trace=req.rid)
            if len(req.prompt) == 0:     # nothing to prefill or decode
                req.done = True
                req.t_done = now
                self._finish(req)
                continue
            self._reset_slot(i)
            req.filled = 0
            self.active[i] = req
            admitted += 1
        return admitted

    def _finish(self, req: Request):
        """Close out a completed (or evicted) request's trace: the request
        span covers submit -> done, whatever path ended it."""
        if self.tracer.enabled and req.t_submit is not None:
            extra = {"error": req.error} if req.error else {}
            self.tracer.add("request", req.t_submit, req.t_done,
                            trace=req.rid, tenant=self.trace_label,
                            tokens_out=len(req.out), **extra)

    def _fail_request(self, i: int, req: Request, kind: str):
        """A poisoned output FAILS the request instead of emitting garbage:
        the slot is freed, the fault counted (``fault/non_finite`` span),
        and the request span still closes so traces reconcile.  The router
        reads ``req.error`` and books a per-tenant failure."""
        now = time.perf_counter()
        self.faults += 1
        req.error = kind
        req.done = True
        req.t_done = now
        self.active[i] = None
        if self.tracer.enabled:
            self.tracer.add("fault/non_finite", now, now, trace=req.rid,
                            tenant=self.trace_label, slot=i)
        self._finish(req)

    def step(self, wait_s: float = 0.0, *,
             admit_cap: int | None = None) -> int:
        """One tick: admit, advance chunked prefills, decode live slots.
        Returns #active.  ``wait_s`` bounds the blocking idle wait — applied
        only when EVERY slot is empty, so a busy batcher never stalls its
        live decodes waiting for new arrivals.  ``admit_cap`` tightens this
        tick's admissions (0 = defer the queue, keep decoding)."""
        if self.injector is not None:
            spec = self.injector.fire("batcher.tick", tenant=self.trace_label)
            if spec is not None:
                if spec.kind == "batcher_stall":
                    if spec.magnitude_s > 0:
                        time.sleep(spec.magnitude_s)
                    return self.n_active   # tick skipped: no admit, no decode
                if spec.kind == "engine_exception":
                    self.faults += 1
                    raise InjectedFault(
                        f"injected batcher fault on {self.trace_label}")
                if spec.kind == "latency_spike" and spec.magnitude_s > 0:
                    time.sleep(spec.magnitude_s)
        self._admit(wait_s=wait_s if not any(self.active) else 0.0,
                    admit_cap=admit_cap)
        # Slots mid-prefill (including just-admitted ones) advance by one
        # chunk instead of decoding; with no chunk configured the whole
        # prompt lands in this tick, which is the pre-policy behavior.
        for i, req in enumerate(self.active):
            if req is not None and req.filled < len(req.prompt):
                self._prefill_tick(i, req)
        if not any(self.active):
            return 0
        tok = np.zeros((self.slots, 1), np.int32)
        live = np.zeros((self.slots,), bool)
        for i, req in enumerate(self.active):
            if req is not None and req.out and req.filled >= len(req.prompt):
                tok[i, 0] = req.out[-1]
                live[i] = True
        if live.any():
            t0 = time.perf_counter()
            logits = self._decode_masked(tok, live)
            if self.injector is not None:
                spec = self.injector.fire("batcher.decode",
                                          tenant=self.trace_label)
                if spec is not None and spec.kind == "non_finite_output":
                    logits = jnp.full_like(logits, jnp.nan)
            self._steps += 1
            stepped = []                 # (slot, request) pairs that decoded
            done_reqs = []
            for i, req in enumerate(self.active):
                if req is None or not live[i]:
                    continue
                self.pos[i] += 1
                # Deliberate sync: per-slot finiteness guard (see above).
                row = np.asarray(logits[i, -1])  # repro: check-ok(lint.host-sync)
                if not np.isfinite(row).all():
                    self._fail_request(i, req, "non_finite_output")
                    continue
                stepped.append((i, req))
                req.out.append(int(row.argmax()))
                if len(req.out) >= self._max_new(req):
                    req.done = True      # completion OR max_new_cap eviction
                    done_reqs.append(req)
                    self.active[i] = None
            # The int(argmax) consumption above synchronized the async
            # dispatch, so [t0, t1] is the honest batched service interval.
            t1 = time.perf_counter()
            self._record("decode_step", t0, t1, batch=len(stepped),
                         emit=False)
            if self.tracer.enabled:
                for i, req in stepped:   # per-request view of the shared step
                    self.tracer.add("decode_step", t0, t1, trace=req.rid,
                                    tenant=self.trace_label, slot=i)
            for req in done_reqs:
                req.t_done = t1
                self._finish(req)
        return self.n_active

    def run_until_drained(self, max_ticks: int = 10_000):
        while (not self.queue.empty() or any(self.active)) \
                and self._steps < max_ticks:
            self.step()


# ---------------------------------------------------------------------------
# Edge plan executor (the paper's serving regime)
# ---------------------------------------------------------------------------

class EdgeEngine:
    """Executes a :class:`DeploymentPlan` for an extreme-edge net.

    The engine owns the quantized weights and the jitted planned forward —
    one Pallas launch per DR7' fusion group, per-layer Pallas block shapes
    for singleton groups, nothing here hard-codes a tile or a group — and
    tracks measured wall time against the plan's estimate so deployments can
    report planned-vs-measured drift.  The forward (groups, tiles, scales
    included) is baked into ONE cached jit at construction: the hot path
    never touches the plan.

    Activation scales are calibrated at construction by running the float
    reference on a representative batch (``calibrate=False`` restores the
    legacy fixed ``x_scale``).
    """

    def __init__(self, cfg, params=None, *, plan=None, x_scale: float = 0.05,
                 seed: int = 0, calibrate: bool = True, qparams=None,
                 calib_x=None, tracer=None):
        from repro.models import edge as edge_lib
        self.cfg = cfg
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.trace_label = cfg.name
        self.plan = plan if plan is not None else edge_lib.deployment_plan(cfg)
        if qparams is None:
            if params is None:
                params = edge_lib.init_edge(jax.random.PRNGKey(seed), cfg)
            if calibrate and calib_x is None:
                calib_x = jax.random.normal(
                    jax.random.fold_in(jax.random.PRNGKey(seed), 7),
                    (cfg.batch, cfg.dims[0]), F32)
            qparams = edge_lib.quantize_edge(
                params, calib_x=calib_x if calibrate else None, act=cfg.act)
        self.qparams = qparams
        self.x_scale = x_scale

        # Named, so the profiler's host ``PjitFunction(edge_forward)``
        # events and the device's ``jit_edge_forward`` module say which
        # program ran.
        def edge_forward(x):
            return edge_lib.edge_forward_q8(self.qparams, cfg, x,
                                            x_scale=x_scale, plan=self.plan)
        self._fwd = jax.jit(edge_forward)
        self._hlo_text: str | None = None
        # Degradation ladder state (repro.serve.resilience): level 0 runs
        # the planned fused megakernel; level 1 the per-layer gemm_int8
        # path (``fused=False`` — bit-exact vs fused, so degrading never
        # changes answers).  The fallback jit is built lazily on first
        # demotion; ``injector``/``faults`` mirror the batcher's hooks.
        self.degrade_level = 0
        self._fwd_fallback = None
        self.injector = None
        self.faults = 0
        self.reset_measurements()

    def _fallback(self):
        """The per-layer (``fused=False``) jit, compiled on first use."""
        if self._fwd_fallback is None:
            from repro.models import edge as edge_lib

            def edge_forward_per_layer(x):
                return edge_lib.edge_forward_q8(
                    self.qparams, self.cfg, x, x_scale=self.x_scale,
                    plan=self.plan, fused=False)
            self._fwd_fallback = jax.jit(edge_forward_per_layer)
        return self._fwd_fallback

    def degrade(self) -> bool:
        """Step down the ladder (fused -> per-layer).  Returns True if a
        demotion happened; False when already at the bottom rung this
        engine owns (the breaker's open state IS the shed rung)."""
        if self.degrade_level == 0:
            self.degrade_level = 1
            return True
        return False

    def restore(self) -> bool:
        """Re-promote to the fused fast path.  Returns True on change."""
        if self.degrade_level > 0:
            self.degrade_level = 0
            return True
        return False

    def hlo_text(self) -> str:
        """Post-optimization HLO of the jitted planned forward — the one
        executable :meth:`infer` runs.  Cached after the first compile; the
        profiler's HLO-overhead report analyzes this text."""
        if self._hlo_text is None:
            x = jnp.zeros((self.cfg.batch, self.cfg.dims[0]), F32)
            self._hlo_text = self._fwd.lower(x).compile().as_text()
        return self._hlo_text

    def infer(self, x, trace=None) -> jax.Array:
        """One forward, returned ready and checked finite on the host.

        The copy of the output to the host is requested as soon as the
        forward is enqueued, so the runtime runs it right behind the
        forward and the call blocks once, until the output is on the host.

        With tracing on, the call is an ``infer`` span holding
        ``engine.dispatch`` (argument handling, the copy of a host input
        to the device, the enqueue; ``h2d_bytes``, and ``d2h_early_bytes``,
        the output bytes whose copy is asked for with no wait for the
        forward before it), ``engine.wait`` (the request for that copy,
        launch, forward and copy, until the output is on the host) and
        ``engine.readback`` (the finiteness check on the host copy;
        ``d2h_bytes``).  They carry ``trace``, the request id, drawn from
        the tracer when the caller has none."""
        tracer = self.tracer
        if not tracer.enabled:
            return self._infer(x, None, None)
        if trace is None:
            trace = tracer.next_trace_id()
        with tracer.span("infer", trace=trace, tenant=self.trace_label):
            return self._infer(x, tracer, trace)

    def _infer(self, x, tracer, trace) -> jax.Array:
        t0 = time.perf_counter()
        spec = None
        if self.injector is not None:
            spec = self.injector.fire("engine.infer", tenant=self.trace_label)
        if spec is not None:
            if spec.kind == "engine_exception":
                self.faults += 1
                raise InjectedFault(
                    f"injected engine fault on {self.trace_label}")
            if spec.kind == "latency_spike" and spec.magnitude_s > 0:
                time.sleep(spec.magnitude_s)   # inside [t0, t1]: visible
        fwd = self._fwd if self.degrade_level == 0 else self._fallback()
        # Deliberate sync, the only one (infer() returns a ready result by
        # contract): np.asarray on the output not yet ready asks for its
        # copy to the host, which the runtime chains behind the forward.
        # Waiting for the forward first would add a second round trip
        # before the copy could start.
        if tracer is None:
            y = fwd(x)
            host = np.asarray(y)  # repro: check-ok(lint.host-sync)
        else:
            label = self.trace_label
            h2d = 0 if isinstance(x, jax.Array) else x.nbytes
            with tracer.span("engine.dispatch", trace=trace, tenant=label,
                             h2d_bytes=h2d) as span:
                y = fwd(x)
                span.set(d2h_early_bytes=y.nbytes)
            with tracer.span("engine.wait", trace=trace, tenant=label):
                host = np.asarray(y)  # repro: check-ok(lint.host-sync)
        if spec is not None and spec.kind == "non_finite_output":
            host = np.full_like(host, np.nan)  # poison; caught just below
        # A poisoned output FAILS the call rather than returning garbage;
        # the reduction is microseconds next to the forward.
        if tracer is None:
            finite = bool(np.isfinite(host).all())
        else:
            with tracer.span("engine.readback", trace=trace, tenant=label,
                             d2h_bytes=y.nbytes):
                finite = bool(np.isfinite(host).all())
        if not finite:
            t1 = time.perf_counter()
            self.faults += 1
            if tracer is not None:
                tracer.add("fault/non_finite", t0, t1, trace=trace,
                           tenant=self.trace_label)
            raise NonFiniteOutput(
                f"{self.trace_label}: non-finite model output")
        dt = time.perf_counter() - t0
        self.total_s += dt
        self.calls += 1
        self._latencies.append(dt)
        return y

    def span_stats(self) -> dict:
        """The edge path is synchronous — one span kind, ``infer``, whose
        service time IS the request latency (no queue decomposition)."""
        if not self._latencies:
            return {}
        agg = summarize(self._latencies)
        agg["total_count"] = self.calls
        return {"infer": agg}

    @property
    def planned_latency_s(self) -> float:
        return self.plan.est_latency_s

    @property
    def measured_mean_s(self) -> float:
        return self.total_s / self.calls if self.calls else 0.0

    @property
    def measured_p50_s(self) -> float:
        """Median over the recent-call window — the robust statistic the
        planned-vs-measured comparisons and the recalibration loop use (one
        scheduler spike must not swing a calibration)."""
        if not self._latencies:
            return 0.0
        xs = sorted(self._latencies)
        return xs[len(xs) // 2]

    def reset_measurements(self):
        """Drop accumulated timings (e.g. after jit warmup)."""
        self.calls, self.total_s = 0, 0.0
        self._latencies = collections.deque(maxlen=256)

    def record_calibration(self, cache=None):
        """Autotune hook: write the measured mean latency back into the plan
        cache (:func:`repro.plan.calibrate.feedback`), so a re-plan with the
        same key returns calibrated costs.  Returns the calibrated plan and
        adopts it as this engine's plan (tiles are unchanged — only cost
        annotations move)."""
        from repro.plan import calibrate
        if not self.calls:
            raise RuntimeError("no measurements recorded yet")
        self.plan = calibrate.feedback(self.plan, self.measured_mean_s,
                                       cache=cache)
        return self.plan
