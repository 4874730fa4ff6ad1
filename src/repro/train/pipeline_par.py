"""GPipe-style pipeline parallelism over a mesh axis (shard_map + ppermute).

The paper's "PL spatial dataflow" regime — one layer group pinned to one
resource set, activations streaming stage-to-stage — is exactly pipeline
parallelism on TPU (DESIGN.md §2).  This module implements it for uniform
layer stacks: the stacked layer params (L, ...) are sharded over the stage
axis (L = n_stages * layers_per_stage); microbatches flow through stages with
``jax.lax.ppermute`` hand-offs; a rotating buffer keeps every stage busy
after the fill phase (the classic schedule: T = n_micro + n_stages - 1 ticks,
bubble fraction (S-1)/(M+S-1)).

This is also the execution model behind :func:`repro.core.lare.lare_tpu`'s
"pipelined-spatial" regime, so the LARE core-equivalence numbers and this
code describe the same machine.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(layer_fn: Callable, stacked_params, x, *, mesh: Mesh,
                   axis: str = "pod", microbatches: int | None = None):
    """Run ``x`` through L stacked layers pipelined over ``axis``.

    layer_fn(params_slice, x_micro) -> x_micro;
    stacked_params leaves: (L, ...) with L % n_stages == 0;
    x: (B, ...) with B % microbatches == 0.
    """
    n_stages = mesh.shape[axis]
    n_micro = microbatches or n_stages
    b = x.shape[0]
    assert b % n_micro == 0, (b, n_micro)

    def staged(params_local, x_all):
        # params_local: (L/n_stages, ...) this stage's layers
        # x_all: full batch (replicated over `axis`)
        stage = jax.lax.axis_index(axis)
        micro = x_all.reshape((n_micro, b // n_micro) + x_all.shape[1:])

        def run_stage(xm):
            def body(h, pl):
                return layer_fn(pl, h), None
            h, _ = jax.lax.scan(body, xm, params_local)
            return h

        ticks = n_micro + n_stages - 1
        buf = jnp.zeros_like(micro[0])
        outs = jnp.zeros_like(micro)

        def tick(carry, t):
            buf, outs = carry
            # Stage 0 ingests microbatch t (if any remain).
            idx = jnp.clip(t, 0, n_micro - 1)
            injected = jnp.where(
                jnp.logical_and(stage == 0, t < n_micro)[None],
                micro[idx].reshape(-1), buf.reshape(-1)).reshape(buf.shape)
            worked = run_stage(injected)
            # Hand off to the next stage (ring; last stage's output wraps
            # to stage 0 where it is captured into `outs`).
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            passed = jax.lax.ppermute(worked, axis, perm)
            # Stage 0 captures the microbatch that finished at tick t
            # (micro m finishes at tick m + n_stages - 1).
            m_done = t - (n_stages - 1)
            capture = jnp.logical_and(stage == 0, m_done >= 0)
            outs = jax.lax.cond(
                capture,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, passed, jnp.clip(m_done, 0, n_micro - 1), 0),
                lambda o: o, outs)
            return (passed, outs), None

        (buf, outs), _ = jax.lax.scan(tick, (buf, outs), jnp.arange(ticks))
        # Only stage 0's `outs` is meaningful; broadcast it.
        outs = jax.lax.psum(
            jnp.where((stage == 0), outs.reshape(-1),
                      jnp.zeros_like(outs).reshape(-1)).reshape(outs.shape),
            axis)
        return outs.reshape(x_all.shape)

    p_spec = jax.tree.map(lambda _: P(axis), stacked_params)
    return jax.shard_map(
        staged, mesh=mesh, in_specs=(p_spec, P()), out_specs=P(),
        check_vma=False,
    )(stacked_params, x)
