"""RG-LRU linear-recurrence kernel (recurrentgemma / Griffin).

The recurrence is the first-order diagonal linear scan

    h_t = a_t * h_{t-1} + b_t,        a_t in (0, 1), elementwise over D,

with RG-LRU's gating folded into the inputs by the caller
(``a_t = exp(-c * softplus(L) * r_t)``, ``b_t = sqrt(1 - a_t^2) * i_t * x_t``).

TPU adaptation: the time dimension cannot ride the MXU, so the kernel blocks
time into VMEM-resident chunks (grid: batch x time-blocks, time innermost /
``arbitrary``) and carries the hidden state in a VMEM scratch across grid
steps — the same on-chip-accumulator discipline as the paper's cascade chain.
Within a block the scan runs as a ``fori_loop`` of VPU vector ops.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _scan_kernel(a_ref, b_ref, o_ref, h_ref, *, block_t: int):
    ti = pl.program_id(1)

    @pl.when(ti == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    a = a_ref[0].astype(jnp.float32)          # (bt, d)
    bb = b_ref[0].astype(jnp.float32)         # (bt, d)

    def step(t, h):
        h = a[t] * h + bb[t]
        o_ref[0, t, :] = h.astype(o_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, block_t, step, h_ref[0])
    h_ref[0] = h


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def linear_scan(
    a: jax.Array,           # (B, T, D) decay in (0,1)
    b: jax.Array,           # (B, T, D) input term
    *,
    block_t: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Computes h_t = a_t * h_{t-1} + b_t along T, h_0 = 0.  Returns h (B,T,D)."""
    bsz, t, d = a.shape
    block_t = min(block_t, t)
    pad_t = (-t) % block_t
    if pad_t:
        # Padding with a=1, b=0 leaves the carried state unchanged.
        a = jnp.pad(a, ((0, 0), (0, pad_t), (0, 0)), constant_values=1.0)
        b = jnp.pad(b, ((0, 0), (0, pad_t), (0, 0)))
    tp = a.shape[1]
    grid = (bsz, tp // block_t)

    out = pl.pallas_call(
        functools.partial(_scan_kernel, block_t=block_t),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_t, d), lambda bi, ti: (bi, ti, 0)),
            pl.BlockSpec((1, block_t, d), lambda bi, ti: (bi, ti, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_t, d), lambda bi, ti: (bi, ti, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, tp, d), a.dtype),
        scratch_shapes=[pltpu.VMEM((1, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="repro_rglru_scan",
    )(a, b)
    return out[:, :t, :]
