"""RWKV-6 ("Finch") recurrence kernel with data-dependent decay.

Per head of size D the recurrence over time is

    S_t = diag(w_t) . S_{t-1} + k_t v_t^T          (state S: D x D, f32)
    o_t = r_t . (S_{t-1} + diag(u) . k_t v_t^T)

with w_t the data-dependent per-channel decay and u the learned "bonus" for
the current token.  TPU adaptation mirrors :mod:`repro.kernels.rglru`: time is
blocked into VMEM chunks (grid: batch*heads x time-blocks, time innermost) and
the D x D state matrix lives in VMEM scratch across grid steps.  The per-step
outer product / matvec are (D, D) VPU/MXU ops with D = head_dim (64 for
rwkv6-7b), so the working set is tiny and stays on-chip — weights-stationary
in exactly the paper's sense.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rwkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, s_ref, *,
                  block_t: int):
    ti = pl.program_id(1)

    @pl.when(ti == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    r = r_ref[0].astype(jnp.float32)          # (bt, d)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    w = w_ref[0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)          # (d,)

    def step(t, s):
        kv = k[t][:, None] * v[t][None, :]                  # (d, d)
        out = (r[t][None, :] @ (s + u[:, None] * kv))[0]     # (d,)
        o_ref[0, t, :] = out.astype(o_ref.dtype)
        return w[t][:, None] * s + kv

    s_ref[...] = jax.lax.fori_loop(0, block_t, step, s_ref[...])


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def rwkv6_scan(
    r: jax.Array,           # (B*H, T, D) receptance
    k: jax.Array,           # (B*H, T, D) key
    v: jax.Array,           # (B*H, T, D) value
    w: jax.Array,           # (B*H, T, D) data-dependent decay in (0,1)
    u: jax.Array,           # (D,) bonus
    *,
    block_t: int = 128,
    interpret: bool = False,
) -> jax.Array:
    bh, t, d = r.shape
    block_t = min(block_t, t)
    pad_t = (-t) % block_t
    if pad_t:
        # w=1, k=0 padding leaves the state untouched.
        r = jnp.pad(r, ((0, 0), (0, pad_t), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad_t), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_t), (0, 0)))
        w = jnp.pad(w, ((0, 0), (0, pad_t), (0, 0)), constant_values=1.0)
    tp = r.shape[1]
    grid = (bh, tp // block_t)
    u2 = u.reshape(1, d)

    out = pl.pallas_call(
        functools.partial(_rwkv6_kernel, block_t=block_t),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_t, d), lambda bi, ti: (bi, ti, 0)),
            pl.BlockSpec((1, block_t, d), lambda bi, ti: (bi, ti, 0)),
            pl.BlockSpec((1, block_t, d), lambda bi, ti: (bi, ti, 0)),
            pl.BlockSpec((1, block_t, d), lambda bi, ti: (bi, ti, 0)),
            pl.BlockSpec((1, d), lambda bi, ti: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_t, d), lambda bi, ti: (bi, ti, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, tp, d), r.dtype),
        scratch_shapes=[pltpu.VMEM((d, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="repro_rwkv6_scan",
    )(r, k, v, w, u2)
    return out[:, :t, :]
