"""Fused dense layer: GEMM + bias + activation (+ residual) in ONE kernel.

This is the DR7' "boundary-crossing eliminator" (DESIGN.md §2): on the AIE the
paper prices each PL<->AIE hand-off at ~3.9% latency; on TPU the analogous
boundary is an un-fused XLA op boundary, which forces the activation tensor
through HBM and pays a kernel dispatch.  Fusing the epilogue into the GEMM's
flush step removes both — `core.boundary.plan_fusion` decides when this is
worthwhile; this kernel is the mechanism.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.tiling import plan_api

_ACTS = {
    "none": lambda x: x,
    "relu": lambda x: jnp.maximum(x, 0.0),
    "gelu": jax.nn.gelu,
    "silu": jax.nn.silu,
    "tanh": jnp.tanh,
    "sigmoid": jax.nn.sigmoid,
}


def _fused_kernel(x_ref, w_ref, b_ref, r_ref, o_ref, acc_ref, *,
                  n_k: int, act: str, residual: bool):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _flush():
        y = acc_ref[...] + b_ref[...].astype(jnp.float32)
        y = _ACTS[act](y)
        if residual:
            y = y + r_ref[...].astype(jnp.float32)
        o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("act", "block_m", "block_k", "block_n", "out_dtype",
                     "interpret"),
)
def fused_dense(
    x: jax.Array,                   # (M, K)
    w: jax.Array,                   # (K, N)
    b: jax.Array,                   # (N,)
    residual: jax.Array | None = None,   # (M, N) optional skip connection
    *,
    act: str = "relu",
    block_m: int | None = None,
    block_k: int | None = None,
    block_n: int | None = None,
    out_dtype: jnp.dtype | None = None,
    interpret: bool = False,
) -> jax.Array:
    """``act(x @ w + b) (+ residual)`` in a single Pallas launch."""
    m, k = x.shape
    _, n = w.shape
    assert b.shape == (n,), b.shape
    if block_m is None or block_k is None or block_n is None:
        plan = plan_api(m, k, n, itemsize=x.dtype.itemsize)
        block_m = block_m or plan.block_m
        block_k = block_k or plan.block_k
        block_n = block_n or plan.block_n
    out_dtype = out_dtype or x.dtype

    pad_m, pad_k, pad_n = (-m) % block_m, (-k) % block_k, (-n) % block_n
    if pad_m or pad_k:
        x = jnp.pad(x, ((0, pad_m), (0, pad_k)))
    if pad_k or pad_n:
        w = jnp.pad(w, ((0, pad_k), (0, pad_n)))
    b2 = jnp.pad(b, (0, pad_n)).reshape(1, -1)
    has_res = residual is not None
    if has_res:
        r2 = jnp.pad(residual, ((0, pad_m), (0, pad_n)))
    else:
        r2 = jnp.zeros((block_m, b2.shape[1]), x.dtype)  # dummy, never read
    mp, kp = x.shape
    np_ = w.shape[1]
    grid = (mp // block_m, np_ // block_n, kp // block_k)

    out = pl.pallas_call(
        functools.partial(_fused_kernel, n_k=grid[2], act=act,
                          residual=has_res),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, block_n), lambda i, j, kk: (0, j)),
            pl.BlockSpec((block_m, block_n),
                         (lambda i, j, kk: (i, j)) if has_res
                         else (lambda i, j, kk: (0, j))),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="repro_fused_dense",
    )(x, w, b2, r2)
    if pad_m or pad_n:
        out = out[:m, :n]
    return out
