"""int8 x int8 -> int32 GEMM with fused dequantization epilogue.

The paper's extreme-edge convention is 8-bit quantization end-to-end (all
Table-I models, the `aie::mmul` i8 datatype, batch 8).  On TPU the analogue is
the int8 MXU path (2x the bf16 peak).  This kernel accumulates in int32 and
applies per-tensor activation scale x per-output-channel weight scale in the
flush step, emitting bf16/f32 — so quantized serving costs one launch, not
three (quant GEMM, dequant, bias would each be a DR7' boundary crossing).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.tiling import plan_api


def _int8_kernel(x_ref, w_ref, sw_ref, sx_ref, o_ref, acc_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(k == n_k - 1)
    def _flush():
        scale = sx_ref[0] * sw_ref[...].astype(jnp.float32)     # (1, bn)
        o_ref[...] = (acc_ref[...].astype(jnp.float32) * scale).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_k", "block_n", "out_dtype", "interpret"),
)
def gemm_int8(
    x: jax.Array,            # (M, K) int8
    w: jax.Array,            # (K, N) int8
    w_scale: jax.Array,      # (N,) f32 per-output-channel
    x_scale: jax.Array | float = 1.0,   # scalar per-tensor
    *,
    block_m: int | None = None,
    block_k: int | None = None,
    block_n: int | None = None,
    out_dtype: jnp.dtype = jnp.bfloat16,
    interpret: bool = False,
) -> jax.Array:
    assert x.dtype == jnp.int8 and w.dtype == jnp.int8
    m, k = x.shape
    _, n = w.shape
    if block_m is None or block_k is None or block_n is None:
        plan = plan_api(m, k, n, itemsize=1)
        block_m = block_m or plan.block_m
        block_k = block_k or plan.block_k
        block_n = block_n or plan.block_n

    pad_m, pad_k, pad_n = (-m) % block_m, (-k) % block_k, (-n) % block_n
    if pad_m or pad_k:
        x = jnp.pad(x, ((0, pad_m), (0, pad_k)))
    if pad_k or pad_n:
        w = jnp.pad(w, ((0, pad_k), (0, pad_n)))
    sw = jnp.pad(jnp.asarray(w_scale, jnp.float32), (0, pad_n)).reshape(1, -1)
    sx = jnp.asarray(x_scale, jnp.float32).reshape(1)
    mp, kp = x.shape
    np_ = w.shape[1]
    grid = (mp // block_m, np_ // block_n, kp // block_k)

    out = pl.pallas_call(
        functools.partial(_int8_kernel, n_k=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, block_n), lambda i, j, kk: (0, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="repro_gemm_int8",
    )(x, w, sw, sx)
    if pad_m or pad_n:
        out = out[:m, :n]
    return out
