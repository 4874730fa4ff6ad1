"""Two-level tiled GEMM — paper Algorithm 2 adapted to the TPU MXU/VMEM.

Mapping from the paper's AIE formulation (DESIGN.md §2):

* the *API-level* tile ``(S_M,S_K,S_N)`` becomes the Pallas ``BlockSpec``
  block shape ``(block_m, block_k, block_n)`` — legal when the last dim is a
  multiple of 128 lanes and the second-to-last a multiple of the dtype's
  sublane packing (8 for f32, 16 for bf16, 32 for int8);
* the ``(R_M,R_K,R_N)`` repeat loops become the Pallas grid — K innermost
  with ``arbitrary`` dimension semantics so the f32 VMEM scratch accumulator
  plays the role of the AIE cascade chain (partial sums stay on-chip);
* "weights stationary" holds per output block: the B block is re-fetched
  across the K grid but never leaves VMEM within a (m, n) program family.

The spatial level (``P_K x P_N`` across compute tiles) is NOT in this file:
it is a mesh sharding decided by ``core.tiling.plan_spatial`` and applied by
``shard_map`` in the distribution layer, with ``psum_scatter`` standing in
for the cascade bus across chips.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.tiling import ApiPlan, plan_api


def _acc_dtype(dtype: jnp.dtype) -> jnp.dtype:
    return jnp.int32 if jnp.issubdtype(dtype, jnp.integer) else jnp.float32


def _gemm_kernel(x_ref, w_ref, o_ref, acc_ref, *, n_k: int):
    """One (m, n) output block; K iterates innermost (grid dim 2)."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...], w_ref[...],
        preferred_element_type=acc_ref.dtype,
    )

    @pl.when(k == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_k", "block_n", "out_dtype", "interpret"),
)
def tiled_gemm(
    x: jax.Array,                 # (M, K)
    w: jax.Array,                 # (K, N)
    *,
    block_m: int | None = None,
    block_k: int | None = None,
    block_n: int | None = None,
    out_dtype: jnp.dtype | None = None,
    interpret: bool = False,
) -> jax.Array:
    """``x @ w`` with explicit two-level tiling (API level of Alg. 2).

    Block shapes default to the planner's DR1' choice for the shape/dtype.
    Inputs whose dims are not multiples of the block are zero-padded (the
    TPU analogue of the paper's "legal shape" restriction).
    """
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    if block_m is None or block_k is None or block_n is None:
        plan = plan_api(m, k, n, itemsize=x.dtype.itemsize)
        block_m = block_m or plan.block_m
        block_k = block_k or plan.block_k
        block_n = block_n or plan.block_n
    out_dtype = out_dtype or (
        jnp.int32 if jnp.issubdtype(x.dtype, jnp.integer) else x.dtype)

    pad_m = (-m) % block_m
    pad_k = (-k) % block_k
    pad_n = (-n) % block_n
    if pad_m or pad_k:
        x = jnp.pad(x, ((0, pad_m), (0, pad_k)))
    if pad_k or pad_n:
        w = jnp.pad(w, ((0, pad_k), (0, pad_n)))
    mp, kp = x.shape
    _, np_ = w.shape
    grid = (mp // block_m, np_ // block_n, kp // block_k)

    acc = _acc_dtype(x.dtype)
    out = pl.pallas_call(
        functools.partial(_gemm_kernel, n_k=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), acc)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="repro_tiled_gemm",
    )(x, w)
    if pad_m or pad_n:
        out = out[:m, :n]
    return out
