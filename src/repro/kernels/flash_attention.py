"""Blocked online-softmax (flash) attention for TPU.

Supports the features the assigned LM architectures need:

* causal masking,
* sliding-window (local) attention — gemma2 / recurrentgemma local layers,
* logit soft-capping  ``cap * tanh(logits / cap)`` — gemma2,
* GQA: ``n_q_heads`` a multiple of ``n_kv_heads`` (KV blocks indexed by
  ``head // group`` in the BlockSpec index maps, so KV is fetched once per
  group, not per query head).

Tiling follows the paper's two-level discipline: the (block_q, block_kv)
choice is the API-level tile (VMEM-bounded, lane-aligned); the KV grid
dimension is innermost/sequential and the running (m, l, acc) statistics in
VMEM scratch play the role of the cascade accumulator.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -0.7 * float(jnp.finfo(jnp.float32).max)
_LANE = 128


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  n_kv: int, block_q: int, block_kv: int, scale: float,
                  causal: bool, window: int | None, softcap: float | None):
    ki = pl.program_id(2)
    qi = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Data-independent block-level skip (causal/window out-of-range blocks).
    q_lo = qi * block_q
    q_hi = q_lo + block_q - 1
    k_lo = ki * block_kv
    k_hi = k_lo + block_kv - 1
    in_range = True
    if causal:
        in_range = jnp.logical_and(in_range, k_lo <= q_hi)
    if window is not None:
        in_range = jnp.logical_and(in_range, k_hi >= q_lo - window + 1)

    @pl.when(in_range)
    def _body():
        q = q_ref[0].astype(jnp.float32)                  # (bq, d)
        k = k_ref[0].astype(jnp.float32)                  # (bkv, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            mask = jnp.logical_and(mask, k_pos <= q_pos)
        if window is not None:
            mask = jnp.logical_and(mask, k_pos > q_pos - window)
        s = jnp.where(mask, s, _NEG)
        m_prev = m_ref[...][:, :1]                        # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)                       # kill masked mass
        alpha = jnp.exp(m_prev - m_new)                   # (bq, 1)
        l_new = l_ref[...][:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v_ref[0].astype(jnp.float32),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == n_kv - 1)
    def _flush():
        l = l_ref[...][:, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "scale", "block_q",
                     "block_kv", "interpret"),
)
def flash_attention(
    q: jax.Array,            # (B, Hq, S, D)
    k: jax.Array,            # (B, Hkv, S, D)
    v: jax.Array,            # (B, Hkv, S, D)
    *,
    causal: bool = True,
    window: int | None = None,
    softcap: float | None = None,
    scale: float | None = None,
    block_q: int = 512,
    block_kv: int = 512,
    interpret: bool = False,
) -> jax.Array:
    b, hq, s, d = q.shape
    _, hkv, sk, _ = k.shape
    assert hq % hkv == 0, (hq, hkv)
    group = hq // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    block_q = min(block_q, s)
    block_kv = min(block_kv, sk)

    pad_q = (-s) % block_q
    pad_kv = (-sk) % block_kv
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_kv:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_kv), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_kv), (0, 0)))
    sp, skp = q.shape[2], k.shape[2]
    qf = q.reshape(b * hq, sp, d)
    kf = k.reshape(b * hkv, skp, d)
    vf = v.reshape(b * hkv, skp, d)
    grid = (b * hq, sp // block_q, skp // block_kv)

    def kv_index(bh, qi, ki):
        # map query head -> kv head:  bh = batch*Hq + h ;  group = Hq//Hkv
        bb = bh // hq
        h = bh % hq
        return (bb * hkv + h // group, ki, 0)

    out = pl.pallas_call(
        functools.partial(
            _flash_kernel, n_kv=grid[2], block_q=block_q, block_kv=block_kv,
            scale=scale, causal=causal, window=window, softcap=softcap),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_kv, d), kv_index),
            pl.BlockSpec((1, block_kv, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, sp, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANE), jnp.float32),   # running max
            pltpu.VMEM((block_q, _LANE), jnp.float32),   # running denom
            pltpu.VMEM((block_q, d), jnp.float32),       # running numerator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="repro_flash_attention",
    )(qf, kf, vf)
    out = out.reshape(b, hq, sp, d)
    if pad_q:
        out = out[:, :, :s, :]
    return out
