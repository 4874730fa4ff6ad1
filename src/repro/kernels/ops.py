"""Public jit'd entry points for the Pallas kernels.

On the CPU every kernel executes in Pallas ``interpret=True`` mode, which
runs the kernel body for correctness only; on a TPU the same call sites
compile to Mosaic.  ``use_interpret()`` picks from the backend and refuses
any other platform; tests force it explicitly so intent is visible.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import fused_dense as _fd
from repro.kernels import fused_mlp as _fm
from repro.kernels import gemm_int8 as _g8
from repro.kernels import rglru as _rg
from repro.kernels import rwkv6 as _rw
from repro.kernels import tiled_gemm as _tg


def use_interpret() -> bool:
    """True on the CPU (the interpreter the tests run), False on a TPU.
    Any other backend raises: these kernels are written for Mosaic, and
    neither compiling them for another target nor interpreting them there
    would say anything about the chip."""
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(
            f"Pallas kernels run compiled on 'tpu' or interpreted on 'cpu'; "
            f"backend {backend!r} is neither")
    return backend == "cpu"


def tiled_gemm(x, w, **kw):
    kw.setdefault("interpret", use_interpret())
    return _tg.tiled_gemm(x, w, **kw)


def fused_dense(x, w, b, residual=None, **kw):
    kw.setdefault("interpret", use_interpret())
    return _fd.fused_dense(x, w, b, residual, **kw)


def gemm_int8(x, w, w_scale, x_scale=1.0, **kw):
    kw.setdefault("interpret", use_interpret())
    return _g8.gemm_int8(x, w, w_scale, x_scale, **kw)


def fused_mlp_q8(x, weights, w_scales, biases, x_scales, **kw):
    """A whole DR7' fusion group (N int8 dense layers) in one launch."""
    kw.setdefault("interpret", use_interpret())
    return _fm.fused_mlp_q8(x, tuple(weights), tuple(w_scales),
                            tuple(biases), x_scales, **kw)


def flash_attention(q, k, v, **kw):
    kw.setdefault("interpret", use_interpret())
    return _fa.flash_attention(q, k, v, **kw)


def linear_scan(a, b, **kw):
    kw.setdefault("interpret", use_interpret())
    return _rg.linear_scan(a, b, **kw)


def rglru(x, gate_a, gate_x, log_lambda, *, c: float = 8.0, **kw):
    """Full RG-LRU layer: gates + the Pallas linear scan.

    a_t = exp(-c * softplus(log_lambda) * sigmoid(gate_a))
    h_t = a_t h_{t-1} + sqrt(1 - a_t^2) * (sigmoid(gate_x) * x_t)
    """
    log_a = -c * jax.nn.softplus(log_lambda)[None, None, :] * jax.nn.sigmoid(
        gate_a.astype(jnp.float32))
    a = jnp.exp(log_a)
    gated = jax.nn.sigmoid(gate_x.astype(jnp.float32)) * x.astype(jnp.float32)
    b = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12)) * gated
    return linear_scan(a.astype(jnp.float32), b.astype(jnp.float32),
                       **kw).astype(x.dtype)


def rwkv6_scan(r, k, v, w, u, **kw):
    kw.setdefault("interpret", use_interpret())
    return _rw.rwkv6_scan(r, k, v, w, u, **kw)
