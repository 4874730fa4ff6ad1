"""Plain float32 reference of a served edge net, and its int4 control.

An edge net is a dense chain: ``h = act(h @ W_i + b_i)`` for every layer but
the last, which is linear.  The weights are regenerated here from the run's
seed by this file's own copy of the published init rule (layer ``i`` draws
``N(0, 1) / sqrt(n_in)`` from ``split(fold_in(PRNGKey(seed), i))[0]``, zero
bias), so the comparison takes nothing the program made: no weights, no
scales, no tables.

``forward`` is the reference: float32 at ``highest`` matmul precision (a
TPU would otherwise run float32 matmuls as bfloat16 passes).  ``forward_int4``
is the control: the same net computed one precision step below the int8 the
configuration serves, with per-output-channel symmetric int4 weights and a
per-tensor symmetric int4 activation scale taken from each batch's own
maximum.  The comparison has to judge the control not correct.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def init_weights(seed: int, dims) -> list[tuple[jax.Array, jax.Array]]:
    """``[(W_i, b_i)]`` of the dense chain ``dims`` for ``seed``."""
    key = jax.random.PRNGKey(seed)
    out = []
    for i, (n_in, n_out) in enumerate(zip(dims[:-1], dims[1:])):
        k, _ = jax.random.split(jax.random.fold_in(key, i))
        w = jax.random.normal(k, (n_in, n_out), F32) / jnp.sqrt(float(n_in))
        out.append((w, jnp.zeros((n_out,), F32)))
    return out


def _chain(weights, x, act: str, matmul):
    h = x.astype(F32)
    last = len(weights) - 1
    for i, (w, b) in enumerate(weights):
        h = matmul(h, w) + b
        if i != last and act == "relu":
            h = jnp.maximum(h, 0.0)
    return h


@jax.jit
def _forward(weights, x):
    with jax.default_matmul_precision("highest"):
        return _chain(weights, x, "relu", jnp.matmul)


def _q4(v, axis=None):
    """Symmetric int4 quantization: values in [-7, 7] and their scale."""
    scale = jnp.max(jnp.abs(v), axis=axis, keepdims=axis is not None) / 7.0
    scale = jnp.maximum(scale, 1e-12)
    return jnp.clip(jnp.round(v / scale), -7, 7), scale


def _matmul_int4(h, w):
    hq, hs = _q4(h)
    wq, ws = _q4(w, axis=0)
    with jax.default_matmul_precision("highest"):
        return (hq @ wq) * hs * ws


@jax.jit
def _forward_int4(weights, x):
    return _chain(weights, x, "relu", _matmul_int4)


def _run(fn, weights, xs: np.ndarray, block: int) -> np.ndarray:
    """``fn`` over ``xs`` (requests, batch, width), ``block`` requests at a
    time, each request quantized and computed on its own rows."""
    outs = []
    for i in range(0, len(xs), block):
        chunk = jnp.asarray(xs[i:i + block])
        outs.append(np.asarray(jax.vmap(lambda x: fn(weights, x))(chunk)))
    return np.concatenate(outs) if outs else np.zeros((0,), np.float32)


def forward(weights, xs: np.ndarray, act: str = "relu",
            block: int = 256) -> np.ndarray:
    """Reference outputs for request inputs ``xs`` (requests, batch, width)."""
    if act != "relu":
        raise ValueError(f"unsupported activation {act!r}")
    return _run(_forward, weights, xs, block)


def forward_int4(weights, xs: np.ndarray, act: str = "relu",
                 block: int = 256) -> np.ndarray:
    """The control: the reference computed in int4."""
    if act != "relu":
        raise ValueError(f"unsupported activation {act!r}")
    return _run(_forward_int4, weights, xs, block)

