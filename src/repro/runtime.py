"""Runtime knobs: remat policy, quantized params, the compile cache.

``maybe_remat`` wraps scan bodies with ``jax.checkpoint`` according to the
active policy ("none" | "block" | "dots"); ``maybe_dequant`` transparently
expands int8-quantized weight leaves ({"q8", "scale"} marker dicts) inside the
per-layer scan body, so at-rest HBM holds int8 while only one layer's weights
ever exist in bf16 — the pjit-path analogue of the fused ``gemm_int8`` kernel.
``enable_compile_cache`` turns on JAX's persistent compilation cache for an
entry point's process.
"""

from __future__ import annotations

import contextlib
import contextvars
import os
import pathlib
from typing import Callable

import jax
import jax.numpy as jnp

_REMAT: contextvars.ContextVar[str] = contextvars.ContextVar(
    "repro_remat", default="none")


@contextlib.contextmanager
def remat_policy(policy: str):
    assert policy in ("none", "block", "dots")
    tok = _REMAT.set(policy)
    try:
        yield
    finally:
        _REMAT.reset(tok)


def maybe_remat(f: Callable) -> Callable:
    pol = _REMAT.get()
    if pol == "none":
        return f
    if pol == "block":
        return jax.checkpoint(f)
    return jax.checkpoint(
        f, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)


def is_q8(leaf) -> bool:
    return isinstance(leaf, dict) and set(leaf) == {"q8", "scale"}


def dequant(leaf, dtype=jnp.bfloat16):
    return (leaf["q8"].astype(jnp.float32)
            * leaf["scale"].astype(jnp.float32)).astype(dtype)


def maybe_dequant(tree, dtype=jnp.bfloat16):
    """Expand {"q8","scale"} marker dicts into dense weights (no-op otherwise)."""
    if not isinstance(tree, dict):
        return tree
    if is_q8(tree):
        return dequant(tree, dtype)
    return {k: maybe_dequant(v, dtype) if isinstance(v, dict) else v
            for k, v in tree.items()}


# Fixed, inside the checkout (src/repro/runtime.py -> <checkout>/.jax_cache):
# a cache is only found again at the same path, so it is never built from a
# temp name, a pid or the time.
COMPILE_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for this process and
    return its directory.  Entry points call it before their first compile;
    importing ``repro`` never does.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the directory: JAX reads it
    itself and no other is set here.  Otherwise the cache is
    :data:`COMPILE_CACHE_DIR`.  Every executable is kept, however fast it
    compiled: this program's kernels compile in about a second, which JAX's
    default one-second floor would mostly skip."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(COMPILE_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
