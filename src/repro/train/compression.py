"""Gradient compression for cross-node reduction (int8 + error feedback).

``compressed_psum(x, axis)`` quantizes to int8 with a per-tensor psum'd
absmax scale, all-reduces the int8 payload as int32 partial sums, and
dequantizes — an 4x wire-bytes reduction vs f32 (2x vs bf16) for the
gradient all-reduce, which is exactly the cross-pod (DCN) bottleneck at
multi-pod scale.  ``ErrorFeedback`` carries the quantization residual into
the next step (Seide et al.), which keeps SGD/Adam convergence intact.

These compose with the explicit shard_map data-parallel trainer
(:func:`build_manual_dp_step`): the pjit/GSPMD path keeps its implicit
reductions, while deployments that need compression (cross-pod DCN) switch
the DP reduction to this explicit path.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

F32 = jnp.float32


def compressed_psum(x: jax.Array, axis: str, *, bits: int = 8) -> jax.Array:
    """int8-quantized psum over a mesh axis (inside shard_map).

    The scale is the psum-max of per-shard absmax, so the int32 accumulation
    of n shards cannot overflow (n * 127 << 2^31)."""
    assert bits == 8, "int8 is the supported wire format"
    absmax = jnp.max(jnp.abs(x)).astype(F32)
    scale = jax.lax.pmax(absmax, axis) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(x.astype(F32) / scale), -127, 127).astype(jnp.int8)
    total = jax.lax.psum(q.astype(jnp.int32), axis)
    return total.astype(F32) * scale


def compress_tree_psum(tree: Any, axis: str) -> Any:
    return jax.tree.map(lambda l: compressed_psum(l, axis), tree)


class ErrorFeedback:
    """Residual carry for compressed reductions: g_hat = C(g + e); e += g - g_hat."""

    @staticmethod
    def init(grads_like: Any, *, world: int = 1) -> Any:
        """Residuals are per-DP-rank: leading `world` dim, sharded over dp."""
        return jax.tree.map(
            lambda g: jnp.zeros((world,) + tuple(g.shape), F32), grads_like)

    @staticmethod
    def apply(grads: Any, residual: Any, axis: str, *, world: int):
        def one(g, e):
            c = g.astype(F32) + e
            absmax = jnp.max(jnp.abs(c)).astype(F32)
            scale = jax.lax.pmax(absmax, axis) / 127.0 + 1e-12
            q = jnp.clip(jnp.round(c / scale), -127, 127).astype(jnp.int8)
            reduced = jax.lax.psum(q.astype(jnp.int32), axis).astype(F32) \
                * scale / world
            new_e = c - q.astype(F32) * scale   # local quantization error
            return reduced, new_e

        flat_g, tdef = jax.tree.flatten(grads)
        flat_e = tdef.flatten_up_to(residual)
        pairs = [one(g, e) for g, e in zip(flat_g, flat_e)]
        return (tdef.unflatten([p[0] for p in pairs]),
                tdef.unflatten([p[1] for p in pairs]))


def build_manual_dp_step(loss_fn: Callable, opt, mesh: Mesh, *,
                         dp_axis: str = "data",
                         compress: bool = True) -> Callable:
    """Explicit shard_map data-parallel train step with (optionally
    compressed) gradient reduction.

    state: {"params" (replicated), "opt" (replicated), "step",
            "residual" (per-shard error feedback, sharded over dp)}.
    batch: leaves with leading dim sharded over `dp_axis`.
    """
    world = mesh.shape[dp_axis]

    def step(state, batch):
        def shard_fn(params, opt_state, step_c, residual, local_batch):
            residual = jax.tree.map(lambda r: r[0], residual)   # drop dp dim
            grads = jax.grad(lambda p: loss_fn(p, local_batch)[0])(params)
            if compress:
                grads, new_res = ErrorFeedback.apply(grads, residual, dp_axis,
                                                     world=world)
            else:
                grads = jax.tree.map(
                    lambda g: jax.lax.pmean(g.astype(F32), dp_axis), grads)
                new_res = residual
            new_params, new_opt = opt.update(grads, opt_state, params, step_c)
            new_res = jax.tree.map(lambda r: r[None], new_res)
            return new_params, new_opt, new_res

        n_batch_dims = jax.tree.map(lambda _: P(dp_axis), batch)
        rep = jax.tree.map(lambda _: P(), state["params"])
        rep_opt = jax.tree.map(lambda _: P(), state["opt"])
        res_spec = jax.tree.map(lambda _: P(dp_axis), state["residual"])
        new_params, new_opt, new_res = jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=(rep, rep_opt, P(), res_spec, n_batch_dims),
            out_specs=(rep, rep_opt, res_spec),
            check_vma=False,
        )(state["params"], state["opt"], state["step"], state["residual"],
          batch)
        return {"params": new_params, "opt": new_opt,
                "step": state["step"] + 1, "residual": new_res}

    return step
