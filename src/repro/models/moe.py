"""Mixture-of-Experts block with scatter-based (one-hot-free) dispatch.

Two execution paths share one math core (:func:`_moe_math`):

* **local** — no mesh context: all experts on one device (smoke tests, edge).
* **shard_map** — expert parallelism over the ``model`` mesh axis.  Two weight
  layouts, picked automatically:

  - ``ep``  (num_experts % model_axis == 0): experts sharded over ``model``;
    each device dispatches the tokens of its data shard to its local experts
    and the per-token contributions are ``psum``-combined over ``model`` —
    the TPU rendition of the paper's cascade-combine.  Expert weights are
    additionally FSDP-sharded over ``data`` (gathered per layer).
  - ``tp``  (few experts, e.g. mixtral's 8 on a 16-way axis): every expert's
    FFN is tensor-parallel over ``model`` (d_ff sharded); dispatch stays
    local; the down-projection partial sums ``psum`` over ``model``.

Dispatch avoids one-hot einsums entirely (they would inflate HLO FLOPs by
>1000x — see DESIGN.md): token->slot assignment is computed with a per-shard
sort, the expert input buffer is built with a ``take(mode=fill)`` gather, and
the combine is a scatter-add.  Capacity drops follow the standard
capacity-factor policy.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models.config import ModelConfig, MoEConfig
from repro.models.layers import dense_init, dtype_of
from repro import sharding as shlib

F32 = jnp.float32


def init_moe(key, cfg: ModelConfig) -> dict:
    mo = cfg.moe
    d, f, e = cfg.d_model, mo.d_ff_expert, mo.num_experts
    dt = dtype_of(cfg)
    ks = jax.random.split(key, 6)
    p = {
        "router": dense_init(ks[0], (d, e), F32, scale=0.02),
        "w_gate": dense_init(ks[1], (e, d, f), dt),
        "w_up": dense_init(ks[2], (e, d, f), dt),
        "w_down": dense_init(ks[3], (e, f, d), dt, scale=1.0 / (f ** 0.5)),
    }
    if mo.router_type == "sigmoid":
        p["router_bias"] = jnp.zeros((e,), F32)
    if mo.num_shared_experts:
        fs = f * mo.num_shared_experts
        p["shared"] = {
            "w_gate": dense_init(ks[4], (d, fs), dt),
            "w_up": dense_init(ks[5], (d, fs), dt),
            "w_down": dense_init(jax.random.fold_in(ks[4], 1), (fs, d), dt,
                                 scale=1.0 / (fs ** 0.5)),
        }
    return p


def _route(p: dict, x2d: jax.Array, mo: MoEConfig):
    """Router scores -> (weights (T,k), ids (T,k), aux load-balance loss)."""
    logits = jnp.einsum("td,de->te", x2d.astype(F32), p["router"],
                        preferred_element_type=F32)
    if mo.router_type == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        sel = scores + p["router_bias"][None, :]
        top_w, top_i = jax.lax.top_k(sel, mo.top_k)
        top_w = jnp.take_along_axis(scores, top_i, axis=1)
        top_w = top_w / (jnp.sum(top_w, axis=1, keepdims=True) + 1e-9)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        top_w, top_i = jax.lax.top_k(probs, mo.top_k)
        top_w = top_w / (jnp.sum(top_w, axis=1, keepdims=True) + 1e-9)
        scores = probs
    # Switch-style load-balance aux: E * sum_e (frac_tokens_e * mean_prob_e).
    t = x2d.shape[0]
    counts = jnp.zeros((mo.num_experts,), F32).at[top_i.reshape(-1)].add(1.0)
    frac = counts / (t * mo.top_k)
    mean_prob = jnp.mean(scores, axis=0)
    aux = mo.num_experts * jnp.sum(frac * mean_prob)
    return top_w, top_i, aux


def _dispatch_indices(top_i: jax.Array, top_w: jax.Array, *,
                      num_experts: int, e_start: int, e_count: int,
                      capacity: int):
    """Token->(expert,slot) assignment via per-shard sort (no one-hots).

    Returns (token_for_slot (e_count, C), weight_for_slot (e_count, C)) where
    out-of-range entries point at token index T (dropped by mode='fill').
    """
    t, k = top_i.shape
    n = t * k
    flat_e = top_i.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    flat_w = top_w.reshape(-1)
    # Slot within expert group = rank among same-expert assignments.
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    group_start = jnp.searchsorted(sorted_e, sorted_e, side="left")
    pos_in_group = jnp.arange(n, dtype=jnp.int32) - group_start.astype(jnp.int32)
    slot = jnp.zeros((n,), jnp.int32).at[order].set(pos_in_group)
    local = (flat_e >= e_start) & (flat_e < e_start + e_count)
    valid = local & (slot < capacity)
    e_idx = jnp.where(valid, flat_e - e_start, e_count)      # OOB -> dropped
    s_idx = jnp.where(valid, slot, capacity)
    token_for_slot = jnp.full((e_count, capacity), t, jnp.int32)
    token_for_slot = token_for_slot.at[e_idx, s_idx].set(flat_t, mode="drop")
    weight_for_slot = jnp.zeros((e_count, capacity), F32)
    weight_for_slot = weight_for_slot.at[e_idx, s_idx].set(flat_w, mode="drop")
    return token_for_slot, weight_for_slot


def _expert_ffn(wg, wu, wd, buf, gather_axes: tuple = ()):
    """buf: (E_loc, C, D) -> (E_loc, C, D); silu-gated FFN, f32 accum.

    Runs ONE EXPERT AT A TIME (checkpointed lax.map, safe here: we are inside
    shard_map, so sharding is manual and the map cannot be "helpfully"
    replicated by GSPMD).  FSDP weight gathers happen per expert inside the
    map — peak gathered weights are one expert's (D,F), not the whole bank
    (measured ~10 GiB on the 671B train cell otherwise, mesh-independent).
    """

    def one(inputs):
        wge, wue, wde, bufe = inputs
        for a in reversed(gather_axes):
            wge = jax.lax.all_gather(wge, a, axis=0, tiled=True)
            wue = jax.lax.all_gather(wue, a, axis=0, tiled=True)
            wde = jax.lax.all_gather(wde, a, axis=1, tiled=True)
        g = jnp.einsum("cd,df->cf", bufe, wge, preferred_element_type=F32)
        u = jnp.einsum("cd,df->cf", bufe, wue, preferred_element_type=F32)
        h = (jax.nn.silu(g) * u).astype(bufe.dtype)
        return jnp.einsum("cf,fd->cd", h, wde, preferred_element_type=F32)

    return jax.lax.map(jax.checkpoint(one), (wg, wu, wd, buf))


def _moe_math(p: dict, x2d: jax.Array, mo: MoEConfig, *,
              e_start: int, e_count: int, capacity: int,
              gather_axes: tuple = ()):
    """Contribution of experts [e_start, e_start+e_count) for tokens x2d."""
    t, d = x2d.shape
    top_w, top_i, aux = _route(p, x2d, mo)
    tok4slot, w4slot = _dispatch_indices(
        top_i, top_w, num_experts=mo.num_experts, e_start=e_start,
        e_count=e_count, capacity=capacity)
    buf = jnp.take(x2d, tok4slot.reshape(-1), axis=0,
                   mode="fill", fill_value=0).reshape(e_count, capacity, d)
    y = _expert_ffn(p["w_gate"], p["w_up"], p["w_down"], buf,
                    gather_axes)                                 # (E_loc,C,D)
    y = y * w4slot[..., None]
    out = jnp.zeros((t, d), F32).at[tok4slot.reshape(-1)].add(
        y.reshape(-1, d), mode="drop")
    return out.astype(x2d.dtype), aux


def _mesh_size(mesh) -> int:
    n = 1
    for a in mesh.axis_names:
        n *= mesh.shape[a]
    return n


def _dp_size(mesh) -> int:
    n = 1
    for a in shlib.dp_axes(mesh):
        n *= mesh.shape[a]
    return n


def _moe_a2a(p: dict, x: jax.Array, cfg: ModelConfig):
    """SP + all-to-all dispatch (beyond-paper, §Perf).

    One shard_map over the whole MoE block with x kept 3-D — the local
    reshape to tokens happens INSIDE (manual sharding), so no GSPMD boundary
    reshard of the mixed (batch@dp, seq@model) residual occurs (measured as
    "involuntary full rematerialization" warnings + >30 GiB of transients
    when the reshape sat outside).  Shared-expert weights stay FSDP-sharded
    and are gathered locally (88 MB/layer for deepseek-v3).
    """
    mo = cfg.moe
    b, s, d = x.shape
    ctx = shlib.current()
    mesh = ctx.mesh
    dp = shlib.dp_axes(mesh)
    dp_n, model_n = _dp_size(mesh), mesh.shape["model"]
    world = dp_n * model_n
    # Full-mesh 2D-EP when experts divide the whole mesh (deepseek: 256
    # experts over 256 chips -> ONE resident expert per device, ZERO weight
    # gathers).  Otherwise EP over model with FSDP gathers.
    ep2d = mo.num_experts % world == 0
    ep_axes = tuple(dp) + ("model",) if ep2d else ("model",)
    e_count = mo.num_experts // (world if ep2d else model_n)
    t_loc = (b // dp_n) * (s // model_n)
    cap_src = max(2, _capacity(t_loc, mo))
    fsdp = () if ep2d else (
        dp if cfg.d_model % max(dp_n, 1) == 0 and dp else ())

    x_spec = P(dp, "model", None)
    w_spec = {"router": P(None, None),
              "w_gate": P(ep_axes, fsdp or None, None),
              "w_up": P(ep_axes, fsdp or None, None),
              "w_down": P(ep_axes, None, fsdp or None)}
    if "router_bias" in p:
        w_spec["router_bias"] = P(None)
    has_shared = "shared" in p
    if has_shared:
        w_spec["shared"] = {"w_gate": P(None, fsdp or None),
                            "w_up": P(None, fsdp or None),
                            "w_down": P(fsdp or None, None)}

    def body(xl, pl):
        bl, sl, _ = xl.shape
        x2 = xl.reshape(bl * sl, d)
        top_w, top_i, aux = _route(pl, x2, mo)
        tok4slot, w4slot = _dispatch_indices(
            top_i, top_w, num_experts=mo.num_experts, e_start=0,
            e_count=mo.num_experts, capacity=cap_src)
        buf = jnp.take(x2, tok4slot.reshape(-1), axis=0,
                       mode="fill", fill_value=0
                       ).reshape(mo.num_experts, cap_src, d)
        buf = jax.lax.all_to_all(buf, ep_axes, split_axis=0,
                                 concat_axis=1, tiled=True)
        y = _expert_ffn(pl["w_gate"], pl["w_up"], pl["w_down"], buf,
                        tuple(fsdp))
        y = jax.lax.all_to_all(y.astype(xl.dtype), ep_axes, split_axis=1,
                               concat_axis=0, tiled=True)
        y = y.astype(F32) * w4slot[..., None]
        out = jnp.zeros((bl * sl, d), F32).at[tok4slot.reshape(-1)].add(
            y.reshape(-1, d), mode="drop")
        if has_shared:
            sw = pl["shared"]
            wg, wu, wd = sw["w_gate"], sw["w_up"], sw["w_down"]
            for a in reversed(fsdp):
                wg = jax.lax.all_gather(wg, a, axis=1, tiled=True)
                wu = jax.lax.all_gather(wu, a, axis=1, tiled=True)
                wd = jax.lax.all_gather(wd, a, axis=0, tiled=True)
            g = jnp.einsum("td,df->tf", x2, wg, preferred_element_type=F32)
            u = jnp.einsum("td,df->tf", x2, wu, preferred_element_type=F32)
            h = (jax.nn.silu(g) * u).astype(xl.dtype)
            out = out + jnp.einsum("tf,fd->td", h, wd,
                                   preferred_element_type=F32)
        return (out.astype(xl.dtype).reshape(bl, sl, d),
                jax.lax.pmean(aux, ep_axes))

    y, aux = jax.shard_map(
        body, mesh=mesh, in_specs=(x_spec, w_spec), out_specs=(x_spec, P()),
        check_vma=False,
    )(x, {k: p[k] for k in w_spec})
    return y, aux


def _capacity(tokens: int, mo: MoEConfig) -> int:
    cap = int(tokens * mo.top_k / mo.num_experts * mo.capacity_factor)
    return max(mo.top_k, min(cap, tokens))


def moe_block(p: dict, x: jax.Array, cfg: ModelConfig) -> tuple[jax.Array, jax.Array]:
    """Apply the MoE FFN.  x: (B, S, D).  Returns (y, aux_loss)."""
    mo = cfg.moe
    b, s, d = x.shape
    ctx = shlib.current()
    x2d = x.reshape(b * s, d)

    a2a_tokens = (mo.impl == "a2a" and ctx is not None
                  and "model" in ctx.mesh.axis_names
                  and mo.num_experts % ctx.mesh.shape["model"] == 0
                  and b % _dp_size(ctx.mesh) == 0
                  and s % ctx.mesh.shape["model"] == 0)
    if a2a_tokens:
        return _moe_a2a(p, x, cfg)

    shared_y = None
    if "shared" in p:
        sp = p["shared"]
        g = jnp.einsum("td,df->tf", x2d, sp["w_gate"], preferred_element_type=F32)
        u = jnp.einsum("td,df->tf", x2d, sp["w_up"], preferred_element_type=F32)
        h = (jax.nn.silu(g) * u).astype(x.dtype)
        h = shlib.shard(h.reshape(b, s, -1),
                        "batch", None, "mlp").reshape(b * s, -1)
        shared_y = jnp.einsum("tf,fd->td", h, sp["w_down"],
                              preferred_element_type=F32).astype(x.dtype)

    if ctx is None or "model" not in ctx.mesh.axis_names:
        cap = _capacity(b * s, mo)
        y, aux = _moe_math(p, x2d, mo, e_start=0, e_count=mo.num_experts,
                           capacity=cap)
    else:
        mesh = ctx.mesh
        model_n = mesh.shape["model"]
        dp = shlib.dp_axes(mesh)
        dp_n = 1
        for a in dp:
            dp_n *= mesh.shape[a]
        layout = "ep" if mo.num_experts % model_n == 0 else "tp"
        t_loc = (b * s) // dp_n if (b * s) % dp_n == 0 else b * s
        cap = _capacity(t_loc, mo)
        batch_axes = dp if b % dp_n == 0 else None
        x_spec = P(batch_axes, None)
        route_p = {k: v for k, v in p.items() if k != "shared"}

        if layout == "ep":
            e_count = mo.num_experts // model_n
            # experts sharded over model on E; FSDP over data on D
            fsdp = dp if cfg.d_model % dp_n == 0 else None
            w_spec = {"router": P(None, None),
                      "w_gate": P("model", fsdp, None),
                      "w_up": P("model", fsdp, None),
                      "w_down": P("model", None, fsdp)}
            if "router_bias" in route_p:
                w_spec["router_bias"] = P(None)

            def _ep(xl, pl):
                e_start = jax.lax.axis_index("model") * e_count
                y, aux = _moe_math(pl, xl, mo, e_start=e_start,
                                   e_count=e_count, capacity=cap,
                                   gather_axes=tuple(fsdp or ()))
                return (jax.lax.psum(y, "model"),
                        jax.lax.psum(aux, "model") / model_n)

            y, aux = jax.shard_map(
                _ep, mesh=mesh,
                in_specs=(x_spec, w_spec),
                out_specs=(x_spec, P()),
                check_vma=False,
            )(x2d, {k: route_p[k] for k in w_spec})
        else:
            # tp layout: all experts local; d_ff sharded over model; D FSDP/data.
            fsdp = dp if cfg.d_model % dp_n == 0 else None
            w_spec = {"router": P(None, None),
                      "w_gate": P(None, fsdp, "model"),
                      "w_up": P(None, fsdp, "model"),
                      "w_down": P(None, "model", fsdp)}
            if "router_bias" in route_p:
                w_spec["router_bias"] = P(None)

            def _tp(xl, pl):
                y, aux = _moe_math(pl, xl, mo, e_start=0,
                                   e_count=mo.num_experts, capacity=cap,
                                   gather_axes=tuple(fsdp or ()))
                return jax.lax.psum(y, "model"), aux

            y, aux = jax.shard_map(
                _tp, mesh=mesh,
                in_specs=(x_spec, w_spec),
                out_specs=(x_spec, P()),
                check_vma=False,
            )(x2d, {k: route_p[k] for k in w_spec})

    if shared_y is not None:
        y = y + shared_y
    return y.reshape(b, s, d), aux


def moe_param_specs(cfg: ModelConfig, mesh) -> dict:
    """PartitionSpecs for MoE params matching moe_block's shard_map layout."""
    mo = cfg.moe
    model_n = mesh.shape["model"] if "model" in mesh.axis_names else 1
    dp = shlib.dp_axes(mesh)
    dp_n = 1
    for a in dp:
        dp_n *= mesh.shape[a]
    fsdp = dp if cfg.d_model % max(dp_n, 1) == 0 and dp else None
    if mo.num_experts % max(model_n, 1) == 0 and model_n > 1:
        specs = {"router": P(None, None),
                 "w_gate": P("model", fsdp, None),
                 "w_up": P("model", fsdp, None),
                 "w_down": P("model", None, fsdp)}
    else:
        specs = {"router": P(None, None),
                 "w_gate": P(None, fsdp, "model"),
                 "w_up": P(None, fsdp, "model"),
                 "w_down": P(None, "model", fsdp)}
    specs["router_bias"] = P(None)
    specs["shared"] = {"w_gate": P(None, "model"), "w_up": P(None, "model"),
                       "w_down": P("model", None)}
    return specs
