"""Compile the served path's kernels for a described TPU v5e, without a chip.

For each of the five edge nets at batch 8, at the shapes the fleet planner
gives them: its fusion group through ``fused_mlp_q8`` (the served
megakernel), and each of its layers through ``gemm_int8`` at the plan's
tile (the per-layer path the degradation ladder falls back to).  The chip's
compiler refuses here what the interpreter accepts, such as a slice not
aligned to the tiling or a kernel that needs too much VMEM.  A compile that
passes says nothing about results or times.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import plan as plan_lib
from repro.kernels import fused_mlp, gemm_int8
from repro.models import edge

F32, I8 = jnp.float32, jnp.int8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


@pytest.fixture(scope="module")
def fleet():
    return plan_lib.plan_fleet([edge.edge_config(n) for n in edge.EDGE_NETS],
                               target="tpu")


def _plan(fleet, name):
    return next(t.plan for t in fleet.tenants if t.net_id == name)


def _arg(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("name", list(edge.EDGE_NETS))
def test_fused_group_compiles_for_v5e(name, fleet, one_chip,
                                      no_compile_cache):
    cfg = edge.edge_config(name)
    plan = _plan(fleet, name)
    groups = [g for g in plan.groups() if len(g) > 1]
    assert groups, "the served plan must fuse something"
    shapes = cfg.layer_shapes
    spec = functools.partial(_arg, one_chip)
    for grp in groups:
        x = spec((cfg.batch, shapes[grp[0]][0]), F32)
        ws = tuple(spec(shapes[i], I8) for i in grp)
        scales = tuple(spec((shapes[i][1],), F32) for i in grp)
        biases = tuple(spec((shapes[i][1],), F32) for i in grp)
        xs = spec((len(grp),), F32)
        compiled = fused_mlp.fused_mlp_q8.lower(
            x, ws, scales, biases, xs, act="relu",
            act_last=grp[-1] != len(shapes) - 1, out_dtype=F32,
            interpret=False).compile()
        assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("name", list(edge.EDGE_NETS))
def test_per_layer_gemm_compiles_for_v5e(name, fleet, one_chip,
                                         no_compile_cache):
    cfg = edge.edge_config(name)
    plan = _plan(fleet, name)
    spec = functools.partial(_arg, one_chip)
    seen = set()
    for i, (k, n) in enumerate(cfg.layer_shapes):
        tile = plan.layer(i).api_tile
        if (k, n, tile) in seen:
            continue
        seen.add((k, n, tile))
        bm, bk, bn = tile
        compiled = gemm_int8.gemm_int8.lower(
            spec((cfg.batch, k), I8), spec((k, n), I8), spec((n,), F32),
            spec((), F32), block_m=bm, block_k=bk, block_n=bn,
            out_dtype=F32, interpret=False).compile()
        assert "tpu_custom_call" in compiled.as_text()
