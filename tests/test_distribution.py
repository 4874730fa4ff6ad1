"""Distribution-layer tests: sharding rules, partitioner, pipeline
parallelism, HLO analyzer (loop multipliers), mesh builders."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import configs, partition, sharding as shlib
from repro.launch.hlo_analysis import analyze_hlo
from repro.launch.mesh import make_host_mesh
from repro.models import api
from repro.train import pipeline_par


def test_shard_noop_without_context():
    x = jnp.ones((4, 4))
    assert shlib.shard(x, "batch", "embed") is x


def test_rules_divisibility_fallback():
    mesh = make_host_mesh(model=1)
    with shlib.use_rules(mesh, {"batch": "data", "heads": "model"}):
        # 3 does not divide the data axis (1 divides everything -> kept)
        x = jnp.ones((3, 8))
        y = shlib.shard(x, "batch", None)
        assert y.shape == x.shape


def test_param_specs_structure():
    mesh = make_host_mesh(model=1)
    cfg = configs.get("gemma2_2b").smoke
    params = jax.eval_shape(lambda k: api.init(cfg, k),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    specs = partition.param_specs(params, cfg, mesh, regime="train")
    flat_p = jax.tree.leaves(params)
    flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_p) == len(flat_s)
    # Specs never exceed the leaf rank.
    for p, s in zip(flat_p, flat_s):
        assert len(s) <= len(p.shape)


def test_param_specs_moe_layouts():
    """EP layout when experts divide the model axis, TP layout otherwise."""
    import os
    mesh = make_host_mesh(model=1)
    ds = configs.get("deepseek_v3_671b")
    params = jax.eval_shape(lambda k: api.init(ds.smoke, k),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    specs = partition.param_specs(params, ds.smoke, mesh, regime="train")
    assert jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))


def test_serve_regime_drops_fsdp():
    mesh = make_host_mesh(model=1)
    cfg = configs.get("gemma2_2b").smoke
    params = jax.eval_shape(lambda k: api.init(cfg, k),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    tr = partition.param_specs(params, cfg, mesh, regime="train")
    sv = partition.param_specs(params, cfg, mesh, regime="serve")
    # serve specs never reference the data axis
    for s in jax.tree.leaves(sv, is_leaf=lambda x: isinstance(x, P)):
        for e in s:
            axes = (e,) if isinstance(e, str) else (e or ())
            assert "data" not in axes


def test_cache_specs_cover_state():
    mesh = make_host_mesh(model=1)
    for name in ("gemma2_2b", "deepseek_v3_671b", "rwkv6_7b",
                 "recurrentgemma_2b"):
        cfg = configs.get(name).smoke
        st = api.decode_state_specs(cfg, 2, 16)
        specs = partition.cache_specs(st, mesh)
        assert len(jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))) \
            == len(jax.tree.leaves(st))


# ---------------------------------------------------------------------------
# Pipeline parallelism (1-stage degenerate case on a single CPU device)
# ---------------------------------------------------------------------------

def test_pipeline_apply_single_stage_exact():
    from repro.launch import mesh as mesh_lib
    # a 1-device mesh whose axis is named "pod"
    mesh = mesh_lib.make_mesh((1,), ("pod",))
    L, D = 4, 8
    ws = jax.random.normal(jax.random.PRNGKey(0), (L, D, D)) * 0.3

    def layer_fn(w, x):
        return jnp.tanh(x @ w)

    x = jax.random.normal(jax.random.PRNGKey(1), (4, D))
    out = pipeline_par.pipeline_apply(layer_fn, ws, x, mesh=mesh,
                                      axis="pod", microbatches=2)
    ref = x
    for i in range(L):
        ref = jnp.tanh(ref @ ws[i])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5,
                               atol=1e-5)


# ---------------------------------------------------------------------------
# HLO analyzer
# ---------------------------------------------------------------------------

def test_analyzer_scales_scan_bodies():
    def scanned(x, ws):
        def body(c, w):
            return c @ w, None
        y, _ = jax.lax.scan(body, x, ws)
        return y

    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    ws = jax.ShapeDtypeStruct((7, 128, 128), jnp.float32)
    txt = jax.jit(scanned).lower(x, ws).compile().as_text()
    r = analyze_hlo(txt)
    expect = 2 * 128**3 * 7
    assert abs(r["flops"] - expect) / expect < 0.01


def test_analyzer_nested_scan():
    def nested(x, ws):
        def outer(c, _):
            def body(cc, w):
                return cc @ w, None
            y, _ = jax.lax.scan(body, c, ws)
            return y, None
        y, _ = jax.lax.scan(outer, x, None, length=3)
        return y

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    ws = jax.ShapeDtypeStruct((5, 64, 64), jnp.float32)
    txt = jax.jit(nested).lower(x, ws).compile().as_text()
    r = analyze_hlo(txt)
    expect = 2 * 64**3 * 5 * 3
    assert abs(r["flops"] - expect) / expect < 0.01


def test_analyzer_counts_collectives_with_groups():
    mesh = make_host_mesh(data=1, model=1)

    def f(x):
        return jax.lax.psum(x, "data")

    x = jnp.ones((8, 128))
    txt = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P(), out_specs=P(),
                                check_vma=False)).lower(x).compile().as_text()
    r = analyze_hlo(txt)
    # group size 1: wire bytes 0, but op counted
    assert "all-reduce" in r["collectives"] or r["collective_wire_bytes"] == 0


def test_production_mesh_shapes():
    """make_production_mesh only works under the 512-device dry-run env; here
    we check the pure logic via mock devices count requirement."""
    import repro.launch.mesh as meshmod
    n = len(jax.devices())
    if n < 512:
        with pytest.raises(Exception):
            meshmod.make_production_mesh()
    host = meshmod.make_host_mesh(model=1)
    assert set(host.axis_names) == {"data", "model"}
