"""Per-kernel allclose sweeps: every Pallas kernel vs its ref.py oracle,
across shapes and dtypes, in interpret mode (CPU executes the kernel body)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RNG = np.random.default_rng(42)


def randn(shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(RNG.normal(size=shape) * scale, dtype)


@pytest.mark.parametrize("backend,interpret", [
    ("cpu", True), ("tpu", False), ("gpu", RuntimeError)])
def test_use_interpret_by_backend(backend, interpret, monkeypatch):
    """Interpreted on the CPU, compiled on a TPU, refused anywhere else."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if interpret is RuntimeError:
        with pytest.raises(RuntimeError, match="'gpu'"):
            ops.use_interpret()
    else:
        assert ops.use_interpret() is interpret


# ---------------------------------------------------------------------------
# tiled_gemm
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,k,n", [
    (8, 64, 64), (8, 192, 256), (16, 128, 384), (33, 100, 130),  # ragged
    (8, 512, 512), (1, 128, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_tiled_gemm_shapes(m, k, n, dtype):
    x, w = randn((m, k), dtype), randn((k, n), dtype)
    out = ops.tiled_gemm(x, w, block_m=8, block_k=64, block_n=128)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref.tiled_gemm(x, w), np.float32),
        rtol=tol, atol=tol * 8)


@pytest.mark.parametrize("blocks", [(8, 128, 128), (16, 64, 256), (32, 256, 128)])
def test_tiled_gemm_block_sweep(blocks):
    bm, bk, bn = blocks
    x, w = randn((32, 256)), randn((256, 512))
    out = ops.tiled_gemm(x, w, block_m=bm, block_k=bk, block_n=bn)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.tiled_gemm(x, w)),
                               rtol=1e-5, atol=1e-4)


def test_tiled_gemm_int8_accum():
    x = jnp.asarray(RNG.integers(-127, 127, (8, 256)), jnp.int8)
    w = jnp.asarray(RNG.integers(-127, 127, (256, 128)), jnp.int8)
    out = ops.tiled_gemm(x, w, block_m=32, block_k=128, block_n=128)
    assert out.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(ref.tiled_gemm(x, w)))


# ---------------------------------------------------------------------------
# fused_dense
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("act", ["none", "relu", "gelu", "silu", "tanh"])
@pytest.mark.parametrize("residual", [False, True])
def test_fused_dense(act, residual):
    x, w = randn((8, 192)), randn((192, 256))
    b = randn((256,))
    r = randn((8, 256)) if residual else None
    out = ops.fused_dense(x, w, b, r, act=act, block_m=8, block_k=64,
                          block_n=128)
    exp = ref.fused_dense(x, w, b, r, act=act)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=1e-5, atol=1e-4)


# ---------------------------------------------------------------------------
# gemm_int8
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,k,n", [(8, 128, 128), (8, 256, 384), (24, 250, 300)])
def test_gemm_int8(m, k, n):
    x = jnp.asarray(RNG.integers(-127, 127, (m, k)), jnp.int8)
    w = jnp.asarray(RNG.integers(-127, 127, (k, n)), jnp.int8)
    sw = jnp.asarray(RNG.uniform(0.01, 0.1, (n,)), jnp.float32)
    out = ops.gemm_int8(x, w, sw, 0.07, block_m=8, block_k=128, block_n=128,
                        out_dtype=jnp.float32)
    exp = ref.gemm_int8(x, w, sw, 0.07, out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), rtol=1e-3,
                               atol=1e-2)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kw", [
    dict(causal=True),
    dict(causal=False),
    dict(causal=True, window=64),
    dict(causal=True, softcap=30.0),
    dict(causal=True, window=96, softcap=50.0),
])
def test_flash_attention_variants(kw):
    B, Hq, Hkv, S, D = 2, 4, 2, 256, 64
    q, k, v = randn((B, Hq, S, D)), randn((B, Hkv, S, D)), randn((B, Hkv, S, D))
    out = ops.flash_attention(q, k, v, block_q=64, block_kv=64, **kw)
    exp = ref.attention(q, k, v, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("s,block", [(128, 128), (192, 64), (512, 256)])
def test_flash_attention_block_sweep(s, block):
    q = randn((1, 2, s, 32))
    k = randn((1, 2, s, 32))
    v = randn((1, 2, s, 32))
    out = ops.flash_attention(q, k, v, causal=True, block_q=block,
                              block_kv=block)
    exp = ref.attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=2e-3, atol=2e-3)


def test_flash_attention_bf16():
    q = randn((1, 2, 128, 64), jnp.bfloat16)
    k = randn((1, 2, 128, 64), jnp.bfloat16)
    v = randn((1, 2, 128, 64), jnp.bfloat16)
    out = ops.flash_attention(q, k, v, causal=True, block_q=64, block_kv=64)
    exp = ref.attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), rtol=3e-2,
                               atol=3e-2)


# ---------------------------------------------------------------------------
# recurrences
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("t,block_t", [(64, 16), (100, 32), (256, 128)])
def test_linear_scan(t, block_t):
    a = jnp.asarray(RNG.uniform(0.4, 0.999, (2, t, 128)), jnp.float32)
    b = randn((2, t, 128))
    out = ops.linear_scan(a, b, block_t=block_t)
    exp = ref.linear_scan(a, b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=1e-4, atol=1e-4)


def test_rglru_layer_finite():
    x = randn((2, 64, 128))
    ga, gx = randn((2, 64, 128)), randn((2, 64, 128))
    ll = randn((128,))
    h = ops.rglru(x, ga, gx, ll, block_t=16)
    assert h.shape == x.shape
    assert bool(jnp.isfinite(h.astype(jnp.float32)).all())


@pytest.mark.parametrize("t,block_t", [(64, 16), (96, 32)])
def test_rwkv6_kernel(t, block_t):
    BH, D = 3, 64
    r, k, v = (randn((BH, t, D), scale=0.5) for _ in range(3))
    w = jnp.asarray(RNG.uniform(0.5, 0.99, (BH, t, D)), jnp.float32)
    u = randn((D,), scale=0.3)
    out = ops.rwkv6_scan(r, k, v, w, u, block_t=block_t)
    exp = ref.rwkv6_scan(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=2e-3, atol=2e-3)


def test_rwkv6_chunked_model_form():
    """models.rwkv chunk-recurrent == sequential oracle."""
    from repro.models.rwkv import rwkv6_chunked
    B, H, T, D = 2, 2, 100, 32
    r, k, v = (randn((B, H, T, D), scale=0.5) for _ in range(3))
    w = jnp.asarray(RNG.uniform(0.3, 0.999, (B, H, T, D)), jnp.float32)
    u = randn((H, D), scale=0.3)
    out, _ = rwkv6_chunked(r, k, v, w, u, chunk=32)
    for bi in range(B):
        for hi in range(H):
            exp = ref.rwkv6_scan(r[bi, hi][None], k[bi, hi][None],
                                 v[bi, hi][None], w[bi, hi][None], u[hi])
            np.testing.assert_allclose(np.asarray(out[bi, hi]),
                                       np.asarray(exp[0]), rtol=2e-3,
                                       atol=2e-3)
