"""Per-kernel shape/dtype sweeps, assert_allclose against the pure-jnp
oracles (interpret mode executes the kernel bodies on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import flash_attention, flash_attention_ref
from repro.kernels.fused_matmul import fused_matmul, matmul1
from repro.kernels.mamba2_scan import mamba2_scan, mamba2_scan_ref
from repro.kernels.moe_gmm import moe_gmm, moe_gmm_ref
from repro.kernels.rwkv6_wkv import rwkv6_wkv, rwkv6_wkv_ref

KEY = jax.random.PRNGKey(7)


def _tol(dtype):
    # f32 tolerance allows k-block accumulation-order differences
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------- fused mm
@pytest.mark.parametrize("m,k,n", [(128, 128, 128), (256, 512, 128),
                                   (512, 256, 384)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("scaled", [False, True])
def test_fused_matmul(m, k, n, dtype, scaled):
    x = jax.random.normal(KEY, (m, k), dtype)
    w = jax.random.normal(jax.random.fold_in(KEY, 1), (k, n), dtype)
    sc = (jnp.abs(jax.random.normal(jax.random.fold_in(KEY, 2), (m, 1)))
          .astype(jnp.float32) if scaled else None)
    got = fused_matmul(x, w, sc, block_m=128, block_n=128, block_k=128,
                       interpret=True)
    want = matmul1(x, w, sc)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


# ------------------------------------------------------------------- flash
@pytest.mark.parametrize("sq,skv,h,hkv,dh", [(128, 128, 4, 4, 64),
                                             (256, 256, 4, 2, 64),
                                             (128, 128, 8, 1, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("mode", ["causal", "window", "full", "softcap"])
def test_flash_attention(sq, skv, h, hkv, dh, dtype, mode):
    kw = {"causal": {"causal": True},
          "window": {"causal": True, "window": 48},
          "full": {"causal": False},
          "softcap": {"causal": True, "softcap": 20.0}}[mode]
    q = jax.random.normal(KEY, (2, h, sq, dh), dtype) * 0.5
    k = jax.random.normal(jax.random.fold_in(KEY, 3), (2, hkv, skv, dh),
                          dtype) * 0.5
    v = jax.random.normal(jax.random.fold_in(KEY, 4), (2, hkv, skv, dh),
                          dtype)
    got = flash_attention(q, k, v, block_q=64, block_k=64, interpret=True,
                          **kw)
    want = flash_attention_ref(q, k, v, **kw)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


# ------------------------------------------------------------------ mamba2
@pytest.mark.parametrize("s,p,n,chunk", [(64, 32, 16, 16), (128, 64, 32, 32),
                                         (96, 64, 64, 32)])
def test_mamba2_scan(s, p, n, chunk):
    bh = 3
    x = jax.random.normal(KEY, (bh, s, p))
    dt = jnp.abs(jax.random.normal(jax.random.fold_in(KEY, 5), (bh, s))) * 0.4 + 0.01
    b = jax.random.normal(jax.random.fold_in(KEY, 6), (bh, s, n)) * 0.5
    c = jax.random.normal(jax.random.fold_in(KEY, 7), (bh, s, n)) * 0.5
    a = -jnp.abs(jax.random.normal(jax.random.fold_in(KEY, 8), (bh,))) - 0.05
    y1, h1 = mamba2_scan(x, dt, b, c, a, chunk=chunk, interpret=True)
    y2, h2 = mamba2_scan_ref(x, dt, b, c, a)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2),
                               rtol=1e-4, atol=1e-4)


# ------------------------------------------------------------------- rwkv6
@pytest.mark.parametrize("s,k,chunk", [(64, 32, 16), (128, 64, 16),
                                       (48, 64, 8)])
def test_rwkv6_wkv(s, k, chunk):
    bh = 3
    r = jax.random.normal(KEY, (bh, s, k)) * 0.5
    kk = jax.random.normal(jax.random.fold_in(KEY, 9), (bh, s, k)) * 0.5
    v = jax.random.normal(jax.random.fold_in(KEY, 10), (bh, s, k))
    lw = jnp.clip(-jnp.abs(
        jax.random.normal(jax.random.fold_in(KEY, 11), (bh, s, k))) * 2,
        -5.0, 0.0)
    u = jax.random.normal(jax.random.fold_in(KEY, 12), (bh, k)) * 0.3
    y1, h1 = rwkv6_wkv(r, kk, v, lw, u, chunk=chunk, interpret=True)
    y2, h2 = rwkv6_wkv_ref(r, kk, v, lw, u)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2),
                               rtol=2e-4, atol=2e-4)


# ----------------------------------------------------------------- moe gmm
@pytest.mark.parametrize("e,c,d,f", [(4, 64, 128, 128), (8, 32, 64, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_moe_gmm(e, c, d, f, dtype):
    x = jax.random.normal(KEY, (e, c, d), dtype)
    w = jax.random.normal(jax.random.fold_in(KEY, 13), (e, d, f), dtype)
    counts = jnp.asarray([c, c // 2, 0, 1] * (e // 4), jnp.int32)
    got = moe_gmm(x, w, counts, block_m=32, block_n=64, block_k=64,
                  interpret=True)
    want = moe_gmm_ref(x, w, counts)
    valid = (np.arange(c)[None, :, None]
             < np.asarray(counts)[:, None, None])
    np.testing.assert_allclose(
        np.asarray(got, np.float32) * valid,
        np.asarray(want, np.float32) * valid, **_tol(dtype))


# --------------------------------------------------- model-vs-oracle (XLA)
def test_model_wkv_chunked_matches_naive():
    from repro.models.rwkv6 import wkv_chunked
    bh, s, h, k = 2, 64, 2, 32
    r = jax.random.normal(KEY, (bh, s, h, k)) * 0.5
    kk = jax.random.normal(jax.random.fold_in(KEY, 14), (bh, s, h, k)) * 0.5
    v = jax.random.normal(jax.random.fold_in(KEY, 15), (bh, s, h, k))
    lw = jnp.clip(-jnp.abs(
        jax.random.normal(jax.random.fold_in(KEY, 16), (bh, s, h, k))),
        -5.0, 0.0)
    u = jax.random.normal(jax.random.fold_in(KEY, 17), (h, k)) * 0.3
    y1, h1 = wkv_chunked(r, kk, v, lw, u)
    # oracle via the kernel ref on flattened heads
    rf = jnp.swapaxes(r, 1, 2).reshape(bh * h, s, k)
    kf = jnp.swapaxes(kk, 1, 2).reshape(bh * h, s, k)
    vf = jnp.swapaxes(v, 1, 2).reshape(bh * h, s, k)
    lf = jnp.swapaxes(lw, 1, 2).reshape(bh * h, s, k)
    uf = jnp.broadcast_to(u[None], (bh, h, k)).reshape(bh * h, k)
    y2, h2 = rwkv6_wkv_ref(rf, kf, vf, lf, uf)
    y2 = jnp.swapaxes(y2.reshape(bh, h, s, k), 1, 2)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(h1),
                               np.asarray(h2.reshape(bh, h, k, k)),
                               rtol=2e-4, atol=2e-4)


def test_model_ssd_chunked_matches_naive():
    from repro.models.mamba2 import ssd_chunked
    bsz, s, h, p, n = 2, 64, 3, 16, 8
    x = jax.random.normal(KEY, (bsz, s, h, p))
    dt = jnp.abs(jax.random.normal(jax.random.fold_in(KEY, 18),
                                   (bsz, s, h))) * 0.4 + 0.01
    a_log = jnp.log(jnp.abs(
        jax.random.normal(jax.random.fold_in(KEY, 19), (h,))) + 0.5)
    b = jax.random.normal(jax.random.fold_in(KEY, 20), (bsz, s, n)) * 0.5
    c = jax.random.normal(jax.random.fold_in(KEY, 21), (bsz, s, n)) * 0.5
    y1, h1 = ssd_chunked(x, dt, a_log, b, c, chunk=16)
    # oracle: kernel ref per (b,h)
    a = -jnp.exp(a_log)
    xf = jnp.swapaxes(x, 1, 2).reshape(bsz * h, s, p)
    dtf = jnp.swapaxes(dt, 1, 2).reshape(bsz * h, s)
    bf = jnp.broadcast_to(b[:, None], (bsz, h, s, n)).reshape(bsz * h, s, n)
    cf = jnp.broadcast_to(c[:, None], (bsz, h, s, n)).reshape(bsz * h, s, n)
    af = jnp.broadcast_to(a[None], (bsz, h)).reshape(bsz * h)
    y2, h2 = mamba2_scan_ref(xf, dtf, bf, cf, af)
    y2 = jnp.swapaxes(y2.reshape(bsz, h, s, p), 1, 2)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h1),
                               np.asarray(h2.reshape(bsz, h, n, p)),
                               rtol=1e-4, atol=1e-4)
