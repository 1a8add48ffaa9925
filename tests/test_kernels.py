"""Pallas kernel validation (interpret=True on CPU): shape/dtype sweeps +
assert_allclose against the pure-jnp oracles, cross-checks against the
XLA-native model paths, and hypothesis property tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.mamba2_ssd.ops import mamba2_ssd
from repro.kernels.mamba2_ssd.ref import ssd_scan_ref
from repro.kernels.rmsnorm.ops import rmsnorm_fused
from repro.kernels.rmsnorm.ref import rmsnorm_ref

RNG = np.random.default_rng(0)


def randn(shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(RNG.standard_normal(shape) * scale, dtype)


TOL = {jnp.float32: dict(atol=2e-5, rtol=2e-5),
       jnp.bfloat16: dict(atol=3e-2, rtol=3e-2)}


# ------------------------------------------------------------------ #
# flash attention
# ------------------------------------------------------------------ #

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,h,kh,s,d,w,bq,bk", [
    (2, 4, 2, 256, 64, None, 64, 64),     # GQA
    (1, 4, 4, 128, 32, 48, 32, 32),       # MHA + window
    (2, 6, 2, 200, 32, None, 64, 64),     # non-divisible seq (padding)
    (1, 8, 1, 128, 128, None, 64, 64),    # MQA, MXU-aligned head
    (1, 2, 2, 100, 64, 32, 32, 64),       # window + ragged + bq≠bk
])
def test_flash_attention_matches_ref(dtype, b, h, kh, s, d, w, bq, bk):
    q = randn((b, s, h, d), dtype)
    k = randn((b, s, kh, d), dtype)
    v = randn((b, s, kh, d), dtype)
    out = flash_attention(q, k, v, n_kv_heads=kh, window=w, bq=bq, bk=bk)
    ref = attention_ref(jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
                        jnp.swapaxes(v, 1, 2), n_kv_heads=kh, window=w)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(jnp.swapaxes(ref, 1, 2), np.float32),
                               **TOL[dtype])


def test_flash_attention_matches_model_blockwise():
    """Kernel ↔ XLA-native twin (models.attention.blockwise_sdpa)."""
    from repro.models.attention import blockwise_sdpa
    q = randn((2, 256, 4, 32))
    k = randn((2, 256, 2, 32))
    v = randn((2, 256, 2, 32))
    a = flash_attention(q, k, v, n_kv_heads=2, bq=64, bk=64)
    b_ = blockwise_sdpa(q, k, v, 2, q_block=64, kv_block=64)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=2e-5,
                               rtol=2e-5)


def test_flash_attention_suffix_decode():
    q = randn((1, 64, 2, 32))
    k = randn((1, 256, 2, 32))
    v = randn((1, 256, 2, 32))
    out = flash_attention(q, k, v, n_kv_heads=2, bq=32, bk=64)
    ref = attention_ref(jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
                        jnp.swapaxes(v, 1, 2), n_kv_heads=2)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(jnp.swapaxes(ref, 1, 2)),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_property():
    from repro.testing import property_testing
    given, settings, st = property_testing()

    @settings(max_examples=10, deadline=None)
    @given(s=st.integers(16, 128), kh=st.sampled_from([1, 2, 4]),
           g=st.sampled_from([1, 2]), d=st.sampled_from([16, 32]),
           seed=st.integers(0, 999))
    def inner(s, kh, g, d, seed):
        r = np.random.default_rng(seed)
        h = kh * g
        q = jnp.asarray(r.standard_normal((1, s, h, d)), jnp.float32)
        k = jnp.asarray(r.standard_normal((1, s, kh, d)), jnp.float32)
        v = jnp.asarray(r.standard_normal((1, s, kh, d)), jnp.float32)
        out = flash_attention(q, k, v, n_kv_heads=kh, bq=32, bk=32)
        ref = attention_ref(jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
                            jnp.swapaxes(v, 1, 2), n_kv_heads=kh)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(jnp.swapaxes(ref, 1, 2)),
                                   atol=3e-5, rtol=3e-5)

    inner()


# ------------------------------------------------------------------ #
# decode attention
# ------------------------------------------------------------------ #

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,h,kh,s,d,bk,fill", [
    (2, 4, 2, 512, 64, 128, 300),     # partially filled cache
    (1, 8, 8, 256, 32, 64, 256),      # fully filled
    (4, 4, 1, 300, 64, 128, 123),     # MQA + ragged cache
])
def test_decode_attention_matches_ref(dtype, b, h, kh, s, d, bk, fill):
    q = randn((b, 1, h, d), dtype)
    k = randn((b, s, kh, d), dtype)
    v = randn((b, s, kh, d), dtype)
    valid = (jnp.arange(s) < fill)
    out = decode_attention(q, k, v, valid, n_kv_heads=kh, bk=bk)
    g = h // kh
    ref = decode_attention_ref(q[:, 0].reshape(b, kh, g, d),
                               jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2),
                               valid)
    np.testing.assert_allclose(np.asarray(out[:, 0].reshape(b, kh, g, d),
                                          np.float32),
                               np.asarray(ref, np.float32), **TOL[dtype])


def test_decode_attention_ring_mask():
    """Scattered valid slots (SWA ring cache pattern)."""
    b, h, kh, s, d = 1, 2, 2, 128, 32
    q = randn((b, 1, h, d))
    k = randn((b, s, kh, d))
    v = randn((b, s, kh, d))
    valid = jnp.asarray(RNG.integers(0, 2, s), bool)
    valid = valid.at[0].set(True)  # at least one valid slot
    out = decode_attention(q, k, v, valid, n_kv_heads=kh, bk=32)
    ref = decode_attention_ref(q[:, 0].reshape(b, kh, 1, d),
                               jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2),
                               valid)
    np.testing.assert_allclose(np.asarray(out[:, 0].reshape(b, kh, 1, d)),
                               np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_decode_attention_per_sequence_mask():
    """(B, S) mask with its own fill per sequence (paged/continuous
    batching), S not a multiple of bk so the padded tail is masked too."""
    b, h, kh, s, d = 3, 4, 2, 200, 32
    q = randn((b, 1, h, d))
    k = randn((b, s, kh, d))
    v = randn((b, s, kh, d))
    fill = jnp.asarray([1, 77, 200])
    valid = jnp.arange(s)[None, :] < fill[:, None]
    out = decode_attention(q, k, v, valid, n_kv_heads=kh, bk=64)
    g = h // kh
    ref = decode_attention_ref(q[:, 0].reshape(b, kh, g, d),
                               jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2),
                               valid)
    np.testing.assert_allclose(np.asarray(out[:, 0].reshape(b, kh, g, d)),
                               np.asarray(ref), atol=2e-5, rtol=2e-5)


# ------------------------------------------------------------------ #
# mamba2 SSD
# ------------------------------------------------------------------ #

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,H,s,P,N,chunk", [
    (2, 4, 256, 32, 32, 64),
    (1, 2, 100, 16, 64, 32),    # ragged seq
    (2, 8, 128, 64, 16, 128),   # single chunk
])
def test_mamba2_ssd_matches_scan(dtype, b, H, s, P, N, chunk):
    x = randn((b, s, H, P), dtype, 0.5)
    dt = jnp.abs(randn((b, s, H), jnp.float32, 0.3)) + 0.01
    B = randn((b, s, N), dtype, 0.5)
    C = randn((b, s, N), dtype, 0.5)
    A = -jnp.abs(jnp.asarray(RNG.uniform(0.5, 2.0, H), jnp.float32))
    D = jnp.asarray(RNG.standard_normal(H), jnp.float32)
    y, hf = mamba2_ssd(x, dt, B, C, A, D, chunk=chunk)
    yr, hr = ssd_scan_ref(jnp.moveaxis(x, 2, 1), jnp.moveaxis(dt, 2, 1),
                          B, C, A, D)
    tol = dict(atol=5e-4, rtol=5e-3) if dtype == jnp.float32 else \
        dict(atol=5e-2, rtol=5e-2)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(jnp.moveaxis(yr, 1, 2), np.float32),
                               **tol)
    np.testing.assert_allclose(np.asarray(hf), np.asarray(hr),
                               atol=1e-3, rtol=1e-2)


def test_mamba2_ssd_matches_model_chunked():
    """Kernel ↔ XLA-native twin (models.ssm.ssd_chunked)."""
    from repro.models.ssm import ssd_chunked
    b, H, s, P, N = 1, 2, 128, 16, 32
    x = randn((b, s, H, P), jnp.float32, 0.5)
    dt = jnp.abs(randn((b, s, H), jnp.float32, 0.3)) + 0.01
    B = randn((b, s, N), jnp.float32, 0.5)
    C = randn((b, s, N), jnp.float32, 0.5)
    A = -jnp.abs(jnp.asarray(RNG.uniform(0.5, 2.0, H), jnp.float32))
    D = jnp.zeros((H,), jnp.float32)
    y_k, h_k = mamba2_ssd(x, dt, B, C, A, D, chunk=32)
    y_m, h_m = ssd_chunked(x, dt, A, B, C, chunk=32)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_m), atol=1e-4,
                               rtol=1e-3)
    np.testing.assert_allclose(np.asarray(h_k), np.asarray(h_m), atol=1e-4,
                               rtol=1e-3)


# ------------------------------------------------------------------ #
# fused rmsnorm
# ------------------------------------------------------------------ #

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape,br", [((512, 1024), 128), ((3, 7, 256), 64),
                                      ((100, 896), 256)])
def test_rmsnorm_matches_ref(dtype, shape, br):
    x = randn(shape, dtype)
    scale = randn(shape[-1:], jnp.float32)
    out = rmsnorm_fused(x, scale, block_rows=br)
    ref = rmsnorm_ref(x, scale)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **TOL[dtype])


def test_rmsnorm_property():
    from repro.testing import property_testing
    given, settings, st = property_testing()

    @settings(max_examples=15, deadline=None)
    @given(r=st.integers(1, 64), d=st.sampled_from([8, 64, 256]),
           seed=st.integers(0, 999))
    def inner(r, d, seed):
        rg = np.random.default_rng(seed)
        x = jnp.asarray(rg.standard_normal((r, d)), jnp.float32)
        scale = jnp.asarray(rg.standard_normal((d,)), jnp.float32)
        out = rmsnorm_fused(x, scale, block_rows=16)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(rmsnorm_ref(x, scale)),
                                   atol=1e-5, rtol=1e-5)

    inner()


# ------------------------------------------------------------------ #
# MoE grouped matmul
# ------------------------------------------------------------------ #

from repro.kernels.moe_gmm.ops import moe_gmm
from repro.kernels.moe_gmm.ref import moe_gmm_ref


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("e,c,d,f,bc,bf", [
    (4, 64, 32, 48, 32, 16),
    (2, 100, 16, 64, 64, 64),   # ragged C (padding path)
    (8, 32, 64, 30, 16, 16),    # ragged F
])
def test_moe_gmm_matches_ref(dtype, e, c, d, f, bc, bf):
    x = randn((e, c, d), dtype, 0.5)
    w = randn((e, d, f), dtype, 0.5)
    nv = jnp.asarray(RNG.integers(1, c + 1, e), jnp.int32)
    out = moe_gmm(x, w, nv, bc=bc, bf=bf)
    ref = moe_gmm_ref(x, w, nv)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **TOL[dtype])


def test_moe_gmm_matches_moe_ffn_expert_compute():
    """Kernel == the einsum inside models.moe (same contraction)."""
    e, c, d, f = 4, 16, 24, 32
    x = randn((e, c, d))
    w = randn((e, d, f))
    out = moe_gmm(x, w)
    ref = jnp.einsum("ecd,edf->ecf", x, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)
