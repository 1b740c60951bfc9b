"""Pallas kernel sweeps vs. pure-jnp oracles (interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import bbm_matmul, flash_attention, quant_matmul
from repro.kernels.ref import attention_ref, bbm_matmul_ref, quant_matmul_ref

RNG = np.random.default_rng(42)


# ------------------------------------------------------------- bbm_matmul
@pytest.mark.parametrize("wl,vbl,kind", [
    (8, 0, 0), (8, 5, 0), (8, 7, 1),
    (12, 0, 0), (12, 7, 0), (12, 11, 1), (12, 13, 0),
])
@pytest.mark.parametrize("shape", [(16, 32, 16), (48, 96, 80), (33, 65, 17)])
def test_bbm_matmul_matches_ref(wl, vbl, kind, shape):
    m, k, n = shape
    x = jnp.asarray(RNG.integers(0, 1 << wl, (m, k)), jnp.int32)
    w = jnp.asarray(RNG.integers(0, 1 << wl, (k, n)), jnp.int32)
    got = bbm_matmul(x, w, wl=wl, vbl=vbl, kind=kind, bm=16, bk=32, bn=16)
    ref = bbm_matmul_ref(x, w, wl=wl, vbl=vbl, kind=kind)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_bbm_matmul_shift_semantics():
    wl = 16
    x = jnp.asarray(RNG.integers(0, 1 << wl, (8, 64)), jnp.int32)
    w = jnp.asarray(RNG.integers(0, 1 << wl, (64, 8)), jnp.int32)
    got = bbm_matmul(x, w, wl=wl, vbl=13, shift=15, bm=8, bk=32, bn=8)
    ref = bbm_matmul_ref(x, w, wl=wl, vbl=13, shift=15)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_bbm_matmul_overflow_guard():
    x = jnp.zeros((4, 4096), jnp.int32)
    w = jnp.zeros((4096, 4), jnp.int32)
    with pytest.raises(ValueError, match="overflow"):
        bbm_matmul(x, w, wl=16, vbl=13)


def test_bbm_matmul_exactness_at_vbl0():
    """VBL=0 -> kernel computes the exact integer matmul."""
    wl = 10
    x = RNG.integers(0, 1 << wl, (24, 48)).astype(np.int32)
    w = RNG.integers(0, 1 << wl, (48, 24)).astype(np.int32)
    got = bbm_matmul(jnp.asarray(x), jnp.asarray(w), wl=wl, vbl=0,
                     bm=8, bk=16, bn=8)
    sx = np.where(x >= 1 << (wl - 1), x - (1 << wl), x).astype(np.int64)
    sw = np.where(w >= 1 << (wl - 1), w - (1 << wl), w).astype(np.int64)
    np.testing.assert_array_equal(np.asarray(got, np.int64), sx @ sw)


# ------------------------------------------------------------ quant_matmul
@pytest.mark.parametrize("shape", [(32, 128, 32), (64, 256, 48), (16, 64, 16),
                                   (32, 96, 32)])
def test_quant_matmul_noiseless_exact(shape):
    """With sums inside f32's exact-int range the kernel == oracle bitwise,
    K tail included (96 is not a multiple of the 64-wide K block)."""
    m, k, n = shape
    x = jnp.asarray(RNG.standard_normal((m, k)), jnp.float32)
    w = jnp.asarray(RNG.standard_normal((k, n)), jnp.float32)
    s = 0.05   # codes ~ +-60 -> |sum| < 2^24
    got = quant_matmul(x, w, s, s, 0.0, 0.0, bm=16, bk=64, bn=16)
    ref = quant_matmul_ref(x, w, s, s, 0.0, 0.0)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_quant_matmul_large_scale_close():
    x = jnp.asarray(RNG.standard_normal((64, 512)), jnp.float32)
    w = jnp.asarray(RNG.standard_normal((512, 64)), jnp.float32)
    got = quant_matmul(x, w, 1e-3, 1e-3, 0.0, 0.0, bm=32, bk=128, bn=32)
    ref = quant_matmul_ref(x, w, 1e-3, 1e-3, 0.0, 0.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-2)


def test_quant_matmul_noise_moments():
    """Injected noise must match the calibrated moments (paper §II.B)."""
    m, k, n = 128, 256, 128
    x = jnp.asarray(RNG.standard_normal((m, k)), jnp.float32)
    w = jnp.asarray(RNG.standard_normal((k, n)), jnp.float32)
    s, mu, sigma = 0.05, -3.5, 12.0
    base = quant_matmul(x, w, s, s, 0.0, 0.0, bm=32, bk=64, bn=32)
    noisy = quant_matmul(x, w, s, s, mu, sigma, seed=3, bm=32, bk=64, bn=32)
    eps = (np.asarray(noisy) - np.asarray(base)) / (s * s)
    assert eps.mean() == pytest.approx(mu * k, rel=0.05)
    assert eps.std() == pytest.approx(sigma * np.sqrt(k), rel=0.05)


def test_quant_matmul_noise_deterministic_and_seeded():
    x = jnp.asarray(RNG.standard_normal((32, 64)), jnp.float32)
    w = jnp.asarray(RNG.standard_normal((64, 32)), jnp.float32)
    a = quant_matmul(x, w, 0.05, 0.05, -1.0, 5.0, seed=1, bm=16, bk=32, bn=16)
    b = quant_matmul(x, w, 0.05, 0.05, -1.0, 5.0, seed=1, bm=16, bk=32, bn=16)
    c = quant_matmul(x, w, 0.05, 0.05, -1.0, 5.0, seed=2, bm=16, bk=32, bn=16)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not np.array_equal(np.asarray(a), np.asarray(c))


# --------------------------------------------------------- flash attention
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(2, 4, 128, 64), (1, 2, 160, 32),
                                   (1, 1, 96, 128)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_ref(dtype, shape, causal):
    b, h, s, d = shape
    q = jnp.asarray(RNG.standard_normal(shape), dtype)
    k = jnp.asarray(RNG.standard_normal(shape), dtype)
    v = jnp.asarray(RNG.standard_normal(shape), dtype)
    got = flash_attention(q, k, v, causal=causal, bq=64, bk=64)
    ref = attention_ref(q, k, v, causal=causal)
    atol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32), atol=atol)


def test_flash_attention_cross_lengths():
    """Decode-like shape: few queries against a long KV."""
    q = jnp.asarray(RNG.standard_normal((1, 2, 32, 64)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((1, 2, 256, 64)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((1, 2, 256, 64)), jnp.float32)
    got = flash_attention(q, k, v, causal=False, bq=32, bk=64)
    ref = attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5)


# ---------------------------------------------------------------- fir kernel
@pytest.mark.parametrize("wl,vbl,kind", [(10, 0, 0), (12, 9, 0), (12, 7, 1)])
@pytest.mark.parametrize("n,block", [(500, 128), (1024, 256)])
def test_fir_bbm_matches_per_tap_reference(wl, vbl, kind, n, block):
    from repro.core.bbm import bbm_type0, bbm_type1
    from repro.kernels.fir_kernel import fir_bbm
    taps = 31
    x = jnp.asarray(RNG.integers(0, 1 << wl, n), jnp.int32)
    h = jnp.asarray(RNG.integers(0, 1 << wl, taps), jnp.int32)
    got = np.asarray(fir_bbm(x, h, wl=wl, vbl=vbl, kind=kind, block=block,
                             interpret=True), np.int64)
    fn = bbm_type0 if kind == 0 else bbm_type1
    xp = np.concatenate([np.zeros(taps - 1, np.int32), np.asarray(x)])
    ref = np.zeros(n, np.int64)
    for t in range(taps):
        ref += np.asarray(fn(jnp.asarray(xp[taps - 1 - t:taps - 1 - t + n]),
                             h[t], wl, vbl), np.int64)
    np.testing.assert_array_equal(got, ref)


def test_fir_bbm_overflow_guard():
    from repro.kernels.fir_kernel import fir_bbm
    x = jnp.zeros(64, jnp.int32)
    h = jnp.zeros(64, jnp.int32)
    with pytest.raises(ValueError, match="overflow"):
        fir_bbm(x, h, wl=16, vbl=13)
