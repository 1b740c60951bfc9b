"""Batched multi-channel FIR filterbank subsystem tests.

Property sweeps promised by the subsystem: the Pallas filterbank kernel
(interpret mode) is bit-for-bit equal to the host fixed-point datapath for
>= 4 channels x 2 tap banks across wl in {8, 12, 16}, both BBM kinds and a
vbl spread; ``bbm_matmul`` equals the closed-form ``bbm_mul`` accumulation;
and the int32 overflow envelope rejects unsafe taps x wl combinations.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.bbm import bbm_mul
from repro.core.multipliers import MulSpec
from repro.dsp import design_lowpass, fir_apply, fir_apply_fixed
from repro.kernels import bbm_matmul, fir_bbm, fir_bbm_bank, min_safe_shift
from repro.kernels.ref import fir_bank_ref
from repro.launch.mesh import make_mesh

RNG = np.random.default_rng(7)

# (wl, vbl) sweep points; kind 0/1 covers bbm0/bbm1
SWEEP = [(8, 0), (8, 5), (12, 7), (12, 11), (16, 13), (16, 15)]


def _bank_case(channels, n, taps, wl):
    x = jnp.asarray(RNG.integers(0, 1 << wl, (channels, n)), jnp.int32)
    h = jnp.asarray(RNG.integers(0, 1 << wl, (channels, taps)), jnp.int32)
    return x, h


# ------------------------------------------------------------- kernel level
@pytest.mark.parametrize("wl,vbl", SWEEP)
@pytest.mark.parametrize("kind", [0, 1])
def test_fir_bank_kernel_matches_closed_form(wl, vbl, kind):
    """(C, N) kernel vs the pure-jnp closed-form oracle, bit for bit."""
    channels, n, taps = 5, 700, 31
    shift = min_safe_shift(taps, wl)
    x, h = _bank_case(channels, n, taps, wl)
    got = fir_bbm_bank(x, h, wl=wl, vbl=vbl, kind=kind, shift=shift,
                       bc=2, bt=128, interpret=True, form="rows")
    ref = fir_bank_ref(x, h, wl=wl, vbl=vbl, kind=kind, shift=shift)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_fir_bank_halo_streams_across_many_blocks():
    """Small time blocks force many halo hand-offs; result is unchanged."""
    wl, vbl, kind, taps = 12, 9, 1, 31
    x, h = _bank_case(3, 1024, taps, wl)
    ref = np.asarray(fir_bbm_bank(x, h, wl=wl, vbl=vbl, kind=kind,
                                  bc=3, bt=1024, interpret=True,
                                  form="rows"))
    for bt in (64, 128, 256):
        got = np.asarray(fir_bbm_bank(x, h, wl=wl, vbl=vbl, kind=kind,
                                      bc=2, bt=bt, interpret=True,
                                      form="rows"))
        np.testing.assert_array_equal(got, ref, err_msg=f"bt={bt}")


def test_fir_bank_shared_taps_broadcast():
    wl, taps = 10, 31
    x, _ = _bank_case(4, 300, taps, wl)
    h1 = jnp.asarray(RNG.integers(0, 1 << wl, taps), jnp.int32)
    got = fir_bbm_bank(x, h1, wl=wl, vbl=5, interpret=True,
                       form="rows")
    ref = fir_bank_ref(x, jnp.broadcast_to(h1, (4, taps)), wl=wl, vbl=5)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_fir_bbm_1d_wrapper_matches_bank():
    wl, vbl, kind = 12, 7, 0
    x = jnp.asarray(RNG.integers(0, 1 << wl, 500), jnp.int32)
    h = jnp.asarray(RNG.integers(0, 1 << wl, 31), jnp.int32)
    got = fir_bbm(x, h, wl=wl, vbl=vbl, kind=kind, block=128,
                  interpret=True, form="rows")
    ref = fir_bank_ref(x[None], h[None], wl=wl, vbl=vbl, kind=kind)[0]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


# --------------------------------------------------- kernel vs host datapath
@pytest.mark.parametrize("wl,vbl", SWEEP)
@pytest.mark.parametrize("name", ["bbm0", "bbm1"])
def test_filterbank_backends_bit_exact(wl, vbl, name):
    """fir_apply host vs pallas-interpret: equal floats, 4 ch x 2 banks."""
    spec = MulSpec(name, wl, vbl)
    x = RNG.standard_normal((4, 600))
    banks = np.stack([design_lowpass(), design_lowpass(stop_weight=0.5)])
    h = banks[[0, 1, 0, 1]]
    a = fir_apply(x, h, spec, backend="host")
    b = fir_apply(x, h, spec, backend="pallas-interpret", block=128, bc=2)
    np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("wl", [8, 12])
def test_fir_bbm_matches_fir_apply_fixed(wl):
    """The interpreted kernel reproduces the original host path exactly.

    ``fir_apply_fixed`` is the seed's shift=0 single-channel entry point;
    wl <= 12 keeps 31 taps inside the int32 envelope without a shift.
    """
    for name, vbl in (("bbm0", 5), ("bbm1", 7), ("booth", 0)):
        spec = MulSpec(name, wl, vbl)
        x = RNG.standard_normal(777)
        h = design_lowpass()
        host = fir_apply_fixed(x, h, spec)
        kern = fir_apply(x, h, spec, backend="pallas-interpret", shift=0,
                         block=256)
        np.testing.assert_array_equal(host, kern)


# ------------------------------------------------- bbm_matmul vs closed form
@pytest.mark.parametrize("wl,vbl", SWEEP)
@pytest.mark.parametrize("kind", [0, 1])
def test_bbm_matmul_matches_bbm_mul(wl, vbl, kind):
    """Kernel matmul == per-element closed-form bbm_mul, then sum over K."""
    m, k, n = 8, 32, 8
    shift = min_safe_shift(k, wl)
    x = jnp.asarray(RNG.integers(0, 1 << wl, (m, k)), jnp.int32)
    w = jnp.asarray(RNG.integers(0, 1 << wl, (k, n)), jnp.int32)
    got = np.asarray(bbm_matmul(x, w, wl=wl, vbl=vbl, kind=kind, shift=shift,
                                bm=8, bk=16, bn=8, interpret=True,
                                form="rows"), np.int64)
    prod = np.asarray(bbm_mul(x[:, :, None], w[None, :, :], wl, vbl,
                              kind=kind), np.int64)
    ref = np.sum(prod >> shift, axis=1)
    np.testing.assert_array_equal(got, ref)


# --------------------------------------------------------- overflow envelope
@pytest.mark.parametrize("taps,wl,shift,ok", [
    (31, 12, 0, True),       # paper workload, no rescale needed
    (31, 16, 0, False),      # paper workload at wl=16 needs shift >= 5
    (31, 16, 5, True),
    (64, 16, 6, False),      # longer bank: 64 * 2^(31-6) == 2^31 exactly
    (64, 16, 7, True),
    (4096, 16, 0, False),
])
def test_overflow_envelope_guard(taps, wl, shift, ok):
    x = jnp.zeros((2, 64), jnp.int32)
    h = jnp.zeros((2, taps), jnp.int32)
    if ok:
        fir_bbm_bank(x, h, wl=wl, vbl=0, shift=shift, bt=64, interpret=True)
    else:
        with pytest.raises(ValueError, match="overflow"):
            fir_bbm_bank(x, h, wl=wl, vbl=0, shift=shift, bt=64,
                         interpret=True)
        assert min_safe_shift(taps, wl) > shift


def test_min_safe_shift_is_minimal():
    for taps, wl in ((31, 8), (31, 12), (31, 16), (64, 16), (1024, 16)):
        s = min_safe_shift(taps, wl)
        assert taps * (2 ** max(2 * wl - 1 - s, 0)) < 2 ** 31
        if s:
            assert taps * (2 ** max(2 * wl - 1 - (s - 1), 0)) >= 2 ** 31


# ------------------------------------------------------------ sharded + serve
def test_sharded_filterbank_single_device_mesh():
    from repro.parallel import sharded_filterbank
    wl, vbl, kind = 12, 9, 0
    mesh = make_mesh((1,), ("data",))
    x, h = _bank_case(4, 256, 31, wl)
    got = sharded_filterbank(x, h, mesh, wl=wl, vbl=vbl, kind=kind)
    ref = fir_bank_ref(x, h, wl=wl, vbl=vbl, kind=kind)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    # the interpreted kernel path agrees with the closed-form path
    got_k = sharded_filterbank(x, h, mesh, wl=wl, vbl=vbl, kind=kind,
                               use_kernel=True, bt=128)
    np.testing.assert_array_equal(np.asarray(got_k), np.asarray(ref))


def test_filterbank_engine_batches_requests():
    from repro.serve import FilterbankEngine
    banks = np.stack([design_lowpass(), design_lowpass(stop_weight=0.5)])
    spec = MulSpec("bbm0", 16, 13)
    eng = FilterbankEngine(banks, spec, backend="host", max_channels=3)
    sigs = [RNG.standard_normal(n) for n in (400, 250, 400, 320)]
    rids = [eng.submit(s, bank=i % 2) for i, s in enumerate(sigs)]
    out = eng.flush()
    assert sorted(out) == sorted(rids)
    assert not eng._pending
    # serving determinism: the quantization scale is per channel, so the
    # same signal served alone produces bit-identical output to the one it
    # got riding in a zero-padded batch of 3
    solo = FilterbankEngine(banks, spec, backend="host")
    rid = solo.submit(sigs[1], bank=1)
    lone = solo.flush()[rid]
    np.testing.assert_array_equal(out[rids[1]], lone)
    # engine output == direct batched fir_apply on the padded batch
    x = np.zeros((3, 400))
    for c, s in enumerate(sigs[:3]):
        x[c, : len(s)] = s
    direct = fir_apply(x, banks[[0, 1, 0]], spec, backend="host")
    np.testing.assert_array_equal(out[rids[0]], direct[0, :400])
    np.testing.assert_array_equal(out[rids[1]], direct[1, :250])


def test_filterbank_engine_rejects_unknown_bank():
    from repro.serve import FilterbankEngine
    eng = FilterbankEngine(design_lowpass(), MulSpec("bbm0", 16, 13))
    with pytest.raises(ValueError, match="bank"):
        eng.submit(np.zeros(16), bank=2)
