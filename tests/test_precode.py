"""Precoded Booth-digit datapath tests.

The decode/accumulate split promises: ``booth_precode`` + the multiply-free
``bbm_rows_product_precoded`` are bit-for-bit equal to the closed forms in
``core.bbm`` and to the raw-code row loop; the precoded FIR and matmul
kernels equal their raw-code wrappers across wl x vbl x kind; a
``PrecodedBank`` behaves exactly like raw taps through ``fir_apply``; and
``FilterbankEngine`` decodes its banks exactly once, at construction,
reusing the planes across flush rounds.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.bbm import bbm_mul
from repro.core.booth import booth_digits
from repro.core.multipliers import MulSpec
from repro.dsp import PrecodedBank, design_lowpass, fir_apply
from repro.kernels import (bbm_matmul, bbm_matmul_precoded, booth_precode,
                           fir_bbm_bank, fir_bbm_bank_precoded,
                           min_safe_shift)
from repro.kernels.booth_rows import (bbm_rows_product,
                                      bbm_rows_product_precoded,
                                      bbm_rows_product_scaled,
                                      split_signed)
from repro.launch.mesh import make_mesh

RNG = np.random.default_rng(11)

# (wl, vbl) sweep points; kind 0/1 covers bbm0/bbm1
SWEEP = [(8, 0), (8, 5), (12, 7), (12, 11), (16, 13), (16, 15)]


def test_precode_planes_match_booth_digits():
    """Exhaustive wl=8: (mag, neg) planes == |d|, neg of ``booth_digits``."""
    wl = 8
    b = jnp.arange(1 << wl, dtype=jnp.int32)
    mag, neg = booth_precode(b, wl)
    assert mag.shape == (wl // 2, 1 << wl)
    d, hw_neg = booth_digits(b, wl)          # row axis last
    np.testing.assert_array_equal(np.asarray(mag), np.abs(np.asarray(d)).T)
    np.testing.assert_array_equal(np.asarray(neg), np.asarray(hw_neg).T)


# ------------------------------------------------------------ row-loop level
@pytest.mark.parametrize("wl,vbl", SWEEP)
@pytest.mark.parametrize("kind", [0, 1])
def test_precoded_rows_match_bbm_mul(wl, vbl, kind):
    """Both accumulate forms == closed-form bbm_mul, bit for bit.

    ``multiply_free=True`` is the silicon/TPU select form, ``False`` the
    one-multiply-per-row form XLA prefers on CPU — same planes, same bits.
    """
    a = jnp.asarray(RNG.integers(0, 1 << wl, 4096), jnp.int32)
    b = jnp.asarray(RNG.integers(0, 1 << wl, 4096), jnp.int32)
    _, a_s = split_signed(a, wl)
    mag, neg = booth_precode(b, wl)
    ref = bbm_mul(a, b, wl, vbl, kind=kind)
    for multiply_free in (True, False):
        got = bbm_rows_product_precoded(a_s, mag, neg, wl=wl, vbl=vbl,
                                        kind=kind,
                                        multiply_free=multiply_free)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref),
                                      err_msg=f"multiply_free={multiply_free}")
    # the raw-code wrapper is decode + accumulate and must agree too
    raw = bbm_rows_product(a_s, b & ((1 << wl) - 1), wl=wl, vbl=vbl,
                           kind=kind)
    np.testing.assert_array_equal(np.asarray(raw), np.asarray(ref))


@pytest.mark.parametrize("wl,vbl", SWEEP)
@pytest.mark.parametrize("kind", [0, 1])
def test_scaled_rows_are_products_over_2_vbl(wl, vbl, kind):
    """Every Broken-Booth product is a multiple of 2^vbl, and the scaled
    row form gives the quotient exactly, in both row-contribution forms
    (the value product's rows form sums these in int32)."""
    a = jnp.asarray(RNG.integers(0, 1 << wl, 4096), jnp.int32)
    b = jnp.asarray(RNG.integers(0, 1 << wl, 4096), jnp.int32)
    _, a_s = split_signed(a, wl)
    mag, neg = booth_precode(b, wl)
    ref = np.asarray(bbm_mul(a, b, wl, vbl, kind=kind)).astype(np.int64)
    assert not (ref % (1 << vbl)).any()
    for multiply_free in (True, False):
        got = bbm_rows_product_scaled(a_s, mag, neg, wl=wl, vbl=vbl,
                                      kind=kind, multiply_free=multiply_free)
        np.testing.assert_array_equal(np.asarray(got), ref >> vbl,
                                      err_msg=f"multiply_free={multiply_free}")


# --------------------------------------------------------------- kernel level
@pytest.mark.parametrize("wl,vbl", SWEEP)
@pytest.mark.parametrize("kind", [0, 1])
def test_fir_kernel_raw_vs_precoded(wl, vbl, kind):
    """Raw-code and precoded-planes kernel entry points are bit-identical."""
    channels, n, taps = 4, 512, 31
    shift = min_safe_shift(taps, wl)
    x = jnp.asarray(RNG.integers(0, 1 << wl, (channels, n)), jnp.int32)
    h = jnp.asarray(RNG.integers(0, 1 << wl, (channels, taps)), jnp.int32)
    raw = fir_bbm_bank(x, h, wl=wl, vbl=vbl, kind=kind, shift=shift,
                       bc=2, bt=128, interpret=True, form="rows")
    hmag, hneg = booth_precode(h, wl)
    pre = fir_bbm_bank_precoded(x, hmag, hneg, wl=wl, vbl=vbl, kind=kind,
                                shift=shift, bc=2, bt=128, interpret=True,
                                form="rows")
    np.testing.assert_array_equal(np.asarray(raw), np.asarray(pre))


@pytest.mark.parametrize("wl,vbl", [(8, 5), (12, 7), (16, 13)])
@pytest.mark.parametrize("kind", [0, 1])
def test_bbm_matmul_raw_vs_precoded(wl, vbl, kind):
    """Precoded matmul == raw wrapper == closed-form accumulation."""
    m, k, n = 8, 32, 8
    shift = min_safe_shift(k, wl)
    x = jnp.asarray(RNG.integers(0, 1 << wl, (m, k)), jnp.int32)
    w = jnp.asarray(RNG.integers(0, 1 << wl, (k, n)), jnp.int32)
    raw = bbm_matmul(x, w, wl=wl, vbl=vbl, kind=kind, shift=shift,
                     bm=8, bk=16, bn=8, interpret=True, form="rows")
    wmag, wneg = booth_precode(w, wl)
    pre = bbm_matmul_precoded(x, wmag, wneg, wl=wl, vbl=vbl, kind=kind,
                              shift=shift, bm=8, bk=16, bn=8, interpret=True,
                              form="rows")
    np.testing.assert_array_equal(np.asarray(raw), np.asarray(pre))
    prod = np.asarray(bbm_mul(x[:, :, None], w[None, :, :], wl, vbl,
                              kind=kind), np.int64)
    ref = np.sum(prod >> shift, axis=1)
    np.testing.assert_array_equal(np.asarray(pre, np.int64), ref)


def test_precoded_kernel_rejects_mismatched_planes():
    x = jnp.zeros((2, 64), jnp.int32)
    hmag, hneg = booth_precode(jnp.zeros((2, 5), jnp.int32), 12)
    with pytest.raises(ValueError, match="plane shapes differ"):
        fir_bbm_bank_precoded(x, hmag, hneg[:1], wl=12, vbl=0,
                              interpret=True)
    with pytest.raises(ValueError, match="wl"):
        fir_bbm_bank_precoded(x, hmag, hneg, wl=8, vbl=0, interpret=True)


# ------------------------------------------------------------------ dsp level
@pytest.mark.parametrize("backend", ["host", "pallas-interpret"])
def test_fir_apply_precoded_bank_matches_raw_taps(backend):
    """fir_apply(x, PrecodedBank) == fir_apply(x, raw taps), both backends."""
    spec = MulSpec("bbm0", 16, 13)
    x = RNG.standard_normal((4, 500))
    banks = np.stack([design_lowpass(), design_lowpass(stop_weight=0.5)])
    idx = [0, 1, 1, 0]
    raw = fir_apply(x, banks[idx], spec, backend=backend, block=128, bc=2)
    bank = PrecodedBank(banks, spec).take(idx)
    pre = fir_apply(x, bank, backend=backend, block=128, bc=2)
    np.testing.assert_array_equal(raw, pre)
    # spec, when passed alongside a bank, must agree with the bank's
    np.testing.assert_array_equal(
        pre, fir_apply(x, bank, spec, backend=backend, block=128, bc=2))
    with pytest.raises(ValueError, match="match"):
        fir_apply(x, bank, MulSpec("bbm0", 16, 11), backend=backend)


def test_precoded_bank_take_is_a_view_not_a_redecode(monkeypatch):
    import repro.dsp.fir as fir_mod
    spec = MulSpec("bbm0", 12, 7)
    banks = np.stack([design_lowpass(), design_lowpass(stop_weight=0.5)])
    bank = PrecodedBank(banks, spec)
    calls = []
    monkeypatch.setattr(fir_mod, "booth_precode",
                        lambda *a, **k: calls.append(1))
    taken = bank.take([1, 0, 1])
    assert not calls                     # gather only, never re-decode
    assert taken.num_banks == 3 and taken.taps == bank.taps
    np.testing.assert_array_equal(taken.hq, bank.hq[[1, 0, 1]])
    np.testing.assert_array_equal(np.asarray(taken.planes[0]),
                                  np.asarray(bank.planes[0])[:, [1, 0, 1]])


def test_sharded_filterbank_precoded_planes_path():
    from repro.parallel import precode_filterbank, sharded_filterbank
    from repro.kernels.ref import fir_bank_ref
    wl, vbl, kind = 12, 9, 1
    mesh = make_mesh((1,), ("data",))
    x = jnp.asarray(RNG.integers(0, 1 << wl, (4, 256)), jnp.int32)
    h = jnp.asarray(RNG.integers(0, 1 << wl, (4, 31)), jnp.int32)
    ref = fir_bank_ref(x, h, wl=wl, vbl=vbl, kind=kind)
    planes = precode_filterbank(h, wl=wl)
    got = sharded_filterbank(x, h, mesh, wl=wl, vbl=vbl, kind=kind,
                             use_kernel=True, bt=128, h_planes=planes)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


# ---------------------------------------------------------------- serve level
def test_filterbank_engine_precodes_banks_once(monkeypatch):
    """The engine builds its PrecodedBank at construction and never decodes
    again across flush rounds; outputs match the direct datapath."""
    import repro.dsp.fir as fir_mod
    from repro.serve import FilterbankEngine
    real = fir_mod.booth_precode
    calls = []

    def counting(*a, **k):
        calls.append(1)
        return real(*a, **k)

    monkeypatch.setattr(fir_mod, "booth_precode", counting)
    banks = np.stack([design_lowpass(), design_lowpass(stop_weight=0.5)])
    spec = MulSpec("bbm0", 16, 13)
    eng = FilterbankEngine(banks, spec, backend="pallas-interpret",
                           max_channels=4, block=128)
    assert len(calls) == 1               # decode phase: once, at construction
    sigs = [RNG.standard_normal(n) for n in (300, 200, 300)]
    rids = [eng.submit(s, bank=i % 2) for i, s in enumerate(sigs)]
    out1 = eng.flush()
    rids2 = [eng.submit(s, bank=1) for s in sigs[:2]]
    out2 = eng.flush()
    assert len(calls) == 1               # two flush rounds, zero re-decodes
    assert sorted(out1) == sorted(rids) and sorted(out2) == sorted(rids2)
    # the cached-bank results equal the one-shot datapath, bit for bit
    solo = fir_apply(sigs[1], banks[1], spec, backend="pallas-interpret",
                     block=128)
    np.testing.assert_array_equal(out1[rids[1]], solo)
