"""Exact-dot + low-bit-correction lowering tests.

The dot form's contract is one algebraic identity and three layers of
bit-exact plumbing on top of it:

  * identity — ``bbm_mul(a, b) == a_s*b_s - booth_correction(a, planes)``
    for every wl x vbl x kind, including vbl = 0 (zero correction), the
    Type-1 "negative zero" 111 triplet, and the extreme operands at
    +/-2^(wl-1).  Checked exhaustively at wl = 8, on targeted edge grids
    at wl = 12/16, and property-based via hypothesis.
  * kernels — ``form="dot"`` is bit-identical to ``form="rows"`` (and to
    the pure-jnp oracles) for the FIR filterbank and the matmul, across
    the sweep, shifts included.
  * envelope — the dot form accumulates exact products before subtracting
    the correction, so its int32 analysis is re-derived
    (``dotform_scaled_bound``): every BBM product is divisible by
    ``2^vbl``, and accumulating at that scale keeps the dot form inside
    the rows-form envelope for *every* vbl — including contraction sizes
    the rows envelope admits only barely.
  * dsp / serve / parallel — ``fir_apply(form=...)``, the engine and the
    sharded filterbank pick the dot form automatically and stay
    bit-identical to the rows datapath.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.bbm import bbm_mul
from repro.core.booth import to_signed
from repro.kernels import (bbm_matmul_precoded, bbm_rows_product_dotform,
                           booth_correction, booth_high_value, booth_precode,
                           booth_value, dotform_scaled_bound,
                           fir_bbm_bank_precoded, min_safe_shift,
                           resolve_form)
from repro.kernels.booth_rows import num_corr_rows, split_signed
from repro.kernels.ref import bbm_matmul_ref, fir_bank_ref
from repro.launch.mesh import make_mesh

RNG = np.random.default_rng(23)

# (wl, vbl) sweep points; kind 0/1 covers bbm0/bbm1
SWEEP = [(8, 0), (8, 5), (12, 7), (12, 11), (16, 13), (16, 15)]


def _identity_check(a, b, wl, vbl, kind):
    """bbm_mul == exact product minus correction, elementwise."""
    _, a_s = split_signed(a, wl)
    mag, neg = booth_precode(b, wl)
    ref = np.asarray(bbm_mul(a, b, wl, vbl, kind=kind), np.int64)
    exact = np.asarray(a_s, np.int64) * np.asarray(to_signed(b, wl), np.int64)
    corr = np.asarray(booth_correction(a_s, mag, neg, wl=wl, vbl=vbl,
                                       kind=kind), np.int64)
    np.testing.assert_array_equal(ref, exact - corr)
    # correction is nonnegative and narrow: bounded by R * 2^vbl per row sum
    assert corr.min() >= 0
    assert corr.max() <= num_corr_rows(wl, vbl) * (1 << vbl)
    # and the packaged third form agrees too
    got = np.asarray(bbm_rows_product_dotform(a_s, mag, neg, wl=wl, vbl=vbl,
                                              kind=kind), np.int64)
    np.testing.assert_array_equal(ref, got)


# ------------------------------------------------------------- the identity
@pytest.mark.parametrize("vbl", [0, 1, 5, 7])
@pytest.mark.parametrize("kind", [0, 1])
def test_identity_exhaustive_wl8(vbl, kind):
    """All 2^16 operand pairs at wl = 8: the identity has no exceptions."""
    wl = 8
    codes = jnp.arange(1 << wl, dtype=jnp.int32)
    a, b = jnp.meshgrid(codes, codes)
    _identity_check(a.ravel(), b.ravel(), wl, vbl, kind)


@pytest.mark.parametrize("wl,vbl", [(12, 7), (12, 11), (16, 13), (16, 15)])
@pytest.mark.parametrize("kind", [0, 1])
def test_identity_edge_operands(wl, vbl, kind):
    """Extremes (+/-2^(wl-1)), zero, and all-ones / 111-triplet patterns.

    The code ``1 << (wl - 1)`` is the most negative operand -2^(wl-1);
    ``(1 << wl) - 1`` is -1, whose Booth digits are all 111 "negative
    zero" triplets (mag 0, neg 1) — the row Type1 truncation exposes.
    """
    top = 1 << (wl - 1)
    edges = [0, 1, 2, top - 1, top, top + 1, (1 << wl) - 1,
             0b111 << (wl - 4), (1 << wl) - 2, top >> 1]
    rnd = RNG.integers(0, 1 << wl, 32).tolist()
    codes = jnp.asarray(sorted(set(edges + rnd)), jnp.int32)
    a, b = jnp.meshgrid(codes, codes)
    _identity_check(a.ravel(), b.ravel(), wl, vbl, kind)


@pytest.mark.parametrize("wl,vbl", SWEEP)
@pytest.mark.parametrize("kind", [0, 1])
@settings(deadline=None, max_examples=50)
@given(a=st.integers(0, (1 << 16) - 1), b=st.integers(0, (1 << 16) - 1))
def test_identity_property(wl, vbl, kind, a, b):
    """Hypothesis sweep: bbm_mul(a, b) == a*b - correction(a_low, digits)."""
    a = jnp.asarray([a & ((1 << wl) - 1)], jnp.int32)
    b = jnp.asarray([b & ((1 << wl) - 1)], jnp.int32)
    _identity_check(a, b, wl, vbl, kind)


def test_vbl0_correction_is_zero():
    """vbl = 0: no break line, the dot form is a pure exact contraction."""
    wl = 12
    a = jnp.asarray(RNG.integers(0, 1 << wl, 512), jnp.int32)
    b = jnp.asarray(RNG.integers(0, 1 << wl, 512), jnp.int32)
    _, a_s = split_signed(a, wl)
    mag, neg = booth_precode(b, wl)
    for kind in (0, 1):
        corr = np.asarray(booth_correction(a_s, mag, neg, wl=wl, vbl=0,
                                           kind=kind))
        assert not corr.any()
    np.testing.assert_array_equal(
        np.asarray(booth_value(mag, neg, wl=wl)), np.asarray(to_signed(b, wl)))
    # with no break line every digit row "survives": bq is the multiplier
    np.testing.assert_array_equal(
        np.asarray(booth_high_value(mag, neg, wl=wl, vbl=0)),
        np.asarray(to_signed(b, wl)))


# ------------------------------------------------------------- kernel level
@pytest.mark.parametrize("wl,vbl", SWEEP)
@pytest.mark.parametrize("kind", [0, 1])
def test_fir_kernel_dot_vs_rows(wl, vbl, kind):
    """form="dot" == form="rows" == oracle for the FIR filterbank."""
    channels, n, taps = 4, 384, 31
    shift = min_safe_shift(taps, wl)
    x = jnp.asarray(RNG.integers(0, 1 << wl, (channels, n)), jnp.int32)
    h = jnp.asarray(RNG.integers(0, 1 << wl, (channels, taps)), jnp.int32)
    hmag, hneg = booth_precode(h, wl)
    ref = fir_bank_ref(x, h, wl=wl, vbl=vbl, kind=kind, shift=shift)
    dot = fir_bbm_bank_precoded(x, hmag, hneg, wl=wl, vbl=vbl, kind=kind,
                                shift=shift, form="dot")
    rows = fir_bbm_bank_precoded(x, hmag, hneg, wl=wl, vbl=vbl, kind=kind,
                                 shift=shift, bc=2, bt=128, interpret=True,
                                 form="rows")
    np.testing.assert_array_equal(np.asarray(dot), np.asarray(ref))
    np.testing.assert_array_equal(np.asarray(rows), np.asarray(ref))
    # the accelerator contraction layout (windowed dot_general / im2col)
    # must agree too — `windowed=True` forces it on CPU so the branch that
    # actually runs on TPU is exercised by this CI
    win = fir_bbm_bank_precoded(x, hmag, hneg, wl=wl, vbl=vbl, kind=kind,
                                shift=shift, form="dot", windowed=True)
    np.testing.assert_array_equal(np.asarray(win), np.asarray(ref))
    # auto (form=None) must resolve to one of the two, never a third thing
    auto = fir_bbm_bank_precoded(x, hmag, hneg, wl=wl, vbl=vbl, kind=kind,
                                 shift=shift, bt=128, interpret=True)
    np.testing.assert_array_equal(np.asarray(auto), np.asarray(ref))


@pytest.mark.parametrize("wl,vbl", [(8, 5), (12, 7), (16, 13), (16, 15)])
@pytest.mark.parametrize("kind", [0, 1])
def test_matmul_dot_vs_rows(wl, vbl, kind):
    """x @ w - correction == the rows kernel == closed-form accumulation."""
    m, k, n = 8, 32, 8
    shift = min_safe_shift(k, wl)
    x = jnp.asarray(RNG.integers(0, 1 << wl, (m, k)), jnp.int32)
    w = jnp.asarray(RNG.integers(0, 1 << wl, (k, n)), jnp.int32)
    wmag, wneg = booth_precode(w, wl)
    ref = bbm_matmul_ref(x, w, wl=wl, vbl=vbl, kind=kind, shift=shift)
    dot = bbm_matmul_precoded(x, wmag, wneg, wl=wl, vbl=vbl, kind=kind,
                              shift=shift, form="dot")
    rows = bbm_matmul_precoded(x, wmag, wneg, wl=wl, vbl=vbl, kind=kind,
                               shift=shift, bm=8, bk=16, bn=8,
                               interpret=True, form="rows")
    np.testing.assert_array_equal(np.asarray(dot), np.asarray(ref))
    np.testing.assert_array_equal(np.asarray(rows), np.asarray(ref))


@pytest.mark.parametrize("kind", [0, 1])
def test_residual_shift_with_truncated_rows(kind):
    """0 < vbl < shift: the per-product ``>> (shift - vbl)`` residual.

    The floor applies to each scaled product (truncated rows included)
    *before* the tap/K reduction — a sum-then-shift rewrite would pass
    every other sweep point (they all have vbl = 0 or vbl >= shift) but
    produce wrong bits here.
    """
    wl, vbl, shift = 16, 3, 6
    x = jnp.asarray(RNG.integers(0, 1 << wl, (3, 257)), jnp.int32)
    h = jnp.asarray(RNG.integers(0, 1 << wl, (3, 31)), jnp.int32)
    hmag, hneg = booth_precode(h, wl)
    ref = fir_bank_ref(x, h, wl=wl, vbl=vbl, kind=kind, shift=shift)
    dot = fir_bbm_bank_precoded(x, hmag, hneg, wl=wl, vbl=vbl, kind=kind,
                                shift=shift, form="dot")
    np.testing.assert_array_equal(np.asarray(dot), np.asarray(ref))
    m, k, n = 5, 32, 5
    xm = jnp.asarray(RNG.integers(0, 1 << wl, (m, k)), jnp.int32)
    w = jnp.asarray(RNG.integers(0, 1 << wl, (k, n)), jnp.int32)
    wmag, wneg = booth_precode(w, wl)
    refm = bbm_matmul_ref(xm, w, wl=wl, vbl=vbl, kind=kind, shift=shift)
    dotm = bbm_matmul_precoded(xm, wmag, wneg, wl=wl, vbl=vbl, kind=kind,
                               shift=shift, form="dot")
    np.testing.assert_array_equal(np.asarray(dotm), np.asarray(refm))


def test_fir_dot_shift_zero_and_unaligned_shapes():
    """No rescale (shift = 0) and odd C/N exercise the non-padded path."""
    wl, vbl, kind = 12, 9, 1
    x = jnp.asarray(RNG.integers(0, 1 << wl, (3, 333)), jnp.int32)
    h = jnp.asarray(RNG.integers(0, 1 << wl, (3, 31)), jnp.int32)
    hmag, hneg = booth_precode(h, wl)
    ref = fir_bank_ref(x, h, wl=wl, vbl=vbl, kind=kind)
    dot = fir_bbm_bank_precoded(x, hmag, hneg, wl=wl, vbl=vbl, kind=kind,
                                form="dot")
    np.testing.assert_array_equal(np.asarray(dot), np.asarray(ref))


# ----------------------------------------------------- re-derived envelope
def test_dotform_scaled_bound_never_looser_than_rows():
    """The re-derived analysis: scaled accumulation <= rows envelope.

    Naively, "accumulate exact products then subtract the correction"
    needs ``k * 2^(2wl-1)`` of headroom — hopeless in int32 at wl = 16.
    The folded form accumulates ``bbm / 2^max(vbl, shift)`` instead, and
    its worst case is never larger than the rows form's, for every vbl.
    """
    for k in (31, 64, 1024, 4096):
        for wl in (8, 12, 16):
            for shift in range(0, 14):
                rows_bound = k * 2 ** max(2 * wl - 1 - shift, 0)
                for vbl in range(0, 2 * wl - 6 if wl >= 14 else wl):
                    assert dotform_scaled_bound(k, wl, vbl, shift)                         <= rows_bound
    assert resolve_form(None) == "dot" == resolve_form("dot")
    assert resolve_form("rows") == "rows"
    with pytest.raises(ValueError, match="form"):
        resolve_form("mxu")


def test_dot_form_safe_at_rows_envelope_boundary():
    """Operating points the rows envelope barely admits stay bit-exact.

    taps=64/wl=16/shift=7 sits one power of two inside the int32 line
    (64 * 2^(31-7) == 2^30), with all-extreme operands (-2^15 codes)
    driving every product to its +2^30 maximum; the int64 numpy oracle
    confirms the scaled dot accumulation never wrapped.  K=4096 at
    shift=13 is a contraction the exact-product sum could never survive
    unscaled (4096 * 2^31 >> 2^31).
    """
    wl, taps, shift = 16, 64, 7
    top = jnp.int32(1 << (wl - 1))          # the -2^15 code
    x = jnp.full((2, 200), top, jnp.int32)
    h = jnp.full((2, taps), top, jnp.int32)
    hmag, hneg = booth_precode(h, wl)
    for vbl, kind in [(0, 0), (13, 0), (13, 1), (15, 1)]:
        dot = np.asarray(fir_bbm_bank_precoded(
            x, hmag, hneg, wl=wl, vbl=vbl, kind=kind, shift=shift,
            form="dot"), np.int64)
        prod = np.asarray(bbm_mul(
            _window_np(np.asarray(x), taps), np.asarray(h)[:, None, :],
            wl, vbl, kind=kind), np.int64)
        ref = np.sum(prod >> shift, axis=-1)
        np.testing.assert_array_equal(dot, ref, err_msg=f"vbl={vbl}")
    # huge-K matmul: rows envelope needs shift=13; the dot form holds too
    k = 4096
    xm = jnp.full((2, k), top, jnp.int32)
    w = jnp.full((k, 3), top, jnp.int32)
    wmag, wneg = booth_precode(w, wl)
    dot = np.asarray(bbm_matmul_precoded(xm, wmag, wneg, wl=wl, vbl=13,
                                         shift=13, form="dot"), np.int64)
    prod = np.asarray(bbm_mul(xm[:, :, None], w[None], wl, 13), np.int64)
    np.testing.assert_array_equal(dot, np.sum(prod >> 13, axis=1))


def _window_np(x, taps):
    """win[c, n, k] = x[c, n-k] with zero codes before the signal."""
    n = x.shape[-1]
    idx = np.arange(n)[:, None] - np.arange(taps)[None, :]
    return np.where(idx >= 0, x[..., np.clip(idx, 0, None)], 0)


# -------------------------------------------------------- dsp / serve level
def test_fir_apply_forms_bit_exact():
    scipy = pytest.importorskip("scipy")  # noqa: F841  (design_lowpass)
    from repro.core.multipliers import MulSpec
    from repro.dsp import design_lowpass, fir_apply
    x = RNG.standard_normal((4, 400))
    banks = np.stack([design_lowpass(), design_lowpass(stop_weight=0.5)])
    h = banks[[0, 1, 1, 0]]
    for name, wl, vbl in [("bbm0", 16, 13), ("bbm1", 16, 13),
                          ("bbm0", 12, 7), ("booth", 16, 0)]:
        spec = MulSpec(name, wl, vbl)
        ref = fir_apply(x, h, spec, backend="host", form="rows")
        for backend in ("host", "pallas-interpret"):
            for form in ("dot", None):
                got = fir_apply(x, h, spec, backend=backend, form=form,
                                block=128, bc=2)
                np.testing.assert_array_equal(ref, got,
                                              err_msg=f"{spec} {backend} "
                                                      f"{form}")


def test_fir_apply_rejects_dot_off_the_hot_path():
    scipy = pytest.importorskip("scipy")  # noqa: F841
    from repro.core.multipliers import MulSpec
    from repro.dsp import design_lowpass, fir_apply
    x = RNG.standard_normal(64)
    h = design_lowpass()
    with pytest.raises(ValueError, match="dot"):
        fir_apply(x, h, MulSpec("bam", 8, 2), backend="host", form="dot")
    with pytest.raises(ValueError, match="dot"):
        fir_apply(x, h, MulSpec("bbm0", 16, 13), backend="host",
                  datapath="wlbit", shift=0, form="dot")
    with pytest.raises(ValueError, match="form"):
        fir_apply(x, h, MulSpec("bbm0", 16, 13), form="mxu")


def test_engine_and_sharded_pick_dot_automatically():
    scipy = pytest.importorskip("scipy")  # noqa: F841
    from repro.core.multipliers import MulSpec
    from repro.dsp import design_lowpass
    from repro.parallel import precode_filterbank, sharded_filterbank
    from repro.serve import FilterbankEngine

    # serving: rows-form engine == dot-form engine == auto engine, request
    # by request
    banks = np.stack([design_lowpass(), design_lowpass(stop_weight=0.5)])
    spec = MulSpec("bbm0", 16, 13)
    sigs = [RNG.standard_normal(n) for n in (250, 180, 250)]
    outs = {}
    for form in ("rows", "dot", None):
        eng = FilterbankEngine(banks, spec, backend="host", max_channels=4,
                               form=form)
        rids = [eng.submit(s, bank=i % 2) for i, s in enumerate(sigs)]
        outs[form] = eng.flush()
        assert sorted(outs[form]) == sorted(rids)
    for rid in outs["rows"]:
        np.testing.assert_array_equal(outs["rows"][rid], outs["dot"][rid])
        np.testing.assert_array_equal(outs["rows"][rid], outs[None][rid])

    # sharded: use_kernel=None resolves to the kernel+dot path off-TPU
    wl, vbl, kind, shift = 16, 13, 1, 5
    mesh = make_mesh((1,), ("data",))
    x = jnp.asarray(RNG.integers(0, 1 << wl, (4, 256)), jnp.int32)
    h = jnp.asarray(RNG.integers(0, 1 << wl, (4, 31)), jnp.int32)
    ref = fir_bank_ref(x, h, wl=wl, vbl=vbl, kind=kind, shift=shift)
    auto = sharded_filterbank(x, h, mesh, wl=wl, vbl=vbl, kind=kind,
                              shift=shift)
    np.testing.assert_array_equal(np.asarray(auto), np.asarray(ref))
    planes = precode_filterbank(h, wl=wl)
    pinned = sharded_filterbank(x, h, mesh, wl=wl, vbl=vbl, kind=kind,
                                shift=shift, h_planes=planes, form="dot")
    np.testing.assert_array_equal(np.asarray(pinned), np.asarray(ref))
