"""Fixed-point FIR filtering built on approximate multipliers (paper §III.C).

The paper's application: a 30-tap-order Parks--McClellan low-pass filter
whose tap multipliers are replaced by Broken-Booth multipliers.  We model
the datapath bit-exactly:

  * input samples and coefficients quantized to Q(1, wl-1),
  * every tap product computed by the selected approximate multiplier
    (`core.multipliers`), with an optional per-product arithmetic right
    shift (the fixed-point MAC rescale),
  * products accumulated at full precision (the 2*wl + log2(taps) bit
    accumulator every sane FIR datapath carries; numerically exact here via
    float64 on the host — int products are < 2^31 so the sum of 31 of them is
    exact in float64's 53-bit mantissa).

``fir_apply`` is the one datapath entry point.  It accepts single signals
``(N,)`` or multi-channel filterbanks ``(C, N)`` with per-channel tap banks
``(C, taps)`` — as raw real taps or as a ``PrecodedBank`` — and dispatches
to one of three backends:

  backend="host"              per-tap shift-and-accumulate over jnp/numpy
                              closed forms (O(C*N) live memory on the hot
                              paths — exact numpy and in-envelope Booth
                              specs never materialize the (C, N, taps)
                              window); supports every registered
                              multiplier and both datapaths
                              ("full" / "wlbit")
  backend="pallas"            the Pallas TPU filterbank kernel
                              (``kernels.fir_bbm_bank_precoded``);
                              Booth-family specs only, compiled on TPU
  backend="pallas-interpret"  same kernel through the Pallas interpreter
                              (bit-exact validation on CPU)

Precoded-bank fast path: the tap bank is the Booth *multiplier* operand
and is constant across samples, blocks and requests, so its quantization
and radix-4 recode are hoisted out of the hot path entirely.
``PrecodedBank(h, spec)`` quantizes once and decodes the Booth digit
planes once; ``fir_apply(x, bank)`` then runs a fused code-level pipeline:
one float64 host quantize of the signal, one host->device transfer, a
single jitted sign-extend -> multiply-free kernel dispatch on the cached
digit planes, one device->host transfer, one float64 descale.  Nothing
else materializes in between.  (Quantize and descale are pinned to host
float64 by the bit-exactness contract: float32 rounding can differ by one
code, and all backends must agree bit for bit.)

All backends share quantization, the shift semantics (floor of each int
product), and the descale arithmetic, so for Booth-family specs their real
outputs are equal bit-for-bit.

`fir_apply_real` is the double-precision reference path; `fir_apply_fixed`
is the original host-only entry point, kept as a thin wrapper.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from scipy.signal import remez

from .. import trace
from ..core.multipliers import MulSpec, mul
from ..kernels.booth_rows import booth_precode, resolve_form
from ..kernels.fir_kernel import (_DOT_WINDOW_BUDGET, fir_bbm_bank_precoded,
                                  min_safe_shift)
from .fixed_point import requant_scale

__all__ = ["design_lowpass", "fir_apply_real", "fir_apply",
           "fir_apply_fixed", "PrecodedBank", "FIR_DELAY", "BBM_KINDS"]

# paper testbed: passband edge 0.25*pi, guard (transition) band 0.1*pi
PASS_EDGE = 0.125      # in cycles/sample (omega / 2pi)
STOP_EDGE = 0.175
NUM_TAPS = 31          # order 30 -> integer group delay of 15
FIR_DELAY = (NUM_TAPS - 1) // 2

# specs the Pallas kernel implements natively: name -> closed-form kind
BBM_KINDS = {"booth": 0, "bbm0": 0, "bbm1": 1}


def design_lowpass(num_taps: int = NUM_TAPS,
                   stop_weight: float = 0.27) -> np.ndarray:
    """Parks-McClellan equiripple low-pass design for the paper's testbed.

    The paper does not state its remez error weighting; ``stop_weight`` is
    calibrated once so the double-precision testbed reproduces the paper's
    reported SNR_out of 25.7 dB (docs/filterbank.md §Testbed calibration —
    with equal weights the same 31-tap design gives 30.1 dB, i.e. our
    testbed is, if anything, conservative about the paper's headline
    numbers).
    """
    h = remez(num_taps, [0.0, PASS_EDGE, STOP_EDGE, 0.5], [1.0, 0.0],
              weight=[1.0, stop_weight])
    return h.astype(np.float64)


def fir_apply_real(x: np.ndarray, h: np.ndarray) -> np.ndarray:
    """Double-precision reference filtering (same alignment as fixed path).

    Accepts (N,)/(taps,) or batched (C, N)/(C, taps) like ``fir_apply``.
    """
    x2, h2, squeeze = _normalize(np.asarray(x, np.float64),
                                 np.asarray(h, np.float64))
    y = np.stack([np.convolve(x2[c], h2[c], mode="full")[: x2.shape[1]]
                  for c in range(x2.shape[0])])
    return y[0] if squeeze else y


def _normalize(x, h):
    """-> (x (C, N), h (C, taps), squeeze) with h broadcast per channel."""
    x = np.asarray(x)
    h = np.asarray(h)
    squeeze = x.ndim == 1
    if squeeze:
        x = x[None, :]
    if h.ndim == 1:
        h = np.broadcast_to(h, (x.shape[0], h.shape[0]))
    if h.shape[0] != x.shape[0]:
        raise ValueError(f"{h.shape[0]} tap banks for {x.shape[0]} channels")
    return x, h, squeeze


@partial(jax.jit, static_argnames=("name", "wl", "param", "hbl", "shift",
                                   "taps"))
def _fir_accum_device(x_int, h_int, name, wl, param, hbl, shift, taps):
    """Fused per-tap shift-and-accumulate on device: O(C*N) live memory.

    One dispatch for the whole filter — the tap loop is unrolled at trace
    time, the delay line advances one sample per tap, and products
    accumulate in int32.  Exact only within the kernel envelope
    ``taps * 2^(2*wl - 1 - shift) < 2^31`` (the caller checks); inside it
    the int32 sum equals the float64 sum of the same integer products.
    """
    f = mul(MulSpec(name, wl, param, hbl))
    acc = jnp.zeros_like(x_int)
    xk = x_int
    for k in range(taps):
        prod = f(xk, h_int[:, k:k + 1])
        if shift:
            prod = prod >> shift
        acc = acc + prod
        if k + 1 < taps:
            # delay by one more sample; zero codes enter from the left
            xk = jnp.pad(xk, ((0, 0), (1, 0)))[:, :-1]
    return acc


def _window(x_int, taps: int):
    """(..., n, taps) sliding window of past samples: w[.., n, k] = x[.., n-k].

    Positions before the signal start hold zero codes (the delay line's
    initial state) — the multiplier still runs on them, like the silicon.
    Only the fallback paths materialize this (C, N, taps) array; the hot
    paths are per-tap shift-and-accumulate.
    """
    n = x_int.shape[-1]
    idx = jnp.arange(n)[:, None] - jnp.arange(taps)[None, :]
    return jnp.where(idx >= 0, x_int[..., jnp.clip(idx, 0)], 0)


@partial(jax.jit, static_argnames=("name", "wl", "param", "hbl"))
def _tap_products(x_int, h_int, name, wl, param, hbl):
    """(C, N, taps) per-tap products — windowed fallback path only."""
    spec = MulSpec(name, wl, param, hbl)
    w = _window(x_int, h_int.shape[-1])
    return mul(spec)(w, h_int[..., None, :])


def _delayed(xq: np.ndarray, k: int) -> np.ndarray:
    """x delayed by k samples with zero codes before the signal starts.

    Zero *initial state*, not suppressed products: before the signal
    starts the delay line holds zero codes and the multiplier still runs
    on them (Type1's zero-operand product is nonzero), exactly like the
    silicon pipeline and the Pallas kernel's zeroed halo.
    """
    if k == 0:
        return xq
    xd = np.zeros_like(xq)
    xd[:, k:] = xq[:, :-k]
    return xd


def _descale(acc, wl: int, shift: int, amp: np.ndarray) -> np.ndarray:
    """Shared accumulator -> real mapping (identical across backends)."""
    return acc * float(1 << shift) / requant_scale(wl) / amp


def _amp(x2: np.ndarray) -> np.ndarray:
    """Per-channel input scale so |x| < 1 with headroom; undone at output.

    Per channel (shape (C, 1)), not per batch, so a channel's quantized
    codes — and therefore its output bits — do not depend on what other
    signals happen to share the batch (serving determinism).
    """
    xmax = np.max(np.abs(x2), axis=-1, keepdims=True)
    return 1.0 / np.where(xmax > 0, 1.0001 * xmax, 1.0)


def _quantize64(x: np.ndarray, wl: int) -> np.ndarray:
    """Float64 host quantizer: real [-1,1) -> signed integers (int64).

    All backends quantize through this one function so that rounding is
    identical (float32 jnp rounding can differ by one code from float64).
    """
    scale = float(1 << (wl - 1))
    return np.clip(np.round(np.asarray(x, np.float64) * scale),
                   -scale, scale - 1).astype(np.int64)


def _codes32(q: np.ndarray, wl: int) -> np.ndarray:
    """Signed integers -> masked wl-bit int32 codes for the jax datapaths."""
    return (q & ((1 << wl) - 1)).astype(np.int32)


class PrecodedBank:
    """Tap banks quantized and Booth-precoded once, reused across calls.

    The decode phase of the Broken-Booth datapath (float64 quantization of
    the real taps + radix-4 digit extraction) depends only on the bank and
    the spec, not on the signals — so a serving engine or a long-lived
    filterbank builds it exactly once and every subsequent ``fir_apply``
    call skips straight to the multiply-free accumulate phase.

    h: (B, taps) real tap banks (or (taps,) for a single bank).
    ``take(idx)`` gathers per-request banks into a request-ordered view —
    a cheap index into the cached codes/planes, never a re-quantize or
    re-decode.  For Booth-family specs at wl <= 16 the digit planes
    (wl//2, B, taps) live on device, ready for either accumulate form:
    the rows kernel walks them as partial-product generators, and the dot
    form reads them twice — reconstructing the exact contraction operand
    (``booth_value``) and driving the low-bit correction
    (``booth_correction``), so they are also the dot form's correction
    planes and *both* backends now consume them.  ``precode=False``
    defers the digit decode until ``planes`` is first read; the default
    decodes eagerly so a serving engine pays the whole decode phase at
    construction, not on the first request.
    """

    def __init__(self, h, spec: MulSpec, *, precode: bool = True):
        h2 = np.atleast_2d(np.asarray(h, np.float64))
        if h2.ndim != 2:
            raise ValueError(f"tap banks must be (B, taps), got {h2.shape}")
        self.spec = spec
        self.h_real = h2
        self.hq = _quantize64(h2, spec.wl)          # int64 host codes
        self._planes = None                         # (mag, neg) digit planes
        if precode:
            self.planes                             # eager decode, cached

    @property
    def num_banks(self) -> int:
        return self.h_real.shape[0]

    @property
    def taps(self) -> int:
        return self.h_real.shape[1]

    @property
    def planes(self):
        """(mag, neg) digit planes of shape (wl//2, B, taps), device side.

        Decoded on first read and cached.  ``None`` for specs the Pallas
        kernel does not implement (non-Booth families, wl > 16) — those run
        on the host backend from ``hq``.
        """
        if self._planes is None and self.spec.name in BBM_KINDS \
                and self.spec.wl <= 16:
            codes = jnp.asarray(_codes32(self.hq, self.spec.wl))
            self._planes = booth_precode(codes, self.spec.wl)
        return self._planes

    def take(self, idx) -> "PrecodedBank":
        """Bank rows gathered per request: a view, never a re-decode."""
        idx = np.asarray(idx, np.int64)
        out = object.__new__(PrecodedBank)
        out.spec = self.spec
        out.h_real = self.h_real[idx]
        out.hq = self.hq[idx]
        out._planes = None if self._planes is None else tuple(
            p[:, jnp.asarray(idx), :] for p in self._planes)
        return out


def fir_apply(x: np.ndarray, h, spec: MulSpec | None = None, *,
              backend: str = "host", datapath: str = "full",
              shift: int | None = None, bc: int = 8,
              block: int = 512, form: str | None = None) -> np.ndarray:
    """Bit-exact fixed-point filtering with the given multiplier spec.

    x: signal(s), (N,) or (C, N); h: real taps, (taps,) or (C, taps) for
    per-channel banks, or a ``PrecodedBank`` whose rows match the channels
    (in which case ``spec`` defaults to the bank's spec).  Output has the
    shape of ``x``, aligned with ``fir_apply_real``.

    datapath="full"  — products accumulated at full precision (growing
                       accumulator, the Table-I-faithful setting).
    datapath="wlbit" — each product rounded back to Q(1, wl-1) and summed in
                       a saturating wl-bit accumulator: the low-power
                       wl-bit-adder datapath.  This is what produces the
                       paper's Fig. 8(a) cliff at small word lengths; with a
                       full-precision accumulator the word length barely
                       matters down to WL=8 (docs/filterbank.md §Testbed
                       calibration).  Host backend only.

    shift — per-product arithmetic right shift before accumulation (the MAC
    rescale).  ``None`` selects 0 when the int32 envelope allows it and the
    minimal safe value otherwise (wl = 16 at 31 taps needs shift = 5), so
    host and Pallas backends agree by default.

    form — Booth-family accumulate form, resolved at trace time and
    bit-identical either way: "rows" walks the wl/2 partial-product rows
    per tap (the silicon emulation), "dot" puts the dominant exact
    contraction on the matmul units and walks only the truncated rows
    (``kernels.booth_rows``), ``None`` auto-picks the dot form.  Applies
    to the Booth-family hot paths of both backends; the exact / wlbit /
    non-Booth paths ignore "rows" and reject an explicit "dot".
    """
    resolve_form(form)     # validate early; selection happens per path
    bank = h if isinstance(h, PrecodedBank) else None
    if bank is not None:
        if spec is not None and spec != bank.spec:
            raise ValueError(f"spec {spec} does not match the precoded "
                             f"bank's {bank.spec}")
        spec = bank.spec
        x2 = np.asarray(x)
        squeeze = x2.ndim == 1
        if squeeze:
            x2 = x2[None, :]
        if bank.num_banks == 1 and x2.shape[0] > 1:
            bank = bank.take(np.zeros(x2.shape[0], np.int64))
        if bank.num_banks != x2.shape[0]:
            raise ValueError(f"{bank.num_banks} precoded banks for "
                             f"{x2.shape[0]} channels")
        taps = bank.taps
    else:
        if spec is None:
            raise ValueError("spec is required unless h is a PrecodedBank")
        x2, h2, squeeze = _normalize(x, h)
        taps = h2.shape[1]
    wl = spec.wl
    if shift is None:
        # the rescale exists for the int32 kernel envelope; wlbit models its
        # own rounding and wl > 16 only runs on the exact int64 host path,
        # so neither needs (or should pay for) a default shift
        shift = 0 if (datapath == "wlbit" or wl > 16) \
            else min_safe_shift(taps, wl)
    with trace.span("fir.quantize"):
        amp = _amp(x2)
        xq = _quantize64(x2 * amp, wl)
    if bank is None:
        # one-shot bank: defer the decode to the first ``planes`` read —
        # the Booth-family dot path (either backend) triggers it once per
        # call, and the rows/exact/fallback host paths never pay it
        bank = PrecodedBank(h2, spec, precode=False)
    if backend in ("pallas", "pallas-interpret"):
        y = _apply_pallas(xq, bank, datapath=datapath, shift=shift,
                          amp=amp, bc=bc, block=block,
                          interpret=backend == "pallas-interpret",
                          form=form)
    elif backend == "host":
        y = _apply_host(xq, bank, datapath=datapath, shift=shift, amp=amp,
                        form=form)
    else:
        raise ValueError(f"unknown backend {backend!r}")
    return y[0] if squeeze else y


def _apply_pallas(xq, bank: PrecodedBank, *, datapath, shift, amp, bc,
                  block, interpret, form=None):
    from ..kernels.ops import fir_filterbank_precoded
    spec = bank.spec
    if spec.name not in BBM_KINDS:
        raise ValueError(f"backend='pallas' supports Booth-family specs "
                         f"{sorted(BBM_KINDS)}, not {spec.name!r}")
    if datapath != "full":
        raise ValueError("backend='pallas' implements the full-precision "
                         "accumulator datapath only")
    wl = spec.wl
    if wl > 16:
        raise ValueError("the int32 kernel datapath supports wl <= 16")
    vbl = 0 if spec.name == "booth" else spec.param
    # fused code-level pipeline: one transfer in, one jitted dispatch on the
    # cached digit planes (sign-extend + accumulate form), one out
    hmag, hneg = bank.planes
    with trace.span("fir.quantize"):
        codes = _codes32(xq, wl)
    with trace.span("fir.to_device"):
        codes = jnp.asarray(codes)
    with trace.span("fir.dispatch"):
        out = fir_filterbank_precoded(codes, hmag, hneg, wl=wl, vbl=vbl,
                                      kind=BBM_KINDS[spec.name], shift=shift,
                                      interpret=interpret, bc=bc, bt=block,
                                      form=form)
    with trace.span("fir.fetch"):
        acc = np.asarray(out, np.float64)
    with trace.span("fir.descale"):
        return _descale(acc, wl, shift, amp)


def _apply_host(xq, bank: PrecodedBank, *, datapath, shift, amp, form=None):
    """Host datapath: exact contraction or per-tap accumulate, by form.

    Tap k contributes ``mul(x[n-k], h[k])``:

      * exact specs run a per-tap loop in int64 numpy (any wl; the
        float64 accumulator is exact while partial sums stay below 2^53),
      * Booth-family approximate specs inside the int32 envelope run a
        single fused device dispatch — the dot form (dense exact
        contraction + scaled truncated rows, from the bank's cached digit
        planes) by default; ``form="rows"`` pins the per-tap loop
        (``_fir_accum_device``).

    Everything else (wlbit's saturating per-product rounding, non-Booth
    multipliers, sub-envelope shifts) falls back to the windowed
    (C, N, taps) product array — off the hot path, semantics unchanged.
    """
    spec = bank.spec
    wl = spec.wl
    hq = bank.hq
    taps = hq.shape[1]
    if datapath not in ("full", "wlbit"):
        raise ValueError(f"unknown datapath {datapath!r}")
    if datapath == "wlbit" and shift:
        raise ValueError("datapath='wlbit' models its own product rounding; "
                         "use shift=0")
    lim = float(1 << (wl - 1))

    # Booth-family hot path on the full-precision datapath: a single fused
    # device dispatch on the bank's cached digit planes, inside the int32
    # envelope.  The dot form (dense exact contraction + scaled truncated
    # rows) is the default — this includes the *exact* "booth" spec
    # (vbl = 0, a pure dot); form="rows" pins the per-tap emulation.
    booth_hot = (datapath == "full" and spec.name in BBM_KINDS
                 and wl <= 16 and min_safe_shift(taps, wl) <= shift)
    if booth_hot:
        vbl = 0 if spec.name == "booth" else spec.param
        use_dot = resolve_form(form) == "dot"
        if use_dot and form is None and jax.default_backend() != "cpu" \
                and xq.size * taps > _DOT_WINDOW_BUDGET:
            # mirror the kernel's auto-form memory gate instead of
            # escalating None to an explicit "dot" (which would bypass
            # it); the fallback here is the host-native per-tap path
            use_dot = False
        if use_dot:
            xc = jnp.asarray(_codes32(xq, wl))
            hmag, hneg = bank.planes     # decoded once per bank, cached
            acc = np.asarray(fir_bbm_bank_precoded(
                xc, hmag, hneg, wl=wl, vbl=vbl, kind=BBM_KINDS[spec.name],
                shift=shift, form="dot"), np.float64)
            return _descale(acc, wl, shift, amp)
    elif form == "dot":
        raise ValueError("form='dot' needs a Booth-family spec on the "
                         "full-precision datapath inside the int32 "
                         "envelope")

    if spec.is_exact:
        # exact quantized path in int64 numpy: valid for any wl (the jax
        # closed forms are int32-bound to wl <= 16)
        acc = np.zeros(xq.shape, np.float64)
        for k in range(taps):
            prod = _delayed(xq, k) * hq[:, k:k + 1]
            if shift:
                prod = prod >> shift        # arithmetic shift == floor
            if datapath == "full":
                acc += prod.astype(np.float64)
            else:
                p_wl = np.clip(np.round(prod / lim), -lim, lim - 1)
                acc = np.clip(acc + p_wl, -lim, lim - 1)
        return _descale(acc, wl, shift, amp) if datapath == "full" \
            else acc / lim / amp

    if wl > 16:
        raise ValueError("approximate fixed-point path supports wl <= 16 "
                         "(int32-exact); the paper's operating point is 16")
    xc = jnp.asarray(_codes32(xq, wl))
    hc = jnp.asarray(_codes32(hq, wl))
    if booth_hot:
        acc = np.asarray(_fir_accum_device(xc, hc, spec.name, wl, spec.param,
                                           spec.hbl, shift, taps), np.float64)
        return _descale(acc, wl, shift, amp)

    # windowed fallback: per-tap products materialized, then reduced
    prod = np.asarray(_tap_products(xc, hc, spec.name, wl, spec.param,
                                    spec.hbl), np.int64)
    if shift:
        prod = prod >> shift
    if datapath == "full":
        return _descale(prod.astype(np.float64).sum(axis=-1), wl, shift, amp)
    # round each 2wl-bit product back to Q(1, wl-1), saturate, then sum in a
    # saturating wl-bit accumulator (left-to-right tap order)
    p_wl = np.clip(np.round(prod.astype(np.float64) / lim), -lim, lim - 1)
    acc = np.zeros(prod.shape[:-1])
    for k in range(p_wl.shape[-1]):
        acc = np.clip(acc + p_wl[..., k], -lim, lim - 1)
    return acc / lim / amp


def fir_apply_fixed(x: np.ndarray, h: np.ndarray, spec: MulSpec,
                    datapath: str = "full") -> np.ndarray:
    """Original host-only entry point (kept for callers and tests)."""
    return fir_apply(x, h, spec, backend="host", datapath=datapath, shift=0)
