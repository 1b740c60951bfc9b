"""Plain reference of the served FIR filter, in numpy int64.

The paper's datapath (arXiv:2003.06727 §III.C), written out from its
definition and independent of the program:

  * each channel is scaled so |x| < 1 (``amp = 1 / (1.0001 max|x|)``,
    undone at the output), then signal and taps are quantized to Q(1, wl-1)
    codes by round-half-even and clipping;
  * every tap product is the Broken-Booth Type 0 product with the tap as
    the radix-4 Booth-recoded operand: row i carries d_i * x with its low
    ``m_i = max(0, vbl - 2i)`` bits cleared (floor toward -inf), weighted
    by 4^i;
  * each product is shifted right (floor) by the smallest shift that keeps
    a ``taps``-term int32 accumulator safe, the delay line starts at zero
    codes, and the sum is scaled back to real numbers.
"""
from __future__ import annotations

import numpy as np
from scipy.signal import remez


def design_taps(spec: dict) -> np.ndarray:
    """The low-pass of the configuration (Parks-McClellan)."""
    return remez(spec["num_taps"], spec["bands"], spec["desired"],
                 weight=spec["weight"]).astype(np.float64)


def quantize(x: np.ndarray, wl: int) -> np.ndarray:
    scale = float(1 << (wl - 1))
    return np.clip(np.round(np.asarray(x, np.float64) * scale),
                   -scale, scale - 1).astype(np.int64)


def booth_digits(b: int, wl: int) -> list:
    """Radix-4 Booth digits d_0 .. d_{wl/2-1} of the signed wl-bit b."""
    bu = int(b) & ((1 << wl) - 1)
    bit = lambda j: (bu >> j) & 1 if j >= 0 else 0
    return [-2 * bit(2 * i + 1) + bit(2 * i) + bit(2 * i - 1)
            for i in range(wl // 2)]


def bbm0(a: np.ndarray, b: int, wl: int, vbl: int) -> np.ndarray:
    """Broken-Booth Type 0 product of signed codes ``a`` by the code b."""
    out = np.zeros_like(a)
    for i, d in enumerate(booth_digits(b, wl)):
        m = max(0, vbl - 2 * i)
        out += (d * a // (1 << m)) * (1 << m) * (1 << (2 * i))
    return out


def min_shift(taps: int, wl: int) -> int:
    s = 0
    while taps * (2 ** max(2 * wl - 1 - s, 0)) >= 2 ** 31:
        s += 1
    return s


def fir(x: np.ndarray, taps: np.ndarray, wl: int, vbl: int) -> np.ndarray:
    """One channel filtered; same length and alignment as the input."""
    x = np.asarray(x, np.float64)
    xmax = np.max(np.abs(x))
    amp = 1.0 / (1.0001 * xmax if xmax > 0 else 1.0)
    xq = quantize(x * amp, wl)
    hq = quantize(taps, wl)
    shift = min_shift(len(taps), wl)
    n = len(x)
    acc = np.zeros(n, np.int64)
    for k, h in enumerate(hq):
        p = bbm0(xq[: n - k], int(h), wl, vbl) >> shift
        acc[k:] += p
    return acc.astype(np.float64) * float(1 << shift) \
        / float(1 << (2 * (wl - 1))) / amp
