"""Pallas TPU kernel: multi-channel direct-form FIR filterbank with
Broken-Booth tap products, precoded-digit datapath.

The paper's own workload as a TPU kernel, scaled out: ``C`` independent
channels, each with its own wl-bit tap bank, computed as

    y[c, n] = sum_k shift(bbm(x[c, n-k], h[c, k]))

with the closed-form Broken-Booth product per tap (Type0/Type1) and an
optional per-product arithmetic right shift (the fixed-point MAC rescale
that keeps the int32 accumulator inside its envelope at wl = 16).

Precoded datapath (the perf story of this kernel): the tap bank is the
Booth *multiplier* operand and it is constant across the whole grid, so
its radix-4 digits are decoded exactly once per call — outside the kernel
— by ``booth_rows.booth_precode`` and streamed in as two digit planes of
shape ``(wl//2, C, taps)``, BlockSpec-tiled like the bank itself.  The
kernel body (``bbm_rows_product_precoded``) is then multiply-free:
each Booth row is a select among ``{0, a_s, a_s << 1}`` plus a negate,
instead of re-deriving digits from the raw code inside every tap of every
``(channels, time)`` grid step.  ``fir_bbm_bank`` keeps the raw-code
signature and precodes internally; ``fir_bbm_bank_precoded`` accepts
already-decoded planes so callers with long-lived banks (serving, the
sharded filterbank) pay the decode once per bank lifetime.

Streaming layout:

  * 2-D grid over (channel blocks, time blocks); BlockSpec tiles of shape
    ``(bc, bt)`` stream through VMEM, so signal length is bounded by HBM,
    not VMEM.
  * The ``taps - 1`` history samples each time block needs from its left
    neighbour are carried through a VMEM scratch buffer: the time axis is
    sequential ("arbitrary" dimension semantics), each step deposits its
    last ``taps - 1`` raw codes into the scratch and the next step reads
    them back — an explicit halo exchange instead of overlapped loads,
    which BlockSpec index maps cannot express.  At ``t == 0`` the halo is
    zeroed (zero initial filter state, matching the host reference).
  * The channel grid axis is "parallel": a megacore split along channels
    keeps its own scratch, and every channel block re-zeroes the halo at
    its first time step, so the carry never crosses channel blocks.

Overflow envelope: taps * 2^(2*wl - 1 - shift) < 2^31 (checked on entry;
at the paper's operating point of 31 taps x wl = 16 this requires
``shift >= 5`` — see ``min_safe_shift``).

Dot form (``form="dot"``): the tap loop collapses into one dense integer
contraction.  ``bbm(a, h) == a*h - correction(a mod 2^vbl, digits)``
(see ``booth_rows``), and since the correction's own linear term is a
contraction too, every product is ``2^vbl * M`` and

    y[c, n] = ( dot(x, bq)[c, n] + Q[c, n] ) << (vbl - shift)

where the dominant term contracts the *full* signal against the
truncation-surviving digit value ``bq`` — a windowed ``lax.dot_general``
(the MXU path) on accelerator backends, a fused multiply-accumulate over
(C, N) slices on CPU — and only the ``ceil(vbl/2)`` truncated rows walk
the digit planes (``Q``).  The scaled accumulation keeps the dot form
inside the rows-form int32 envelope for every vbl
(``booth_rows.dotform_scaled_bound`` carries the re-derived analysis).
The dot form is plain jitted XLA (no ``pallas_call``): handing the
contraction to XLA is the whole point, and it is what reaches the matmul
units on every backend.  ``form=None`` auto-picks it; every form is
bit-identical.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.booth import num_pp_rows
from .booth_rows import (bbm_rows_product_precoded, booth_high_value,
                         booth_precode, resolve_form, scaled_trunc_rows,
                         split_signed)

__all__ = ["fir_bbm", "fir_bbm_bank", "fir_bbm_bank_precoded",
           "min_safe_shift"]

# auto-form only: above this many int32 elements the windowed dot operand
# (C, N, taps) stops being a fair trade against the streaming rows kernel
# on accelerator backends, so form=None falls back to streaming there.  An
# explicit form="dot" is honored regardless — the caller owns the memory
# then.  (The CPU dot branch is per-tap over (C, N) slices and never
# materializes the window, so no gate applies.)
_DOT_WINDOW_BUDGET = 1 << 26


def min_safe_shift(taps: int, wl: int) -> int:
    """Smallest per-product shift keeping the int32 accumulator safe."""
    shift = 0
    while taps * (2 ** max(2 * wl - 1 - shift, 0)) >= 2 ** 31:
        shift += 1
    return shift


def _check_envelope(taps: int, wl: int, shift: int) -> None:
    if taps * (2 ** max(2 * wl - 1 - shift, 0)) >= 2 ** 31:
        raise ValueError(
            f"accumulator may overflow int32: taps={taps}, wl={wl}, "
            f"shift={shift}; raise `shift` to >= {min_safe_shift(taps, wl)}")


def _fir_bank_kernel(x_ref, hm_ref, hs_ref, o_ref, halo_ref, *, wl: int,
                     vbl: int, kind: int, taps: int, shift: int, bt: int):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _zero_state():
        # zero initial filter state at the start of every channel block's
        # time sweep (also isolates channel blocks from one another)
        halo_ref[...] = jnp.zeros_like(halo_ref)

    # halo exchange: taps-1 raw codes deposited by the previous time block
    xs = jnp.concatenate([halo_ref[...], x_ref[...]], axis=1)
    _, xs_s = split_signed(xs, wl)          # sign-extend once per block

    acc = jnp.zeros(o_ref.shape, jnp.int32)
    for k in range(taps):
        # window of samples feeding tap k for each output in the block
        a_s = xs_s[:, taps - 1 - k:taps - 1 - k + bt]
        # tap k's (wl//2, bc, 1) digit planes as static ref slices: the
        # TPU lowering has no 3-D gather for indexing a loaded value
        prod = bbm_rows_product_precoded(
            a_s, hm_ref[:, :, k:k + 1], hs_ref[:, :, k:k + 1],
            wl=wl, vbl=vbl, kind=kind)
        if shift:
            prod = prod >> shift
        acc = acc + prod
    o_ref[...] = acc
    halo_ref[...] = xs[:, bt:]              # carry history to the next block


def _fir_bank_dotform(x, hmag, hneg, *, wl: int, vbl: int, kind: int,
                      shift: int, windowed: bool | None = None):
    """Dot-form filterbank: exact contraction + scaled truncated rows.

    Bit-identical to the rows kernel.  Every BBM product is ``2^vbl * M``
    with ``M = a*bq + sum_{r<R} ((d_r*a - neg_r*kind) >> m_r)`` — the
    exact-dot-minus-correction identity with the correction's own linear
    term ``dot(a mod 2^vbl, h)`` folded into the contraction (see
    ``booth_rows.dotform_scaled_bound``) — so the tap loop contracts the
    *full* signal against the truncation-surviving digit value ``bq`` and
    only the ``R = ceil(vbl/2)`` truncated rows walk the digit planes.
    Accumulating at the ``2^-max(vbl, shift)`` scale keeps every partial
    sum inside the rows-form int32 envelope.

    On accelerator backends the contraction is a windowed
    ``lax.dot_general`` over an im2col stack — the matmul-unit (MXU)
    path.  On CPU the same contraction runs as a fused per-tap
    multiply-accumulate over (C, N) slices: XLA CPU has no separate
    matmul unit, and the im2col materialization costs more than it buys.
    Both are trace-time choices of the same arithmetic; ``windowed``
    overrides the backend default (mirroring the rows form's
    ``multiply_free`` knob) so either branch is testable on any backend.
    A ``shift > vbl`` residual forces the per-tap branch — its floor
    applies per product, which the summed window cannot express.
    """
    n = x.shape[1]
    taps = hmag.shape[2]
    _, x_s = split_signed(x, wl)
    bq = booth_high_value(hmag, hneg, wl=wl, vbl=vbl)        # (C, taps)
    # zero codes before the signal starts: the delay line's initial
    # state, same as the rows kernel's zeroed halo
    xp = jnp.pad(x_s, ((0, 0), (taps - 1, 0)))
    u = max(shift - vbl, 0)       # per-product residual rescale (rare)
    if windowed is None:
        windowed = jax.default_backend() != "cpu"
    if windowed and u == 0:
        win = jnp.stack([xp[:, taps - 1 - k: taps - 1 - k + n]
                         for k in range(taps)], axis=-1)     # (C, N, taps)
        dn = (((2,), (1,)), ((0,), (0,)))
        acc = jax.lax.dot_general(win, bq, dn,
                                  preferred_element_type=jnp.int32)
        q = scaled_trunc_rows(win, hmag[:, :, None, :], hneg[:, :, None, :],
                              wl=wl, vbl=vbl, kind=kind)
        if q is not None:
            acc = acc + jnp.sum(q, axis=-1, dtype=jnp.int32)
    else:
        acc = jnp.zeros_like(x_s)
        for k in range(taps):
            a = xp[:, taps - 1 - k: taps - 1 - k + n]
            m_k = a * bq[:, k:k + 1]
            q = scaled_trunc_rows(a, hmag[:, :, k, None], hneg[:, :, k, None],
                                  wl=wl, vbl=vbl, kind=kind)
            if q is not None:
                m_k = m_k + q
            if u:
                m_k = m_k >> u        # shift > vbl: floor per product
            acc = acc + m_k
    if vbl > shift:
        acc = acc << (vbl - shift)
    return acc


@functools.partial(jax.jit, static_argnames=("wl", "vbl", "kind", "shift",
                                             "bc", "bt", "interpret",
                                             "form", "windowed"))
def fir_bbm_bank_precoded(x, hmag, hneg, *, wl: int, vbl: int, kind: int = 0,
                          shift: int = 0, bc: int = 8, bt: int = 512,
                          interpret: bool = False,
                          form: str | None = None,
                          windowed: bool | None = None):
    """Broken-Booth FIR filterbank on precoded tap-digit planes.

    x: (C, N) int32 wl-bit signal codes, one row per channel.
    hmag, hneg: (wl//2, C, taps) int32 digit planes from
        ``booth_precode`` of the (C, taps) tap bank — decoded once per
        bank, reused across every call that shares the bank.
    form: "rows" (the streaming Pallas kernel), "dot" (exact contraction
        + scaled truncated rows, on the matmul units) or None (auto: the
        dot form — its envelope is never narrower — except when the
        windowed operand would exceed the streaming budget on accelerator
        backends).  Bit-identical either way; ``bc``/``bt``/``interpret``
        only shape the rows form and ``windowed`` (the dot form's
        im2col-vs-per-tap contraction layout) only the dot form.
    Returns (C, N) int32 accumulator values (sum of shifted products).
    """
    channels, n = x.shape
    n_rows, hc, taps = hmag.shape
    if hmag.shape != hneg.shape:
        raise ValueError(f"mag/neg plane shapes differ: "
                         f"{hmag.shape} vs {hneg.shape}")
    if n_rows != num_pp_rows(wl) or hc != channels:
        raise ValueError(f"digit planes {hmag.shape} do not match "
                         f"wl={wl}, channels={channels}")
    _check_envelope(taps, wl, shift)
    if form is None and jax.default_backend() != "cpu" \
            and channels * n * taps > _DOT_WINDOW_BUDGET:
        form = "rows"     # keep the streaming kernel: the (C, N, taps)
        #                   windowed operand would defeat its VMEM budget
    if resolve_form(form) == "dot":
        return _fir_bank_dotform(x, hmag, hneg, wl=wl, vbl=vbl, kind=kind,
                                 shift=shift, windowed=windowed)

    bc = min(bc, channels)
    bt = min(bt, n)
    nc = pl.cdiv(channels, bc)
    nt = pl.cdiv(n, bt)
    # tail padding only; the taps-1 history halo travels through scratch
    xp = jnp.pad(x, ((0, nc * bc - channels), (0, nt * bt - n)))
    pad_c = ((0, 0), (0, nc * bc - channels), (0, 0))
    hmp = jnp.pad(hmag, pad_c)
    hsp = jnp.pad(hneg, pad_c)

    kernel = functools.partial(_fir_bank_kernel, wl=wl, vbl=vbl, kind=kind,
                               taps=taps, shift=shift, bt=bt)
    plane_spec = pl.BlockSpec((n_rows, bc, taps), lambda c, t: (0, c, 0))
    out = pl.pallas_call(
        kernel,
        grid=(nc, nt),
        in_specs=[
            pl.BlockSpec((bc, bt), lambda c, t: (c, t)),
            plane_spec,
            plane_spec,
        ],
        out_specs=pl.BlockSpec((bc, bt), lambda c, t: (c, t)),
        out_shape=jax.ShapeDtypeStruct((nc * bc, nt * bt), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bc, taps - 1), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(xp, hmp, hsp)
    return out[:channels, :n]


@functools.partial(jax.jit, static_argnames=("wl", "vbl", "kind", "shift",
                                             "bc", "bt", "interpret",
                                             "form"))
def fir_bbm_bank(x, h, *, wl: int, vbl: int, kind: int = 0, shift: int = 0,
                 bc: int = 8, bt: int = 512, interpret: bool = False,
                 form: str | None = None):
    """Bit-exact Broken-Booth FIR filterbank from raw tap codes.

    x: (C, N) int32 wl-bit signal codes, one row per channel.
    h: (C, taps) int32 wl-bit tap codes (per-channel banks) or (taps,)
       to share one bank across all channels.
    Returns (C, N) int32 accumulator values (sum of shifted products).

    Thin raw-code wrapper: precodes ``h`` once (outside the grid) and
    dispatches to ``fir_bbm_bank_precoded``.
    """
    channels = x.shape[0]
    if h.ndim == 1:
        h = jnp.broadcast_to(h[None, :], (channels, h.shape[0]))
    hmag, hneg = booth_precode(h, wl)
    return fir_bbm_bank_precoded(x, hmag, hneg, wl=wl, vbl=vbl, kind=kind,
                                 shift=shift, bc=bc, bt=bt,
                                 interpret=interpret, form=form)


def fir_bbm(x, h, *, wl: int, vbl: int, kind: int = 0, shift: int = 0,
            block: int = 512, interpret: bool = False,
            form: str | None = None):
    """Single-channel Broken-Booth FIR: x (N,) codes, h (taps,) codes.

    Thin wrapper over the (channels, time) filterbank kernel with C = 1.
    """
    return fir_bbm_bank(x[None, :], h[None, :], wl=wl, vbl=vbl, kind=kind,
                        shift=shift, bc=1, bt=block, interpret=interpret,
                        form=form)[0]
