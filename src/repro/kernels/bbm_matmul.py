"""Pallas TPU kernel: bit-exact Broken-Booth approximate matmul,
precoded-digit datapath.

Computes ``out[m, n] = sum_k shift(bbm(x[m, k], w[k, n]))`` where ``bbm`` is
the closed-form Broken-Booth product (Type0/Type1) and ``shift`` an optional
arithmetic right shift applied per product (the fixed-point MAC rescale).

TPU adaptation notes (this is the paper's multiplier *as a TPU kernel*):
  * The MXU performs exact multiplies only — but that does NOT keep a broken
    multiplier off it: clearing the low ``m`` bits of a two's-complement row
    is subtraction of its low bits, so every BBM product is the *exact*
    product minus a correction built from the low ``vbl`` bits of ``x``
    (``booth_rows.booth_correction``), and folding the correction's own
    linear term back into the contraction gives
    ``bbm(x, w) == 2^vbl * (x*wq + truncated-row terms)``.  ``form="dot"``
    computes exactly that: the dominant ``x @ wq`` contraction rides the
    hardware's native matmul units (MXU on TPU, XLA's matmul lowering on
    CPU), and each of the ``ceil(vbl/2)`` truncated rows folds into a few
    more narrow contractions (``_dot_scaled``: the row's K-reduction is a
    digit dot minus a one-hot residue dot per (digit, sign) pair — no
    (M, K, N) temporary).  ``form="rows"`` keeps the pure-VPU row emulation — still
    the bit-exact reference datapath for validating the silicon and
    calibrating the statistical noise model that the quantized fast path
    (quant_matmul) uses.  ``form=None`` auto-picks the dot form; its
    scaled accumulation stays inside the rows-form int32 envelope for
    every vbl (``booth_rows.dotform_scaled_bound`` has the re-derived
    analysis).
  * ``w`` is the Booth *multiplier* operand and is constant across the whole
    grid (every (i, j) tile re-reads the same weight blocks), so its radix-4
    digits are decoded exactly once per call by ``booth_rows.booth_precode``
    and streamed in as ``(wl//2, K, N)`` planes, BlockSpec-tiled like ``w``
    itself.  The in-kernel row loop is then multiply-free (select/negate/
    shift per row).  ``bbm_matmul`` keeps the raw-code signature and
    precodes internally; ``bbm_matmul_precoded`` accepts decoded planes for
    callers whose weights are long-lived.
  * The Booth row loop (wl/2 iterations) is unrolled at trace time; each row
    materializes one (bm, bk, bn) int32 tile in VMEM.  With the default
    8x128x128 blocking (the smallest the TPU's (8, 128) tiling allows) that
    is 512 KiB live — comfortably inside the ~16 MiB VMEM budget together
    with the x/w/out tiles.
  * Accumulation is int32.  Callers must respect the documented overflow
    envelope: K * 2^(2*wl - 1 - shift) < 2^31 (asserted in ops.py).

Block shapes are (bm, bk) x (bk, bn) -> (bm, bn) with a 3-D grid over
(M/bm, N/bn, K/bk); the K axis accumulates in place (output revisited).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.booth import num_pp_rows
from ..core.faults import apply_acc_fault, apply_plane_faults
from ..trace import AMM_PV_DOT, AMM_PV_ROWS
from .booth_rows import (amm_chunk_len, bbm_rows_product_precoded,
                         bbm_rows_product_scaled, booth_digit_rows,
                         booth_high_value, booth_precode, f32_exact_chunk_len,
                         num_corr_rows, resolve_form, scaled_trunc_rows,
                         signed_digit, split_signed)
from .ref import amm_quantize

__all__ = ["bbm_matmul_kernel", "bbm_matmul", "bbm_matmul_coded",
           "bbm_matmul_coded_kblocks", "bbm_matmul_dynamic",
           "bbm_matmul_precoded", "bbm_matmul_scaled", "dot_scaled_chunked",
           "pv_form"]

# auto-form only: above this many int32 elements the shift > vbl residual
# branch's (M, K, N) per-product temporary stops being a fair trade against
# the tiled rows kernel, so form=None falls back to streaming there.  (The
# shift <= vbl dot form is fully contracted and needs no such gate.)  An
# explicit form="dot" is honored regardless — the caller owns the memory.
_DOT_CORR_BUDGET = 1 << 26

# the (signed digit, raw sign bit) pairs a radix-4 row can take, per BBM
# kind.  Each pair is one dense contraction of the dot form's mod-term:
# kind 0 folds the sign into the row value (the digit alone determines the
# residue), kind 1 one's-complements (the 111 "negative zero" triplet —
# digit 0, sign 1 — has residue (0 - 1) & mask, which is why it appears).
_MOD_BRANCHES = {0: ((1, 0), (2, 0), (-1, 0), (-2, 0)),
                 1: ((1, 0), (2, 0), (0, 1), (-1, 1), (-2, 1))}


def _dot_i32(x, y, *, f32_chunk: int = 0):
    """int32 contraction ``x @ y``, optionally via exact-envelope f32 gemms.

    ``f32_chunk = 0`` is the historical lowering: one s32 dot.  A positive
    ``f32_chunk`` (from ``booth_rows.f32_exact_chunk_len``) splits the
    contraction into K-chunks inside the caller's f32-exact envelope —
    every product and every partial sum is an integer of magnitude
    <= 2^24, so the f32 gemm computes the exact integer and the cast back
    to int32 is exact.  Bit-identical either way; the f32 route is what
    lets the flash-amm tile arithmetic ride the f32 matmul units
    (HIGHEST precision pins the TPU MXU to the exact f32 decomposition;
    CPU XLA ignores it).
    """
    if not f32_chunk:
        return jax.lax.dot(x, y, preferred_element_type=jnp.int32)
    k = x.shape[-1]
    xf = x.astype(jnp.float32)
    yf = y.astype(jnp.float32)
    acc = None
    for lo in range(0, k, f32_chunk):
        part = jax.lax.dot(xf[:, lo:lo + f32_chunk],
                           yf[lo:lo + f32_chunk, :],
                           precision=jax.lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32
                           ).astype(jnp.int32)
        acc = part if acc is None else acc + part
    return acc


def _dot_scaled(x_s, wmag, wneg, *, wl: int, vbl: int, kind: int,
                f32_chunk: int = 0):
    """``sum_k bbm(x, w) / 2^vbl`` as pure dense contractions, int32.

    Every BBM product is ``2^vbl * M`` with
    ``M = x*bq + sum_{r<R} q_r``, ``q_r = (d_r*x - neg_r*kind) >> m_r``
    (the folded dot form).  Writing the floor as subtraction of the
    residue, a whole row's K-reduction collapses to contractions:

        sum_k q_{r,k} = [ dot(x, d_r) - kind * sum_k neg_r
                          - sum_k ((d_r*x - neg_r*kind) mod 2^m_r) ] >> m_r

    and the residue sum — the only nonlinear term — depends on ``x`` only
    through ``x mod 2^m_r`` and on the weight only through which of the
    few (digit, sign) pairs its row takes (``_MOD_BRANCHES``): a one-hot
    indicator per pair turns it into ``dot(residue_pair(x), indicator)``.
    So the whole reduction is the dominant ``x @ bq`` matmul plus a
    handful of narrow contractions per truncated row — nothing ever
    materializes an (M, K, N) intermediate, which is what lets the
    ``amm_dense`` bitexact mode run at model batch sizes in O(M*N) live
    memory.  The bracket is exactly divisible by ``2^m_r`` (it is a sum
    of ``2^m_r * q`` terms), so the shift is an exact division.

    int32-exact for chunks within ``booth_rows.amm_chunk_len(wl, vbl)``.
    x_s: (M, K) signed codes; wmag/wneg: (wl//2, K, N) digit planes.
    ``f32_chunk``: nonzero routes every contraction through ``_dot_i32``'s
    exact-envelope f32 gemms (bit-identical; the flash-amm fast path).
    """
    bq = booth_high_value(wmag, wneg, wl=wl, vbl=vbl)        # (K, N)
    acc = _dot_i32(x_s, bq, f32_chunk=f32_chunk)
    for r in range(num_corr_rows(wl, vbl)):
        m = vbl - 2 * r                   # > 0 for every correction row
        mask = (1 << m) - 1
        d = signed_digit(wmag[r], wneg[r])                   # (K, N)
        rowdot = _dot_i32(x_s, d, f32_chunk=f32_chunk)
        if kind:
            rowdot = rowdot - jnp.sum(wneg[r], axis=0,
                                      dtype=jnp.int32)[None, :]
        xm = x_s & mask                                      # (M, K)
        modsum = None
        for v, s in _MOD_BRANCHES[kind]:
            t = (v * xm - s) & mask                          # (M, K)
            ind = (d == v) if kind == 0 else (d == v) & (wneg[r] == s)
            part = _dot_i32(t, ind.astype(jnp.int32), f32_chunk=f32_chunk)
            modsum = part if modsum is None else modsum + part
        acc = acc + ((rowdot - modsum) >> m)
    return acc


def _matmul_dotform(x, wmag, wneg, *, wl: int, vbl: int, kind: int,
                    shift: int):
    """Dot-form matmul: dense contractions + exact-division row folding.

    Bit-identical to the rows kernel.  The ``shift <= vbl`` common case is
    the fully contracted ``_dot_scaled`` reduction (no (M, K, N)
    temporary); only ``shift > vbl`` — a residual floor applied per
    product, *before* the K reduction — still walks a windowed
    per-product term.  Accumulating at the ``2^-max(vbl, shift)`` scale
    keeps every partial sum inside the rows-form int32 envelope
    (``booth_rows.dotform_scaled_bound``).
    """
    _, x_s = split_signed(x, wl)
    u = max(shift - vbl, 0)       # per-product residual rescale (rare)
    if u == 0:
        acc = _dot_scaled(x_s, wmag, wneg, wl=wl, vbl=vbl, kind=kind)
    else:
        # shift > vbl: the residual floor applies per product, before
        # the K reduction — inherently per-(m, k, n)
        wq = booth_high_value(wmag, wneg, wl=wl, vbl=vbl)    # (K, N)
        q = scaled_trunc_rows(x_s[:, :, None], wmag[:, None, :, :],
                              wneg[:, None, :, :], wl=wl, vbl=vbl,
                              kind=kind)                     # (M, K, N)
        m_prod = x_s[:, :, None] * wq[None]
        if q is not None:
            m_prod = m_prod + q
        acc = jnp.sum(m_prod >> u, axis=1, dtype=jnp.int32)
    if vbl > shift:
        acc = acc << (vbl - shift)
    return acc


@functools.partial(jax.jit, static_argnames=("wl", "vbl", "kind", "fault"))
def bbm_matmul_scaled(x, wmag, wneg, *, wl: int, vbl: int, kind: int = 0,
                      fault=None):
    """``sum_k bbm(x[m,k], w[k,n])`` as float32, any K — the amm datapath.

    The model-scale entry point behind ``amm_dense`` mode="bitexact":
    contracts K in chunks of ``booth_rows.amm_chunk_len(wl, vbl)`` so
    every chunk partial is an *exact* int32 at the ``2^-vbl`` product
    scale (``_dot_scaled``), accumulates the partials in float32 in chunk
    order, and rescales by ``2^vbl`` (a power of two: exact in float32).
    K within one chunk — every LM operating point at vbl >= wl - 3 —
    is therefore exact end to end; beyond it only the cross-chunk float32
    adds round, at relative 2^-24.  Never materializes an (M, K, N)
    intermediate for any K (the scalar closed forms do, which is what
    limited the old bitexact mode to reduced configs).

    x: (M, K) int32 codes; wmag/wneg: (wl//2, K, N) planes from
    ``booth_precode``.  Returns float32 (M, N) at full product scale.

    fault: optional ``core.faults.FaultSpec`` (static).  "plane" faults
    hit the weight digit planes *before* the chunk split (mask shape =
    the caller's (wl//2, K, N) planes, so the scalar oracle
    ``ref.amm_faulty_ref`` faults the same cells); "acc" faults XOR a
    keyed upset into each chunk's int32 partial, folded by chunk index —
    the same draws the oracle's python chunk loop makes.  ``None`` (and
    any disabled spec) traces the identical program as before.
    """
    mm, kk = x.shape
    n_rows, kk2, nn = wmag.shape
    if wmag.shape != wneg.shape or n_rows != num_pp_rows(wl) or kk != kk2:
        raise ValueError(f"digit planes {wmag.shape}/{wneg.shape} do not "
                         f"match wl={wl}, K={kk}")
    wmag, wneg = apply_plane_faults(wmag, wneg, fault, vbl=vbl)
    _, x_s = split_signed(x, wl)
    chunk = amm_chunk_len(wl, vbl)
    scale = float(1 << vbl)
    if kk <= chunk:
        acc = _dot_scaled(x_s, wmag, wneg, wl=wl, vbl=vbl, kind=kind)
        acc = apply_acc_fault(acc, fault, 0)
        return acc.astype(jnp.float32) * scale
    n_chunks = -(-kk // chunk)
    pad = n_chunks * chunk - kk
    # zero codes decode to all-zero digits (mag 0, neg 0): every padded
    # column contributes 0 to every contraction, so padding is exact
    # (plane faults were applied above, on the caller's unpadded planes —
    # padded columns are clean zeros and still contribute nothing)
    x_s = jnp.pad(x_s, ((0, 0), (0, pad)))
    wmag = jnp.pad(wmag, ((0, 0), (0, pad), (0, 0)))
    wneg = jnp.pad(wneg, ((0, 0), (0, pad), (0, 0)))
    xc = x_s.reshape(mm, n_chunks, chunk).transpose(1, 0, 2)
    wmc = wmag.reshape(n_rows, n_chunks, chunk, nn).transpose(1, 0, 2, 3)
    wnc = wneg.reshape(n_rows, n_chunks, chunk, nn).transpose(1, 0, 2, 3)

    def body(acc, xs):
        ci, xi, mi, ni = xs
        part = _dot_scaled(xi, mi, ni, wl=wl, vbl=vbl, kind=kind)
        part = apply_acc_fault(part, fault, ci)
        return acc + part.astype(jnp.float32), None

    acc, _ = jax.lax.scan(body, jnp.zeros((mm, nn), jnp.float32),
                          (jnp.arange(n_chunks), xc, wmc, wnc))
    return acc * scale


def dot_scaled_chunked(x, wmag, wneg, *, wl: int, vbl: int, kind: int,
                       f32_dots: bool = False):
    """Kernel-safe chunked ``sum_k bbm(x, w)`` — bitwise ``bbm_matmul_scaled``.

    Same contraction schedule as ``bbm_matmul_scaled`` (K chunked by
    ``amm_chunk_len``, int32-exact partials accumulated in float32 in
    chunk order, rescaled by ``2^vbl``), but built from a static python
    loop over ragged chunk slices instead of pad + ``lax.scan`` — legal
    inside a Pallas kernel body, where scan over sliced operands is not.
    The two schedules are bit-identical: padded zero codes decode to
    all-zero digit planes and contribute 0 to every contraction
    (including the kind-1 residue branch, whose indicator is gated on the
    padded ``wneg``), so ragged-final-chunk partials equal padded-chunk
    partials and the float32 adds see the same values in the same order.

    ``f32_dots=True`` additionally routes each chunk's contractions
    through the exact-envelope f32 gemms (``f32_exact_chunk_len``) — the
    flash-amm fast path; still bit-identical, falls back to s32 dots at
    operating points with no f32 envelope.

    x: (M, K) int32 codes; wmag/wneg: (wl//2, K, N) planes.  Returns
    float32 (M, N) at full product scale.
    """
    kk = x.shape[-1]
    _, x_s = split_signed(x, wl)
    chunk = amm_chunk_len(wl, vbl)
    f32_chunk = f32_exact_chunk_len(wl, vbl) if f32_dots else 0
    scale = float(1 << vbl)
    if kk <= chunk:
        return _dot_scaled(x_s, wmag, wneg, wl=wl, vbl=vbl, kind=kind,
                           f32_chunk=f32_chunk).astype(jnp.float32) * scale
    acc = None
    for lo in range(0, kk, chunk):
        part = _dot_scaled(x_s[:, lo:lo + chunk],
                           wmag[:, lo:lo + chunk],
                           wneg[:, lo:lo + chunk],
                           wl=wl, vbl=vbl, kind=kind, f32_chunk=f32_chunk)
        part = part.astype(jnp.float32)
        acc = part if acc is None else acc + part
    return acc * scale


def bbm_matmul_dynamic(a, b, *, wl: int, vbl: int, kind: int = 0,
                       fault=None):
    """Both-operands-dynamic Broken-Booth matmul — the attention entry point.

    ``bbm_matmul_scaled`` contracts quantized codes against a *precoded*
    multiplier operand: the weight-side calling convention, where the
    dynamic scale and radix-4 digit planes are derived once per parameter
    and cached (``AmmRuntime.precode``).  Attention has no weight side —
    the score product ``Q @ K^T`` and the value product ``P @ V`` multiply
    activations by activations, and both operands change every call — so
    this wrapper quantizes *both* sides per call (``ref.amm_quantize``
    dynamic-range scales, derived from this (M, K) / (K, N) slice alone:
    vmapping over batch/head axes yields per-slice scales), decodes ``b``'s
    digit planes inline, contracts through the same chunked
    digit-dot-minus-residue-dot correction (K chunked by
    ``booth_rows.amm_chunk_len`` so every intermediate stays int32-exact
    per chunk), and descales.

    a: (M, K) float, b: (K, N) float.  Returns (M, N) in ``a.dtype``,
    bit-identical to the scalar closed-form oracle ``ref.amm_dot_ref``
    (same quantizer, same chunk schedule, same descale expression).

    Deliberately not jitted as a unit (only the ``bbm_matmul_scaled``
    core is): XLA's fusion can round ``amm_quantize``'s dynamic-scale
    division differently inside a larger compiled program than op-by-op,
    so the bitwise dot-vs-oracle contract holds *per compilation
    context* — both sides of a comparison must be traced the same way,
    which the shared attention schedule guarantees and an extra jit
    boundary here would break.

    fault: optional ``core.faults.FaultSpec`` forwarded to
    ``bbm_matmul_scaled`` — hardware-fault injection on the ``b``-side
    digit planes / the chunk accumulator, oracled by
    ``ref.amm_faulty_ref`` (bit-identical under the same spec).
    """
    aq, s_a = amm_quantize(a, wl)
    bq, s_b = amm_quantize(b, wl)
    mag, neg = booth_precode(bq, wl)
    yq = bbm_matmul_scaled(aq, mag, neg, wl=wl, vbl=vbl, kind=kind,
                           fault=fault)
    return (yq * (s_a * s_b)).astype(a.dtype)


def bbm_matmul_coded(a, b_codes, s_b, *, wl: int, vbl: int, kind: int = 0):
    """Codes-in sibling of ``bbm_matmul_dynamic``: ``b`` arrives quantized.

    The int-code KV cache entry point.  ``a`` (M, K) float is quantized
    per call; ``b_codes`` (K, N) are wl-bit codes frozen at cache-write
    time with scale(s) ``s_b`` — a scalar, or an (N,) vector when columns
    were quantized in groups (the per-block K-cache scales, expanded to
    per-column by the caller).  Skipping the per-call ``b``-side
    ``amm_quantize`` is the point: that max/round/clip pass over the whole
    cache slice is the hot non-matmul cost of the dynamic entry at decode.

    When ``s_b`` equals the scale ``amm_quantize`` would derive for the
    float ``b``, this is bit-identical to ``bbm_matmul_dynamic(a, b)``
    minus the straight-through caveats: same contraction, and the descale
    ``yq * (s_a * s_b)`` broadcasts a per-column vector through the same
    float expression as the scalar.  Not jitted as a unit for the same
    per-compilation-context reason as the dynamic entry.
    """
    aq, s_a = amm_quantize(a, wl)
    mag, neg = booth_precode(jnp.asarray(b_codes, jnp.int32), wl)
    yq = bbm_matmul_scaled(aq, mag, neg, wl=wl, vbl=vbl, kind=kind)
    s_b = jnp.asarray(s_b, jnp.float32)
    if s_b.ndim == 1:
        s_b = s_b[None, :]
    return (yq * (s_a * s_b)).astype(a.dtype)


def pv_form(block: int, wl: int, vbl: int) -> str:
    """"rows" or "dot": the form ``bbm_matmul_coded_kblocks`` forms a
    K-block partial of the value product in, for blocks of ``block``
    positions at word length ``wl`` and ``vbl`` nullified bits.

    Decided from static shapes alone, so every caller at a given shape
    runs the same program, and both forms give the same integers (the
    choice never changes a bit).  A block within ``amm_chunk_len`` takes
    the multiply-free rows form: its sum over the block's positions stays
    inside int32, and the v5e compiler's cost model gives it under a
    fifth of the batched dot form's bytes and about three fifths of its
    operations at both served block shapes (PERF.md section 7 row 12).
    A longer block keeps the dot form, whose chunks stay inside the int32
    envelope where the rows sum would not.
    """
    return "rows" if block <= amm_chunk_len(wl, vbl) else "dot"


def bbm_matmul_coded_kblocks(a, b_codes, s_b, *, wl: int, vbl: int,
                             kind: int = 0, block: int):
    """``bbm_matmul_coded`` with per-K-block ``b`` scales (the PV product).

    The V cache quantizes rows in groups of ``block`` positions, so the
    contraction cannot descale once at the end: each K-block's integer
    partial descales by its own ``s_a * s_b[j]`` before the float32
    combine.  All ``K // block`` blocks contract at once, so the op count
    does not grow with the number of blocks: the codes are regrouped to
    (nb, M, block) x (nb, block, N) and every block's exact integer
    partial at the ``2^-vbl`` product scale comes out of one of two forms
    (``pv_form``, chosen from the shapes):

      rows  the V codes' digit planes decoded per block and the
            multiply-free Broken-Booth rows (``bbm_rows_product_scaled``)
            formed over (nb, M, block, N), summed over the block's
            positions in int32 — no dot at all, on the VPU;
      dot   ``booth_precode`` + ``bbm_matmul_scaled`` vmapped over the
            block axis: the dot form's contractions, batched.  The digit
            planes are decoded inside the vmap so they come out block
            axis first (decoding the whole slice and then moving the
            block axis in front costs a transpose of every plane).

    Both partials are the same int32 (a block within
    ``booth_rows.amm_chunk_len`` is one exact chunk of the dot form; a
    longer one keeps the dot form), cast to float32 at ``2^vbl``.  ``a``'s
    dynamic scale is derived once over the whole (M, K) slice, matching
    what the dynamic entry would compute for the same ``a``.  The product
    runs under the ``amm.pv_rows`` or ``amm.pv_dot`` scope.

    The descaled blocks are then added one at a time in block order, in
    a loop over the blocks (``lax.scan``).  Float addition order and
    rounding are part of the bitwise contract with
    ``ref.amm_coded_kblocks_ref``: a reduction over the block axis
    (``jnp.sum``) is free to reassociate the float adds (pairwise or
    tree order), and an unrolled chain of adds fuses with the descales,
    where a backend may contract a multiply and an add into one rounding
    (XLA:CPU does at 4-8 blocks); either would break it.  With a single
    block this reduces exactly to ``bbm_matmul_coded``.

    a: (M, K) float; b_codes: (K, N) codes with K % block == 0;
    s_b: (K // block,) f32.
    """
    kk = b_codes.shape[0]
    if kk % block:
        raise ValueError(f"K={kk} not a multiple of block={block}")
    form = pv_form(block, wl, vbl)
    with jax.named_scope(AMM_PV_ROWS if form == "rows" else AMM_PV_DOT):
        return _coded_kblocks(a, b_codes, s_b, wl=wl, vbl=vbl, kind=kind,
                              block=block, form=form)


def _coded_kblocks(a, b_codes, s_b, *, wl, vbl, kind, block, form):
    """``bbm_matmul_coded_kblocks`` in the given form (same bits)."""
    kk, nn = b_codes.shape
    nb = kk // block
    aq, s_a = amm_quantize(a, wl)
    aq = aq.reshape(aq.shape[0], nb, block).transpose(1, 0, 2)
    b_codes = jnp.asarray(b_codes, jnp.int32).reshape(nb, block, nn)
    if form == "rows":
        _, x_s = split_signed(aq, wl)
        # per-row digits, unstacked, so each row's fuses into the product
        mag, neg = booth_digit_rows(b_codes, wl)          # R x (nb, block, N)
        prod = bbm_rows_product_scaled(
            x_s[:, :, :, None], [m[:, None] for m in mag],
            [g[:, None] for g in neg],
            wl=wl, vbl=vbl, kind=kind)                    # (nb, M, block, N)
        yq = jnp.sum(prod, axis=2, dtype=jnp.int32)
        yq = yq.astype(jnp.float32) * float(1 << vbl)
    else:
        def block_partial(x, codes):
            mag, neg = booth_precode(codes, wl)
            return bbm_matmul_scaled(x, mag, neg, wl=wl, vbl=vbl, kind=kind)

        yq = jax.vmap(block_partial)(aq, b_codes)         # (nb, M, N)
    parts = yq * (s_a * jnp.asarray(s_b, jnp.float32))[:, None, None]
    # the adds run in a loop over the blocks, apart from the descales: in
    # one fused loop a backend may contract a descale and the next add
    # into an FMA, one rounding where the oracle has two (XLA:CPU does at
    # 4-8 blocks), and a barrier between them is dropped before fusion
    acc, _ = jax.lax.scan(lambda acc, p: (acc + p, None), parts[0], parts[1:])
    return acc.astype(a.dtype)


def bbm_matmul_kernel(x_ref, wm_ref, ws_ref, o_ref, *, wl: int, vbl: int,
                      kind: int, shift: int, n_k: int):
    """One (bm, bn) output tile; grid axis 2 streams K blocks."""
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...]                      # (bm, bk) int32, wl-bit codes
    _, x_s = split_signed(x, wl)
    a = x_s[:, :, None]                                      # (bm, bk, 1)
    # (wl//2, bk, bn) digit planes; row r broadcasts (bk, bn) against a
    prod = bbm_rows_product_precoded(a, wm_ref[...], ws_ref[...],
                                     wl=wl, vbl=vbl, kind=kind)
    # per-product rescale then reduce over the k axis of the tile
    if shift:
        prod = prod >> shift
    o_ref[...] += jnp.sum(prod, axis=1, dtype=jnp.int32)


@functools.partial(jax.jit, static_argnames=("wl", "vbl", "kind", "shift",
                                             "bm", "bk", "bn", "interpret",
                                             "form"))
def bbm_matmul_precoded(x, wmag, wneg, *, wl: int, vbl: int, kind: int = 0,
                        shift: int = 0, bm: int = 8, bk: int = 128,
                        bn: int = 128, interpret: bool = False,
                        form: str | None = None):
    """Tiled approximate matmul on precoded weight-digit planes.

    x: (M, K) int32 codes; wmag, wneg: (wl//2, K, N) planes from
    ``booth_precode`` of the (K, N) weight code matrix.
    form: "rows" (VPU row emulation), "dot" (dense contraction + scaled
    truncated rows, on the matmul units) or None (auto: the dot form).
    Bit-identical; ``bm``/``bk``/``bn``/``interpret`` only shape the rows
    form.  Blocks are clamped to the array dims; the TPU lowering needs
    each block's last two dims divisible by (8, 128) or equal to the
    array's, which the defaults are.
    """
    mm, kk = x.shape
    n_rows, kk2, nn = wmag.shape
    if wmag.shape != wneg.shape:
        raise ValueError(f"mag/neg plane shapes differ: "
                         f"{wmag.shape} vs {wneg.shape}")
    if n_rows != num_pp_rows(wl) or kk != kk2:
        raise ValueError(f"digit planes {wmag.shape} do not match "
                         f"wl={wl}, K={kk}")
    if form is None and shift > vbl and mm * kk * nn > _DOT_CORR_BUDGET:
        # only the per-product residual floor (shift > vbl) still
        # materializes an (M, K, N) temporary; the shift <= vbl dot form
        # is fully contracted (_dot_scaled) and has no size cliff
        form = "rows"
    if resolve_form(form) == "dot":
        return _matmul_dotform(x, wmag, wneg, wl=wl, vbl=vbl, kind=kind,
                               shift=shift)
    bm, bk, bn = min(bm, mm), min(bk, kk), min(bn, nn)
    grid = (pl.cdiv(mm, bm), pl.cdiv(nn, bn), pl.cdiv(kk, bk))
    kernel = functools.partial(bbm_matmul_kernel, wl=wl, vbl=vbl, kind=kind,
                               shift=shift, n_k=grid[2])
    plane_spec = pl.BlockSpec((n_rows, bk, bn), lambda i, j, k: (0, k, j))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            plane_spec,
            plane_spec,
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mm, nn), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, wmag, wneg)


@functools.partial(jax.jit, static_argnames=("wl", "vbl", "kind", "shift",
                                             "bm", "bk", "bn", "interpret",
                                             "form"))
def bbm_matmul(x, w, *, wl: int, vbl: int, kind: int = 0, shift: int = 0,
               bm: int = 8, bk: int = 128, bn: int = 128,
               interpret: bool = False, form: str | None = None):
    """Tiled bit-exact approximate matmul.  x: (M, K) w: (K, N), int32 codes.

    Thin raw-code wrapper: precodes ``w`` once (hoisting the recode out of
    the grid, which re-reads every weight block M/bm times) and dispatches
    to ``bbm_matmul_precoded``.
    """
    wmag, wneg = booth_precode(w, wl)
    return bbm_matmul_precoded(x, wmag, wneg, wl=wl, vbl=vbl, kind=kind,
                               shift=shift, bm=bm, bk=bk, bn=bn,
                               interpret=interpret, form=form)
