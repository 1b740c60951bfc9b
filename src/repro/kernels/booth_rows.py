"""Shared Broken-Booth row arithmetic for the Pallas kernels, split into a
decode phase and an accumulate phase.

Hardware Booth multipliers recode the multiplier operand exactly once per
product; in the FIR filterbank and ``bbm_matmul`` one operand — the tap
bank / weight matrix — is *constant* across samples, time blocks and
requests, so its radix-4 digits never change.  The split mirrors that:

  ``booth_precode(bu, wl)``
      decode phase: unsigned wl-bit codes -> per-row digit planes
      ``(mag, neg)``, each of shape ``(wl//2,) + bu.shape``.  ``mag`` is the
      digit magnitude in {0, 1, 2}; ``neg`` is the raw ``b_{2r+1}`` bit —
      the hardware S/sign flag (the 111 "negative zero" triplet has
      ``mag = 0, neg = 1``, which Type1 truncation exposes).  Computed once
      per bank, outside the kernel grid.

  ``bbm_rows_product_precoded(a_s, mag, neg, ...)``
      accumulate phase.  On TPU it is multiply-free: digits are in
      {-2,-1,0,1,2}, so each row contribution is a select among
      ``{0, a_s, a_s << 1}`` with a negate — shift/select/add only, which
      is what the silicon's partial product generators do and what the VPU
      likes (32-bit multiplies are multi-pass there, selects are not).
      Off-TPU (XLA CPU, the Pallas interpreter) the same planes feed a
      one-multiply-per-row form instead, because there ``d * a_s`` is a
      single fast vector op and a select chain is three.  Both forms are
      bit-identical; the ``(x >> m) << m`` truncation (the paper's VBL
      nullification; floor toward -inf for two's complement) is unchanged.

The row planes are stacked on a *leading* axis so kernel BlockSpecs keep
the large dimensions last (TPU lane/sublane friendly): a ``(C, taps)`` bank
precodes to ``(wl//2, C, taps)`` planes tiled exactly like the bank itself.

``bbm_rows_product`` is the raw-code wrapper (decode + accumulate in one
call) kept for callers that do not hoist the recode.  Everything is
resolved at trace time: the row loop is unrolled over the ``wl/2`` radix-4
rows and the per-row mask widths are Python ints, so both phases are safe
to call from inside a Pallas kernel body as well as from plain jitted code.
Bit-exact to the closed forms in ``core.bbm`` (``bbm_type0`` / ``bbm_type1``).

Dot form (the exact-product decomposition): clearing the low ``m`` bits of
a two's-complement value is subtraction of its low bits,
``(p >> m) << m  ==  p - (p & (2^m - 1))``, so every truncated Booth row is
``d_r*A - ((d_r*A) mod 2^m_r)`` and the whole Broken-Booth product
collapses to

    bbm(a, b)  ==  a_s * b_s  -  correction(a mod 2^vbl, digit planes)

where the dominant ``a_s * b_s`` term is an *exact* multiply — so a sum of
BBM products (FIR tap loop, matmul K axis) is one dense integer
contraction on the hardware's native matmul units plus a narrow correction
built entirely from masks on the low ``vbl`` bits of ``a``
(``booth_correction``; only the ``ceil(vbl/2)`` rows with a nonzero break
column participate).  ``bbm_rows_product_dotform`` is the per-element form
of that identity — the third bit-exact accumulate form.

The kernels use the *folded* equivalent: the correction's own linear term
``dot(a mod 2^vbl, h)`` is itself a dense contraction, and folding it back
in shows every BBM product is divisible by ``2^vbl`` —

    bbm(a, b) == 2^vbl * [ a*bq + sum_{r<R} ((d_r*a - neg_r*kind) >> m_r) ]

with ``bq = booth_high_value`` the truncation-surviving digit value.
Accumulating the bracketed scale keeps the dot form inside the rows-form
int32 envelope for every vbl (``dotform_scaled_bound`` carries the
re-derived analysis).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.booth import num_pp_rows

__all__ = ["amm_chunk_len", "bbm_rows_product", "bbm_rows_product_precoded",
           "bbm_rows_product_dotform", "bbm_rows_product_scaled",
           "booth_correction", "booth_digit_rows", "booth_high_value",
           "booth_precode", "booth_precode_faulty", "booth_value",
           "dotform_scaled_bound", "f32_exact_chunk_len", "num_corr_rows",
           "resolve_form", "scaled_trunc_rows", "signed_digit",
           "split_signed"]


def split_signed(x, wl: int):
    """(unsigned wl-bit view, signed reinterpretation) of int32 codes."""
    mask = (1 << wl) - 1
    sign = 1 << (wl - 1)
    xu = x & mask
    return xu, jnp.where(xu >= sign, xu - (1 << wl), xu)


def booth_precode(bu, wl: int):
    """Decode phase: radix-4 digit planes of unsigned wl-bit codes ``bu``.

    Returns ``(mag, neg)`` int32 arrays of shape ``(wl//2,) + bu.shape``:
    ``mag[r]`` the magnitude of Booth digit r in {0, 1, 2} and ``neg[r]``
    the raw ``b_{2r+1}`` sign bit.  The signed digit is ``d = mag`` when
    ``neg == 0`` and ``d = -mag`` when ``neg == 1`` (the 111 triplet gives
    ``mag = 0, neg = 1``).  Call once per constant operand and feed the
    planes to ``bbm_rows_product_precoded``.
    """
    mags, negs = booth_digit_rows(bu, wl)
    return jnp.stack(mags), jnp.stack(negs)


def booth_digit_rows(bu, wl: int):
    """``booth_precode``'s planes as two lists of ``wl//2`` arrays.

    For a caller that decodes inside the same program that consumes the
    digits: unstacked, each row's (mag, neg) can fuse into its consumer
    where a stacked plane array is materialized whole first.  Every
    consumer of the planes indexes them by row, so the lists stand in
    for the stacked arrays.
    """
    bu = jnp.asarray(bu, jnp.int32) & ((1 << wl) - 1)
    mags, negs = [], []
    prev_hi = None
    for r in range(num_pp_rows(wl)):
        # booth digit of b for row r: d = -2*b_hi + b_mid + b_lo
        b_hi = (bu >> (2 * r + 1)) & 1
        b_mid = (bu >> (2 * r)) & 1
        b_lo = jnp.zeros_like(b_mid) if r == 0 else prev_hi
        prev_hi = b_hi
        d = -2 * b_hi + b_mid + b_lo
        mags.append(jnp.abs(d))
        negs.append(b_hi)
    return mags, negs


def booth_precode_faulty(bu, wl: int, fault=None, *, vbl: int = 0):
    """Decode phase with hardware faults injected into the digit planes.

    ``booth_precode`` followed by ``core.faults.apply_plane_faults`` —
    the injection hook every consumer of precoded planes shares, so the
    dot-form datapath, the scalar oracle and a faulted ``PrecodedBank``
    all derive *the same* faulted planes from the same ``FaultSpec``
    (keyed masks depend only on the spec and the plane shape).  A
    ``None``/disabled/non-"plane" spec returns the clean decode
    bit-identically.  ``vbl`` scopes ``rows="corr"`` faults to the
    truncated correction rows of the operating point.
    """
    from ..core.faults import apply_plane_faults
    mag, neg = booth_precode(bu, wl)
    return apply_plane_faults(mag, neg, fault, vbl=vbl)


def bbm_rows_product_precoded(a_s, mag, neg, *, wl: int, vbl: int, kind: int,
                              multiply_free: bool | None = None):
    """Accumulate phase: Broken-Booth product from precoded digit planes.

    ``a_s`` is a signed int32 array; ``mag[r]`` / ``neg[r]`` must broadcast
    against it (planes from ``booth_precode``).  Bit-identical to
    ``core.bbm.bbm_mul`` for in-range operands; ``vbl = 0`` reduces both
    kinds to the exact Booth product.

    ``multiply_free`` picks the row-contribution form (same values either
    way, decided at trace time):

      True   select among ``{0, a_s, a_s << 1}`` + negate — the silicon
             partial-product generator, and the fast form on the TPU VPU,
             where a 32-bit multiply is multi-pass and a select is not.
      False  one ``d * a_s`` multiply per row — the fast form everywhere
             XLA lowers to real vector ISAs (CPU, the interpreter), where
             an int32 multiply is a single op and the select chain is
             three.
      None   auto: multiply-free on TPU backends, multiply elsewhere.
    """
    if multiply_free is None:
        multiply_free = jax.default_backend() == "tpu"
    # the shared "2A" generate feeds only the select form; the multiply
    # form folds the digit into the (small) plane and never reads it
    a2 = a_s << 1 if multiply_free else None
    prod = None
    for r in range(num_pp_rows(wl)):
        rows = _booth_row(a_s, a2, mag[r], neg[r], kind=kind,
                          multiply_free=multiply_free)
        m = max(0, vbl - 2 * r)           # bits nullified in this row
        contrib = (rows >> m) << m        # floor for two's complement
        if kind == 1 and m == 0:          # S dot survives only at m == 0
            contrib = contrib + neg[r]
        term = contrib << (2 * r)
        prod = term if prod is None else prod + term
    return prod


def bbm_rows_product_scaled(a_s, mag, neg, *, wl: int, vbl: int, kind: int,
                            multiply_free: bool | None = None):
    """``bbm_rows_product_precoded(...) >> vbl``, formed at that scale.

    Every Broken-Booth product is a multiple of ``2^vbl`` (row r < R is
    floored to a multiple of ``2^(m_r + 2r) = 2^vbl``, and every row above
    sits at ``4^r >= 2^vbl``), so the product divided by ``2^vbl`` is an
    exact integer: row r < R contributes its row value floored by
    ``m_r`` bits, unshifted, and row r >= R its row value (with Type1's S
    dot) shifted up by ``2r - vbl``.  That drops the ``<< m`` and
    ``<< 2r`` of each truncated row and keeps a sum of ``2^(2wl-1-vbl)``
    magnitudes in int32 — the scale the dot form's ``_dot_scaled``
    accumulates at, so a K-sum of these equals its partial bit for bit.
    Same operands, broadcasting and ``multiply_free`` rule as
    ``bbm_rows_product_precoded``.
    """
    if multiply_free is None:
        multiply_free = jax.default_backend() == "tpu"
    a2 = a_s << 1 if multiply_free else None
    prod = None
    for r in range(num_pp_rows(wl)):
        rows = _booth_row(a_s, a2, mag[r], neg[r], kind=kind,
                          multiply_free=multiply_free)
        m = vbl - 2 * r
        if m > 0:
            term = rows >> m
        else:
            if kind == 1:
                rows = rows + neg[r]
            term = rows << -m
        prod = term if prod is None else prod + term
    return prod


def _booth_row(a_s, a2, m_r, s_r, *, kind: int, multiply_free: bool):
    """Row r's partial product before truncation: ``d_r * a_s``, or for
    Type1 the one's complement ``-|d_r| * a_s - 1`` of a negative row
    (the S dot is added back by the caller where the row keeps it)."""
    if multiply_free:
        pos = jnp.where(m_r == 2, a2, jnp.where(m_r == 1, a_s, 0))
    if kind == 0:
        if multiply_free:
            return jnp.where(s_r == 1, -pos, pos)
        # fold the sign into the (small) digit plane: one full-size
        # multiply per row, no full-size select at all
        return signed_digit(m_r, s_r) * a_s
    if not multiply_free:
        pos = m_r * a_s
    return jnp.where(s_r == 1, -pos - 1, pos)


def signed_digit(mag_r, neg_r):
    """Signed Booth digit of one row plane: ``d = -mag`` when ``neg``.

    The single place the (mag, neg) encoding is turned back into a signed
    digit — every form (value reconstruction, correction, dot kernels)
    goes through here, so an encoding change has one site to touch.
    """
    return jnp.where(neg_r == 1, -mag_r, mag_r)


def num_corr_rows(wl: int, vbl: int) -> int:
    """Rows whose break column is nonzero: only they feed the correction.

    Row r nullifies ``m_r = max(0, vbl - 2r)`` bits, so rows with
    ``2r >= vbl`` contribute nothing; ``vbl = 0`` means no correction at
    all (the exact Booth product).
    """
    return min(num_pp_rows(wl), (vbl + 1) // 2)


def booth_value(mag, neg, *, wl: int):
    """Signed multiplier value reconstructed from its digit planes.

    ``sum_r d_r * 4^r == to_signed(b, wl)`` — the radix-4 recode is exact —
    so precoded callers never need the raw codes to form the dense
    contraction operand of the dot form.  Bank-sized work (tiny next to
    the signal), safe inside jit.
    """
    val = None
    for r in range(num_pp_rows(wl)):
        term = signed_digit(mag[r], neg[r]) << (2 * r)
        val = term if val is None else val + term
    return val


def booth_correction(a_s, mag, neg, *, wl: int, vbl: int, kind: int):
    """Low-bit correction ``c >= 0`` with ``bbm(a, b) == a_s*b_s - c``.

    Derivation: ``(p >> m) << m == p - (p & (2^m - 1))`` for two's
    complement, so per row

      Type0:  trunc_r = d_r*A - ((d_r*A) & mask_r)
      Type1:  row_r   = d_r*A - neg_r          (one's complement + S dot)
              trunc_r + sdot_r = d_r*A - [((d_r*A - neg_r) & mask_r)
                                          + neg_r]   for m_r > 0

    and ``sum_r d_r*A*4^r`` is the exact product.  Every masked term
    depends only on the low ``m_r <= vbl`` bits of ``A``, so the whole
    correction runs on ``a_s & (2^vbl - 1)`` — narrow masks and adds, no
    wide arithmetic.  ``vbl = 0`` returns the all-zero correction.

    ``mag[r]`` / ``neg[r]`` must broadcast against ``a_s`` exactly as in
    ``bbm_rows_product_precoded``; the result has the broadcast shape.
    """
    a_low = a_s & ((1 << vbl) - 1)        # nonneg, < 2^vbl: narrow products
    corr = None
    for r in range(num_corr_rows(wl, vbl)):
        m = vbl - 2 * r                   # > 0 for every correction row
        mask = (1 << m) - 1
        rows = signed_digit(mag[r], neg[r]) * a_low
        if kind == 0:
            term = rows & mask
        else:
            # the 111 "negative zero" triplet (mag 0, neg 1) lands here
            # too: ((0 - 1) & mask) + 1 == 2^m, the dropped all-ones row
            term = ((rows - neg[r]) & mask) + neg[r]
        t = term << (2 * r)
        corr = t if corr is None else corr + t
    if corr is None:
        shape = jnp.broadcast_shapes(jnp.shape(a_s), jnp.shape(mag[0]))
        corr = jnp.zeros(shape, jnp.int32)
    return corr


def bbm_rows_product_dotform(a_s, mag, neg, *, wl: int, vbl: int, kind: int):
    """Third bit-exact accumulate form: exact product minus correction.

    ``a_s * booth_value(planes) - booth_correction(...)`` — the
    per-element statement of the dot-form identity.  Same contract as
    ``bbm_rows_product_precoded`` (bit-identical to ``core.bbm.bbm_mul``);
    the payoff comes when the exact term is *summed* before the correction
    (FIR tap loop, matmul K axis): the sum is then one dense contraction
    on the matmul units (see the kernel dot forms and
    ``dotform_scaled_bound``).
    """
    b_s = booth_value(mag, neg, wl=wl)
    return a_s * b_s - booth_correction(a_s, mag, neg, wl=wl, vbl=vbl,
                                        kind=kind)


def booth_high_value(mag, neg, *, wl: int, vbl: int):
    """Truncation-surviving digit value, pre-divided by ``2^vbl``.

    The rows with a nonzero break column (r < R) lose their low bits to
    the VBL nullification; the rows above survive intact and their summed
    weight ``sum_{r >= R} d_r * 4^r`` is divisible by ``2^vbl`` (because
    ``2R >= vbl``).  Returns ``bq = sum_{r >= R} d_r << (2r - vbl)`` — the
    integer the dot form contracts the *full* signal against.  ``vbl = 0``
    reduces to ``booth_value`` (the exact multiplier).
    """
    r0 = num_corr_rows(wl, vbl)
    bq = None
    for r in range(r0, num_pp_rows(wl)):
        term = signed_digit(mag[r], neg[r]) << (2 * r - vbl)
        bq = term if bq is None else bq + term
    if bq is None:
        bq = jnp.zeros(jnp.shape(mag[0]), jnp.int32)
    return bq


def scaled_trunc_rows(a_s, mag, neg, *, wl: int, vbl: int, kind: int):
    """``Q = sum_{r<R} ((d_r*a - neg_r*kind) >> m_r)`` — the folded dot
    form's truncated-row term, at the ``2^-vbl`` product scale.

    The one implementation of the per-row truncation semantics (including
    Type1's ``- neg_r`` and the negative-zero 111 triplet) shared by every
    dot-form kernel; ``mag[r]`` / ``neg[r]`` broadcast against ``a_s``.
    Returns ``None`` when no row is truncated (``vbl = 0``).
    """
    q = None
    for r in range(num_corr_rows(wl, vbl)):
        rowp = signed_digit(mag[r], neg[r]) * a_s
        if kind == 1:
            rowp = rowp - neg[r]
        qr = rowp >> (vbl - 2 * r)
        q = qr if q is None else q + qr
    return q


def dotform_scaled_bound(k: int, wl: int, vbl: int, shift: int) -> int:
    """Worst-case |accumulator| of the dot form — the re-derived envelope.

    The naive reading of "accumulate exact products, then subtract the
    correction" overflows int32 long before the rows form does (the raw
    ``sum_k a*b`` is ``2^vbl`` larger than the truncated sum).  The fix is
    algebraic, not a wider accumulator: every truncated row term is
    divisible by ``2^vbl`` (row r < R contributes
    ``((d_r*a - neg_r*kind) >> m_r) * 2^(m_r + 2r)`` with
    ``m_r + 2r == vbl``; row r >= R contributes ``d_r*a*4^r`` with
    ``2r >= vbl``), so the *whole BBM product* is ``2^vbl * M`` and the
    dot form accumulates the scaled ``M = a*bq + sum_r q_r`` directly:

        y = (dot(a, bq) + sum_k sum_{r<R} q_{r,k}) << (vbl - shift)

    (per-product ``>> (shift - vbl)`` inside the sum when shift > vbl).
    Accumulating at scale ``2^-max(vbl, shift)`` bounds the partial sums
    by ``k * 2^(2wl - 1 - max(vbl, shift))`` — never looser than the rows
    envelope ``k * 2^(2wl - 1 - shift)``, so the dot form is int32-safe
    whenever the rows form is, for every vbl.  Returns that bound.
    """
    return k * 2 ** max(2 * wl - 1 - max(vbl, shift), 0)


def amm_chunk_len(wl: int, vbl: int) -> int:
    """Largest K-chunk the contracted dot form accumulates int32-exactly.

    The contracted lowering (``bbm_matmul.bbm_matmul_scaled`` and the
    ``amm_dense`` bitexact mode built on it) sums BBM products at their
    natural ``2^-vbl`` scale through three int32 intermediates, each with
    its own worst-case growth per accumulated product:

      * the scaled total ``M = a*bq + sum_r q_r``:   ``2^(2wl - 1 - vbl)``
        (``dotform_scaled_bound``),
      * the per-row digit contraction ``dot(a, d_r)``:  ``2^wl``
        (``|d| <= 2``, ``|a| <= 2^(wl-1)``),
      * the per-row mod-term contraction:             ``< 2^vbl``
        (each residue is ``< 2^m_r <= 2^vbl``).

    A chunk of this length keeps every one of them strictly inside int32,
    so chunk partials are *exact integers* and any cross-chunk combine
    order gives the same result — the property the oracle-equality tests
    lean on.  Returns at least 1 (``wl = 16, vbl = 0`` degenerates to
    per-product chunks: the exact full-scale product alone fills int32).
    """
    bound = 2 ** 31 - 1
    c = bound >> max(2 * wl - 1 - vbl, 0)
    if num_corr_rows(wl, vbl):
        c = min(c, bound >> (wl + 1), bound >> vbl)
    return max(c, 1)


def f32_exact_chunk_len(wl: int, vbl: int) -> int:
    """Largest K-chunk the dot form contracts *exactly* in float32.

    Same three intermediates as ``amm_chunk_len``, tighter budget: every
    integer of magnitude <= 2^24 is exact in float32, and when the sum of
    |term| over a chunk stays <= 2^24 every partial sum — in *any*
    association order, so tree-reducing matmul units included — is an
    exactly-representable integer and every add is exact.  Chunks of this
    length therefore let the dot form's contractions ride the f32 matmul
    units (measured ~5x the s32 dot throughput on CPU XLA; the native MXU
    lanes on TPU at HIGHEST precision) while remaining bit-identical to
    the int32 contraction.  Unlike ``amm_chunk_len`` this may return 0 —
    operating points whose single product already overflows the budget
    (e.g. wl=16, vbl<=6) have no exact f32 envelope and keep s32 dots.
    """
    bound = 1 << 24
    c = bound >> max(2 * wl - 1 - vbl, 0)
    if num_corr_rows(wl, vbl):
        c = min(c, bound >> (wl + 1), bound >> vbl)
    return c


def resolve_form(form: str | None) -> str:
    """Trace-time accumulate-form selection: "rows" | "dot" | None (auto).

    ``None`` picks the dot form: its re-derived envelope
    (``dotform_scaled_bound``) is never looser than the rows envelope, so
    no operating point needs a *numerical* fallback, and it is the faster
    form wherever the backend has real matmul/vector throughput.  (The
    kernel entry points still route oversized auto-form calls to "rows"
    for *memory* reasons — their windowed / correction temporaries trade
    against the rows form's streaming; see ``_DOT_WINDOW_BUDGET`` /
    ``_DOT_CORR_BUDGET`` at the call sites.)  ``"rows"`` keeps the
    streaming Pallas emulation.
    """
    if form in (None, "dot"):
        return "dot"
    if form == "rows":
        return "rows"
    raise ValueError(f"unknown accumulate form {form!r} "
                     f"(expected 'rows', 'dot' or None)")


def bbm_rows_product(a_s, bu, *, wl: int, vbl: int, kind: int):
    """Broken-Booth product of signed ``a_s`` and unsigned wl-bit ``bu``.

    Raw-code wrapper: decodes ``bu`` then accumulates, for callers whose
    multiplier operand is not constant (or not worth hoisting).  ``a_s``
    and ``bu`` are int32 arrays with broadcast-compatible shapes; the
    result has the broadcast shape.  Bit-identical to
    ``core.bbm.bbm_mul(a, b, wl, vbl, kind)`` for in-range operands.
    """
    mag, neg = booth_precode(bu, wl)
    return bbm_rows_product_precoded(a_s, mag, neg, wl=wl, vbl=vbl,
                                     kind=kind)
