"""Pallas TPU kernel: flash attention (blockwise online softmax).

The serving/training fast path for the 32k prefill shapes.  Standard
two-level blocking: grid = (batch*heads, Q blocks, KV blocks); the running
max/denominator/accumulator live in VMEM scratch across the KV axis (declared
"arbitrary" so the revisits are sequential).

Causal masking is applied at block granularity: KV blocks entirely in the
future are masked via the per-element comparison (the pure-JAX chunked
attention in models/attention.py skips them outright; the kernel keeps the
grid static).

Validated against ref.attention_ref in interpret mode over shape/dtype sweeps
(tests/test_kernels.py).  The multi-pod dry-run deliberately lowers the pure
JAX path instead (Pallas kernels do not lower to the CPU backend used for the
512-device compile check) — selected by ModelRuntime.use_pallas_attention.

Approximate attention (``flash_attention_amm``): the Broken-Booth product
*does* graft into this tile arithmetic — PR 3's identity makes every
approximate block product an exact integer dot minus a ceil(vbl/2)-row
correction, which is plain (bq, bk)-tile matmul work.  The lowering
contract: Q/K/V are quantized to wl-bit int32 codes *outside* the grid
(``ref.amm_quantize`` per (batch*head, block) — the same per-slice scales
``bbm_matmul_dynamic`` derives under ``amm_dot``'s vmap), and the kernel
takes codes + per-block scales + K's precoded radix-4 digit planes as
operands.  Each tile's score block is ``exact_dot - correction`` via the
``_dot_scaled`` branch structure (``bbm_matmul.dot_scaled_chunked``: digit
dot minus per-(digit, sign) one-hot residue dots), with the integer
accumulation completing before the online-softmax renormalization touches
it — the docs/attention.md envelope argument, per tile.  The PV product
gets the same treatment against V's inline-decoded planes; the
probability block is quantized in-tile (it exists nowhere else).  The
m/l/acc VMEM scratch scheme is unchanged from the exact kernel.  Off-TPU
the same tile step runs as a jitted XLA scan (``use_kernel=False``), and
the tile contractions ride the f32 matmul units through the exact-f32
envelope (``booth_rows.f32_exact_chunk_len``) — bit-identical to the s32
dots, and the reason flash-amm beats the chunked path on wall clock.
Routing lives in ``models.attention.attention``; bitwise equality against
the chunked-amm path is the tests/test_flash_amm.py contract.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .bbm_matmul import dot_scaled_chunked
from .booth_rows import booth_precode
from .ref import amm_quantize

__all__ = ["flash_attention", "flash_attention_amm", "flash_amm_operands",
           "FLASH_AMM_BQ", "FLASH_AMM_BK"]

NEG_INF = -1e30

# flash-amm tile sizes: the chunked-amm reference must be run at the same
# blocking for the bitwise-equality contract (quantization is per block)
FLASH_AMM_BQ = 128
FLASH_AMM_BK = 128


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                 scale: float, causal: bool, bq: int, bk: int, n_kv: int,
                 skv: int):
    kv_idx = pl.program_id(2)

    @pl.when(kv_idx == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0]                                   # (bq, d)
    k = k_ref[0]                                   # (bk, d)
    v = v_ref[0]                                   # (bk, d)
    # zero out-of-range KV rows: the final block may be padded with
    # uninitialized memory, and 0 * NaN would poison the p @ v product.
    kv_rows = kv_idx * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
    kv_valid = kv_rows < skv
    k = jnp.where(kv_valid, k, 0)
    v = jnp.where(kv_valid, v, 0)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale

    k_pos = kv_idx * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    live = k_pos < skv
    if causal:
        q_pos = pl.program_id(1) * bq + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bk), 0)
        live = live & (q_pos >= k_pos)
    s = jnp.where(live, s, NEG_INF)

    m_prev = m_scr[...]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_cur)
    alpha = jnp.exp(m_prev - m_cur)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_scr[...] = m_cur

    @pl.when(kv_idx == n_kv - 1)
    def _done():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk",
                                             "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, bq: int = 128,
                    bk: int = 128, interpret: bool = False):
    """q: (B, H, Sq, D); k, v: (B, H, Skv, D) -> (B, H, Sq, D).

    GQA is handled by the caller repeating KV heads (or by reshaping groups
    into the batch axis); the kernel sees matched head counts.
    """
    b, h, sq, d = q.shape
    _, _, skv, _ = k.shape
    bq = min(bq, sq)
    bk = min(bk, skv)
    qf = q.reshape(b * h, sq, d)
    kf = k.reshape(b * h, skv, d)
    vf = v.reshape(b * h, skv, d)
    grid = (b * h, pl.cdiv(sq, bq), pl.cdiv(skv, bk))
    kernel = functools.partial(
        _attn_kernel, scale=1.0 / (d ** 0.5), causal=causal,
        bq=bq, bk=bk, n_kv=grid[2], skv=skv)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda g, i, j: (g, i, 0)),
            pl.BlockSpec((1, bk, d), lambda g, i, j: (g, j, 0)),
            pl.BlockSpec((1, bk, d), lambda g, i, j: (g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda g, i, j: (g, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, h, sq, d)


# ------------------------------------------------------------- flash + amm
def _amm_product(af, bf, ac, bmag, bneg, s_a, s_b, *, wl: int, vbl: int,
                 kind: int):
    """One tile product through the amm datapath — ``amm_dot`` per tile.

    Replicates the straight-through composition of
    ``models.common.amm_dot`` over ``bbm_matmul_dynamic`` exactly: exact
    f32 dot, dot-form approximate product from pre-derived codes/planes/
    scales, ``exact + stop_gradient(approx - exact)``.  The only
    difference is *where* the pieces were computed (codes and scales
    arrive as operands instead of being derived in-call) and that the
    integer contractions take the exact-f32-envelope fast path
    (``f32_dots=True``) — both bit-preserving.
    """
    exact = af @ bf
    yq = dot_scaled_chunked(ac, bmag, bneg, wl=wl, vbl=vbl, kind=kind,
                            f32_dots=True)
    approx = (yq * (s_a * s_b)).astype(af.dtype)
    return exact + jax.lax.stop_gradient(approx - exact)


def _amm_tile_step(m_prev, l_prev, acc_prev, qf, kf, vf, qc, kmag, kneg, vc,
                   s_q, s_k, s_v, q_idx, kv_idx, *, wl: int, vbl: int,
                   kind: int, causal: bool, bq: int, bk: int, kv_len: int):
    """One (q-block, kv-block) online-softmax step on the amm datapath.

    The single source of truth for the flash-amm tile arithmetic: the
    Pallas kernel body and the off-TPU XLA scan both call this, so the
    two lowerings cannot drift.  Operand shapes (one tile):
    qf (bq, d) f32 pre-scaled queries, kf/vf (bk, d) f32, qc (bq, d) i32
    codes, kmag/kneg (wl//2, d, bk) K digit planes, vc (bk, d) i32 codes,
    s_q/s_k/s_v scalar block scales; m/l/acc are (bq, 1)/(bq, 1)/(bq, d).

    Float op order is copied from ``chunked_attention``'s kv_block —
    score product, mask, max, exp, renormalize, PV product, accumulate —
    because bitwise equality with that path is the contract.  The P block
    is quantized here (it exists only inside the step) and V's planes are
    decoded inline from its codes; both are elementwise and tile-local.
    """
    s = _amm_product(qf, kf.swapaxes(-1, -2), qc, kmag, kneg, s_q, s_k,
                     wl=wl, vbl=vbl, kind=kind)             # (bq, bk)
    q_pos = q_idx * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = kv_idx * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    live = k_pos < kv_len
    if causal:
        live = live & (q_pos >= k_pos)
    s = jnp.where(live, s, NEG_INF)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + p.sum(axis=-1, keepdims=True)
    pc, s_p = amm_quantize(p, wl)
    vmag, vneg = booth_precode(vc, wl)
    pv = _amm_product(p, vf, pc, vmag, vneg, s_p, s_v,
                      wl=wl, vbl=vbl, kind=kind)            # (bq, d)
    acc_new = acc_prev * alpha + pv
    return m_new, l_new, acc_new


def _attn_amm_kernel(qf_ref, kf_ref, vf_ref, qc_ref, km_ref, kn_ref, vc_ref,
                     qs_ref, ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr, *,
                     wl: int, vbl: int, kind: int, causal: bool, bq: int,
                     bk: int, n_kv: int, kv_len: int):
    """Pallas body: ``_amm_tile_step`` + the exact kernel's scratch scheme.

    qs/ks/vs are the whole flattened ``(bh * nq|nk,)`` per-block scale
    arrays in SMEM, read as scalars at this grid point's block.
    """
    g, q_idx, kv_idx = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    n_q = pl.num_programs(1)

    @pl.when(kv_idx == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    m, l, acc = _amm_tile_step(
        m_scr[...], l_scr[...], acc_scr[...],
        qf_ref[0], kf_ref[0], vf_ref[0], qc_ref[0], km_ref[0], kn_ref[0],
        vc_ref[0], qs_ref[g * n_q + q_idx], ks_ref[g * n_kv + kv_idx],
        vs_ref[g * n_kv + kv_idx],
        q_idx, kv_idx, wl=wl, vbl=vbl, kind=kind, causal=causal,
        bq=bq, bk=bk, kv_len=kv_len)
    m_scr[...] = m
    l_scr[...] = l
    acc_scr[...] = acc

    @pl.when(kv_idx == n_kv - 1)
    def _done():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("wl", "vbl", "kind", "causal",
                                             "bq", "bk", "kv_len",
                                             "interpret"))
def _flash_amm_pallas(qf, kf, vf, qc, kmag, kneg, vc, qs, ks, vs, *,
                      wl: int, vbl: int, kind: int, causal: bool, bq: int,
                      bk: int, kv_len: int, interpret: bool):
    """Pallas dispatch: grid (batch*heads, Q blocks, KV blocks)."""
    bh, sqp, d = qf.shape
    _, skvp, _ = kf.shape
    nr = kmag.shape[1]
    nq, nk = sqp // bq, skvp // bk
    kmag = kmag.reshape(bh, nr, d, nk * bk)
    kneg = kneg.reshape(bh, nr, d, nk * bk)
    grid = (bh, nq, nk)
    kernel = functools.partial(_attn_amm_kernel, wl=wl, vbl=vbl, kind=kind,
                               causal=causal, bq=bq, bk=bk, n_kv=nk,
                               kv_len=kv_len)
    plane_spec = pl.BlockSpec((1, nr, d, bk), lambda g, i, j: (g, 0, 0, j))
    # per-block scales: a (1, 1) VMEM block is not (8, 128)-tileable, so
    # each whole scale array sits in SMEM and the body reads its scalar
    scale_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda g, i, j: (g, i, 0)),   # qf
            pl.BlockSpec((1, bk, d), lambda g, i, j: (g, j, 0)),   # kf
            pl.BlockSpec((1, bk, d), lambda g, i, j: (g, j, 0)),   # vf
            pl.BlockSpec((1, bq, d), lambda g, i, j: (g, i, 0)),   # qc
            plane_spec,                                            # kmag
            plane_spec,                                            # kneg
            pl.BlockSpec((1, bk, d), lambda g, i, j: (g, j, 0)),   # vc
            scale_spec, scale_spec, scale_spec,                    # qs ks vs
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda g, i, j: (g, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sqp, d), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf, qc, kmag, kneg, vc, qs.reshape(-1), ks.reshape(-1),
      vs.reshape(-1))


@functools.partial(jax.jit, static_argnames=("wl", "vbl", "kind", "causal",
                                             "bq", "bk", "kv_len"))
def _flash_amm_xla(qf, kf, vf, qc, kmag, kneg, vc, qs, ks, vs, *,
                   wl: int, vbl: int, kind: int, causal: bool, bq: int,
                   bk: int, kv_len: int):
    """Off-TPU lowering of the same tile step: vmap over (batch*heads,
    Q blocks), ``lax.scan`` over KV blocks — one fused XLA program, no
    per-block score materialization, and bit-identical to the kernel (the
    tile arithmetic is shared; only the loop plumbing differs)."""
    bh, sqp, d = qf.shape
    _, skvp, _ = kf.shape
    nq, nk = sqp // bq, skvp // bk
    qfb = qf.reshape(bh, nq, bq, d)
    qcb = qc.reshape(bh, nq, bq, d)
    kfb = kf.reshape(bh, nk, bk, d)
    vfb = vf.reshape(bh, nk, bk, d)
    vcb = vc.reshape(bh, nk, bk, d)
    kmb = kmag.transpose(0, 3, 1, 2, 4)        # (bh, nk, nr, d, bk)
    knb = kneg.transpose(0, 3, 1, 2, 4)

    def q_block(qi, qf_i, qc_i, qs_i, kfh, vfh, kmh, knh, vch, ksh, vsh):
        init = (jnp.full((bq, 1), NEG_INF, jnp.float32),
                jnp.zeros((bq, 1), jnp.float32),
                jnp.zeros((bq, d), jnp.float32))

        def body(carry, inp):
            ki, kf_j, vf_j, km_j, kn_j, vc_j, ks_j, vs_j = inp
            carry = _amm_tile_step(*carry, qf_i, kf_j, vf_j, qc_i, km_j,
                                   kn_j, vc_j, qs_i, ks_j, vs_j, qi, ki,
                                   wl=wl, vbl=vbl, kind=kind, causal=causal,
                                   bq=bq, bk=bk, kv_len=kv_len)
            return carry, None

        (m, l, acc), _ = jax.lax.scan(
            body, init, (jnp.arange(nk), kfh, vfh, kmh, knh, vch, ksh, vsh))
        return acc / jnp.maximum(l, 1e-30)

    per_head = jax.vmap(
        q_block, in_axes=(0, 0, 0, 0) + (None,) * 7)
    out = jax.vmap(per_head, in_axes=(None, 0, 0, 0) + (0,) * 7)(
        jnp.arange(nq), qfb, qcb, qs, kfb, vfb, kmb, knb, vcb, ks, vs)
    return out.reshape(bh, sqp, d)


def flash_attention_amm(q, k, v, *, wl: int, vbl: int, kind: int,
                        causal: bool = True, bq: int = FLASH_AMM_BQ,
                        bk: int = FLASH_AMM_BK, use_kernel=None,
                        interpret=None):
    """Flash attention on the Broken-Booth datapath.  (B, H, S, D) in/out.

    q: (B, H, Sq, D); k, v: (B, H, Skv, D) with matched head counts (the
    caller repeats KV heads for GQA, as for ``flash_attention``).
    wl/vbl/kind: the dot-form lowering parameters
    (``AmmRuntime.attn_lowering``).  use_kernel: None picks the Pallas
    kernel on TPU and the fused XLA scan elsewhere; both run the shared
    ``_amm_tile_step``.  interpret: kernel-path interpret mode (None:
    interpret off-TPU — CPU CI runs the kernel this way).

    Bit-identical to ``chunked_attention(..., bq, bk, amm)`` at matched
    head counts and tile sizes: the decode phase here (this wrapper, not
    the grid) quantizes Q/K/V per (batch*head, block) with
    ``ref.amm_quantize`` — the same slices, hence the same dynamic-range
    scales, that ``amm_dot``'s vmapped ``bbm_matmul_dynamic`` derives
    per kv-block on the chunked path — and precodes K's digit planes
    once for the whole grid (every q-block revisits them).  Deliberately
    not jitted as a unit, mirroring ``bbm_matmul_dynamic``: the quantize
    runs op-by-op so the per-compilation-context bitwise contract against
    the chunked path holds.
    """
    b, h, sq, d = q.shape
    ops, bq, bk = flash_amm_operands(q, k, v, wl=wl, bq=bq, bk=bk)
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    if use_kernel:
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        out = _flash_amm_pallas(*ops, wl=wl, vbl=vbl, kind=kind,
                                causal=causal, bq=bq, bk=bk,
                                kv_len=k.shape[2], interpret=interpret)
    else:
        out = _flash_amm_xla(*ops, wl=wl, vbl=vbl, kind=kind, causal=causal,
                             bq=bq, bk=bk, kv_len=k.shape[2])
    return out[:, :sq].reshape(b, h, sq, d).astype(q.dtype)


def flash_amm_operands(q, k, v, *, wl: int, bq: int = FLASH_AMM_BQ,
                       bk: int = FLASH_AMM_BK):
    """Decode phase of ``flash_attention_amm``: the grid's operands.

    Pads Q/K/V to whole tiles, quantizes them per (batch*head, block)
    with ``ref.amm_quantize`` and precodes K's digit planes.  Returns
    ``(ops, bq, bk)``: ``ops = (qf, kf, vf, qc, kmag, kneg, vc, qs, ks,
    vs)`` as both lowerings take them, the tiles clamped to the sequence
    lengths.
    """
    b, h, sq, d = q.shape
    _, _, skv, _ = k.shape
    bq = min(bq, sq)
    bk = min(bk, skv)
    nq, nk = -(-sq // bq), -(-skv // bk)
    pad_q = nq * bq - sq
    pad_k = nk * bk - skv
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    bh = b * h
    # the chunked path scales queries *before* quantization (q_block);
    # padded rows/cols are zeros there too, so scales match exactly
    qf = q.reshape(bh, nq * bq, d).astype(jnp.float32) * (1.0 / d ** 0.5)
    kf = k.reshape(bh, nk * bk, d).astype(jnp.float32)
    vf = v.reshape(bh, nk * bk, d).astype(jnp.float32)
    quant = jax.vmap(jax.vmap(lambda t: amm_quantize(t, wl)))
    qc, qs = quant(qf.reshape(bh, nq, bq, d))
    kc, ks = quant(kf.reshape(bh, nk, bk, d))   # == quantize of k^T blocks
    vc, vs = quant(vf.reshape(bh, nk, bk, d))
    qc = qc.reshape(bh, nq * bq, d)
    vc = vc.reshape(bh, nk * bk, d)
    # K's radix-4 digit planes, decoded once per call over the k^T code
    # blocks: (wl//2, bh, nk, d, bk) -> (bh, wl//2, d, nk, bk)
    kmag, kneg = booth_precode(kc.transpose(0, 1, 3, 2), wl)
    kmag = kmag.transpose(1, 0, 3, 2, 4)
    kneg = kneg.transpose(1, 0, 3, 2, 4)
    return (qf, kf, vf, qc, kmag, kneg, vc, qs, ks, vs), bq, bk
