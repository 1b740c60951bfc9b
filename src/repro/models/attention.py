"""Attention blocks: GQA (llama/qwen/grok/yi/chameleon/zamba/whisper) and
MLA (deepseek-v3), with chunked online-softmax attention for long context.

The chunked path is pure JAX (lax.scan over query and KV blocks) so it
lowers on any backend — it is what the 512-device dry-run compiles.  On TPU
the Pallas flash kernel (kernels/flash_attention.py) is selected via
``use_pallas`` (numerics validated equal in tests).

Approximate attention (``amm=``): the score product ``Q @ K^T`` and the
value product ``P @ V`` can route through the bit-exact Broken-Booth
dot-form datapath (``models.common.amm_dot`` on
``kernels.bbm_matmul_dynamic``) — the activation x activation counterpart
of the MLPs' ``amm_dense``.  Both products are formed *per KV block*, each
block's integer accumulation completing before any online-softmax
renormalization touches its result, so the softmax algebra composes
unchanged (docs/attention.md carries the envelope argument).

Routing (prefill, no cache) — ``use_pallas`` picks the flash lowering for
both exact *and* amm attention:
  * exact-flash:  ``use_pallas``, ``amm`` inactive — the Pallas kernel in
    kernels/flash_attention.py.
  * flash-amm:    ``use_pallas``, ``amm`` active with a Booth-family
    bitexact lowering — ``kernels.flash_attention.flash_attention_amm``
    (Pallas kernel on TPU, fused XLA scan elsewhere), wrapped in a
    ``custom_vjp`` whose backward is the chunked path's STE gradient.
  * chunked-amm / chunked-exact: everything else — the pure-JAX path
    below, which is also the flash-amm bit-equality reference
    (``flash_amm_chunked_equiv``) and the oracle-comparison path.
Falling off the flash path while ``use_pallas`` was requested (sequence
cap, amm family without a lowering) emits a ``FlashFallbackWarning``
naming the reason, so long-context runs can tell why they landed on the
chunked path.

KV caches are ``(batch, seq, kv_heads, head_dim)`` per tensor (MLA caches the
compressed latent ``(batch, seq, kv_latent+rope)``), updated with
``dynamic_update_slice`` at the decode position — a scalar, or a (B,)
per-slot vector under continuous batching.  The serving-side int-code
variant (``serve.kv_cache``) stores wl-bit codes plus per-block f32 scales
instead of float values; ``code_cache_update`` freezes each token's codes
at write time and ``decode_attention_codes`` contracts them directly
(docs/serving.md).
"""
from __future__ import annotations

import warnings
from functools import partial
from typing import Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from .. import trace
from ..configs.base import ArchConfig
from .common import Spec, amm_dot, apply_rope, rmsnorm

__all__ = ["attn_table", "mla_table", "attention", "mla_attention",
           "mla_project", "mla_absorb_query", "latent_attention",
           "latent_attention_codes",
           "chunked_attention", "code_cache_dequant", "code_cache_update",
           "decode_attention", "decode_attention_codes",
           "flash_amm_chunked_equiv", "FlashFallbackWarning",
           "reset_flash_fallback_dedup"]

NEG_INF = -1e30

# flash-path sequence cap: above this the kernel's (batch*heads, S, D)
# operand working set outgrows the tested envelope and the chunked path is
# selected instead.  Module-level so tests (and long-context experiments)
# can lower it to exercise the fallback warning.
_FLASH_SEQ_CAP = 32768


class FlashFallbackWarning(UserWarning):
    """A ``use_pallas`` attention call fell back to the chunked path."""


# (reason, caller file, caller line) triples that already warned: a decode
# loop hitting the same fallback every step (or every retrace) says it
# once, not once per token — repetition adds noise, not information
_seen_fallbacks: set = set()


def reset_flash_fallback_dedup() -> None:
    """Forget which fallback sites have warned (tests, a new serving run)."""
    _seen_fallbacks.clear()


def _flash_fallback(reason: str, **ctx):
    import sys
    f = sys._getframe(2)     # the user call site stacklevel=3 attributes to
    site = (reason, f.f_code.co_filename, f.f_lineno)
    if site in _seen_fallbacks:
        return
    _seen_fallbacks.add(site)
    detail = ", ".join(f"{k}={v}" for k, v in ctx.items())
    warnings.warn(FlashFallbackWarning(
        f"use_pallas requested but attention fell back to the chunked "
        f"path: {reason} ({detail})"), stacklevel=3)


def _maybe_constrain(x, *axes):
    """with_sharding_constraint that degrades to a no-op when no mesh is
    in context (single-host tests); the dry-run lowers under `with mesh:`."""
    from jax.sharding import PartitionSpec as _P
    try:
        return jax.lax.with_sharding_constraint(x, _P(*axes))
    except (RuntimeError, ValueError):
        return x


# --------------------------------------------------------------- parameters
def attn_table(cfg: ArchConfig) -> Dict[str, Spec]:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    t = {
        "wq": Spec((d, h, hd), ("embed", "heads", "head_dim")),
        "wk": Spec((d, kv, hd), ("embed", "kv_heads", "head_dim")),
        "wv": Spec((d, kv, hd), ("embed", "kv_heads", "head_dim")),
        "wo": Spec((h, hd, d), ("heads", "head_dim", "embed")),
    }
    if cfg.qkv_bias:
        t["bq"] = Spec((h, hd), ("heads", "head_dim"), "zeros")
        t["bk"] = Spec((kv, hd), ("kv_heads", "head_dim"), "zeros")
        t["bv"] = Spec((kv, hd), ("kv_heads", "head_dim"), "zeros")
    if cfg.qk_norm:
        t["q_norm"] = Spec((hd,), ("head_dim",), "ones")
        t["k_norm"] = Spec((hd,), ("head_dim",), "ones")
    return t


def mla_table(cfg: ArchConfig) -> Dict[str, Spec]:
    d, h = cfg.d_model, cfg.n_heads
    qk_n, qk_r, v_hd = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    if cfg.q_lora_rank:
        q = {"wq_a": Spec((d, cfg.q_lora_rank), ("embed", "q_latent")),
             "q_a_norm": Spec((cfg.q_lora_rank,), ("q_latent",), "ones"),
             "wq_b": Spec((cfg.q_lora_rank, h, qk_n + qk_r),
                          ("q_latent", "heads", "head_dim"))}
    else:                                   # a direct query projection
        q = {"wq": Spec((d, h, qk_n + qk_r), ("embed", "heads", "head_dim"))}
    return {
        **q,
        "w_dkv": Spec((d, cfg.kv_lora_rank + qk_r), ("embed", "kv_latent")),
        "kv_norm": Spec((cfg.kv_lora_rank,), ("kv_latent",), "ones"),
        "w_uk": Spec((cfg.kv_lora_rank, h, qk_n),
                     ("kv_latent", "heads", "head_dim")),
        "w_uv": Spec((cfg.kv_lora_rank, h, v_hd),
                     ("kv_latent", "heads", "head_dim")),
        "wo": Spec((h, v_hd, d), ("heads", "head_dim", "embed")),
    }


# ----------------------------------------------------------- core attention
def chunked_attention(q, k, v, *, causal: bool, q_offset=0,
                      bq: int = 512, bk: int = 1024, kv_len=None,
                      remat_qblock: bool = False,
                      causal_skip: bool = False,
                      p_bf16: bool = False,
                      amm=None, amm_oracle: bool = False):
    """Online-softmax blockwise attention, pure JAX.

    q: (B, Sq, H, D), k/v: (B, Skv, KV, D) with H a multiple of KV (GQA).
    q_offset: global position of q[0] (for causal masking vs. a cache).
    kv_len: number of valid kv positions (<= Skv), static or traced.
    remat_qblock: checkpoint each q-block so the backward pass recomputes
      the (bq x bk) score blocks instead of saving them through the KV scan
      (flash-attention-style backward; see docs/perf.md §Model-side perf
      levers — the saved score residuals are the dominant memory term of
      the baseline).
    causal_skip: unroll the q-block loop in python so each q block scans
      only its own past KV blocks — halves attention FLOPs and score
      traffic vs. the masked full grid.  Needs causal, static q_offset == 0
      and modest nq (HLO grows linearly in nq); falls back otherwise.
    amm: optional ``AmmRuntime`` — form the per-block score and value
      products through the approximate datapath (``common.amm_dot``; the
      caller gates on ``AmmRuntime.attn_active``).  ``p_bf16`` is ignored
      on that path: the amm product owns its own quantization.
    amm_oracle: with ``amm``, form the products through the scalar closed
      forms instead of the dot-form contraction — the hook
      ``kernels.ref.amm_attention_ref`` uses to oracle this schedule.
    Returns (B, Sq, H, D).
    """
    b, sq, h, d = q.shape
    _, skv, kvh, _ = k.shape
    dv = v.shape[-1]
    groups = h // kvh
    bq = min(bq, sq)
    bk = min(bk, skv)
    nq, nk = -(-sq // bq), -(-skv // bk)
    pad_q = nq * bq - sq
    pad_k = nk * bk - skv
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    if kv_len is None:
        kv_len = skv
    # (B, nq, bq, H, D) -> scan over nq
    qb = q.reshape(b, nq, bq, h, d).transpose(1, 0, 3, 2, 4)   # (nq,B,H,bq,D)
    kb = k.reshape(b, nk, bk, kvh, d).transpose(1, 0, 3, 2, 4)
    vb = v.reshape(b, nk, bk, kvh, dv).transpose(1, 0, 3, 2, 4)
    scale = 1.0 / (d ** 0.5)

    def q_block(qi, q_i, kb_s, vb_s, n_blocks):
        q_i = q_i.astype(jnp.float32) * scale               # (B,H,bq,D)
        qg = q_i.reshape(b, kvh, groups * bq, d)            # group fold

        def kv_block(carry, inp):
            ki, k_j, v_j = inp
            m, l, acc = carry
            if amm is not None:
                # the Broken-Booth score product: one both-sides-dynamic
                # approximate matmul per (batch, kv-head) slice
                s = amm_dot(qg, k_j.astype(jnp.float32).swapaxes(-1, -2),
                            amm, oracle=amm_oracle)          # (B,KV,g*bq,bk)
            else:
                s = jnp.einsum("bgqd,bgkd->bgqk", qg,
                               k_j.astype(jnp.float32))     # (B,KV,g*bq,bk)
            s4 = s.reshape(b, kvh, groups, bq, bk)
            qpos = q_offset + qi * bq + jnp.arange(bq)
            kpos = ki * bk + jnp.arange(bk)
            live = (kpos < kv_len)[None, :]
            if causal:
                live = live & (qpos[:, None] >= kpos[None, :])
            s4 = jnp.where(live[None, None, None], s4, NEG_INF)
            s = s4.reshape(b, kvh, groups * bq, bk)
            m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + p.sum(axis=-1, keepdims=True)
            if amm is not None:
                # the Broken-Booth value product; p's block rows are the
                # finished (un-normalized) probabilities, quantized per
                # (batch, kv-head) slice like the scores
                pv = amm_dot(p, v_j.astype(jnp.float32), amm,
                             oracle=amm_oracle)
            elif p_bf16:
                # halve the probability-block HBM traffic; the f32 psum of
                # l_new keeps the normalizer exact (docs/perf.md
                # §Model-side perf levers)
                pv = jnp.einsum("bgqk,bgkd->bgqd", p.astype(jnp.bfloat16),
                                v_j.astype(jnp.bfloat16),
                                preferred_element_type=jnp.float32)
            else:
                pv = jnp.einsum("bgqk,bgkd->bgqd", p,
                                v_j.astype(jnp.float32))
            acc_new = acc * alpha + pv
            return (m_new, l_new, acc_new), None

        init = (jnp.full((b, kvh, groups * bq, 1), NEG_INF, jnp.float32),
                jnp.zeros((b, kvh, groups * bq, 1), jnp.float32),
                jnp.zeros((b, kvh, groups * bq, dv), jnp.float32))
        (m, l, acc), _ = jax.lax.scan(
            kv_block, init, (jnp.arange(n_blocks), kb_s, vb_s))
        out = acc / jnp.maximum(l, 1e-30)
        return out.reshape(b, kvh, groups, bq, dv).reshape(b, h, bq, dv)

    use_skip = (causal_skip and causal and isinstance(q_offset, int)
                and q_offset == 0 and nq <= 16)
    if use_skip:
        # python-unrolled q blocks, each scanning only its past KV blocks
        def one(qi, q_i):
            n_blocks = min(-(-((qi + 1) * bq) // bk), nk)
            return q_block(qi, q_i, kb[:n_blocks], vb[:n_blocks], n_blocks)
        fn = jax.checkpoint(one, static_argnums=(0,)) if remat_qblock else one
        outs = jnp.stack([fn(qi, qb[qi]) for qi in range(nq)])
    else:
        def block_fn(qi, q_i):
            return q_block(qi, q_i, kb, vb, nk)
        if remat_qblock:
            block_fn = jax.checkpoint(block_fn)
        outs = jax.lax.map(lambda args: block_fn(*args),
                           (jnp.arange(nq), qb))
    out = outs.transpose(1, 0, 3, 2, 4).reshape(b, nq * bq, h, dv)
    return out[:, :sq].astype(q.dtype)


def flash_amm_chunked_equiv(q, k, v, amm, *, causal: bool = True):
    """The chunked-amm run that flash-amm is bit-identical to.

    (B, H, S, D) operands with matched head counts, exactly as
    ``flash_attention_amm`` takes them.  Quantization is per block, so the
    equality contract needs the chunked schedule at the flash tile sizes —
    this wrapper pins them (``FLASH_AMM_BQ``/``FLASH_AMM_BK``) and is both
    the test reference and the backward function of the flash-amm
    ``custom_vjp`` (the chunked path's straight-through gradient *is* the
    flash-amm gradient).
    """
    from ..kernels.flash_attention import FLASH_AMM_BK, FLASH_AMM_BQ
    out = chunked_attention(q.transpose(0, 2, 1, 3),
                            k.transpose(0, 2, 1, 3),
                            v.transpose(0, 2, 1, 3), causal=causal,
                            bq=FLASH_AMM_BQ, bk=FLASH_AMM_BK, amm=amm)
    return out.transpose(0, 2, 1, 3)


def _flash_amm_impl(amm, causal, q, k, v):
    from ..kernels.flash_attention import flash_attention_amm
    wl, vbl, kind = amm.attn_lowering
    return flash_attention_amm(q, k, v, wl=wl, vbl=vbl, kind=kind,
                               causal=causal)


@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _flash_amm_ste(amm, causal, q, k, v):
    """Flash-amm forward with the chunked path's STE gradient.

    The kernel composes ``exact + stop_gradient(approx - exact)`` per
    tile, but differentiating *through* a Pallas call is not supported —
    so the backward runs ``jax.vjp`` of the bit-identical chunked
    schedule instead, which routes every gradient through the exact
    products (the same straight-through rule ``amm_dot`` implements).
    """
    return _flash_amm_impl(amm, causal, q, k, v)


def _flash_amm_fwd(amm, causal, q, k, v):
    return _flash_amm_impl(amm, causal, q, k, v), (q, k, v)


def _flash_amm_bwd(amm, causal, res, g):
    q, k, v = res
    _, vjp = jax.vjp(lambda qq, kk, vv: flash_amm_chunked_equiv(
        qq, kk, vv, amm, causal=causal), q, k, v)
    return vjp(g)


_flash_amm_ste.defvjp(_flash_amm_fwd, _flash_amm_bwd)


def decode_attention(q, k_cache, v_cache, kv_len, *, amm=None,
                     amm_oracle: bool = False, amm_ste: bool = True):
    """Single-position attention against a float cache (requantize-per-call).

    q: (B, 1, H, D); caches: (B, S, KV, D); kv_len: valid length — a
    traced scalar, or a (B,) per-slot vector under continuous batching.
    amm/amm_oracle: as in ``chunked_attention``; ``amm_ste=False`` returns
    the pure approximate forward (no straight-through composition — see
    ``amm_dot``).

    The amm products are quantized per (batch, kv-head) over the *whole*
    cache slice on every call.  Two consequences the int-code cache path
    (``decode_attention_codes``) exists to remove: every decode step pays
    the K/V-side max/round/clip requantize pass, and a token's quantized
    representation is a function of everything else in the slice — an
    envelope-edge arrival *later* in the sequence (or garbage in a reused
    slot past ``kv_len``, which the NEG_INF mask hides from the softmax
    but not from the dynamic-range scale) moves the shared scale and
    silently re-rounds every earlier token's codes.  Frozen-at-write codes
    make each token's bits independent of later arrivals;
    tests/test_amm_attention.py pins the drift this path allows.
    """
    b, _, h, d = q.shape
    _, s, kvh, _ = k_cache.shape
    dv = v_cache.shape[-1]
    groups = h // kvh
    qf = q.astype(jnp.float32).reshape(b, kvh, groups, d) / (d ** 0.5)
    if amm is not None:
        sc = amm_dot(qf, k_cache.astype(jnp.float32).transpose(0, 2, 3, 1),
                     amm, oracle=amm_oracle, ste=amm_ste)   # (B,KV,g,S)
    else:
        sc = jnp.einsum("bkgd,bskd->bkgs", qf, k_cache.astype(jnp.float32))
    kvl = jnp.asarray(kv_len)
    if kvl.ndim == 1:
        kvl = kvl[:, None, None, None]
    live = jnp.arange(s)[None, None, None, :] < kvl
    sc = jnp.where(live, sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    if amm is not None:
        out = amm_dot(p, v_cache.astype(jnp.float32).transpose(0, 2, 1, 3),
                      amm, oracle=amm_oracle, ste=amm_ste)  # (B,KV,g,Dv)
    else:
        out = jnp.einsum("bkgs,bskd->bkgd", p, v_cache.astype(jnp.float32))
    return out.reshape(b, 1, h, dv).astype(q.dtype)


# ------------------------------------------------------- int-code KV cache
def _code_write_slot(codes, scales, vf, p, *, lim: int, block: int):
    """Single-slot quantized cache write with first-touch block scales.

    codes: (S, KV, hd) int codes; scales: (nb, KV) f32, 0.0 marking a
    never-written block (``amm_quantize`` scales are floored at 1e-12, so
    0.0 is unreachable as a real scale); vf: (s, KV, hd) f32 rows to
    write at position ``p``.  The first write touching a block fixes its
    per-kv-head scale from that write's dynamic range — exactly the
    ``amm_quantize`` scale expression, per head — and every later write
    into the block quantizes (and clips) against the frozen scale, so a
    token's codes never change after they are written.
    """
    s_new = vf.shape[0]
    nb = scales.shape[0]
    n_touch = -(-s_new // block) + 1     # worst-case block-misaligned span
    b0 = p // block
    blk_scales = []
    for t in range(n_touch):
        bi = b0 + t
        rel = bi * block - p + jnp.arange(block)   # block rows -> vf rows
        m = (rel >= 0) & (rel < s_new)
        vals = jnp.abs(vf[jnp.clip(rel, 0, s_new - 1)]) * m[:, None, None]
        cand = jnp.maximum(jnp.max(vals, axis=(0, 2)) * (1.0 / lim), 1e-12)
        bic = jnp.clip(bi, 0, nb - 1)
        old = jax.lax.dynamic_slice_in_dim(scales, bic, 1, axis=0)[0]
        sc = jnp.where(old > 0.0, old, cand)
        keep = m.any() & (bi < nb)
        scales = jax.lax.dynamic_update_slice_in_dim(
            scales, jnp.where(keep, sc, old)[None], bic, axis=0)
        blk_scales.append(sc)
    per_blk = jnp.stack(blk_scales)                       # (n_touch, KV)
    tok_blk = (p + jnp.arange(s_new)) // block - b0
    sc_tok = per_blk[tok_blk]                             # (s, KV)
    q = jnp.clip(jnp.round(vf / sc_tok[..., None]), -lim - 1, lim)
    codes = jax.lax.dynamic_update_slice(
        codes, q.astype(codes.dtype), (p,) + (0,) * (codes.ndim - 1))
    return codes, scales


def code_cache_update(codes, scales, x, pos, *, wl: int):
    """Write new K/V rows into an int-code cache leaf as frozen codes.

    codes: (B, S, KV, hd); scales: (B, nb, KV) f32 with nb * block == S;
    x: (B, s, KV, hd) float rows; pos: scalar or (B,) per-slot positions.
    Returns (codes, scales) updated.  Scale candidates use the
    ``kernels.ref.amm_quantize`` expression per (block, kv-head) — on a
    block's first one-shot write the frozen scale is bit-identical to the
    scale the requantize-per-call path would derive for the same values,
    which is what makes the code-domain decode testable by
    ``assert_array_equal`` rather than allclose.
    """
    lim = 2 ** (wl - 1) - 1
    block = codes.shape[1] // scales.shape[1]
    vf = jnp.asarray(x, jnp.float32)
    p = jnp.asarray(pos, jnp.int32)
    fn = partial(_code_write_slot, lim=lim, block=block)
    return jax.vmap(fn, in_axes=(0, 0, 0, 0 if p.ndim else None))(
        codes, scales, vf, p)


def code_cache_dequant(codes, scales, kv_len=None):
    """Expand an int-code cache leaf back to float32 values.

    codes: (B, S, KV, hd); scales: (B, nb, KV).  Positions past ``kv_len``
    (scalar or (B,)) are zeroed — a reused slot may hold stale codes in a
    block whose scale is already frozen, and downstream consumers assume
    dead cache rows are zeros.
    """
    b, s = codes.shape[0], codes.shape[1]
    block = s // scales.shape[1]
    sc = jnp.repeat(scales, block, axis=1)                # (B, S, KV)
    out = codes.astype(jnp.float32) * sc[..., None]
    if kv_len is not None:
        kvl = jnp.broadcast_to(
            jnp.asarray(kv_len, jnp.int32).reshape(-1), (b,))
        live = jnp.arange(s)[None, :] < kvl[:, None]
        out = jnp.where(live[:, :, None, None], out, 0.0)
    return out


def decode_attention_codes(q, cache, kv_len, *, amm, amm_oracle: bool = False):
    """Single-position attention straight from the int-code KV cache.

    q: (B, 1, H, D); cache: per-layer slice of the code cache —
    ``{"k_codes", "k_scale", "v_codes", "v_scale"}`` leaves shaped as in
    ``code_cache_update``.  kv_len: scalar or (B,) per-slot lengths.

    Cached codes feed ``kernels.bbm_matmul.bbm_matmul_coded`` directly
    (per-column K scales expanded from the per-block grid; per-K-block V
    descale via the kblocks variant), skipping the per-call K/V-side
    requantize of ``bbm_matmul_dynamic``.  Only ``q`` and the softmax
    probabilities are quantized per call.  The forward value is the pure
    approximate product (no straight-through composition — at decode time
    no exact-valued K/V exists to compose against), i.e. the faithful
    serving semantics of hardware with no exact multiplier.

    Codes past ``kv_len`` are zeroed before the contraction: the NEG_INF
    score mask forces their softmax weights to exactly 0.0 (hence p-codes
    of 0), but ``bbm_type1(0, w) != 0`` for negative-row ``w``, so stale
    V codes in a reused slot would otherwise leak into the PV product.
    Zero codes contribute exactly nothing under both truncation kinds.

    amm_oracle=True forms every product through the scalar closed forms
    (``kernels.ref.amm_coded_ref`` / ``amm_coded_kblocks_ref``) on the
    same schedule — bit-identical by the codes-in amm contract.
    """
    if amm is None or not amm.attn_active or amm.attn_lowering is None:
        raise ValueError("int-code KV cache decode requires an active "
                         "Booth-family bitexact amm attention lowering "
                         "(mode='bitexact', Booth-family mul, apply_to "
                         "'attn' or 'all')")
    wl, vbl, kind = amm.attn_lowering
    kc, vc = cache["k_codes"], cache["v_codes"]
    ks, vs = cache["k_scale"], cache["v_scale"]
    b, s, kvh, d = kc.shape
    dv = vc.shape[-1]
    block = s // ks.shape[1]
    h = q.shape[2]
    groups = h // kvh
    qf = q.astype(jnp.float32).reshape(b, kvh, groups, d) / (d ** 0.5)
    kvl = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32).reshape(-1), (b,))
    if amm_oracle:
        from ..kernels.ref import amm_coded_kblocks_ref, amm_coded_ref
        spec = amm.spec
        qk_fn = lambda a, c, sc: amm_coded_ref(a, c, sc, spec)
        pv_fn = lambda a, c, sc: amm_coded_kblocks_ref(a, c, sc, spec,
                                                       block=block)
    else:
        from ..kernels.bbm_matmul import (bbm_matmul_coded,
                                          bbm_matmul_coded_kblocks)
        qk_fn = partial(bbm_matmul_coded, wl=wl, vbl=vbl, kind=kind)
        pv_fn = partial(bbm_matmul_coded_kblocks, wl=wl, vbl=vbl, kind=kind,
                        block=block)

    def head_slice(qs, kT, ksl, vcs, vsl, n):
        # qs (g, d) f32; kT (d, S) codes; ksl (nb,); vcs (S, dv); vsl (nb,)
        live = jnp.arange(s) < n
        sc = qk_fn(qs, jnp.where(live[None, :], kT, 0),
                   jnp.repeat(ksl, block))
        sc = jnp.where(live[None, :], sc, NEG_INF)
        pr = jax.nn.softmax(sc, axis=-1)
        return pv_fn(pr, jnp.where(live[:, None], vcs, 0), vsl)

    fn = jax.vmap(jax.vmap(head_slice, in_axes=(0, 0, 0, 0, 0, None)),
                  in_axes=(0, 0, 0, 0, 0, 0))
    out = fn(qf,
             kc.transpose(0, 2, 3, 1).astype(jnp.int32),
             ks.transpose(0, 2, 1),
             vc.transpose(0, 2, 1, 3).astype(jnp.int32),
             vs.transpose(0, 2, 1),
             kvl)                                         # (B, KV, g, Dv)
    return out.reshape(b, 1, h, dv).astype(q.dtype)


def _cache_put(buf, new, pos):
    """dynamic_update_slice at the decode position(s).

    A scalar ``pos`` is the classic single-front write; a (B,) vector
    (continuous batching: every slot at its own depth) vmaps the update
    over the leading batch axis.
    """
    p = jnp.asarray(pos, jnp.int32)
    if p.ndim == 0:
        return jax.lax.dynamic_update_slice(
            buf, new, (0, p) + (0,) * (buf.ndim - 2))
    return jax.vmap(lambda c, n_, q_: jax.lax.dynamic_update_slice(
        c, n_, (q_,) + (0,) * (c.ndim - 1)))(buf, new, p)


# ------------------------------------------------------------ GQA attention
class KVUpdate(NamedTuple):
    k: jnp.ndarray
    v: jnp.ndarray


def attention(p, x, cfg: ArchConfig, *, positions, cache=None, pos=None,
              causal: bool = True, kv=None, use_pallas: bool = False,
              remat_qblock: bool = False, shard_heads: bool = False,
              causal_skip: bool = False, p_bf16: bool = False, amm=None):
    """GQA attention.  x: (B, S, d_model).

    cache: optional dict {"k","v"} (B, S_max, KV, D) for decode; ``pos`` is
    the current decode position (traced scalar).  kv: optional externally
    provided (k, v) (cross-attention).  amm: optional ``AmmRuntime`` — the
    score/value products go through the approximate datapath (the Q/K/V/O
    projections stay exact; docs/attention.md).  ``use_pallas`` selects
    the flash lowering for exact *and* amm-active prefill (exact-flash /
    flash-amm; the module docstring has the routing table); calls that
    fall off it — sequence beyond ``_FLASH_SEQ_CAP``, an amm family with
    no dot-form lowering, cache-backed prefill — take the chunked path,
    with a ``FlashFallbackWarning`` when ``use_pallas`` was requested.
    GQA note: the flash lowerings repeat KV heads before quantizing, so
    their per-block scales are per *repeated* head; the chunked path
    group-folds and scales per KV head.  Both are valid amm schedules —
    the bit-equality contract is defined at matched head counts
    (``flash_amm_chunked_equiv``).  Returns (out, new_cache).
    """
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    with jax.named_scope(trace.ATTN_PROJ):
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
        if kv is None:
            k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
            v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
        else:
            k, v = kv
        if cfg.qkv_bias:
            q = q + p["bq"]
            if kv is None:
                k = k + p["bk"]
                v = v + p["bv"]
        if cfg.qk_norm:
            q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
            k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
        q = apply_rope(q, positions, cfg.rope_theta)
        if kv is None:
            k = apply_rope(k, positions, cfg.rope_theta)

    new_cache = None
    if cache is not None and s > 1 and jnp.ndim(pos) == 1:
        raise ValueError("multi-token prefill needs a scalar position; "
                         "per-slot position vectors are decode-only")
    if cache is not None and "k_codes" in cache:
        # int-code KV cache: quantize at write (frozen codes + first-touch
        # block scales), decode straight from codes; prefill dequantizes
        # once and rides the standard chunked schedule
        if amm is None or amm.attn_lowering is None:
            raise ValueError("int-code KV cache requires an active "
                             "Booth-family bitexact amm attention lowering")
        wl = amm.attn_lowering[0]
        with jax.named_scope(trace.ATTN_CODE_CACHE):
            ck, sk = code_cache_update(cache["k_codes"], cache["k_scale"],
                                       k, pos, wl=wl)
            cv, sv = code_cache_update(cache["v_codes"], cache["v_scale"],
                                       v, pos, wl=wl)
            new_cache = {"k_codes": ck, "k_scale": sk,
                         "v_codes": cv, "v_scale": sv}
            if s == 1:
                out = decode_attention_codes(q, new_cache, kv_len=pos + s,
                                             amm=amm)
            else:
                kk = code_cache_dequant(ck, sk, kv_len=pos + s)
                vv = code_cache_dequant(cv, sv, kv_len=pos + s)
                out = chunked_attention(q, kk, vv, causal=causal,
                                        q_offset=pos, kv_len=pos + s,
                                        remat_qblock=remat_qblock, amm=amm)
    elif cache is not None:
        ck = _cache_put(cache["k"], k.astype(cache["k"].dtype), pos)
        cv = _cache_put(cache["v"], v.astype(cache["v"].dtype), pos)
        new_cache = {"k": ck, "v": cv}
        if s == 1:
            out = decode_attention(q, ck, cv, kv_len=pos + s, amm=amm)
        else:  # multi-token prefill against the cache
            kk, vv = ck, cv
            if shard_heads and ck.shape[2] < q.shape[2]:
                # same head-sharding trick as the train path: the cache
                # keeps kv_heads, only the compute tensors are repeated
                groups = q.shape[2] // ck.shape[2]
                kk = jnp.repeat(ck, groups, axis=2)
                vv = jnp.repeat(cv, groups, axis=2)
                q = _maybe_constrain(q, None, None, "model", None)
                kk = _maybe_constrain(kk, None, None, "model", None)
                vv = _maybe_constrain(vv, None, None, "model", None)
            out = chunked_attention(q, kk, vv, causal=causal, q_offset=pos,
                                    kv_len=pos + s,
                                    remat_qblock=remat_qblock, amm=amm)
    elif use_pallas and s <= _FLASH_SEQ_CAP and (
            amm is None or amm.attn_lowering is not None):
        groups = q.shape[2] // k.shape[2]
        kk = jnp.repeat(k, groups, axis=2)
        vv = jnp.repeat(v, groups, axis=2)
        qt = q.transpose(0, 2, 1, 3)
        kt = kk.transpose(0, 2, 1, 3)
        vt = vv.transpose(0, 2, 1, 3)
        if amm is None:
            from ..kernels import flash_attention
            out = flash_attention(qt, kt, vt, causal=causal)
        else:
            out = _flash_amm_ste(amm, causal, qt, kt, vt)
        out = out.transpose(0, 2, 1, 3)
    else:
        if use_pallas:
            if s > _FLASH_SEQ_CAP:
                _flash_fallback(
                    "sequence length exceeds the flash cap",
                    shape=x.shape, seq=s, cap=_FLASH_SEQ_CAP,
                    amm="inactive" if amm is None else
                    f"{amm.cfg.mul}/wl={amm.cfg.wl}")
            else:
                _flash_fallback(
                    "amm family has no flash lowering",
                    shape=x.shape, seq=s,
                    amm=f"{amm.cfg.mul}/mode={amm.cfg.mode}")
        if shard_heads and k.shape[2] < q.shape[2]:
            # GQA head sharding: kv_heads (e.g. 8) does not divide the
            # 16-way model axis, which leaves the whole attention replicated
            # per device.  Repeating KV to the full head count lets GSPMD
            # shard the n_heads axis (padding if not divisible) — 16x less
            # attention compute/memory per chip at the price of kv
            # duplication (docs/perf.md §Model-side perf levers).
            groups = q.shape[2] // k.shape[2]
            k = jnp.repeat(k, groups, axis=2)
            v = jnp.repeat(v, groups, axis=2)
            q = _maybe_constrain(q, None, None, "model", None)
            k = _maybe_constrain(k, None, None, "model", None)
            v = _maybe_constrain(v, None, None, "model", None)
        out = chunked_attention(q, k, v, causal=causal,
                                remat_qblock=remat_qblock,
                                causal_skip=causal_skip, p_bf16=p_bf16,
                                amm=amm)
    with jax.named_scope(trace.ATTN_PROJ):
        y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return y, new_cache


# ------------------------------------------------------------ MLA attention
def mla_project(p, x, cfg: ArchConfig, positions):
    """(q_nope, q_rope, latent) of rows x (B, S, d): rotated rope queries
    (B, S, H, rope), the no-position queries (B, S, H, nope), and the
    cached latent (B, S, kv_lora + rope): the RMS-normed compressed KV
    beside the rotated shared rope key."""
    nope = cfg.qk_nope_dim
    with jax.named_scope(trace.ATTN_PROJ):
        if cfg.q_lora_rank:         # queries through the low-rank path
            q_lat = rmsnorm(x @ p["wq_a"], p["q_a_norm"], cfg.norm_eps)
            q = jnp.einsum("bsr,rhk->bshk", q_lat, p["wq_b"])
        else:
            q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
        q_nope, q_rope = q[..., :nope], q[..., nope:]
        q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

        # compressed KV latent + decoupled rope key
        latent = x @ p["w_dkv"]                       # (B,S,kv_lora+rope)
        c_kv = rmsnorm(latent[..., :cfg.kv_lora_rank], p["kv_norm"],
                       cfg.norm_eps)
        k_rope = apply_rope(latent[..., None, cfg.kv_lora_rank:],
                            positions, cfg.rope_theta)  # (B,S,1,rope)
        lat_cat = jnp.concatenate([c_kv, k_rope[..., 0, :]], axis=-1)
    return q_nope, q_rope, lat_cat


def mla_absorb_query(q_nope, q_rope, w_uk):
    """The absorbed query (B, S, H, kv_lora + rope): ``[q_nope,h W_uk,hᵀ ;
    q_rope,h]``, whose product with a cached latent ``[c_t ; k_rope,t]``
    is the head's score before the 1/sqrt(nope + rope) scale."""
    with jax.named_scope(trace.ATTN_PROJ):
        return jnp.concatenate(
            [jnp.einsum("bshk,rhk->bshr", q_nope, w_uk),
             q_rope.astype(jnp.float32)], axis=-1)


def _latent_kv_len(kv_len, b):
    return jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32).reshape(-1), (b,))


def latent_attention(q_abs, lat, q_pos, kv_len, *, scale: float,
                     n_value: int):
    """Absorbed latent attention over a float latent cache, exact.

    q_abs (B, T, H, L) from ``mla_absorb_query``; lat (B, S, L); q_pos
    (B, T) each query's position; kv_len scalar or (B,).  Scores are
    ``q_abs · lat_t * scale`` at positions below ``kv_len`` and at or
    before the query's; values are the probabilities times the first
    ``n_value`` latent columns.  Returns (B, T, H, n_value) float32: the
    heads' value latents, before ``W_uv``.
    """
    b, s = lat.shape[:2]
    kvl = _latent_kv_len(kv_len, b)
    kpos = jnp.arange(s)
    live = ((kpos[None, None, :] < kvl[:, None, None])
            & (kpos[None, None, :] <= q_pos[:, :, None]))      # (B,T,S)
    lat = jnp.where((kpos[None, :] < kvl[:, None])[..., None],
                    lat.astype(jnp.float32), 0.0)
    sc = jnp.einsum("bthl,bsl->bths", q_abs.astype(jnp.float32) * scale,
                    lat)
    pr = jax.nn.softmax(jnp.where(live[:, :, None, :], sc, NEG_INF), -1)
    return jnp.einsum("bths,bsr->bthr", pr, lat[..., :n_value])


# token rows of one prefill contracted together (memory of the batched
# per-block value partials: rows x heads x blocks x n_value int32)
LATENT_PREFILL_TOKENS = 16


def latent_attention_codes(q_abs, codes, scales, q_pos, kv_len, *, amm,
                           scale: float, n_value: int, n_ctx=None):
    """Absorbed latent attention straight from the int-code latent cache.

    q_abs (B, T, H, L) float; codes (B, S, L) wl-bit latent codes with
    one scale per (slot, block) in scales (B, nb); q_pos (B, T); kv_len
    scalar or (B,).  For each slot and each query token, as in a decode
    step: the token's H absorbed query rows are quantized together and
    multiplied with the codes as they are (``bbm_matmul_coded``, the
    block scales expanded per column), the scores masked to positions
    below ``kv_len`` and not after the token, the softmax probabilities
    quantized together and multiplied with the first ``n_value`` code
    columns block by block (``bbm_matmul_coded_kblocks``).  Codes past
    ``kv_len`` are zeroed first (a reused slot's stale rows).  A prefill
    runs its tokens ``LATENT_PREFILL_TOKENS`` at a time.  ``n_ctx``, a
    static bound on ``kv_len`` where the caller has one (a prefill from
    position 0), limits the products to the blocks below it: the blocks
    past it hold zeroed codes, whose scores are masked and whose value
    partials are 0.  Returns (B, T, H, n_value) float32, the value
    latents before ``W_uv``.
    """
    from ..kernels.bbm_matmul import (bbm_matmul_coded,
                                      bbm_matmul_coded_kblocks)
    wl, vbl, kind = amm.attn_lowering
    block = codes.shape[1] // scales.shape[1]
    if n_ctx is not None:
        nb = min(-(-n_ctx // block), scales.shape[1])
        codes, scales = codes[:, :nb * block], scales[:, :nb]
    b, s, _ = codes.shape
    t = q_abs.shape[1]
    kvl = _latent_kv_len(kv_len, b)
    kpos = jnp.arange(s)

    def slot(qa, qp, cod, sc, n):
        live_k = kpos < n
        cz = jnp.where(live_k[:, None], cod.astype(jnp.int32), 0)
        cT, cv = cz.T, cz[:, :n_value]
        cols = jnp.repeat(sc, block)

        def token(args):
            q_t, p_t = args                                   # (H, L), ()
            sco = bbm_matmul_coded(q_t.astype(jnp.float32) * scale, cT,
                                   cols, wl=wl, vbl=vbl, kind=kind)
            live = live_k & (kpos <= p_t)
            pr = jax.nn.softmax(jnp.where(live[None, :], sco, NEG_INF),
                                axis=-1)
            return bbm_matmul_coded_kblocks(pr, cv, sc, wl=wl, vbl=vbl,
                                            kind=kind, block=block)
        if t == 1:
            return token((qa[0], qp[0]))[None]
        return jax.lax.map(token, (qa, qp),
                           batch_size=min(t, LATENT_PREFILL_TOKENS))

    return jax.vmap(slot)(q_abs, q_pos, codes, scales, kvl)


def mla_attention(p, x, cfg: ArchConfig, *, positions, cache=None, pos=None,
                  remat_qblock: bool = False, shard_heads: bool = False,
                  causal_skip: bool = False, p_bf16: bool = False,
                  amm=None):
    """DeepSeek-V3 multi-head latent attention.

    The cache stores the compressed latent (B, S, kv_lora + rope_dim).
    Without a cache (training) K/V are expanded from it (the "naive"
    formulation).  Against a cache, a model without q-LoRA runs the
    absorbed form, in prefill and decode alike: ``W_uk`` is folded into
    the query (``mla_absorb_query``), the scores are its product with the
    cached latents and the values the probabilities' product with the
    cached ``c_t``, expanded by ``W_uv`` afterwards
    (``latent_attention_codes`` on the int-code cache, where the codes
    feed the Broken-Booth contraction as they are; ``latent_attention``
    on a float cache).  q-LoRA models keep the expanded form against a
    cache too, the int-code latent dequantized at read.  amm: as in
    ``attention`` — the score/value products route through the
    approximate datapath; the projections stay exact.
    Returns (out, new_cache).
    """
    b, s, _ = x.shape
    nope, rope_d = cfg.qk_nope_dim, cfg.qk_rope_dim
    q_nope, q_rope, lat_cat = mla_project(p, x, cfg, positions)

    if cache is not None and s > 1 and jnp.ndim(pos) == 1:
        raise ValueError("multi-token prefill needs a scalar position; "
                         "per-slot position vectors are decode-only")
    if cache is not None and not cfg.q_lora_rank:
        return _mla_absorbed(p, q_nope, q_rope, lat_cat, cfg, cache=cache,
                             positions=positions, pos=pos, amm=amm)
    if cache is not None and "lat_codes" in cache:
        # int-code latent cache: the compressed latent is quantized at
        # write (frozen codes, first-touch block scales) and dequantized
        # at read — the K/V re-expansion einsums need float latents, so
        # MLA gets the frozen-representation and memory wins of the code
        # cache while its score/value products keep per-call scales over
        # the dequantized values (docs/serving.md)
        if amm is None or amm.attn_lowering is None:
            raise ValueError("int-code KV cache requires an active "
                             "Booth-family bitexact amm attention lowering")
        wl = amm.attn_lowering[0]
        lc, ls = code_cache_update(
            cache["lat_codes"][:, :, None, :], cache["lat_scale"][..., None],
            lat_cat[:, :, None, :], pos, wl=wl)
        new_cache = {"lat_codes": lc[:, :, 0, :], "lat_scale": ls[..., 0]}
        kv_len = pos + s
        lat_all = code_cache_dequant(lc, ls, kv_len=kv_len)[:, :, 0, :]
    elif cache is not None:
        new_lat = _cache_put(cache["latent"],
                             lat_cat.astype(cache["latent"].dtype), pos)
        kv_len = pos + s
        lat_all = new_lat
        new_cache = {"latent": new_lat}
    else:
        lat_all = lat_cat
        kv_len = s
        new_cache = None

    c_all = lat_all[..., :cfg.kv_lora_rank]
    kr_all = lat_all[..., None, cfg.kv_lora_rank:]          # (B,S,1,rope)
    k_nope = jnp.einsum("bsr,rhk->bshk", c_all, p["w_uk"])  # (B,S,H,nope)
    v_all = jnp.einsum("bsr,rhk->bshk", c_all, p["w_uv"])   # (B,S,H,v_hd)
    k_full = jnp.concatenate(
        [k_nope, jnp.broadcast_to(
            kr_all, k_nope.shape[:3] + (rope_d,))], axis=-1)
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)

    if shard_heads and cache is None:
        # MLA has a full per-head K/V after expansion: shard the 128-head
        # axis directly.
        q_full = _maybe_constrain(q_full, None, None, "model", None)
        k_full = _maybe_constrain(k_full, None, None, "model", None)
        v_all = _maybe_constrain(v_all, None, None, "model", None)
    if cache is not None and s == 1:
        out = decode_attention(q_full, k_full, v_all, kv_len=kv_len,
                               amm=amm)
    elif cache is not None:
        out = chunked_attention(q_full, k_full, v_all, causal=True,
                                q_offset=pos, kv_len=kv_len,
                                remat_qblock=remat_qblock, amm=amm)
    else:
        out = chunked_attention(q_full, k_full, v_all, causal=True,
                                remat_qblock=remat_qblock,
                                causal_skip=causal_skip, p_bf16=p_bf16,
                                amm=amm)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return y, new_cache


def _mla_absorbed(p, q_nope, q_rope, lat_cat, cfg: ArchConfig, *, cache,
                  positions, pos, amm):
    """The absorbed form against a cache (``mla_attention``)."""
    s = lat_cat.shape[1]
    r = cfg.kv_lora_rank
    scale = 1.0 / ((cfg.qk_nope_dim + cfg.qk_rope_dim) ** 0.5)
    q_abs = mla_absorb_query(q_nope, q_rope, p["w_uk"])
    kv_len = pos + s
    if "lat_codes" in cache:
        if amm is None or amm.attn_lowering is None:
            raise ValueError("int-code KV cache requires an active "
                             "Booth-family bitexact amm attention lowering")
        with jax.named_scope(trace.ATTN_LATENT_CACHE):
            lc, ls = code_cache_update(
                cache["lat_codes"][:, :, None, :],
                cache["lat_scale"][..., None], lat_cat[:, :, None, :], pos,
                wl=amm.attn_lowering[0])
            new_cache = {"lat_codes": lc[:, :, 0, :],
                         "lat_scale": ls[..., 0]}
            o_lat = latent_attention_codes(
                q_abs, new_cache["lat_codes"], new_cache["lat_scale"],
                positions, kv_len, amm=amm, scale=scale, n_value=r,
                n_ctx=kv_len if isinstance(kv_len, int) else None)
    else:
        if amm is not None:
            raise ValueError("approximate latent attention reads the "
                             "int-code latent cache (kv_codes); a float "
                             "latent cache runs its products exact")
        new_lat = _cache_put(cache["latent"],
                             lat_cat.astype(cache["latent"].dtype), pos)
        new_cache = {"latent": new_lat}
        o_lat = latent_attention(q_abs, new_lat, positions, kv_len,
                                 scale=scale, n_value=r)
    with jax.named_scope(trace.ATTN_PROJ):
        out = jnp.einsum("bshr,rhk->bshk", o_lat, p["w_uv"])
        y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return y, new_cache
