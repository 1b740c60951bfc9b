"""Oracle/routing suite for approximate attention (apply_to="attn"/"all").

The attention score product ``Q @ K^T`` and value product ``P @ V`` are
activation x activation — no weight side, nothing to precode — so their
Broken-Booth lowering is the both-operands-dynamic dot form
(``kernels.bbm_matmul_dynamic`` via ``models.common.amm_dot``).  This
suite holds that datapath to *bitwise* equality against the scalar
closed-form oracles (``kernels.ref.amm_attention_ref`` /
``amm_decode_attention_ref``) across wl x vbl x kind, pins the
``apply_to`` routing (attention exact under "mlp" — the pre-routing code
path — and MLPs exact under "attn"), checks decode-vs-prefill cache
parity at the LM level, and verifies the flash-amm routing:
``use_pallas`` with amm attention active selects the flash-amm lowering
(kernels/flash_attention.py), bit-identical to the chunked schedule at
the flash tile sizes (the full contract lives in tests/test_flash_amm.py).
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import AmmConfig, get_arch, reduced
from repro.core.multipliers import MulSpec
from repro.kernels.bbm_matmul import (_coded_kblocks,
                                      bbm_matmul_coded_kblocks,
                                      bbm_matmul_dynamic, pv_form)
from repro.kernels.booth_rows import amm_chunk_len
from repro.kernels.ref import (AMM_BOOTH_KINDS, amm_attention_ref,
                               amm_coded_kblocks_ref,
                               amm_decode_attention_codes_ref,
                               amm_decode_attention_ref, amm_dot_ref,
                               amm_quantize)
from repro.models import ModelRuntime, init_cache, lm_apply, lm_init
from repro.models import attention as attention_mod
from repro.models.attention import (attention, attn_table, chunked_attention,
                                    code_cache_dequant, code_cache_update,
                                    decode_attention, decode_attention_codes)
from repro.models.common import AmmRuntime, amm_dot, init_params
from repro.serve.kv_cache import code_dtype, init_code_cache

RNG = np.random.default_rng(29)

# Booth-family cells across word lengths, both truncation kinds, the
# exact multiplier, and the single-digit-chunk operating point (16, 3)
# whose PV product crosses the int32-exact chunk boundary
SWEEP = [("bbm0", 8, 5), ("bbm1", 8, 7), ("bbm0", 12, 7), ("bbm1", 12, 11),
         ("bbm0", 16, 13), ("bbm1", 16, 15), ("bbm0", 16, 3),
         ("booth", 16, 0)]


def _rt(mul, wl, vbl, apply_to="all", mode="bitexact"):
    return AmmRuntime.build(AmmConfig(mode=mode, mul=mul, wl=wl, param=vbl,
                                      apply_to=apply_to))


def _qkv(b=2, sq=16, skv=16, h=4, kv=2, d=8, seed=3):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((b, sq, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, skv, kv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, skv, kv, d)), jnp.float32)
    return q, k, v


# ------------------------------------------------ product-level oracle
@pytest.mark.parametrize("mul,wl,vbl", SWEEP)
def test_bbm_matmul_dynamic_matches_scalar_oracle(mul, wl, vbl):
    """The both-sides-dynamic entry point == the scalar closed forms,
    including full-scale (envelope-edge) rows/columns."""
    rng = np.random.default_rng(7)
    a = rng.standard_normal((5, 12))
    b = rng.standard_normal((12, 9))
    a[0, :] = np.abs(a).max() * 1.5          # quantizes to +lim everywhere
    b[:, 0] = -np.abs(b).max()
    a, b = jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)
    got = np.asarray(bbm_matmul_dynamic(a, b, wl=wl, vbl=vbl,
                                        kind=AMM_BOOTH_KINDS[mul]))
    ref = np.asarray(amm_dot_ref(a, b, MulSpec(mul, wl, vbl)))
    np.testing.assert_array_equal(got, ref)


def test_amm_dot_batched_matches_oracle():
    """Leading batch axes vmap to per-slice dynamic scales on both sides."""
    rng = np.random.default_rng(11)
    a = jnp.asarray(rng.standard_normal((2, 3, 5, 12)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((2, 3, 12, 7)), jnp.float32)
    rt = _rt("bbm0", 16, 13)
    np.testing.assert_array_equal(
        np.asarray(amm_dot(a, b, rt)),
        np.asarray(amm_dot(a, b, rt, oracle=True)))


def test_amm_dot_is_ste():
    """Gradients ride the exact batched matmul, not the integer path."""
    rt = _rt("bbm0", 16, 13)
    a = jnp.asarray(RNG.standard_normal((2, 4, 8)), jnp.float32)
    b = jnp.asarray(RNG.standard_normal((2, 8, 5)), jnp.float32)
    g1 = jax.grad(lambda x: jnp.sum(amm_dot(x, b, rt)))(a)
    g2 = jax.grad(lambda x: jnp.sum(x @ b))(a)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=1e-6)


# --------------------------------------------- attention-level oracle
@pytest.mark.parametrize("mul,wl,vbl", SWEEP)
def test_chunked_attention_matches_scalar_oracle(mul, wl, vbl):
    q, k, v = _qkv()
    got = chunked_attention(q, k, v, causal=True, bq=8, bk=8,
                            amm=_rt(mul, wl, vbl))
    ref = amm_attention_ref(q, k, v, MulSpec(mul, wl, vbl), causal=True,
                            bq=8, bk=8)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_chunked_attention_amm_noncausal_and_kvlen():
    """Masking interactions: cross-attention (causal=False) and a traced
    kv_len that dead-zeroes part of the final KV block."""
    q, k, v = _qkv(sq=12, skv=20)
    rt = _rt("bbm0", 16, 13)
    spec = MulSpec("bbm0", 16, 13)
    for causal, kv_len in ((False, None), (True, 13), (False, 13)):
        got = chunked_attention(q, k, v, causal=causal, bq=8, bk=8,
                                kv_len=kv_len, amm=rt)
        ref = amm_attention_ref(q, k, v, spec, causal=causal, bq=8, bk=8,
                                kv_len=kv_len)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


@pytest.mark.parametrize("mul,wl,vbl", [("bbm0", 16, 13), ("bbm1", 16, 15),
                                        ("bbm0", 16, 3)])
def test_decode_attention_matches_scalar_oracle(mul, wl, vbl):
    """Single-position decode against a cache with a dead (zero) tail."""
    rng = np.random.default_rng(13)
    q = jnp.asarray(rng.standard_normal((2, 1, 4, 8)), jnp.float32)
    kc = np.zeros((2, 16, 2, 8), np.float32)
    vc = np.zeros((2, 16, 2, 8), np.float32)
    kc[:, :10] = rng.standard_normal((2, 10, 2, 8))
    vc[:, :10] = rng.standard_normal((2, 10, 2, 8))
    kc, vc = jnp.asarray(kc), jnp.asarray(vc)
    got = decode_attention(q, kc, vc, 10, amm=_rt(mul, wl, vbl))
    ref = amm_decode_attention_ref(q, kc, vc, 10, MulSpec(mul, wl, vbl))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_amm_attention_actually_differs_from_exact():
    """The routing is not a no-op: a truncating spec changes the output
    (and the exact Booth spec vbl=0 changes only by quantization)."""
    q, k, v = _qkv()
    exact = np.asarray(chunked_attention(q, k, v, causal=True, bq=8, bk=8))
    approx = np.asarray(chunked_attention(q, k, v, causal=True, bq=8, bk=8,
                                          amm=_rt("bbm0", 16, 13)))
    assert not np.array_equal(exact, approx)
    assert np.max(np.abs(exact - approx)) < 0.05   # still an approximation


# --------------------------------------------------------- flash routing
def test_flash_amm_route_selected_under_amm(monkeypatch):
    """use_pallas with amm attention active selects the flash-amm lowering
    (the old behavior — silently falling back to the chunked path — is
    gone), and its output is bit-identical to the chunked schedule run at
    the flash tile sizes with KV heads repeated (the equality contract;
    tests/test_flash_amm.py sweeps it at the kernel level)."""
    from repro.models.attention import flash_amm_chunked_equiv
    cfg = reduced(get_arch("qwen2-0.5b"))
    cfg = dataclasses.replace(cfg, amm=AmmConfig(mode="bitexact", mul="bbm0",
                                                 wl=16, param=13,
                                                 apply_to="all"))
    p = init_params(attn_table(cfg), jax.random.key(0))
    x = jnp.asarray(RNG.standard_normal((2, 16, cfg.d_model)), jnp.float32)
    positions = jnp.arange(16)[None, :] * jnp.ones((2, 1), jnp.int32)
    rt = AmmRuntime.build(cfg.amm)
    called = []
    orig = attention_mod._flash_amm_ste

    def spy(amm, causal, q, k, v):
        called.append(True)
        return orig(amm, causal, q, k, v)

    monkeypatch.setattr(attention_mod, "_flash_amm_ste", spy)
    y_pl, _ = attention(p, x, cfg, positions=positions, use_pallas=True,
                        amm=rt)
    assert called, "use_pallas + active amm must take the flash-amm route"

    # reference: repeat KV heads (as the route does), then the chunked
    # schedule at flash tiles — bitwise equal per the flash-amm contract
    def chunked_ref(pp, xx, *, positions):
        def fake_flash(amm, causal, q, k, v):
            return flash_amm_chunked_equiv(q, k, v, amm, causal=causal)
        monkeypatch.setattr(attention_mod, "_flash_amm_ste", fake_flash)
        out, _ = attention(pp, xx, cfg, positions=positions,
                           use_pallas=True, amm=rt)
        return out

    y_ref = chunked_ref(p, x, positions=positions)
    np.testing.assert_array_equal(np.asarray(y_pl), np.asarray(y_ref))


# ------------------------------------------------------- apply_to routing
def _lm(apply_to, mode="bitexact"):
    cfg = reduced(get_arch("qwen2-0.5b"))
    cfg = dataclasses.replace(cfg, amm=AmmConfig(mode=mode, mul="bbm0",
                                                 wl=16, param=13,
                                                 apply_to=apply_to))
    rt = ModelRuntime.build(cfg)
    params = lm_init(cfg, jax.random.key(0))
    return cfg, rt, params


def test_routing_properties():
    assert _rt("bbm0", 16, 13, "mlp").attn_active is False
    assert _rt("bbm0", 16, 13, "attn").attn_active is True
    assert _rt("bbm0", 16, 13, "all").attn_active is True
    assert _rt("bbm0", 16, 13, "attn").mlp_active is False
    assert _rt("bbm0", 16, 13, "mlp").mlp_active is True
    assert _rt("bbm0", 16, 13, "all").mlp_active is True
    # only the bitexact Booth datapath has an attention lowering
    assert _rt("bbm0", 16, 13, "all", mode="noise").attn_active is False
    assert _rt("bam", 8, 4, "all").attn_active is False
    # noise keeps its historical MLP routing
    assert _rt("bbm0", 16, 13, "all", mode="noise").mlp_active is True


def test_apply_to_validated():
    with pytest.raises(ValueError):
        AmmConfig(apply_to="attention")


def test_apply_to_mlp_keeps_attention_exact(monkeypatch):
    """Regression pin: under apply_to="mlp" the attention layer never
    receives an amm runtime — it executes the identical (pre-routing)
    code path, so "mlp" output is bit-identical to pre-PR behavior by
    construction.  Under "all" the same spy sees the runtime arrive."""
    seen = []
    orig = attention_mod.chunked_attention

    def spy(*args, **kw):
        seen.append(kw.get("amm"))
        return orig(*args, **kw)

    monkeypatch.setattr(attention_mod, "chunked_attention", spy)
    toks = jnp.asarray(RNG.integers(0, 512, (2, 8)), jnp.int32)
    cfg, rt, params = _lm("mlp")
    lm_apply(params, cfg, rt, toks, rng=jax.random.key(2))
    assert seen and all(a is None for a in seen)
    seen.clear()
    cfg, rt, params = _lm("all")
    lm_apply(params, cfg, rt, toks, rng=jax.random.key(2))
    assert seen and all(a is not None for a in seen)


def test_no_dead_plane_cache_under_attn_only_routing():
    """apply_to="attn" routes no weight-side matmul: lm_amm_planes must
    return None instead of building an MLP digit-plane cache nothing
    reads (dead startup work + memory held for the process lifetime)."""
    from repro.models import lm_amm_planes
    cfg, rt, params = _lm("attn")
    assert lm_amm_planes(cfg, rt.amm, params) is None
    cfg, rt, params = _lm("all")
    assert lm_amm_planes(cfg, rt.amm, params) is not None


def test_apply_to_cells_are_distinct():
    """mlp / attn / all route different matmul families: all three logits
    differ pairwise, and each stays finite."""
    toks = jnp.asarray(RNG.integers(0, 512, (2, 10)), jnp.int32)
    outs = {}
    for ap in ("mlp", "attn", "all"):
        cfg, rt, params = _lm(ap)
        logits, _, _ = lm_apply(params, cfg, rt, toks, rng=jax.random.key(2))
        outs[ap] = np.asarray(logits)
        assert np.isfinite(outs[ap]).all()
    assert not np.array_equal(outs["mlp"], outs["attn"])
    assert not np.array_equal(outs["mlp"], outs["all"])
    assert not np.array_equal(outs["attn"], outs["all"])


@pytest.mark.parametrize("apply_to", ["attn", "all"])
def test_decode_matches_prefill_under_attn_routing(apply_to):
    """Cache parity: token-by-token decode through the approximate
    attention datapath reproduces the parallel forward.

    Not bitwise — decode quantizes its products over the whole cache
    slice while the chunked prefill quantizes per KV block (different
    dynamic-scale granularity, docs/attention.md) and the cache itself is
    bf16 — but it must stay within the same tolerance the exact path's
    incremental-vs-parallel test uses."""
    cfg, rt, params = _lm(apply_to)
    b, s = 2, 10
    toks = jnp.asarray(RNG.integers(0, cfg.vocab, (b, s)), jnp.int32)
    full, _, _ = lm_apply(params, cfg, rt, toks, mode="train")
    caches = init_cache(cfg, b, 16)
    outs = []
    for t in range(s):
        lg, _, caches = lm_apply(params, cfg, rt, toks[:, t:t + 1],
                                 mode="decode", caches=caches,
                                 pos=jnp.int32(t))
        outs.append(lg[:, 0])
    inc = jnp.stack(outs, axis=1)
    assert float(jnp.max(jnp.abs(inc - full))) < 1e-2


def test_train_step_grads_under_attn_routing():
    """STE keeps the loss differentiable with attention approximated."""
    from repro.models import lm_loss
    cfg, rt, params = _lm("all")
    toks = jnp.asarray(RNG.integers(0, cfg.vocab, (2, 8)), jnp.int32)
    labels = jnp.roll(toks, -1, axis=-1)
    loss, grads = jax.value_and_grad(
        lambda p: lm_loss(p, cfg, rt, toks, labels,
                          rng=jax.random.key(3))[0])(params)
    assert np.isfinite(float(loss))
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                         for g in jax.tree.leaves(grads)))
    assert np.isfinite(float(gnorm)) and float(gnorm) > 0


def test_encdec_cross_attention_routed(monkeypatch):
    """Whisper-family cross-attention is part of the apply_to contract:
    under "all" every attention() invocation — decoder self-attention
    AND both cross-attention sites — must receive the amm runtime."""
    import repro.models.transformer as tr
    seen = []
    orig = tr.attention

    def spy(*args, **kw):
        seen.append(kw.get("amm"))
        return orig(*args, **kw)

    monkeypatch.setattr(tr, "attention", spy)
    cfg = reduced(get_arch("whisper-base"))
    cfg = dataclasses.replace(cfg, amm=AmmConfig(mode="bitexact", mul="bbm0",
                                                 wl=16, param=13,
                                                 apply_to="all"))
    rt = ModelRuntime.build(cfg)
    params = lm_init(cfg, jax.random.key(0))
    toks = jnp.asarray(RNG.integers(0, cfg.vocab, (2, 8)), jnp.int32)
    enc = jnp.ones((2, cfg.encoder_len, cfg.d_model), jnp.float32) * 0.01
    logits, _, _ = lm_apply(params, cfg, rt, toks, rng=jax.random.key(2),
                            encoder_embeds=enc)
    assert seen and all(a is not None for a in seen)
    assert np.isfinite(np.asarray(logits)).all()


# ------------------------------------------------ int-code KV cache oracle
def _code_cache(k, v, wl, *, block, pos=0, s_buf=None):
    """Code-cache leaves for one layer, written in one shot at ``pos``.

    ``s_buf`` sizes the cache buffer (default: exactly the written rows);
    a larger buffer leaves unwritten blocks at the 0.0 sentinel."""
    b, s, kvh, d = k.shape
    s_buf = s_buf or s
    nb = s_buf // block
    dt = code_dtype(wl)
    kc = jnp.zeros((b, s_buf, kvh, d), dt)
    vc = jnp.zeros((b, s_buf, kvh, v.shape[-1]), dt)
    ks = jnp.zeros((b, nb, kvh), jnp.float32)
    vs = jnp.zeros((b, nb, kvh), jnp.float32)
    kc, ks = code_cache_update(kc, ks, k, pos, wl=wl)
    vc, vs = code_cache_update(vc, vs, v, pos, wl=wl)
    return {"k_codes": kc, "k_scale": ks, "v_codes": vc, "v_scale": vs}


@pytest.mark.parametrize("mul,wl,vbl", SWEEP)
def test_decode_attention_codes_matches_codes_oracle(mul, wl, vbl):
    """The codes-in datapath == the scalar closed-form codes oracle, with
    multi-block scales, ragged per-slot kv_len (written-but-dead tails)
    and envelope-edge rows in both K and V."""
    rng = np.random.default_rng(17)
    b, s, kvh, d = 2, 16, 2, 8
    q = jnp.asarray(rng.standard_normal((b, 1, 4, d)), jnp.float32)
    k = rng.standard_normal((b, s, kvh, d))
    v = rng.standard_normal((b, s, kvh, d))
    k[0, 3] = np.abs(k).max() * 100.0      # pins its block's scale high
    v[1, 5] = -np.abs(v).max() * 100.0
    cache = _code_cache(jnp.asarray(k, jnp.float32),
                        jnp.asarray(v, jnp.float32), wl, block=4)
    kv_len = jnp.asarray([7, 12], jnp.int32)
    got = decode_attention_codes(q, cache, kv_len, amm=_rt(mul, wl, vbl))
    ref = amm_decode_attention_codes_ref(q, cache, kv_len,
                                         MulSpec(mul, wl, vbl))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def _kblock_operands(wl, nb, *, block=16, m=7, n=64, seed=23):
    """PV-product operands: (M, K) floats, and V codes quantized per
    block of ``block`` rows with one envelope-edge row per block (a
    different magnitude each), so every block's scale differs."""
    rng = np.random.default_rng(seed)
    a = rng.standard_normal((m, nb * block))
    v = rng.standard_normal((nb * block, n))
    codes, scales = [], []
    for j in range(nb):
        blk = v[j * block:(j + 1) * block].copy()
        blk[j % block] *= 10.0 * (1 + j)
        c, s = amm_quantize(jnp.asarray(blk, jnp.float32), wl)
        codes.append(np.asarray(c))
        scales.append(np.asarray(s))
    return (jnp.asarray(a, jnp.float32), jnp.asarray(np.concatenate(codes)),
            jnp.asarray(np.stack(scales), jnp.float32))


@pytest.mark.parametrize("jit", [False, True], ids=["eager", "jit"])
@pytest.mark.parametrize("mul,wl,vbl", [("bbm0", 8, 5), ("bbm1", 8, 7),
                                        ("bbm0", 16, 13), ("bbm1", 16, 15)])
def test_coded_kblocks_matches_kblocks_oracle(mul, wl, vbl, jit):
    """The PV product at the served geometry (48 blocks of 16, M = 7,
    N = 64) == the per-block scalar oracle, eager and jitted (the served
    decode is jitted): one batched contraction over every block and the
    block-order combine give the oracle's bits."""
    a, codes, scales = _kblock_operands(wl, 48)
    assert len(set(np.asarray(scales).tolist())) == 48
    fn = lambda a_, c_, s_: bbm_matmul_coded_kblocks(
        a_, c_, s_, wl=wl, vbl=vbl, kind=AMM_BOOTH_KINDS[mul], block=16)
    got = (jax.jit(fn) if jit else fn)(a, codes, scales)
    ref = amm_coded_kblocks_ref(a, codes, scales, MulSpec(mul, wl, vbl),
                                block=16)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


# (M, N) of the value product's K-blocks where it is served: qwen2-0.5b's
# decode (7 query heads a KV head, head dim 64) and Moonlight's absorbed
# latent attention (16 heads, the 512 value columns of the latent)
PV_SHAPES = {"qwen2": (7, 64), "moonlight": (16, 512)}
# the ops that form a block partial: the dot form's contractions, the rows
# form's row arithmetic (select or multiply by backend) and block sums
_PV_OPS = ("dot", "multiply", "select", "shift-right-arithmetic", "reduce")


def _pv_op_counts(m, n, nb, form=None):
    """Op counts of the jitted PV product at wl = 16, vbl = 13, Type 0, in
    the form the gate picks (``form=None``) or the one given."""
    a, codes, scales = _kblock_operands(16, nb, m=m, n=n)
    if form is None:
        fn = lambda a_, c_, s_: bbm_matmul_coded_kblocks(
            a_, c_, s_, wl=16, vbl=13, kind=0, block=16)
    else:
        fn = lambda a_, c_, s_: _coded_kblocks(
            a_, c_, s_, wl=16, vbl=13, kind=0, block=16, form=form)
    ops = re.findall(r"= \S+ ([a-z-]+)\(",
                     jax.jit(fn).lower(a, codes, scales).compile().as_text())
    return {op: ops.count(op) for op in _PV_OPS}


@pytest.mark.parametrize("shape,form", [("qwen2", None), ("moonlight", None),
                                        ("qwen2", "dot")])
def test_coded_kblocks_contracts_all_blocks_at_once(shape, form):
    """The jitted PV product forms every K-block at once: its ops do not
    grow from 4 blocks to 48 (a per-block loop would hold 12 x them; below
    3 blocks the loop that adds the blocks folds away and fusion regroups
    the descales).  At both served shapes the gate picks the rows form,
    with no dot at all, and every op of the row arithmetic is counted; the
    dot form, which blocks longer than ``amm_chunk_len`` keep, holds 36
    dots (the high-digit dot plus 7 truncated rows x (digit dot + 4
    residue dots))."""
    m, n = PV_SHAPES[shape]
    if form is None:
        assert pv_form(16, 16, 13) == "rows"
    few, many = (_pv_op_counts(m, n, nb, form) for nb in (4, 48))
    assert few == many
    assert few["dot"] == (36 if form == "dot" else 0)


def test_pv_form_at_served_shapes():
    """The form gate, a pure function of the block's length and the
    operating point: the served blocks of 16 positions (qwen2's (7 x
    16)·(16 x 64) and Moonlight's (16 x 16)·(16 x 512)) take the rows form
    at both word lengths; a block longer than ``amm_chunk_len`` keeps the
    dot form (the rows sum over it would leave int32)."""
    for wl, vbl in ((16, 13), (8, 5), (16, 15)):
        assert pv_form(16, wl, vbl) == "rows"
    assert amm_chunk_len(16, 1) < 16
    assert pv_form(16, 16, 1) == "dot"
    assert pv_form(amm_chunk_len(16, 13) + 1, 16, 13) == "dot"
    assert pv_form(amm_chunk_len(16, 13), 16, 13) == "rows"


@pytest.mark.parametrize("nb", [6, 48])
@pytest.mark.parametrize("mul,wl,vbl", [("bbm0", 8, 5), ("bbm1", 8, 7),
                                        ("bbm0", 16, 13), ("bbm1", 16, 15)])
def test_coded_kblocks_latent_shape_matches_oracle(mul, wl, vbl, nb):
    """The PV product at Moonlight's latent shape (M = 16, N = 512, blocks
    of 16 with distinct scales: 48 as at the cell's 768 positions, and 6),
    jitted, == the per-block scalar oracle: Moonlight's served geometry
    beside qwen2's."""
    a, codes, scales = _kblock_operands(wl, nb, m=16, n=512)
    assert len(set(np.asarray(scales).tolist())) == nb
    got = jax.jit(lambda a_, c_, s_: bbm_matmul_coded_kblocks(
        a_, c_, s_, wl=wl, vbl=vbl, kind=AMM_BOOTH_KINDS[mul], block=16))(
            a, codes, scales)
    ref = amm_coded_kblocks_ref(a, codes, scales, MulSpec(mul, wl, vbl),
                                block=16)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


@pytest.mark.parametrize("nb", [4, 8])
@pytest.mark.parametrize("mul,wl,vbl", [("bbm0", 16, 13), ("bbm1", 16, 15)])
def test_coded_kblocks_few_blocks_match_oracle(mul, wl, vbl, nb):
    """Jitted at a few blocks, where XLA:CPU fuses the whole block combine
    into one loop, the PV product still == the oracle bit for bit in both
    forms: a descale is never contracted with the next add into one
    rounding (an FMA), which an unrolled chain of adds let it do."""
    a, codes, scales = _kblock_operands(wl, nb)
    ref = np.asarray(amm_coded_kblocks_ref(a, codes, scales,
                                           MulSpec(mul, wl, vbl), block=16))
    for form in ("rows", "dot"):
        got = jax.jit(lambda a_, c_, s_, form=form: _coded_kblocks(
            a_, c_, s_, wl=wl, vbl=vbl, kind=AMM_BOOTH_KINDS[mul], block=16,
            form=form))(a, codes, scales)
        np.testing.assert_array_equal(np.asarray(got), ref, err_msg=form)


@pytest.mark.parametrize("shape", list(PV_SHAPES))
@pytest.mark.parametrize("mul,wl,vbl", [("bbm0", 8, 5), ("bbm1", 16, 15),
                                        ("bbm0", 16, 13)])
def test_coded_kblocks_forms_agree(mul, wl, vbl, shape):
    """Both forms of the PV product give the same bits at both served
    shapes, jitted: the dot form stays for blocks the rows sum cannot
    hold in int32."""
    m, n = PV_SHAPES[shape]
    a, codes, scales = _kblock_operands(wl, 6, m=m, n=n)
    got = {form: np.asarray(jax.jit(
        lambda a_, c_, s_, form=form: _coded_kblocks(
            a_, c_, s_, wl=wl, vbl=vbl, kind=AMM_BOOTH_KINDS[mul], block=16,
            form=form))(a, codes, scales)) for form in ("rows", "dot")}
    np.testing.assert_array_equal(got["rows"], got["dot"])


@pytest.mark.parametrize("mul,wl,vbl", SWEEP)
def test_code_decode_degenerate_equals_requantize_path(mul, wl, vbl):
    """In the degenerate geometry — one scale block covering the whole
    slice, a single one-shot write, kv_len == written extent — the frozen
    write-time scale is bit-identical to the scale the requantize-per-call
    path derives per (slot, kv-head), so the two decodes agree bitwise.
    (The requantize reference runs ste=False: ``exact + (approx - exact)``
    is not bitwise ``approx`` in f32, and the code path never forms an
    exact product at all.)"""
    rng = np.random.default_rng(19)
    b, s, kvh, d = 2, 16, 2, 8
    q = jnp.asarray(rng.standard_normal((b, 1, 4, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, kvh, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, kvh, d)), jnp.float32)
    cache = _code_cache(k, v, wl, block=s)
    got = decode_attention_codes(q, cache, s, amm=_rt(mul, wl, vbl))
    ref = amm_decode_attention_ref(q, k, v, s, MulSpec(mul, wl, vbl),
                                   ste=False)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_frozen_codes_immune_to_later_arrivals():
    """The scale-drift regression pin.  Under the old whole-slice
    requantize, any write into the cache buffer — even past ``kv_len`` —
    moved the dynamic scale and with it every already-served token's
    bits.  Frozen codes make token t's contribution depend only on state
    at its own write: (a) appending envelope-edge rows after position n
    leaves the kv_len=n decode bitwise unchanged, (b) even a late write
    *into a live block* quantizes against the block's frozen first-touch
    scale instead of re-gridding its neighbours, and (c) the requantize
    path demonstrably drifts on the same scenario."""
    mul, wl, vbl = "bbm0", 8, 5
    rt = _rt(mul, wl, vbl)
    rng = np.random.default_rng(23)
    b, s, kvh, d, n = 2, 16, 2, 8, 8
    q = jnp.asarray(rng.standard_normal((b, 1, 4, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, n, kvh, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, n, kvh, d)), jnp.float32)
    cache = _code_cache(k, v, wl, block=4, s_buf=s)
    before = np.asarray(decode_attention_codes(q, cache, n, amm=rt))

    # (a) envelope-edge arrivals at positions >= n
    edge = jnp.full((b, 4, kvh, d), 100.0, jnp.float32)
    kc, ks = code_cache_update(cache["k_codes"], cache["k_scale"], edge, n,
                               wl=wl)
    vc, vs = code_cache_update(cache["v_codes"], cache["v_scale"], edge, n,
                               wl=wl)
    grown = {"k_codes": kc, "k_scale": ks, "v_codes": vc, "v_scale": vs}
    after = np.asarray(decode_attention_codes(q, grown, n, amm=rt))
    np.testing.assert_array_equal(before, after)

    # (b) a late write into a live block cannot re-grid its neighbours:
    # rows 0..5 freeze block 1's scale; an edge row at position 6 clips
    # against it, and rows 0..5 keep their exact codes
    part = _code_cache(k[:, :6], v[:, :6], wl, block=4, s_buf=s)
    old_rows = np.asarray(part["k_codes"])[:, :6].copy()
    kc2, ks2 = code_cache_update(part["k_codes"], part["k_scale"],
                                 edge[:, :1], 6, wl=wl)
    np.testing.assert_array_equal(np.asarray(kc2)[:, :6], old_rows)
    np.testing.assert_array_equal(np.asarray(ks2), np.asarray(part["k_scale"]))

    # (c) the documented drift this replaces: the requantize-per-call path
    # rescales the whole buffer, so the same dead-tail write changes the
    # served bits
    kf = np.zeros((b, s, kvh, d), np.float32)
    vf = np.zeros((b, s, kvh, d), np.float32)
    kf[:, :n], vf[:, :n] = np.asarray(k), np.asarray(v)
    ref_before = np.asarray(decode_attention(
        q, jnp.asarray(kf), jnp.asarray(vf), n, amm=rt, amm_ste=False))
    kf[:, n:n + 4] = 100.0
    vf[:, n:n + 4] = 100.0
    ref_after = np.asarray(decode_attention(
        q, jnp.asarray(kf), jnp.asarray(vf), n, amm=rt, amm_ste=False))
    assert not np.array_equal(ref_before, ref_after), \
        "whole-slice requantize no longer drifts; update the docs"


def test_code_cache_roundtrip_and_sentinel():
    """Dequantize inverts quantize to within one code step; untouched
    blocks keep the 0.0 never-written sentinel."""
    wl = 8
    rng = np.random.default_rng(31)
    k = jnp.asarray(rng.standard_normal((1, 8, 2, 4)), jnp.float32)
    nb = 8 // 4
    kc = jnp.zeros((1, 16, 2, 4), code_dtype(wl))
    ks = jnp.zeros((1, 4, 2), jnp.float32)
    kc, ks = code_cache_update(kc, ks, k, 0, wl=wl)
    assert (np.asarray(ks)[:, :nb] > 0).all()
    assert (np.asarray(ks)[:, nb:] == 0).all()          # sentinel intact
    deq = np.asarray(code_cache_dequant(kc, ks, kv_len=8))
    err = np.abs(deq[:, :8] - np.asarray(k))
    step = np.asarray(ks)[:, :nb].max()
    assert err.max() <= 0.5 * step + 1e-7
    assert (deq[:, 8:] == 0).all()


def test_decode_attention_codes_rejects_inactive_amm():
    q = jnp.zeros((1, 1, 2, 4), jnp.float32)
    cache = _code_cache(jnp.zeros((1, 8, 1, 4)), jnp.zeros((1, 8, 1, 4)),
                        8, block=4)
    with pytest.raises(ValueError, match="lowering"):
        decode_attention_codes(q, cache, 4, amm=None)
    with pytest.raises(ValueError, match="lowering"):
        decode_attention_codes(q, cache, 4, amm=_rt("bbm0", 8, 5, "mlp"))


def test_gqa_lm_decode_with_code_cache_tracks_float_cache():
    """Full-model GQA decode on the int-code cache: the logits stay close
    to the float-cache decode (the gap is bounded quantization error, not
    drift) and the cache leaves hold frozen int codes."""
    cfg = reduced(get_arch("qwen2-0.5b"))
    cfg = dataclasses.replace(
        cfg, amm=AmmConfig(mode="bitexact", mul="bbm0", wl=8, param=5,
                           apply_to="attn"))
    rt = ModelRuntime.build(cfg)
    params = lm_init(cfg, jax.random.key(0))
    toks = jnp.asarray(RNG.integers(0, cfg.vocab, (2, 6)), jnp.int32)
    ccache = init_code_cache(cfg, 2, 16, wl=8)
    fcache = init_cache(cfg, 2, 16)
    snap = None
    for t in range(6):
        lc, _, ccache = lm_apply(params, cfg, rt, toks[:, t:t + 1],
                                 mode="decode", caches=ccache,
                                 pos=jnp.int32(t))
        lf, _, fcache = lm_apply(params, cfg, rt, toks[:, t:t + 1],
                                 mode="decode", caches=fcache,
                                 pos=jnp.int32(t))
        assert float(jnp.max(jnp.abs(lc - lf))) < 0.5
        if t == 2:
            snap = np.asarray(ccache["k_codes"])[:, :, :3].copy()
    assert ccache["k_codes"].dtype == jnp.int8
    # frozen-at-write at the full-model level: rows written by step 2
    # are bitwise untouched by steps 3..5
    np.testing.assert_array_equal(
        np.asarray(ccache["k_codes"])[:, :, :3], snap)


def test_mla_lm_decode_with_code_latent_cache():
    """MLA (deepseek) serves from an int-code latent cache: decode runs,
    logits stay finite and near the float-latent decode, and latent codes
    freeze at write (later steps never rewrite earlier rows)."""
    cfg = reduced(get_arch("deepseek-v3-671b"))
    cfg = dataclasses.replace(
        cfg, amm=AmmConfig(mode="bitexact", mul="bbm0", wl=8, param=5,
                           apply_to="attn"))
    rt = ModelRuntime.build(cfg)
    params = lm_init(cfg, jax.random.key(0))
    toks = jnp.asarray(RNG.integers(0, cfg.vocab, (2, 5)), jnp.int32)
    ccache = init_code_cache(cfg, 2, 16, wl=8)
    assert set(ccache) == {"lat_codes", "lat_scale"}
    fcache = init_cache(cfg, 2, 16)
    snap = None
    for t in range(5):
        lc, _, ccache = lm_apply(params, cfg, rt, toks[:, t:t + 1],
                                 mode="decode", caches=ccache,
                                 pos=jnp.int32(t))
        lf, _, fcache = lm_apply(params, cfg, rt, toks[:, t:t + 1],
                                 mode="decode", caches=fcache,
                                 pos=jnp.int32(t))
        assert np.isfinite(np.asarray(lc)).all()
        assert float(jnp.max(jnp.abs(lc - lf))) < 0.5
        if t == 2:
            snap = np.asarray(ccache["lat_codes"])[:, :, :3].copy()
    assert ccache["lat_codes"].dtype == jnp.int8
    np.testing.assert_array_equal(
        np.asarray(ccache["lat_codes"])[:, :, :3], snap)


def test_mla_attn_routing_finite():
    """MLA (deepseek) threads the same amm routing through its expanded
    K/V products."""
    cfg = reduced(get_arch("deepseek-v3-671b"))
    cfg = dataclasses.replace(cfg, amm=AmmConfig(mode="bitexact", mul="bbm0",
                                                 wl=16, param=13,
                                                 apply_to="all"))
    rt = ModelRuntime.build(cfg)
    params = lm_init(cfg, jax.random.key(0))
    toks = jnp.asarray(RNG.integers(0, cfg.vocab, (2, 8)), jnp.int32)
    exact_cfg = dataclasses.replace(cfg, amm=AmmConfig(mode="off"))
    l_amm, _, _ = lm_apply(params, cfg, rt, toks, rng=jax.random.key(2))
    l_off, _, _ = lm_apply(params, exact_cfg, rt=ModelRuntime.build(exact_cfg),
                           tokens=toks, rng=jax.random.key(2))
    assert np.isfinite(np.asarray(l_amm)).all()
    assert not np.array_equal(np.asarray(l_amm), np.asarray(l_off))
