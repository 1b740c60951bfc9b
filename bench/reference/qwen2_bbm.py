"""Plain reference of qwen2 served on the Broken-Booth datapath.

It replays the schedule the server ran: every admission (a prompt
prefilled into a freshly zeroed slot) and every decode step (one token for
each of the batch's rows, live or not, at its own position), in order,
and gives the final hidden state of each row that produced a served
token.  It is written from the model's description and the datapath's
definition in plain ``jax.numpy``, imports nothing of the program, and
takes nothing the program made but the schedule: the token ids fed and
their positions.  Layer by layer over the whole schedule, so that one
layer's weights and key-value codes are held at a time.

  * qwen2 (arXiv:2407.10671): token embedding, per layer an RMS-normed GQA
    attention with biased Q/K/V projections and rotary positions, then an
    RMS-normed SiLU-gated MLP, both residual; a final RMS norm and the tied
    embedding as LM head.  The residual stream is bfloat16 (the model's
    published dtype), weights and everything else float32.  Rotary
    positions rotate adjacent pairs of a head's dimensions; the published
    model rotates its two halves.  The two differ by a fixed permutation
    of the Q and K columns, which random weights absorb.
  * The exact products (Q/K/V/O projections and the LM head) are taken at
    the configuration's ``exact_precision``: ``bfloat16`` rounds both
    operands to bfloat16 and sums in float32, ``float32`` is full float32,
    ``int8`` (a control) quantizes rows and columns symmetrically to int8.
  * Every MLP product and both attention products (scores and values) go
    through the Broken-Booth Type 0 multiplier on ``wl``-bit codes: the
    right operand (weight, key or value) is radix-4 Booth recoded, row i of
    a product has its low ``m_i = max(0, vbl - 2i)`` bits cleared, and the
    sum is scaled back by the operands' scales.  Codes come from symmetric
    dynamic-range quantization, ``round(v / s)`` clipped, ``s = max|v| *
    (1 / (2^(wl-1) - 1))``, one scale over the whole operand of a product:
    a weight matrix; an MLP input as it went through the server, all rows
    of one prefill or of one decode step's batch together; in attention,
    one (query rows of a key-value head, key or value block) slice.
  * Keys and values are held as codes with one scale per (16-position
    block, key-value head), fixed by the first write that touches the
    block.  A prefill dequantizes them and attends blockwise with an online
    softmax (``prefill_q_block`` query rows by ``prefill_kv_block`` key
    positions, each score and value block a product of its own); a decode
    step multiplies the codes as they are, and sums the value product
    block by block.

Integer sums are exact: each Booth row's contribution is an int8 x int8
matrix product with int32 accumulation, the activation side split into
7-bit limbs.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
NEG_INF = -1e30
KV_BLOCK = 16


# ------------------------------------------------------------ the multiplier
def quantize(v, wl: int, axes=None):
    """(int32 codes, f32 scale) with one scale over ``axes`` (None: all)."""
    lim = 2 ** (wl - 1) - 1
    vf = jnp.asarray(v, jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(vf), axis=axes, keepdims=axes is not None)
                    * (1.0 / lim), 1e-12)
    return jnp.clip(jnp.round(vf / s), -lim - 1, lim).astype(jnp.int32), s


def booth_digits(b, wl: int):
    """Radix-4 Booth digits of signed wl-bit codes, d_0 .. d_{wl/2-1}."""
    bu = b & ((1 << wl) - 1)
    out = []
    for i in range(wl // 2):
        hi = (bu >> (2 * i + 1)) & 1
        mid = (bu >> (2 * i)) & 1
        lo = (bu >> (2 * i - 1)) & 1 if i else 0
        out.append(-2 * hi + mid + lo)
    return out


def b_parts(b, wl: int, vbl: int):
    """The right operand (K, N) as int8 indicator slabs stacked along K,
    one per (truncated row, digit value), and the untruncated rows' digits
    summed into one slab; ``a_parts`` lines the left operand up with them."""
    slabs, exact = [], None
    for i, d_i in enumerate(booth_digits(b, wl)):
        m = max(0, vbl - 2 * i)
        if m == 0:
            e = d_i * (1 << (2 * i - vbl))
            exact = e if exact is None else exact + e
            continue
        for d in (-2, -1, 1, 2):
            slabs.append((d_i == d).astype(jnp.int8))
    if exact is not None:
        slabs.append(exact.astype(jnp.int8))
    return jnp.concatenate(slabs, axis=-2)


def a_parts(a, wl: int, vbl: int):
    """The left operand (..., M, K) beside ``b_parts``: ``(d a) >> m`` for
    each truncated row's digit value d, then ``a`` itself."""
    parts = []
    for i in range(wl // 2):
        m = max(0, vbl - 2 * i)
        if m:
            parts += [(d * a) >> m for d in (-2, -1, 1, 2)]
    if any(vbl - 2 * i <= 0 for i in range(wl // 2)):
        parts.append(a)
    return jnp.concatenate(parts, axis=-1)


def _idot(a, b):
    """Exact int32 ``a @ b`` for int32 a (|a| < 2^17) and small int8 b."""
    out = None
    for j in range(3):
        limb = (a >> (7 * j)) if j == 2 else ((a >> (7 * j)) & 127)
        part = jnp.matmul(limb.astype(jnp.int8), b,
                          preferred_element_type=jnp.int32) << (7 * j)
        out = part if out is None else out + part
    return out


def bbm_int(a, b, wl: int, vbl: int):
    """sum_k BBM0(a[m, k], b[k, n]) / 2^vbl as exact int32 (M, N).

    Every product is a multiple of 2^vbl.  A truncated row i contributes
    floor(d_i a / 2^m_i) once; the untruncated rows contribute
    d_i a 4^i / 2^vbl.
    """
    return _idot(a_parts(a, wl, vbl), b_parts(b, wl, vbl))


def descale(q, s_a, s_b, vbl: int):
    return (q.astype(jnp.float32) * float(1 << vbl)) * (s_a * s_b)


def amm(x, w_parts, s_w, wl: int, vbl: int):
    """An MLP product: one scale over every row of ``x`` (M, K)."""
    xq, s_x = quantize(x, wl)
    return descale(_idot(a_parts(xq, wl, vbl), w_parts), s_x, s_w, vbl)


def amm_dyn(a, b, wl: int, vbl: int):
    """An attention product of two activations, one scale for each."""
    aq, s_a = quantize(a, wl)
    bq, s_b = quantize(b, wl)
    return descale(bbm_int(aq, bq, wl, vbl), s_a, s_b, vbl)


# --------------------------------------------------------- exact products
def xdot(x, w, precision: str):
    """x (M, K) @ w (K, N) at the stated precision of the exact products."""
    if precision == "float32":
        return jnp.dot(x, w, precision=HI)
    if precision == "bfloat16":
        return jnp.dot(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
    if precision == "int8":
        xq, sx = quantize(x, 8, axes=(1,))
        wq, sw = quantize(w, 8, axes=(0,))
        y = jnp.matmul(xq.astype(jnp.int8), wq.astype(jnp.int8),
                       preferred_element_type=jnp.int32)
        return y.astype(jnp.float32) * (sx * sw)
    raise ValueError(f"unknown precision {precision!r}")


# ----------------------------------------------------------------- the model
def rmsnorm(x, w, eps):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w


def rope(x, positions, theta):
    """x (T, heads, hd) at positions (T,)."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions[:, None, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def qkv(lw, x, positions, prec, theta):
    """Biased, rotated Q (T, H, hd) and K, V (T, KV, hd) of normed rows."""
    a = lw["attn"]

    def proj(w, b):
        d, n, hd = w.shape
        return xdot(x, w.reshape(d, n * hd), prec).reshape(-1, n, hd) + b
    q = proj(a["wq"], a["bq"])
    k = proj(a["wk"], a["bk"])
    v = proj(a["wv"], a["bv"])
    return rope(q, positions, theta), rope(k, positions, theta), v


def out_proj(lw, o, prec):
    h, hd, d = lw["attn"]["wo"].shape
    return xdot(o.reshape(-1, h * hd), lw["attn"]["wo"].reshape(h * hd, d),
                prec)


def mlp(lw, x, wl, vbl):
    m = lw["mlp_parts"]
    gate = amm(x, m["w_gate"], m["s_gate"], wl, vbl)
    up = amm(x, m["w_up"], m["s_up"], wl, vbl)
    return amm(jax.nn.silu(gate) * up, m["w_down"], m["s_down"], wl, vbl)


def write_codes(codes, scales, v, pos, wl):
    """Rows ``v`` (s, KV, hd) written at ``pos`` of one slot's codes
    (S, KV, hd) and block scales (nb, KV): a block's scale is fixed by
    the first write that touches it (0 marks a block never written), and
    the rows are quantized against their block's scale and clipped."""
    lim = 2 ** (wl - 1) - 1
    s_new = v.shape[0]
    rows = pos + jnp.arange(s_new)
    blk = rows // KV_BLOCK
    nb = scales.shape[0]
    absmax = jnp.max(jnp.abs(v), axis=2)                          # (s, KV)
    cand = jax.ops.segment_max(absmax, blk, num_segments=nb)      # (nb, KV)
    cand = jnp.maximum(cand * (1.0 / lim), 1e-12)
    touched = jnp.zeros(nb, bool).at[blk].set(True)[:, None]
    scales = jnp.where((scales > 0.0) | ~touched, scales, cand)
    q = jnp.clip(jnp.round(v / scales[blk][..., None]), -lim - 1, lim)
    codes = jax.lax.dynamic_update_slice(
        codes, q.astype(codes.dtype), (pos, 0, 0))
    return codes, scales


def prefill_attention(q, kk, vv, t, wl, vbl, bq, bk):
    """Causal attention of q (T, H, hd) over dequantized keys and values
    (S, KV, hd) whose first ``t`` positions hold the prompt: query blocks
    of ``bq`` rows and key blocks of ``bk`` positions, an online softmax
    over the key blocks, each score and value block an amm product per
    key-value head."""
    tq, h, hd = q.shape
    s, kvh, _ = kk.shape
    g = h // kvh
    bq, bk = min(bq, tq), min(bk, s)
    nq, nk = -(-tq // bq), -(-s // bk)
    q = jnp.pad(q, ((0, nq * bq - tq), (0, 0), (0, 0)))
    kk = jnp.pad(kk, ((0, nk * bk - s), (0, 0), (0, 0)))
    vv = jnp.pad(vv, ((0, nk * bk - s), (0, 0), (0, 0)))
    outs = []
    for qi in range(nq):
        qb = q[qi * bq:(qi + 1) * bq] * (1.0 / (hd ** 0.5))       # (bq,H,hd)
        qpos = qi * bq + jnp.arange(bq)
        heads = []
        for j in range(kvh):
            qg = qb[:, j * g:(j + 1) * g].transpose(1, 0, 2).reshape(g * bq, hd)
            m = jnp.full((g * bq, 1), NEG_INF, jnp.float32)
            l_ = jnp.zeros((g * bq, 1), jnp.float32)
            acc = jnp.zeros((g * bq, hd), jnp.float32)
            for ki in range(nk):
                kb = kk[ki * bk:(ki + 1) * bk, j]                      # (bk,hd)
                vb = vv[ki * bk:(ki + 1) * bk, j]
                sc = amm_dyn(qg, kb.T, wl, vbl).reshape(g, bq, bk)
                kpos = ki * bk + jnp.arange(bk)
                live = (kpos[None, :] < t) & (qpos[:, None] >= kpos[None, :])
                sc = jnp.where(live[None], sc, NEG_INF).reshape(g * bq, bk)
                m_new = jnp.maximum(m, sc.max(axis=-1, keepdims=True))
                p = jnp.exp(sc - m_new)
                alpha = jnp.exp(m - m_new)
                l_ = l_ * alpha + p.sum(axis=-1, keepdims=True)
                acc = acc * alpha + amm_dyn(p, vb, wl, vbl)
                m = m_new
            o = acc / jnp.maximum(l_, 1e-30)
            heads.append(o.reshape(g, bq, hd).transpose(1, 0, 2))
        outs.append(jnp.concatenate(heads, axis=1))
    return jnp.concatenate(outs, axis=0)[:tq]


def decode_attention(q, kc, ks, vc, vs, n, wl, vbl):
    """One row's attention from its slot's codes: q (H, hd); codes
    (S, KV, hd), block scales (nb, KV); positions below ``n`` live.  The
    query of each key-value head and its probabilities are quantized per
    call; the value product is summed block by block."""
    h, hd = q.shape
    s, kvh, _ = kc.shape
    g = h // kvh
    nb = s // KV_BLOCK
    live = jnp.arange(s) < n
    heads = []
    for j in range(kvh):
        qq, s_q = quantize(q[j * g:(j + 1) * g] / (hd ** 0.5), wl)
        kt = jnp.where(live[None, :], kc[:, j].T, 0)              # (hd, S)
        sc = descale(bbm_int(qq, kt, wl, vbl), s_q,
                     jnp.repeat(ks[:, j], KV_BLOCK)[None, :], vbl)
        p = jax.nn.softmax(jnp.where(live[None, :], sc, NEG_INF), axis=-1)
        pq, s_p = quantize(p, wl)                                  # (g, S)
        vcod = jnp.where(live[:, None], vc[:, j], 0)               # (S, hd)
        pb = pq.reshape(g, nb, KV_BLOCK).transpose(1, 0, 2)       # (nb,g,16)
        vb = vcod.reshape(nb, KV_BLOCK, hd)
        yq = jax.vmap(lambda a, b: bbm_int(a, b, wl, vbl))(pb, vb)
        parts = descale(yq, s_p, vs[:, j][:, None, None], vbl)    # (nb,g,hd)
        heads.append(jax.lax.fori_loop(
            1, nb, lambda i, acc: acc + parts[i], parts[0]))
    return jnp.concatenate(heads, axis=0)                          # (H, hd)


@partial(jax.jit, static_argnames=("o",), donate_argnums=(2,))
def prefill_layer(lw, h, cache, slot, *, o):
    """One layer of one admission: slot ``slot`` is zeroed, then its
    prompt rows h (T, d) go through the layer from position 0."""
    wl, vbl, prec = o["wl"], o["vbl"], o["prec"]
    t = h.shape[0]
    cache = {k: v.at[slot].set(0) for k, v in cache.items()}
    x = rmsnorm(h, lw["attn_norm"], o["eps"])
    q, k, v = qkv(lw, x, jnp.arange(t), prec, o["theta"])
    kc, ks = write_codes(cache["k_codes"][slot], cache["k_scale"][slot], k,
                         0, wl)
    vc, vs = write_codes(cache["v_codes"][slot], cache["v_scale"][slot], v,
                         0, wl)
    live = (jnp.arange(kc.shape[0]) < t)[:, None, None]
    blk = jnp.arange(kc.shape[0]) // KV_BLOCK
    kk = jnp.where(live, kc * ks[blk][..., None], 0.0)
    vv = jnp.where(live, vc * vs[blk][..., None], 0.0)
    att = prefill_attention(q, kk, vv, t, wl, vbl, o["bq"], o["bk"])
    h = h + out_proj(lw, att, prec).astype(h.dtype)
    h = h + mlp(lw, rmsnorm(h, lw["mlp_norm"], o["eps"]), wl,
                vbl).astype(h.dtype)
    cache = {"k_codes": cache["k_codes"].at[slot].set(kc),
             "k_scale": cache["k_scale"].at[slot].set(ks),
             "v_codes": cache["v_codes"].at[slot].set(vc),
             "v_scale": cache["v_scale"].at[slot].set(vs)}
    return h, cache


@partial(jax.jit, static_argnames=("o",), donate_argnums=(2,))
def decode_layer(lw, h, cache, pos, *, o):
    """One layer of one decode step: row b of h (B, d) is slot b's token
    at position pos[b]; the MLP takes all B rows as one operand."""
    wl, vbl, prec = o["wl"], o["vbl"], o["prec"]
    x = rmsnorm(h, lw["attn_norm"], o["eps"])
    q, k, v = qkv(lw, x, pos, prec, o["theta"])        # (B, heads, hd)
    wr =jax.vmap(lambda c, s, r, p: write_codes(c, s, r[None], p, wl))
    kc, ks = wr(cache["k_codes"], cache["k_scale"], k, pos)
    vc, vs = wr(cache["v_codes"], cache["v_scale"], v, pos)
    att = jax.vmap(lambda *a: decode_attention(*a, wl, vbl))(
        q, kc, ks, vc, vs, pos + 1)
    h = h + out_proj(lw, att, prec).astype(h.dtype)
    h = h + mlp(lw, rmsnorm(h, lw["mlp_norm"], o["eps"]), wl,
                vbl).astype(h.dtype)
    return h, {"k_codes": kc, "k_scale": ks, "v_codes": vc, "v_scale": vs}


@partial(jax.jit, static_argnames=("wl", "vbl"))
def layer_weights(layers, i, *, wl, vbl):
    """Layer ``i``'s weights with its MLP weights quantized and recoded."""
    lw = jax.tree.map(lambda x: x[i], layers)
    parts = {}
    for name, key in (("w_gate", "s_gate"), ("w_up", "s_up"),
                      ("w_down", "s_down")):
        wq, s = quantize(lw["mlp"][name], wl)
        parts[name], parts[key] = b_parts(wq, wl, vbl), s
    return dict(lw, mlp_parts=parts)


class Options(dict):
    """Static options of the layer functions (hashable)."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def options(config: dict, wl: int, vbl: int, precision: str) -> Options:
    blocks = config["attention_blocks"]
    return Options(wl=wl, vbl=vbl, prec=precision,
                   eps=float(config["rms_norm_eps"]),
                   theta=float(config["rope_theta"]),
                   bq=int(blocks["prefill_q"]), bk=int(blocks["prefill_kv"]))


def replay(weights, config: dict, schedule: List[Dict], slots: int,
           max_len: int, opts: Options):
    """Final hidden states (bf16) of every row the schedule marks.

    schedule: in order, ``{"prefill": slot, "tokens": (T,)}`` or
    ``{"tokens": (B,), "pos": (B,)}``, each with ``"rows"``: the row
    indices whose output is compared (a prefill's last row).
    """
    kvh = config["num_key_value_heads"]
    hd = config["hidden_size"] // config["num_attention_heads"]
    nb = max_len // KV_BLOCK
    embed = weights["embed"]
    hs = [embed[jnp.asarray(ev["tokens"])].astype(jnp.bfloat16)
          for ev in schedule]
    pos = [None if "prefill" in ev else jnp.asarray(ev["pos"], jnp.int32)
           for ev in schedule]
    with jax.default_matmul_precision("highest"):
        for i in range(config["num_hidden_layers"]):
            lw = layer_weights(weights["layers"], i, wl=opts["wl"],
                               vbl=opts["vbl"])
            cache = {
                "k_codes": jnp.zeros((slots, max_len, kvh, hd), jnp.int32),
                "v_codes": jnp.zeros((slots, max_len, kvh, hd), jnp.int32),
                "k_scale": jnp.zeros((slots, nb, kvh), jnp.float32),
                "v_scale": jnp.zeros((slots, nb, kvh), jnp.float32)}
            for e, ev in enumerate(schedule):
                if "prefill" in ev:
                    hs[e], cache = prefill_layer(
                        lw, hs[e], cache, jnp.int32(ev["prefill"]), o=opts)
                else:
                    hs[e], cache = decode_layer(lw, hs[e], cache, pos[e],
                                                o=opts)
            del lw, cache
    return jnp.concatenate([h[jnp.asarray(ev["rows"], jnp.int32)]
                            for h, ev in zip(hs, schedule) if len(ev["rows"])])


@partial(jax.jit, static_argnames=("eps", "prec"))
def logits(h, final_norm, embed, *, eps, prec):
    return xdot(rmsnorm(h, final_norm, eps), embed.T, prec)


def gaps(weights, config, h_ref, tokens, *, h_top=None, precision: str,
         top_precision: str = None, chunk: int = 1024):
    """How far below the reference's best logit each token's logit lies.

    ``tokens`` (N,) are the served tokens; with ``h_top`` (a control's
    hidden states) the tokens are instead those the control puts first.
    """
    eps = float(config["rms_norm_eps"])
    out = []
    with jax.default_matmul_precision("highest"):
        for a in range(0, h_ref.shape[0], chunk):
            ref = logits(h_ref[a:a + chunk], weights["final_norm"],
                         weights["embed"], eps=eps, prec=precision)
            if h_top is None:
                tok = jnp.asarray(tokens[a:a + chunk], jnp.int32)
            else:
                tok = jnp.argmax(logits(
                    h_top[a:a + chunk], weights["final_norm"],
                    weights["embed"], eps=eps, prec=top_precision), axis=-1)
            got = jnp.take_along_axis(ref, tok[:, None], axis=-1)[:, 0]
            out.append(np.asarray(jnp.max(ref, axis=-1) - got, np.float64))
    return np.concatenate(out) if out else np.zeros(0)
