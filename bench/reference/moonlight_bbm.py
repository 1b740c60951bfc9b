"""Plain reference of Moonlight-16B-A3B served on the Broken-Booth datapath.

It replays the schedule the server ran, as ``qwen2_bbm`` does for qwen2
(every admission into a freshly zeroed slot and every decode step, in
order, layer by layer over the whole schedule), and gives the final
hidden state of each row that produced a served token.  A prompt may
come in chunks: each chunk is one call from the position the last one
reached, into the slot's cache slice held aside until the last chunk
(the slot's row of the decode steps in between is a dead row).  Written
from the model's description in plain ``jax.numpy``; it imports nothing
of the program, and the multiplier, the quantizer, the exact products, the
norms, rotary positions and the code writes are ``qwen2_bbm``'s own.

  * Moonlight-16B-A3B (DeepSeek-V3 architecture, its published
    config.json): token embedding; per layer an RMS-normed multi-head
    latent attention, then an RMS-normed feed-forward, both residual; the
    first layer's feed-forward is a dense SiLU-gated MLP, every later
    one a mixture of experts; a final RMS norm and an untied LM head.
  * Latent attention without q-LoRA: ``q = x W_q`` per head, split into a
    no-position part (128) and a rotated part (64); the cached latent is
    ``[RMSnorm(x W_dkv[:512]) ; rope(x W_dkv[512:])]``, 576 wide, shared
    by the 16 heads.  In the absorbed form a head's score at position t
    is ``[q_nope W_uk,hᵀ ; q_rope] · latent_t / sqrt(192)`` and its output
    ``(sum_t p(t) c_t) W_uv,h``, then ``W_o``; in exact arithmetic that is
    the published formulation with K and V expanded from the latent.
  * The expert layer: router scores ``sigmoid(x W_r)`` in float32 at full
    precision; the experts are chosen on the scores plus the correction
    bias, weighted by the scores alone, normalized over the six and
    scaled by 2.446; two shared experts are one SiLU-gated MLP of twice
    the expert width, added to every token.
  * The exact products (W_q, W_dkv, folding W_uk into the query, W_uv,
    W_o and the LM head) are taken at the configuration's
    ``exact_precision``.  Every MLP and expert product and both latent
    products (scores and values) go through the Broken-Booth Type 0
    multiplier on ``wl``-bit codes (``qwen2_bbm``'s module docstring), one
    scale over: a weight matrix; a dense or shared-expert input, all rows
    of the call; a routed expert's input, the rows routed to it in the
    call; one token's 16 absorbed query rows; one token's 16 rows of
    probabilities.
  * The latent cache holds codes with one scale per (slot, 16-position
    block), fixed by the first write that touches the block.  Scores
    multiply the codes as they are, the block scales expanded per
    position; values multiply the first 512 code columns block by block.
    A prefill attends token by token as a decode step would, each token
    masked to the prompt positions at or before it.

Departures, each as the program has them:

  * rotary positions rotate adjacent pairs of the 64 rope dimensions; the
    published model rotates its two halves.  The two differ by a fixed
    permutation of W_q's and W_dkv's rope columns, which random weights
    absorb;
  * the residual stream is bfloat16 (the published dtype), weights and
    everything else float32;
  * the expert share: the layer holds experts ``first_held ..
    first_held + n_routed_experts - 1`` of the router's outputs, and only
    their part of each token's output is computed; the router scores and
    chooses among all of them.

Routing: the program's float32 hidden states differ from these by
rounding, and near a tie one chosen expert can differ, which changes a
token far more than rounding does.  So each layer takes the expert ids
the program logged, and returns its own biased scores beside them, for
the runner to judge each choice against (``route_gaps``).  A control
(``own_routing``) chooses its own experts.
"""
from __future__ import annotations

from functools import partial
from pathlib import Path
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench import manifest as mf

Q = mf.load_module(Path(__file__).resolve().parent / "qwen2_bbm.py")
KV_BLOCK = Q.KV_BLOCK


# ----------------------------------------------------------- latent attention
def project(a, x, positions, o):
    """(q_nope (T, H, nope), q_rope (T, H, rope), latent (T, r + rope))."""
    prec, nope, r = o["prec"], o["nope"], o["r"]
    d, h, qk = a["wq"].shape
    q = Q.xdot(x, a["wq"].reshape(d, h * qk), prec).reshape(-1, h, qk)
    q_rope = Q.rope(q[..., nope:], positions, o["theta"])
    lat = Q.xdot(x, a["w_dkv"], prec)
    c = Q.rmsnorm(lat[:, :r], a["kv_norm"], o["eps"])
    k_rope = Q.rope(lat[:, None, r:], positions, o["theta"])[:, 0]
    return q[..., :nope], q_rope, jnp.concatenate([c, k_rope], axis=-1)


def per_head(x, w, prec):
    """x (T, H, K) times w (H, K, N), head by head: (T, H, N)."""
    return jax.vmap(lambda xh, wh: Q.xdot(xh, wh, prec),
                    in_axes=(1, 0), out_axes=1)(x, w)


def absorb(a, q_nope, q_rope, prec):
    """[q_nope,h W_uk,hᵀ ; q_rope,h]: (T, H, r + rope)."""
    w_uk = a["w_uk"].transpose(1, 2, 0)                  # (H, nope, r)
    return jnp.concatenate([per_head(q_nope, w_uk, prec), q_rope], axis=-1)


def latent_token(q, qpos, codes, scales, n, o):
    """One token's absorbed attention from one slot's latent codes.

    q (H, L) absorbed query; codes (S, L); scales (nb,); positions below
    ``n`` and not after ``qpos`` live.  Returns the heads' value latents
    (H, r)."""
    wl, vbl, r = o["wl"], o["vbl"], o["r"]
    s = codes.shape[0]
    kpos = jnp.arange(s)
    cz = jnp.where((kpos < n)[:, None], codes, 0)
    qq, s_q = Q.quantize(q * o["qk_scale"], wl)
    sc = Q.descale(Q.bbm_int(qq, cz.T, wl, vbl), s_q,
                   jnp.repeat(scales, KV_BLOCK)[None, :], vbl)
    live = (kpos < n) & (kpos <= qpos)
    p = jax.nn.softmax(jnp.where(live[None, :], sc, Q.NEG_INF), axis=-1)
    pq, s_p = Q.quantize(p, wl)                                  # (H, S)
    nb = s // KV_BLOCK
    pb = pq.reshape(-1, nb, KV_BLOCK).transpose(1, 0, 2)          # (nb,H,16)
    vb = cz[:, :r].reshape(nb, KV_BLOCK, r)
    yq = jax.vmap(lambda a, b: Q.bbm_int(a, b, wl, vbl))(pb, vb)
    parts = Q.descale(yq, s_p, scales[:, None, None], vbl)        # (nb,H,r)
    return jax.lax.fori_loop(1, nb, lambda i, acc: acc + parts[i],
                             parts[0])


def attn_out(a, o_lat, prec):
    """(T, H, r) value latents -> (T, d): W_uv per head, then W_o."""
    o = per_head(o_lat, a["w_uv"].transpose(1, 0, 2), prec)     # (T,H,v)
    h, v, d = a["wo"].shape
    return Q.xdot(o.reshape(-1, h * v), a["wo"].reshape(h * v, d), prec)


# ------------------------------------------------------------ feed-forward
def route_scores(m, x):
    """(scores, biased scores), (T, E): float32 at full precision."""
    logits = jnp.dot(x.astype(jnp.float32), m["router"],
                     precision=Q.HI)
    scores = jax.nn.sigmoid(logits)
    return scores, scores + m["router_bias"]


def experts(m, x, ids, o):
    """(routed + shared expert output (T, d), biased scores (T, E), the
    ids used (T, k)).  ``ids`` None: choose by the biased scores."""
    wl, vbl = o["wl"], o["vbl"]
    scores, biased = route_scores(m, x)
    if ids is None:
        _, ids = jax.lax.top_k(biased, o["top_k"])
    w = jnp.take_along_axis(scores, ids, axis=-1)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9) * o["rsf"]
    held = o["first_held"] + jnp.arange(m["experts"]["s_gate"].shape[0])
    sel = ids[None, :, :] == held[:, None, None]              # (E_h, T, k)
    routed = sel.any(-1)                                       # (E_h, T)
    xe = jnp.where(routed[..., None], x[None], 0.0)

    def one(xx, p):
        gate = Q.amm(xx, p["w_gate"], p["s_gate"], wl, vbl)
        up = Q.amm(xx, p["w_up"], p["s_up"], wl, vbl)
        return Q.amm(jax.nn.silu(gate) * up, p["w_down"], p["s_down"], wl,
                     vbl)
    ye = jax.vmap(one)(xe, m["experts"])                       # (E_h,T,d)
    we = jnp.sum(jnp.where(sel, w[None], 0.0), axis=-1)        # (E_h, T)
    y = jnp.sum(ye * we[..., None], axis=0)
    y = y + Q.mlp({"mlp_parts": m["shared_parts"]}, x, wl, vbl)
    return y, biased, ids


def ffn(lw, h, ids, o):
    """The layer's feed-forward on rows h (T, d), residual added; with it
    the routing (biased scores, ids) of an expert layer, else None."""
    x = Q.rmsnorm(h, lw["mlp_norm"], o["eps"])
    if "moe" not in lw:
        return h + Q.mlp(lw, x, o["wl"], o["vbl"]).astype(h.dtype), None
    y, biased, used = experts(lw["moe"], x, ids, o)
    return h + y.astype(h.dtype), (biased, used)


# ------------------------------------------------------------------ layers
@partial(jax.jit, static_argnames=("o",), donate_argnums=(2,))
def prefill_layer(lw, h, sub, start, ids, *, o):
    """One layer of one prefill call: its prompt rows h (T, d) go through
    the layer from position ``start`` into one slot's cache slice
    ``sub`` (codes (S, 1, L), scales (nb, 1); zeros at a prompt's first
    call)."""
    a, prec = lw["attn"], o["prec"]
    t = h.shape[0]
    x = Q.rmsnorm(h, lw["attn_norm"], o["eps"])
    pos = start + jnp.arange(t)
    q_nope, q_rope, lat = project(a, x, pos, o)
    codes, scales = Q.write_codes(sub["codes"], sub["scale"], lat[:, None],
                                  start, o["wl"])
    qa = absorb(a, q_nope, q_rope, prec)
    o_lat = jax.vmap(lambda q, p: latent_token(
        q, p, codes[:, 0], scales[:, 0], start + t, o))(qa, pos)
    h = h + attn_out(a, o_lat, prec).astype(h.dtype)
    h, route = ffn(lw, h, ids, o)
    return h, {"codes": codes, "scale": scales}, route


@partial(jax.jit, donate_argnums=(0,))
def put_slot(cache, slot, sub):
    """The cache with slot ``slot``'s slice replaced by ``sub``."""
    return {k: v.at[slot].set(sub[k]) for k, v in cache.items()}


@partial(jax.jit, static_argnames=("o",), donate_argnums=(2,))
def decode_layer(lw, h, cache, pos, ids, *, o):
    """One layer of one decode step: row b of h (B, d) is slot b's token
    at position pos[b]; the feed-forward takes all B rows as one call."""
    a, prec = lw["attn"], o["prec"]
    x = Q.rmsnorm(h, lw["attn_norm"], o["eps"])
    q_nope, q_rope, lat = project(a, x, pos, o)
    codes, scales = jax.vmap(lambda c, s, v, p: Q.write_codes(
        c, s, v[None, None], p, o["wl"]))(cache["codes"], cache["scale"],
                                          lat, pos)
    qa = absorb(a, q_nope, q_rope, prec)
    o_lat = jax.vmap(lambda q, p, c, s: latent_token(
        q, p, c[:, 0], s[:, 0], p + 1, o))(qa, pos, codes, scales)
    h = h + attn_out(a, o_lat, prec).astype(h.dtype)
    h, route = ffn(lw, h, ids, o)
    return h, {"codes": codes, "scale": scales}, route


def _parts(w, wl, vbl):
    wq, s = Q.quantize(w, wl)
    return Q.b_parts(wq, wl, vbl), s


def _mlp_parts(mlp, wl, vbl):
    parts = {}
    for name, key in (("w_gate", "s_gate"), ("w_up", "s_up"),
                      ("w_down", "s_down")):
        parts[name], parts[key] = _parts(mlp[name], wl, vbl)
    return parts


@partial(jax.jit, static_argnames=("wl", "vbl"))
def dense_weights(lw, *, wl, vbl):
    """The dense layer's weights with its MLP weights recoded."""
    return dict(lw, mlp_parts=_mlp_parts(lw["mlp"], wl, vbl))


@partial(jax.jit, static_argnames=("wl", "vbl"))
def moe_weights(layers, i, *, wl, vbl):
    """Expert layer ``i`` with every held expert's and the shared
    experts' weights recoded (each matrix its own scale)."""
    lw = jax.tree.map(lambda x: x[i], layers)
    m = lw["moe"]
    moe = {"router": m["router"], "router_bias": m["router_bias"],
           "experts": jax.vmap(lambda g, u, d: _mlp_parts(
               {"w_gate": g, "w_up": u, "w_down": d}, wl, vbl))(
                   m["w_gate"], m["w_up"], m["w_down"]),
           "shared_parts": _mlp_parts(m["shared"], wl, vbl)}
    return {"attn": lw["attn"], "attn_norm": lw["attn_norm"],
            "mlp_norm": lw["mlp_norm"], "moe": moe}


def options(config: dict, wl: int, vbl: int, precision: str,
            own_routing: bool = False) -> Q.Options:
    nope, rope_d = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    return Q.Options(wl=wl, vbl=vbl, prec=precision,
                   eps=float(config["rms_norm_eps"]),
                   theta=float(config["rope_theta"]), nope=nope,
                   r=config["kv_lora_rank"],
                   qk_scale=1.0 / ((nope + rope_d) ** 0.5),
                   top_k=config["num_experts_per_tok"],
                   rsf=float(config["routed_scaling_factor"]),
                   first_held=config["expert_share"]["first_held"],
                   own_routing=own_routing)


def replay(weights, config: dict, schedule: List[Dict], routing,
           slots: int, max_len: int, opts: Q.Options):
    """(final hidden states (bf16) of every row the schedule marks,
    routing): ``routing`` holds, per event, the (expert layers, T, k) ids
    the program logged; the returned one, per event and expert layer,
    (biased scores (T, E), ids used (T, k)).

    schedule: as in ``qwen2_bbm.replay``; a prefill event may also give
    its first position (``start``, 0 if absent) and whether it ends its
    prompt (``last``, true if absent).
    """
    lat = config["kv_lora_rank"] + config["qk_rope_head_dim"]
    first = config["first_k_dense_replace"]
    nb = max_len // KV_BLOCK
    wl, vbl = opts["wl"], opts["vbl"]
    hs = [weights["embed"][jnp.asarray(ev["tokens"])].astype(jnp.bfloat16)
          for ev in schedule]
    pos = [None if "prefill" in ev else jnp.asarray(ev["pos"], jnp.int32)
           for ev in schedule]
    route = [[] for _ in schedule]
    with jax.default_matmul_precision("highest"):
        for i in range(config["num_hidden_layers"]):
            if i < first:
                lw = dense_weights(weights["dense_prefix"][i], wl=wl,
                                   vbl=vbl)
            else:
                lw = moe_weights(weights["layers"], i - first, wl=wl,
                                 vbl=vbl)
            cache = {"codes": jnp.zeros((slots, max_len, 1, lat), jnp.int32),
                     "scale": jnp.zeros((slots, nb, 1), jnp.float32)}
            fed = {}            # slot -> the slice of a prompt in chunks
            for e, ev in enumerate(schedule):
                ids = None
                if i >= first and not opts["own_routing"]:
                    ids = jnp.asarray(np.asarray(routing[e][i - first])
                                      .reshape(len(ev["tokens"]), -1),
                                      jnp.int32)
                if "prefill" in ev:
                    slot, start = ev["prefill"], ev.get("start", 0)
                    sub = fed.pop(slot) if start else {
                        "codes": jnp.zeros((max_len, 1, lat), jnp.int32),
                        "scale": jnp.zeros((nb, 1), jnp.float32)}
                    hs[e], sub, r = prefill_layer(
                        lw, hs[e], sub, jnp.int32(start), ids, o=opts)
                    if ev.get("last", True):
                        cache = put_slot(cache, jnp.int32(slot), sub)
                    else:
                        fed[slot] = sub
                else:
                    hs[e], cache, r = decode_layer(lw, hs[e], cache, pos[e],
                                                   ids, o=opts)
                if r is not None:
                    route[e].append(r)
            del lw, cache, fed
    # marked rows picked on the host: indexing on the device compiles a
    # gather for every count of marked rows a step has
    rows = np.concatenate([np.asarray(h)[np.asarray(ev["rows"], np.int64)]
                           for h, ev in zip(hs, schedule)
                           if len(ev["rows"])])
    return jnp.asarray(rows), route


@jax.jit
def _route_gaps(biased, ids):
    kth = jax.lax.top_k(biased, ids.shape[-1])[0][:, -1:]
    return kth - jnp.take_along_axis(biased, ids, axis=-1)


def route_gaps(route_ref, route_ids=None) -> np.ndarray:
    """How far below the reference's k-th best biased score each chosen
    expert's biased score lies (0 or less: among the reference's own
    top k), for every row, expert layer and choice.  The choices are the
    reference's ids (the program's, logged) or, given ``route_ids`` (a
    control's routing), those."""
    out = []
    for e, layers in enumerate(route_ref):
        for j, (biased, ids) in enumerate(layers):
            if route_ids is not None:
                ids = route_ids[e][j][1]
            out.append(np.asarray(_route_gaps(biased, ids)).reshape(-1))
    return np.concatenate(out) if out else np.zeros(0)


def gaps(weights, config, h_ref, tokens, **kw):
    """``qwen2_bbm.gaps`` through the untied LM head."""
    head = {"final_norm": weights["final_norm"],
            "embed": weights["lm_head"].T}
    return Q.gaps(head, config, h_ref, tokens, **kw)
