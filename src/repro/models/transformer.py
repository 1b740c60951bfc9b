"""Decoder-only LM covering the dense / moe / ssm / hybrid / vlm families.

One parameter table + one apply function per family concern, composed by
config.  Layers run under ``jax.lax.scan`` over stacked parameters (compile
time stays flat in depth — essential for the 512-device dry-run), with:

  * dense / vlm:  [attn + mlp] x L
  * moe:          first_k_dense dense layers (unstacked python loop), then
                  [attn + moe] scanned; optional MTP head (deepseek)
  * ssm:          [mamba2] x L
  * hybrid:       groups of ``shared_attn_every`` mamba layers, a weight-
                  shared attention+mlp block after each group (zamba2); the
                  shared block's KV caches are stacked per invocation

Modes: "train" (causal, no caches), "prefill" (returns filled caches),
"decode" (single position against caches).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .. import trace
from ..configs.base import ArchConfig
from .attention import attention, attn_table, mla_attention, mla_table
from .common import (AmmRuntime, Spec, cross_entropy_loss, init_params,
                     param_logical_axes, rmsnorm)
from .mamba2 import mamba_apply, mamba_table
from .moe import mlp_apply, mlp_table, moe_apply, moe_table

__all__ = ["lm_table", "lm_init", "lm_apply", "lm_amm_planes", "lm_loss",
           "init_cache", "ModelRuntime"]


@dataclasses.dataclass(frozen=True)
class ModelRuntime:
    """Static knobs threaded through apply (jit-static).

    attn_remat / shard_heads are the beyond-paper perf levers recorded in
    docs/perf.md §Model-side perf levers (defaults keep the paper-faithful
    baseline).
    """
    amm: AmmRuntime
    remat: bool = False
    use_pallas_attention: bool = False
    attn_remat: bool = False
    shard_heads: bool = False
    causal_skip: bool = False
    moe_gather_weights: bool = False
    attn_p_bf16: bool = False

    @staticmethod
    def build(cfg: ArchConfig, remat: bool = False,
              use_pallas: bool = False, attn_remat: bool = False,
              shard_heads: bool = False, causal_skip: bool = False,
              moe_gather_weights: bool = False,
              attn_p_bf16: bool = False) -> "ModelRuntime":
        return ModelRuntime(AmmRuntime.build(cfg.amm), remat, use_pallas,
                            attn_remat, shard_heads, causal_skip,
                            moe_gather_weights, attn_p_bf16)

    def build_planes(self, cfg: ArchConfig, params):
        """Per-parameter Booth digit-plane cache for these weights.

        Convenience for serving/eval entry points whose params are fixed:
        ``lm_apply(..., amm_planes=rt.build_planes(cfg, params))`` hoists
        the bitexact datapath's weight decode phase out of every step.
        None when the configured amm mode caches nothing.
        """
        return lm_amm_planes(cfg, self.amm, params)


# ----------------------------------------------------------------- tables
def _attn_block_table(cfg: ArchConfig) -> Dict[str, Any]:
    d = cfg.d_model
    t = {"attn_norm": Spec((d,), ("embed",), "ones")}
    t["attn"] = mla_table(cfg) if cfg.use_mla else attn_table(cfg)
    return t


def _dense_layer_table(cfg: ArchConfig, d_ff=None) -> Dict[str, Any]:
    d = cfg.d_model
    t = _attn_block_table(cfg)
    t["mlp_norm"] = Spec((d,), ("embed",), "ones")
    t["mlp"] = mlp_table(d, d_ff or cfg.d_ff)
    return t


def _moe_layer_table(cfg: ArchConfig) -> Dict[str, Any]:
    d = cfg.d_model
    t = _attn_block_table(cfg)
    t["mlp_norm"] = Spec((d,), ("embed",), "ones")
    t["moe"] = moe_table(cfg)
    return t


def _ssm_layer_table(cfg: ArchConfig) -> Dict[str, Any]:
    return {"norm": Spec((cfg.d_model,), ("embed",), "ones"),
            "mamba": mamba_table(cfg)}


def _stack(table: Dict, n: int) -> Dict:
    """Prefix every Spec with a stacked 'layers' axis."""
    return jax.tree.map(
        lambda s: Spec((n,) + s.shape, ("layers",) + s.axes, s.init, s.scale),
        table, is_leaf=lambda x: isinstance(x, Spec))


def lm_table(cfg: ArchConfig) -> Dict[str, Any]:
    d, v = cfg.d_model, cfg.vocab
    t: Dict[str, Any] = {
        "embed": Spec((v, d), ("vocab", "embed"), "normal", 0.01),
        "final_norm": Spec((d,), ("embed",), "ones"),
    }
    if not cfg.tie_embeddings:
        t["lm_head"] = Spec((d, v), ("embed", "vocab"), "normal", 0.01)

    if cfg.family in ("dense", "vlm", "audio"):
        layer = _dense_layer_table(cfg)
        if cfg.is_encoder_decoder:
            enc_layer = _dense_layer_table(cfg)
            t["encoder"] = {
                "layers": _stack(enc_layer, cfg.n_encoder_layers),
                "norm": Spec((d,), ("embed",), "ones"),
            }
            dec = _dense_layer_table(cfg)
            dec["xattn_norm"] = Spec((d,), ("embed",), "ones")
            dec["xattn"] = attn_table(cfg)
            t["layers"] = _stack(dec, cfg.n_layers)
        else:
            t["layers"] = _stack(layer, cfg.n_layers)
    elif cfg.family == "moe":
        t["dense_prefix"] = [
            _dense_layer_table(cfg) for _ in range(cfg.first_k_dense)]
        t["layers"] = _stack(_moe_layer_table(cfg),
                             cfg.n_layers - cfg.first_k_dense)
        if cfg.mtp_depth:
            mtp = _moe_layer_table(cfg)
            mtp["proj"] = Spec((2 * d, d), (None, "embed"))
            mtp["norm"] = Spec((d,), ("embed",), "ones")
            t["mtp"] = mtp
    elif cfg.family == "ssm":
        t["layers"] = _stack(_ssm_layer_table(cfg), cfg.n_layers)
    elif cfg.family == "hybrid":
        every = cfg.shared_attn_every
        assert cfg.n_layers % every == 0
        groups, per = cfg.n_layers // every, every
        inner = _stack(_ssm_layer_table(cfg), per)
        t["layers"] = _stack(inner, groups)          # (groups, per, ...)
        t["shared_block"] = _dense_layer_table(cfg)
    else:
        raise ValueError(cfg.family)
    return t


def lm_init(cfg: ArchConfig, key, dtype=jnp.float32):
    return init_params(lm_table(cfg), key, dtype)


def lm_amm_planes(cfg: ArchConfig, amm: AmmRuntime, params):
    """Booth digit-plane cache for every amm-approximated weight.

    The bitexact approximate-matmul datapath quantizes and radix-4-decodes
    its weight operand on every call; weights are constant across decode
    steps and serving requests, so the whole decode phase (dynamic scale +
    digit planes, ``AmmRuntime.precode``) is derived once here and
    threaded through ``lm_apply(amm_planes=...)``.  The tree is sparse —
    it mirrors ``params`` only where ``amm_dense`` is actually applied
    (the gated MLPs: dense/vlm/audio layer stacks, the MoE dense prefix
    and shared experts, the hybrid shared block) — and layer-stacked
    entries keep the layers axis leading so ``jax.lax.scan`` slices them
    exactly like the parameters.  Returns None when nothing is cacheable
    (mode != "bitexact", non-Booth family, SSM-only or encoder-decoder
    configs — the latter fall back to per-call precode inside the layer)
    or when no weight-side matmul routes through amm at all
    (apply_to="attn": ``mlp_apply`` would never read the planes, so
    building them would be dead startup work held for the process
    lifetime).
    """
    if not (amm.cacheable and amm.mlp_active):
        return None
    stacked = jax.vmap(amm.precode)           # (L, K, N) -> per-layer cache

    def mlp(p_mlp, is_stacked):
        f = stacked if is_stacked else amm.precode
        return {k: f(p_mlp[k]) for k in ("w_gate", "w_up", "w_down")}

    if cfg.family in ("dense", "vlm", "audio") and not cfg.is_encoder_decoder:
        return {"layers": {"mlp": mlp(params["layers"]["mlp"], True)}}
    if cfg.family == "moe":
        planes = {"dense_prefix": [{"mlp": mlp(p["mlp"], False)}
                                   for p in params["dense_prefix"]]}
        if cfg.n_shared_experts:
            planes["layers"] = {"moe": {"shared": mlp(
                params["layers"]["moe"]["shared"], True)}}
        return planes
    if cfg.family == "hybrid":
        return {"shared_block": {"mlp": mlp(params["shared_block"]["mlp"],
                                            False)}}
    return None


def lm_logical_axes(cfg: ArchConfig):
    return param_logical_axes(lm_table(cfg))


# ----------------------------------------------------------------- caches
def init_cache(cfg: ArchConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> Dict[str, Any]:
    """Decode caches for one full model (layer-stacked)."""
    hd = cfg.resolved_head_dim
    if cfg.family in ("dense", "vlm", "audio"):
        n = cfg.n_layers
        c = {"k": jnp.zeros((n, batch, max_len, cfg.n_kv_heads, hd), dtype),
             "v": jnp.zeros((n, batch, max_len, cfg.n_kv_heads, hd), dtype)}
        if cfg.is_encoder_decoder:
            c["xk"] = jnp.zeros(
                (n, batch, cfg.encoder_len, cfg.n_kv_heads, hd), dtype)
            c["xv"] = jnp.zeros(
                (n, batch, cfg.encoder_len, cfg.n_kv_heads, hd), dtype)
        return c
    if cfg.family == "moe":
        n = cfg.n_layers
        lat = cfg.kv_lora_rank + cfg.qk_rope_dim
        if cfg.use_mla:
            return {"latent": jnp.zeros((n, batch, max_len, lat), dtype)}
        return {"k": jnp.zeros((n, batch, max_len, cfg.n_kv_heads, hd), dtype),
                "v": jnp.zeros((n, batch, max_len, cfg.n_kv_heads, hd), dtype)}
    if cfg.family == "ssm":
        return {"ssm": jnp.zeros((cfg.n_layers, batch, cfg.ssm_heads,
                                  cfg.ssm_headdim, cfg.ssm_state), jnp.float32),
                "conv": jnp.zeros((cfg.n_layers, batch, cfg.ssm_conv - 1,
                                   cfg.d_inner + 2 * cfg.ssm_groups
                                   * cfg.ssm_state), dtype)}
    if cfg.family == "hybrid":
        groups = cfg.n_layers // cfg.shared_attn_every
        per = cfg.shared_attn_every
        return {
            "ssm": jnp.zeros((groups, per, batch, cfg.ssm_heads,
                              cfg.ssm_headdim, cfg.ssm_state), jnp.float32),
            "conv": jnp.zeros((groups, per, batch, cfg.ssm_conv - 1,
                               cfg.d_inner + 2 * cfg.ssm_groups
                               * cfg.ssm_state), dtype),
            "k": jnp.zeros((groups, batch, max_len, cfg.n_kv_heads, hd),
                           dtype),
            "v": jnp.zeros((groups, batch, max_len, cfg.n_kv_heads, hd),
                           dtype),
        }
    raise ValueError(cfg.family)


# ------------------------------------------------------------------ blocks
def _attn_block(p, h, cfg, rt, *, positions, cache=None, pos=None, kv=None):
    fn = mla_attention if cfg.use_mla else attention
    # apply_to routing: "attn"/"all" (bitexact Booth family) sends the
    # score/value products through the approximate datapath; "mlp" keeps
    # attention exact — bit-identical to the pre-routing code path
    kw = {"remat_qblock": rt.attn_remat, "shard_heads": rt.shard_heads,
          "causal_skip": rt.causal_skip, "p_bf16": rt.attn_p_bf16,
          "amm": rt.amm if rt.amm.attn_active else None}
    if not cfg.use_mla:
        kw.update(use_pallas=rt.use_pallas_attention, kv=kv)
    y, new_cache = fn(p["attn"], rmsnorm(h, p["attn_norm"], cfg.norm_eps),
                      cfg, positions=positions, cache=cache, pos=pos, **kw)
    return h + y.astype(h.dtype), new_cache


def _dense_block(p, h, cfg, rt, key, *, positions, cache=None, pos=None,
                 planes=None):
    h, new_cache = _attn_block(p, h, cfg, rt, positions=positions,
                               cache=cache, pos=pos)
    y = mlp_apply(p["mlp"], rmsnorm(h, p["mlp_norm"], cfg.norm_eps),
                  rt.amm, key, planes=(planes or {}).get("mlp"))
    return h + y.astype(h.dtype), new_cache


def _moe_block(p, h, cfg, rt, key, *, positions, cache=None, pos=None,
               planes=None):
    """(h, new_cache, aux, expert ids): against a cache (serving) the
    expert layer runs dropless and returns each token's expert ids
    (B, S, k); without one the ids are None."""
    h, new_cache = _attn_block(p, h, cfg, rt, positions=positions,
                               cache=cache, pos=pos)
    out = moe_apply(p["moe"], rmsnorm(h, p["mlp_norm"], cfg.norm_eps),
                    cfg, amm=rt.amm, key=key,
                    gather_weights=rt.moe_gather_weights,
                    planes=(planes or {}).get("moe"),
                    serving=cache is not None)
    y, aux, ids = out if cache is not None else out + (None,)
    return h + y.astype(h.dtype), new_cache, aux, ids


def _ssm_block(p, h, cfg, rt, *, state=None, conv_state=None):
    y, new_states = mamba_apply(p["mamba"], rmsnorm(h, p["norm"],
                                                    cfg.norm_eps),
                                cfg, state=state, conv_state=conv_state)
    return h + y.astype(h.dtype), new_states


# ------------------------------------------------------------------- apply
def lm_apply(params, cfg: ArchConfig, rt: ModelRuntime, tokens, *,
             mode: str = "train", caches=None, pos=None, rng=None,
             encoder_embeds=None, amm_planes=None):
    """Forward pass.

    tokens: (B, S) int32 (for mode="decode", S == 1).
    encoder_embeds: (B, enc_len, d) precomputed frame embeddings (whisper
    stub frontend).
    amm_planes: optional ``lm_amm_planes`` cache — the bitexact
    approximate-matmul weight decode hoisted out of the step (serving:
    built once at engine construction).  Bit-identical to passing None.
    Returns (logits, aux, new_caches); ``aux["moe_aux"]`` is the MoE
    load-balance loss, and against caches a MoE model's
    ``aux["expert_ids"]`` (moe layers, B, S, k) holds the experts each
    token was routed to.
    """
    if rng is None:
        rng = jax.random.key(0)
    amm_planes = amm_planes or {}
    h = params["embed"][tokens].astype(jnp.bfloat16)
    b, s = tokens.shape
    # pos: scalar decode front, or a (B,) per-slot vector (continuous
    # batching: every resident request at its own depth)
    off = jnp.asarray(pos if pos is not None else 0)
    if off.ndim == 1:
        off = off[:, None]
    positions = (jnp.arange(s)[None, :] + off) * jnp.ones((b, 1), jnp.int32)
    aux_total = jnp.float32(0.0)
    aux_extra: Dict[str, Any] = {}
    new_caches: Dict[str, Any] = {}
    decode = mode == "decode"

    def maybe_remat(f):
        return jax.checkpoint(f) if (rt.remat and mode == "train") else f

    # ---------------- encoder (whisper) ----------------
    enc_out = None
    if cfg.is_encoder_decoder:
        assert encoder_embeds is not None
        e = encoder_embeds.astype(h.dtype)
        epos = jnp.arange(e.shape[1])[None, :] * jnp.ones((b, 1), jnp.int32)

        def enc_layer(carry, p_l):
            hh = carry
            hh, _ = _attn_block(p_l, hh, cfg, rt, positions=epos)
            y = mlp_apply(p_l["mlp"],
                          rmsnorm(hh, p_l["mlp_norm"], cfg.norm_eps),
                          rt.amm, rng)
            return hh + y.astype(hh.dtype), None

        enc_out, _ = jax.lax.scan(
            lambda c, p_l: (maybe_remat(enc_layer)(c, p_l)),
            e, params["encoder"]["layers"])
        enc_out = rmsnorm(enc_out, params["encoder"]["norm"], cfg.norm_eps)

    # ---------------- decoder stacks ----------------
    if cfg.family in ("dense", "vlm", "audio") and not cfg.is_encoder_decoder:
        def layer(carry, xs):
            hh, key = carry
            p_l, cache_l, planes_l = xs
            key, sub = jax.random.split(key)
            hh, new_c = _dense_block(
                p_l, hh, cfg, rt, sub, positions=positions,
                cache=cache_l, pos=pos, planes=planes_l)
            return (hh, key), new_c

        # pass the cache dict through whole: the attention layer routes on
        # its keys ({"k","v"} float values vs the int-code leaves)
        cache_xs = caches if caches is not None else None
        (h, _), new_kv = jax.lax.scan(
            maybe_remat(layer), (h, rng),
            (params["layers"], cache_xs, amm_planes.get("layers")))
        if caches is not None:
            new_caches = new_kv

    elif cfg.is_encoder_decoder:
        def dec_layer(carry, xs):
            hh, key = carry
            p_l, cache_l = xs
            key, sub = jax.random.split(key)
            cache_self = ({"k": cache_l["k"], "v": cache_l["v"]}
                          if cache_l is not None else None)
            hh, new_self = _attn_block(p_l, hh, cfg, rt, positions=positions,
                                       cache=cache_self, pos=pos)
            # cross attention: keys/values from encoder output or cache.
            # Same amm routing as _attn_block — the apply_to contract
            # covers every score/value product, cross-attention included
            xamm = rt.amm if rt.amm.attn_active else None
            if cache_l is not None and enc_out is None:
                xkv = (cache_l["xk"], cache_l["xv"])
                xn, _ = attention(
                    p_l["xattn"], rmsnorm(hh, p_l["xattn_norm"], cfg.norm_eps),
                    cfg, positions=positions, kv=xkv, causal=False,
                    amm=xamm)
            else:
                enc_pos = jnp.arange(enc_out.shape[1])[None] * jnp.ones(
                    (b, 1), jnp.int32)
                ek = jnp.einsum("bsd,dhk->bshk", enc_out, p_l["xattn"]["wk"])
                ev = jnp.einsum("bsd,dhk->bshk", enc_out, p_l["xattn"]["wv"])
                ek = ek + (p_l["xattn"]["bk"] if cfg.qkv_bias else 0)
                from .common import apply_rope
                ek = apply_rope(ek, enc_pos, cfg.rope_theta)
                xn, _ = attention(
                    p_l["xattn"], rmsnorm(hh, p_l["xattn_norm"], cfg.norm_eps),
                    cfg, positions=positions, kv=(ek, ev), causal=False,
                    amm=xamm)
            hh = hh + xn.astype(hh.dtype)
            y = mlp_apply(p_l["mlp"], rmsnorm(hh, p_l["mlp_norm"],
                                              cfg.norm_eps), rt.amm, sub)
            new_c = None
            if cache_l is not None:
                new_c = dict(new_self or {"k": cache_l["k"],
                                          "v": cache_l["v"]})
                if enc_out is not None:
                    new_c["xk"], new_c["xv"] = ek.astype(
                        cache_l["xk"].dtype), ev.astype(cache_l["xv"].dtype)
                else:
                    new_c["xk"], new_c["xv"] = cache_l["xk"], cache_l["xv"]
            return (hh + y.astype(hh.dtype), key), new_c

        (h, _), new_kv = jax.lax.scan(
            maybe_remat(dec_layer), (h, rng), (params["layers"], caches))
        if caches is not None:
            new_caches = new_kv

    elif cfg.family == "moe":
        # unstacked dense prefix
        prefix_planes = amm_planes.get("dense_prefix") or []
        prefix_new = []
        for i, p_l in enumerate(params["dense_prefix"]):
            cache_l = (jax.tree.map(lambda c: c[i], caches)
                       if caches is not None else None)
            rng, sub = jax.random.split(rng)
            h, new_c = _dense_block(p_l, h, cfg, rt, sub,
                                    positions=positions,
                                    cache=cache_l, pos=pos,
                                    planes=(prefix_planes[i]
                                            if i < len(prefix_planes)
                                            else None))
            prefix_new.append(new_c)

        def layer(carry, xs):
            hh, key, aux = carry
            p_l, cache_l, planes_l = xs
            key, sub = jax.random.split(key)
            hh, new_c, aux_l, ids = _moe_block(p_l, hh, cfg, rt, sub,
                                               positions=positions,
                                               cache=cache_l, pos=pos,
                                               planes=planes_l)
            return (hh, key, aux + aux_l), (new_c, ids)

        k_pref = cfg.first_k_dense
        cache_xs = (jax.tree.map(lambda c: c[k_pref:], caches)
                    if caches is not None else None)
        (h, _, aux_total), (new_kv, expert_ids) = jax.lax.scan(
            maybe_remat(layer), (h, rng, aux_total),
            (params["layers"], cache_xs, amm_planes.get("layers")))
        if caches is not None:
            # (moe layers, B, S, k): each token's experts, layer by layer
            aux_extra["expert_ids"] = expert_ids
            # re-assemble the full layer-stacked cache (prefix + scanned)
            stacked_prefix = jax.tree.map(
                lambda *xs: jnp.stack(xs, axis=0), *prefix_new) \
                if prefix_new else None
            if stacked_prefix is not None:
                new_caches = jax.tree.map(
                    lambda a, b2: jnp.concatenate([a, b2], axis=0),
                    stacked_prefix, new_kv)
            else:
                new_caches = new_kv

    elif cfg.family == "ssm":
        def layer(carry, xs):
            hh = carry
            p_l, st = xs
            state = st["ssm"] if st is not None else None
            conv = st["conv"] if st is not None else None
            hh, (ns, ncv) = _ssm_block(p_l, hh, cfg, rt,
                                       state=state, conv_state=conv)
            out = ({"ssm": ns, "conv": ncv} if ns is not None else None)
            return hh, out

        st_xs = ({"ssm": caches["ssm"], "conv": caches["conv"]}
                 if caches is not None else None)
        h, new_st = jax.lax.scan(maybe_remat(layer), h,
                                 (params["layers"], st_xs))
        if caches is not None:
            new_caches = new_st

    elif cfg.family == "hybrid":
        shared = params["shared_block"]

        def group(carry, xs):
            hh, key = carry
            p_g, st_g = xs

            def inner(c, xs2):
                h2 = c
                p_l, st = xs2
                state = st["ssm"] if st is not None else None
                conv = st["conv"] if st is not None else None
                h2, (ns, ncv) = _ssm_block(p_l, h2, cfg, rt,
                                           state=state, conv_state=conv)
                return h2, ({"ssm": ns, "conv": ncv}
                            if ns is not None else None)

            ssm_xs = ({"ssm": st_g["ssm"], "conv": st_g["conv"]}
                      if st_g is not None else None)
            hh, new_inner = jax.lax.scan(inner, hh, (p_g, ssm_xs))
            key, sub = jax.random.split(key)
            cache_g = ({"k": st_g["k"], "v": st_g["v"]}
                       if st_g is not None else None)
            hh, new_kv_g = _dense_block(shared, hh, cfg, rt, sub,
                                        positions=positions,
                                        cache=cache_g, pos=pos,
                                        planes=amm_planes.get(
                                            "shared_block"))
            out = None
            if st_g is not None:
                out = {"ssm": new_inner["ssm"], "conv": new_inner["conv"],
                       "k": new_kv_g["k"], "v": new_kv_g["v"]}
            return (hh, key), out

        (h, _), new_g = jax.lax.scan(maybe_remat(group), (h, rng),
                                     (params["layers"], caches))
        if caches is not None:
            new_caches = new_g
    else:
        raise ValueError(cfg.family)

    with jax.named_scope(trace.LM_HEAD):
        h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
        head = (params["embed"].T if cfg.tie_embeddings
                else params["lm_head"])
        logits = (h @ head.astype(h.dtype)).astype(jnp.float32)
    return logits, {"moe_aux": aux_total, **aux_extra}, new_caches


def lm_loss(params, cfg: ArchConfig, rt: ModelRuntime, tokens, labels, *,
            rng=None, encoder_embeds=None, moe_aux_weight: float = 1e-2,
            mtp_weight: float = 0.1, amm_planes=None):
    """Training loss: next-token CE + MoE aux (+ MTP if configured).

    amm_planes is accepted for API symmetry with ``lm_apply`` (eval loss
    over fixed weights); training steps pass None — the weights change
    every update, so there is nothing to cache across calls.
    """
    logits, aux, _ = lm_apply(params, cfg, rt, tokens, mode="train", rng=rng,
                              encoder_embeds=encoder_embeds,
                              amm_planes=amm_planes)
    loss = cross_entropy_loss(logits, labels)
    total = loss + moe_aux_weight * aux["moe_aux"]
    metrics = {"ce": loss, "moe_aux": aux["moe_aux"]}
    if cfg.mtp_depth and "mtp" in params:
        # MTP: predict token t+2 from (h_t, emb(label_t)) through one extra
        # block (deepseek-v3 §MTP, depth 1).
        p_m = params["mtp"]
        h_in = params["embed"][tokens].astype(jnp.bfloat16)
        emb_next = params["embed"][labels].astype(jnp.bfloat16)
        h_m = jnp.concatenate([rmsnorm(h_in, p_m["norm"], cfg.norm_eps),
                               emb_next], axis=-1) @ p_m["proj"]
        b, s = tokens.shape
        positions = jnp.arange(s)[None, :] * jnp.ones((b, 1), jnp.int32)
        mtp_rng = rng if rng is not None else jax.random.key(1)
        h_m, _, _aux, _ = _moe_block(p_m, h_m, cfg, rt, mtp_rng,
                                     positions=positions)
        head = (params["embed"].T if cfg.tie_embeddings
                else params["lm_head"])
        logits_m = (rmsnorm(h_m, params["final_norm"], cfg.norm_eps)
                    @ head.astype(h_m.dtype)).astype(jnp.float32)
        # labels shifted once more (t+2): reuse labels rolled by 1
        labels2 = jnp.roll(labels, -1, axis=-1)
        mtp_loss = cross_entropy_loss(logits_m[:, :-1], labels2[:, :-1])
        total = total + mtp_weight * mtp_loss
        metrics["mtp"] = mtp_loss
    return total, metrics
