"""Mixture-of-Experts block with sort-based capacity dispatch.

Dispatch is the standard dropping formulation: tokens are routed to their
top-k experts, each expert processes at most ``capacity`` tokens
(capacity_factor * k * T / E), overflow tokens lose that expert's
contribution.  Implemented with sort/cumsum/scatter only — no (T, E, C)
one-hot tensors — so it scales to 256 experts x 1M tokens and shards with
experts on the "model" mesh axis (expert parallelism; XLA inserts the
all-to-alls at the dispatch/combine boundaries).

The router follows DeepSeek-V3: sigmoid affinities, top-k, normalized
weights, plus an auxiliary load-balance loss (Switch-style) returned to the
caller.  With ``router_bias`` (noaux_tc) a correction bias is added to the
affinities to choose the experts and left out of their weights; the
weights, normalized over the k, are scaled by ``routed_scaling_factor``.

Serving (``serving=True``: every call against a cache, prefill included)
gives each expert a buffer of every token, so no token loses an expert.
An expert share (``experts_held``, ``expert_offset``) holds the weights of
some experts only: the router still scores all of them and chooses among
all of them, and the layer computes the held experts' part of each token's
output (expert parallelism, one device's share).  Under an active amm MLP
route each held expert's rows go through the Broken-Booth contraction
with one quantization scale over the rows routed to it in that call (the
buffer's zero padding never moves a scale).
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from .. import trace
from ..configs.base import ArchConfig
from .common import Spec

__all__ = ["moe_table", "moe_apply", "moe_route", "mlp_table", "mlp_apply"]


def mlp_table(d_model: int, d_ff: int, prefix_axes=("embed", "mlp")) -> Dict:
    a_in, a_out = prefix_axes
    return {
        "w_gate": Spec((d_model, d_ff), (a_in, a_out)),
        "w_up": Spec((d_model, d_ff), (a_in, a_out)),
        "w_down": Spec((d_ff, d_model), (a_out, a_in)),
    }


def mlp_apply(p, x, amm=None, key=None, planes=None):
    """Gated MLP; ``planes`` is the optional per-weight digit-plane cache
    (``{"w_gate": .., "w_up": .., "w_down": ..}`` of ``AmmRuntime.precode``
    entries) for the bitexact approximate-matmul datapath.  Routing
    follows ``AmmRuntime.mlp_active``: apply_to="attn" leaves the MLPs
    exact so the attention contribution is measurable in isolation."""
    from .common import amm_dense
    if amm is not None and amm.mlp_active:
        pl_ = planes or {}
        g = amm_dense(x, p["w_gate"], amm, key, planes=pl_.get("w_gate"))
        u = amm_dense(x, p["w_up"], amm, key, planes=pl_.get("w_up"))
        h = jax.nn.silu(g) * u
        return amm_dense(h, p["w_down"], amm, key, planes=pl_.get("w_down"))
    h = jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])
    return h @ p["w_down"]


def moe_table(cfg: ArchConfig) -> Dict[str, Spec]:
    d, e, ff = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    n = cfg.n_local_experts            # the router scores all e experts
    t = {
        "router": Spec((d, e), ("embed", "experts"), "normal", 0.006),
        "w_gate": Spec((n, d, ff), ("experts", "embed", "expert_mlp")),
        "w_up": Spec((n, d, ff), ("experts", "embed", "expert_mlp")),
        "w_down": Spec((n, ff, d), ("experts", "expert_mlp", "embed")),
    }
    if cfg.router_bias:
        t["router_bias"] = Spec((e,), ("experts",), "zeros")
    if cfg.n_shared_experts:
        sff = ff * cfg.n_shared_experts
        t["shared"] = mlp_table(d, sff)
    return t


def _dispatch(expert_ids, top_k: int, n_tokens: int, n_experts: int,
              capacity: int):
    """Build gather indices from flat (T*k,) routing decisions.

    Returns (slot_token, token_slot):
      slot_token: (E*C,) *token* index feeding each expert slot (T = pad)
      token_slot: (T*k,) slot index each routing decision landed in (E*C =
                  dropped/pad)
    """
    tk = expert_ids.shape[0]
    # decisions sorted by expert, stable -> token order within expert
    order = jnp.argsort(expert_ids, stable=True)               # (T*k,)
    sorted_e = expert_ids[order]
    # rank within expert = sorted index - start offset of that expert
    counts = jnp.bincount(expert_ids, length=n_experts)
    starts = jnp.cumsum(counts) - counts                       # (E,)
    rank = jnp.arange(tk) - starts[sorted_e]                   # (T*k,)
    keep = rank < capacity
    nc = n_experts * capacity
    slot = sorted_e * capacity + jnp.minimum(rank, capacity - 1)
    oob = jnp.where(keep, slot, nc)            # out-of-bounds -> dropped
    # scatter token ids into slots (mode="drop" discards overflow)
    slot_token = jnp.full((nc,), n_tokens, jnp.int32)
    slot_token = slot_token.at[oob].set(
        (order // top_k).astype(jnp.int32), mode="drop")
    token_slot = jnp.full((tk,), nc, jnp.int32)
    token_slot = token_slot.at[order.astype(jnp.int32)].set(
        oob.astype(jnp.int32))
    return slot_token, token_slot


def moe_route(p, xf, cfg: ArchConfig):
    """(weights (T, k) f32, expert ids (T, k) int32, logits (T, E) f32).

    Float32 at ``Precision.HIGHEST``: the router chooses k of E, so its
    scores are taken at full precision on every backend.  The correction
    bias (``router_bias``) moves the choice only; the weights are the
    chosen experts' sigmoid affinities normalized over the k, times
    ``routed_scaling_factor``.
    """
    logits = jnp.dot(xf.astype(jnp.float32), p["router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.sigmoid(logits)
    if cfg.router_bias:
        _, gate_idx = jax.lax.top_k(probs + p["router_bias"], cfg.top_k)
        gate_vals = jnp.take_along_axis(probs, gate_idx, axis=-1)
    else:
        gate_vals, gate_idx = jax.lax.top_k(probs, cfg.top_k)  # (T,k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)
    if cfg.routed_scaling_factor != 1.0:
        gate_vals = gate_vals * cfg.routed_scaling_factor
    return gate_vals, gate_idx, logits


def moe_apply(p, x, cfg: ArchConfig, *, capacity_factor: float = 1.25,
              amm=None, key=None, gather_weights: bool = False,
              planes=None, serving: bool = False):
    """x: (B, S, d) -> (y, aux_loss); with ``serving`` (y, aux_loss,
    expert ids (B, S, k) int32).  ``planes``: optional digit-plane cache
    for the shared-expert MLP (``{"shared": {...}}``).

    Decode (s == 1) and serving calls run with capacity = T (dropless): a
    serving call must not lose expert contributions to capacity.

    gather_weights: constrain expert weights to P("model", None, None)
    before the expert einsums.  Under FSDP rules the weights' d axis is
    sharded over "data", and GSPMD resolves the contraction by ALL-REDUCING
    the (E, C, d_ff) partial products — tens of GB of f32 per layer (the
    dominant collective term of the MoE baselines, docs/perf.md
    §Model-side perf levers).  Gathering the weights instead moves ~30x
    fewer bytes.
    """
    b, s, d = x.shape
    t = b * s
    e, k = cfg.n_experts, cfg.top_k
    n_loc, lo = cfg.n_local_experts, cfg.expert_offset
    xf = x.reshape(t, d)

    with jax.named_scope(trace.MOE_ROUTE):
        gate_vals, gate_idx, logits = moe_route(p, xf, cfg)

        # Switch-style load-balance aux loss
        sprobs = jax.nn.softmax(logits, axis=-1)
        frac_routed = jnp.mean(
            jax.nn.one_hot(gate_idx[:, 0], e, dtype=jnp.float32), axis=0)
        aux = e * jnp.sum(frac_routed * jnp.mean(sprobs, axis=0))

        if serving or s == 1:
            capacity = t                 # a token picks an expert once
        else:
            capacity = max(int(capacity_factor * k * t / e), 1)
        flat_e = gate_idx.reshape(-1)                          # (T*k,)
        if n_loc == e and lo == 0:
            slot_token, token_slot = _dispatch(flat_e, k, t, e, capacity)
        else:
            # decisions for experts held elsewhere go to one extra bucket
            # whose slots are dropped: their contribution is another
            # device's share
            local = flat_e - lo
            held = (local >= 0) & (local < n_loc)
            slot_token, token_slot = _dispatch(
                jnp.where(held, local, n_loc), k, t, n_loc + 1, capacity)
            nc = n_loc * capacity
            slot_token = slot_token[:nc]
            token_slot = jnp.where(token_slot < nc, token_slot, nc)

        # gather tokens into (E_held, C, d), run experts batched
        xg = jnp.concatenate([xf, jnp.zeros((1, d), xf.dtype)], axis=0)
        xe = xg[slot_token].reshape(n_loc, capacity, d)
    w_gate, w_up, w_down = p["w_gate"], p["w_up"], p["w_down"]
    if gather_weights:
        from .attention import _maybe_constrain
        if e % 16 == 0:              # EP: experts carry the model axis
            ax_up, ax_down = ("model", None, None), ("model", None, None)
        else:                        # TP-experts (grok: 8 experts, 16-way)
            ax_up, ax_down = (None, None, "model"), (None, "model", None)
        w_gate = _maybe_constrain(w_gate, *ax_up)
        w_up = _maybe_constrain(w_up, *ax_up)
        w_down = _maybe_constrain(w_down, *ax_down)
    with jax.named_scope(trace.MOE_EXPERTS):
        if amm is not None and amm.mlp_active:
            # one amm_dense per held expert: its rows quantized together
            ye = jax.vmap(lambda xx, g, u, dn: mlp_apply(
                {"w_gate": g, "w_up": u, "w_down": dn}, xx, amm, key))(
                    xe, w_gate, w_up, w_down)
        else:
            h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, w_gate)) * \
                jnp.einsum("ecd,edf->ecf", xe, w_up)
            h = h.astype(xe.dtype)
            ye = jnp.einsum("ecf,efd->ecd", h, w_down)         # (E,C,d)
        yflat = jnp.concatenate(
            [ye.reshape(n_loc * capacity, d), jnp.zeros((1, d), ye.dtype)],
            axis=0)
        per_decision = yflat[token_slot].reshape(t, k, d)      # (T,k,d)
        y = jnp.einsum("tkd,tk->td", per_decision,
                       gate_vals.astype(per_decision.dtype))

    if cfg.n_shared_experts:
        with jax.named_scope(trace.MOE_SHARED):
            y = y + mlp_apply(p["shared"], xf, amm, key,
                              planes=(planes or {}).get("shared"))
    if serving:
        return y.reshape(b, s, d), aux, gate_idx.reshape(b, s, k)
    return y.reshape(b, s, d), aux
