"""Model FLOPs and bytes of latent-attention MoE serving, from the
configuration's shapes and the logged routing alone.

The work counts what the algorithm needs, whatever implements it: the
absorbed latent attention, the dense layer or the shared experts, the
held experts each token was routed to, and the LM head slice.  An
emulated contraction that takes many passes counts once; an expert that
no token was routed to does no work and reads nothing.  Weights and
latent codes count 2 bytes each (the 16-bit codes of the datapath).
"""
from __future__ import annotations

from typing import Iterable, Tuple

import numpy as np

WEIGHT_BYTES = 2
CODE_BYTES = 2


def _dims(c: dict):
    return (c["hidden_size"], c["num_attention_heads"],
            c["qk_nope_head_dim"], c["qk_rope_head_dim"],
            c["v_head_dim"], c["kv_lora_rank"])


def attn_params(c: dict) -> int:
    """Weights one token multiplies in one latent-attention layer, in the
    absorbed form: W_q, the KV down-projection, W_uk folded into the
    query, W_uv and W_o."""
    d, h, nope, rope, v, r = _dims(c)
    return (d * h * (nope + rope) + d * (r + rope) + h * nope * r
            + h * r * v + h * v * d)


def expert_params(c: dict) -> int:
    """One routed expert's SiLU-gated MLP."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def fixed_params(c: dict) -> int:
    """Weights every token multiplies whatever its routing: attention of
    every layer, the dense layers, each expert layer's router and shared
    experts, and the LM head slice."""
    d = c["hidden_size"]
    n, first = c["num_hidden_layers"], c["first_k_dense_replace"]
    shared = c["n_shared_experts"] * expert_params(c)
    router = d * c["expert_share"]["router_outputs"]
    return (n * attn_params(c) + first * 3 * d * c["intermediate_size"]
            + (n - first) * (router + shared) + d * c["vocab_size"])


def latent_flops(c: dict, context: int) -> int:
    """Score and value FLOPs of one token over ``context`` cached
    positions, all layers: 16 heads x (576 + 512) per position."""
    _, h, _, rope, _, r = _dims(c)
    return 2 * h * ((r + rope) + r) * context * c["num_hidden_layers"]


def held(ids, c: dict) -> np.ndarray:
    """Mask of the logged expert ids (any shape) that this chip holds."""
    lo = c["expert_share"]["first_held"]
    ids = np.asarray(ids)
    return (ids >= lo) & (ids < lo + c["n_routed_experts"])


def tokens_flops(c: dict, contexts: Iterable[int], ids) -> int:
    """Model FLOPs of tokens at ``contexts`` (each attends to that many
    positions) whose routing is ``ids`` (expert layers, tokens, k)."""
    contexts = [int(x) for x in contexts]
    routed = int(held(ids, c).sum())
    return (2 * (fixed_params(c) * len(contexts)
                 + expert_params(c) * routed)
            + sum(latent_flops(c, x) for x in contexts))


def experts_hit(ids, c: dict) -> int:
    """Held experts with at least one row routed to them, summed over the
    expert layers; ids (expert layers, rows, k)."""
    ids = np.asarray(ids)
    return sum(len(np.unique(layer[held(layer, c)]))
               for layer in ids.reshape(ids.shape[0], -1))


def decode_step(c: dict, contexts: Iterable[int], ids) -> Tuple[int, int]:
    """(FLOPs, bytes) of one decode step over its live rows: ``contexts``
    each row's context (its position + 1), ``ids`` their routing
    (expert layers, rows, k).  Bytes: every weight the step must read
    once, experts no row was routed to left out, the embedding rows of
    its tokens, and each row's context of latent codes in every layer."""
    contexts = [int(x) for x in contexts]
    d = c["hidden_size"]
    lat = c["kv_lora_rank"] + c["qk_rope_head_dim"]
    weights = (fixed_params(c) + expert_params(c) * experts_hit(ids, c)
               + d * len(contexts))
    codes = lat * sum(contexts) * c["num_hidden_layers"]
    return (tokens_flops(c, contexts, ids),
            WEIGHT_BYTES * weights + CODE_BYTES * codes)


def rows_per_expert(ids, c: dict) -> float:
    """Mean rows routed to each held expert in one call, over the expert
    layers; ids (expert layers, rows, k), every row of the call."""
    return float(held(ids, c).sum()) / (np.asarray(ids).shape[0]
                                        * c["n_routed_experts"])
