"""Operations and bytes the algorithm needs, from shapes alone.

These count the work a layer has to do, whatever implements it: an
emulated contraction that takes 36 passes counts once, and a later
lowering that packs codes narrower cannot raise a share of a peak.
"""
from __future__ import annotations

from typing import Iterable


def fir_nominal(samples: int, taps: int, *, in_bytes: int = 2,
                out_bytes: int = 4):
    """(ops, bytes) of a ``taps``-tap FIR over ``samples`` output samples.

    One multiply and one add per tap and sample; each input sample is read
    once as a wl=16 code (2 B) and each output written once as the 32-bit
    accumulator (4 B).
    """
    return 2 * taps * samples, (in_bytes + out_bytes) * samples


def roofline_least_s(ops: float, nbytes: float, peak_ops: float,
                     peak_bytes_per_s: float):
    """(least seconds, bound) where bound is "compute" or "memory"."""
    t_ops = ops / peak_ops
    t_mem = nbytes / peak_bytes_per_s
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")


def lm_matmul_params(c: dict) -> int:
    """Weights that take part in a matmul, per token: attention
    projections, the gated MLP and the (tied) LM head.  Norms, biases and
    the embedding gather do no matmul."""
    d, h, kv = c["hidden_size"], c["num_attention_heads"], \
        c["num_key_value_heads"]
    hd = c.get("head_dim") or d // h
    ff = c["intermediate_size"]
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * ff
    return per_layer * c["num_hidden_layers"] + d * c["vocab_size"]


def lm_attention_flops(c: dict, context: int) -> int:
    """Score and value FLOPs of one token that attends to ``context``
    positions, over all layers."""
    h = c["num_attention_heads"]
    hd = c.get("head_dim") or c["hidden_size"] // h
    return 4 * h * hd * context * c["num_hidden_layers"]


def lm_model_flops(c: dict, contexts: Iterable[int]) -> int:
    """Model FLOPs of processing one token at each of ``contexts``
    (a token at position p attends to p + 1 positions)."""
    dense = 2 * lm_matmul_params(c)
    total = 0
    for ctx in contexts:
        total += dense + lm_attention_flops(c, ctx)
    return total


def lm_span_flops(c: dict, start: int, stop: int) -> int:
    """Model FLOPs of the tokens at positions start .. stop-1."""
    n = stop - start
    # sum over p of (p + 1) for p in [start, stop)
    ctx_sum = (start + 1 + stop) * n // 2
    return 2 * lm_matmul_params(c) * n + lm_attention_flops(c, 1) * ctx_sum
