"""Shared model machinery: declarative params, norms, RoPE, and the paper's
approximate-matmul (`amm`) layer.

Parameters are declared once as ``Spec`` entries (shape + logical axes +
init); both the real initializer and the dry-run's shape/sharding trees are
derived from the same table, so sharding rules can never drift from shapes.

Logical axis names (mapped to mesh axes by parallel/logical.py):
  layers, embed, heads, kv_heads, head_dim, mlp, experts, expert_mlp,
  vocab, kv_latent, q_latent, ssm_inner, ssm_state, ssm_heads, conv, batch,
  seq, scalar
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import trace
from ..configs.base import AmmConfig
from ..core.multipliers import MulSpec
from ..core.noise import make_noise_model
from ..kernels.bbm_matmul import bbm_matmul_dynamic, bbm_matmul_scaled
from ..kernels.booth_rows import booth_precode
from ..kernels.ref import (AMM_BOOTH_KINDS, amm_approx_ref,
                           amm_effective_vbl, amm_quantize)

__all__ = ["Spec", "init_params", "param_logical_axes", "rmsnorm",
           "rope_freqs", "apply_rope", "amm_dense", "amm_dot", "AmmRuntime",
           "cross_entropy_loss"]


@dataclasses.dataclass(frozen=True)
class Spec:
    """Declaration of one parameter tensor."""
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    init: str = "normal"        # normal | zeros | ones | small
    scale: float = 0.02

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def _init_one(key, spec: Spec, dtype):
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, dtype)
    scale = spec.scale if spec.init == "normal" else 1e-3
    return (jax.random.normal(key, spec.shape, jnp.float32) * scale
            ).astype(dtype)


def init_params(table: Dict[str, Any], key, dtype=jnp.float32):
    """Materialize a (possibly nested) dict of Spec into arrays."""
    leaves, treedef = jax.tree.flatten(
        table, is_leaf=lambda x: isinstance(x, Spec))
    keys = jax.random.split(key, len(leaves))
    vals = [_init_one(k, s, dtype) for k, s in zip(keys, leaves)]
    return jax.tree.unflatten(treedef, vals)


def param_logical_axes(table: Dict[str, Any]):
    """The same tree with each Spec replaced by its logical axis tuple."""
    return jax.tree.map(lambda s: s.axes, table,
                        is_leaf=lambda x: isinstance(x, Spec))


# ---------------------------------------------------------------- numerics
def rmsnorm(x, w, eps: float = 1e-5):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w


def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x, positions, theta: float):
    """x: (..., seq, heads, head_dim); positions: (..., seq)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                       # (d/2,)
    ang = positions[..., :, None, None].astype(jnp.float32) * freqs  # (...,s,1,d/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    out = jnp.stack([y1, y2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


# ----------------------------------------------------- approximate matmul
@dataclasses.dataclass(frozen=True)
class AmmRuntime:
    """Resolved runtime for an AmmConfig: moments from the characterization
    cache, kept as python floats so they fold into the jaxpr."""
    cfg: AmmConfig
    mu: float = 0.0
    sigma: float = 0.0

    @staticmethod
    def build(cfg: AmmConfig) -> "AmmRuntime":
        if cfg.mode != "noise":
            return AmmRuntime(cfg)
        spec = MulSpec(cfg.mul, cfg.wl, cfg.param)
        nm = make_noise_model(spec, sample=1 << 18)
        return AmmRuntime(cfg, mu=nm.mean, sigma=float(np.sqrt(nm.var)))

    @property
    def spec(self) -> MulSpec:
        return MulSpec(self.cfg.mul, self.cfg.wl, self.cfg.param)

    @property
    def cacheable(self) -> bool:
        """Does mode="bitexact" run the precodable dot-form datapath?"""
        return (self.cfg.mode == "bitexact"
                and self.cfg.mul in AMM_BOOTH_KINDS)

    @property
    def mlp_active(self) -> bool:
        """Do the model's MLP (weight-side) matmuls route through amm?

        ``apply_to`` is the model-level router: "mlp" and "all" cover the
        gated MLPs (every mode), "attn" leaves them exact so the attention
        contribution can be measured in isolation.
        """
        return (self.cfg.mode != "off"
                and self.cfg.apply_to in ("mlp", "all"))

    @property
    def attn_active(self) -> bool:
        """Do the attention score/value products route through amm?

        ``Q @ K^T`` and ``P @ V`` multiply activations by activations —
        there is no weight side, so only the bitexact Booth-family
        datapath has a lowering for them (``amm_dot`` on
        ``kernels.bbm_matmul_dynamic``).  mode="noise" keeps attention
        exact even under apply_to="all": its moments are calibrated for
        the per-matmul quantize-then-perturb pipeline and have not been
        characterized for softmax-coupled products (docs/attention.md).
        """
        return (self.cfg.mode == "bitexact"
                and self.cfg.mul in AMM_BOOTH_KINDS
                and self.cfg.apply_to in ("attn", "all"))

    @property
    def attn_lowering(self):
        """``(wl, vbl, kind)`` of the Booth-family dot-form lowering.

        The static parameters every bitexact attention product lowers
        with — ``amm_dot``'s vmapped ``bbm_matmul_dynamic``, and the
        flash-amm kernel's in-tile correction — derived in one place so
        the two datapaths can never disagree on them.  None when the
        configured mode/family has no dot-form lowering.
        """
        kind = AMM_BOOTH_KINDS.get(self.cfg.mul)
        if kind is None or self.cfg.mode != "bitexact":
            return None
        return (self.cfg.wl, amm_effective_vbl(self.spec), kind)

    def precode(self, w):
        """Per-parameter digit-plane cache entry for one (K, N) weight.

        Weights are constant across decode steps and serving requests, so
        their dynamic quantization scale and radix-4 Booth digit planes —
        the whole decode phase of the Broken-Booth datapath — can be
        derived once per parameter and reused by every ``amm_dense`` call
        (the ``dsp.PrecodedBank`` argument, at model scale).  Returns
        ``{"mag", "neg", "s_w"}`` with planes of shape (wl//2, K, N), or
        None when the configured mode/family has nothing to cache.
        ``jax.vmap(rt.precode)`` handles layer-stacked (L, K, N) weights
        (per-layer scales, planes (L, wl//2, K, N) — scan-sliceable).
        """
        if not self.cacheable:
            return None
        wq, s_w = amm_quantize(w, self.cfg.wl)
        mag, neg = booth_precode(wq, self.cfg.wl)
        return {"mag": mag, "neg": neg, "s_w": s_w}


def _amm_bitexact_approx(x, w, rt: AmmRuntime, planes=None):
    """Forward value of mode="bitexact": the dot-form Broken-Booth matmul.

    Booth-family specs run on ``kernels.bbm_matmul_scaled``: quantize to
    int codes, contract via the folded dot form (exact ``x @ bq`` integer
    matmul + a few narrow contractions per truncated row, int32-exact in
    K-chunks), descale — bit-identical to the scalar closed forms
    (``kernels.ref.amm_dense_ref``) but O(M*N) live memory instead of the
    oracle's (..., K, N) product grid, so it serves real model shapes.
    Non-Booth families (bam/kulkarni/etm) have no dot lowering and keep
    the scalar oracle path (reduced configs only, as before).

    ``planes``: optional ``AmmRuntime.precode(w)`` cache entry — skips
    the per-call weight quantization + digit decode; bit-identical to the
    uncached path.
    """
    cfg = rt.cfg
    kind = AMM_BOOTH_KINDS.get(cfg.mul)
    if kind is None:
        return amm_approx_ref(x, w, rt.spec)
    wl = cfg.wl
    vbl = amm_effective_vbl(rt.spec)
    with jax.named_scope(trace.AMM_CONTRACT):
        xq, s_x = amm_quantize(x, wl)
    if planes is None:
        with jax.named_scope(trace.AMM_WEIGHT_DECODE):
            planes = rt.precode(w)
    with jax.named_scope(trace.AMM_CONTRACT):
        yq = bbm_matmul_scaled(xq.reshape(-1, x.shape[-1]), planes["mag"],
                               planes["neg"], wl=wl, vbl=vbl, kind=kind)
        yq = yq.reshape(x.shape[:-1] + (w.shape[-1],))
        return (yq * (s_x * planes["s_w"])).astype(x.dtype)


def amm_dense(x, w, rt: AmmRuntime, key=None, planes=None):
    """Matmul over the last axis of x with the paper's technique applied.

    Straight-through estimator: gradients flow through the exact product;
    the forward value carries the quantization + approximate-multiplier
    error.  x: (..., K), w: (K, N).

    planes: optional per-parameter cache from ``AmmRuntime.precode(w)``
    (mode="bitexact" only) — the weight-side decode phase hoisted out of
    the hot loop; bit-identical with or without.
    """
    cfg = rt.cfg
    with jax.named_scope(trace.AMM_STE_EXACT):
        exact = x @ w
    if cfg.mode == "off":
        return exact
    if cfg.mode == "noise":
        # one quantizer for both amm modes (kernels.ref.amm_quantize):
        # the noise and bitexact columns of lm_quality must sit on the
        # same code grid or their gap stops measuring the noise model.
        # XLA dead-code-eliminates the unused codes on the pallas branch
        # (the kernel quantizes in-tile from the same scales).
        xq_i, s_x = amm_quantize(x, cfg.wl)
        wq_i, s_w = amm_quantize(w, cfg.wl)
        if cfg.use_pallas:
            # fused Pallas path: quantize -> matmul -> in-kernel hash
            # noise -> descale, one pass over VMEM tiles (interpret-mode
            # off TPU).  Seeded from `key` so draws differ across steps.
            from ..kernels.ops import quant_matmul
            seed = (jnp.int32(0) if key is None
                    else jax.random.randint(key, (), 0, 2 ** 31 - 1,
                                            jnp.int32))
            # the kernel has no JVP rule and needs none: the STE routes
            # every gradient through `exact`, so cut the tangents at the
            # kernel's operands instead of after its output
            sg = jax.lax.stop_gradient
            yq = quant_matmul(
                sg(x.reshape(-1, x.shape[-1]).astype(jnp.float32)),
                sg(w.astype(jnp.float32)), s_x, s_w,
                rt.mu if key is not None else 0.0,
                rt.sigma if key is not None else 0.0,
                wl=cfg.wl, seed=seed)
            approx = yq.reshape(x.shape[:-1] + (w.shape[-1],)).astype(x.dtype)
            return exact + jax.lax.stop_gradient(approx - exact)
        yq = xq_i.astype(jnp.float32) @ wq_i.astype(jnp.float32)
        k_len = x.shape[-1]
        if key is not None and (rt.mu != 0.0 or rt.sigma != 0.0):
            z = jax.random.normal(key, yq.shape, jnp.float32)
            yq = yq + rt.mu * k_len + rt.sigma * (k_len ** 0.5) * z
        approx = (yq * (s_x * s_w)).astype(x.dtype)
        return exact + jax.lax.stop_gradient(approx - exact)
    if cfg.mode == "bitexact":
        approx = _amm_bitexact_approx(x, w, rt, planes=planes)
        with jax.named_scope(trace.AMM_STE_EXACT):
            return exact + jax.lax.stop_gradient(approx - exact)
    raise ValueError(f"unknown amm mode {cfg.mode!r}")


def amm_dot(a, b, rt: AmmRuntime, *, oracle: bool = False, ste: bool = True):
    """Both-operands-dynamic approximate matmul — the attention-side
    ``amm_dense``.

    Contracts the trailing axis of ``a`` against the second-to-last axis
    of ``b``, batched over their (matching) leading axes: the shape of the
    attention score product ``Q @ K^T`` and value product ``P @ V``.
    Neither operand is a parameter, so there is nothing to precode or
    cache — both sides are quantized per call, and the vmap over the
    leading (batch, head) axes gives every slice its own pair of dynamic
    scales (per-block quantization; docs/attention.md).

    Straight-through like ``amm_dense``: gradients flow through the exact
    batched matmul, the forward value carries the Broken-Booth error.
    Only the bitexact Booth-family datapath has a lowering here; callers
    gate on ``AmmRuntime.attn_active`` (the guard below is defensive and
    returns the exact product).

    oracle=True forms every product through the scalar closed forms
    (``kernels.ref.amm_dot_ref``) instead of the dot-form contraction —
    bit-identical by the amm contract.  ``kernels.ref.amm_attention_ref``
    uses it to oracle the attention datapath while sharing the softmax
    schedule.

    ste=False skips the straight-through composition and returns the raw
    approximate product.  ``exact + (approx - exact)`` is not bitwise
    ``approx`` in float32, so inference paths that must match the pure
    code-domain datapath (the int-code KV cache, whose decode never forms
    an exact product at all) need the uncomposed value.
    """
    lowering = rt.attn_lowering
    if lowering is None:
        return a @ b
    if oracle:
        from ..kernels.ref import amm_dot_ref
        approx = amm_dot_ref(a, b, rt.spec)
    else:
        wl, vbl, kind = lowering
        fn = partial(bbm_matmul_dynamic, wl=wl, vbl=vbl, kind=kind)
        for _ in range(a.ndim - 2):
            fn = jax.vmap(fn)
        approx = fn(a, b)
    if not ste:
        return approx
    exact = a @ b
    return exact + jax.lax.stop_gradient(approx - exact)


# ------------------------------------------------------------------- loss
def cross_entropy_loss(logits, labels, *, z_loss: float = 1e-4):
    """Mean token cross entropy (fp32 logsumexp) + optional z-loss."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    loss = jnp.mean(lse - ll)
    if z_loss:
        loss = loss + z_loss * jnp.mean(jnp.square(lse))
    return loss
