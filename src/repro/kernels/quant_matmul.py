"""Pallas TPU kernel: fixed-point quantized matmul with fused approximate-
multiplier noise injection (the scalable "silicon simulation" fast path).

Implements the paper's §II.B white-noise error model generatively:

    out = (x_q @ w_q) * s_x * s_w  +  eps,
    eps ~ Normal(K * mu, K * sigma^2) * s_x * s_w   per output element

where (mu, sigma) are the characterized per-product error moments of the
chosen approximate multiplier (core.noise.NoiseModel) in the integer domain,
and K is the contraction length.  The matmul itself runs on the MXU in
bf16->f32; the noise is generated *inside the kernel* from a counter-based
hash (squares64-style) keyed on (seed, tile coordinates, lane), so the kernel
stays a single fused pass over VMEM tiles: quantize -> MXU -> noise -> scale.

The quantization scales and the noise seed enter as tiny *operand* blocks
(a (1, 2) f32 scale pair and a (1, 1) int32 seed, broadcast to every tile),
not as trace-time constants: ``amm_dense`` computes its scales dynamically
from the activations (``jnp.max(|x|)``) inside the jitted train/serve step,
so the kernel must accept traced scalars — and a traced seed keeps one
compiled kernel across noise draws instead of one per seed.  (mu, sigma)
stay static: they come from the characterization cache as python floats.

This is the TPU-native statement of the paper's idea at model scale: the
quality impact of the proposed multiplier on a workload can be evaluated at
full training/serving throughput, because the error model — not the broken
datapath — is what executes.  ``models.common.amm_dense`` reaches it via
``AmmConfig.use_pallas`` for mode="noise".
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["quant_matmul_kernel", "quant_matmul"]


def _hash_normal(shape, seed, salt):
    """Two rounds of a squares-style counter hash -> approx N(0,1).

    Box-Muller over two 31-bit uniforms derived from (seed, salt, position).
    Statistical quality is ample for noise injection (validated in
    tests/test_kernels.py against moment targets).
    """
    r = jax.lax.broadcasted_iota(jnp.uint32, shape, len(shape) - 2)
    c = jax.lax.broadcasted_iota(jnp.uint32, shape, len(shape) - 1)
    ctr = r * jnp.uint32(0x9E3779B9) + c * jnp.uint32(0x85EBCA6B)
    ctr = ctr + seed.astype(jnp.uint32) * jnp.uint32(0xC2B2AE35)
    ctr = ctr + salt.astype(jnp.uint32) * jnp.uint32(0x27D4EB2F)

    def squares(x, key):
        x = x * key
        x = (x >> 16) | (x << 16)
        x = x * x + key
        x = (x >> 16) | (x << 16)
        x = x * x + key
        return x

    def uniform(x):
        # top 31 bits through int32: the TPU lowering has no uint32 -> f32
        # cast, and a value below 2^31 converts the same either way
        x = jax.lax.bitcast_convert_type(x >> 1, jnp.int32)
        return x.astype(jnp.float32) / 2147483648.0

    u1 = uniform(squares(ctr, jnp.uint32(0xB5AD4ECE)))
    u2 = uniform(squares(ctr ^ jnp.uint32(0xDEADBEEF),
                         jnp.uint32(0x548C9DEC)))
    u1 = jnp.clip(u1, 1e-7, 1.0)              # uniforms in [0, 1)
    return jnp.sqrt(-2.0 * jnp.log(u1)) * jnp.cos(2.0 * jnp.pi * u2)


def quant_matmul_kernel(x_ref, w_ref, s_ref, seed_ref, o_ref, *, mu: float,
                        sigma: float, k_total: int, n_k: int, wl: int):
    """One (bm, bn) tile; K streamed on grid axis 2, noise added on last step.

    s_ref: (1, 2) f32 [s_x, s_w]; seed_ref: (1, 1) int32 — the same block
    broadcast to every grid point.
    """
    k_idx = pl.program_id(2)
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(k_idx == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    lim = float(2 ** (wl - 1))
    sx = s_ref[0, 0]
    sw = s_ref[0, 1]
    xq = jnp.clip(jnp.round(x_ref[...] / sx), -lim, lim - 1)
    wq = jnp.clip(jnp.round(w_ref[...] / sw), -lim, lim - 1)
    bk = x_ref.shape[1]
    if k_total % bk:
        # the last K block runs past the operands: its padding is not
        # zeros (NaN in interpret mode), so zero both sides of the tail
        col = k_idx * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        row = k_idx * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
        xq = jnp.where(col < k_total, xq, 0.0)
        wq = jnp.where(row < k_total, wq, 0.0)
    acc = jnp.dot(xq, wq, preferred_element_type=jnp.float32)
    o_ref[...] += acc

    @pl.when(k_idx == n_k - 1)
    def _finalize():
        salt = i * jnp.int32(7919) + j
        z = _hash_normal(o_ref.shape, seed_ref[0, 0], salt)
        eps = mu * k_total + sigma * jnp.sqrt(float(k_total)) * z
        o_ref[...] = (o_ref[...] + eps) * (sx * sw)


@functools.partial(jax.jit, static_argnames=("mu", "sigma", "wl", "bm",
                                             "bk", "bn", "interpret"))
def quant_matmul(x, w, s_x, s_w, mu, sigma, *, wl: int = 16,
                 bm: int = 128, bk: int = 512, bn: int = 128,
                 seed=0, interpret: bool = False):
    """Fused quantize->matmul->noise->dequantize.

    x: (M, K) float, w: (K, N) float; s_x, s_w: quantization scales (real
    value = code * s) — python floats or traced f32 scalars; seed: python
    int or traced int32 scalar; mu, sigma: per-product integer-domain
    error moments of the multiplier spec being simulated (static floats
    from the characterization cache).
    """
    mm, kk = x.shape
    _, nn = w.shape
    bm = min(bm, mm)
    bn = min(bn, nn)
    bk = min(bk, kk)
    scales = jnp.stack([jnp.asarray(s_x, jnp.float32),
                        jnp.asarray(s_w, jnp.float32)]).reshape(1, 2)
    seed_arr = jnp.asarray(seed, jnp.int32).reshape(1, 1)
    grid = (pl.cdiv(mm, bm), pl.cdiv(nn, bn), pl.cdiv(kk, bk))
    kernel = functools.partial(
        quant_matmul_kernel,
        mu=float(mu), sigma=float(sigma), k_total=kk, n_k=grid[2], wl=wl)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, 2), lambda i, j, k: (0, 0)),
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mm, nn), jnp.float32),
        interpret=interpret,
    )(x, w, scales, seed_arr)
