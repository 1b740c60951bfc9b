"""Compressed gradient all-reduce (distributed-optimization trick).

Two codecs, both with error feedback (the residual of one step is added
back before the next quantization, so compression error does not bias the
trajectory — it behaves like the paper's white noise source):

  "int8"    — blockwise-scaled int8 with deterministic-stochastic rounding
              (counter-hash), 4x reduction over fp32 on the wire
  "bf16"    — mantissa truncation: the paper's VBL idea applied to the
              communication payload (drop the low 16 mantissa bits)

Implemented as a shard_map over the data axis so the quantize -> psum ->
dequantize pipeline is explicit (XLA cannot fuse through a psum dtype
change on its own).  The pure-jax reference path (`allreduce_ref`) backs the
tests; multi-device behaviour is exercised in tests/test_parallel.py via a
subprocess with forced host devices.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["compress_decompress", "compressed_allreduce", "allreduce_ref"]

BLOCK = 256


def _block_scale(x2d):
    s = jnp.max(jnp.abs(x2d), axis=-1, keepdims=True) / 127.0
    return jnp.maximum(s, 1e-12)


def compress_decompress(g, codec: str, key=None):
    """One round-trip through the codec (for error-feedback bookkeeping)."""
    if codec == "bf16":
        return g.astype(jnp.bfloat16).astype(g.dtype)
    if codec == "int8":
        flat = g.reshape(-1)
        pad = (-flat.shape[0]) % BLOCK
        fp = jnp.pad(flat, (0, pad)).reshape(-1, BLOCK)
        s = _block_scale(fp)
        scaled = fp / s
        if key is not None:
            noise = jax.random.uniform(key, scaled.shape) - 0.5
            q = jnp.clip(jnp.round(scaled + noise), -127, 127)
        else:
            q = jnp.clip(jnp.round(scaled), -127, 127)
        out = (q.astype(jnp.int8).astype(jnp.float32) * s).reshape(-1)
        return out[:flat.shape[0]].reshape(g.shape).astype(g.dtype)
    raise ValueError(codec)


def allreduce_ref(gs_stacked, codec: str):
    """Reference: mean over a stacked leading 'device' axis, each shard
    compressed before the sum (what the shard_map path computes)."""
    comp = jax.vmap(lambda g: compress_decompress(g, codec))(gs_stacked)
    return jnp.mean(comp, axis=0)


def compressed_allreduce(grads, mesh: Mesh, codec: str = "int8",
                         axis: str = "data", error_buf=None):
    """All-reduce-mean `grads` over `axis` with on-the-wire compression.

    grads must be replicated-or-sharded consistently with the mesh; the
    shard_map treats each leaf as locally owned and psums the quantized
    payload.  Returns (mean_grads, new_error_buf).
    """
    if error_buf is None:
        error_buf = jax.tree.map(jnp.zeros_like, grads)

    def per_shard(g, e):
        g_fb = g + e
        if codec == "int8":
            flat = g_fb.reshape(-1)
            pad = (-flat.shape[0]) % BLOCK
            fp = jnp.pad(flat, (0, pad)).reshape(-1, BLOCK)
            # shared per-block scale (pmax = one tiny fp32 collective) so
            # the int8 sums decode exactly: sum(q_i) * s / n == mean
            s = jax.lax.pmax(_block_scale(fp), axis)
            q = jnp.clip(jnp.round(fp / s), -127, 127).astype(jnp.int8)
            qsum = jax.lax.psum(q.astype(jnp.int32), axis)
            n = jax.lax.psum(1, axis)
            mean = (qsum.astype(jnp.float32) * s / n).reshape(-1)
            mean = mean[:flat.shape[0]].reshape(g.shape).astype(g.dtype)
            sent = (q.astype(jnp.float32) * s).reshape(-1)
            sent = sent[:flat.shape[0]].reshape(g.shape)
        else:
            comp = g_fb.astype(jnp.bfloat16)
            mean = (jax.lax.psum(comp.astype(jnp.float32), axis)
                    / jax.lax.psum(1, axis)).astype(g.dtype)
            sent = comp.astype(jnp.float32)
        new_e = (g_fb - sent).astype(e.dtype)
        return mean, new_e

    def inner(g_tree, e_tree):
        leaves_g, tdef = jax.tree.flatten(g_tree)
        leaves_e = tdef.flatten_up_to(e_tree)
        res = [per_shard(g, e) for g, e in zip(leaves_g, leaves_e)]
        return (tdef.unflatten([m for m, _ in res]),
                tdef.unflatten([e2 for _, e2 in res]))

    fn = jax.shard_map(
        inner, mesh=mesh,
        in_specs=(P(axis), P(axis)),      # leading dim owned per data shard
        out_specs=(P(axis), P(axis)),
        check_vma=False,
    )
    return fn(grads, error_buf)
