"""Channel-sharded Broken-Booth FIR filterbank (shard_map over the mesh).

Channels are embarrassingly parallel in the filterbank: y[c] depends only
on x[c] and h[c].  ``sharded_filterbank`` splits the channel axis across a
mesh axis with ``shard_map`` and runs the single-device datapath on each
shard — the Pallas kernel on TPU, the pure-jnp closed form elsewhere — so a
(C, N) batch is served by ``mesh.shape[axis]`` devices with no collectives
at all (the sharding *is* the decomposition).

The tap bank is the Booth multiplier operand and is constant across the
batch, so its radix-4 digits are decoded exactly once — *outside* the
shard_map — and the (wl//2, C, taps) digit planes are what gets sharded
along the channel axis; each shard runs the accumulate phase only.
Long-lived callers can decode once per bank lifetime with
``precode_filterbank`` and pass the planes to every call.

Accumulate-form selection is per shard and trace-time: the dot form
(dense exact contraction on the matmul units + scaled truncated rows —
``kernels.booth_rows``) is the default on every backend; ``form="rows"``
pins the streaming kernel emulation instead.

Everything is integer-code level: (C, N) int32 wl-bit signal codes in,
(C, N) int32 accumulator values out, bit-identical to the unsharded kernel
because each channel's computation is untouched by the split.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..kernels.booth_rows import booth_precode, resolve_form
from ..kernels.fir_kernel import (_DOT_WINDOW_BUDGET, _check_envelope,
                                  fir_bbm_bank_precoded)
from ..kernels.ops import on_tpu
from ..kernels.ref import fir_bank_ref

__all__ = ["precode_filterbank", "sharded_filterbank"]


def precode_filterbank(h, *, wl: int, channels: int | None = None):
    """Decode a (C, taps) tap bank once -> (hmag, hneg) digit planes.

    h: (C, taps) int32 codes, or (taps,) to share one bank across
    ``channels`` rows.  The planes feed ``sharded_filterbank(h_planes=...)``
    across any number of calls that reuse the bank.
    """
    h = jnp.asarray(h)
    if h.ndim == 1:
        if channels is None:
            raise ValueError("channels is required to broadcast a shared "
                             "(taps,) bank")
        h = jnp.broadcast_to(h[None, :], (channels, h.shape[0]))
    return booth_precode(h, wl)


def sharded_filterbank(x, h, mesh: Mesh, *, wl: int, vbl: int, kind: int = 0,
                       shift: int = 0, axis: str = "data",
                       use_kernel: bool | None = None, bc: int = 8,
                       bt: int = 512, h_planes=None,
                       form: str | None = None):
    """Filterbank over ``mesh`` with channels sharded on mesh axis ``axis``.

    x: (C, N) int32 codes, h: (C, taps) int32 codes (or (taps,) shared).
    C must divide by the mesh axis size; pad channels first if it does not.
    ``use_kernel=None`` picks the kernel datapath everywhere: on TPU
    always, and off-TPU because the auto form is the dot form — plain
    XLA, not the interpreter.  Only ``form="rows"`` off-TPU falls back to
    the jnp closed form (the interpreter inside shard_map would only slow
    things down); ``use_kernel=False`` forces that path.  ``form`` pins
    the accumulate form ("rows"/"dot"; None auto).  ``h_planes`` takes
    the digit planes from ``precode_filterbank`` so a long-lived bank is
    decoded once, not once per call; when omitted the decode still runs
    only once per call, outside the shard_map.
    """
    if h.ndim == 1:
        h = jnp.broadcast_to(h[None, :], (x.shape[0], h.shape[0]))
    # the kernel path checks this itself; the closed-form host path would
    # silently wrap int32 instead — guard both uniformly
    _check_envelope(h.shape[1], wl, shift)
    n_shards = mesh.shape[axis]
    if x.shape[0] % n_shards:
        raise ValueError(f"channels={x.shape[0]} not divisible by "
                         f"mesh axis {axis!r} of size {n_shards}")
    resolve_form(form)        # validate on every path, incl. the jnp one
    if use_kernel is None:
        # auto: the kernel datapath, unless a form=None off-TPU shard
        # would hit the kernel's own auto-form memory fallback to
        # *interpreted* rows — there the jnp closed form below is the
        # sane default instead.  An explicit form="dot" is always
        # honored (the caller owns the memory then).
        per_shard = (x.shape[0] // n_shards) * x.shape[1] * h.shape[1]
        dot_auto = resolve_form(form) == "dot" and (
            form == "dot"
            or jax.default_backend() == "cpu"
            or per_shard <= _DOT_WINDOW_BUDGET)
        use_kernel = on_tpu() or dot_auto

    if use_kernel:
        if h_planes is None:
            h_planes = booth_precode(h, wl)     # once, outside the shard_map
        hmag, hneg = h_planes
        if hmag.shape[1] != x.shape[0]:
            raise ValueError(f"h_planes cover {hmag.shape[1]} channels, "
                             f"x has {x.shape[0]}")
        apply_fn = functools.partial(fir_bbm_bank_precoded, wl=wl, vbl=vbl,
                                     kind=kind, shift=shift, bc=bc, bt=bt,
                                     interpret=not on_tpu(), form=form)
        fn = jax.shard_map(
            lambda xs, hm, hn: apply_fn(xs, hm, hn),
            mesh=mesh,
            in_specs=(P(axis, None), P(None, axis, None),
                      P(None, axis, None)),
            out_specs=P(axis, None),
            check_vma=False,
        )
        return fn(x, hmag, hneg)

    fn = jax.shard_map(
        lambda xs, hs: fir_bank_ref(xs, hs, wl=wl, vbl=vbl, kind=kind,
                                    shift=shift),
        mesh=mesh,
        in_specs=(P(axis, None), P(axis, None)),
        out_specs=P(axis, None),
        check_vma=False,
    )
    return fn(x, h)
