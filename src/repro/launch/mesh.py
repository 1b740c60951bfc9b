"""Mesh construction and per-device peak rates.

This module is the only place a ``Mesh`` is built.  Every mesh uses
``AxisType.Auto`` axes: the model code places data with logical sharding
constraints (``parallel/logical.py``) and plain indexing, which the
``Explicit`` default of ``jax.make_mesh`` rejects (a gather such as
``embed[tokens]`` or a cache update then raises ``ShardingTypeError``).

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state; the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax import
and then calls it.

Production target: TPU v5e, 16x16 = 256 chips per pod; multi-pod doubles
over the data-center network on a leading "pod" axis.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh", "make_production_mesh", "make_host_mesh", "PEAKS",
           "PRODUCTION_KIND", "peaks"]

# Published per-chip peaks, keyed by ``jax.Device.device_kind``.
# TPU v5e: Google Cloud documentation, "TPU v5e" (system architecture).
PEAKS = {
    "TPU v5 lite": {
        "peak_flops_bf16": 197e12,     # FLOP/s per chip
        "peak_ops_int8": 393e12,       # OP/s per chip
        "hbm_bw": 819e9,               # bytes/s per chip
        "ici_bw": 50e9,                # bytes/s per link (1,600 Gbit/s total)
        "hbm_bytes": 16e9,             # capacity per chip
    },
}

# the device kind the production mesh (and so the dry-run roofline) targets
PRODUCTION_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> dict:
    """Peak rates of one chip of ``device_kind``; unknown kinds raise."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1, *, devices=None):
    """Small ``(data, model)`` mesh over ``devices`` (default: all)."""
    devices = jax.devices() if devices is None else list(devices)
    n = len(devices)
    data = min(data, n)
    model = min(model, max(n // data, 1))
    return make_mesh((data, model), ("data", "model"),
                     devices=devices[:data * model])
