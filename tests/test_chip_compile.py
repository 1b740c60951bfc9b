"""Main-path kernels compile for a TPU v5e chip, at real widths.

Nothing here runs on a chip.  Each test lowers one kernel for a chip
described by ``get_topology_desc("v5e:2x2")`` (one of its devices) and
compiles it with the installed TPU compiler, which refuses what interpret
mode accepts: blocks the (8, 128) tiling cannot hold, gathers and casts
Mosaic has no lowering for, programs that overflow the device.  The
widths are those ``chip_smoke.py`` runs: the paper's Table IV filter (31
taps, order 30) over 64 channels x 65,536 samples, qwen2-0.5b's MLP
(896 x 4864) and head geometry (14 heads, head_dim 64, 4k context).

The topology is described inside a fixture, so a worker that is not given
this file never loads the TPU library.  Code that picks a form from
``jax.default_backend()`` sees the CPU here; each test patches it to
"tpu" so the branch the chip takes is the one compiled.
"""
from __future__ import annotations

import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.dsp.fir import NUM_TAPS
from repro.kernels.bbm_matmul import (bbm_matmul_coded_kblocks,
                                      bbm_matmul_precoded, bbm_matmul_scaled)
from repro.kernels.fir_kernel import fir_bbm_bank_precoded
from repro.kernels.flash_attention import flash_attention_amm
from repro.kernels.quant_matmul import quant_matmul

WL, VBL = 16, 13                       # the paper's Table IV operating point
CHANNELS, SAMPLES, TAPS = 64, 65536, NUM_TAPS
M, D_MODEL, D_FF = 128, 896, 4864      # qwen2-0.5b MLP, a 128-token block
HEADS, SEQ, HEAD_DIM = 14, 4096, 64    # qwen2-0.5b attention at 4k
SLOTS, KV_HEADS, CTX = 64, 2, 768      # the chat cell's decode step
ROWS = WL // 2
i32, f32 = jnp.int32, jnp.float32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile written to the persistent cache cannot be read back
    # without a chip: keep these compiles out of it
    jax.config.update("jax_enable_compilation_cache", False)
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_chip(monkeypatch, one_chip):
    """Shapes placed on the described chip, with the TPU branches taken."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert compiled.memory_analysis() is not None
    return compiled


@pytest.mark.parametrize("form", ["rows", "dot"])
@pytest.mark.parametrize("kind", [0, 1])
def test_fir_filterbank_compiles(on_chip, kind, form):
    compiled = _compile(lambda x, m, n: fir_bbm_bank_precoded(
        x, m, n, wl=WL, vbl=VBL, kind=kind, shift=5, form=form,
        interpret=False),
        on_chip((CHANNELS, SAMPLES), i32),
        on_chip((ROWS, CHANNELS, TAPS), i32),
        on_chip((ROWS, CHANNELS, TAPS), i32))
    assert ("tpu_custom_call" in compiled.as_text()) == (form == "rows")


def test_rows_bbm_matmul_compiles(on_chip):
    compiled = _compile(lambda x, m, n: bbm_matmul_precoded(
        x, m, n, wl=WL, vbl=VBL, kind=0, shift=16, form="rows",
        interpret=False),
        on_chip((M, D_MODEL), i32), on_chip((ROWS, D_MODEL, D_FF), i32),
        on_chip((ROWS, D_MODEL, D_FF), i32))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("k,n", [(D_MODEL, D_FF), (D_FF, D_MODEL)])
def test_bbm_matmul_scaled_compiles(on_chip, k, n):
    _compile(lambda x, m, ng: bbm_matmul_scaled(x, m, ng, wl=WL, vbl=VBL,
                                                kind=0),
             on_chip((M, k), i32), on_chip((ROWS, k, n), i32),
             on_chip((ROWS, k, n), i32))


def test_flash_amm_compiles(on_chip):
    compiled = _compile(lambda q, k, v: flash_attention_amm(
        q, k, v, wl=WL, vbl=VBL, kind=0, causal=True, use_kernel=True,
        interpret=False),
        *[on_chip((1, HEADS, SEQ, HEAD_DIM), f32)] * 3)
    assert "tpu_custom_call" in compiled.as_text()


def test_quant_matmul_compiles(on_chip):
    compiled = _compile(lambda x, w, sx, sw, seed: quant_matmul(
        x, w, sx, sw, 0.5, 3.0, wl=WL, seed=seed, interpret=False),
        on_chip((M, D_MODEL), f32), on_chip((D_MODEL, D_FF), f32),
        on_chip((), f32), on_chip((), f32), on_chip((), i32))
    assert "tpu_custom_call" in compiled.as_text()


# the served value products, one layer: (vmapped batch, M, N) at 768
# positions in blocks of 16: qwen2's decode (64 slots x 2 KV heads, 7 query
# heads a KV head, head dim 64) and Moonlight's absorbed latent attention
# (64 slots, 16 heads, the latent's 512 value columns)
PV_SERVED = {"qwen2": ((SLOTS, KV_HEADS), HEADS // KV_HEADS, HEAD_DIM),
             "moonlight": ((SLOTS,), 16, 512)}


@pytest.mark.parametrize("shape", list(PV_SERVED))
def test_value_product_rows_form_compiles_fused(on_chip, shape):
    """The served value products take the rows form on the chip with no
    dot, and no array as large as a row product (batch x 48 blocks x M x
    16 x N elements, in any layout) reaches memory: the rows and the
    block sums fuse."""
    batch, m, n = PV_SERVED[shape]
    fn = lambda a, c, s: bbm_matmul_coded_kblocks(a, c, s, wl=WL, vbl=VBL,
                                                  kind=0, block=16)
    for _ in batch:
        fn = jax.vmap(fn)
    compiled = _compile(fn, on_chip(batch + (m, CTX), f32),
                        on_chip(batch + (CTX, n), i32),
                        on_chip(batch + (CTX // 16,), f32))
    hlo = compiled.as_text()
    assert " dot(" not in hlo and " convolution(" not in hlo
    row = math.prod(batch) * (CTX // 16) * m * 16 * n
    # the element count of every array a top-level instruction names
    entry = hlo[hlo.index("\nENTRY"):]
    sizes = [math.prod(int(d) for d in dims.split(",") if d)
             for line in entry.splitlines()
             for dims in re.findall(r"\b(?:pred|[suf]\d+|bf16)\[([\d,]*)\]",
                                    line.split(" metadata=")[0])]
    assert sizes and max(sizes) < row
