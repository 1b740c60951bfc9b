"""Filterbank benchmark: accumulate-form trajectory + end-to-end serving.

Times the batched multi-channel Broken-Booth FIR datapath through
``dsp.fir_apply`` (quantize -> filterbank -> descale) and derives
throughput in filtered samples/second plus the paper-anchored quality
number (mean SNR_out across channels at the wl=16 operating point), and
the perf trajectory of the datapath across PRs, on the same shapes:

  * kernel: the PR-1 kernel body (Booth digits re-derived from the raw tap
    codes inside every tap of every grid step; reproduced locally here) vs
    the PR-2 precoded rows kernel (digit planes decoded once per bank) vs
    the dot form (exact contraction on the matmul units minus the low-bit
    correction — on CPU the rows kernel runs through the Pallas
    interpreter while the dot form is what the entry point actually
    lowers to: plain compiled XLA; that asymmetry *is* the design, the
    dot form exists to reach the platform matmul instead of emulating
    rows),
  * host: the PR-1 windowed host path vs the PR-2 per-tap
    shift-and-accumulate path vs the dot form,
  * serving: fresh decode-per-flush (PR-1) vs ``FilterbankEngine``'s
    cached ``PrecodedBank`` (PR-2, rows form) vs the engine on the dot
    form.

Every comparison also asserts bit-exactness; a rows-side mismatch shows
up as ``kernel_bitexact: 0`` and a dot-form mismatch as
``dotform_bitexact: 0`` in the derived dict (CI fails on either).
Results are written to ``BENCH_filterbank.json`` with platform/version
metadata in the ``config`` block so trajectories across machines are
interpretable.

On CPU the rows kernel runs through the Pallas interpreter, which is
orders of magnitude slower than compiled TPU code — so the host
closed-form backend is swept densely and the kernels are sampled at the
wl=16 operating point.  On a TPU backend the sweep times the compiled
kernels themselves.
"""
from __future__ import annotations

import functools
import json
import os
import platform as platform_mod
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.multipliers import MulSpec, mul
from repro.dsp import PrecodedBank, design_lowpass, fir_apply
from repro.dsp.fir import _amp, _codes32, _descale, _quantize64
from repro.dsp.testbed import run_filterbank_case
from repro.kernels import (booth_precode, fir_bbm_bank_precoded,
                           min_safe_shift, on_tpu)
from repro.kernels.booth_rows import split_signed


def _pr1_rows_product(a_s, bu, *, wl, vbl, kind):
    """The PR-1 row loop, reproduced verbatim as the baseline: Booth digits
    re-derived from the raw code per row, one array op at a time."""
    prod = None
    prev_hi = None
    for r in range(wl // 2):
        b_hi = (bu >> (2 * r + 1)) & 1
        b_mid = (bu >> (2 * r)) & 1
        b_lo = jnp.zeros_like(b_mid) if r == 0 else prev_hi
        prev_hi = b_hi
        d = -2 * b_hi + b_mid + b_lo
        m = max(0, vbl - 2 * r)
        if kind == 0:
            rows = d * a_s
            contrib = (rows >> m) << m
        else:
            mag = jnp.abs(d)
            pos = mag * a_s
            rows = jnp.where(b_hi == 1, -pos - 1, pos)
            contrib = (rows >> m) << m
            if m == 0:
                contrib = contrib + b_hi
        term = contrib << (2 * r)
        prod = term if prod is None else prod + term
    return prod

# (channels, signal length) grid; wl -> paper-ish operating vbl
SHAPES = [(4, 1 << 11), (8, 1 << 12), (16, 1 << 12)]
POINTS = [(8, 5), (12, 9), (16, 13)]
# reduced configuration for the CI smoke step
SMOKE_SHAPES = [(4, 1 << 10)]
SMOKE_POINTS = [(16, 13)]


def _time(fn, repeats: int = 3) -> float:
    """Median wall time — robust to scheduler noise on shared CPU runners."""
    return _time_many([fn], repeats)[0]


def _time_many(fns, repeats: int = 3) -> list[float]:
    """Median wall times of several candidates, measured round-robin.

    Cells that are compared against each other (rows vs dot form, legacy
    vs precoded) must not be timed in separate back-to-back batches: on a
    shared 2-core runner the load drifts on the scale of one batch, and a
    sequential A-then-B measurement hands whichever ran in the quieter
    window a phantom speedup.  Interleaving the rounds makes every
    candidate sample the same noise distribution.
    """
    for fn in fns:
        fn()                               # warm-up / compile
    ts = [[] for _ in fns]
    for _ in range(repeats):
        for i, fn in enumerate(fns):
            t0 = time.perf_counter()
            fn()
            ts[i].append(time.perf_counter() - t0)
    return [float(np.median(t)) for t in ts]


# ----------------------------------------------------- PR-1 kernel baseline
def _legacy_fir_kernel(x_ref, h_ref, o_ref, halo_ref, *, wl, vbl, kind,
                       taps, shift, bt):
    """The PR-1 kernel body: recode inside the hot loop (baseline only)."""
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _zero_state():
        halo_ref[...] = jnp.zeros_like(halo_ref)

    xs = jnp.concatenate([halo_ref[...], x_ref[...]], axis=1)
    h = h_ref[...]
    mask = (1 << wl) - 1
    acc = jnp.zeros(o_ref.shape, jnp.int32)
    for k in range(taps):
        _, a_s = split_signed(xs[:, taps - 1 - k:taps - 1 - k + bt], wl)
        bu = (h[:, k] & mask)[:, None]
        # digits re-derived from the raw code for every tap of every step
        prod = _pr1_rows_product(a_s, bu, wl=wl, vbl=vbl, kind=kind)
        if shift:
            prod = prod >> shift
        acc = acc + prod
    o_ref[...] = acc
    halo_ref[...] = xs[:, bt:]


@functools.partial(jax.jit, static_argnames=("wl", "vbl", "kind", "shift",
                                             "bc", "bt", "interpret"))
def _legacy_fir_bank(x, h, *, wl, vbl, kind=0, shift=0, bc=8, bt=512,
                     interpret=False):
    channels, n = x.shape
    taps = h.shape[1]
    bc = min(bc, channels)
    bt = min(bt, n)
    nc = pl.cdiv(channels, bc)
    nt = pl.cdiv(n, bt)
    xp = jnp.pad(x, ((0, nc * bc - channels), (0, nt * bt - n)))
    hp = jnp.pad(h, ((0, nc * bc - channels), (0, 0)))
    kernel = functools.partial(_legacy_fir_kernel, wl=wl, vbl=vbl, kind=kind,
                               taps=taps, shift=shift, bt=bt)
    out = pl.pallas_call(
        kernel,
        grid=(nc, nt),
        in_specs=[
            pl.BlockSpec((bc, bt), lambda c, t: (c, t)),
            pl.BlockSpec((bc, taps), lambda c, t: (c, 0)),
        ],
        out_specs=pl.BlockSpec((bc, bt), lambda c, t: (c, t)),
        out_shape=jax.ShapeDtypeStruct((nc * bc, nt * bt), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bc, taps - 1), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(xp, hp)
    return out[:channels, :n]


# ------------------------------------------------------- PR-1 host baseline
def _legacy_host_windowed(x, h, spec, shift):
    """The PR-1 host path: (C, N, taps) gathered window (baseline only)."""
    amp = _amp(x)
    xq = _quantize64(x * amp, spec.wl)
    hq = _quantize64(h, spec.wl)
    n = xq.shape[-1]
    taps = hq.shape[-1]
    idx = np.arange(n)[:, None] - np.arange(taps)[None, :]
    win = np.where(idx >= 0, xq[..., np.clip(idx, 0, None)], 0)
    prod = np.asarray(mul(spec)(jnp.asarray(_codes32(win, spec.wl)),
                                jnp.asarray(_codes32(hq, spec.wl))[:, None, :]),
                      np.int64)
    if shift:
        prod = prod >> shift
    return _descale(prod.astype(np.float64).sum(axis=-1), spec.wl, shift, amp)


# --------------------------------------------------------------- the sweep
def _kernel_micro(channels, n, wl, vbl, interpret, rows):
    """Kernel trajectory: legacy body vs precoded rows vs dot form.

    -> (speedup_precoded, speedup_dotform, ok_rows, ok_dot).  The rows
    cells run the kernel exactly as the entry point does on this backend
    (interpreted off-TPU); the dot cell runs what ``form=None`` resolves
    to — compiled XLA on the platform matmul — so ``kernel_speedup_dotform``
    is the measured win of the new auto-picked path over the PR-2 one.
    """
    rng = np.random.default_rng(2)
    shift = min_safe_shift(31, wl)
    x = jnp.asarray(rng.integers(0, 1 << wl, (channels, n)), jnp.int32)
    h = jnp.asarray(rng.integers(0, 1 << wl, (channels, 31)), jnp.int32)
    kw = dict(wl=wl, vbl=vbl, kind=0, shift=shift, bc=min(channels, 8),
              bt=min(n, 512), interpret=interpret)
    hmag, hneg = booth_precode(h, wl)
    t_leg, t_pre, t_dot = _time_many(
        [lambda: jax.block_until_ready(_legacy_fir_bank(x, h, **kw)),
         lambda: jax.block_until_ready(
             fir_bbm_bank_precoded(x, hmag, hneg, form="rows", **kw)),
         lambda: jax.block_until_ready(
             fir_bbm_bank_precoded(x, hmag, hneg, form="dot", **kw))],
        repeats=15)
    ref = np.asarray(_legacy_fir_bank(x, h, **kw))
    ok_rows = bool(np.array_equal(ref, np.asarray(
        fir_bbm_bank_precoded(x, hmag, hneg, form="rows", **kw))))
    ok_dot = bool(np.array_equal(ref, np.asarray(
        fir_bbm_bank_precoded(x, hmag, hneg, form="dot", **kw))))
    rows.append({"cell": "kernel_raw_recode", "channels": channels, "n": n,
                 "wl": wl, "vbl": vbl, "us_per_call": t_leg * 1e6})
    rows.append({"cell": "kernel_precoded", "channels": channels, "n": n,
                 "wl": wl, "vbl": vbl, "us_per_call": t_pre * 1e6})
    rows.append({"cell": "kernel_dotform", "channels": channels, "n": n,
                 "wl": wl, "vbl": vbl, "us_per_call": t_dot * 1e6})
    return t_leg / t_pre, t_pre / t_dot, ok_rows, ok_dot


def _host_micro(channels, n, wl, vbl, rows):
    """Host trajectory: windowed (PR-1) vs per-tap (PR-2) vs dot form.

    -> (speedup_per_tap, speedup_dotform, ok_rows, ok_dot).  All three
    are compiled
    host datapaths on the same signals; the dot cell measures the
    identity rewrite alone (same backend, same pipeline).
    """
    rng = np.random.default_rng(3)
    spec = MulSpec("bbm0", wl, vbl)
    shift = min_safe_shift(31, wl)
    x = rng.standard_normal((channels, n))
    banks = np.stack([design_lowpass(), design_lowpass(stop_weight=0.5)])
    h = banks[np.arange(channels) % 2]
    t_win, t_tap, t_dot = _time_many(
        [lambda: _legacy_host_windowed(x, h, spec, shift),
         lambda: fir_apply(x, h, spec, backend="host", shift=shift,
                           form="rows"),
         lambda: fir_apply(x, h, spec, backend="host", shift=shift,
                           form="dot")], repeats=9)
    ref = _legacy_host_windowed(x, h, spec, shift)
    ok = bool(np.array_equal(ref, fir_apply(x, h, spec, backend="host",
                                            shift=shift, form="rows")))
    ok_dot = bool(np.array_equal(ref, fir_apply(x, h, spec, backend="host",
                                                shift=shift, form="dot")))
    rows.append({"cell": "host_windowed", "channels": channels, "n": n,
                 "wl": wl, "vbl": vbl, "us_per_call": t_win * 1e6})
    rows.append({"cell": "host_per_tap", "channels": channels, "n": n,
                 "wl": wl, "vbl": vbl, "us_per_call": t_tap * 1e6})
    rows.append({"cell": "host_dotform", "channels": channels, "n": n,
                 "wl": wl, "vbl": vbl, "us_per_call": t_dot * 1e6})
    return t_win / t_tap, t_tap / t_dot, ok, ok_dot


def _engine_micro(wl, vbl, n_req, n_samp, block, backend, rows):
    """Serving trajectory: fresh decode vs cached rows vs cached dot form.

    -> (speedup_cached, speedup_dotform, ok_rows, ok_dot, rate).
    ``speedup_cached``
    keeps the PR-2 meaning (fresh-vs-cached, rows form on both sides);
    ``speedup_dotform`` is cached-rows vs cached-dot on the same engine
    configuration, and ``rate`` reports the best serving throughput.
    """
    from repro.serve import FilterbankEngine
    rng = np.random.default_rng(4)
    spec = MulSpec("bbm0", wl, vbl)
    banks = np.stack([design_lowpass(), design_lowpass(stop_weight=0.5)])
    sigs = [rng.standard_normal(n_samp) for _ in range(n_req)]
    engines = {form: FilterbankEngine(banks, spec, backend=backend,
                                      max_channels=n_req, block=block,
                                      form=form)
               for form in ("rows", "dot")}

    def cached_round(form):
        eng = engines[form]
        for i, s in enumerate(sigs):
            eng.submit(s, bank=i % 2)
        return eng.flush()

    x = np.stack(sigs)
    h = banks[np.arange(n_req) % 2]

    def fresh_round():
        # PR-1 per-flush behaviour: quantize + recode the banks every time
        return fir_apply(x, h, spec, backend=backend, block=block,
                         form="rows")

    t_cached, t_dot, t_fresh = _time_many(
        [lambda: cached_round("rows"), lambda: cached_round("dot"),
         fresh_round], repeats=15)
    ref = fresh_round()
    out = cached_round("rows")             # rids ascend in submit order
    out_dot = cached_round("dot")
    ok = bool(np.array_equal(np.stack([out[r] for r in sorted(out)]), ref))
    ok_dot = bool(np.array_equal(
        np.stack([out_dot[r] for r in sorted(out_dot)]), ref))
    rate = n_req * n_samp / min(t_cached, t_dot)
    rows.append({"cell": "engine_fresh_bank", "channels": n_req, "n": n_samp,
                 "wl": wl, "vbl": vbl, "backend": backend,
                 "us_per_call": t_fresh * 1e6})
    rows.append({"cell": "engine_cached_bank", "channels": n_req,
                 "n": n_samp, "wl": wl, "vbl": vbl, "backend": backend,
                 "us_per_call": t_cached * 1e6,
                 "samples_per_s": n_req * n_samp / t_cached})
    rows.append({"cell": "engine_dotform", "channels": n_req,
                 "n": n_samp, "wl": wl, "vbl": vbl, "backend": backend,
                 "us_per_call": t_dot * 1e6,
                 "samples_per_s": n_req * n_samp / t_dot})
    return t_fresh / t_cached, t_cached / t_dot, ok, ok_dot, rate


def filterbank_sweep(smoke: bool = False, out: str | None = None):
    rng = np.random.default_rng(0)
    shapes = SMOKE_SHAPES if smoke else SHAPES
    points = SMOKE_POINTS if smoke else POINTS
    banks = np.stack([design_lowpass(), design_lowpass(stop_weight=0.5)])
    # timed sweep: what the entry point runs on this backend (the dot
    # form off-TPU); the bit-exactness checkpoint pins form="rows" on the
    # kernel side so the Pallas rows pipeline is cross-checked against the
    # auto datapath on every sweep shape
    backend = "pallas" if on_tpu() else "host"
    check_backend = "pallas" if on_tpu() else "pallas-interpret"
    rows = []
    best_rate = 0.0
    bitexact = True
    for channels, n in shapes:
        x = rng.standard_normal((channels, n))
        h = banks[np.arange(channels) % 2]
        for wl, vbl in points:
            spec = MulSpec("bbm0", wl, vbl)
            dt = _time(lambda: fir_apply(x, h, spec, backend=backend))
            rate = channels * n / dt
            best_rate = max(best_rate, rate)
            rows.append({"cell": "sweep", "channels": channels, "n": n,
                         "wl": wl, "vbl": vbl, "backend": backend,
                         "us_per_call": dt * 1e6, "samples_per_s": rate})
        # one kernel cell per shape: bit-exactness checkpoint vs host
        wl, vbl = points[-1]
        spec = MulSpec("bbm0", wl, vbl)
        shift = min_safe_shift(h.shape[1], wl)
        a = fir_apply(x, h, spec, backend="host", shift=shift)
        b = fir_apply(x, h, spec, backend=check_backend, shift=shift,
                      form="rows")
        bitexact &= bool(np.array_equal(a, b))

    # accumulate-form micro-benchmarks at the wl=16 operating point.  The
    # kernel and engine cells run at serving-representative block sizes
    # (a couple of thousand samples per dispatch): the decode phase is a
    # fixed per-call cost, so giant signals would amortize away exactly
    # the overhead the precoded path removes.
    wl, vbl = 16, 13
    k_speed, k_dot_speed, k_ok, k_dot_ok = _kernel_micro(
        4, 1 << 11, wl, vbl, not on_tpu(), rows)
    h_speed, h_dot_speed, h_ok, h_dot_ok = _host_micro(
        *((4, 1 << 10) if smoke else (8, 1 << 12)), wl, vbl, rows)
    e_req, e_samp = (3, 512) if smoke else (8, 512)
    e_speed, e_dot_speed, e_ok, e_dot_ok, e_rate = _engine_micro(
        wl, vbl, e_req, e_samp, min(512, e_samp), check_backend, rows)
    bitexact &= k_ok and h_ok and e_ok
    dot_bitexact = k_dot_ok and h_dot_ok and e_dot_ok

    derived = {
        "best_samples_per_s": best_rate,
        "kernel_bitexact": int(bitexact),
        "dotform_bitexact": int(dot_bitexact),
        "kernel_speedup_precoded": k_speed,
        "kernel_speedup_dotform": k_dot_speed,
        "host_speedup_per_tap": h_speed,
        "host_speedup_dotform": h_dot_speed,
        "engine_speedup_cached_bank": e_speed,
        "engine_speedup_dotform": e_dot_speed,
        "engine_samples_per_s": e_rate,
        "cells": len(rows),
    }
    if not smoke:
        snrs = run_filterbank_case(MulSpec("bbm0", 16, 13), channels=4,
                                   n=1 << 12)
        derived["mean_snr_db_wl16_vbl13"] = float(np.mean(snrs))
    if out:
        config = {
            "smoke": smoke, "backend": backend, "on_tpu": on_tpu(),
            # platform metadata: bench trajectories are only comparable
            # within one (machine, backend, jax) triple
            "jax_backend": jax.default_backend(),
            "jax_version": jax.__version__,
            "numpy_version": np.__version__,
            "python_version": platform_mod.python_version(),
            "platform": platform_mod.platform(),
            "machine": platform_mod.machine(),
            "cpu_count": os.cpu_count(),
        }
        with open(out, "w") as f:
            json.dump({"config": config, "derived": derived, "rows": rows},
                      f, indent=1)
    return rows, derived


def main(argv=None) -> int:
    import argparse
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--smoke", action="store_true",
                   help="reduced configuration for CI")
    p.add_argument("--out", default="BENCH_filterbank.json",
                   help="results file (the sweep only writes one when "
                        "invoked through this entry point)")
    args = p.parse_args(argv)
    _, derived = filterbank_sweep(smoke=args.smoke, out=args.out)
    print(json.dumps(derived, indent=1, sort_keys=True))
    # CI gate: both the rows pipeline and the dot form must be bit-exact
    return 0 if derived["kernel_bitexact"] and derived["dotform_bitexact"] \
        else 1


if __name__ == "__main__":
    raise SystemExit(main())
