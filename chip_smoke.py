"""Bring-up smoke run on a TPU, through the entry points a user calls.

    python3 chip_smoke.py             # one chip: kernels, filterbank, serving
    python3 chip_smoke.py --chips 4   # four chips: the sharded paths only

One chip:
  * kernels: the paper's filter (31 taps, WL=16, VBL=13) through the
    compiled FIR kernel in rows and dot form, bbm0 and bbm1, over 64
    channels x 65,536 samples; ``bbm_matmul_scaled`` at qwen2-0.5b's MLP
    widths; flash-amm at its head geometry (14 heads, head_dim 64) at 4k.
    Each takes int codes quantized once on the CPU and is compared with
    its ``kernels/ref.py`` oracle run on the CPU device.
  * filterbank engine: ``FilterbankEngine(backend="pallas")`` serving a
    few flushes, bitwise equal to the same requests on ``backend="host"``
    run on the CPU device.
  * LM serving: qwen2-0.5b at its published widths and depth (random
    weights from a seed) through ``make_serve_fns`` + continuous
    ``Scheduler`` with the int-code KV cache: four requests with amm
    bitexact on every matmul; four with it on the attention products,
    each stream equal to its solo run; and the amm-off prefill logits
    against the same forward on the CPU device.

Four chips: three train steps on a (data=2, model=2) mesh against the same
steps on one chip, and ``sharded_filterbank`` over four data shards.

The last line of standard output is the result, a JSON object.  Without a
TPU, or outside a checkout of this repository, the script prints no result
and exits non-zero.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
import warnings
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
SEED = 0

# kernels and filterbank: the paper's Table IV operating point
WL, VBL = 16, 13
CHANNELS, SAMPLES = 64, 65536
MM_ROWS = 64                       # activation rows for the MLP contraction
MM_SHAPES = ((896, 4864), (4864, 896))   # qwen2-0.5b MLP (K, N)
ATTN_HEADS, ATTN_SEQ, HEAD_DIM = 14, 4096, 64
FLASH_TOL = 1e-4                   # max |diff| / max |oracle|, see phase
# filterbank engine
FB_REQUESTS, FB_SAMPLES, FB_FLUSHES = 64, 16384, 3
# LM serving
SLOTS, MAX_LEN, MAX_NEW = 4, 288, 16
PROMPT_LENS = (32, 256, 96, 160)
CONFORM_LENS = (32, 96, 32, 96)    # each distinct length compiles a prefill
LOGIT_TOL = 5e-2                   # relative L2, amm off, chip vs CPU
# four chips: depth cut, widths kept
TRAIN_LAYERS, TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 4, 8, 256, 3
LOSS_TOL = 1e-3                    # relative, (2, 2) mesh vs one chip


def log(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def _bitwise(name: str, got, want) -> None:
    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape or not np.array_equal(got, want):
        bad = (np.inf if got.shape != want.shape
               else int(np.sum(got != want)))
        raise AssertionError(f"{name}: {bad} elements differ from the "
                             f"oracle")


def _timed(fn, *args, **kw):
    """(result, seconds) of one call, ended on the device."""
    import jax
    t = time.perf_counter()
    out = jax.block_until_ready(fn(*args, **kw))
    return out, time.perf_counter() - t


# ------------------------------------------------------------------ kernels
def phase_kernels(tpu, cpu) -> None:
    import jax
    import jax.numpy as jnp
    from repro.dsp.fir import NUM_TAPS, PrecodedBank, design_lowpass
    from repro.core.multipliers import MulSpec
    from repro.kernels import booth_precode, min_safe_shift
    from repro.kernels.bbm_matmul import bbm_matmul_scaled
    from repro.kernels.fir_kernel import fir_bbm_bank_precoded
    from repro.kernels.flash_attention import (_flash_amm_pallas,
                                               _flash_amm_xla,
                                               flash_amm_operands)
    from repro.kernels.ref import bbm_matmul_ref, fir_bank_ref

    rng = np.random.default_rng(SEED)
    mask = (1 << WL) - 1
    # the paper's filter, one bank per channel, quantized once on the host
    taps = PrecodedBank(design_lowpass(), MulSpec("bbm0", WL, VBL)).hq
    h = np.broadcast_to(taps & mask, (CHANNELS, NUM_TAPS)).astype(np.int32)
    x = rng.integers(0, 1 << WL, (CHANNELS, SAMPLES)).astype(np.int32)
    shift = min_safe_shift(NUM_TAPS, WL)
    x_t, h_t = jax.device_put((x, h), tpu)
    hmag, hneg = booth_precode(h_t, WL)
    ref_fn = jax.jit(fir_bank_ref,
                     static_argnames=("wl", "vbl", "kind", "shift"))
    for kind in (0, 1):
        with jax.default_device(cpu):
            ref = np.concatenate([
                np.asarray(ref_fn(jnp.asarray(x[c:c + 8]),
                                  jnp.asarray(h[c:c + 8]), wl=WL, vbl=VBL,
                                  kind=kind, shift=shift))
                for c in range(0, CHANNELS, 8)])
        for form in ("rows", "dot"):
            fn = lambda: fir_bbm_bank_precoded(
                x_t, hmag, hneg, wl=WL, vbl=VBL, kind=kind, shift=shift,
                form=form, interpret=False)
            _, first = _timed(fn)
            got, steady = _timed(fn)
            _bitwise(f"fir bbm{kind} {form}", got, ref)
            log("kernels", kernel=f"fir_{form}", mul=f"bbm{kind}",
                shape=[CHANNELS, SAMPLES, NUM_TAPS], bitwise=True,
                first_s=first, steady_s=steady)

    ref_fn = jax.jit(bbm_matmul_ref,
                     static_argnames=("wl", "vbl", "kind", "shift"))
    for k, n in MM_SHAPES:
        a = rng.integers(0, 1 << WL, (MM_ROWS, k)).astype(np.int32)
        w = rng.integers(0, 1 << WL, (k, n)).astype(np.int32)
        with jax.default_device(cpu):
            # one K chunk at this operating point, so the datapath's f32
            # result is the exact integer sum, scaled by 2^vbl
            ref = np.concatenate([
                np.asarray(ref_fn(jnp.asarray(a[r:r + 8]), jnp.asarray(w),
                                  wl=WL, vbl=VBL, kind=0, shift=VBL))
                for r in range(0, MM_ROWS, 8)]).astype(np.float32)
            ref = ref * np.float32(1 << VBL)
        a_t, w_t = jax.device_put((a, w), tpu)
        wmag, wneg = booth_precode(w_t, WL)
        fn = jax.jit(lambda a, m, g: bbm_matmul_scaled(a, m, g, wl=WL,
                                                       vbl=VBL, kind=0))
        _, first = _timed(fn, a_t, wmag, wneg)
        got, steady = _timed(fn, a_t, wmag, wneg)
        _bitwise(f"bbm_matmul_scaled {k}x{n}", got, ref)
        log("kernels", kernel="bbm_matmul_scaled", shape=[MM_ROWS, k, n],
            bitwise=True, first_s=first, steady_s=steady)

    # flash-amm: operands (codes, scales, K planes) quantized once on the
    # CPU.  Its output passes through exp and the online-softmax divides,
    # which the chip and the CPU round differently, so it is held to a
    # relative tolerance; its integer tile products are the dot form the
    # matmul check above holds bitwise.
    shape = (1, ATTN_HEADS, ATTN_SEQ, HEAD_DIM)
    q, k_, v = (rng.standard_normal(shape).astype(np.float32)
                for _ in range(3))
    static = dict(wl=WL, vbl=VBL, kind=0, causal=True)
    with jax.default_device(cpu):
        ops, bq, bk = flash_amm_operands(jnp.asarray(q), jnp.asarray(k_),
                                         jnp.asarray(v), wl=WL)
        ref = np.asarray(_flash_amm_xla(*ops, bq=bq, bk=bk,
                                        kv_len=ATTN_SEQ, **static))
    ops_t = jax.device_put(ops, tpu)
    fn = lambda: _flash_amm_pallas(*ops_t, bq=bq, bk=bk, kv_len=ATTN_SEQ,
                                   interpret=False, **static)
    _, first = _timed(fn)
    got, steady = _timed(fn)
    err = float(np.max(np.abs(np.asarray(got) - ref)) / np.max(np.abs(ref)))
    if not err <= FLASH_TOL:
        raise AssertionError(f"flash-amm: relative error {err} > "
                             f"{FLASH_TOL}")
    log("kernels", kernel="flash_amm", shape=list(shape), rel_err=err,
        tol=FLASH_TOL, first_s=first, steady_s=steady)


# --------------------------------------------------------- filterbank engine
def phase_filterbank(cpu) -> None:
    import jax
    from repro.core.multipliers import MulSpec
    from repro.dsp import design_lowpass
    from repro.serve import FilterbankEngine

    spec = MulSpec("bbm0", WL, VBL)
    h = design_lowpass()[None, :]
    rng = np.random.default_rng(SEED + 1)
    flushes = [[rng.standard_normal(FB_SAMPLES)
                for _ in range(FB_REQUESTS)] for _ in range(FB_FLUSHES)]

    def serve(engine):
        outs, times = [], []
        for signals in flushes:
            for s in signals:
                engine.submit(s)
            t = time.perf_counter()
            res = engine.flush()
            times.append(time.perf_counter() - t)
            outs.append([res[r] for r in sorted(res)])
        return outs, times

    chip = FilterbankEngine(h, spec, backend="pallas",
                            max_channels=FB_REQUESTS)
    got, times = serve(chip)
    with jax.default_device(cpu):
        want, _ = serve(FilterbankEngine(h, spec, backend="host",
                                         max_channels=FB_REQUESTS))
    for i, (g, w) in enumerate(zip(got, want)):
        _bitwise(f"filterbank flush {i}", np.stack(g), np.stack(w))
    if chip.failed:
        raise AssertionError(f"filterbank engine quarantined {chip.failed}")
    log("filterbank", requests=FB_REQUESTS * FB_FLUSHES,
        samples=FB_SAMPLES, bitwise=True, first_s=times[0],
        steady_s_per_flush=float(np.mean(times[1:])))


# -------------------------------------------------------------- LM serving
def phase_serving(tpu, cpu) -> None:
    import jax
    import jax.numpy as jnp
    from repro.configs import get_arch
    from repro.configs.base import AmmConfig
    from repro.launch.mesh import make_host_mesh
    from repro.launch.serve import plane_cache_for
    from repro.models import ModelRuntime, init_cache, lm_init
    from repro.serve.engine import Request, Scheduler, make_serve_fns

    base = get_arch("qwen2-0.5b")
    params = lm_init(base, jax.random.key(SEED))
    mesh = make_host_mesh(1, 1)
    rng = np.random.default_rng(SEED + 2)
    finite = []

    def checked(fn):
        def call(*args):
            logits, caches = fn(*args)
            finite.append(bool(jnp.all(jnp.isfinite(logits))))
            return logits, caches
        return call

    def server(apply_to):
        """A ``serve(prompts)`` over continuous batching, as the launcher
        builds it (``--amm bitexact --amm-attn [attn] --kv-codes
        --flash-attn --continuous``)."""
        cfg = dataclasses.replace(base, amm=AmmConfig(
            mode="bitexact", mul="bbm0", wl=WL, param=VBL,
            apply_to=apply_to))
        rt = ModelRuntime.build(cfg, use_pallas=True)
        planes = plane_cache_for(cfg, rt, params)
        prefill_j, decode_j = make_serve_fns(
            cfg, rt, mesh, batch=SLOTS, max_len=MAX_LEN, amm_planes=planes,
            kv_codes=True)

        def serve(prompts):
            sched = Scheduler(cfg, rt, params, SLOTS, MAX_LEN,
                              decode_fn=checked(decode_j),
                              prefill_fn=checked(prefill_j),
                              continuous=True, kv_codes=True)
            reqs = [Request(rid=i, prompt=p, max_new=MAX_NEW)
                    for i, p in enumerate(prompts)]
            for r in reqs:
                sched.submit(r)
            times = []
            while True:
                t = time.perf_counter()
                live = sched.step()
                times.append(time.perf_counter() - t)
                if not live:
                    break
            for r in reqs:
                if r.error or not r.done or len(r.out) != MAX_NEW:
                    raise AssertionError(
                        f"request {r.rid}: done={r.done} error={r.error} "
                        f"out={len(r.out)}")
            if not all(finite):
                raise AssertionError("non-finite logits while serving")
            return [r.out for r in reqs], times
        return serve, planes is not None

    # every matmul on the multiplier, at published widths and depth
    prompts = [rng.integers(0, base.vocab, n).tolist() for n in PROMPT_LENS]
    serve, cached = server("all")
    t0 = time.perf_counter()
    _, times = serve(prompts)
    first_s = time.perf_counter() - t0
    steady = times[len(prompts):-1]   # after the last admission's prefill
    log("serving", arch=base.name, layers=base.n_layers,
        d_model=base.d_model, amm="bitexact bbm0 wl16 vbl13 apply_to=all",
        kv_codes=True, plane_cache=cached, requests=len(prompts),
        prompt_lens=list(PROMPT_LENS), max_new=MAX_NEW,
        steps=len(times) - 1, first_run_s=first_s,
        steady_s_per_step=float(np.mean(steady)) if steady else None,
        peak_bytes_in_use=(tpu.memory_stats() or {}).get(
            "peak_bytes_in_use"))

    # the batched-equals-solo contract (docs/serving.md) covers
    # attention-side routing: MLP routing quantizes the whole decode batch
    # with one activation scale, so its streams may move with the batch
    prompts = [rng.integers(0, base.vocab, n).tolist() for n in CONFORM_LENS]
    serve, _ = server("attn")
    batched, _ = serve(prompts)
    for i, p in enumerate(prompts):
        solo = serve([p])[0][0]
        if batched[i] != solo:
            raise AssertionError(f"request {i}: batched stream "
                                 f"{batched[i]} != solo stream {solo}")
    log("serving_conformance", amm="bitexact bbm0 wl16 vbl13 apply_to=attn",
        kv_codes=True, requests=len(prompts), prompt_lens=list(CONFORM_LENS),
        solo_equal=True)

    # amm off: the chip's prefill logits against the same forward on CPU
    cfg_off = dataclasses.replace(base, amm=AmmConfig(mode="off"))
    rt_off = ModelRuntime.build(cfg_off)
    tokens = np.asarray([prompts[0]], np.int32)

    def prefill_logits(device, p):
        fn, _ = make_serve_fns(cfg_off, rt_off,
                               make_host_mesh(1, 1, devices=[device]),
                               batch=1, max_len=MAX_LEN)
        with jax.default_device(device):
            logits, _ = fn(p, jnp.asarray(tokens),
                           init_cache(cfg_off, 1, MAX_LEN))
        return np.asarray(logits, np.float64)

    got = prefill_logits(tpu, params)
    want = prefill_logits(cpu, jax.device_put(params, cpu))
    rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    if not (np.all(np.isfinite(got)) and rel <= LOGIT_TOL):
        raise AssertionError(f"amm-off prefill logits: relative L2 {rel} "
                             f"> {LOGIT_TOL}")
    log("serving_reference", amm="off", prompt_len=len(prompts[0]),
        rel_l2=rel, tol=LOGIT_TOL,
        max_abs=float(np.max(np.abs(got - want))))


# -------------------------------------------------------------- four chips
def phase_train(devices) -> None:
    import jax
    from repro.configs import get_arch
    from repro.data.pipeline import DataConfig, batches
    from repro.launch.mesh import make_host_mesh
    from repro.models import ModelRuntime
    from repro.train.optimizer import OptConfig
    from repro.train.trainstep import (TrainConfig, init_train_state,
                                       make_train_step)

    cfg = dataclasses.replace(get_arch("qwen2-0.5b"), n_layers=TRAIN_LAYERS)
    rt = ModelRuntime.build(cfg)
    tc = TrainConfig(opt=OptConfig(total_steps=TRAIN_STEPS))
    dc = DataConfig(vocab=cfg.vocab, seq_len=TRAIN_SEQ,
                    global_batch=TRAIN_BATCH)
    data = [(t, l) for (t, l, _), _ in zip(batches(dc, 0),
                                          range(TRAIN_STEPS))]

    def run(mesh):
        step = make_train_step(cfg, rt, tc, mesh, global_batch=TRAIN_BATCH)
        params, opt = init_train_state(cfg, tc, mesh, jax.random.key(SEED))
        leaves = jax.tree.leaves(params)
        spans = {s.device for x in leaves for s in x.addressable_shards}
        split = sum(x.addressable_shards[0].data.shape != x.shape
                    for x in leaves)
        losses, times = [], []
        for i, (t, l) in enumerate(data):
            t0 = time.perf_counter()
            params, opt, m = step(params, opt, t, l, jax.random.key(i))
            losses.append(float(m["loss"]))
            times.append(time.perf_counter() - t0)
        return losses, spans, split, times

    losses4, spans, split, times = run(make_host_mesh(2, 2,
                                                      devices=devices[:4]))
    if len(spans) != 4 or not split:
        raise AssertionError(f"params span {len(spans)} devices, "
                             f"{split} leaves split")
    losses1, _, _, _ = run(make_host_mesh(1, 1, devices=devices[:1]))
    rel = max(abs(a - b) / abs(b) for a, b in zip(losses4, losses1))
    if not (np.all(np.isfinite(losses4)) and rel <= LOSS_TOL):
        raise AssertionError(f"losses {losses4} on (2, 2) vs {losses1} on "
                             f"one chip: relative {rel} > {LOSS_TOL}")
    log("train", arch=cfg.name, layers=cfg.n_layers, mesh=[2, 2],
        batch=TRAIN_BATCH, seq=TRAIN_SEQ, losses=losses4,
        losses_one_chip=losses1, rel=rel, tol=LOSS_TOL,
        param_devices=len(spans), split_leaves=split,
        first_step_s=times[0], steady_s_per_step=float(np.mean(times[1:])))


def phase_sharded_filterbank(devices, cpu) -> None:
    import jax
    import jax.numpy as jnp
    from repro.dsp.fir import NUM_TAPS
    from repro.kernels import min_safe_shift
    from repro.kernels.ref import fir_bank_ref
    from repro.launch.mesh import make_mesh
    from repro.parallel import sharded_filterbank

    rng = np.random.default_rng(SEED + 3)
    x = rng.integers(0, 1 << WL, (CHANNELS, SAMPLES)).astype(np.int32)
    h = rng.integers(0, 1 << WL, (CHANNELS, NUM_TAPS)).astype(np.int32)
    shift = min_safe_shift(NUM_TAPS, WL)
    mesh = make_mesh((4,), ("data",), devices=devices[:4])
    got, first = _timed(sharded_filterbank, jnp.asarray(x), jnp.asarray(h),
                        mesh, wl=WL, vbl=VBL, kind=1, shift=shift)
    ref_fn = jax.jit(fir_bank_ref,
                     static_argnames=("wl", "vbl", "kind", "shift"))
    with jax.default_device(cpu):
        ref = np.concatenate([
            np.asarray(ref_fn(jnp.asarray(x[c:c + 8]),
                              jnp.asarray(h[c:c + 8]), wl=WL, vbl=VBL,
                              kind=1, shift=shift))
            for c in range(0, CHANNELS, 8)])
    _bitwise("sharded_filterbank", got, ref)
    log("sharded_filterbank", shards=4, shape=[CHANNELS, SAMPLES, NUM_TAPS],
        mul="bbm1", bitwise=True, first_s=first)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=[1, 4], default=1)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print("chip_smoke: run from a checkout of this repository",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s), found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    tpu = devices[0]
    device = {"platform": tpu.platform, "kind": tpu.device_kind,
              "count": len(devices)}
    log("device", **device)

    from repro.launch import use_compile_cache
    from repro.models.attention import FlashFallbackWarning
    warnings.simplefilter("error", FlashFallbackWarning)
    log("compile_cache", dir=use_compile_cache())
    cpu = jax.devices("cpu")[0]

    t0 = time.perf_counter()
    if args.chips == 4:
        phase_train(devices)
        phase_sharded_filterbank(devices, cpu)
    else:
        phase_kernels(tpu, cpu)
        phase_filterbank(cpu)
        phase_serving(tpu, cpu)
    log("done", seconds=time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
