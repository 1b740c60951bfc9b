"""Serving launcher: batched decoding with the slot scheduler.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --reduced \
        --requests 6 --max-new 16 --amm bitexact --vbl 13

--amm bitexact serves through the true Broken-Booth datapath (dot-form
lowering); every approximated weight's digit planes are precoded once into
the jitted serve fns when they fit the device (``plane_cache_for``), so the
per-step cost is the contraction, not the decode.  --amm-attn widens the
routing to the attention score/value products (``--amm-attn`` alone =
apply_to="all", ``--amm-attn attn`` = attention only); those are
activation x activation, so they quantize per step — there are no weight
planes to cache for them.

--continuous switches the Scheduler to continuous batching: requests are
admitted into free slots every step (prefill on a batch-1 slot slice) and
evicted the step they finish, so a long prompt never stalls resident
decodes.  --kv-codes stores the KV cache as wl-bit int codes + per-block
f32 scales (docs/serving.md); it requires --amm bitexact with a
Booth-family --mul and --amm-attn (``validate_serve_flags``).
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np

from ..configs import ARCH_NAMES, get_arch, reduced
from ..configs.base import AmmConfig
from ..models import ModelRuntime, lm_init
from ..serve.engine import Request, Scheduler, make_serve_fns
from . import (add_amm_attn_arg, resolve_amm_apply_to, use_compile_cache,
               validate_amm_args, validate_serve_flags)
from .mesh import make_host_mesh


def _tree_bytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def plane_cache_for(cfg, rt: ModelRuntime, params, bytes_limit=None):
    """The digit-plane cache to bake into the serve fns, or None.

    With the cache, the bitexact datapath's weight decode happens once and
    every token after pays contractions only; without it each call decodes
    inline, bit-identically.  The cache's bytes come from the param shapes
    (``jax.eval_shape``) before anything is built: at wl=16 it is 64 B per
    MLP weight, about 20 GB at qwen2-0.5b's published widths.  It is built
    only when it and the params fit in half of ``bytes_limit`` (default:
    the first device's ``bytes_limit``; no limit where the backend reports
    none), leaving the rest for the KV cache and temporaries.  Prints
    which path was taken.
    """
    shapes = jax.eval_shape(lambda p: rt.build_planes(cfg, p), params)
    if shapes is None:
        return None
    need = _tree_bytes(shapes)
    if bytes_limit is None:
        stats = jax.devices()[0].memory_stats() or {}
        bytes_limit = stats.get("bytes_limit")
    if bytes_limit is not None and \
            need + _tree_bytes(params) > bytes_limit // 2:
        print(f"[serve] plane cache {need} B does not fit {bytes_limit} B: "
              f"serving uncached (weights decoded inline per call)")
        return None
    print(f"[serve] plane cache {need} B: serving cached")
    return rt.build_planes(cfg, params)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES, default="qwen2-0.5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--amm", choices=["off", "noise", "bitexact"],
                    default="off")
    ap.add_argument("--mul", default="bbm0")
    ap.add_argument("--wl", type=int, default=16)
    ap.add_argument("--vbl", type=int, default=13)
    ap.add_argument("--amm-pallas", action="store_true",
                    help="mode=noise: fused Pallas quant_matmul kernel")
    ap.add_argument("--flash-attn", action="store_true",
                    help="route prefill attention through the flash "
                         "lowering (exact-flash, or flash-amm when "
                         "--amm-attn makes attention amm-active); decode "
                         "keeps the cache path")
    ap.add_argument("--continuous", action="store_true",
                    help="continuous batching: per-step admission into "
                         "free slots, per-request eviction, prefill on "
                         "batch-1 slot slices")
    ap.add_argument("--kv-codes", action="store_true",
                    help="store the KV cache as wl-bit int codes + "
                         "per-block f32 scales; needs --amm bitexact with "
                         "a Booth-family --mul and --amm-attn")
    add_amm_attn_arg(ap)
    args = ap.parse_args(argv)
    apply_to = resolve_amm_apply_to(ap, args)
    validate_amm_args(ap, args)
    validate_serve_flags(ap, args)
    use_compile_cache()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    cfg = dataclasses.replace(
        cfg, amm=AmmConfig(mode=args.amm, mul=args.mul, wl=args.wl,
                           param=args.vbl, use_pallas=args.amm_pallas,
                           apply_to=apply_to))
    rt = ModelRuntime.build(cfg, use_pallas=args.flash_attn)
    params = lm_init(cfg, jax.random.key(0))
    mesh = make_host_mesh(1, 1)
    planes = plane_cache_for(cfg, rt, params)
    prefill_j, decode_j = make_serve_fns(cfg, rt, mesh, batch=args.slots,
                                         max_len=args.max_len,
                                         amm_planes=planes,
                                         kv_codes=args.kv_codes)
    sched = Scheduler(cfg, rt, params, args.slots, args.max_len,
                      decode_fn=decode_j,
                      prefill_fn=prefill_j if args.continuous else None,
                      continuous=args.continuous, kv_codes=args.kv_codes)

    rng = np.random.default_rng(0)
    for rid in range(args.requests):
        prompt = rng.integers(0, cfg.vocab, rng.integers(4, 12)).tolist()
        sched.submit(Request(rid=rid, prompt=prompt, max_new=args.max_new))

    t0 = time.perf_counter()
    steps = tokens = 0
    while sched.step():
        steps += 1
    dt = time.perf_counter() - t0
    print(f"[serve] {args.requests} requests in {steps} decode steps, "
          f"{dt:.2f}s")
    return steps


if __name__ == "__main__":
    main()
