"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b \
        --steps 50 --batch 8 --seq 256 --reduced --amm noise --vbl 13

On this CPU container use --reduced (tiny same-family config); on a real
fleet drop it and point --mesh-data/--mesh-model at the slice.  The loop is
the fault-tolerant one (checkpoint/restart, straggler monitor).
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import jax.numpy as jnp

from ..configs import ARCH_NAMES, get_arch, reduced
from ..configs.base import AmmConfig
from ..data.pipeline import DataConfig, batches
from ..models import ModelRuntime
from ..parallel.logical import tree_shardings
from ..train.loop import LoopConfig, train_loop
from ..train.optimizer import OptConfig
from ..train.trainstep import TrainConfig, make_train_step, init_train_state
from . import (add_amm_attn_arg, resolve_amm_apply_to, use_compile_cache,
               validate_amm_args)
from .mesh import make_host_mesh


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES, default="qwen2-0.5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--mesh-data", type=int, default=1)
    ap.add_argument("--mesh-model", type=int, default=1)
    ap.add_argument("--amm", choices=["off", "noise", "bitexact"],
                    default="off")
    ap.add_argument("--mul", default="bbm0")
    ap.add_argument("--wl", type=int, default=16)
    ap.add_argument("--vbl", type=int, default=13)
    ap.add_argument("--amm-pallas", action="store_true",
                    help="mode=noise: route through the fused Pallas "
                         "quant_matmul kernel (TPU fast path; interpreted "
                         "on CPU).  mode=bitexact needs no flag — it "
                         "always lowers to the dot-form contractions.")
    ap.add_argument("--flash-attn", action="store_true",
                    help="route attention through the flash lowering "
                         "(exact-flash, or flash-amm when --amm-attn makes "
                         "attention amm-active); gradients take the "
                         "chunked path's straight-through rule either way")
    add_amm_attn_arg(ap)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=20)
    args = ap.parse_args(argv)
    apply_to = resolve_amm_apply_to(ap, args)
    validate_amm_args(ap, args)
    use_compile_cache()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    cfg = dataclasses.replace(
        cfg, amm=AmmConfig(mode=args.amm, mul=args.mul, wl=args.wl,
                           param=args.vbl, use_pallas=args.amm_pallas,
                           apply_to=apply_to))
    rt = ModelRuntime.build(cfg, use_pallas=args.flash_attn)
    mesh = make_host_mesh(args.mesh_data, args.mesh_model)
    tc = TrainConfig(microbatches=args.microbatches,
                     opt=OptConfig(lr=args.lr, total_steps=args.steps))
    step_fn = make_train_step(cfg, rt, tc, mesh, global_batch=args.batch,
                              with_encoder=cfg.is_encoder_decoder)
    params, opt = init_train_state(cfg, tc, mesh, jax.random.key(0))

    dc = DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                    global_batch=args.batch)
    lc = LoopConfig(total_steps=args.steps, ckpt_every=args.ckpt_every,
                    ckpt_dir=args.ckpt_dir)
    if cfg.is_encoder_decoder:
        enc = jnp.zeros((args.batch, cfg.encoder_len, cfg.d_model),
                        jnp.float32)
        raw_step = step_fn
        step_fn = lambda p, o, t, l, r: raw_step(p, o, t, l, r, enc)

    def data_iter(start):
        for toks, labels, step in batches(dc, start):
            yield jnp.asarray(toks), jnp.asarray(labels), step

    params, opt, hist = train_loop(
        step_fn, params, opt, data_iter, lc, rng=jax.random.key(42))
    print(f"[train] done: {len(hist)} steps, "
          f"final loss {hist[-1]['loss']:.4f}, "
          f"stragglers flagged: {sum(h['straggler'] for h in hist)}")
    return hist


if __name__ == "__main__":
    main()
