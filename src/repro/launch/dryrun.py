import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
# The two lines above MUST run before any other import pulls in jax: the CPU
# backend locks its device count at first initialization.

"""Multi-pod dry-run: lower + compile every (architecture x shape x mesh)
cell with ShapeDtypeStruct inputs (no allocation), and record:

  * memory_analysis()  — proves the step fits per-device HBM,
  * cost_analysis()    — HLO FLOPs / bytes for the roofline,
  * collective bytes   — parsed from the post-SPMD optimized HLO.

Usage:
  python -m repro.launch.dryrun --arch llama3.2-3b --shape train_4k
  python -m repro.launch.dryrun --all            # every assigned cell
Results append incrementally to --out (default benchmarks/dryrun_results.json).
"""
import argparse
import json
import re
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import ARCH_NAMES, SHAPES, get_arch
from ..configs.base import ArchConfig, ShapeConfig
from ..models import ModelRuntime, init_cache, lm_logical_axes, lm_table
from ..models.common import Spec
from .mesh import make_production_mesh

DEFAULT_OUT = "benchmarks/dryrun_results.json"


# ------------------------------------------------------------- input specs
def param_structs(cfg: ArchConfig, dtype=jnp.bfloat16):
    """ShapeDtypeStruct tree for the parameters (weak-type-correct)."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, dtype),
        lm_table(cfg), is_leaf=lambda x: isinstance(x, Spec))


def input_specs(cfg: ArchConfig, shape: ShapeConfig) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    b, s = shape.global_batch, shape.seq_len
    out: Dict[str, Any] = {}
    if shape.kind == "train":
        out["tokens"] = jax.ShapeDtypeStruct((b, s), jnp.int32)
        out["labels"] = jax.ShapeDtypeStruct((b, s), jnp.int32)
    elif shape.kind == "prefill":
        out["tokens"] = jax.ShapeDtypeStruct((b, s), jnp.int32)
    else:  # decode: one new token against a cache of length s
        out["tokens"] = jax.ShapeDtypeStruct((b, 1), jnp.int32)
    if cfg.is_encoder_decoder:
        out["encoder_embeds"] = jax.ShapeDtypeStruct(
            (b, cfg.encoder_len, cfg.d_model), jnp.bfloat16)
    if shape.is_decode or shape.kind == "prefill":
        out["caches"] = jax.eval_shape(
            lambda: init_cache(cfg, b, s))
    return out


# --------------------------------------------------------- HLO collective scan
_COLL_RE = re.compile(
    r"(\w[\w.\-]*)\s*=\s*([a-z0-9]+)\[([\d,]*)\][^ ]*\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)")
_GROUP_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
_GROUP_V2 = re.compile(r"replica_groups=\[(\d+),(\d+)\]")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
                "s64": 8, "u64": 8, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1, "c64": 8}


def collective_bytes(hlo_text: str) -> Dict[str, Any]:
    """Estimated per-device wire bytes per collective family (ring costs)."""
    totals = {"all-gather": 0.0, "all-reduce": 0.0, "reduce-scatter": 0.0,
              "all-to-all": 0.0, "collective-permute": 0.0}
    counts = dict.fromkeys(totals, 0)
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        _, dt, dims, op = m.groups()
        nbytes = _DTYPE_BYTES.get(dt, 4) * int(
            np.prod([int(x) for x in dims.split(",") if x] or [1]))
        n = 1
        g = _GROUP_RE.search(line)
        if g:
            n = max(len(g.group(1).split(",")), 1)
        else:
            g2 = _GROUP_V2.search(line)
            if g2:
                n = int(g2.group(2))
        if n <= 1:
            continue
        ring = (n - 1) / n
        if op == "all-gather":
            wire = nbytes * ring                     # result is full size
        elif op == "reduce-scatter":
            wire = nbytes * (n - 1)                  # result is 1/n input
        elif op == "all-reduce":
            wire = 2 * nbytes * ring
        elif op == "all-to-all":
            wire = nbytes * ring
        else:                                        # collective-permute
            wire = nbytes
        totals[op] += wire
        counts[op] += 1
    return {"bytes": totals, "counts": counts,
            "total": sum(totals.values())}


# ----------------------------------------------------------------- lowering
def lower_cell(arch: str, shape_name: str, *, multi_pod: bool,
               microbatches: int = 4, remat: bool = True,
               opt_state_dtype: str = "float32",
               attn_remat: bool = False, shard_heads: bool = False,
               causal_skip: bool = False, moe_gather: bool = False,
               p_bf16: bool = False,
               extra_rules: Optional[dict] = None) -> Dict[str, Any]:
    cfg = get_arch(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = int(np.prod(list(mesh.shape.values())))
    rt = ModelRuntime.build(cfg, remat=remat, attn_remat=attn_remat,
                            shard_heads=shard_heads, causal_skip=causal_skip,
                            moe_gather_weights=moe_gather,
                            attn_p_bf16=p_bf16)
    specs = input_specs(cfg, shape)
    p_struct = param_structs(cfg)

    t0 = time.time()
    # lower under the mesh context so P-based sharding constraints resolve
    mesh_ctx = mesh
    if shape.kind == "train":
        from ..train.optimizer import OptConfig
        from ..train.trainstep import TrainConfig, make_train_step
        from ..train.optimizer import init_opt
        tc = TrainConfig(
            microbatches=microbatches,
            opt=OptConfig(state_dtype=getattr(jnp, opt_state_dtype)))
        step = make_train_step(cfg, rt, tc, mesh,
                               with_encoder=cfg.is_encoder_decoder,
                               global_batch=shape.global_batch)
        opt_struct = jax.eval_shape(lambda p: init_opt(p, tc.opt), p_struct)
        key_struct = jax.eval_shape(lambda: jax.random.key(0))
        args = [p_struct, opt_struct, specs["tokens"], specs["labels"],
                key_struct]
        if cfg.is_encoder_decoder:
            args.append(specs["encoder_embeds"])
        with mesh_ctx:
            lowered = step.lower(*args)
    else:
        from ..serve.engine import make_serve_fns
        b = shape.global_batch
        prefill_j, decode_j = make_serve_fns(cfg, rt, mesh, batch=b,
                                             max_len=shape.seq_len)
        enc = ((specs["encoder_embeds"],) if cfg.is_encoder_decoder else ())
        with mesh_ctx:
            if shape.kind == "prefill":
                lowered = prefill_j.lower(p_struct, specs["tokens"],
                                          specs["caches"], *enc)
            else:
                pos_struct = jax.ShapeDtypeStruct((), jnp.int32)
                lowered = decode_j.lower(p_struct, specs["tokens"],
                                         specs["caches"], pos_struct, *enc)
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    mem_info = {
        "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
        "output_bytes": getattr(mem, "output_size_in_bytes", None),
        "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
        "code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
    }
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    cost_info = {k: float(v) for k, v in cost.items()
                 if isinstance(v, (int, float)) and k in
                 ("flops", "bytes accessed", "transcendentals",
                  "utilization operand 0 {}", "bytes accessed output {}")}
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)

    # dump the optimized HLO for the trip-count-aware roofline analysis
    import gzip
    hlo_dir = os.path.join(os.path.dirname(DEFAULT_OUT) or ".", "hlo")
    os.makedirs(hlo_dir, exist_ok=True)
    mesh_tag = "2x16x16" if multi_pod else "16x16"
    vtag = ""
    if attn_remat or shard_heads or causal_skip or moe_gather or p_bf16 \
            or opt_state_dtype != "float32" or microbatches != 4:
        vtag = f"_v-ar{int(attn_remat)}-sh{int(shard_heads)}" \
               f"-cs{int(causal_skip)}-mg{int(moe_gather)}-pb{int(p_bf16)}" \
               f"-od{opt_state_dtype}-mb{microbatches}"
    hlo_path = os.path.join(
        hlo_dir, f"{arch}_{shape_name}_{mesh_tag}{vtag}.hlo.gz")
    with gzip.open(hlo_path, "wt") as f:
        f.write(hlo)

    return {
        "hlo_path": hlo_path,
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": n_dev,
        "kind": shape.kind,
        "microbatches": microbatches if shape.kind == "train" else None,
        "remat": remat,
        "attn_remat": attn_remat,
        "shard_heads": shard_heads,
        "opt_state_dtype": opt_state_dtype if shape.kind == "train" else None,
        "t_lower_s": round(t_lower, 2),
        "t_compile_s": round(t_compile, 2),
        "memory": mem_info,
        "cost": cost_info,
        "collectives": coll,
        "ok": True,
    }


def append_result(res: Dict[str, Any], path: str):
    data = []
    if os.path.exists(path):
        with open(path) as f:
            data = json.load(f)
    # replace a previous run of the same cell
    keyf = lambda r: (r.get("arch"), r.get("shape"), r.get("mesh"),
                      r.get("variant", ""))
    data = [r for r in data if keyf(r) != keyf(res)]
    data.append(res)
    with open(path, "w") as f:
        json.dump(data, f, indent=1)


def eligible(arch: str, shape_name: str) -> bool:
    cfg = get_arch(arch)
    if shape_name == "long_500k" and not cfg.sub_quadratic:
        return False        # full-attention archs skip 500k: quadratic
                            # score memory is out of budget at that length
    return True


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES)
    ap.add_argument("--shape", choices=sorted(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--microbatches", type=int, default=4)
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--attn-remat", action="store_true")
    ap.add_argument("--shard-heads", action="store_true")
    ap.add_argument("--causal-skip", action="store_true")
    ap.add_argument("--moe-gather", action="store_true")
    ap.add_argument("--p-bf16", action="store_true")
    ap.add_argument("--opt-dtype", default="float32")
    ap.add_argument("--variant", default="",
                    help="label for perf-iteration variants")
    ap.add_argument("--out", default=DEFAULT_OUT)
    args = ap.parse_args()

    cells = []
    if args.all:
        for a in ARCH_NAMES:
            for sname in SHAPES:
                if eligible(a, sname):
                    cells.append((a, sname))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    for arch, sname in cells:
        for mp in meshes:
            tag = f"{arch} x {sname} x {'2x16x16' if mp else '16x16'}"
            try:
                res = lower_cell(arch, sname, multi_pod=mp,
                                 microbatches=args.microbatches,
                                 remat=not args.no_remat,
                                 attn_remat=args.attn_remat,
                                 shard_heads=args.shard_heads,
                                 causal_skip=args.causal_skip,
                                 moe_gather=args.moe_gather,
                                 p_bf16=args.p_bf16,
                                 opt_state_dtype=args.opt_dtype)
                if args.variant:
                    res["variant"] = args.variant
                append_result(res, args.out)
                print(f"[dryrun] OK  {tag}  compile={res['t_compile_s']}s "
                      f"flops={res['cost'].get('flops', 0):.3e} "
                      f"coll={res['collectives']['total']:.3e}B")
            except Exception as e:  # noqa: BLE001
                res = {"arch": arch, "shape": sname,
                       "mesh": "2x16x16" if mp else "16x16",
                       "ok": False, "error": f"{type(e).__name__}: {e}",
                       "trace": traceback.format_exc()[-2000:]}
                if args.variant:
                    res["variant"] = args.variant
                append_result(res, args.out)
                print(f"[dryrun] FAIL {tag}: {e}")


if __name__ == "__main__":
    main()
