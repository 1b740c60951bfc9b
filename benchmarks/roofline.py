"""Roofline analysis over the dry-run results (deliverable g).

Reads benchmarks/dryrun_results.json (written by repro.launch.dryrun) and
derives, per (arch x shape x mesh):

    compute term    = HLO_FLOPs / (chips * peak_FLOP/s)
    memory term     = HLO_bytes / (chips * HBM_bw)
    collective term = collective_bytes / (chips * link_bw)

plus MODEL_FLOPS = 6*N*D (dense) or 6*N_active*D (MoE) and the useful-
compute ratio MODEL_FLOPS / HLO_FLOPs.  cost_analysis() numbers from the
CPU-backend SPMD compile are per-partition; terms are per-chip seconds.
"""
from __future__ import annotations

import json
import os
from typing import Dict, List, Optional

from repro.configs import SHAPES, get_arch
from repro.launch.mesh import PRODUCTION_KIND, peaks

HW = peaks(PRODUCTION_KIND)   # the dry-run compiles for the production mesh

RESULTS = os.path.join(os.path.dirname(__file__), "dryrun_results.json")


# --------------------------------------------------------- parameter counts
def param_count(arch: str) -> Dict[str, float]:
    """(total, active-per-token) parameter counts from the config."""
    c = get_arch(arch)
    d, v = c.d_model, c.vocab
    hd = c.resolved_head_dim
    emb = v * d * (1 if c.tie_embeddings else 2)
    per_layer_attn = 0.0
    if c.use_mla:
        per_layer_attn = (d * c.q_lora_rank + c.q_lora_rank * c.n_heads
                          * (c.qk_nope_dim + c.qk_rope_dim)
                          + d * (c.kv_lora_rank + c.qk_rope_dim)
                          + c.kv_lora_rank * c.n_heads
                          * (c.qk_nope_dim + c.v_head_dim)
                          + c.n_heads * c.v_head_dim * d)
    elif c.n_heads:
        per_layer_attn = d * hd * (c.n_heads * 2 + c.n_kv_heads * 2)
    mlp_dense = 3 * d * c.d_ff
    total = emb
    active = emb
    if c.family == "moe":
        moe = 3 * d * c.moe_d_ff
        shared = moe * c.n_shared_experts
        n_moe = c.n_layers - c.first_k_dense
        total += (c.first_k_dense * (per_layer_attn + mlp_dense)
                  + n_moe * (per_layer_attn + c.n_experts * moe + shared
                             + d * c.n_experts))
        active += (c.first_k_dense * (per_layer_attn + mlp_dense)
                   + n_moe * (per_layer_attn + c.top_k * moe + shared))
    elif c.family == "ssm":
        di = c.d_inner
        per = (d * (2 * di + 2 * c.ssm_groups * c.ssm_state + c.ssm_heads)
               + di * d)
        total += c.n_layers * per
        active = total
    elif c.family == "hybrid":
        di = c.d_inner
        per = (d * (2 * di + 2 * c.ssm_groups * c.ssm_state + c.ssm_heads)
               + di * d)
        shared_blk = per_layer_attn + mlp_dense
        total += c.n_layers * per + shared_blk
        active = total
    else:
        n_dec = c.n_layers
        total += n_dec * (per_layer_attn + mlp_dense)
        if c.is_encoder_decoder:
            total += (c.n_encoder_layers * (per_layer_attn + mlp_dense)
                      + n_dec * per_layer_attn)   # cross attention
        active = total
    if c.family != "moe":
        active = total
    return {"total": total, "active": active}


def model_flops(arch: str, shape_name: str) -> float:
    """6*N(active)*tokens for the step this cell lowers."""
    sh = SHAPES[shape_name]
    n = param_count(arch)["active"]
    if sh.kind == "train":
        tokens = sh.global_batch * sh.seq_len
        return 6.0 * n * tokens
    if sh.kind == "prefill":
        tokens = sh.global_batch * sh.seq_len
        return 2.0 * n * tokens          # forward only
    return 2.0 * n * sh.global_batch     # decode: 1 token per row


# ----------------------------------------------------------------- analysis
def analyze(results_path: str = RESULTS,
            mesh: Optional[str] = "16x16") -> List[Dict]:
    with open(results_path) as f:
        data = json.load(f)
    rows = []
    for r in data:
        if not r.get("ok"):
            rows.append({"arch": r["arch"], "shape": r["shape"],
                         "mesh": r.get("mesh"), "ok": False,
                         "error": r.get("error", "?")[:120]})
            continue
        if mesh and r["mesh"] != mesh:
            continue
        chips = r["n_devices"]
        # trip-count-aware costs from the dumped HLO (hlo_analysis.py);
        # XLA's cost_analysis() visits scan bodies once and is only kept
        # as a fallback + diagnostic.
        hlo_path = r.get("hlo_path")
        if hlo_path and os.path.exists(hlo_path):
            from benchmarks.hlo_analysis import analyze_file
            corrected = analyze_file(hlo_path)
            flops = corrected["flops"]
            bytes_acc = corrected["bytes"]
            coll = corrected["collective_bytes"]
        else:
            flops = r["cost"].get("flops", 0.0)
            bytes_acc = r["cost"].get("bytes accessed", 0.0)
            coll = r["collectives"]["total"]
        # cost_analysis on the SPMD-partitioned module is per-partition
        t_compute = flops / HW["peak_flops_bf16"]
        t_memory = bytes_acc / HW["hbm_bw"]
        t_coll = coll / HW["ici_bw"]
        terms = {"compute": t_compute, "memory": t_memory,
                 "collective": t_coll}
        dominant = max(terms, key=terms.get)
        mf = model_flops(r["arch"], r["shape"])
        mf_per_chip = mf / chips
        useful = mf_per_chip / flops if flops else 0.0
        bound = max(terms.values())
        # achievable step time = dominant term (perfect overlap);
        # roofline fraction = useful compute time / bound
        t_useful = mf_per_chip / HW["peak_flops_bf16"]
        frac = t_useful / bound if bound > 0 else 0.0
        rows.append({
            "arch": r["arch"], "shape": r["shape"], "mesh": r["mesh"],
            "variant": r.get("variant", ""), "ok": True,
            "t_compute_s": t_compute, "t_memory_s": t_memory,
            "t_collective_s": t_coll, "dominant": dominant,
            "model_flops": mf, "hlo_flops_per_chip": flops,
            "useful_ratio": useful, "roofline_frac": frac,
        })
    return rows


def render_table(rows: List[Dict]) -> str:
    hdr = (f"{'arch':18s} {'shape':12s} {'mesh':8s} {'compute_s':>10s} "
           f"{'memory_s':>10s} {'collect_s':>10s} {'dom':>9s} "
           f"{'useful':>7s} {'roofline':>9s}")
    lines = [hdr, "-" * len(hdr)]
    for r in sorted(rows, key=lambda x: (x["arch"], x["shape"])):
        if not r.get("ok"):
            lines.append(f"{r['arch']:18s} {r['shape']:12s} FAILED: "
                         f"{r.get('error', '')}")
            continue
        lines.append(
            f"{r['arch']:18s} {r['shape']:12s} {r['mesh']:8s} "
            f"{r['t_compute_s']:10.3e} {r['t_memory_s']:10.3e} "
            f"{r['t_collective_s']:10.3e} {r['dominant']:>9s} "
            f"{r['useful_ratio']:7.2f} {r['roofline_frac']:9.3f}")
    return "\n".join(lines)


if __name__ == "__main__":
    rows = analyze()
    print(render_table(rows))
