"""Dry-run machinery + roofline analysis units (no 512-device compile here;
the full sweep runs via `python -m repro.launch.dryrun --all`)."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from benchmarks.hlo_analysis import (analyze_hlo, split_computations,
                                     trip_count)
from benchmarks.roofline import model_flops, param_count


def test_param_counts_sane():
    """Headline parameter counts should land near the model names."""
    targets = {
        "deepseek-v3-671b": (600e9, 750e9),
        "grok-1-314b": (280e9, 360e9),
        "qwen1.5-110b": (95e9, 125e9),
        "yi-34b": (30e9, 40e9),
        "llama3.2-3b": (2.5e9, 4.5e9),
        "qwen2-0.5b": (0.3e9, 0.7e9),
        "chameleon-34b": (30e9, 40e9),
        "mamba2-370m": (0.25e9, 0.5e9),
        "zamba2-2.7b": (2.0e9, 3.5e9),
        "whisper-base": (0.05e9, 0.12e9),
    }
    for arch, (lo, hi) in targets.items():
        n = param_count(arch)["total"]
        assert lo <= n <= hi, f"{arch}: {n / 1e9:.2f}B not in [{lo / 1e9}, {hi / 1e9}]"


def test_moe_active_params_much_smaller():
    pc = param_count("deepseek-v3-671b")
    assert pc["active"] < 0.12 * pc["total"]      # ~37B of 671B


def test_model_flops_scaling():
    f_train = model_flops("yi-34b", "train_4k")
    f_prefill = model_flops("yi-34b", "prefill_32k")
    f_decode = model_flops("yi-34b", "decode_32k")
    assert f_train > f_prefill > f_decode
    # train: 6ND with 1M tokens
    n = param_count("yi-34b")["active"]
    assert f_train == pytest.approx(6 * n * 4096 * 256)


SYNTH_HLO = """
HloModule test, is_scheduled=true

%body (arg: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %gte0 = s32[] get-tuple-element(%arg), index=0
  %gte1 = f32[8,8]{1,0} get-tuple-element(%arg), index=1
  %d = f32[8,8]{1,0} dot(%gte1, %gte1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %one = s32[] constant(1)
  %next = s32[] add(%gte0, %one)
  ROOT %t = (s32[], f32[8,8]) tuple(%next, %d)
}

%cond (arg2: (s32[], f32[8,8])) -> pred[] {
  %gte = s32[] get-tuple-element(%arg2), index=0
  %lim = s32[] constant(10)
  ROOT %cmp = pred[] compare(%gte, %lim), direction=LT
}

ENTRY %main (x: f32[8,8]) -> f32[8,8] {
  %zero = s32[] constant(0)
  %tup = (s32[], f32[8,8]) tuple(%zero, %x)
  %w = (s32[], f32[8,8]) while(%tup), condition=%cond, body=%body
  ROOT %out = f32[8,8]{1,0} get-tuple-element(%w), index=1
}
"""


def test_analyze_synthetic_while():
    res = analyze_hlo(SYNTH_HLO)
    # 10 iterations x (2 * 8*8*8) flops
    assert res["flops"] == pytest.approx(10 * 2 * 8 * 8 * 8)


def test_trip_count_from_condition():
    comps = split_computations(SYNTH_HLO)
    assert "cond" in comps
    assert trip_count(comps["cond"]) == 10


def test_analyzer_matches_known_scan():
    """End-to-end against a real compile (single host device)."""
    script = r"""
import jax, jax.numpy as jnp, sys, json
sys.path.insert(0, ".")
from benchmarks.hlo_analysis import analyze_hlo
N, L = 64, 7
def f(x, ws):
    def body(c, w):
        return jnp.tanh(c @ w), None
    y, _ = jax.lax.scan(body, x, ws)
    return y.sum()
comp = jax.jit(f).lower(jax.ShapeDtypeStruct((N, N), jnp.float32),
                        jax.ShapeDtypeStruct((L, N, N), jnp.float32)).compile()
res = analyze_hlo(comp.as_text())
print(json.dumps({"flops": res["flops"], "expect": 2.0 * N**3 * L}))
"""
    env = dict(os.environ, PYTHONPATH=os.path.abspath("src"))
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=300,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["flops"] == pytest.approx(res["expect"], rel=0.01)


def test_eligibility_rules():
    from repro.launch import dryrun  # noqa: F401  (import only; no jax use)
    # long_500k only for sub-quadratic archs
    from repro.configs import get_arch
    assert get_arch("mamba2-370m").sub_quadratic
    assert get_arch("zamba2-2.7b").sub_quadratic
    assert not get_arch("yi-34b").sub_quadratic
    assert not get_arch("deepseek-v3-671b").sub_quadratic


def test_dryrun_results_if_present():
    """When the sweep has run, every recorded cell must be ok=True."""
    path = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                        "dryrun_results.json")
    if not os.path.exists(path):
        pytest.skip("dry-run sweep not yet executed")
    data = json.load(open(path))
    bad = [f"{r['arch']}/{r['shape']}/{r.get('mesh')}"
           for r in data if not r.get("ok")]
    assert not bad, f"failed dry-run cells: {bad}"
    # coverage: every eligible (arch x shape) on the single-pod mesh
    from repro.configs import ARCH_NAMES, SHAPES, get_arch
    seen = {(r["arch"], r["shape"], r["mesh"]) for r in data if r.get("ok")}
    missing = []
    for a in ARCH_NAMES:
        for s in SHAPES:
            if s == "long_500k" and not get_arch(a).sub_quadratic:
                continue
            if (a, s, "16x16") not in seen:
                missing.append(f"{a}/{s}")
    assert not missing, f"missing single-pod cells: {missing}"


def test_peaks_keyed_by_device_kind():
    """Peaks come from one table keyed by ``device_kind`` (Google Cloud,
    "TPU v5e"); a kind with no published entry raises, never defaults."""
    from repro.launch.mesh import PRODUCTION_KIND, peaks
    v5e = peaks("TPU v5 lite")
    assert v5e["peak_flops_bf16"] == 197e12
    assert v5e["peak_ops_int8"] == 393e12
    assert v5e["hbm_bw"] == 819e9
    assert peaks(PRODUCTION_KIND) is v5e
    with pytest.raises(KeyError, match="no published peaks"):
        peaks("cpu")
