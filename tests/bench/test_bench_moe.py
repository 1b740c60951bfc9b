"""The latent-attention MoE cell: configuration, weights, counts, readers,
the routing judge, and a sound run at a test size on the CPU."""
import json
import shutil
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import counts_moe  # noqa: E402
from bench import manifest as mf  # noqa: E402
from bench import run as br  # noqa: E402

MAN = mf.load_manifest(ROOT)
CELL = "moonlight-16b-a3b-bbm0.chat"
CFG = json.loads((ROOT / "bench/configs/moonlight-16b-a3b-bbm0.json")
                 .read_text())
CATALOG_NOTE = "https://huggingface.co/moonshotai/Moonlight-16B-A3B"


def _runner():
    return mf.load_module(ROOT / "bench/runners/mla_moe_serve.py")


@pytest.mark.parametrize("cell,extra", [
    (CELL, {"moe.rows_per_expert", "moe.decode_roofline", "mfu.lm"})])
def test_new_cells_resolve_and_report(cell, extra):
    c = mf.resolve(MAN, cell)
    assert c.chips == 1
    assert {m["name"] for m in c.end_to_end} == \
        {"setup_s", "tokens_per_s", "itl_p95_ms"}
    # lm.admit_ms stays the chat cell's: test_bench_spans.py pins its list
    want = {"lm.decode_step_ms", "lm.prefill_us_per_token",
            "lm.slot_occupancy", "lm.ttft_p50_ms", "idle_share.lm"} | extra
    assert {m["name"] for m in c.per_layer} == want
    for m in c.per_layer:
        assert hasattr(c.reader(m["name"]), "read")


def test_config_keeps_published_widths():
    cfg = _runner().arch(CFG)
    assert CFG["source"].startswith(CATALOG_NOTE)
    assert (cfg.d_model, cfg.n_heads, cfg.kv_lora_rank, cfg.qk_nope_dim,
            cfg.qk_rope_dim, cfg.v_head_dim, cfg.d_ff, cfg.moe_d_ff) == \
        (2048, 16, 512, 128, 64, 128, 11264, 1408)
    assert (cfg.n_experts, cfg.top_k, cfg.n_shared_experts) == (64, 6, 2)
    assert (cfg.n_layers, cfg.first_k_dense, cfg.n_local_experts,
            cfg.expert_offset, cfg.vocab) == (9, 1, 8, 0, 20480)
    assert cfg.router_bias and cfg.routed_scaling_factor == 2.446
    assert cfg.q_lora_rank == 0 and not cfg.tie_embeddings
    for key, n in CFG["published"].items():
        assert key in CFG["reduced"] and CFG[key] < n


def test_weights_match_the_program_layout():
    from repro.models import lm_table
    from repro.models.common import Spec
    run = _runner()
    got = jax.eval_shape(lambda: run.make_weights(CFG, 2 ** 31 + 1))
    table = lm_table(run.arch(CFG))
    spec = lambda x: isinstance(x, Spec)
    assert jax.tree.structure(got) == jax.tree.structure(table, is_leaf=spec)
    assert [x.shape for x in jax.tree.leaves(got)] == \
        [s.shape for s in jax.tree.leaves(table, is_leaf=spec)]
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(got))
    assert n == 970_107_904


# ------------------------------------------------------------------ counts
def test_counts_of_one_decode_step():
    """Two live rows at contexts 10 and 20; in each of the 8 expert
    layers row 0 goes to experts 0-5 (all held), row 1 to experts 3-8
    (3-7 held): experts 0-7 each hit once per layer."""
    d, h = 2048, 16
    attn = d * h * 192 + d * 576 + h * 128 * 512 + h * 512 * 128 \
        + h * 128 * d
    assert attn == 13_762_560
    expert = 3 * d * 1408
    fixed = 9 * attn + 3 * d * 11264 + 8 * (d * 64 + 2 * expert) \
        + d * 20480
    assert counts_moe.fixed_params(CFG) == fixed
    ids = np.zeros((8, 2, 6), np.int64)
    ids[:, 0] = np.arange(6)
    ids[:, 1] = np.arange(3, 9)
    latent = 2 * h * (576 + 512) * 9
    flops = 2 * (2 * fixed + 11 * 8 * expert) + latent * (10 + 20)
    nbytes = 2 * (fixed + 8 * 8 * expert + 2 * d) + 2 * 576 * 30 * 9
    assert counts_moe.decode_step(CFG, [10, 20], ids) == (flops, nbytes)
    assert counts_moe.rows_per_expert(ids, CFG) == 11 / 8


def test_counts_leave_out_experts_no_row_chose():
    ids = np.full((8, 1, 6), 40)            # every choice held elsewhere
    f, b = counts_moe.decode_step(CFG, [1], ids)
    assert f == 2 * counts_moe.fixed_params(CFG) \
        + counts_moe.latent_flops(CFG, 1)
    assert b == 2 * (counts_moe.fixed_params(CFG) + 2048) + 2 * 576 * 9


# ----------------------------------------------------------------- readers
class _Run:
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}

    def __init__(self, host=None, reduced=None):
        self.host, self.reduced = host or {}, reduced


def test_readers_read_known_values_and_none_without_data():
    rows = mf.load_module(ROOT / "bench/metrics/moe.rows_per_expert.py")
    roof = mf.load_module(ROOT / "bench/metrics/moe.decode_roofline.py")
    steps = [(197e9, 819e6), (394e9, 819e6)]        # 1 ms and 2 ms least
    red = {"programs": {"jit_decode": {"s": 0.3, "calls": 2},
                        "jit_prefill": {"s": 9.0, "calls": 1}}}
    run = _Run({"rows_per_expert": [6.0, 5.5, 6.5],
                "decode_work": steps}, red)
    assert rows.read(run) == 6.0
    assert abs(roof.read(run) - 100 * 1.5e-3 / 0.15) < 1e-9
    assert rows.read(_Run()) is None
    assert roof.read(_Run({"decode_work": steps})) is None     # no trace
    assert roof.read(_Run({}, red)) is None                    # no counter


# ----------------------------------------------------------- routing judge
def _judge(gaps, delta):
    c = mf.resolve(MAN, CELL)
    c.params = dict(c.params, limits=dict(c.params["limits"],
                                          route_delta=delta))
    run = br.Run(c, 1, 1.0, False, [])
    _runner().judge_routing(run, gaps)
    return {x["name"]: x for x in run.checks}["routing_outside_margin"]


def test_routing_judge():
    inside = _judge([-0.3, 0.0, 0.0009, 0.001], 0.001)
    assert inside["ok"] and inside["value"] == 0
    outside = _judge([-0.3, 0.0, 0.0011], 0.001)
    assert not outside["ok"] and outside["value"] == 1


def test_route_gaps_measure_below_the_kth_best():
    ref = mf.load_module(ROOT / "bench/reference/moonlight_bbm.py")
    biased = jax.numpy.asarray([[0.9, 0.8, 0.7, 0.1]])
    route = [[(biased, jax.numpy.asarray([[0, 1]]))]]
    np.testing.assert_allclose(ref.route_gaps(route), [-0.1, 0.0],
                               atol=1e-6)
    own = [[(None, jax.numpy.asarray([[0, 3]]))]]
    np.testing.assert_allclose(ref.route_gaps(route, own), [-0.1, 0.7],
                               atol=1e-6)


# --------------------------------------------------- a sound run on the CPU
SMALL = dict(
    config={"hidden_size": 64, "intermediate_size": 128,
            "moe_intermediate_size": 32, "num_attention_heads": 4,
            "num_key_value_heads": 4, "qk_nope_head_dim": 16,
            "qk_rope_head_dim": 8, "v_head_dim": 16, "kv_lora_rank": 32,
            "n_routed_experts": 4, "num_experts_per_tok": 2,
            "num_hidden_layers": 3, "vocab_size": 512,
            "expert_share": {"router_outputs": 8, "first_held": 2},
            # the CPU takes the exact products in full float32
            "datapath": {"wl": 16, "vbl": 13, "exact_precision": "float32"},
            # the 40-token prompts come in three chunks (16, 16, 8)
            "serving": {"prefill_chunk": 16}},
    traffic={"clients": 4, "block": 4,
             "prompt_len": {"median": 14, "sigma": 1.0,
                            "buckets": [8, 16, 40]},
             "output_len": {"median": 12, "sigma": 0.8, "min": 4,
                            "max": 23}},
    # on the CPU this size reads a widest gap of 0 and every choice inside
    # the reference's top k on sound runs; the 8-bit multiplier reads a
    # widest gap of 0.07 and choices up to 0.011 below the k-th best
    workload={"slots": 4, "max_len": 64,
              "limits": {"max_gap": 1e-3, "mean_gap": 1e-5,
                         "min_checked_tokens": 40, "route_delta": 1e-4}})


def _edit(path: Path, **kw):
    data = json.loads(path.read_text())
    for k, v in kw.items():
        data[k] = dict(data[k], **v) if isinstance(v, dict) else v
    path.write_text(json.dumps(data))


def test_sound_run_is_correct_and_control_fails(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    b = tmp_path / "bench"
    _edit(b / "configs" / "moonlight-16b-a3b-bbm0.json", **SMALL["config"])
    _edit(b / "traffic" / "chat.json", **SMALL["traffic"])
    _edit(b / "workloads" / f"{CELL}.json", **SMALL["workload"])
    cell = mf.resolve(MAN, CELL, b)
    run = br.Run(cell, 2 ** 31 + 99, 1.0, False, jax.devices("cpu")[:1])
    run.controls = ["amm8"]
    cell.runner().run(run)
    out = br.result(run)
    assert out["correct"], out["checks"]
    assert out["compiles_in_window"] == 0
    assert out["checks"]["routing_outside_margin"]["value"] == 0
    assert run.counters["model_flops"] > 0
    assert run.host["rows_per_expert"] and run.host["decode_work"]
    ctl = br.result(run.control_runs["amm8"])
    assert not ctl["correct"]
    assert ctl["checks"]["max_gap"]["value"] > \
        ctl["checks"]["max_gap"]["limit"]
