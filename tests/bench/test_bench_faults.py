"""A run with the timed path broken underneath comes out not correct.

Each test skips the harness's look for a chip and drives the rest of a
run on the CPU, on a copy of the cell cut to a size a test can hold: once
sound, once with a fault planted where the answers are produced, and once
reading the control (the reference at the next precision down in the
program's place).
"""
import json
import shutil
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import manifest as mf  # noqa: E402
from bench import run as br  # noqa: E402

SEED = 2 ** 31 + 99


def _edit(path: Path, **kw):
    data = json.loads(path.read_text())
    for k, v in kw.items():
        if isinstance(v, dict):
            data[k] = dict(data[k], **v)
        else:
            data[k] = v
    path.write_text(json.dumps(data))


def _cell(tmp_path, name, *, config, traffic, workload):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    b = tmp_path / "bench"
    man = mf.load_manifest(ROOT)
    entry = {w["name"]: w for w in man["workloads"]}[name]
    _edit(b / "configs" / f"{entry['config']}.json", **config)
    _edit(b / "traffic" / f"{entry['traffic']}.json", **traffic)
    _edit(b / "workloads" / f"{name}.json", **workload)
    return mf.resolve(man, name, b)


def _drive(cell, seconds, controls=()):
    import jax
    run = br.Run(cell, SEED, seconds, False, jax.devices("cpu")[:1])
    run.controls = list(controls)
    cell.runner().run(run)
    return run, br.result(run)


@pytest.fixture
def fir_cell(tmp_path):
    return _cell(tmp_path, "fir30-bbm0.block16k",
                 config={"engine": {"max_channels": 4}},
                 traffic={"requests_per_flush": 4, "pool_flushes": 2,
                          "samples": 1024},
                 workload={"check_requests": 4})


def test_fir_sound_run_is_correct_and_control_fails(fir_cell):
    run, out = _drive(fir_cell, 0.5, controls=["wl8"])
    assert out["correct"], out["checks"]
    assert out["metrics"]["samples_per_s"]["value"] > 0
    ctl = br.result(run.control_runs["wl8"])
    assert not ctl["correct"]
    assert ctl["checks"]["mismatched_samples"]["value"] > \
        ctl["checks"]["mismatched_samples"]["limit"]


def test_fir_altered_answer_fails(fir_cell, monkeypatch):
    import repro.dsp.fir as fir
    real = fir.fir_apply

    def altered(*a, **k):
        y = np.array(real(*a, **k))
        y[..., 7] += 2.0 ** -20          # one sample of every answer
        return y
    monkeypatch.setattr(fir, "fir_apply", altered)
    _, out = _drive(fir_cell, 0.5)
    assert not out["correct"]
    assert out["checks"]["mismatched_samples"]["value"] > 0


LM_SMALL = dict(
    config={"hidden_size": 128, "intermediate_size": 512,
            "num_attention_heads": 4, "num_key_value_heads": 2,
            "num_hidden_layers": 2, "vocab_size": 8192,
            # the CPU takes the exact products in full float32
            "datapath": {"wl": 16, "vbl": 13, "exact_precision": "float32"}},
    traffic={"clients": 4, "block": 4,
             "prompt_len": {"median": 14, "sigma": 1.0,
                            "buckets": [8, 16, 40]},
             "output_len": {"median": 12, "sigma": 0.8, "min": 4,
                            "max": 23}},
    # on the CPU this size reads a widest gap of 0 on sound runs (the
    # replay gives the served tokens exactly), 0.0033 for the int8 exact
    # products and 0.46 for the 8-bit multiplier: its own limit sits between
    workload={"slots": 4, "max_len": 64,
              "limits": {"max_gap": 1e-3, "mean_gap": 1e-5,
                         "min_checked_tokens": 40}})


@pytest.fixture
def lm_cell(tmp_path):
    return _cell(tmp_path, "qwen2-0.5b-bbm0.chat", **LM_SMALL)


def test_lm_sound_run_is_correct_and_controls_fail(lm_cell):
    run, out = _drive(lm_cell, 3.0, controls=["amm8", "exact_int8"])
    assert out["correct"], out["checks"]
    assert out["compiles_in_window"] == 0
    assert run.counters["checked_tokens"] >= 40
    # every decode step ran all four rows, live or not, as one batch
    for name in ("amm8", "exact_int8"):
        ctl = br.result(run.control_runs[name])
        assert not ctl["correct"], name
        assert ctl["checks"]["max_gap"]["value"] > \
            ctl["checks"]["max_gap"]["limit"], name


def test_lm_altered_token_fails(lm_cell, monkeypatch):
    from repro.serve import engine
    real = engine.Scheduler.step

    def step(self):
        n = real(self)
        for s in self.slots:          # every fourth token, as produced
            if s is not None and len(s.out) % 4 == 0:
                s.out[-1] = (s.out[-1] + 1) % self.cfg.vocab
        return n
    monkeypatch.setattr(engine.Scheduler, "step", step)
    _, out = _drive(lm_cell, 3.0)
    assert not out["correct"]
    assert out["checks"]["max_gap"]["value"] > \
        out["checks"]["max_gap"]["limit"]
