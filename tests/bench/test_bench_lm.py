"""The LM configuration as the benchmark builds it for the program."""
import json
import sys
from pathlib import Path

import jax

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import manifest as mf  # noqa: E402

CFG = json.loads((ROOT / "bench" / "configs" /
                  "qwen2-0.5b-bbm0.json").read_text())


def _runner():
    return mf.load_module(ROOT / "bench/runners/lm_serve.py")


def test_published_widths():
    cfg = _runner().arch(CFG)
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.resolved_head_dim, cfg.d_ff, cfg.vocab) == \
        (24, 896, 14, 2, 64, 4864, 151936)
    assert cfg.qkv_bias and cfg.tie_embeddings
    assert (cfg.rope_theta, cfg.norm_eps) == (1e6, 1e-6)
    assert (cfg.amm.mode, cfg.amm.mul, cfg.amm.wl, cfg.amm.param,
            cfg.amm.apply_to) == ("bitexact", "bbm0", 16, 13, "all")


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
    assert {str(x.dtype) for x in jax.tree.leaves(got)} == {"float32"}


def _bbm0_scalar(a: int, b: int, wl: int, vbl: int) -> int:
    bu = b & ((1 << wl) - 1)
    bit = lambda j: (bu >> j) & 1 if j >= 0 else 0
    p = 0
    for i in range(wl // 2):
        d = -2 * bit(2 * i + 1) + bit(2 * i) + bit(2 * i - 1)
        m = max(0, vbl - 2 * i)
        p += (d * a // 2 ** m) * 2 ** m * 4 ** i
    return p


def test_reference_contraction_is_the_closed_form():
    import numpy as np
    ref = mf.load_module(ROOT / "bench/reference/qwen2_bbm.py")
    g = np.random.default_rng(3)
    for wl, vbl in ((16, 13), (8, 5)):
        lim = 2 ** (wl - 1)
        a = g.integers(-lim, lim, (5, 9))
        b = g.integers(-lim, lim, (9, 4))
        a[0, 0], b[0, 0] = -lim, -lim            # the extreme codes
        want = [[sum(_bbm0_scalar(int(a[m, k]), int(b[k, n]), wl, vbl)
                     for k in range(9)) for n in range(4)] for m in range(5)]
        got = ref.bbm_int(jax.numpy.asarray(a, "int32"),
                          jax.numpy.asarray(b, "int32"), wl, vbl)
        np.testing.assert_array_equal(np.asarray(got) * 2 ** vbl, want)


def test_ttft_reader_is_the_median_in_ms():
    rd = mf.load_module(ROOT / "bench/metrics/lm.ttft_p50_ms.py")

    class Run:
        host = {"ttft_s": [0.9, 0.6, 0.7]}

    assert abs(rd.read(Run) - 700.0) < 1e-9
    Run.host = {"ttft_s": []}
    assert rd.read(Run) is None
