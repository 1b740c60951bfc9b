"""Operation and byte counts against hand-computed values."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import counts, peaks  # noqa: E402

QWEN = json.loads((ROOT / "bench" / "configs" /
                   "qwen2-0.5b-bbm0.json").read_text())


def test_qwen2_matmul_params():
    # per layer: q 896x14x64, k and v 896x2x64 each, o 14x64x896,
    # gate/up/down 3 x 896x4864; then the tied head 896 x 151936
    per_layer = 802_816 + 229_376 + 802_816 + 13_074_432
    assert per_layer == 14_909_440
    assert counts.lm_matmul_params(QWEN) == 24 * per_layer + 136_134_656
    assert counts.lm_matmul_params(QWEN) == 493_961_216


def test_qwen2_token_flops():
    # one token at position 0 attends to 1 position:
    # 2 x 493,961,216 + 4 x 14 heads x 64 x 1 x 24 layers
    assert counts.lm_model_flops(QWEN, [1]) == 987_922_432 + 86_016
    # a 128-token prompt: contexts 1..128 sum to 8,256
    assert counts.lm_span_flops(QWEN, 0, 128) == \
        128 * 987_922_432 + 86_016 * 8_256
    assert counts.lm_span_flops(QWEN, 128, 131) == \
        counts.lm_model_flops(QWEN, [129, 130, 131])


def test_fir_31_taps():
    ops, nbytes = counts.fir_nominal(64 * 16384, 31)
    assert ops == 62 * 1_048_576 == 65_011_712
    assert nbytes == 6 * 1_048_576 == 6_291_456
    pk = peaks.peaks("TPU v5 lite")
    least, bound = counts.roofline_least_s(ops, nbytes, pk["int8_ops"],
                                           pk["hbm_bytes_per_s"])
    assert bound == "memory"
    assert least == pytest.approx(6_291_456 / 819e9)


def test_peaks_table():
    pk = peaks.peaks("TPU v5 lite")
    assert (pk["bf16_flops"], pk["int8_ops"], pk["hbm_bytes_per_s"]) == \
        (197e12, 393e12, 819e9)
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")
