"""The per-layer metrics that read the program's span table
(``repro.trace.recorded()``): their values on a known table, and their
absence where the program keeps no table or recorded nothing."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import manifest as mf  # noqa: E402
from repro import trace as program  # noqa: E402

READERS = ("fir.host_ms_per_flush", "fir.transfer_ms_per_flush",
           "lm.admit_ms")
CELLS = {"fir.host_ms_per_flush": "fir30-bbm0.block16k",
         "fir.transfer_ms_per_flush": "fir30-bbm0.block16k",
         "lm.admit_ms": "qwen2-0.5b-bbm0.chat"}


def _reader(name):
    return mf.load_module(ROOT / "bench" / "metrics" / f"{name}.py")


TABLE = {
    "repro.fir.flush": {"calls": 4, "s": 0.100, "self_s": 0.002},
    "repro.fir.stack": {"calls": 4, "s": 0.010, "self_s": 0.010},
    "repro.fir.quantize": {"calls": 8, "s": 0.030, "self_s": 0.024},
    "repro.fir.descale": {"calls": 4, "s": 0.008, "self_s": 0.008},
    "repro.fir.split": {"calls": 4, "s": 0.002, "self_s": 0.002},
    "repro.fir.to_device": {"calls": 4, "s": 0.004, "self_s": 0.004},
    "repro.fir.fetch": {"calls": 4, "s": 0.012, "self_s": 0.012},
    "repro.sched.admit": {"calls": 2, "s": 0.300, "self_s": 0.010},
    "repro.sched.first_token": {"calls": 2, "s": 0.250, "self_s": 0.250},
}


@pytest.mark.parametrize("metric,want", [
    ("fir.host_ms_per_flush", (10 + 24 + 8 + 2) / 4),
    ("fir.transfer_ms_per_flush", (4 + 12) / 4),
    # the admission less the wait for its first token, per admission
    ("lm.admit_ms", (300 - 250) / 2),
])
def test_span_readers(monkeypatch, metric, want):
    monkeypatch.setattr(program, "recorded", lambda: TABLE)
    assert _reader(metric).read(None) == pytest.approx(want)


@pytest.mark.parametrize("metric", READERS)
def test_readers_leave_out_a_window_without_their_spans(monkeypatch,
                                                        metric):
    monkeypatch.setattr(program, "recorded", lambda: {})
    assert _reader(metric).read(None) is None


@pytest.mark.parametrize("metric", READERS)
def test_readers_leave_out_a_program_without_spans(monkeypatch, metric):
    # the parent's program has no ``repro.trace``: importing it fails
    monkeypatch.setitem(sys.modules, "repro.trace", None)
    assert _reader(metric).read(None) is None


def test_readers_read_spans_written_under_a_profiler(tmp_path):
    import jax
    program.clear()
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(2):
            with program.span("fir.flush"):
                with program.span("fir.quantize"):
                    pass
                with program.span("fir.fetch"):
                    pass
        with program.span("sched.admit"):
            with program.span("sched.first_token"):
                pass
    try:
        for metric in READERS:
            v = _reader(metric).read(None)
            assert v is not None and v >= 0.0, metric
    finally:
        program.clear()


def test_span_metrics_are_listed_for_their_cells():
    man = json.loads((ROOT / "BENCHMARK.json").read_text())
    by = {m["name"]: m for m in man["per_layer"]}
    for name, cell in CELLS.items():
        assert by[name]["source"] == "program_span"
        assert by[name]["workloads"] == [cell]
