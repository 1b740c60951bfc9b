"""The trace reduction: device busy union, time by program, idle gaps."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import trace  # noqa: E402

DEV = "/device:TPU:0"


def _op(start, dur, name="fusion.1"):
    return {"plane": DEV, "line": trace.OPS_LINE, "name": name,
            "start": start, "dur": dur}


def _mod(start, dur, name):
    return {"plane": DEV, "line": trace.MODULES_LINE, "name": name,
            "start": start, "dur": dur}


def _span(name, start, dur):
    return {"name": "bench." + name, "start": start, "dur": dur}


EVENTS = {
    "device": [
        _op(10, 20), _op(20, 20, "fusion.2"),       # union [10, 40)
        _op(60, 10),                                  # [60, 70)
        _op(95, 15, "convolution.3"),                 # clipped to [95, 100)
        _op(200, 10),                                 # after the window
        _mod(10, 30, "jit_decode(11)"), _mod(60, 10, "jit_decode(11)"),
        _mod(95, 15, "jit_prefill(12)"), _mod(200, 10, "jit_decode(11)"),
    ],
    "host": [_span("traced", 0, 100), _span("sched.step", 0, 50),
             _span("client.collect", 50, 50)],
}


def test_busy_is_the_union_inside_the_window():
    r = trace.reduce(EVENTS)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(45e-9)


def test_program_time_and_calls():
    r = trace.reduce(EVENTS)
    assert r["programs"]["jit_decode"] == {"s": pytest.approx(40e-9),
                                           "calls": 2}
    assert trace.program_seconds(r, "jit_prefill") == \
        (pytest.approx(15e-9), 1)
    assert trace.program_seconds(r, "jit_pre") == (0.0, 0)


def test_top_ops_and_idle_gaps():
    r = trace.reduce(EVENTS)
    ops = dict(r["device_ops"])
    assert ops["jit_decode:fusion.1"] == pytest.approx(30e-9)
    assert ops["jit_prefill:convolution.3"] == pytest.approx(5e-9)
    gaps = dict(r["idle_gaps"])
    # idle [0,10) and [40,50) under the step span, [50,60) and [70,95)
    # under the collect span; a tie goes to the first span
    assert gaps["bench.sched.step"] == pytest.approx(30e-9)
    assert gaps["bench.client.collect"] == pytest.approx(25e-9)
    assert sum(gaps.values()) == pytest.approx(100e-9 - r["busy_s"])


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce({"device": [], "host": []})
