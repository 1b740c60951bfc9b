"""Host spans and device scopes of the serving path (``repro.trace``).

The spans record only while a profiler trace runs and change no output;
the scopes reach the decode program's HLO ``op_name`` metadata, where a
trace reduction finds them.
"""
from __future__ import annotations

import dataclasses
import gc
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import trace
from repro.configs import get_arch, reduced
from repro.configs.base import AmmConfig
from repro.core.multipliers import MulSpec
from repro.dsp import design_lowpass
from repro.launch.mesh import make_host_mesh
from repro.models import ModelRuntime, lm_init
from repro.serve import FilterbankEngine
from repro.serve.engine import Request, Scheduler, make_serve_fns
from repro.serve.kv_cache import KV_BLOCK, init_code_cache


@pytest.fixture
def profiler(tmp_path):
    """Runs the block under a ``jax.profiler`` trace, with a clean table."""
    trace.clear()
    jax.profiler.start_trace(str(tmp_path))
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def test_span_is_a_no_op_without_a_profiler():
    trace.clear()
    with trace.span("fir.flush") as s:
        pass
    assert s is None
    assert trace.recorded() == {}


def test_nested_spans_record_self_time(profiler):
    with trace.span("outer"):
        time.sleep(0.01)
        with trace.span("inner"):
            time.sleep(0.02)
        with trace.span("inner"):
            time.sleep(0.02)
    rec = trace.recorded()
    outer, inner = rec["repro.outer"], rec["repro.inner"]
    assert outer["calls"] == 1 and inner["calls"] == 2
    assert inner["s"] == pytest.approx(inner["self_s"])
    assert inner["s"] >= 0.04
    assert outer["self_s"] == pytest.approx(outer["s"] - inner["s"])
    assert outer["self_s"] >= 0.01


def test_gc_spans_mark_a_collection(profiler):
    trace.gc_spans()
    trace.gc_spans()
    assert gc.callbacks.count(trace._gc_span) == 1
    with trace.span("phase"):
        gc.collect()
    rec = trace.recorded()
    assert rec["repro.host.gc"]["calls"] >= 1
    # the collection is the phase's child, not its self time
    assert rec["repro.phase"]["self_s"] == pytest.approx(
        rec["repro.phase"]["s"] - rec["repro.host.gc"]["s"], abs=1e-3)


# ----------------------------------------------------------- device scopes
def _lm(wl=16, vbl=13, layers=2):
    cfg = reduced(get_arch("qwen2-0.5b"), layers=layers)
    return dataclasses.replace(cfg, amm=AmmConfig(
        mode="bitexact", mul="bbm0", wl=wl, param=vbl, apply_to="all"))


def _decode_scope_stacks(cfg):
    """The name stacks of the serving decode step's HLO ops, as the
    benchmark builds the step (no plane cache, int-code KV cache)."""
    rt = ModelRuntime.build(cfg)
    slots, max_len = 4, 2 * KV_BLOCK
    _, decode_j = make_serve_fns(cfg, rt, make_host_mesh(1, 1),
                                 batch=slots, max_len=max_len,
                                 kv_codes=True)
    params = jax.eval_shape(lambda: lm_init(cfg, jax.random.key(0)))
    caches = jax.eval_shape(lambda: init_code_cache(
        cfg, slots, max_len, wl=rt.amm.attn_lowering[0]))
    toks = jax.ShapeDtypeStruct((slots, 1), jnp.int32)
    pos = jax.ShapeDtypeStruct((slots,), jnp.int32)
    hlo = decode_j.lower(params, toks, caches, pos).as_text(
        dialect="hlo", debug_info=True)
    # a name stack inside the layer scan's body is relative to it
    return [n.split("/") for n in set(re.findall(r'op_name="([^"]*)"', hlo))]


def test_decode_program_carries_the_scopes():
    """The serving decode step as the benchmark builds it (no plane cache,
    int-code KV cache): each scope names some of its HLO ops."""
    stacks = _decode_scope_stacks(_lm())
    for scope in trace.SCOPES:
        assert any(scope in st for st in stacks), scope
    # the weight decode is its own scope, never inside the contraction's
    assert not any(trace.AMM_WEIGHT_DECODE in st and trace.AMM_CONTRACT in st
                   for st in stacks)


def test_value_product_scope_sits_in_the_code_cache_scope():
    """The decode step's value product runs under the scope of the form
    its shapes chose (``pv_form``), inside ``attn.code_cache``; the other
    form's scope names no op."""
    from repro.kernels.bbm_matmul import pv_form
    cfg = _lm()
    stacks = _decode_scope_stacks(cfg)
    form = pv_form(KV_BLOCK, cfg.amm.wl, cfg.amm.param)
    taken = trace.AMM_PV_ROWS if form == "rows" else trace.AMM_PV_DOT
    other = trace.AMM_PV_DOT if form == "rows" else trace.AMM_PV_ROWS

    def depth(st, scope):
        # a scope opened inside a vmap reads "vmap(vmap(<scope>))"
        return next((i for i, n in enumerate(st) if scope in n), None)

    inside = [st for st in stacks if depth(st, taken) is not None]
    assert inside
    assert all(trace.ATTN_CODE_CACHE in st[:depth(st, taken)]
               for st in inside)
    assert not any(depth(st, other) is not None for st in stacks)


# ------------------------------------------- outputs with spans on and off
def _flush_twice():
    spec = MulSpec("bbm0", 16, 13)
    g = np.random.default_rng(7)
    sigs = [g.standard_normal(n) for n in (300, 512, 17)]
    eng = FilterbankEngine(design_lowpass()[None, :], spec,
                           backend="pallas-interpret", max_channels=2,
                           block=128)
    out = []
    for _ in range(2):
        rids = [eng.submit(s) for s in sigs]
        res = eng.flush()
        out.append([res[r] for r in rids])
    return out


def _serve_lm():
    cfg = _lm(wl=8, vbl=5)
    rt = ModelRuntime.build(cfg)
    params = lm_init(cfg, jax.random.key(1))
    sched = Scheduler(cfg, rt, params, 2, 2 * KV_BLOCK, continuous=True,
                      kv_codes=True)
    reqs = [Request(rid=i, prompt=[3 + i, 5, 7][: 1 + i], max_new=3)
            for i in range(3)]
    for r in reqs:
        sched.submit(r)
    while sched.step():
        pass
    return [r.out for r in reqs]


def test_filterbank_flush_is_bitwise_the_same_traced(tmp_path):
    plain = _flush_twice()
    trace.clear()
    with jax.profiler.trace(str(tmp_path)):
        traced = _flush_twice()
    for a, b in zip(plain, traced):
        for x, y in zip(a, b):
            assert np.array_equal(x, y)
    rec = trace.recorded()
    for name in ("flush", "stack", "bank_take", "quantize", "to_device",
                 "dispatch", "fetch", "descale", "split"):
        assert rec[f"repro.fir.{name}"]["calls"] >= 2, name
    # two batches of at most 2 channels per flush, two flushes
    assert rec["repro.fir.flush"]["calls"] == 2
    assert rec["repro.fir.dispatch"]["calls"] == 4


def test_scheduler_steps_are_bitwise_the_same_traced(tmp_path):
    plain = _serve_lm()
    trace.clear()
    with jax.profiler.trace(str(tmp_path)):
        traced = _serve_lm()
    assert plain == traced
    rec = trace.recorded()
    assert rec["repro.sched.admit"]["calls"] == 3
    for name in ("reset_slot", "slot_take", "prefill", "slot_put",
                 "first_token"):
        assert rec[f"repro.sched.{name}"]["calls"] == 3, name
    steps = rec["repro.sched.decode"]["calls"]
    assert steps >= 2
    assert rec["repro.sched.sample"]["calls"] == steps
    assert rec["repro.sched.commit"]["calls"] == steps
    # an admission's phases are its children
    admit = rec["repro.sched.admit"]
    assert admit["self_s"] < admit["s"]
