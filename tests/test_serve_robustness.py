"""Degradation paths in the serving engines: isolate, retry, never wedge.

Covers the ``FilterbankEngine`` quarantine ladder (retry -> bisection ->
eject; the regression for the dispatch-before-dequeue livelock), the
``Scheduler``'s per-slot failure isolation / deadlines / guard-tripped
exact re-serve, the scheduler edge cases (empty prompt, prompt past
``max_len``, slot recycling after a mid-stream failure, FIFO admission),
and the launcher-side early argument validation.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import pytest
from numpy.testing import assert_array_equal

import jax
from repro.configs import get_arch, reduced
from repro.configs.base import AmmConfig
from repro.core.guards import GuardConfig
from repro.core.multipliers import MulSpec
from repro.dsp.fir import design_lowpass, fir_apply
from repro.models import ModelRuntime, lm_init
from repro.serve.engine import FilterbankEngine, Request, Scheduler

RNG = np.random.default_rng(23)
SPEC = MulSpec("bbm0", 16, 13)


# ------------------------------------------------------ FilterbankEngine
def _poisoned(engine, poison_sig):
    """Wrap the engine's dispatch to raise on batches holding one signal."""
    inner = engine._apply

    def flaky(x, h, spec, **kw):
        for row in np.asarray(x):
            if len(poison_sig) <= len(row) and np.array_equal(
                    row[: len(poison_sig)], poison_sig):
                raise RuntimeError("injected poison")
        return inner(x, h, spec, **kw)

    engine._apply = flaky


def test_poison_request_is_quarantined_not_livelocked():
    """Regression for the dispatch-before-dequeue wedge: one poison
    request used to re-raise out of every flush forever.  Now it is
    bisected down, quarantined into ``failed``, and every healthy
    neighbour in the same batch is served the same flush."""
    eng = FilterbankEngine(design_lowpass(), SPEC, backend="host",
                           max_channels=8, max_retries=1)
    sigs = [RNG.standard_normal(96) for _ in range(6)]
    _poisoned(eng, sigs[3])
    rids = [eng.submit(s) for s in sigs]
    out = eng.flush()
    assert set(out) == set(rids) - {rids[3]}
    assert rids[3] in eng.failed and "poison" in eng.failed[rids[3]]
    assert not eng._pending
    assert eng.flush() == {}             # drained: no re-raise, no wedge
    assert eng.stats["quarantined"] == 1 and eng.stats["bisections"] >= 1
    # healthy outputs are the normal datapath's, unchanged by the drama
    clean = FilterbankEngine(design_lowpass(), SPEC, backend="host")
    r0 = clean.submit(sigs[0])
    assert_array_equal(out[rids[0]], clean.flush()[r0])


def test_transient_failure_saved_by_retry():
    eng = FilterbankEngine(design_lowpass(), SPEC, backend="host",
                           max_retries=2)
    inner = eng._apply
    calls = {"n": 0}

    def transient(x, h, spec, **kw):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("transient blip")
        return inner(x, h, spec, **kw)

    eng._apply = transient
    rid = eng.submit(RNG.standard_normal(64))
    out = eng.flush()
    assert rid in out and not eng.failed
    assert eng.stats["retries"] == 1


def test_retries_exhausted_without_bisection_quarantines_singleton():
    eng = FilterbankEngine(design_lowpass(), SPEC, backend="host",
                           max_retries=1)

    def always(x, h, spec, **kw):
        raise RuntimeError("hard fault")

    eng._apply = always
    rid = eng.submit(RNG.standard_normal(32))
    assert eng.flush() == {}
    assert rid in eng.failed and eng.stats["retries"] == 1


def test_guard_trip_reserves_on_exact_datapath():
    """A zero error budget trips on any approximate output; the request
    must come back served by the exact Booth datapath, bit for bit."""
    guard = GuardConfig(budget_abs=0.0, budget_every=1)
    eng = FilterbankEngine(design_lowpass(), SPEC, backend="host",
                           guard=guard)
    sig = RNG.standard_normal(128)
    rid = eng.submit(sig)
    out = eng.flush()
    exact = fir_apply(sig, design_lowpass(), MulSpec("booth", 16, 0),
                      backend="host")
    assert_array_equal(out[rid], exact)
    assert eng.stats["guard_trips"] == 1
    assert eng.stats["exact_reserves"] == 1


def test_guard_quiet_when_within_budget():
    guard = GuardConfig(budget_abs=1.0, budget_every=1)
    eng = FilterbankEngine(design_lowpass(), SPEC, backend="host",
                           guard=guard)
    rid = eng.submit(RNG.standard_normal(64))
    out = eng.flush()
    assert rid in out and eng.stats["guard_trips"] == 0


# ------------------------------------------------------------- Scheduler
@pytest.fixture(scope="module")
def lm():
    cfg = reduced(get_arch("qwen2-0.5b"))
    cfg = dataclasses.replace(
        cfg, amm=AmmConfig(mode="bitexact", mul="bbm0", wl=16, param=13,
                           apply_to="mlp"))
    rt = ModelRuntime.build(cfg)
    params = lm_init(cfg, jax.random.key(0))
    return cfg, rt, params


def _drain(sched, cap=200):
    steps = 0
    while sched.step():
        steps += 1
        assert steps < cap, "scheduler failed to terminate"
    return steps


def _poison_wrapper(sched, poison_tok):
    """decode_fn raising whenever a marker token is in the batch."""
    inner = sched._default_fn

    def fn(p, t, c, q):
        if (np.asarray(t) == poison_tok).any():
            raise RuntimeError("poison token")
        return inner(p, t, c, q)

    return fn


def test_poison_request_fails_alone(lm):
    """A deterministically-raising request must fail by itself: its slot
    neighbour decodes to completion in the same run."""
    cfg, rt, params = lm
    sched = Scheduler(cfg, rt, params, 2, 32, max_retries=1)
    sched.decode_fn = _poison_wrapper(sched, 499)   # in the reduced vocab
    good = Request(rid=0, prompt=[1, 2], max_new=3)
    bad = Request(rid=1, prompt=[499, 2], max_new=3)
    sched.submit(good)
    sched.submit(bad)
    _drain(sched)
    assert good.done and good.error is None and len(good.out) == 3
    assert bad.done and bad.error and "poison" in bad.error
    assert bad.out == []
    assert sched.stats["failed"] == 1 and sched.stats["probes"] >= 1
    assert sched.stats["retries"] == 1


def test_slot_recycled_after_midstream_failure(lm):
    """The poison hits mid-stream (after the prompt); the freed slot must
    admit and finish the queued request."""
    cfg, rt, params = lm
    sched = Scheduler(cfg, rt, params, 1, 32, max_retries=1)
    inner = sched._default_fn
    state = {"calls": 0}

    def fn(p, t, c, q):
        state["calls"] += 1
        # the third step fails hard enough to exhaust the retry (call 4)
        # and reproduce under the isolation probe (call 5)
        if 3 <= state["calls"] <= 5:
            raise RuntimeError("mid-stream fault")
        return inner(p, t, c, q)

    sched.decode_fn = fn
    first = Request(rid=0, prompt=[1, 2], max_new=8)
    second = Request(rid=1, prompt=[3], max_new=2)
    sched.submit(first)
    sched.submit(second)
    _drain(sched)
    assert first.done and first.error is not None
    assert second.done and second.error is None and len(second.out) == 2


def test_systemic_failure_reraises(lm):
    """A failure no single-slot probe reproduces is systemic: surface it
    instead of silently failing every request."""
    cfg, rt, params = lm
    sched = Scheduler(cfg, rt, params, 2, 32, max_retries=0)

    def always(p, t, c, q):
        raise RuntimeError("the accelerator is on fire")

    sched.decode_fn = always
    sched.submit(Request(rid=0, prompt=[1], max_new=1))
    with pytest.raises(RuntimeError, match="on fire"):
        sched.step()


def test_deadline_expires_request(lm):
    cfg, rt, params = lm
    sched = Scheduler(cfg, rt, params, 1, 32)
    req = Request(rid=0, prompt=[1, 2, 3, 4], max_new=20, deadline=6)
    sched.submit(req)
    _drain(sched)
    assert req.done and req.error == "deadline"
    assert sched.stats["deadline_expired"] == 1


def test_guard_trip_reserves_request_exactly(lm):
    """Zero budget + approximate datapath: every audited step trips, and
    the request is replayed on the exact datapath (mode="off")."""
    cfg, rt, params = lm
    guard = GuardConfig(budget_abs=0.0, budget_every=1)
    sched = Scheduler(cfg, rt, params, 1, 32, guard=guard)
    req = Request(rid=0, prompt=[1, 2, 3], max_new=3)
    sched.submit(req)
    _drain(sched)
    assert req.done and req.exact and len(req.out) == 3
    assert sched.stats["guard_trips"] >= 1
    assert sched.stats["exact_reserves"] == 1
    # the re-served output is what the exact scheduler produces
    cfg_off = dataclasses.replace(
        cfg, amm=dataclasses.replace(cfg.amm, mode="off"))
    rt_off = ModelRuntime.build(cfg_off)
    ref_sched = Scheduler(cfg_off, rt_off, params, 1, 32)
    ref = Request(rid=0, prompt=[1, 2, 3], max_new=3)
    ref_sched.submit(ref)
    _drain(ref_sched)
    assert req.out == ref.out


# ----------------------------------------------- scheduler edge cases
def test_empty_prompt_decodes_from_token_zero(lm):
    cfg, rt, params = lm
    sched = Scheduler(cfg, rt, params, 1, 32)
    req = Request(rid=0, prompt=[], max_new=2)
    sched.submit(req)
    _drain(sched)
    assert req.done and req.error is None and len(req.out) == 2


def test_prompt_past_max_len_rejected_at_submit(lm):
    """Previously a livelock: the slot could never finish.  Now it is a
    clear error before the request ever holds a slot."""
    cfg, rt, params = lm
    sched = Scheduler(cfg, rt, params, 1, 8)
    with pytest.raises(ValueError, match="cannot fit max_len"):
        sched.submit(Request(rid=0, prompt=list(range(8)), max_new=1))
    with pytest.raises(ValueError, match="max_new"):
        sched.submit(Request(rid=1, prompt=[1], max_new=0))
    # near the cap is fine — and terminates (pos-cap applies mid-prompt)
    req = Request(rid=2, prompt=list(range(7)), max_new=4)
    sched.submit(req)
    _drain(sched)
    assert req.done


def test_fifo_admission_order_under_slot_contention(lm):
    """One slot, three requests: completion follows submission order."""
    cfg, rt, params = lm
    sched = Scheduler(cfg, rt, params, 1, 32)
    done_order = []
    reqs = [Request(rid=i, prompt=[i + 1], max_new=2) for i in range(3)]
    for r in reqs:
        sched.submit(r)
    while sched.step():
        for r in reqs:
            if r.done and r.rid not in done_order:
                done_order.append(r.rid)
    assert done_order == [0, 1, 2]


# -------------------------------------------- launcher arg validation
def test_launchers_reject_bad_amm_args_at_parse_time():
    from repro.launch.serve import main as serve_main
    from repro.launch.train import main as train_main
    bad = [["--amm", "bitexact", "--vbl", "16"],          # vbl >= wl
           ["--amm", "bitexact", "--wl", "18"],           # wl out of range
           ["--amm", "bitexact", "--wl", "7"],            # odd wl
           ["--amm", "bitexact", "--vbl", "-1"],
           ["--amm", "noise", "--mul", "madeup"]]         # unknown kind
    for argv in bad:
        with pytest.raises(SystemExit):
            serve_main(["--reduced"] + argv)
        with pytest.raises(SystemExit):
            train_main(["--reduced", "--steps", "1"] + argv)


def test_serve_launcher_rejects_kv_codes_without_booth_attention():
    """--kv-codes stores Booth attention codes: anything short of a
    bitexact Booth-family amm with attention routed must die at parse
    time (``launch.validate_serve_flags``), not deep in Scheduler init."""
    from repro.launch.serve import main as serve_main
    bad = [["--kv-codes"],                                     # amm off
           ["--kv-codes", "--amm", "noise", "--amm-attn"],     # not bitexact
           ["--kv-codes", "--amm", "bitexact", "--mul", "bam",
            "--wl", "8", "--vbl", "5", "--amm-attn"],          # non-Booth
           ["--kv-codes", "--amm", "bitexact", "--wl", "8",
            "--vbl", "5"]]                                     # no --amm-attn
    for argv in bad:
        with pytest.raises(SystemExit):
            serve_main(["--reduced"] + argv)


def test_serve_plane_cache_sized_before_it_is_built(capsys):
    """``plane_cache_for`` works out the digit-plane cache's bytes from the
    param shapes (4*wl B per MLP weight plus one f32 scale per weight
    matrix) and builds it only when cache and params fit in half the
    device's bytes; otherwise it serves uncached, which is bit-identical."""
    import jax.numpy as jnp
    from repro.launch.serve import plane_cache_for
    from repro.models import lm_apply
    wl = 16
    cfg = dataclasses.replace(reduced(get_arch("qwen2-0.5b")), amm=AmmConfig(
        mode="bitexact", mul="bbm0", wl=wl, param=13, apply_to="mlp"))
    rt = ModelRuntime.build(cfg)
    params = lm_init(cfg, jax.random.key(0))
    weights = 3 * cfg.n_layers * cfg.d_model * cfg.d_ff
    need = 4 * wl * weights + 4 * 3 * cfg.n_layers
    total = need + sum(x.nbytes for x in jax.tree.leaves(params))

    planes = plane_cache_for(cfg, rt, params, bytes_limit=2 * total)
    assert planes is not None
    assert f"plane cache {need} B: serving cached" in capsys.readouterr().out
    assert plane_cache_for(cfg, rt, params, bytes_limit=2 * total - 2) is None
    assert "serving uncached" in capsys.readouterr().out

    toks = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
    cached, _, _ = lm_apply(params, cfg, rt, toks, amm_planes=planes)
    uncached, _, _ = lm_apply(params, cfg, rt, toks)
    assert_array_equal(np.asarray(cached), np.asarray(uncached))

    off = dataclasses.replace(cfg, amm=AmmConfig(mode="off"))
    assert plane_cache_for(off, ModelRuntime.build(off), params) is None
