"""Serving conformance suite for continuous batching + the int-code cache.

The contract under test: with attention-side amm routing
(``apply_to="attn"`` — per-(slot, head) quantization scales) every
request's token stream is *bitwise* the stream it would produce running
solo, no matter how admissions, evictions and failures interleave around
it.  ``kv_codes=True`` strengthens this to the cache representation
itself: codes freeze at write time, so later arrivals cannot move a
resident's quantization grid (the scale-drift fix pinned numerically in
tests/test_amm_attention.py).

Covers: random admission interleavings vs solo runs (seeded numpy always;
a hypothesis property variant when the real package is installed), FIFO
admission, prefill/decode disaggregation (a resident gains exactly one
token per step while long prompts queue), slot recycling after mid-stream
poison failure, deadline eviction, the int8 code-cache memory contract,
and the ``Scheduler`` constructor's kv_codes validation.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

import jax
import jax.numpy as jnp
from repro.configs import get_arch, reduced
from repro.configs.base import AmmConfig
from repro.core.guards import GuardConfig
from repro.models import ModelRuntime, lm_init
from repro.serve.engine import Request, Scheduler
from repro.serve.kv_cache import KV_BLOCK, memory_report

WL, VBL = 8, 5
SLOTS = 3
MAX_LEN = 2 * KV_BLOCK


@pytest.fixture(scope="module")
def lm():
    cfg = reduced(get_arch("qwen2-0.5b"))
    cfg = dataclasses.replace(
        cfg, amm=AmmConfig(mode="bitexact", mul="bbm0", wl=WL, param=VBL,
                           apply_to="attn"))
    rt = ModelRuntime.build(cfg)
    params = lm_init(cfg, jax.random.key(0))
    return cfg, rt, params


def _sched(lm, slots=SLOTS, **kw):
    cfg, rt, params = lm
    kw.setdefault("kv_codes", True)
    return Scheduler(cfg, rt, params, slots, MAX_LEN, continuous=True, **kw)


def _drain(sched, cap=300):
    steps = 0
    while sched.step():
        steps += 1
        assert steps < cap, "scheduler failed to terminate"
    return steps


def _solo_stream(lm, prompt, max_new, *, kv_codes=True):
    """The reference stream: same scheduler, same slot count, one request."""
    sched = _sched(lm, kv_codes=kv_codes)
    req = Request(rid=0, prompt=list(prompt), max_new=max_new)
    sched.submit(req)
    _drain(sched)
    assert req.done and req.error is None
    return req.out


def _run_interleaved(lm, arrivals, *, kv_codes=True):
    """Drive one continuous scheduler through an arrival schedule.

    ``arrivals``: [(step, prompt, max_new)] sorted by step; requests are
    submitted right before the scheduler step they arrive at.
    """
    sched = _sched(lm, kv_codes=kv_codes)
    reqs = []
    t, idx = 0, 0
    while True:
        while idx < len(arrivals) and arrivals[idx][0] <= t:
            _, prompt, max_new = arrivals[idx]
            r = Request(rid=idx, prompt=list(prompt), max_new=max_new)
            reqs.append(r)
            sched.submit(r)
            idx += 1
        n = sched.step()
        t += 1
        if n == 0 and idx >= len(arrivals) and not sched.queue:
            break
        assert t < 500, "interleaved run failed to terminate"
    return sched, reqs


def _random_arrivals(rng, vocab, n=4):
    arrivals = []
    step = 0
    for _ in range(n):
        step += int(rng.integers(0, 3))
        plen = int(rng.integers(0, 9))          # 0 = empty prompt
        prompt = rng.integers(1, vocab, plen).tolist()
        arrivals.append((step, prompt, int(rng.integers(1, 5))))
    return arrivals


# ------------------------------------------------ solo-vs-batched bitwise
def _assert_conformant(lm, seed, *, kv_codes):
    cfg = lm[0]
    rng = np.random.default_rng(seed)
    arrivals = _random_arrivals(rng, cfg.vocab)
    sched, reqs = _run_interleaved(lm, arrivals, kv_codes=kv_codes)
    assert sched.stats["completed"] == len(reqs)
    solo_memo = {}
    for r, (_, prompt, max_new) in zip(reqs, arrivals):
        assert r.done and r.error is None
        key = (tuple(prompt), max_new)
        if key not in solo_memo:
            solo_memo[key] = _solo_stream(lm, prompt, max_new,
                                          kv_codes=kv_codes)
        assert r.out == solo_memo[key], (
            f"request {r.rid} (seed {seed}): batched stream {r.out} != "
            f"solo stream {solo_memo[key]}")


def test_streams_bitwise_equal_to_solo_runs_code_cache(lm):
    """Random interleavings, int-code cache: every stream == its solo run."""
    for seed in (7, 23):
        _assert_conformant(lm, seed, kv_codes=True)


def test_streams_bitwise_equal_to_solo_runs_float_cache(lm):
    """Same contract on the float cache — continuous batching alone must
    not change anyone's bits either (per-slot requantize scales)."""
    _assert_conformant(lm, 11, kv_codes=False)


@settings(max_examples=5, deadline=None)
@given(st.integers(min_value=0, max_value=2 ** 31 - 1))
def test_streams_conformant_property(lm, seed):
    """Hypothesis-driven interleavings (skips when hypothesis is absent:
    the seeded trials above keep the contract pinned in CI)."""
    _assert_conformant(lm, seed, kv_codes=True)


# ------------------------------------------------------ scheduling policy
def test_fifo_admission_under_slot_contention(lm):
    """One slot, three requests: admission and completion follow
    submission order, one prefill per step."""
    sched = _sched(lm, slots=1)
    reqs = [Request(rid=i, prompt=[i + 1], max_new=2) for i in range(3)]
    for r in reqs:
        sched.submit(r)
    done_order, first_tok_order = [], []
    while sched.step() or sched.queue:
        for r in reqs:
            if r.out and r.rid not in first_tok_order:
                first_tok_order.append(r.rid)
            if r.done and r.rid not in done_order:
                done_order.append(r.rid)
    assert first_tok_order == [0, 1, 2]
    assert done_order == [0, 1, 2]


def test_resident_decodes_every_step_while_prompts_queue(lm):
    """Prefill/decode disaggregation: with a queue of long prompts and
    ``max_prefills_per_step=1``, a resident request still gains exactly
    one token every scheduler step — admissions cost it wall-clock only,
    never a decode turn."""
    sched = _sched(lm)
    resident = Request(rid=0, prompt=[1, 2], max_new=12)
    sched.submit(resident)
    sched.step()                      # prefill emits token 1, decode adds 1
    assert len(resident.out) == 2
    long = list(range(1, 13))
    for i in range(1, 4):
        sched.submit(Request(rid=i, prompt=long, max_new=2))
    prev_out, prev_pre = len(resident.out), sched.stats["prefills"]
    while not resident.done:
        sched.step()
        assert len(resident.out) - prev_out == 1
        assert sched.stats["prefills"] - prev_pre <= 1
        prev_out, prev_pre = len(resident.out), sched.stats["prefills"]
    assert resident.error is None and len(resident.out) == 12


def test_slot_recycled_after_midstream_poison(lm):
    """A mid-stream decode failure frees its slot (cache slice zeroed for
    the next admission) and never leaks: the neighbour finishes, and a
    request submitted afterwards is served by the recycled slot."""
    sched = _sched(lm, slots=2, max_retries=1)
    inner = sched._default_fn
    state = {"calls": 0}

    def fn(p, t, c, q):
        state["calls"] += 1
        # decode call 3 fails, call 4 exhausts the retry, call 5 is the
        # slot-0 isolation probe reproducing it -> slot 0 is the poison
        if 3 <= state["calls"] <= 5:
            raise RuntimeError("mid-stream fault")
        return inner(p, t, c, q)

    sched.decode_fn = fn
    first = Request(rid=0, prompt=[1, 2], max_new=8)
    second = Request(rid=1, prompt=[3], max_new=3)
    sched.submit(first)
    sched.submit(second)
    _drain(sched)
    assert first.done and first.error and "fault" in first.error
    assert second.done and second.error is None and len(second.out) == 3
    assert sched.stats["failed"] == 1 and sched.stats["probes"] >= 1
    assert all(s is None for s in sched.slots)
    assert (sched.pos == 0).all()
    late = Request(rid=2, prompt=[5, 6], max_new=2)
    sched.submit(late)
    _drain(sched)
    assert late.done and late.error is None
    # the recycled slot serves the same bits as a fresh scheduler
    assert late.out == _solo_stream(lm, [5, 6], 2)


def test_deadline_evicts_in_continuous_mode(lm):
    sched = _sched(lm)
    req = Request(rid=0, prompt=[1, 2], max_new=20, deadline=3)
    sched.submit(req)
    _drain(sched)
    assert req.done and req.error == "deadline"
    assert sched.stats["deadline_expired"] == 1
    assert all(s is None for s in sched.slots)


def test_prompt_near_cap_terminates(lm):
    sched = _sched(lm)
    req = Request(rid=0, prompt=list(range(1, MAX_LEN - 1)), max_new=8)
    sched.submit(req)
    _drain(sched)
    assert req.done and req.error is None and 1 <= len(req.out) <= 8


# --------------------------------------------------- code-cache contract
def test_code_cache_dtype_and_memory_ratio(lm):
    """wl=8 codes are int8 and halve the bf16 cache bytes exactly; the
    per-block scale planes are accounted separately and stay small."""
    cfg, _, _ = lm
    sched = _sched(lm)
    assert sched.caches["k_codes"].dtype == jnp.int8
    assert sched.caches["k_scale"].dtype == jnp.float32
    rep = memory_report(cfg, SLOTS, MAX_LEN, wl=WL)
    assert rep["ratio_codes"] == 2.0
    assert rep["ratio_total"] > 1.5
    assert rep["scale_overhead"] < 0.25


def test_kv_codes_requires_attention_routing():
    cfg = reduced(get_arch("qwen2-0.5b"))
    cfg = dataclasses.replace(
        cfg, amm=AmmConfig(mode="bitexact", mul="bbm0", wl=WL, param=VBL,
                           apply_to="mlp"))          # attention not routed
    rt = ModelRuntime.build(cfg)
    params = lm_init(cfg, jax.random.key(0))
    with pytest.raises(ValueError, match="attention lowering"):
        Scheduler(cfg, rt, params, 1, MAX_LEN, kv_codes=True)


def test_kv_codes_rejects_exact_budget_guard(lm):
    """The guard's sampled budget audit replays steps on the exact
    datapath, which cannot read an int-code cache — rejected up front."""
    cfg, rt, params = lm
    guard = GuardConfig(budget_abs=0.0, budget_every=1)
    with pytest.raises(ValueError, match="guard budget audit"):
        Scheduler(cfg, rt, params, 1, MAX_LEN, kv_codes=True, guard=guard)


def _fake_programs(vocab):
    """(prefill, decode, calls): serving programs that log their calls;
    a prefill adds 10 to every cache leaf and predicts its last position
    + 1, a decode adds 1 and predicts each row's token + 1."""
    calls = []

    def onehot(idx):
        return jax.nn.one_hot(jnp.asarray(idx), vocab)

    def prefill(p, tokens, caches, start=0):
        calls.append(("prefill", np.asarray(tokens)[0].tolist(), start))
        caches = jax.tree.map(lambda c: c + 10, caches)
        return onehot([start + tokens.shape[1]]), caches

    def decode(p, tokens, caches, pos):
        calls.append(("decode", np.asarray(tokens)[:, 0].tolist(),
                      np.asarray(pos).tolist()))
        caches = jax.tree.map(lambda c: c + 1, caches)
        return onehot(np.asarray(tokens)[:, 0] + 1), caches
    return prefill, decode, calls


def test_prefill_chunks_feed_one_prompt_a_step(lm):
    """``prefill_chunk``: a prompt is fed a chunk a step from the position
    the last chunk reached, one prompt at a time; a resident gains one
    token every step meanwhile; the fed slot is a dead row (token 0,
    position 0) of the decode steps until its last chunk, whose slice
    then replaces whatever those steps wrote into the slot."""
    cfg, rt, params = lm
    prefill, decode, calls = _fake_programs(cfg.vocab)
    sched = Scheduler(cfg, rt, params, 2, MAX_LEN, continuous=True,
                      decode_fn=decode, prefill_fn=prefill, prefill_chunk=2)
    resident = Request(rid=0, prompt=[1], max_new=10)
    fed = Request(rid=1, prompt=[3, 4, 5, 6, 7], max_new=2)
    sched.submit(resident)
    sched.step()
    calls.clear()
    sched.submit(fed)
    for n in range(3):
        before = len(resident.out)
        sched.step()
        assert len(resident.out) == before + 1
        assert fed.out == ([] if n < 2 else [5, 6])
    assert [c for c in calls if c[0] == "prefill"] == [
        ("prefill", [3, 4], 0), ("prefill", [5, 6], 2), ("prefill", [7], 4)]
    decodes = [c for c in calls if c[0] == "decode"]
    assert [d[1][1] for d in decodes[:2]] == [0, 0]      # dead row
    assert [d[2][1] for d in decodes[:2]] == [0, 0]
    assert decodes[2][1][1] == 5 and decodes[2][2][1] == 5
    # the fed slot: 3 chunks' writes, then the one decode step since
    # (its first token 5 came from the last chunk, 6 from that step)
    leaf = jax.tree.leaves(sched.caches)[0]
    assert float(leaf.reshape(leaf.shape[0], 2, -1)[0, 1, 0]) == 31
    assert sched.stats["prefills"] == 2


def test_prefill_chunks_admit_several_whole_prompts_a_step(lm):
    """With ``max_prefills_per_step`` above 1, a step admits prompts of
    one chunk until the cap, and stops at a prompt left in flight; the
    next step only feeds that prompt on."""
    cfg, rt, params = lm
    prefill, decode, calls = _fake_programs(cfg.vocab)
    sched = Scheduler(cfg, rt, params, 4, MAX_LEN, continuous=True,
                      decode_fn=decode, prefill_fn=prefill, prefill_chunk=2,
                      max_prefills_per_step=3)
    for i, prompt in enumerate([[1, 2], [3], [4, 5, 6], [7]]):
        sched.submit(Request(rid=i, prompt=prompt, max_new=4))
    sched.step()
    assert [c[1:] for c in calls if c[0] == "prefill"] == [
        ([1, 2], 0), ([3], 0), ([4, 5], 0)]
    calls.clear()
    sched.step()
    assert [c[1:] for c in calls if c[0] == "prefill"] == [([6], 2)]
    calls.clear()
    sched.step()
    assert [c[1:] for c in calls if c[0] == "prefill"] == [([7], 0)]


def test_prefill_chunks_drain_a_lone_prompt(lm):
    """A prompt in flight counts as live: a caller that steps while
    ``step()`` returns non-zero drains a lone chunked request."""
    cfg, rt, params = lm
    prefill, decode, calls = _fake_programs(cfg.vocab)
    sched = Scheduler(cfg, rt, params, 2, MAX_LEN, continuous=True,
                      decode_fn=decode, prefill_fn=prefill, prefill_chunk=2)
    req = Request(rid=0, prompt=[3, 4, 5, 6, 7], max_new=3)
    sched.submit(req)
    assert _drain(sched) == 4   # 3 feed the chunks (the last decodes too)
    assert req.done and req.out == [5, 6, 7]


def test_prefill_chunk_requires_continuous_mode(lm):
    cfg, rt, params = lm
    with pytest.raises(ValueError, match="prefill_chunk"):
        Scheduler(cfg, rt, params, 1, MAX_LEN, prefill_chunk=4)
