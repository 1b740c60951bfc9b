"""Serving engine: prefill + decode steps and a slot-based batch scheduler.

``make_serve_fns`` builds the two jitted entry points the dry-run lowers:

  prefill_fn(params, tokens, caches[, start]) -> (logits_last, caches)
  decode_fn(params, tokens_1, caches, pos)  -> (logits, caches)

A MoE model's programs return a third output, each token's expert ids
per MoE layer, (moe layers, B, S, k) int32, left on the device; the
scheduler passes it by (a caller that wraps the programs can keep it).

The KV caches are sharded by logical rules (batch over data, kv_heads over
model, MLA latent over seq on model — see parallel/logical.py), and decode
donates the cache buffers so each step updates in place.

``Scheduler`` serves LM requests from a fixed pool of batch slots in two
modes.  The legacy flush mode (``continuous=False``) admits only into an
idle batch and walks every resident in lockstep — the homogeneous-position
simplification.  Continuous mode (``continuous=True``) admits per step
into any free slot, prefills the prompt as one batch-1 dispatch against
the slot's cache slice (so a long prompt never stalls resident decodes),
decodes all residents with per-slot positions, and evicts on completion
or failure.  With ``kv_codes=True`` the cache holds wl-bit int codes plus
per-block scales (``serve.kv_cache``): token representations are frozen
at write time, so each request's token stream is bitwise-identical to its
solo run — the batch-invariance contract tests/test_serve_continuous.py
pins (the requantize-per-call float cache cannot make it under staggered
admission).

``FilterbankEngine`` is the batched request path for the paper's own
workload: FIR filtering requests accumulate into channel slots and are
served by a single multi-channel Broken-Booth filterbank dispatch
(``dsp.fir_apply``), one kernel call per flush instead of one per signal.
The tap banks are fixed for the engine's lifetime, so their quantization
and Booth recode happen exactly once, at construction, via
``dsp.PrecodedBank``; every flush gathers the cached digit planes by
request index instead of re-deriving them.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import trace
from ..configs.base import ArchConfig
from ..core.guards import GuardConfig, finite_rows
from ..models import ModelRuntime, init_cache, lm_amm_planes, lm_apply
from ..parallel.logical import (RULES, RULES_MULTIPOD, batch_pspec,
                                is_multipod, spec_to_pspec, tree_shardings)
from .kv_cache import (KV_BLOCK, batch_axis_tree, code_cache_logical_axes,
                       init_code_cache, reset_slot, slot_put, slot_take)

__all__ = ["cache_logical_axes", "make_serve_fns", "Request", "Scheduler",
           "FilterRequest", "FilterbankEngine"]


def cache_logical_axes(cfg: ArchConfig, *,
                       kv_codes: bool = False) -> Dict[str, Any]:
    """Logical axes for every cache leaf (mirrors models.init_cache).

    kv_codes=True mirrors ``serve.kv_cache.init_code_cache`` instead.
    """
    if kv_codes:
        return code_cache_logical_axes(cfg)
    if cfg.family in ("dense", "vlm", "audio"):
        kvax = ("layers", "batch", "seq", "kv_heads", "head_dim")
        c = {"k": kvax, "v": kvax}
        if cfg.is_encoder_decoder:
            c["xk"] = kvax
            c["xv"] = kvax
        return c
    if cfg.family == "moe":
        if cfg.use_mla:
            # no head axis to shard: shard the *sequence* over model
            return {"latent": ("layers", "batch", "seq_model", "kv_latent")}
        kvax = ("layers", "batch", "seq", "kv_heads", "head_dim")
        return {"k": kvax, "v": kvax}
    if cfg.family == "ssm":
        return {"ssm": ("layers", "batch", "ssm_heads", "head_dim",
                        "ssm_state"),
                "conv": ("layers", "batch", "conv", "ssm_inner")}
    if cfg.family == "hybrid":
        return {"ssm": ("layers", None, "batch", "ssm_heads", "head_dim",
                        "ssm_state"),
                "conv": ("layers", None, "batch", "conv", "ssm_inner"),
                "k": ("layers", "batch", "seq", "kv_heads", "head_dim"),
                "v": ("layers", "batch", "seq", "kv_heads", "head_dim")}
    raise ValueError(cfg.family)


def cache_shardings(cfg: ArchConfig, mesh: Mesh, batch: int, max_len: int,
                    *, kv_codes: bool = False, kv_wl: int = 16,
                    kv_block: int = KV_BLOCK):
    from ..models import init_cache
    rules = dict(RULES_MULTIPOD if is_multipod(mesh) else RULES)
    rules["seq_model"] = "model"
    if kv_codes:
        structs = jax.eval_shape(lambda: init_code_cache(
            cfg, batch, max_len, wl=kv_wl, block=kv_block))
    else:
        structs = jax.eval_shape(lambda: init_cache(cfg, batch, max_len))
    return jax.tree.map(
        lambda axes, st: NamedSharding(
            mesh, spec_to_pspec(axes, rules, tuple(st.shape), mesh)),
        cache_logical_axes(cfg, kv_codes=kv_codes), structs,
        is_leaf=lambda x: isinstance(x, tuple))


def make_serve_fns(cfg: ArchConfig, rt: ModelRuntime, mesh: Mesh, *,
                   batch: int, max_len: int, amm_planes=None,
                   kv_codes: bool = False, kv_block: int = KV_BLOCK):
    """(prefill_fn, decode_fn) jitted with explicit shardings.

    amm_planes: optional ``lm_amm_planes`` cache for the bitexact
    approximate-matmul datapath — serving weights are fixed, so the
    weight-side quantize + Booth decode happens once here instead of in
    every prefill/decode step (the closures capture the concrete planes).
    Attention routing (``AmmConfig.apply_to`` "attn"/"all") needs no
    wiring beyond ``rt``: the score/value products are activation x
    activation, quantized per step inside ``lm_apply`` — there is no
    weight side for a plane cache to hoist (docs/attention.md).

    kv_codes=True shards the int-code cache layout instead (requires an
    active Booth-family bitexact attention lowering on ``rt``).  ``pos``
    accepts a scalar or a (B,) per-slot vector either way (the vector is
    replicated — it is B int32s).  Continuous-mode prefill calls the
    prefill fn on batch-1 slot slices, retracing once per distinct prompt
    length, and per first position ``start`` (static, positional) where
    the scheduler feeds prompts in chunks (NamedShardings are
    shape-agnostic, so the same jitted fn serves both the warmup
    full-batch prefill and the slot slices).
    """
    from ..models import lm_logical_axes, lm_table
    if kv_codes and rt.amm.attn_lowering is None:
        raise ValueError("kv_codes serving requires an active Booth-family "
                         "bitexact amm attention lowering")
    p_rules = RULES_MULTIPOD if is_multipod(mesh) else RULES
    p_sh = tree_shardings(lm_logical_axes(cfg), mesh, p_rules,
                          shapes_tree=lm_table(cfg))
    c_sh = cache_shardings(
        cfg, mesh, batch, max_len, kv_codes=kv_codes,
        kv_wl=(rt.amm.attn_lowering[0] if kv_codes else 16),
        kv_block=kv_block)
    b_sh = NamedSharding(mesh, batch_pspec(mesh, batch))
    scalar = NamedSharding(mesh, P())

    routing = cfg.family == "moe"

    def outputs(logits, aux, new_caches):
        if routing:
            return logits[:, -1], new_caches, aux["expert_ids"]
        return logits[:, -1], new_caches

    def prefill(params, tokens, caches, start=0, encoder_embeds=None):
        # the tokens from position ``start`` on (static: 0 unless the
        # scheduler feeds the prompt in chunks); a MoE model takes it as a
        # Python int, so that its latent attention reads the prompt's
        # positions
        return outputs(*lm_apply(
            params, cfg, rt, tokens, mode="decode", caches=caches,
            pos=start if routing else jnp.int32(start),
            encoder_embeds=encoder_embeds, amm_planes=amm_planes))

    def decode(params, tokens, caches, pos, encoder_embeds=None):
        return outputs(*lm_apply(
            params, cfg, rt, tokens, mode="decode", caches=caches, pos=pos,
            encoder_embeds=encoder_embeds, amm_planes=amm_planes))

    enc_sh = (b_sh,) if cfg.is_encoder_decoder else ()
    out_sh = (b_sh, c_sh) + ((scalar,) if routing else ())
    prefill_j = jax.jit(prefill, in_shardings=(p_sh, b_sh, c_sh) + enc_sh,
                        out_shardings=out_sh, static_argnums=(3,))
    decode_j = jax.jit(decode,
                       in_shardings=(p_sh, b_sh, c_sh, scalar) + enc_sh,
                       out_shardings=out_sh,
                       donate_argnums=(2,))
    return prefill_j, decode_j


@dataclasses.dataclass
class FilterRequest:
    rid: int
    signal: np.ndarray            # 1-D real samples
    bank: int = 0                 # which tap bank filters this request


class FilterbankEngine:
    """Batched FIR serving: N pending requests -> one filterbank dispatch.

    Tap banks are designed/passed once at construction; each request names
    the bank that should filter it.  Construction also quantizes and
    Booth-precodes the banks exactly once (``dsp.PrecodedBank``) — the
    decode phase of the Broken-Booth datapath never runs again for the
    engine's lifetime, and the cached digit planes double as the dot
    form's correction planes, so every flush picks the exact-dot +
    correction lowering automatically (``form=None``; pass ``form="rows"``
    to pin the row emulation).  ``flush`` pads the pending signals to a
    common length, stacks them into a (C, N) batch, gathers the
    per-request banks out of the precoded cache (an index, not a
    re-quantize/re-recode), runs the whole batch through ``dsp.fir_apply``
    (host or Pallas backend) in a single call, and returns each request's
    output trimmed back to its own length.

    Building an engine installs ``repro.trace.gc_spans()`` (once per
    process): while a profiler trace runs, every garbage collection of the
    process is written to it as a ``repro.host.gc`` span.
    """

    def __init__(self, h_banks: np.ndarray, spec, *, backend: str = "host",
                 max_channels: int = 64, block: int = 512,
                 form: Optional[str] = None,
                 guard: Optional[GuardConfig] = None, max_retries: int = 1):
        from ..dsp.fir import BBM_KINDS, PrecodedBank, fir_apply
        from ..kernels.booth_rows import resolve_form
        h_banks = np.atleast_2d(np.asarray(h_banks, np.float64))
        self.h_banks = h_banks
        self.spec = spec
        self.backend = backend
        self.max_channels = max_channels
        self.block = block
        resolve_form(form)    # fail fast: flush() dispatches before it
        if form == "dot" and (spec.name not in BBM_KINDS or spec.wl > 16):
            # reject at construction what every flush would reject — the
            # whole queue would otherwise drain straight into quarantine
            raise ValueError(f"form='dot' needs a Booth-family spec at "
                             f"wl <= 16, not {spec}")
        self.form = form          # "rows" | "dot" | None (auto: dot)
        self.guard = guard
        self.max_retries = max_retries
        self._apply = fir_apply
        # decode phase hoisted out of the serving hot loop: built once here,
        # reused (gathered by request index) across every flush.  Both
        # backends read the digit planes now — they double as the dot
        # form's correction planes — so always decode eagerly; the bank
        # itself skips the decode for specs no kernel form implements.
        self.bank = PrecodedBank(h_banks, spec)
        self._pending: List[FilterRequest] = []
        self._next_rid = 0
        self._dispatches = 0      # audit cadence counter (guard.budget_every)
        # requests the degradation path gave up on: {rid: repr(error)}.
        # Quarantined, not retried — resubmit explicitly to try again.
        self.failed: Dict[int, str] = {}
        self.stats = {"dispatches": 0, "served": 0, "retries": 0,
                      "bisections": 0, "quarantined": 0, "guard_trips": 0,
                      "exact_reserves": 0}
        trace.gc_spans()

    def submit(self, signal: np.ndarray, bank: int = 0) -> int:
        """Queue one signal; returns its request id."""
        if not 0 <= bank < len(self.h_banks):
            raise ValueError(f"unknown tap bank {bank}")
        rid = self._next_rid
        self._next_rid += 1
        self._pending.append(FilterRequest(rid, np.asarray(signal), bank))
        return rid

    def flush(self) -> Dict[int, np.ndarray]:
        """Serve every pending request; returns {rid: filtered signal}.

        Degradation path: a raising backend is retried up to
        ``max_retries`` times; a batch that still fails is bisected so the
        poison request ends up alone and is *quarantined* (recorded in
        ``self.failed``, ejected from the queue) while every healthy
        request in the same batch is still served.  The queue is dequeued
        before serving on purpose — the old dispatch-before-dequeue order
        meant one poison request re-raised out of every future ``flush``
        and wedged the queue permanently.  With ``guard`` set, per-channel
        runtime guards run on every flush (finite outputs; sampled error
        budget vs the exact-Booth datapath) and a tripped channel is
        transparently re-served on the exact datapath.
        """
        results: Dict[int, np.ndarray] = {}
        with trace.span("fir.flush"):
            while self._pending:
                batch = self._pending[: self.max_channels]
                # dequeue *before* serving: failures below are retried,
                # bisected, and at worst quarantined — never left to wedge
                # the queue for every later flush
                self._pending = self._pending[self.max_channels:]
                self._serve(batch, results)
        return results

    def _stack(self, batch: List[FilterRequest]) -> np.ndarray:
        with trace.span("fir.stack"):
            n = max(len(r.signal) for r in batch)
            x = np.zeros((len(batch), n))
            for c, r in enumerate(batch):
                x[c, : len(r.signal)] = r.signal
        return x

    def _dispatch(self, batch: List[FilterRequest]) -> np.ndarray:
        """One filterbank call with bounded retry; raises when exhausted."""
        x = self._stack(batch)
        with trace.span("fir.bank_take"):
            h = self.bank.take([r.bank for r in batch])
        for attempt in range(self.max_retries + 1):
            self.stats["dispatches"] += 1
            self._dispatches += 1
            try:
                return np.asarray(self._apply(
                    x, h, self.spec, backend=self.backend, block=self.block,
                    form=self.form))
            except Exception:
                if attempt == self.max_retries:
                    raise
                self.stats["retries"] += 1

    def _serve(self, batch: List[FilterRequest],
               results: Dict[int, np.ndarray]):
        """Serve one batch with bisection quarantine + runtime guards."""
        try:
            y = self._dispatch(batch)
        except Exception as e:
            if len(batch) == 1:
                # the poison request, isolated: eject it instead of
                # livelocking the engine
                self.failed[batch[0].rid] = repr(e)
                self.stats["quarantined"] += 1
                return
            # batch bisection: each half retries independently, so the
            # poison request converges to a singleton and every healthy
            # neighbour is still served this flush
            self.stats["bisections"] += 1
            mid = len(batch) // 2
            self._serve(batch[:mid], results)
            self._serve(batch[mid:], results)
            return
        bad = self._guard_channels(batch, y)
        with trace.span("fir.split"):
            for c, r in enumerate(batch):
                if c in bad:
                    results[r.rid] = self._reserve_exact(r)
                else:
                    results[r.rid] = y[c, : len(r.signal)]
                self.stats["served"] += 1

    def _guard_channels(self, batch: List[FilterRequest],
                        y: np.ndarray) -> set:
        """Indices of channels whose runtime guards tripped this dispatch."""
        if self.guard is None:
            return set()
        from ..core.guards import guard_rows
        y_exact = None
        if self.guard.budget_active \
                and self._dispatches % self.guard.budget_every == 0:
            # sampled accuracy audit: the same batch through the exact
            # datapath (one extra dispatch on audited flushes only)
            y_exact = self._exact_batch(batch)
        rep = guard_rows(y, self.guard, y_exact=y_exact)
        if rep.ok:
            return set()
        bad = {c for c in range(len(batch)) if not rep.row_ok[c]}
        self.stats["guard_trips"] += len(bad)
        return bad

    def _exact_spec(self):
        """Exact-Booth comparand at this engine's word length."""
        from ..core.multipliers import MulSpec
        return MulSpec("booth", self.spec.wl, 0)

    def _exact_batch(self, batch: List[FilterRequest]) -> np.ndarray:
        x = self._stack(batch)
        h = self.h_banks[[r.bank for r in batch]]
        return np.asarray(self._apply(x, h, self._exact_spec(),
                                      backend="host", form=None))

    def _reserve_exact(self, r: FilterRequest) -> np.ndarray:
        """Serve one guard-tripped request on the exact datapath."""
        self.stats["exact_reserves"] += 1
        y = self._apply(r.signal[None, :], self.h_banks[[r.bank]],
                        self._exact_spec(), backend="host", form=None)
        return np.asarray(y)[0]


@dataclasses.dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new: int
    out: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # degradation-path fields: why the request failed (None = healthy),
    # an optional per-request deadline in scheduler steps, whether the
    # output was re-served on the exact datapath after a guard trip
    error: Optional[str] = None
    deadline: Optional[int] = None
    exact: bool = False
    _pending: List[int] = dataclasses.field(default_factory=list)
    _steps: int = 0


class Scheduler:
    """Slot-based LM batch scheduler over the jitted decode step.

    Two scheduling modes:

      * ``continuous=False`` (legacy flush mode): requests are admitted
        only when every resident is at the same depth, prompts are fed one
        token per step through the batched decode, and the whole batch
        walks in lockstep (the homogeneous-position simplification in
        ``step``).
      * ``continuous=True``: per-step admission into any free slot (FIFO,
        at most ``max_prefills_per_step`` admissions per step so a queue
        of long prompts cannot starve resident decodes), the prompt
        prefilled as ONE batch-1 dispatch against the slot's cache slice
        (or, with ``prefill_chunk``, ``prefill_chunk`` tokens a step: one
        prompt at a time, its slice held aside until the last chunk, so
        no prompt holds a step for more than one chunk and the gap
        between a resident's tokens no longer depends on the admitted
        prompt's length), then per-slot-position batched decode over all
        residents; slots are evicted and their cache slice zeroed for
        reuse on completion or failure.  When the per-row arithmetic is row-independent —
        exact matmuls, or attention-side amm routing whose ``amm_dot``
        vmaps a fresh quantization scale per (slot, head) slice — a
        request's token stream is identical whether it shares the batch
        or runs solo, and with ``kv_codes=True`` its cache bits are too:
        the contract tests/test_serve_continuous.py pins bitwise with
        ``apply_to="attn"``.  MLP amm routing (apply_to "mlp"/"all") is
        the exception: ``amm_dense`` quantizes the activation block with
        one whole-batch scale, so batch composition can move every row's
        code grid.

    ``kv_codes=True`` stores the KV cache as wl-bit int codes plus
    per-block f32 scales (``serve.kv_cache``; requires an active
    Booth-family bitexact amm attention lowering on ``rt``): decode feeds
    frozen cached codes straight into the integer datapath, skipping the
    per-call K/V requantize, and a token's quantized representation never
    drifts as later tokens arrive.

    Building a scheduler installs ``repro.trace.gc_spans()`` (once per
    process), as ``FilterbankEngine`` does.

    Degradation policy (all opt-in, all off on the lean default path):

      * a raising decode step is retried ``max_retries`` times with capped
        exponential backoff (``backoff`` / ``backoff_cap`` seconds);
      * if it still raises, each live slot is *probed* one at a time (its
        token alone, padding elsewhere, against a throwaway cache copy) to
        identify which request the failure follows — poison requests fail
        alone (``Request.error`` set, slot recycled) and the surviving
        slots decode normally the same step.  A failure no probe can
        attribute re-raises: that is systemic, not a poison request.
      * with ``guard`` set, per-slot runtime guards run on the step's
        logits (finite check; sampled error budget vs the exact datapath
        every ``guard.budget_every`` steps) and a tripped request is
        re-served from scratch on the *exact* datapath
        (``AmmConfig.mode="off"``), marked ``Request.exact``;
      * ``Request.deadline`` bounds how many scheduler steps a request may
        hold a slot; past it the request fails with error="deadline".

    Retrying a *donating* ``decode_fn`` (launch/serve.py's jitted step
    donates the caches) requires snapshotting the caches before each call
    — that copy is the price of the robust path and is only paid when
    ``max_retries > 0`` or a guard audit needs the pre-step caches.
    ``stats`` counts steps, retries, probes, failures, guard trips,
    exact re-serves, deadline expiries, and completions.
    """

    def __init__(self, cfg: ArchConfig, rt: ModelRuntime, params,
                 batch_slots: int, max_len: int, decode_fn=None, *,
                 prefill_fn=None, continuous: bool = False,
                 kv_codes: bool = False, kv_block: int = KV_BLOCK,
                 max_prefills_per_step: int = 1,
                 prefill_chunk: Optional[int] = None,
                 guard: Optional[GuardConfig] = None, max_retries: int = 0,
                 backoff: float = 0.0, backoff_cap: float = 1.0):
        self.cfg, self.rt, self.params = cfg, rt, params
        self.slots: List[Optional[Request]] = [None] * batch_slots
        self.pos = np.zeros(batch_slots, np.int32)
        self.max_len = max_len
        if kv_codes:
            if not rt.amm.attn_active or rt.amm.attn_lowering is None:
                raise ValueError(
                    "kv_codes stores the cache as Broken-Booth int codes; "
                    "it requires an active Booth-family bitexact amm "
                    "attention lowering (AmmConfig mode='bitexact', "
                    "Booth-family mul, apply_to 'attn'/'all')")
            if guard is not None and guard.budget_active:
                raise ValueError(
                    "the guard budget audit replays the step on the exact "
                    "datapath, which cannot read an int-code cache — use "
                    "finite-only guards or kv_codes=False")
            self.caches = init_code_cache(
                cfg, batch_slots, max_len,
                wl=rt.amm.attn_lowering[0], block=kv_block)
        else:
            self.caches = init_cache(cfg, batch_slots, max_len)
        self.continuous = continuous
        self.kv_codes = kv_codes
        self.max_prefills_per_step = max_prefills_per_step
        if prefill_chunk is not None and (not continuous
                                          or prefill_chunk < 1):
            raise ValueError("prefill_chunk needs continuous=True and at "
                             f"least one token, got {prefill_chunk}")
        self.prefill_chunk = prefill_chunk
        # (slot, its cache slice, tokens fed) of a prompt fed in chunks
        self._chunking = None
        self._bax = batch_axis_tree(
            cache_logical_axes(cfg, kv_codes=kv_codes))
        self.queue: List[Request] = []
        self.decode_fn = decode_fn
        self.prefill_fn = prefill_fn
        self.guard = guard
        self.max_retries = max_retries
        self.backoff = backoff
        self.backoff_cap = backoff_cap
        self.stats = {"steps": 0, "decoded": 0, "completed": 0,
                      "prefills": 0, "retries": 0, "probes": 0,
                      "failed": 0, "guard_trips": 0, "exact_reserves": 0,
                      "deadline_expired": 0}
        # serving weights are fixed: hoist the bitexact datapath's weight
        # quantize + Booth digit decode out of the decode loop (None for
        # amm modes with nothing to cache).  A supplied decode_fn owns its
        # own closure (launch/serve.py bakes the planes into the jitted
        # fn) — only the fallback path needs a cache here, so don't build
        # and hold a second copy of the (wl//2, K, N) planes.
        self.amm_planes = (lm_amm_planes(cfg, rt.amm, params)
                           if decode_fn is None else None)
        trace.gc_spans()

    def submit(self, req: Request):
        """Queue one request; invalid specs raise here, not mid-serve.

        A prompt of ``max_len`` or more tokens can never produce a token
        (the cache has no position left after the prefill), so it is
        rejected at submit time — the old behaviour was a scheduler
        livelock.  Empty prompts are legal: decoding starts from token 0.
        """
        if req.max_new < 1:
            raise ValueError(f"request {req.rid}: max_new must be >= 1, "
                             f"got {req.max_new}")
        if len(req.prompt) >= self.max_len:
            raise ValueError(
                f"request {req.rid}: prompt of {len(req.prompt)} tokens "
                f"cannot fit max_len={self.max_len} (needs at least one "
                f"free position to decode)")
        self.queue.append(req)

    def _admit(self):
        for i, s in enumerate(self.slots):
            if s is None and self.queue:
                req = self.queue.pop(0)
                self.slots[i] = req
                self.pos[i] = 0
                req._pending = list(req.prompt)     # tokens still to feed
                req._steps = 0

    @staticmethod
    def _unpack(out):
        """(logits, caches) of a serving program's outputs (a MoE
        program's third, its expert ids, is not read here)."""
        return out[0], out[1]

    @staticmethod
    def _pos_arr(pos):
        """Decode position operand: scalar (flush mode) or (B,) vector."""
        return jnp.asarray(pos, jnp.int32)

    def _default_fn(self, p, t, c, q):
        logits, _, new_c = lm_apply(
            p, self.cfg, self.rt, jnp.asarray(t), mode="decode",
            caches=c, pos=self._pos_arr(q), amm_planes=self.amm_planes)
        return logits[:, -1], new_c

    def _default_prefill(self, p, t, c, start=0):
        logits, _, new_c = lm_apply(
            p, self.cfg, self.rt, jnp.asarray(t), mode="decode",
            caches=c, pos=jnp.int32(start), amm_planes=self.amm_planes)
        return logits[:, -1], new_c

    def _fail(self, i: int, reason: str):
        s = self.slots[i]
        s.error = reason
        s.done = True
        self.slots[i] = None
        self.pos[i] = 0
        self.stats["failed"] += 1

    def _snapshot(self):
        """Host-independent copy of the caches (donation-safe retry)."""
        return jax.tree.map(jnp.copy, self.caches)

    def _probe_poison(self, fn, toks, pos, live) -> List[int]:
        """Which live slots does the decode failure follow?

        Each probe decodes one slot's real token with padding everywhere
        else, against a throwaway cache copy (a donating fn consumes it —
        which is fine, it is a copy).  Deterministic poison follows its
        slot; a failure that no single-slot probe reproduces is systemic.
        """
        poison = []
        for i in live:
            t = np.zeros_like(toks)
            t[i] = toks[i]
            self.stats["probes"] += 1
            try:
                fn(self.params, jnp.asarray(t), self._snapshot(),
                   self._pos_arr(pos))
            except Exception:
                poison.append(i)
        return poison

    def _decode_isolated(self, fn, toks, pos, live):
        """The decode step with retry + poison isolation.

        Returns (logits, live) — ``live`` shrinks when poison requests are
        failed out.  Returns (None, live) when nothing is left to decode
        this step; re-raises when the failure is systemic.
        """
        donating = self.decode_fn is not None
        last = None
        for attempt in range(self.max_retries + 1):
            backup = self._snapshot() if donating and self.max_retries \
                else None
            try:
                logits, self.caches = self._unpack(fn(
                    self.params, jnp.asarray(toks), self.caches,
                    self._pos_arr(pos)))
                return logits, live
            except Exception as e:
                last = e
                if backup is not None:
                    self.caches = backup
                if attempt < self.max_retries:
                    self.stats["retries"] += 1
                    if self.backoff > 0:
                        time.sleep(min(self.backoff * (2 ** attempt),
                                       self.backoff_cap))
        if self.max_retries == 0 and donating:
            # no retry budget means no pre-call snapshot was taken and a
            # donating fn has consumed the caches: nothing to salvage
            raise last
        poison = self._probe_poison(fn, toks, pos, live)
        if not poison:
            raise last            # systemic: every single-slot probe passed
        for i in poison:
            self._fail(i, f"decode failed: {last!r}")
        live = [i for i in live if i not in poison]
        if not live:
            return None, live
        toks = toks.copy()
        for i in poison:
            toks[i] = 0
        logits, self.caches = self._unpack(fn(
            self.params, jnp.asarray(toks), self.caches, self._pos_arr(pos)))
        return logits, live

    def _guard_slots(self, logits, toks, pos, pre_caches, live) -> List[int]:
        """Live slots whose runtime guards tripped on this step's logits."""
        if self.guard is None:
            return []
        arr = np.asarray(logits)
        ok = finite_rows(arr) if self.guard.finite \
            else np.ones(arr.shape[0], bool)
        if self.guard.budget_active and pre_caches is not None \
                and self.stats["steps"] % self.guard.budget_every == 0:
            # sampled accuracy audit: the same step on the exact datapath
            exact_logits, _ = self._exact_fn()(self.params,
                                               jnp.asarray(toks),
                                               pre_caches,
                                               self._pos_arr(pos))
            err = np.abs(arr.astype(np.float64)
                         - np.asarray(exact_logits, np.float64))
            ok &= np.where(np.isfinite(err), err, np.inf).mean(axis=-1) \
                <= self.guard.budget_abs
        tripped = [i for i in live if not ok[i]]
        self.stats["guard_trips"] += len(tripped)
        return tripped

    def _rt_exact(self) -> ModelRuntime:
        """This scheduler's runtime with the approximate datapath off."""
        from ..models.common import AmmRuntime
        cfg_off = dataclasses.replace(self.rt.amm.cfg, mode="off")
        return dataclasses.replace(self.rt, amm=AmmRuntime(cfg_off))

    def _exact_fn(self):
        rt = self._rt_exact()

        def fn(p, t, c, q):
            logits, _, new_c = lm_apply(p, self.cfg, rt, jnp.asarray(t),
                                        mode="decode", caches=c, pos=q)
            return logits[:, -1], new_c
        return fn

    def _reserve_exact(self, req: Request):
        """Regenerate one guard-tripped request on the exact datapath.

        From-scratch greedy decode at batch 1 — the robust slow path: a
        guard trip means the approximate output cannot be trusted, so the
        whole request replays on ``AmmConfig.mode="off"``.
        """
        self.stats["exact_reserves"] += 1
        fn = self._exact_fn()
        caches = init_cache(self.cfg, 1, self.max_len)
        req.out = []
        pending = list(req.prompt)
        tok = pending.pop(0) if pending else 0
        pos = 0
        while len(req.out) < req.max_new and pos < self.max_len - 1:
            logits, caches = fn(self.params,
                                jnp.asarray([[tok]], jnp.int32), caches,
                                jnp.int32(pos))
            pos += 1
            if pending:
                tok = pending.pop(0)
            else:
                tok = int(np.asarray(jnp.argmax(logits, axis=-1))[0])
                req.out.append(tok)
        req.exact = True
        req.done = True

    # ------------------------------------------------- continuous batching
    def _finish(self, i: int):
        """Complete slot ``i``: evict and free it for the next admission."""
        s = self.slots[i]
        s.done = True
        self.slots[i] = None
        self.pos[i] = 0
        self.stats["completed"] += 1

    def _prefill_slot(self, i: int):
        """Prefill slot ``i``'s prompt as one batch-1 dispatch.

        The slot's cache slice is carved out (``slot_take``), the whole
        prompt runs through the prefill fn at position 0, and the slice is
        written back — resident decodes in other slots are untouched, so a
        long prompt costs them nothing but wall-clock.  The prefill's last
        logits are the model's prediction past the prompt: the first
        generated token falls out of the prefill itself.  Empty prompts
        prefill the single pad token 0, matching flush-mode semantics
        (decoding starts from token 0).

        With ``prefill_chunk`` one call feeds the next chunk only, from
        the position the last one reached; until the last chunk the slice
        stays aside in ``_chunking`` (the slot's row of the decode steps
        meanwhile is a dead row, whose write the slice replaces).
        """
        req = self.slots[i]
        toks = list(req.prompt) or [0]
        fn = self.prefill_fn or self._default_prefill
        if self._chunking is None:
            with trace.span("sched.slot_take"):
                sub = slot_take(self.caches, self._bax, i)
            start = 0
        else:
            _, sub, start = self._chunking
            self._chunking = None
        end, at = len(toks), ()
        if self.prefill_chunk is not None:
            end, at = min(end, start + self.prefill_chunk), (start,)
        last = None
        with trace.span("sched.prefill"):
            for attempt in range(self.max_retries + 1):
                try:
                    logits, sub = self._unpack(fn(
                        self.params, jnp.asarray([toks[start:end]], jnp.int32),
                        sub, *at))
                    break
                except Exception as e:
                    last = e
                    if attempt < self.max_retries:
                        self.stats["retries"] += 1
                        if self.backoff > 0:
                            time.sleep(min(self.backoff * (2 ** attempt),
                                           self.backoff_cap))
            else:
                self._fail(i, f"prefill failed: {last!r}")
                return
        self.stats["decoded"] += end - start
        if end < len(toks):
            self._chunking = (i, sub, end)
            return
        with trace.span("sched.slot_put"):
            self.caches = slot_put(self.caches, self._bax, sub, i)
        self.pos[i] = len(toks)
        self.stats["prefills"] += 1
        req._pending = []
        with trace.span("sched.first_token"):
            req.out.append(int(np.asarray(jnp.argmax(logits, axis=-1)
                                          ).reshape(-1)[0]))
        if len(req.out) >= req.max_new or self.pos[i] >= self.max_len - 1:
            self._finish(i)

    def _step_continuous(self) -> int:
        """One continuous-batching step: admit, prefill, decode residents.

        Admission is FIFO into free slots, capped at
        ``max_prefills_per_step`` per step — the prefill/decode
        disaggregation knob: residents decode every step regardless of how
        deep the prompt queue is.  Each admission zeroes the slot's cache
        slice (stale codes/values and frozen block scales from the
        previous occupant) before prefilling.  Freshly admitted slots join
        the same step's decode — their (token, position) trajectory is
        self-contained, so step alignment cannot change any request's
        stream.  With ``prefill_chunk`` a step feeds the prompt in flight
        its next chunk, or else admits as above, each admission feeding
        its first chunk, and stops at a prompt left in flight; the slot
        joins the decode once its last chunk is in.
        """
        admitted = 0
        if self._chunking is not None:      # the prompt in flight goes on
            self._prefill_slot(self._chunking[0])
            admitted = self.max_prefills_per_step
        for i in range(len(self.slots)):
            if not self.queue or admitted >= self.max_prefills_per_step \
                    or self._chunking is not None:
                break
            if self.slots[i] is None:
                req = self.queue.pop(0)
                self.slots[i] = req
                req._steps = 0
                req._pending = []
                self.pos[i] = 0
                with trace.span("sched.admit"):
                    with trace.span("sched.reset_slot"):
                        self.caches = reset_slot(self.caches, self._bax, i)
                    self._prefill_slot(i)    # may fail or finish the slot
                admitted += 1
        feeding = self._chunking[0] if self._chunking is not None else None
        live = [i for i, s in enumerate(self.slots)
                if s is not None and i != feeding]
        fed = int(feeding is not None)     # a live request, not yet a row
        if not live:
            return fed
        self.stats["steps"] += 1
        toks = np.zeros((len(self.slots), 1), np.int32)
        for i in live:
            toks[i, 0] = self.slots[i].out[-1]
        pos = self.pos.copy()   # (B,): dead slots write pad at 0, wiped on
        fn = self.decode_fn or self._default_fn       # the next admission
        audit = (self.guard is not None and self.guard.budget_active
                 and self.stats["steps"] % self.guard.budget_every == 0)
        pre_caches = self._snapshot() if audit else None
        n_live = len(live) + fed
        with trace.span("sched.decode"):
            logits, live = self._decode_isolated(fn, toks, pos, live)
        if logits is None:
            return n_live
        for i in self._guard_slots(logits, toks, pos, pre_caches, live):
            self._reserve_exact(self.slots[i])
            self.slots[i] = None
            self.pos[i] = 0
            live = [j for j in live if j != i]
        with trace.span("sched.sample"):
            nxt = np.asarray(jnp.argmax(logits, axis=-1))
        with trace.span("sched.commit"):
            for i in live:
                s = self.slots[i]
                self.pos[i] += 1
                s._steps += 1
                self.stats["decoded"] += 1
                s.out.append(int(nxt[i]))
                if len(s.out) >= s.max_new \
                        or self.pos[i] >= self.max_len - 1:
                    self._finish(i)
                elif s.deadline is not None and s._steps >= s.deadline:
                    self._fail(i, "deadline")
                    self.stats["deadline_expired"] += 1
        return n_live

    def step(self) -> int:
        """One decode step over all live slots; returns #live requests."""
        if self.continuous:
            return self._step_continuous()
        self._admit()
        live = [i for i, s in enumerate(self.slots) if s is not None]
        if not live:
            return 0
        self.stats["steps"] += 1
        toks = np.zeros((len(self.slots), 1), np.int32)
        for i in live:
            s = self.slots[i]
            # peek, don't pop: the prompt token is only consumed once the
            # decode call commits, so a retried step does not lose it
            toks[i, 0] = (s._pending[0] if s._pending
                          else (s.out[-1] if s.out else 0))
        pos = int(self.pos[live[0]])   # homogeneous-pos simplification
        fn = self.decode_fn or self._default_fn
        audit = (self.guard is not None and self.guard.budget_active
                 and self.stats["steps"] % self.guard.budget_every == 0)
        pre_caches = self._snapshot() if audit else None
        n_live = len(live)
        logits, live = self._decode_isolated(fn, toks, pos, live)
        if logits is None:
            return n_live
        for i in self._guard_slots(logits, toks, pos, pre_caches, live):
            self._reserve_exact(self.slots[i])
            self.slots[i] = None
            live = [j for j in live if j != i]
        nxt = np.asarray(jnp.argmax(logits, axis=-1))
        for i in live:
            s = self.slots[i]
            self.pos[i] += 1
            s._steps += 1
            self.stats["decoded"] += 1
            if s._pending:
                s._pending.pop(0)       # committed: the step consumed it
            if not s._pending:           # prompt drained: this step's
                # logits are the model's prediction past the prompt, so
                # the same step that consumes the last prompt token also
                # emits the first generated token (pre-robustness parity)
                s.out.append(int(nxt[i]))
                if len(s.out) >= s.max_new:
                    s.done = True
                    self.slots[i] = None
                    self.stats["completed"] += 1
                    continue
            if self.pos[i] >= self.max_len - 1:
                # cache positions exhausted: finish (or fail, mid-prompt)
                # whether or not the prompt is drained — the old in-branch
                # check livelocked on prompts at the length cap
                if s._pending:
                    self._fail(i, "context exhausted mid-prompt")
                else:
                    s.done = True
                    self.slots[i] = None
                    self.stats["completed"] += 1
            elif s.deadline is not None and s._steps >= s.deadline:
                self._fail(i, "deadline")
                self.stats["deadline_expired"] += 1
        return n_live
