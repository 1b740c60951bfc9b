"""Host spans and device scopes of the serving path.

``span(name)`` marks one phase of a host call: a
``jax.profiler.TraceAnnotation`` named ``repro.<name>``, which the profiler
writes into its trace on the device trace's clock.  With no profiler
running it is a shared no-op context.  While a profiler trace is being
recorded, every span also adds its host-clock time to a table kept in this
process (``recorded()``: calls, seconds and self seconds per name, self
being what no child span covers), so a traced run can read its phase split
without parsing the trace.  The table follows the profiler session, which
is process-wide too.

Rules for the call sites: no span inside a per-request, per-row or
per-layer loop (one span around the loop instead), and no span adds a
device sync; a span wraps the sync the code already has.

The names given to ``jax.named_scope`` inside the jitted programs are the
constants below.  They land in every HLO op's ``op_name`` metadata, where a
reduction of the device trace finds them, and cost nothing at run time.

``gc_spans()`` marks Python's garbage collections as ``repro.host.gc``
spans, so a host stall that is a collection shows as one in a trace.
"""
from __future__ import annotations

import contextlib
import gc
import threading
import time
from typing import Dict

import jax

__all__ = ["PREFIX", "SCOPES", "MOE_SCOPES", "span", "recorded", "clear",
           "gc_spans"]

PREFIX = "repro."

# device scopes (``jax.named_scope``) of the model step
AMM_WEIGHT_DECODE = "amm.weight_decode"  # weight quantize + Booth digit decode
AMM_CONTRACT = "amm.contract"    # activation quantize, contraction, descale
AMM_STE_EXACT = "amm.ste_exact"  # exact product + straight-through compose
ATTN_PROJ = "attn.proj"          # exact Q/K/V/O projections
ATTN_CODE_CACHE = "attn.code_cache"  # int-code cache write + attention
LM_HEAD = "lm.head"              # final norm and the LM head
SCOPES = (AMM_WEIGHT_DECODE, AMM_CONTRACT, AMM_STE_EXACT, ATTN_PROJ,
          ATTN_CODE_CACHE, LM_HEAD)
# the latent-attention and expert layers' scopes (MLA + MoE families)
ATTN_LATENT_CACHE = "attn.latent_cache"  # latent cache write + both latent
                                         # products (scores, values)
MOE_ROUTE = "moe.route"          # router, expert choice and dispatch
MOE_EXPERTS = "moe.experts"      # the routed experts this device holds
MOE_SHARED = "moe.shared"        # the shared experts
MOE_SCOPES = (ATTN_LATENT_CACHE, MOE_ROUTE, MOE_EXPERTS, MOE_SHARED)

_table: Dict[str, list] = {}      # name -> [calls, seconds, self seconds]
_lock = threading.Lock()
_local = threading.local()


def _open_spans() -> list:
    st = getattr(_local, "stack", None)
    if st is None:
        st = _local.stack = []
    return st


class _Span(jax.profiler.TraceAnnotation):
    """A span opened while a profiler trace is recorded: written to the
    trace, and its host-clock time added to the table on exit."""

    def __init__(self, name: str):
        super().__init__(name)
        self.name = name
        self._frame = None

    def __enter__(self):
        super().__enter__()
        self._frame = [time.perf_counter(), 0.0]       # start, child seconds
        _open_spans().append(self._frame)
        return self

    def __exit__(self, *exc):
        fr = self._frame
        dur = time.perf_counter() - fr[0]
        st = _open_spans()
        while st and st.pop() is not fr:
            pass
        if st:
            st[-1][1] += dur
        with _lock:
            rec = _table.setdefault(self.name, [0, 0.0, 0.0])
            rec[0] += 1
            rec[1] += dur
            rec[2] += dur - fr[1]
        return super().__exit__(*exc)


_OFF = contextlib.nullcontext()


def span(name: str):
    """``with span("fir.quantize"):`` marks a phase as
    ``repro.fir.quantize``; a shared no-op when no profiler runs."""
    if jax.profiler.TraceAnnotation.is_enabled():
        return _Span(PREFIX + name)
    return _OFF


def recorded() -> Dict[str, dict]:
    """{span name: {"calls", "s", "self_s"}} of the spans closed while a
    profiler trace was being recorded, since the last ``clear()``."""
    with _lock:
        return {k: {"calls": v[0], "s": v[1], "self_s": v[2]}
                for k, v in _table.items()}


def clear() -> None:
    with _lock:
        _table.clear()


_gc_open: list = []


def _gc_span(phase: str, info: dict) -> None:
    if phase == "start":
        if jax.profiler.TraceAnnotation.is_enabled():
            s = _Span(PREFIX + "host.gc")
            s.__enter__()
            _gc_open.append(s)
    elif _gc_open:
        _gc_open.pop().__exit__(None, None, None)


def gc_spans() -> None:
    """Mark every garbage collection of this process as a ``repro.host.gc``
    span while a profiler trace is recorded (installed once; idempotent)."""
    if _gc_span not in gc.callbacks:
        gc.callbacks.append(_gc_span)
