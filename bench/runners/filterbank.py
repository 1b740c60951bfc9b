"""The paper's FIR filterbank served by ``serve.FilterbankEngine``.

One closed-loop client: it submits a flush's worth of requests, calls
``flush()``, takes the answers and submits the next batch, cycling through
a pool of signals drawn from the seed at set-up.  Set-up builds the engine
(taps quantized and Booth-precoded once) and serves one flush to load or
compile the dispatch.  The window counts every output sample returned.

``correct``: a seeded sample of the answers returned in the window is
recomputed by the plain reference (``reference/fir_bbm.py``) and must
agree bit for bit.
"""
from __future__ import annotations

import copy
import time

import numpy as np

from bench import traffic
from bench.check import Reservoir
from bench.trace import span


def run(run) -> None:
    from repro.core.multipliers import MulSpec
    from repro.serve import FilterbankEngine

    cfg, mix, params = run.cell.config, run.cell.traffic, run.cell.params
    ref = run.cell.reference()
    mul, eng = cfg["multiplier"], cfg["engine"]
    taps = ref.design_taps(cfg["filter"])
    with span("setup.generate"):
        pool = traffic.filterbank_pool(mix, run.seed)
    engine = FilterbankEngine(taps[None, :], MulSpec(mul["kind"], mul["wl"],
                                                     mul["vbl"]),
                              backend=eng["backend"],
                              max_channels=eng["max_channels"],
                              block=eng["block"], form=eng["form"])
    for s in pool[0]:                       # load or compile the dispatch
        engine.submit(s)
    engine.flush()

    sample = Reservoir(params["check_requests"],
                       traffic.rng(run.seed, "check"))
    flush_s, samples, attempted, served, k = [], 0, 0, 0, 0
    with run.window():
        end = time.perf_counter() + run.seconds
        while True:
            p = k % len(pool)
            with span("client.submit"):
                rids = [engine.submit(s) for s in pool[p]]
            t = time.perf_counter()
            with span("engine.flush"):
                res = engine.flush()
            flush_s.append(time.perf_counter() - t)
            with span("client.collect"):
                got = [res.get(rid) for rid in rids]
                ok = [j for j, y in enumerate(got) if y is not None]
                samples += sum(len(got[j]) for j in ok)
                served += len(ok)
                for j, slot in sample.offer(len(ok)):
                    sample.put(slot, (p, ok[j], np.array(got[ok[j]])))
            attempted += len(rids)
            k += 1
            if time.perf_counter() >= end:
                break
    run.read_memory_peak()

    run.metrics["samples_per_s"] = samples / run.window_s
    run.attempted, run.failed = attempted, attempted - served
    run.host["flush_s"] = flush_s
    run.counters.update(flushes=len(flush_s), samples=samples,
                        taps=len(taps),
                        dispatches=engine.stats["dispatches"],
                        flush_ms_quartiles=[float(q) * 1e3 for q in
                                            np.percentile(flush_s,
                                                          [25, 50, 75])],
                        flush_ms_max=float(np.max(flush_s)) * 1e3)
    del engine, res

    # the comparison, after the window: every sampled answer bit for bit
    wl, vbl = mul["wl"], mul["vbl"]
    want = [ref.fir(pool[p][j], taps, wl, vbl) for p, j, _ in sample.items]
    run.counters["checked_samples"] = sum(len(y) for _, _, y in sample.items)
    judge(run, [y for _, _, y in sample.items], want)
    # a control: the reference one precision down in the program's place
    run.control_runs = {}
    for name in run.controls:
        ctl = cfg["controls"][name]
        got = [ref.fir(pool[p][j], taps, ctl["wl"], ctl["vbl"])
               for p, j, _ in sample.items]
        cr = copy.copy(run)
        cr.checks, cr.counters = [], dict(run.counters)
        judge(cr, got, want)
        run.control_runs[name] = cr


def judge(run, got, want) -> None:
    params = run.cell.params
    bad = sum(int(np.sum(y != w)) for y, w in zip(got, want))
    run.check("mismatched_samples", bad, params["limits"]["mismatched_samples"])
    run.check("failed_requests", run.failed, 0)
    n = len(got)
    run.check("checked_answers", n, params["check_requests"],
              ok=n == params["check_requests"])
