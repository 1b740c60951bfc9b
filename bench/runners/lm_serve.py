"""An LM served by ``make_serve_fns`` and the continuous ``Scheduler``.

Closed loop: each client holds one request at a time and sends its next
as soon as the last one finishes.  Set-up makes the weights on the device
in one jitted call from the seed, builds the serving programs as the
launcher does (plane cache if it fits, int-code KV cache), loads or
compiles the decode step and one prefill per prompt length of the mix,
and fills every slot.  The window steps the scheduler; a token counts at
the end of the step that produced it.

Every call the scheduler makes into the serving programs is logged, from
the first on: which slot a prompt was prefilled into, and the tokens and
positions of every decode step's rows, with the request each live row
belongs to.  That costs a list append per call and no device sync.

``correct``: after the window the plain reference (``reference/<name>``)
replays that log from the start, every batch as it was, and every token
served by the window's close is compared: none may lie further below the
reference's best logit than the limit.
"""
from __future__ import annotations

import copy
import dataclasses
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import counts, traffic
from bench.trace import span


def arch(c: dict):
    """The program's configuration object for this config file."""
    from repro.configs.base import AmmConfig, ArchConfig
    h = c["num_attention_heads"]
    return ArchConfig(
        name=c["name"], family="dense", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=h,
        n_kv_heads=c["num_key_value_heads"], head_dim=c["hidden_size"] // h,
        d_ff=c["intermediate_size"], vocab=c["vocab_size"],
        qkv_bias=c["qkv_bias"], tie_embeddings=c["tie_word_embeddings"],
        rope_theta=c["rope_theta"], norm_eps=c["rms_norm_eps"],
        amm=AmmConfig(**c["amm"]))


def make_weights(c: dict, seed: int):
    """The whole parameter tree, float32, made on the device in one call."""
    d, h, kv = c["hidden_size"], c["num_attention_heads"], \
        c["num_key_value_heads"]
    hd, ff, n, v = d // h, c["intermediate_size"], c["num_hidden_layers"], \
        c["vocab_size"]
    w = c["weights"]
    shapes = {
        "embed": ((v, d), w["embed_std"]),
        "final_norm": ((d,), None),
        "layers": {
            "attn_norm": ((n, d), None),
            "mlp_norm": ((n, d), None),
            "attn": {"wq": ((n, d, h, hd), w["std"]),
                     "wk": ((n, d, kv, hd), w["std"]),
                     "wv": ((n, d, kv, hd), w["std"]),
                     "wo": ((n, h, hd, d), w["std"]),
                     "bq": ((n, h, hd), w["bias_std"]),
                     "bk": ((n, kv, hd), w["bias_std"]),
                     "bv": ((n, kv, hd), w["bias_std"])},
            "mlp": {"w_gate": ((n, d, ff), w["std"]),
                    "w_up": ((n, d, ff), w["std"]),
                    "w_down": ((n, ff, d), w["std"])},
        },
    }
    leaves, tree = jax.tree.flatten(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    jseed = int(np.random.SeedSequence(int(seed) % (1 << 64))
                .generate_state(1)[0])

    @jax.jit
    def init(key):
        keys = jax.random.split(key, len(leaves))
        return jax.tree.unflatten(tree, [
            jnp.ones(shape, jnp.float32) if std is None
            else jax.random.normal(k, shape, jnp.float32) * std
            for k, (shape, std) in zip(keys, leaves)])

    return init(jax.random.key(jseed))


@dataclasses.dataclass
class Track:
    req: object
    sent: float
    times: list = dataclasses.field(default_factory=list)


class Log:
    """Every call into the serving programs, in order (see the module
    docstring).  ``sched`` is set once the scheduler exists."""

    def __init__(self, prefill_fn, decode_fn):
        self.calls, self.sched = [], None
        self._prefill, self._decode = prefill_fn, decode_fn

    def prefill(self, params, tokens, caches):
        slots = self.sched.slots
        slot = next(i for i, s in enumerate(slots)
                    if s is not None and not s.out)   # the one admitted
        self.calls.append(("prefill", slot, tokens, slots[slot]))
        return self._prefill(params, tokens, caches)

    def decode(self, params, tokens, caches, pos):
        rows = [(s, len(s.out)) if s is not None else None
                for s in self.sched.slots]
        self.calls.append(("decode", tokens, pos, rows))
        return self._decode(params, tokens, caches, pos)

    def schedule(self):
        """The replay's schedule and the served token of each marked row
        (the log converted to host arrays, after the window)."""
        events, served = [], []
        for call in self.calls:
            if call[0] == "prefill":
                _, slot, tokens, req = call
                toks = np.asarray(tokens).reshape(-1)
                rows = [len(toks) - 1] if req.out else []
                served += [req.out[0]] if req.out else []
                events.append({"prefill": slot, "tokens": toks,
                               "rows": rows})
            else:
                _, tokens, pos, rows = call
                marked = [i for i, r in enumerate(rows)
                          if r is not None and len(r[0].out) > r[1]]
                served += [rows[i][0].out[rows[i][1]] for i in marked]
                events.append({"tokens": np.asarray(tokens).reshape(-1),
                               "pos": np.asarray(pos).reshape(-1),
                               "rows": marked})
        return events, np.asarray(served, np.int64)


def run(run) -> None:
    from repro.launch.mesh import make_host_mesh
    from repro.launch.serve import plane_cache_for
    from repro.models import ModelRuntime
    from repro.serve.engine import Request, Scheduler, make_serve_fns

    c, mix, params = run.cell.config, run.cell.traffic, run.cell.params
    cfg = arch(c)
    slots, max_len = params["slots"], params["max_len"]
    srv = c["serving"]
    with span("setup.weights"):
        weights = jax.block_until_ready(make_weights(c, run.seed))
    rt = ModelRuntime.build(cfg, use_pallas=srv["use_pallas"])
    mesh = make_host_mesh(1, 1, devices=run.devices)
    planes = plane_cache_for(cfg, rt, weights)
    prefill_j, decode_j = make_serve_fns(
        cfg, rt, mesh, batch=slots, max_len=max_len, amm_planes=planes,
        kv_codes=srv["kv_codes"])
    log = Log(prefill_j, decode_j)
    sched = Scheduler(cfg, rt, weights, slots, max_len,
                      decode_fn=log.decode, prefill_fn=log.prefill,
                      continuous=srv["continuous"], kv_codes=srv["kv_codes"],
                      max_prefills_per_step=srv["max_prefills_per_step"])
    log.sched = sched
    gen = traffic.lm_requests(mix, run.seed, c["vocab_size"])
    rid = iter(range(1 << 62))
    active, finished = [], []

    def send(now):
        r = next(gen)
        req = Request(rid=next(rid), prompt=r["prompt"], max_new=r["max_new"])
        sched.submit(req)
        active.append(Track(req, now))

    def collect(now):
        for tk in list(active):
            new = len(tk.req.out) - len(tk.times)
            tk.times.extend([now] * new)
            if tk.req.done:
                active.remove(tk)
                finished.append(tk)
                send(now)

    with span("setup.fill"):
        # One request served before the clients start leaves the cache as
        # every later admission finds it, a decode step's output.  The
        # clients' first requests then fill every slot, which loads or
        # compiles each slot's admission and a prefill of every prompt
        # length the mix has: nothing is left to compile in the window.
        buckets = mix["prompt_len"]["buckets"]
        sched.submit(Request(rid=-1, prompt=[0] * min(buckets), max_new=2))
        sched.step()
        now = time.perf_counter()
        for _ in range(mix["clients"]):
            send(now)
        while sched.queue:
            sched.step()
            collect(time.perf_counter())

    # A traced run opens its window at a step that admits a request and
    # closes it once it has traced ``trace_seconds`` and ``trace_prefills``
    # admissions: the profiler's device buffer holds some tens of decode
    # steps, and stopping the profiler takes about 3 s per traced step.
    while run.trace and not sched.queue:
        sched.step()
        collect(time.perf_counter())
    live_per_step = []
    n_calls = len(log.calls)
    with run.window():
        t0 = time.perf_counter()
        end = t0 + run.seconds
        if run.trace:
            end_traced = t0 + min(run.seconds, params["trace_seconds"])
        while True:
            with span("sched.step"):
                live_per_step.append(sched.step())
            now = time.perf_counter()
            with span("client.collect"):
                collect(now)
            if now >= end:
                break
            if run.trace and now >= end_traced and sum(
                    c[0] == "prefill" for c in log.calls[n_calls:]) \
                    >= params["trace_prefills"]:
                break
    t1 = now
    run.read_memory_peak()

    # end-to-end numbers over the window (t0, t1]
    tracks = finished + active
    inwin = lambda t: t0 < t <= t1
    tokens, gaps, ttft = 0, [], []
    prefill_tokens, flops = 0, 0
    for tk in tracks:
        n_p = len(tk.req.prompt)
        for j, t in enumerate(tk.times):
            if not inwin(t):
                continue
            tokens += 1
            if j == 0:
                ttft.append(t - tk.sent)
                prefill_tokens += n_p
                flops += counts.lm_span_flops(c, 0, n_p)
            else:
                flops += counts.lm_span_flops(c, n_p + j - 1, n_p + j)
                if tk.times[j - 1] > t0:
                    gaps.append(t - tk.times[j - 1])
    run.metrics["tokens_per_s"] = tokens / run.window_s
    run.metrics["itl_p95_ms"] = (float(np.percentile(gaps, 95)) * 1e3
                                 if gaps else None)
    run.host["live_per_step"] = live_per_step
    run.host["ttft_s"] = ttft
    errors = [tk for tk in tracks if tk.req.error]
    run.attempted = len(tracks)
    run.failed = len(errors)
    run.counters.update(
        tokens=tokens, gaps=len(gaps), first_tokens=len(ttft),
        prefill_tokens=prefill_tokens, model_flops=flops,
        steps=len(live_per_step), finished=len(finished),
        **{f"sched.{k}": v for k, v in sched.stats.items()})

    # free the program's state before the reference runs
    del sched, prefill_j, decode_j, planes
    log._prefill = log._decode = None
    gc.collect()
    check(run, weights, log)


def check(run, weights, log) -> None:
    """Replay the log through the reference; compare every served token.

    With ``run.controls`` (a list of control names of the configuration)
    each control is also replayed and judged by the same comparison, its
    own first choices standing in for the served tokens; the results go
    to ``run.control_runs`` as runs of their own.
    """
    c, params = run.cell.config, run.cell.params
    ref = run.cell.reference()
    events, served = log.schedule()
    stated = c["datapath"]
    t = time.perf_counter()
    with span("check.reference"):
        h_ref = ref.replay(weights, c, events, params["slots"],
                           params["max_len"], ref.options(
                               c, stated["wl"], stated["vbl"],
                               stated["exact_precision"]))
        worst = ref.gaps(weights, c, h_ref, served,
                         precision=stated["exact_precision"])
    run.counters["checked_tokens"] = len(served)
    run.counters["check_s"] = time.perf_counter() - t
    judge(run, worst)
    run.control_runs = {}
    for name in run.controls:
        ctl = c["controls"][name]
        h_ctl = ref.replay(weights, c, events, params["slots"],
                           params["max_len"], ref.options(
                               c, ctl["wl"], ctl["vbl"],
                               ctl["exact_precision"]))
        g = ref.gaps(weights, c, h_ref, None, h_top=h_ctl,
                     precision=stated["exact_precision"],
                     top_precision=ctl["exact_precision"])
        cr = copy.copy(run)
        cr.checks, cr.counters = [], dict(run.counters)
        judge(cr, g)
        run.control_runs[name] = cr
        del h_ctl


def judge(run, gaps) -> None:
    """The compared numbers: the widest gap, and the mean gap over every
    compared token (steady from seed to seed where the widest is set by
    the nearest tie); the shape of the rest is recorded beside them."""
    limits = run.cell.params["limits"]
    gaps = np.asarray(gaps, np.float64)
    n = len(gaps)
    run.check("max_gap", float(np.max(gaps)) if n else float("inf"),
              limits["max_gap"])
    run.check("mean_gap", float(np.mean(gaps)) if n else float("inf"),
              limits["mean_gap"])
    run.check("failed_requests", run.failed, 0)
    run.check("checked_tokens", n, limits["min_checked_tokens"],
              ok=n >= limits["min_checked_tokens"])
    if n:
        run.counters.update({
            "gap.nonzero_share": float(np.mean(gaps > 0)),
            "gap.p99": float(np.percentile(gaps, 99)),
            "gap.p999": float(np.percentile(gaps, 99.9))})
