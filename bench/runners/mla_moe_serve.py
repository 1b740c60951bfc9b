"""A latent-attention MoE model served by ``make_serve_fns`` and the
continuous ``Scheduler``, on one chip's share of an expert-parallel
deployment.

As ``lm_serve`` (closed loop, set-up, the logged calls, the window, the
end-to-end numbers), with four differences:

  * the configuration is a DeepSeek-V3-style one (latent attention without
    q-LoRA, a leading dense layer, routed and shared experts); the
    program holds ``n_routed_experts`` of the router's experts from
    ``expert_share.first_held`` on, and a slice of the vocabulary;
  * the scheduler feeds a prompt ``serving.prefill_chunk`` tokens a step
    (one prompt at a time), so a step carries at most one chunk of
    prefill whatever the admitted prompt's length; the log keeps each
    chunk's first position, and a chunk's slot is no live row of the
    decode steps it shares;
  * every serving call returns each token's expert ids per expert layer as
    a device array; the log keeps them beside the call, and nothing reads
    them before the window has closed;
  * ``correct`` replays the log through the reference with the program's
    expert ids and judges each choice against the reference's own biased
    router scores: a chosen expert may lie at most ``route_delta`` below
    the reference's k-th best (``routing_outside_margin``, limit 0),
    beside the served tokens' logit gaps, as ``lm_serve`` judges them.

Model FLOPs (``mfu.lm``) and the decode steps' least time
(``moe.decode_roofline``) come from ``bench/counts_moe.py`` on the logged
routing; ``moe.rows_per_expert`` counts the rows routed to each held
expert in the window's decode steps.
"""
from __future__ import annotations

import contextlib
import copy
import gc
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from bench import counts_moe, traffic
from bench import manifest as mf
from bench.trace import span

LM = mf.load_module(Path(__file__).resolve().parent / "lm_serve.py")
Log, Track, judge = LM.Log, LM.Track, LM.judge


def arch(c: dict):
    """The program's configuration object for this config file."""
    from repro.configs.base import AmmConfig, ArchConfig
    if c["n_group"] != 1 or c["topk_group"] != 1 or c["q_lora_rank"]:
        raise ValueError("group-limited routing and q-LoRA are not served")
    share = c["expert_share"]
    return ArchConfig(
        name=c["name"], family="moe", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["v_head_dim"],
        d_ff=c["intermediate_size"], vocab=c["vocab_size"],
        tie_embeddings=c["tie_word_embeddings"], rope_theta=c["rope_theta"],
        norm_eps=c["rms_norm_eps"], n_experts=share["router_outputs"],
        experts_held=c["n_routed_experts"],
        expert_offset=share["first_held"],
        n_shared_experts=c["n_shared_experts"],
        top_k=c["num_experts_per_tok"],
        moe_d_ff=c["moe_intermediate_size"],
        first_k_dense=c["first_k_dense_replace"],
        routed_scaling_factor=c["routed_scaling_factor"],
        router_bias=c["topk_method"] == "noaux_tc",
        use_mla=True, q_lora_rank=0, kv_lora_rank=c["kv_lora_rank"],
        qk_nope_dim=c["qk_nope_head_dim"], qk_rope_dim=c["qk_rope_head_dim"],
        v_head_dim=c["v_head_dim"], mtp_depth=c["num_nextn_predict_layers"],
        amm=AmmConfig(**c["amm"]))


def make_weights(c: dict, seed: int):
    """The whole parameter tree, float32, made on the device in one call."""
    d, h, v = c["hidden_size"], c["num_attention_heads"], c["vocab_size"]
    nope, rope, vh = c["qk_nope_head_dim"], c["qk_rope_head_dim"], \
        c["v_head_dim"]
    r = c["kv_lora_rank"]
    first = c["first_k_dense_replace"]
    n = c["num_hidden_layers"] - first
    e, eff = c["n_routed_experts"], c["moe_intermediate_size"]
    sff = eff * c["n_shared_experts"]
    w = c["weights"]
    std = w["std"]

    def attn(*lead):
        return {"wq": (lead + (d, h, nope + rope), std),
                "w_dkv": (lead + (d, r + rope), std),
                "kv_norm": (lead + (r,), None),
                "w_uk": (lead + (r, h, nope), std),
                "w_uv": (lead + (r, h, vh), std),
                "wo": (lead + (h, vh, d), std)}

    def mlp(ff, *lead):
        return {"w_gate": (lead + (d, ff), std),
                "w_up": (lead + (d, ff), std),
                "w_down": (lead + (ff, d), std)}

    shapes = {
        "embed": ((v, d), w["embed_std"]),
        "lm_head": ((d, v), w["head_std"]),
        "final_norm": ((d,), None),
        "dense_prefix": [{"attn_norm": ((d,), None), "attn": attn(),
                          "mlp_norm": ((d,), None),
                          "mlp": mlp(c["intermediate_size"])}
                         for _ in range(first)],
        "layers": {
            "attn_norm": ((n, d), None), "attn": attn(n),
            "mlp_norm": ((n, d), None),
            "moe": {"router": ((n, d, c["expert_share"]["router_outputs"]),
                               w["router_std"]),
                    "router_bias": ((n, c["expert_share"]["router_outputs"]),
                                    w["router_bias_std"]),
                    "w_gate": ((n, e, d, eff), std),
                    "w_up": ((n, e, d, eff), std),
                    "w_down": ((n, e, eff, d), std),
                    "shared": mlp(sff, n)},
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
            jnp.ones(shape, jnp.float32) if sd is None
            else jax.random.normal(k, shape, jnp.float32) * sd
            for k, (shape, sd) in zip(keys, leaves)])

    return init(jax.random.key(jseed))


class RoutedLog(Log):
    """``Log`` that also keeps each call's expert ids, as returned, and
    each prefill's first position: a prompt may come in chunks."""

    def __init__(self, prefill_fn, decode_fn):
        super().__init__(prefill_fn, decode_fn)
        self.routing = []

    def prefill(self, params, tokens, caches, start=0):
        slots = self.sched.slots
        slot = next(i for i, s in enumerate(slots)
                    if s is not None and not s.out)   # the one being fed
        self.calls.append(("prefill", slot, tokens, slots[slot], start))
        out = self._prefill(params, tokens, caches, start)
        self.routing.append(out[2])
        return out

    def decode(self, params, tokens, caches, pos):
        # a slot whose prompt is still being fed is no live row
        rows = [(s, len(s.out)) if s is not None and s.out else None
                for s in self.sched.slots]
        self.calls.append(("decode", tokens, pos, rows))
        out = self._decode(params, tokens, caches, pos)
        self.routing.append(out[2])
        return out

    def schedule(self):
        """As ``Log.schedule``; a prefill event also gives its first
        position (``start``) and whether it ends its prompt (``last``):
        only the last chunk of a prompt produces a served token."""
        events, served = [], []
        for call in self.calls:
            if call[0] == "prefill":
                _, slot, tokens, req, start = call
                toks = np.asarray(tokens).reshape(-1)
                last = start + len(toks) == max(len(req.prompt), 1)
                rows = [len(toks) - 1] if last and req.out else []
                served += [req.out[0]] if rows else []
                events.append({"prefill": slot, "tokens": toks,
                               "start": start, "last": last, "rows": rows})
            else:
                _, tokens, pos, rows = call
                marked = [i for i, r in enumerate(rows)
                          if r is not None and len(r[0].out) > r[1]]
                served += [rows[i][0].out[rows[i][1]] for i in marked]
                events.append({"tokens": np.asarray(tokens).reshape(-1),
                               "pos": np.asarray(pos).reshape(-1),
                               "rows": marked})
        return events, np.asarray(served, np.int64)


def window_work(c: dict, calls, routing):
    """(model FLOPs, [(FLOPs, bytes) per decode step], [rows per held
    expert per decode step]) of the calls made in the window."""
    flops, steps, rows = 0, [], []
    for call, ids in zip(calls, routing):
        ids = np.asarray(ids)                  # (expert layers, B, T, k)
        if call[0] == "prefill":
            t, start = ids.shape[2], call[4]
            flops += counts_moe.tokens_flops(
                c, range(start + 1, start + t + 1), ids[:, 0])
            continue
        _, _, pos, live_rows = call
        pos = np.asarray(pos).reshape(-1)
        live = [i for i, x in enumerate(live_rows) if x is not None]
        f, b = counts_moe.decode_step(c, pos[live] + 1, ids[:, live, 0])
        flops += f
        steps.append((f, b))
        rows.append(counts_moe.rows_per_expert(ids[:, :, 0], c))
    return flops, steps, rows


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
    log = RoutedLog(prefill_j, decode_j)
    sched = Scheduler(cfg, rt, weights, slots, max_len,
                      decode_fn=log.decode, prefill_fn=log.prefill,
                      continuous=srv["continuous"], kv_codes=srv["kv_codes"],
                      max_prefills_per_step=srv["max_prefills_per_step"],
                      prefill_chunk=srv["prefill_chunk"])
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
        # as lm_serve: one request first, then every slot filled, which
        # compiles or loads every program the window will call
        buckets = mix["prompt_len"]["buckets"]
        sched.submit(Request(rid=-1, prompt=[0] * min(buckets), max_new=2))
        sched.step()
        now = time.perf_counter()
        for _ in range(mix["clients"]):
            send(now)
        while sched.queue:
            sched.step()
            collect(time.perf_counter())

    # a traced window opens at a step that admits a request (as lm_serve)
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
                    c_[0] == "prefill" for c_ in log.calls[n_calls:]) \
                    >= params["trace_prefills"]:
                break
    t1 = now
    run.read_memory_peak()

    tracks = finished + active
    tokens, gaps, ttft, prefill_tokens = 0, [], [], 0
    for tk in tracks:
        for j, t in enumerate(tk.times):
            if not t0 < t <= t1:
                continue
            tokens += 1
            if j == 0:
                ttft.append(t - tk.sent)
                prefill_tokens += len(tk.req.prompt)
            elif tk.times[j - 1] > t0:
                gaps.append(t - tk.times[j - 1])
    flops, steps, rows = window_work(c, log.calls[n_calls:],
                                     log.routing[n_calls:])
    run.metrics["tokens_per_s"] = tokens / run.window_s
    run.metrics["itl_p95_ms"] = (float(np.percentile(gaps, 95)) * 1e3
                                 if gaps else None)
    run.host.update(live_per_step=live_per_step, ttft_s=ttft,
                    decode_work=steps, rows_per_expert=rows)
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


# the reference's programs compile in seconds and the serving programs'
# in minutes: the reference's are kept out of the persistent compile
# cache, which may be capped in size, so that they never push the serving
# programs out of it
REFERENCE_MIN_CACHED_COMPILE_S = 60.0


@contextlib.contextmanager
def reference_compiles_uncached():
    key = "jax_persistent_cache_min_compile_time_secs"
    before = getattr(jax.config, key)
    jax.config.update(key, max(before, REFERENCE_MIN_CACHED_COMPILE_S))
    try:
        yield
    finally:
        jax.config.update(key, before)


def check(run, weights, log) -> None:
    """Replay the log through the reference with the program's expert
    ids; judge every served token and every choice of experts.

    With ``run.controls`` each control is replayed choosing its own
    experts and judged by the same comparisons, its first choices
    standing in for the served tokens and its choices of experts for the
    program's; the results go to ``run.control_runs``.
    """
    with reference_compiles_uncached():
        _check(run, weights, log)


def _check(run, weights, log) -> None:
    c, params = run.cell.config, run.cell.params
    ref = run.cell.reference()
    events, served = log.schedule()
    routing = [np.asarray(r) for r in log.routing]
    stated = c["datapath"]
    t = time.perf_counter()
    with span("check.reference"):
        h_ref, route = ref.replay(
            weights, c, events, routing, params["slots"], params["max_len"],
            ref.options(c, stated["wl"], stated["vbl"],
                        stated["exact_precision"]))
        worst = ref.gaps(weights, c, h_ref, served,
                         precision=stated["exact_precision"])
        route_gaps = ref.route_gaps(route)
    run.counters["checked_tokens"] = len(served)
    run.counters["check_s"] = time.perf_counter() - t
    judge(run, worst)
    judge_routing(run, route_gaps)
    run.control_runs = {}
    for name in run.controls:
        ctl = c["controls"][name]
        h_ctl, route_ctl = ref.replay(
            weights, c, events, routing, params["slots"], params["max_len"],
            ref.options(c, ctl["wl"], ctl["vbl"], ctl["exact_precision"],
                        own_routing=True))
        g = ref.gaps(weights, c, h_ref, None, h_top=h_ctl,
                     precision=stated["exact_precision"],
                     top_precision=ctl["exact_precision"])
        cr = copy.copy(run)
        cr.checks, cr.counters = [], dict(run.counters)
        judge(cr, g)
        judge_routing(cr, ref.route_gaps(route, route_ctl))
        run.control_runs[name] = cr
        del h_ctl, route_ctl


def judge_routing(run, gaps) -> None:
    """Every chosen expert's biased score may lie at most ``route_delta``
    below the reference's k-th best: the count of those further below is
    compared with 0; the widest and mean gap are recorded beside it."""
    delta = run.cell.params["limits"]["route_delta"]
    gaps = np.asarray(gaps, np.float64)
    run.check("routing_outside_margin", int(np.sum(gaps > delta)), 0)
    if len(gaps):
        run.counters.update({
            "gap.route_max": float(np.max(gaps)),
            "gap.route_mean": float(np.mean(np.maximum(gaps, 0.0))),
            "gap.route_choices": int(len(gaps)),
            "gap.route_below_kth": int(np.sum(gaps > 0))})
