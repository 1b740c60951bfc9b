"""Moonlight-16B-A3B (DeepSeek-V3 architecture without q-LoRA) at a
reduced size on the CPU: absorbed latent attention, the noaux_tc router,
dropless serving, the expert share, and serving through the int-code
latent cache against the plain reference."""
import dataclasses
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import trace
from repro.configs import AmmConfig, get_arch, reduced
from repro.launch.mesh import make_host_mesh
from repro.models import ModelRuntime, init_cache, lm_apply, lm_init
from repro.models.attention import mla_attention
from repro.models.common import init_params
from repro.models.moe import moe_apply, moe_route, moe_table
from repro.serve.engine import Request, Scheduler, make_serve_fns
from repro.serve.kv_cache import init_code_cache

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import manifest as mf  # noqa: E402

REF = mf.load_module(ROOT / "bench/reference/moonlight_bbm.py")
AMM = AmmConfig(mode="bitexact", mul="bbm0", wl=16, param=13,
                apply_to="all")


def _cfg(**kw):
    """reduced(): d 64, one dense layer then 2 MoE layers, 8 experts,
    top-2; here 4 of them held (experts 2-5), so the share is exercised."""
    cfg = reduced(get_arch("moonlight-16b-a3b"), layers=3)
    return dataclasses.replace(cfg, experts_held=4, expert_offset=2, **kw)


def _params(cfg, seed=0):
    p = lm_init(cfg, jax.random.key(seed))
    g = np.random.default_rng(seed)
    bias = p["layers"]["moe"]["router_bias"]
    p["layers"]["moe"]["router_bias"] = jnp.asarray(
        g.normal(0.0, 0.05, bias.shape), jnp.float32)
    return p


def test_published_config():
    c = get_arch("moonlight-16b-a3b")
    assert (c.n_layers, c.d_model, c.n_heads, c.vocab, c.d_ff,
            c.moe_d_ff) == (27, 2048, 16, 163840, 11264, 1408)
    assert (c.n_experts, c.top_k, c.n_shared_experts, c.first_k_dense) == \
        (64, 6, 2, 1)
    assert (c.q_lora_rank, c.kv_lora_rank, c.qk_nope_dim, c.qk_rope_dim,
            c.v_head_dim) == (0, 512, 128, 64, 128)
    assert c.router_bias and c.routed_scaling_factor == 2.446
    assert (c.rope_theta, c.norm_eps, c.mtp_depth) == (5e4, 1e-5, 0)
    assert c.n_local_experts == 64 and not c.tie_embeddings


def test_absorbed_mla_equals_naive():
    """Against a cache the model without q-LoRA runs the absorbed form;
    at float32 with amm off it equals the expanded (naive) form, prefill
    and decode alike.  Tolerance 2e-5: float32 reassociation over
    contractions of at most 64 terms on outputs of order 0.1."""
    cfg = _cfg()
    p = _params(cfg)["layers"]
    p = jax.tree.map(lambda x: x[0], p)["attn"]
    b, s = 2, 9
    x = jnp.asarray(np.random.default_rng(1).standard_normal(
        (b, s, cfg.d_model)), jnp.float32)
    pos = jnp.arange(s)[None] * jnp.ones((b, 1), jnp.int32)
    with jax.default_matmul_precision("highest"):
        naive, _ = mla_attention(p, x, cfg, positions=pos)
        cache = init_cache(cfg, b, 16, dtype=jnp.float32)
        cache = {"latent": cache["latent"][0]}
        pre, cache = mla_attention(p, x[:, :6], cfg, positions=pos[:, :6],
                                   cache=cache, pos=jnp.int32(0))
        outs = [pre]
        for t in range(6, s):
            y, cache = mla_attention(
                p, x[:, t:t + 1], cfg, positions=pos[:, t:t + 1],
                cache=cache, pos=jnp.full((b,), t, jnp.int32))
            outs.append(y)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(outs, 1)),
                               np.asarray(naive), atol=2e-5, rtol=0)


def test_router_bias_chooses_and_weights_do_not():
    cfg = dataclasses.replace(_cfg(), top_k=2, routed_scaling_factor=2.5)
    e = cfg.n_experts
    logits = jnp.asarray([[2.0, 1.0, 0.5, 0.0, -1.0, -2.0, -3.0, -4.0]])
    p = {"router": jnp.eye(e, dtype=jnp.float32),
         "router_bias": jnp.zeros(e).at[7].set(10.0)}
    w, ids, _ = moe_route(p, logits, cfg)
    assert sorted(np.asarray(ids)[0].tolist()) == [0, 7]   # bias chose 7
    s = jax.nn.sigmoid(logits)[0]
    want = np.asarray([s[0], s[7]]) / float(s[0] + s[7]) * 2.5
    got = dict(zip(np.asarray(ids)[0].tolist(), np.asarray(w)[0].tolist()))
    np.testing.assert_allclose([got[0], got[7]], want, rtol=1e-6)
    np.testing.assert_allclose(float(jnp.sum(w)), 2.5, rtol=1e-6)


def _dense_moe(p, x, cfg, held=range(0)):
    """Every token through its chosen experts among ``held`` (all when
    empty), weights as routed; plus the shared experts."""
    w, ids, _ = moe_route(p, x, cfg)
    held = list(held) or list(range(cfg.n_experts))
    y = jnp.zeros_like(x)
    for j, ex in enumerate(held):
        g, u, dn = (p[k][j] for k in ("w_gate", "w_up", "w_down"))
        ye = (jax.nn.silu(x @ g) * (x @ u)) @ dn
        we = jnp.sum(jnp.where(ids == ex, w, 0.0), axis=-1)
        y = y + we[:, None] * ye
    sh = p["shared"]
    return y + (jax.nn.silu(x @ sh["w_gate"]) * (x @ sh["w_up"])) \
        @ sh["w_down"]


def test_serving_prefill_drops_no_token():
    """Every token chooses the same two experts (a large bias): the
    capacity path drops most of them, the serving path none."""
    cfg = dataclasses.replace(_cfg(), experts_held=0, expert_offset=0)
    p = init_params(moe_table(cfg), jax.random.key(3))
    p["router_bias"] = jnp.zeros(cfg.n_experts).at[jnp.array([1, 4])].set(
        100.0)
    x = jnp.asarray(np.random.default_rng(4).standard_normal(
        (1, 24, cfg.d_model)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = _dense_moe(p, x[0], cfg)
        served, _, _ = moe_apply(p, x, cfg, serving=True)
        trained, _ = moe_apply(p, x, cfg)
    np.testing.assert_allclose(np.asarray(served[0]), np.asarray(want),
                               atol=1e-5, rtol=0)
    assert float(jnp.max(jnp.abs(trained[0] - want))) > 1e-2


@pytest.mark.parametrize("amm", [None, AMM], ids=["off", "bbm0"])
def test_expert_shares_add_up_to_the_layer(amm):
    """The 8 shares of a 64-expert layer (top-6, as published), each the
    held experts' part plus the shared experts, add up to the uncut layer
    once the shared experts are counted once.  Each held expert quantizes
    its own rows, so under amm the experts' outputs are the same in a
    share and in the whole; the sum differs by float32 reassociation."""
    cfg = dataclasses.replace(
        _cfg(), n_experts=64, top_k=6, experts_held=0, expert_offset=0,
        amm=amm or AmmConfig())
    rt = ModelRuntime.build(cfg)
    p = init_params(moe_table(cfg), jax.random.key(5))
    p["router_bias"] = jnp.asarray(np.random.default_rng(5).normal(
        0.0, 0.05, cfg.n_experts), jnp.float32)
    x = jnp.asarray(np.random.default_rng(6).standard_normal(
        (2, 8, cfg.d_model)), jnp.float32)
    shared = dataclasses.replace(cfg, experts_held=1, expert_offset=0)
    with jax.default_matmul_precision("highest"):
        whole, _, _ = moe_apply(p, x, cfg, amm=rt.amm, serving=True)
        total = -7.0 * _shared_only(p, x, shared, rt)
        for i in range(8):
            part = dataclasses.replace(cfg, experts_held=8,
                                       expert_offset=8 * i)
            ps = dict(p, **{k: p[k][8 * i:8 * i + 8]
                            for k in ("w_gate", "w_up", "w_down")})
            y, _, _ = moe_apply(ps, x, part, amm=rt.amm, serving=True)
            total = total + y
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=2e-5, rtol=0)


def _shared_only(p, x, cfg, rt):
    """The layer with no routed expert held: the shared experts alone."""
    ps = dict(p, **{k: jnp.zeros_like(p[k][:1])
                    for k in ("w_gate", "w_up", "w_down")})
    far = dataclasses.replace(cfg, expert_offset=cfg.n_experts)
    return moe_apply(ps, x, far, amm=rt.amm, serving=True)[0]


# ------------------------------------------------ serving against references
def _hf(cfg):
    """The configuration as the plain reference reads it."""
    return {"hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
            "qk_nope_head_dim": cfg.qk_nope_dim,
            "qk_rope_head_dim": cfg.qk_rope_dim,
            "v_head_dim": cfg.v_head_dim, "kv_lora_rank": cfg.kv_lora_rank,
            "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
            "num_experts_per_tok": cfg.top_k,
            "routed_scaling_factor": cfg.routed_scaling_factor,
            "expert_share": {"first_held": cfg.expert_offset},
            "first_k_dense_replace": cfg.first_k_dense,
            "num_hidden_layers": cfg.n_layers}


def _serve(params, cfg, toks, n_prompt, caches, chunk=None):
    """Prefill ``n_prompt`` tokens (``chunk`` at a time if given), then
    decode the rest one at a time: (logits of every served position,
    each call's expert ids)."""
    rt = ModelRuntime.build(cfg)
    ids = []
    for start in range(0, n_prompt, chunk or n_prompt):
        end = min(n_prompt, start + (chunk or n_prompt))
        lg, aux, caches = lm_apply(params, cfg, rt, jnp.asarray(
            toks[None, start:end]), mode="decode", caches=caches, pos=start)
        ids.append(aux["expert_ids"])
    out = [lg[0, -1]]
    for p in range(n_prompt, len(toks)):
        lg, aux, caches = lm_apply(params, cfg, rt, jnp.asarray(
            toks[None, p:p + 1]), mode="decode", caches=caches,
            pos=jnp.full((1,), p, jnp.int32))
        out.append(lg[0, 0])
        ids.append(aux["expert_ids"])
    return jnp.stack(out), [np.asarray(i) for i in ids]


def _plain_forward(params, cfg, toks):
    """The published formulation in plain float32: K and V expanded from
    the latent, causal softmax, the routed experts this device holds and
    the shared experts; residual stream bfloat16 as served."""
    Q = REF.Q
    r, nope = cfg.kv_lora_rank, cfg.qk_nope_dim
    t = len(toks)
    pos = jnp.arange(t)
    h = params["embed"][jnp.asarray(toks)].astype(jnp.bfloat16)
    held = range(cfg.expert_offset, cfg.expert_offset + cfg.n_local_experts)
    for i in range(cfg.n_layers):
        if i < cfg.first_k_dense:
            lw = params["dense_prefix"][i]
        else:
            lw = jax.tree.map(lambda x: x[i - cfg.first_k_dense],
                              params["layers"])
        a = lw["attn"]
        x = Q.rmsnorm(h, lw["attn_norm"], cfg.norm_eps)
        q = jnp.einsum("td,dhk->thk", x, a["wq"])
        qf = jnp.concatenate([q[..., :nope], Q.rope(
            q[..., nope:], pos, cfg.rope_theta)], axis=-1)
        lat = x @ a["w_dkv"]
        c = Q.rmsnorm(lat[:, :r], a["kv_norm"], cfg.norm_eps)
        kr = Q.rope(lat[:, None, r:], pos, cfg.rope_theta)
        k = jnp.concatenate([jnp.einsum("tr,rhk->thk", c, a["w_uk"]),
                             jnp.broadcast_to(kr, (t, cfg.n_heads,
                                                   cfg.qk_rope_dim))], -1)
        v = jnp.einsum("tr,rhk->thk", c, a["w_uv"])
        s = jnp.einsum("thk,shk->hts", qf, k) / (qf.shape[-1] ** 0.5)
        s = jnp.where(pos[:, None] >= pos[None, :], s, -1e30)
        o = jnp.einsum("hts,shk->thk", jax.nn.softmax(s, -1), v)
        h = h + jnp.einsum("thk,hkd->td", o, a["wo"]).astype(h.dtype)
        x = Q.rmsnorm(h, lw["mlp_norm"], cfg.norm_eps)
        if i < cfg.first_k_dense:
            m = lw["mlp"]
            y = (jax.nn.silu(x @ m["w_gate"]) * (x @ m["w_up"])) \
                @ m["w_down"]
        else:
            y = _dense_moe(lw["moe"], x, cfg, held)
        h = h + y.astype(h.dtype)
    return Q.rmsnorm(h, params["final_norm"], cfg.norm_eps) \
        @ params["lm_head"]


# A float32 rounding difference can flip an element of the bfloat16
# residual (2^-8 relative) or a 16-bit code, and the model passes such a
# flip on: here the served logits (of order 0.3) lie 1.0e-3 (amm off) and
# 1.2e-3 (amm on) from their references.  0.01 leaves room for other
# platforms, while the 8-bit multiplier one precision down moves them by
# 0.2.
SERVE_TOL = 0.01


def test_serving_through_code_cache_matches_reference():
    """Prefill, then decode through the int-code latent cache, against
    the plain reference's replay of the same calls (float32 exact
    products on the CPU, the program's logged expert ids)."""
    cfg = _cfg(amm=AMM)
    params = _params(cfg)
    toks = np.random.default_rng(2).integers(0, cfg.vocab, 15)
    n_prompt, s = 12, 32
    got, ids = _serve(params, cfg, toks, n_prompt,
                      init_code_cache(cfg, 1, s, wl=16))
    sched = [{"prefill": 0, "tokens": toks[:n_prompt], "rows": [n_prompt - 1]}]
    sched += [{"tokens": toks[p:p + 1], "pos": np.array([p]), "rows": [0]}
              for p in range(n_prompt, len(toks))]
    head = {"final_norm": params["final_norm"],
            "embed": params["lm_head"].T}

    def ref_logits(wl, vbl):
        h, route = REF.replay(params, _hf(cfg), sched, ids, 1, s,
                              REF.options(_hf(cfg), wl, vbl, "float32"))
        with jax.default_matmul_precision("highest"):
            return REF.Q.logits(h, head["final_norm"], head["embed"],
                                eps=cfg.norm_eps, prec="float32"), route

    want, route = ref_logits(16, 13)
    assert float(jnp.max(jnp.abs(got - want))) < SERVE_TOL
    assert float(np.max(REF.route_gaps(route))) <= 0.0
    low, _ = ref_logits(8, 5)
    assert float(jnp.max(jnp.abs(got - low))) > SERVE_TOL


def test_chunked_prefill_through_code_cache_matches_reference():
    """A prompt fed in chunks, each from the position the last reached
    (as the scheduler's ``prefill_chunk`` feeds it), then decoded through
    the int-code latent cache, against the reference's replay of the same
    chunks; chunk boundaries off the 16-position blocks included."""
    cfg = _cfg(amm=AMM)
    params = _params(cfg)
    toks = np.random.default_rng(3).integers(0, cfg.vocab, 26)
    n_prompt, chunk, s = 22, 9, 48
    got, ids = _serve(params, cfg, toks, n_prompt,
                      init_code_cache(cfg, 1, s, wl=16), chunk=chunk)
    sched = [{"prefill": 0, "tokens": toks[a:min(a + chunk, n_prompt)],
              "start": a, "last": a + chunk >= n_prompt,
              "rows": [n_prompt - 1 - a] if a + chunk >= n_prompt else []}
             for a in range(0, n_prompt, chunk)]
    sched += [{"tokens": toks[p:p + 1], "pos": np.array([p]), "rows": [0]}
              for p in range(n_prompt, len(toks))]
    h, route = REF.replay(params, _hf(cfg), sched, ids, 1, s,
                          REF.options(_hf(cfg), 16, 13, "float32"))
    with jax.default_matmul_precision("highest"):
        want = REF.Q.logits(h, params["final_norm"], params["lm_head"].T,
                            eps=cfg.norm_eps, prec="float32")
    assert float(jnp.max(jnp.abs(got - want))) < SERVE_TOL
    assert float(np.max(REF.route_gaps(route))) <= 0.0


def test_serving_float_cache_matches_plain_forward():
    """With amm off: prefill, then decode through the float latent cache
    (absorbed), against the plain float32 forward of the whole sequence
    in the published, expanded formulation.  Tolerance as above: the
    bfloat16 residual passes rounding flips on."""
    cfg = _cfg()
    params = _params(cfg)
    toks = np.random.default_rng(2).integers(0, cfg.vocab, 15)
    with jax.default_matmul_precision("highest"):
        got, _ = _serve(params, cfg, toks, 12,
                        init_cache(cfg, 1, 32, dtype=jnp.float32))
        want = _plain_forward(params, cfg, toks)[11:]
    assert float(jnp.max(jnp.abs(got - want))) < SERVE_TOL


def test_scheduler_serves_and_programs_return_routing():
    """make_serve_fns + the continuous Scheduler with the int-code latent
    cache: requests complete, each program call returns its expert ids
    (expert layers, B, S, k), and the decode program's HLO carries the
    latent-attention and expert scopes."""
    cfg = _cfg(amm=AMM)
    rt = ModelRuntime.build(cfg)
    params = _params(cfg)
    slots, max_len = 2, 32
    prefill_j, decode_j = make_serve_fns(
        cfg, rt, make_host_mesh(1, 1), batch=slots, max_len=max_len,
        kv_codes=True)
    shapes = []

    def keep(fn):
        def call(*a):
            out = fn(*a)
            shapes.append(out[2].shape)
            return out
        return call
    sched = Scheduler(cfg, rt, params, slots, max_len,
                      decode_fn=keep(decode_j), prefill_fn=keep(prefill_j),
                      continuous=True, kv_codes=True)
    reqs = [Request(rid=i, prompt=[3 + i, 5, 7], max_new=3)
            for i in range(3)]
    for r in reqs:
        sched.submit(r)
    for _ in range(20):
        if all(r.done for r in reqs):
            break
        sched.step()
    assert all(r.done and len(r.out) == 3 and r.error is None for r in reqs)
    n_moe = cfg.n_layers - cfg.first_k_dense
    assert (n_moe, 1, 3, cfg.top_k) in shapes               # a prefill
    assert (n_moe, slots, 1, cfg.top_k) in shapes           # a decode step
    caches = jax.eval_shape(lambda: init_code_cache(cfg, slots, max_len,
                                                    wl=16))
    hlo = decode_j.lower(
        params, jax.ShapeDtypeStruct((slots, 1), jnp.int32), caches,
        jax.ShapeDtypeStruct((slots,), jnp.int32)).as_text(
            dialect="hlo", debug_info=True)
    stacks = [n.split("/") for n in
              set(re.findall(r'op_name="([^"]*)"', hlo))]
    for scope in trace.MOE_SCOPES:
        assert any(scope in st for st in stacks), scope
