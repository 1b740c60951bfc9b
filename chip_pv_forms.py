"""Time the two forms of the code-cache value product alone, on the chip.

    python3 chip_pv_forms.py [--out FILE] [--repeats N]

``bbm_matmul_coded_kblocks`` forms each K-block's integer partial either
in the multiply-free Booth rows form or in the batched dot form, chosen by
``pv_form`` from the block's shapes.  This times both forms at the shapes
the served decode steps run them at, one layer's worth per call, vmapped
as the models vmap them:

  * qwen2-0.5b (``decode_attention_codes``): 64 slots x 2 KV heads, each
    (7, 768) probabilities against (768, 64) V codes in blocks of 16;
  * moonlight-16b-a3b (``latent_attention_codes``): 64 slots, each
    (16, 768) probabilities against (768, 512) latent codes in blocks
    of 16.

at wl 16 (vbl 13, the served operating point) and wl 8 (vbl 5), Type 0.
The forms run in interleaved rounds under the profiler, and each call's
time is its program's span on the device's modules line (device time,
no host dispatch).  Each line of the output is one (shape, wl, form)
with its median and least milliseconds a call, the compiled program's
temporary bytes, whether the two forms gave the same bits, and the form
``pv_form`` picks there; a form that fails to compile or run (out of
memory, say) gives a line with its error instead.  A run without a TPU
prints nothing and exits non-zero: these are device times.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import tempfile

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from bench.trace import MODULES_LINE, load_events, module_name  # noqa: E402
from repro.kernels.bbm_matmul import _coded_kblocks, pv_form  # noqa: E402

BLOCK = 16
CTX = 768
# name: (vmapped batch axes, M, N)
SHAPES = {"qwen2-0.5b": ((64, 2), 7, 64),
          "moonlight-16b-a3b": ((64,), 16, 512)}
OPERATING_POINTS = ((16, 13), (8, 5))
FORMS = ("rows", "dot")


def layer_fn(name, batch, *, wl, vbl, form):
    """One layer's value products: ``_coded_kblocks`` vmapped over the
    ``batch`` axes, jitted; its device program is ``jit_<name>``."""
    def fn(a, c, s):
        return _coded_kblocks(a, c, s, wl=wl, vbl=vbl, kind=0, block=BLOCK,
                              form=form)
    for _ in batch:
        fn = jax.vmap(fn)

    def named(a, c, s):
        return fn(a, c, s)
    named.__name__ = name
    return jax.jit(named)


def operands(batch, m, n, wl, seed=0):
    rng = np.random.default_rng(seed)
    p = rng.random(batch + (m, CTX), np.float32)
    p /= p.sum(-1, keepdims=True)
    half = 1 << (wl - 1)
    codes = rng.integers(-half, half, batch + (CTX, n), dtype=np.int32)
    scales = rng.uniform(0.5, 2.0, batch + (CTX // BLOCK,)).astype(np.float32)
    return jnp.asarray(p), jnp.asarray(codes), jnp.asarray(scales)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", default=None, help="also write the lines here")
    ap.add_argument("--repeats", type=int, default=20)
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"needs a TPU, found {dev.platform}", file=sys.stderr)
        return 1
    out = open(args.out, "w") if args.out else None

    def emit(line):
        print(json.dumps(line), flush=True)
        if out:
            out.write(json.dumps(line) + "\n")
            out.flush()

    for name, (batch, m, n) in SHAPES.items():
        for wl, vbl in OPERATING_POINTS:
            ops = operands(batch, m, n, wl)
            head = {"shape": name, "m": m, "block": BLOCK, "n": n, "wl": wl,
                    "vbl": vbl, "gate": pv_form(BLOCK, wl, vbl),
                    "device": dev.device_kind}
            fns, temp, outs = {}, {}, {}
            for form in FORMS:
                prog = f"pv_{form}_{re.sub(r'\W', '_', name)}_wl{wl}"
                fn = layer_fn(prog, batch, wl=wl, vbl=vbl, form=form)
                try:
                    compiled = fn.lower(*ops).compile()
                    temp[form] = compiled.memory_analysis().temp_size_in_bytes
                    outs[form] = np.asarray(jax.block_until_ready(fn(*ops)))
                except Exception as e:  # noqa: BLE001 (e.g. out of memory)
                    emit({**head, "form": form,
                          "error": f"{type(e).__name__}: {e}"[:300]})
                    continue
                fns["jit_" + prog] = (form, fn)
            same = (bool(np.array_equal(outs["rows"], outs["dot"]))
                    if len(outs) == 2 else None)
            logdir = tempfile.mkdtemp(prefix="pv_forms_")
            jax.profiler.start_trace(logdir)
            for _ in range(args.repeats):
                for form, fn in fns.values():
                    jax.block_until_ready(fn(*ops))
            jax.profiler.stop_trace()
            ts = {form: [] for form, _ in fns.values()}
            for e in load_events(logdir)["device"]:
                prog = module_name(e["name"])
                if e["line"] == MODULES_LINE and prog in fns:
                    ts[fns[prog][0]].append(e["dur"] * 1e-6)
            for form, t in ts.items():
                emit({**head, "form": form,
                      "device_ms": float(np.median(t)) if t else None,
                      "device_ms_min": float(np.min(t)) if t else None,
                      "calls": len(t), "temp_bytes": int(temp[form]),
                      "bitwise_equal": same})
    if out:
        out.close()
    return 0

if __name__ == "__main__":
    sys.exit(main())
