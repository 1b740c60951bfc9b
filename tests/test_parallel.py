"""Distribution-layer tests: logical rules, shape-aware sharding, and a
multi-device (8 forced host devices) subprocess exercising shard_map
compressed all-reduce and a 2x4 mesh train step."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import make_host_mesh
from repro.parallel.logical import (OPT_RULES_MULTIPOD, RULES,
                                    RULES_MULTIPOD, batch_pspec,
                                    spec_to_pspec)


def test_rules_basic():
    assert spec_to_pspec(("embed", "mlp"), RULES) == P("data", "model")
    assert spec_to_pspec(("vocab", "embed"), RULES) == P("model", "data")
    assert spec_to_pspec(("layers", "embed", "heads"), RULES) == \
        P(None, "data", "model")


def test_rules_no_duplicate_mesh_axis():
    # experts takes model; mlp inside the expert must fall back to None
    got = spec_to_pspec(("experts", "embed", "expert_mlp"), RULES)
    assert got == P("model", "data", None)
    got2 = spec_to_pspec(("heads", "kv_heads"), RULES)
    assert got2 == P("model", None)


def test_rules_multipod_batch():
    assert spec_to_pspec(("batch", "seq"), RULES_MULTIPOD) == \
        P(("pod", "data"), None)
    assert spec_to_pspec(("embed", "mlp"), OPT_RULES_MULTIPOD) == \
        P(("pod", "data"), "model")


def test_divisibility_dropping():
    mesh = make_host_mesh(1, 1)

    class FakeMesh:
        shape = {"data": 16, "model": 16, "pod": 2}
    fm = FakeMesh()
    # 14 heads on a 16-way axis -> replicated
    got = spec_to_pspec(("embed", "heads"), RULES, shape=(896, 14), mesh=fm)
    assert got == P("data", None)
    # divisible stays sharded
    got = spec_to_pspec(("embed", "heads"), RULES, shape=(896, 64), mesh=fm)
    assert got == P("data", "model")
    # multipod batch of 1 -> fully replicated
    got = spec_to_pspec(("batch",), RULES_MULTIPOD, shape=(1,), mesh=fm)
    assert got == P(None)
    # batch 32 divisible by pod*data=32
    got = spec_to_pspec(("batch",), RULES_MULTIPOD, shape=(32,), mesh=fm)
    assert got == P(("pod", "data"))


MULTIDEV_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np, json
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch.mesh import make_host_mesh, make_mesh

mesh = make_host_mesh(4, 2)

# --- compressed allreduce over the data axis
from repro.parallel.compress import compressed_allreduce, allreduce_ref
rng = np.random.default_rng(0)
g = jnp.asarray(rng.standard_normal((4, 64)), jnp.float32)
sharded = jax.device_put(g, NamedSharding(mesh, P("data", None)))
means, errs = compressed_allreduce({"g": sharded}, mesh, codec="int8")
ref = np.mean(np.asarray(g).reshape(4, 1, 64), axis=0)  # mean over shards
got = np.asarray(means["g"])
# each shard's row equals the mean of all shards' rows (approximately)
err = float(np.abs(got - np.broadcast_to(ref, got.shape)).max())
assert err < 0.05, err

# --- channel-sharded filterbank: 8 channels over 8 data shards
from repro.parallel.filterbank import sharded_filterbank
from repro.kernels.ref import fir_bank_ref
mesh1 = make_mesh((8,), ("data",))
xc = jnp.asarray(rng.integers(0, 1 << 12, (8, 256)), jnp.int32)
hc = jnp.asarray(rng.integers(0, 1 << 12, (8, 31)), jnp.int32)
got_fb = sharded_filterbank(xc, hc, mesh1, wl=12, vbl=9, kind=1)
ref_fb = fir_bank_ref(xc, hc, wl=12, vbl=9, kind=1)
assert np.array_equal(np.asarray(got_fb), np.asarray(ref_fb))
try:
    sharded_filterbank(xc[:6], hc[:6], mesh1, wl=12, vbl=9)
    raise SystemExit("divisibility guard did not fire")
except ValueError:
    pass

# --- tiny train step on a real 4x2 mesh
from repro.configs import get_arch, reduced
from repro.models import ModelRuntime
from repro.train.trainstep import TrainConfig, make_train_step, init_train_state
from repro.train.optimizer import OptConfig
cfg = reduced(get_arch("llama3.2-3b"))
rt = ModelRuntime.build(cfg)
tc = TrainConfig(microbatches=2, opt=OptConfig(lr=1e-3, total_steps=10))
step = make_train_step(cfg, rt, tc, mesh, global_batch=8)
params, opt = init_train_state(cfg, tc, mesh, jax.random.key(0))
toks = jax.random.randint(jax.random.key(1), (8, 32), 0, cfg.vocab)
labels = jnp.roll(toks, -1, axis=-1)
l0 = None
for i in range(3):
    params, opt, metrics = step(params, opt, toks, labels,
                                jax.random.fold_in(jax.random.key(2), i))
    if l0 is None:
        l0 = float(metrics["loss"])
l1 = float(metrics["loss"])
assert np.isfinite(l1)
assert l1 < l0          # overfits the fixed batch
print(json.dumps({"ok": True, "l0": l0, "l1": l1, "int8_err": err,
                  "filterbank_ok": True}))
"""


def test_multidevice_subprocess():
    env = dict(os.environ,
               PYTHONPATH=os.path.abspath("src"))
    out = subprocess.run([sys.executable, "-c", MULTIDEV_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["ok"] and res["l1"] < res["l0"]
