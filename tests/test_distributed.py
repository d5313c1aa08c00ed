"""Distributed semantics: the jitted Byz-VR-MARINA step on a multi-device
mesh must produce the SAME trajectory as the single-device run (same seeds),
and the sharded aggregation path must equal the gspmd path.

Multi-device CPU requires XLA_FLAGS set before jax init, so these tests run
in subprocesses.
"""
import os
import subprocess
import sys

import pytest

# each case spawns a fresh 8-device jax subprocess -> opt-in
pytestmark = pytest.mark.slow

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import (ByzVRMarinaConfig, get_aggregator, get_attack,
                        get_compressor, make_init, make_step)
from repro.data import (corrupt_labels_logreg, init_logreg_params,
                        logreg_loss, make_logreg_data)

assert jax.device_count() == 8
KEY = jax.random.PRNGKey(0)
DIM = 16
N = 4
data = make_logreg_data(KEY, n_samples=200, dim=DIM, n_workers=N,
                        homogeneous=True)
loss_fn = logreg_loss(0.01)
cfg = ByzVRMarinaConfig(n_workers=N, n_byz=1, p=0.3, lr=0.3,
                        aggregator=get_aggregator("cm", bucket_size=2),
                        compressor=get_compressor("randk", ratio=0.5),
                        attack=get_attack("ALIE"))
step_fn = make_step(cfg, loss_fn, corrupt_labels_logreg)
anchor = data.stacked()
state0 = make_init(cfg, loss_fn, corrupt_labels_logreg)(
    init_logreg_params(DIM), anchor, KEY)

def run(jit_kwargs, tag):
    step = jax.jit(step_fn, **jit_kwargs)
    state = jax.tree.map(lambda x: x, state0)
    k = KEY
    losses = []
    for it in range(10):
        k, k1, k2 = jax.random.split(k, 3)
        mb = data.sample_batches(k1, 16)
        state, m = step(state, mb, anchor, k2)
        losses.append(float(m["loss"]))
    return losses, [float(x) for x in
                    jax.device_get(state["params"]["w"]).tolist()]

# single-logical-device reference (everything replicated on device 0)
ref_losses, ref_w = run({}, "ref")

# sharded: worker axis over 'data' (4), model params replicated over 'model'
mesh = jax.make_mesh((4, 2), ("data", "model"))
wspec = NamedSharding(mesh, P("data"))
rep = NamedSharding(mesh, P())
state_sh = {"params": {"w": rep, "b": rep}, "g": {"w": rep, "b": rep},
            "opt_state": None, "step": rep}
batch_sh = {"x": NamedSharding(mesh, P("data", None, None)),
            "y": NamedSharding(mesh, P("data", None))}
with mesh:
    sh_losses, sh_w = run(dict(in_shardings=(state_sh, batch_sh, batch_sh,
                                             rep),
                               out_shardings=None), "sharded")

import numpy as np
err_l = max(abs(a - b) for a, b in zip(ref_losses, sh_losses))
err_w = max(abs(a - b) for a, b in zip(ref_w, sh_w))
print(json.dumps({"err_loss": err_l, "err_w": err_w,
                  "losses": ref_losses[:3]}))
assert err_l < 1e-4, (ref_losses, sh_losses)
assert err_w < 1e-4
print("DISTRIBUTED_OK")
"""

A2A_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
import dataclasses
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import ByzVRMarinaConfig, get_aggregator
from repro.core.sharded_agg import tree_aggregate_all_to_all

mesh = jax.make_mesh((4, 2), ("data", "model"))
n = 4
key = jax.random.PRNGKey(0)
sent = {"w": jax.random.normal(key, (n, 6, 8)),
        "b": jax.random.normal(jax.random.fold_in(key, 1), (n, 10))}
specs = {"w": P(None, "model"), "b": P(None)}
agg = get_aggregator("cm", bucket_size=2)
cfg = ByzVRMarinaConfig(n_workers=n, aggregator=agg,
                        worker_axes=("data",), model_axis="model",
                        mesh=mesh, grad_specs=specs, agg_mode="all_to_all")

with mesh:
    got = jax.jit(lambda s: tree_aggregate_all_to_all(cfg, key, s))(sent)
want = agg.tree(key, sent)
import numpy as np
for k in sent:
    np.testing.assert_allclose(np.asarray(got[k]), np.asarray(want[k]),
                               rtol=1e-5, atol=1e-6)
print("A2A_OK")

# Pallas-kernel aggregation path inside the shard_map body: default-on for
# TPU backends (use_pallas_agg auto), pinned here via the env-var override
from repro.core import sharded_agg
assert sharded_agg.use_pallas_agg() == (jax.default_backend() == "tpu")
os.environ["REPRO_PALLAS_AGG"] = "1"
assert sharded_agg.use_pallas_agg()
try:
    with mesh:
        got_p = jax.jit(lambda s: tree_aggregate_all_to_all(cfg, key, s))(sent)
finally:
    os.environ["REPRO_PALLAS_AGG"] = "0"
    assert not sharded_agg.use_pallas_agg()
    del os.environ["REPRO_PALLAS_AGG"]
for k in sent:
    np.testing.assert_allclose(np.asarray(got_p[k]), np.asarray(want[k]),
                               rtol=1e-5, atol=1e-6)
print("A2A_PALLAS_OK")
"""

SPARSE_SCRIPT = r"""
import jax, jax.numpy as jnp
from repro.core import (ByzVRMarinaConfig, get_aggregator, get_attack,
                        get_compressor, make_init, make_step)
from repro.data import (init_logreg_params, logreg_loss, make_logreg_data)

KEY = jax.random.PRNGKey(0)
DIM = 20
data = make_logreg_data(KEY, n_samples=200, dim=DIM, n_workers=4)
loss_fn = logreg_loss(0.01)
full = {"x": data.features, "y": data.labels}

cfg = ByzVRMarinaConfig(
    n_workers=4, n_byz=1, p=0.15, lr=0.4,
    aggregator=get_aggregator("cm", bucket_size=2),
    compressor=get_compressor("randk", ratio=0.5, common_randomness=True),
    attack=get_attack("ALIE"), agg_mode="sparse_support")
step = jax.jit(make_step(cfg, loss_fn))
anchor = data.stacked()
state = make_init(cfg, loss_fn)(init_logreg_params(DIM), anchor, KEY)
k = KEY
l0 = float(loss_fn(state["params"], full))
for it in range(400):
    k, k1, k2 = jax.random.split(k, 3)
    state, m = step(state, data.sample_batches(k1, 16), anchor, k2)
    assert jnp.isfinite(m["loss"])
l1 = float(loss_fn(state["params"], full))
assert l1 < l0 - 0.1, (l0, l1)
print("SPARSE_OK", l0, l1)
"""

SPEC_A2A_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
from repro.api import RunSpec, run

spec = RunSpec(task="logreg", method="marina", n_workers=4, n_byz=1,
               p=0.3, lr=0.3, attack="ALIE", aggregator="cm", bucket_size=2,
               agg_mode="all_to_all", steps=4,
               data_kwargs={"n_samples": 80, "dim": 12, "batch_size": 8})
a2a = run(spec, log_every=1)
ref = run(spec.replace(agg_mode="gspmd"), log_every=1)
err = max(abs(a["loss"] - b["loss"])
          for a, b in zip(a2a.history, ref.history))
assert err < 1e-5, err
print("SPEC_A2A_OK", err)
"""

A2A_WARMUP_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
from repro.api import RunSpec, build

spec = RunSpec(task="logreg", method="marina", n_workers=4, n_byz=1,
               p=0.3, lr=0.3, attack="ALIE", aggregator="cm", bucket_size=2,
               agg_mode="all_to_all", steps=4,
               data_kwargs={"n_samples": 80, "dim": 12, "batch_size": 8})
exp = build(spec)
sizes = []
exp.run(log_every=1, warmup=True,
        callback=lambda it, state, m: sizes.append(exp.step._cache_size()))
assert len(sizes) == 4 and len(set(sizes)) == 1, sizes
print("A2A_WARMUP_OK", sizes)
"""

MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
import jax
from repro.launch.mesh import make_production_mesh, n_workers, worker_axes

m1 = make_production_mesh()
assert dict(m1.shape) == {"data": 16, "model": 16}, m1.shape
assert n_workers(m1) == 16
m2 = make_production_mesh(multi_pod=True)
assert dict(m2.shape) == {"pod": 2, "data": 16, "model": 16}
assert n_workers(m2) == 32
assert worker_axes(m2) == ("pod", "data")
m3 = make_production_mesh(model_parallel=64)
assert dict(m3.shape) == {"data": 4, "model": 64}
print("MESH_OK")
"""


def _run(src):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    return subprocess.run([sys.executable, "-c", src], capture_output=True,
                          text=True, env=env, timeout=600)


def test_sharded_step_matches_single_device():
    r = _run(SCRIPT)
    assert "DISTRIBUTED_OK" in r.stdout, r.stdout + r.stderr


def test_production_mesh_shapes():
    r = _run(MESH_SCRIPT)
    assert "MESH_OK" in r.stdout, r.stdout + r.stderr


def test_all_to_all_aggregation_matches_gspmd():
    """§Perf all_to_all sharded CM == reference tree CM on a real mesh,
    with both the jnp and the Pallas-kernel per-device rules."""
    r = _run(A2A_SCRIPT)
    assert "A2A_OK" in r.stdout, r.stdout + r.stderr
    assert "A2A_PALLAS_OK" in r.stdout, r.stdout + r.stderr


def test_run_spec_all_to_all_matches_gspmd():
    """The declarative API's agg_mode="all_to_all" (mesh derived from the
    visible devices by api.runner) must match the gspmd trajectory."""
    r = _run(SPEC_A2A_SCRIPT)
    assert "SPEC_A2A_OK" in r.stdout, r.stdout + r.stderr


def test_all_to_all_warmup_leaves_no_compile_in_the_run():
    """The all_to_all step hands its state back on the mesh, placed unlike
    the start state: warm-up must compile that placement too, so no round
    of the timed loop compiles."""
    r = _run(A2A_WARMUP_SCRIPT)
    assert "A2A_WARMUP_OK" in r.stdout, r.stdout + r.stderr


def test_sparse_support_mode_trains():
    """§Perf sparse-support (common-randomness RandK) trains under attack."""
    r = _run(SPARSE_SCRIPT)
    assert "SPARSE_OK" in r.stdout, r.stdout + r.stderr
