"""The plain reference at a size a CPU test run holds (tests/tiny.py).

Its per-worker gradients wait in host memory and the aggregation takes one
leaf's stack at a time; the arithmetic is the one the reference had when
all of them stayed on the device, so each round's loss and aggregate must
hash to what that version gave (recorded from it on the CPU)."""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchlib import harness
from benchlib.data import Traffic
from benchlib.refround import RNG, Reference
from tiny import tiny_cell

SEED = 2147483999


def digest(loss, leaves):
    h = hashlib.sha256(np.float64(loss).tobytes())
    for a in leaves:
        h.update(np.asarray(jax.device_get(a), np.float32).tobytes())
    return h.hexdigest()[:16]


@pytest.mark.parametrize("workload,full,diff", [
    ("mamba2-130m.vrmarina-randk", "de9c9952314289d6", "1b31a057c09ccfdd"),
    ("mamba2-130m.vrmarina-uncompressed", "de9c9952314289d6",
     "cfb9031569f61c52"),
    ("musicgen-medium.vrmarina-uncompressed", "7429ab740270338b",
     "e6bb016d1d1d972b"),
])
def test_rounds_give_the_recorded_aggregates(workload, full, diff):
    cell = tiny_cell(workload)
    _, k_init, k_run, k_data = harness.seed_keys(SEED)
    data = Traffic(cell.arch, cell.traffic, k_data)
    p0 = jax.jit(lambda k: cell.model.init_params(k, cell.arch))(k_init)
    ref = Reference(cell.model, cell.arch, cell.traffic)
    x0 = jax.tree.map(lambda a: a.astype(jnp.float32), p0)
    _, _, k_agg0 = jax.random.split(k_run, 3)
    loss, g = ref._full(x0, data.anchor(0), k_agg0)
    assert digest(loss, g) == full
    k_step, _ = jax.random.split(jax.random.fold_in(k_run, 1))
    ks = dict(zip(RNG, jax.random.split(k_step, len(RNG))))
    xn = jax.tree.map(ref._round_down, x0,
                      jax.tree.unflatten(jax.tree.structure(x0), g))
    loss, g1 = ref._diff(xn, x0, data.minibatch(0), g, ks["q"], ks["agg"])
    assert digest(loss, g1) == diff


def test_recomputed_layers_give_the_same_gradients(monkeypatch):
    # long sequences recompute mamba2's layers in the backward pass
    # (configs/mamba2-130m.py, SAVED_BYTES): the loss and gradients are
    # those of the stored layers, to float32 rounding
    from benchlib.refops import make_policy
    cell = tiny_cell("mamba2-130m.vrmarina-a2a-4chip")
    _, k_init, _, k_data = harness.seed_keys(SEED)
    batch = jax.tree.map(lambda a: a[0], Traffic(
        cell.arch, cell.traffic, k_data).anchor(0))
    x0 = jax.tree.map(lambda a: a.astype(jnp.float32),
                      cell.model.init_params(k_init, cell.arch))

    def grads():
        return jax.jit(jax.value_and_grad(lambda p: cell.model.loss(
            p, batch, cell.arch, make_policy("f32"))))(x0)

    stored = grads()
    monkeypatch.setattr(cell.model, "SAVED_BYTES", 0)
    recomputed = grads()
    assert float(recomputed[0]) == float(stored[0])
    for a, b in zip(jax.tree.leaves(recomputed[1]),
                    jax.tree.leaves(stored[1])):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-5 * float(
            jnp.max(jnp.abs(b))))


@pytest.mark.parametrize("change,match", [
    ({"aggregator": "rfa"}, "aggregator 'rfa'"),
    ({"method": "sgd"}, "method 'sgd'"),
    ({"attack": "IPM"}, "attack 'IPM'"),
    ({"compressor": "topk"}, "compressor 'topk'"),
    ({"compressor_kwargs": {"ratio": 0.1, "common_randomness": True}},
     "common_randomness"),
    ({"participation": 0.5}, "participation"),
    ({"aggregator_kwargs": {"T": 8}}, "aggregator_kwargs"),
])
def test_a_round_it_does_not_model_is_refused(change, match):
    cell = tiny_cell()
    with pytest.raises(harness.BenchError, match=match):
        Reference(cell.model, cell.arch, dict(cell.traffic, **change))
