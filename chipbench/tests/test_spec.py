"""The program's RunSpec is made from the traffic file: the harness's own
keys go where they always went, every other key names a RunSpec field."""
import pytest
from repro.api import RunSpec, resolve_agg_mode

from benchlib import harness
from tiny import load

SHARED = dict(task="lm", method="marina", n_workers=8, n_byz=1,
              attack="ALIE", attack_kwargs={"z": 1.06}, aggregator="cm",
              bucket_size=2, p=0.1, lr=1e-4, steps=3, seed=7)


@pytest.mark.parametrize("workload,want", [
    ("mamba2-130m.vrmarina-randk", dict(
        arch="mamba2-130m", compressor="randk",
        compressor_kwargs={"ratio": 0.1},
        agg_mode=resolve_agg_mode("auto"),
        data_kwargs={"seq_len": 128, "per_worker_batch": 1,
                     "remat": False}, **SHARED)),
    ("musicgen-medium.vrmarina-uncompressed", dict(
        arch="musicgen-medium@3L", compressor="identity",
        compressor_kwargs={}, agg_mode=resolve_agg_mode("auto"),
        data_kwargs={"seq_len": 1500, "per_worker_batch": 1,
                     "remat": True}, **SHARED)),
    ("mamba2-130m.vrmarina-uncompressed", dict(
        arch="mamba2-130m", compressor="identity", compressor_kwargs={},
        agg_mode=resolve_agg_mode("auto"),
        data_kwargs={"seq_len": 128, "per_worker_batch": 1,
                     "remat": False}, **SHARED)),
])
def test_run_spec_of_each_cell(workload, want):
    got = harness.run_spec(load(workload), 7)
    # the cell's arch is registered by run_spec, so the literal is made now
    assert got == RunSpec(**want)


def test_traffic_keys_reach_the_spec():
    cell = harness.load_cell("mamba2-130m.vrmarina-randk")
    cell.traffic = dict(cell.traffic, participation=0.5, aggregator="rfa",
                        aggregator_kwargs={"T": 8}, bucket_size=0)
    spec = harness.run_spec(cell, 7)
    assert spec.participation == 0.5
    assert spec.aggregator == "rfa" and spec.aggregator_kwargs == {"T": 8}
    assert spec.bucket_size == 0
    assert "anchor_batches" not in spec.data_kwargs


@pytest.mark.parametrize("key", ["participaton", "anchor_batch", "steps",
                                 "seed", "data_kwargs"])
def test_a_key_that_is_no_settable_field_is_refused(key):
    cell = harness.load_cell("mamba2-130m.vrmarina-randk")
    cell.traffic = dict(cell.traffic, **{key: 1})
    with pytest.raises(harness.BenchError, match=key):
        harness.run_spec(cell, 7)
