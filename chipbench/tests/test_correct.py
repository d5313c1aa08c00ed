"""``correct`` at a size a CPU test run holds (tests/tiny.py): a sound run
passes; the control (the reference in float8 in the program's place) and
each fault a training cell can have, planted under the harness, fail.
The harness's look for a chip is skipped; the rest of a run is driven as
on the chip. Readings on CPU are deterministic for a seed."""
import jax
import pytest

from benchlib import check, harness
from benchlib.refround import Reference
from tiny import tiny_cell


# the tiny cell's block with each mamba2 traffic mix: RandK and none
WORKLOADS = ["mamba2-130m.vrmarina-randk", "mamba2-130m.vrmarina-uncompressed"]


def run(workload, fault=None, seed=2):
    return harness.run(tiny_cell(workload), seed, 0.5, False,
                       t_process=0.0, require_tpu=False, fault=fault)


@pytest.mark.parametrize("workload", WORKLOADS)
def test_sound_run_is_correct(workload):
    out = run(workload)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert out["attempted"] >= 2 and out["failed"] == 0


@pytest.mark.parametrize("workload", WORKLOADS)
@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch"])
def test_planted_fault_is_not_correct(fault, workload):
    out = run(workload, fault)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("workload", WORKLOADS)
def test_control_is_not_correct(workload):
    cell = tiny_cell(workload)
    init = jax.jit(lambda k: cell.model.init_params(k, cell.arch))
    ref = Reference(cell.model, cell.arch, cell.traffic)
    control = Reference(cell.model, cell.arch, cell.traffic, "fp8")
    from benchlib.data import Traffic
    for seed in (1, 2, 3):
        _, k_init, k_run, k_data = harness.seed_keys(seed)
        traffic = Traffic(cell.arch, cell.traffic, k_data)
        p0 = init(k_init)
        rounds = harness.Schedule(k_run, cell.traffic["p"]).check
        want = ref.run(p0, traffic, k_run, rounds)
        got = control.run(p0, traffic, k_run, rounds)
        ok, checks = check.verdict(check.gaps(got, want), cell.limits)
        assert not ok, (seed, checks)
