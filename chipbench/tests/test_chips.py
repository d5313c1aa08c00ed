"""What a cell on several chips reads: shares of a peak over the cell's
chips, the trace's planes of the cell's devices alone, the collectives'
device time (``exchange_ms``), and the all_to_all cell driven through the
harness on four virtual CPU devices."""
import json
import os
import subprocess
import sys
import types

import pytest

from benchlib import flops, harness, layers_report, spans, trace
from tiny import tiny_cell

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
METRICS = os.path.join(BENCH, "metrics")
A2A = "mamba2-130m.vrmarina-a2a-4chip"


def reader(name):
    return harness.load_module(os.path.join(METRICS, name + ".py"),
                               "bench_metric_" + name)


# --------------------------------------------------------------------------
# shares of a peak
# --------------------------------------------------------------------------

def recorded_ctx(chips):
    """A reader's context from the recorded v5e trace of the RandK cell
    (tests/data/trace-v5e.json: the stack join's layer times, its span as
    the window) with that cell's FLOPs over 5 difference rounds."""
    with open(os.path.join(HERE, "data", "trace-v5e.json")) as f:
        rec = json.load(f)
    events = [tuple(e) for e in rec["events"]]
    layer_ns = trace.layer_times(
        events, {rec["module"]: trace.hlo_stacks(rec["hlo"])},
        trace.load_layers(os.path.join(BENCH, "layers")))
    window_s = (max(e for _, e, *_ in events)
                - min(s for s, *_ in events)) / 1e9
    cell = harness.load_cell("mamba2-130m.vrmarina-randk")
    return layers_report.Context(
        layer_ns=layer_ns, rounds=5, full_rounds=0, window_s=window_s,
        busy_s=trace.union_ns(events) / 1e9, trace_window_s=window_s,
        window_flops=5 * flops.round_flops(cell.arch, cell.traffic, False,
                                           cell.model),
        agg_bytes=flops.aggregation_bytes(8, 128_940_480, "bfloat16"),
        peaks=harness.peaks_for("TPU v5 lite"), chips=chips,
        planes=["/device:TPU:0"])


# the readings of the readers that divided by one chip's peak, on the
# same context
@pytest.mark.parametrize("name,one_chip", [
    ("step_mfu", 0.4375841928181728), ("agg_roofline", 17.76382137057096)])
def test_shares_of_a_peak_count_the_cells_chips(name, one_chip):
    read = reader(name).read
    assert read(recorded_ctx(1)) == one_chip
    assert read(recorded_ctx(4)) == one_chip / 4


# --------------------------------------------------------------------------
# the cell's planes
# --------------------------------------------------------------------------

def fake_profile(planes):
    """A ``ProfileData`` stand-in: {plane: ([(start, end, execution)],
    [(start, end, op)])}."""
    def ev(s, e, name, **stats):
        return types.SimpleNamespace(start_ns=s, duration_ns=e - s,
                                     name=name, stats=list(stats.items()))

    def plane(name, execs, ops):
        return types.SimpleNamespace(name=name, lines=[
            types.SimpleNamespace(name=trace.MODULE_LINE, events=[
                ev(s, e, x) for s, e, x in execs]),
            types.SimpleNamespace(name=trace.OP_LINE, events=[
                ev(s, e, op) for s, e, op in ops])])

    return types.SimpleNamespace(planes=[plane(k, *v)
                                         for k, v in planes.items()])


STEP = ([(0, 1000, "jit_step(7)")], [(0, 900, "fusion.1")])
OTHER = ([(0, 100, "jit_other(9)")], [(0, 100, "add.1")])


def test_device_events_keep_the_cells_planes():
    pd = fake_profile({"/device:TPU:0": STEP, "/device:TPU:1": STEP,
                       "/device:TPU:2": OTHER, "/device:TPU:3": ([], [])})
    assert sorted(trace.device_events(pd)) == [
        "/device:TPU:0", "/device:TPU:1", "/device:TPU:2"]
    got = trace.device_events(pd, ["/device:TPU:0", "/device:TPU:1"])
    assert got == {p: [(0.0, 900.0, "jit_step", "fusion.1")]
                   for p in ("/device:TPU:0", "/device:TPU:1")}
    assert trace.plane_name(types.SimpleNamespace(id=3)) == "/device:TPU:3"


@pytest.mark.parametrize("planes", [["/device:TPU:0"], ["/device:TPU:5"]])
def test_a_step_op_outside_the_cells_planes_fails_the_run(planes):
    pd = fake_profile({"/device:TPU:0": STEP, "/device:TPU:1": STEP})
    with pytest.raises(harness.BenchError):
        trace.device_events(pd, planes)


# --------------------------------------------------------------------------
# the exchange
# --------------------------------------------------------------------------

HLO = '''HloModule jit_step, is_scheduled=true

%add (x: f32[], y: f32[]) -> f32[] {
  %x = f32[] parameter(0)
  %y = f32[] parameter(1)
  ROOT %sum = f32[] add(%x, %y)
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0:T(256)} fusion(%a), kind=kLoop, calls=%add, metadata={op_name="jit(step)/grad/mul"}
  %all-to-all.2 = f32[4,2]{1,0:T(8,128)} all-to-all(%fusion.1), channel_id=1, replica_groups={{0,1,2,3}}, dimensions={0}, metadata={op_name="jit(step)/aggregate/shard_map/all_to_all"}
  %ag.3 = (f32[2]{0}, f32[8]{0}) all-gather-start(%all-to-all.2), channel_id=2, replica_groups={{0,1,2,3}}, dimensions={0}, metadata={op_name="jit(step)/aggregate/shard_map/all_gather"}
  %ag.4 = bf16[8]{0:T(256)(2,1)} all-gather-done(%ag.3), metadata={op_name="jit(step)/aggregate/shard_map/all_gather"}
  %all-reduce.5 = f32[8]{0} all-reduce(%a), channel_id=3, replica_groups={{0,1,2,3}}, to_apply=%add, metadata={op_name="jit(step)/attack/reduce_sum"}
  %fusion.6 = f32[8]{0} fusion(%all-reduce.5, %ag.4), kind=kLoop, calls=%add, metadata={op_name="jit(step)/attack/all-reduce"}
  ROOT %cp.7 = f32[8]{0} collective-permute(%fusion.6), channel_id=4, source_target_pairs={{0,1},{1,0}}, metadata={op_name="jit(step)/update/ppermute"}
}
'''


def test_hlo_collectives_by_opcode():
    # the fusion that reads a collective's result is not one
    assert spans.hlo_collectives(HLO) == {
        "all-to-all.2", "ag.3", "ag.4", "all-reduce.5", "cp.7"}


def four_planes():
    """Two step executions on each of four planes; chip c's collectives
    take 10 * (c + 1) ns more than chip 0's."""
    dev, execs = {}, {}
    for c in range(4):
        extra = 10 * (c + 1)
        ops = []
        for t0 in (0, 2000):
            ops += [(t0, t0 + 300, "jit_step", "fusion.1"),
                    (t0 + 300, t0 + 400 + extra, "jit_step", "all-to-all.2"),
                    (t0 + 500, t0 + 600, "jit_step", "ag.3"),
                    (t0 + 550, t0 + 560, "jit_step", "fusion.1"),
                    (t0 + 600, t0 + 650, "jit_step", "ag.4"),
                    (t0 + 700, t0 + 720, "jit_step", "all-reduce.5"),
                    (t0 + 720, t0 + 900, "jit_step", "fusion.6"),
                    (t0 + 900, t0 + 905, "jit_step", "cp.7")]
        dev[f"/device:TPU:{c}"] = ops
        execs[f"/device:TPU:{c}"] = [(0, 1000, "jit_step(7)"),
                                     (2000, 3000, "jit_step(7)")]
    return dev, execs


def test_exchange_ms_on_four_planes(monkeypatch):
    dev, execs = four_planes()
    d = spans.device_scopes(dev, execs, "jit_step", spans.hlo_scopes(HLO),
                            spans.hlo_collectives(HLO))
    # a round on chip c: all-to-all 100 + 10 (c + 1), all-gather start 100
    # less the 10 of the fusion inside it, done 50, all-reduce 20,
    # permute 5; the mean of 10 (c + 1) over four chips is 25
    assert d.exchange_ns == pytest.approx(2 * (100 + 25 + 90 + 50 + 20 + 5))
    sp = spans.Spans(rounds=2, device=d, has_layers=True, has_kinds=False,
                     host_ns={}, n_round_spans=2, log_idle_ns=0.0)
    monkeypatch.setattr(spans, "read", lambda ctx: sp)
    read = reader("exchange_ms").read
    assert read(types.SimpleNamespace(rounds=2)) == pytest.approx(290e-6)
    # a step with no collective has nothing to read
    sp.device.exchange_ns = 0.0
    assert read(types.SimpleNamespace(rounds=2)) is None


# --------------------------------------------------------------------------
# the all_to_all cell on four virtual devices
# --------------------------------------------------------------------------

def test_all_to_all_needs_as_many_devices_as_chips():
    # this process sees one CPU device
    with pytest.raises(harness.BenchError, match="all_to_all"):
        harness.build_program(tiny_cell(A2A), 7)


CHILD = f"""
import json, sys
sys.path[:0] = {[HERE, BENCH, os.path.join(os.path.dirname(BENCH), "src")]!r}
import jax
from benchlib import harness
from tiny import tiny_cell
cell = tiny_cell({A2A!r})
exp = harness.build_program(cell, 7)
out = {{"agg_mode": exp.spec.agg_mode, "n_workers": exp.spec.n_workers,
        "mesh": dict(exp.cfg.mesh.shape), "devices": len(jax.devices())}}
for fault in (None, "unchanged_state", "half_batch", "no_exchange"):
    r = harness.run(cell, 2, 0.5, False, t_process=0.0, require_tpu=False,
                    fault=fault)
    out[str(fault)] = {{"correct": r["correct"], "checks": r["checks"],
                       "attempted": r["attempted"], "failed": r["failed"]}}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def a2a_runs():
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    out = subprocess.run([sys.executable, "-c", CHILD], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_all_to_all_cell_meshes_one_worker_a_device(a2a_runs):
    assert a2a_runs["agg_mode"] == "all_to_all"
    assert a2a_runs["devices"] == a2a_runs["n_workers"] == 4
    assert a2a_runs["mesh"] == {"data": 4, "model": 1}


def test_all_to_all_sound_run_is_correct(a2a_runs):
    r = a2a_runs["None"]
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 2 and r["failed"] == 0


@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch",
                                   "no_exchange"])
def test_all_to_all_planted_fault_is_not_correct(a2a_runs, fault):
    r = a2a_runs[fault]
    assert not r["correct"], r["checks"]
