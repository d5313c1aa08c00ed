"""The trace reduction: HLO stacks, layer attribution, self time, busy
union and idle gaps, on a small trace with counts made by hand, and on
an excerpt recorded on a TPU v5e (tests/data/trace-v5e.json)."""
import json
import os

import pytest

from benchlib import trace

HERE = os.path.dirname(os.path.abspath(__file__))
LAYERS_DIR = os.path.join(os.path.dirname(HERE), "layers")

HLO = '''HloModule jit_step, is_scheduled=true

FileNames
1 "/ck/src/repro/models/layers.py"
2 "/ck/src/repro/core/compressors.py"
3 "/ck/src/repro/core/tree_utils.py"
4 "/ck/src/repro/core/estimators.py"

FunctionNames
1 "mlp"
2 "rand_k.<locals>.compress"
3 "masked_mean_std.<locals>.mean_leaf"
4 "masked_mean_std"
5 "MarinaEstimator.round"

FileLocations
1 {file_name_id=1 function_name_id=1 line=394 end_line=394 column=8 end_column=20}
2 {file_name_id=2 function_name_id=2 line=143 end_line=143 column=8 end_column=20}
3 {file_name_id=3 function_name_id=3 line=60 end_line=60 column=8 end_column=20}
4 {file_name_id=3 function_name_id=4 line=62 end_line=62 column=8 end_column=20}
5 {file_name_id=4 function_name_id=5 line=121 end_line=121 column=8 end_column=20}

StackFrames
1 {file_location_id=5 parent_frame_id=1}
2 {file_location_id=1 parent_frame_id=1}
3 {file_location_id=2 parent_frame_id=1}
4 {file_location_id=4 parent_frame_id=1}
5 {file_location_id=3 parent_frame_id=4}

ENTRY %main () -> f32[] {
  %dot.1 = f32[8,8]{1,0} dot(%a, %b), metadata={op_name="jit(step)/dot_general" stack_frame_id=2}
  %sort.2 = f32[8]{0} sort(%c), metadata={op_name="jit(step)/sort" stack_frame_id=3}
  %fusion.3 = f32[8]{0} fusion(%d), kind=kLoop, metadata={op_name="jit(step)/reduce_sum" stack_frame_id=5}
  %while.4 = f32[8]{0} while(%e), metadata={op_name="jit(step)/while" stack_frame_id=1}
  %copy.5 = f32[8]{0} copy(%f)
  ROOT %old.6 = f32[] add(%g, %h), metadata={op_name="x" source_file="/ck/src/repro/models/model.py" source_line=3}
}
'''

# (start, end, module, op) in ns; while.4 encloses sort.2 and fusion.3
EVENTS = [
    (0, 100, "jit_step(7)", "dot.1"),
    (150, 400, "jit_step(7)", "while.4"),
    (160, 260, "jit_step(7)", "sort.2"),
    (300, 340, "jit_step(7)", "fusion.3"),
    (500, 520, "jit_step(7)", "copy.5"),
    (520, 530, "jit_step(7)", "old.6"),
    (600, 650, "jit_other", "add.1"),
]


def test_hlo_stacks_innermost_first():
    st = trace.hlo_stacks(HLO)
    assert trace.hlo_module_name(HLO) == "jit_step"
    assert st["fusion.3"] == [
        ("/ck/src/repro/core/tree_utils.py",
         "masked_mean_std.<locals>.mean_leaf"),
        ("/ck/src/repro/core/tree_utils.py", "masked_mean_std"),
        ("/ck/src/repro/core/estimators.py", "MarinaEstimator.round")]
    assert "copy.5" not in st          # no metadata: no stack
    assert st["old.6"] == [("/ck/src/repro/models/model.py", "")]


def test_layer_times_by_hand():
    layers = trace.load_layers(LAYERS_DIR)
    got = trace.layer_times(EVENTS, {"jit_step": trace.hlo_stacks(HLO)},
                            layers)
    # dot.1 100 + old.6 10 (model step); sort.2 100 (compression);
    # fusion.3 40 (aggregation, through masked_mean_std); while.4's own
    # 250 - 100 - 40 = 110 (estimator); copy.5 20 (no stack); add.1 50
    # elsewhere
    assert got == {"model step": 110.0, "compression": 100.0,
                   "aggregation": 40.0, "estimator": 110.0,
                   "unattributed": 20.0, "other programs": 50.0}


def test_busy_union_and_gaps():
    assert trace.union_ns(EVENTS) == 100 + 250 + 30 + 50
    assert trace.idle_gaps(EVENTS, 0, 700) == [
        (100, 150), (400, 500), (530, 600), (650, 700)]


RECORDED = os.path.join(HERE, "data", "trace-v5e.json")


def test_recorded_v5e_trace():
    """252 ops of the step program from a traced window on a TPU v5e (the
    longest, the robust_agg kernels and a run of consecutive ops), with the
    layer times and busy time a quadratic reimplementation gave."""
    with open(RECORDED) as f:
        rec = json.load(f)
    layers = trace.load_layers(LAYERS_DIR)
    events = [tuple(e) for e in rec["events"]]
    got = trace.layer_times(events, {rec["module"]: trace.hlo_stacks(
        rec["hlo"])}, layers)
    assert got == pytest.approx(rec["expected_layer_ns"])
    assert trace.union_ns(events) == pytest.approx(rec["expected_busy_ns"])


def test_idle_share_fails_on_a_union_longer_than_the_window():
    import types

    from benchlib import harness
    reader = harness.load_module(
        os.path.join(os.path.dirname(HERE), "metrics", "idle_share.py"),
        "bench_metric_idle_share")
    assert reader.read(types.SimpleNamespace(busy_s=1.5, window_s=2.0)) \
        == pytest.approx(25.0)
    with pytest.raises(harness.BenchError):
        reader.read(types.SimpleNamespace(busy_s=2.001, window_s=2.0))
