"""The scope and span reduction (benchlib/spans.py): op names to layers,
the neighbour rule for instructions without metadata, device time by scope
and round kind, host spans and the device's idle time inside them, on a
small trace with counts made by hand; the count check of the round-kind
readers; the HLO and host spans a CPU trace records."""
import json
import os
import types

import pytest

from benchlib import harness, spans

HERE = os.path.dirname(os.path.abspath(__file__))
METRICS = os.path.join(os.path.dirname(HERE), "metrics")

HLO = '''HloModule jit_step, is_scheduled=true

%fc (p.0: f32[8]) -> f32[8] {
  %p.0 = f32[8]{0} parameter(0)
  ROOT %mul.0 = f32[8]{0} multiply(%p.0, %p.0), metadata={op_name="jit(step)/cond/branch_0_fun/diff_round/vmap(grad)/transpose(jvp())/mul"}
}

%shared (p.3: f32[8]) -> f32[8] {
  %p.3 = f32[8]{0} parameter(0)
  ROOT %neg.0 = f32[8]{0} negate(%p.3), metadata={op_name="jit(step)/cond/branch_1_fun/full_round/grad/neg"}
}

%branch_0 (p.1: f32[8]) -> f32[8] {
  %p.1 = f32[8]{0} parameter(0)
  %fusion.10 = f32[8]{0} fusion(%p.1), kind=kLoop, calls=%shared
  %sort.1 = f32[8]{0} sort(%p.1), metadata={op_name="jit(step)/cond/branch_0_fun/diff_round/compress/vmap(compress)/sort"}
  %scatter.12 = f32[8]{0} add(%sort.1, %sort.1), metadata={op_name="scatter"}
  %fusion.2 = f32[8]{0} fusion(%sort.1), kind=kLoop, calls=%fc
  %copy.3 = f32[8]{0} copy(%fusion.2)
  ROOT %cc.4 = f32[8]{0} custom-call(%copy.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/cond/branch_0_fun/diff_round/aggregate/attack/mean;jit(step)/cond/branch_0_fun/diff_round/aggregate/robust_agg"}
}

%branch_1 (p.2: f32[8]) -> f32[8] {
  %p.2 = f32[8]{0} parameter(0)
  ROOT %dot.5 = f32[8]{0} dot(%p.2, %p.2), metadata={op_name="jit(step)/cond/branch_1_fun/full_round/grad/jit(loss)/dot_general"}
}

ENTRY %main (a: f32[8], b: pred[]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %b = pred[] parameter(1)
  %xor.8 = f32[8]{0} xor(%a, %a), metadata={op_name="jit(step)/jit(_threefry_split)/xor"}
  %conditional.6 = f32[8]{0} conditional(%b, %xor.8, %xor.8), branch_computations={%branch_0, %branch_1}, metadata={op_name="jit(step)/cond"}
  %hoisted.9 = f32[8]{0} negate(%a), metadata={op_name="jit(step)/cond/branch_1_fun/full_round/grad/neg"}
  ROOT %fusion.7 = f32[8]{0} subtract(%conditional.6, %a), metadata={op_name="jit(step)/jit(update)/update/sub"}
}
'''

# two executions of jit_step: a difference round, then a full round; the
# conditional encloses its branch's ops; another program runs between
EXECS = {"/device:TPU:0": [(0, 1000, "jit_step(7)"),
                           (1500, 1600, "jit_other(9)"),
                           (2000, 3000, "jit_step(7)")]}
EVENTS = {"/device:TPU:0": [
    (0, 10, "jit_step", "xor.8"),
    (20, 700, "jit_step", "conditional.6"),
    (30, 230, "jit_step", "sort.1"),
    (240, 340, "jit_step", "fusion.2"),
    (350, 370, "jit_step", "copy.3"),
    (380, 580, "jit_step", "cc.4"),
    (710, 800, "jit_step", "fusion.7"),
    (1500, 1600, "jit_other", "add.1"),
    (2000, 2010, "jit_step", "xor.8"),
    (2020, 2500, "jit_step", "conditional.6"),
    (2030, 2330, "jit_step", "dot.5"),
    (2510, 2600, "jit_step", "fusion.7"),
    (2600, 2650, "jit_step", "hoisted.9"),
]}


def test_scope_of_takes_the_innermost_layer_and_the_round_kind():
    assert spans.scope_of("jit(step)/cond/branch_0_fun/diff_round/compress/"
                          "vmap(compress)/sort") == ("compress", "diff_round")
    assert spans.scope_of("jit(step)/cond/branch_1_fun/full_round/grad/"
                          "transpose(jvp(grad))/mul") == ("grad",
                                                          "full_round")
    assert spans.scope_of("jit(step)/x/aggregate/attack/mean;jit(step)/"
                          "x/grad/y") == ("attack", None)
    # a function named like a scope is not one
    assert spans.scope_of("jit(step)/jit(update)/sub") == (None, None)
    assert spans.scope_of("jit(step)/cond") == (None, None)


def test_hlo_scopes_with_the_neighbour_rule():
    sc = spans.hlo_scopes(HLO)
    assert sc["sort.1"] == ("compress", "diff_round")
    # no metadata: the fusion takes its computation's root, the copy its
    # operand, so both count to the difference round's gradient
    assert sc["fusion.2"] == ("grad", "diff_round")
    assert sc["copy.3"] == ("grad", "diff_round")
    assert sc["cc.4"] == ("attack", "diff_round")
    assert sc["dot.5"] == ("grad", "full_round")
    assert sc["conditional.6"] == (None, None)
    assert sc["fusion.7"] == ("update", None)
    assert sc["xor.8"] == (None, None)
    # a fusion computation both branches share names this op from the
    # full round; it runs in the difference round's branch
    assert sc["fusion.10"] == ("grad", "diff_round")
    # a compiler pass's bare op_name names no scope: the operand's counts
    assert sc["scatter.12"] == ("compress", "diff_round")
    # hoisted out of both branches: it runs every round, so no round kind
    assert sc["hoisted.9"] == ("grad", None)
    assert spans.has_scopes(sc, spans.LAYERS)
    assert spans.has_scopes(sc, spans.ROUND_KINDS)
    assert not spans.has_scopes(spans.hlo_scopes(
        HLO.replace("diff_round", "x").replace("full_round", "x")),
        spans.ROUND_KINDS)


def test_device_scopes_by_hand():
    d = spans.device_scopes(EVENTS, EXECS, "jit_step",
                            spans.hlo_scopes(HLO))
    # conditional self time: 680 - 200 - 100 - 20 - 200 = 160 and
    # 480 - 300 = 180; the other program's op counts nowhere
    assert d.layer_ns == {"compress": 200.0,
                          "grad": 100.0 + 20.0 + 300.0 + 50.0,
                          "attack": 200.0, "update": 180.0,
                          spans.UNSCOPED: 10.0 + 10.0 + 160.0 + 180.0}
    assert d.step_ns == 1410.0
    assert d.kind_ns == {"diff_round": 520.0, "full_round": 300.0}
    assert d.kind_execs == {"full_round": 1, "diff_round": 1}
    assert (d.exec_ns, d.n_execs) == (2000.0, 2)


def test_an_op_that_starts_with_its_first_inner_op_encloses_it():
    # in start order with ties shortest first, as the trace's events come:
    # the loop (fusion.2) starts with a short op (copy.3) inside it
    events = {"/device:TPU:0": [
        (0, 1000, "jit_step", "conditional.6"),
        (100, 110, "jit_step", "copy.3"),
        (100, 600, "jit_step", "fusion.2"),
        (200, 300, "jit_step", "sort.1")]}
    execs = {"/device:TPU:0": [(0, 1000, "jit_step(7)")]}
    d = spans.device_scopes(events, execs, "jit_step", spans.hlo_scopes(HLO))
    # conditional 1000 - 500, loop 500 - 10 - 100, copy 10, sort 100
    assert d.step_ns == d.exec_ns == 1000.0
    assert d.layer_ns == {spans.UNSCOPED: 500.0, "grad": 390.0 + 10.0,
                          "compress": 100.0}


def test_self_times_count_each_instant_once():
    # a loop, an op inside it, and an asynchronous op that starts inside
    # that one and runs past it: each instant goes to the latest-started
    # op covering it, so the sum is the union
    events = [(0, 100, "m", "loop"), (10, 20, "m", "a"), (15, 90, "m", "b")]
    assert spans.self_times(events) == [20.0, 5.0, 75.0]
    # ties: the shorter op takes what it covers
    assert spans.self_times([(0, 50, "m", "x"), (0, 10, "m", "y")]) == [
        40.0, 10.0]


RECORDED = os.path.join(HERE, "data", "trace-v5e-scoped.json")


def test_recorded_v5e_scoped_trace():
    """395 ops of two step executions of a traced RandK window on a TPU
    v5e (a difference round, then a full-gradient round: their longest
    ops, conditionals, loops, a run of consecutive ops and ops without
    metadata), with the scope and round-kind times a quadratic
    reimplementation gave."""
    with open(RECORDED) as f:
        rec = json.load(f)
    plane = "/device:TPU:0"
    d = spans.device_scopes(
        {plane: [tuple(e) for e in rec["events"]]},
        {plane: [tuple(x) for x in rec["executions"]]}, rec["module"],
        spans.hlo_scopes(rec["hlo"]))
    assert d.layer_ns == pytest.approx(rec["expected_layer_ns"])
    assert d.kind_ns == pytest.approx(rec["expected_kind_ns"])
    assert d.kind_execs == rec["expected_kind_execs"]


def test_host_spans_and_idle_inside_them():
    events = [(0, 1000, "round", 0), (10, 50, "feed", None),
              (60, 100, "dispatch", None), (500, 900, "log", None),
              (1000, 2000, "round", 1), (1010, 1040, "feed", None),
              (1050, 1090, "dispatch", None),
              (2500, 2600, "feed", None)]         # outside every round
    host = spans.host_spans(events)
    assert host["round"] == [(0, 1000, 0), (1000, 2000, 1)]
    assert host["feed"] == [(10, 50), (1010, 1040)]
    assert host["log"] == [(500, 900)]
    assert host["checkpoint"] == []
    dev = [(0, 600, "m", "a"), (700, 1000, "m", "b")]
    assert spans.idle_within(dev, host["log"]) == 100.0


def _sp(full, diff):
    dev = spans.DeviceScopes(
        layer_ns={"grad": 4e6, spans.UNSCOPED: 1e6}, step_ns=5e6,
        exec_ns=5e6, kind_ns={"full_round": 2e6, "diff_round": 3e6},
        kind_execs={"full_round": full, "diff_round": diff},
        n_execs=full + diff)
    return spans.Spans(rounds=5, device=dev, has_layers=True,
                       has_kinds=True, host_ns={"feed": 5e6},
                       n_round_spans=5, log_idle_ns=0.0)


@pytest.mark.parametrize("name,full,diff,want", [
    ("diff_round_ms", 1, 4, 3.0 / 4), ("full_round_ms", 1, 4, 2.0),
    ("diff_round_ms", 1, 3, None), ("full_round_ms", 2, 4, None)])
def test_round_kind_readers_fail_the_run_on_a_count_mismatch(
        monkeypatch, name, full, diff, want):
    reader = harness.load_module(os.path.join(METRICS, name + ".py"),
                                 "bench_metric_" + name)
    monkeypatch.setattr(spans, "read", lambda ctx: _sp(full, diff))
    ctx = types.SimpleNamespace(rounds=5, full_rounds=1)
    if want is None:
        with pytest.raises(harness.BenchError):
            reader.read(ctx)
    else:
        assert reader.read(ctx) == pytest.approx(want)


def test_readers_print_nothing_without_scopes(monkeypatch):
    monkeypatch.setattr(spans, "read", lambda ctx: None)
    ctx = types.SimpleNamespace(rounds=5, full_rounds=1)
    for f in sorted(os.listdir(METRICS)):
        if f.endswith(("_scope_ms.py", "_round_ms.py")) or f in (
                "unscoped_share.py", "feed_host_ms.py", "log_idle_ms.py"):
            reader = harness.load_module(os.path.join(METRICS, f),
                                         "bench_metric_" + f[:-3])
            assert reader.read(ctx) is None, f


def test_a_cpu_trace_records_the_hlo_and_the_spans(tmp_path):
    import glob

    import jax
    import jax.numpy as jnp

    from benchlib import trace

    @jax.jit
    def step(x):
        with jax.named_scope("grad"):
            y = jnp.sin(x) * 2.0
        with jax.named_scope("update"):
            return x - y

    x = jnp.ones((8,))
    step(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        for it in range(2):
            with jax.profiler.StepTraceAnnotation("round", step_num=it):
                with jax.profiler.TraceAnnotation("dispatch"):
                    x = step(x)
        x.block_until_ready()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    protos = spans.recorded_hlo(path)
    name = next(k for k in protos if k.startswith("jit_step("))
    sc = spans.hlo_scopes(spans.hlo_text(protos[name]))
    assert {layer for layer, _ in sc.values()} >= {"grad", "update"}
    host = spans.host_spans(spans.annotation_events(trace.load(
        str(tmp_path))))
    assert [st for _, _, st in host["round"]] == [0, 1]
    assert len(host["dispatch"]) == 2
