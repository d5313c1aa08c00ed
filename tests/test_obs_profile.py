"""The names the program gives its parts on a profiler trace
(``repro.obs.profile``): the round step's layer and round-kind scopes in
each compiled instruction's ``op_name``, and the training loop's host spans
on the trace's host plane."""
import glob
import os
import re

import jax
import pytest

from repro.api import RunSpec, build
from repro.obs import profile

# instructions that only route values in and out of the branch computations
CONTROL_OPS = ("parameter", "tuple", "get-tuple-element")

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%[\w.\-]+\s*=\s*.*?\s([\w\-]+)\(")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_WRAP = re.compile(r"^(\w+)\((.*)\)$")


def _scope_names(path: str) -> list:
    """The name-stack entries of one op_name path, transform wrappers
    (``vmap(compress)``, ``transpose(jvp(grad))``) taken off; ``jit(f)``
    names a function, not a scope, and stays."""
    out = []
    for part in path.split("/"):
        m = _WRAP.match(part)
        while m and m.group(1) not in ("jit", "pjit"):
            part = m.group(2)
            m = _WRAP.match(part)
        out.append(part)
    return out


def _spec(compressor, agg_mode, **kw):
    ckw = {"ratio": 0.5} if compressor == "randk" else {}
    if agg_mode == "sparse_support":
        ckw["common_randomness"] = True
    return RunSpec(task="logreg", method="marina", n_workers=5, n_byz=1,
                   p=0.3, lr=0.1, attack="ALIE", aggregator="cm",
                   bucket_size=2, compressor=compressor,
                   compressor_kwargs=ckw, agg_mode=agg_mode, steps=3,
                   seed=0, data_kwargs={"n_samples": 60, "dim": 13,
                                        "batch_size": 4}, **kw)


@pytest.mark.parametrize("compressor,agg_mode", [
    ("randk", "pallas"), ("identity", "pallas"), ("randk", "gspmd"),
    ("identity", "gspmd"), ("randk", "sparse_support")])
def test_every_round_op_lies_under_one_layer_scope(compressor, agg_mode):
    exp = build(_spec(compressor, agg_mode))
    state, k_run = exp.start()
    hlo = exp.step.lower(*exp.step_args(state, 0, k_run)).compile().as_text()
    seen, kinds = set(), set()
    for line in hlo.splitlines():
        instr, op = _INSTR.match(line), _OP_NAME.search(line)
        if not instr or not op:
            continue
        # an instruction XLA merged from several lists each source's path
        for path in op.group(1).split(";"):
            names = _scope_names(path)
            kind = [n for n in names if n in profile.ROUND_SCOPES]
            layers = {n for n in names if n in profile.LAYER_SCOPES}
            seen |= layers
            kinds |= set(kind)
            if kind and instr.group(1) not in CONTROL_OPS:
                assert len(kind) == 1 and len(layers) == 1, (
                    instr.group(1), path)
    assert kinds == set(profile.ROUND_SCOPES)
    want = {"grad", "compress", "attack", "aggregate", "update"}
    assert seen == want


def _host_events(trace_dir, names=profile.LOOP_SPANS):
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in names:
                    out.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                ev.name, dict(ev.stats).get("step_num")))
    return out


def test_run_loop_spans_on_the_host_plane(tmp_path):
    exp = build(_spec("randk", "gspmd"))
    exp.run(log_every=2)                      # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        exp.run(log_every=2)
    events = _host_events(str(tmp_path))
    rounds = sorted((s, e, st) for s, e, name, st in events
                    if name == "round")
    assert [st for _, _, st in rounds] == [0, 1, 2]
    for s, e, step in rounds:
        inside = sorted(name for s2, e2, name, _ in events
                        if name != "round" and s <= s2 and e2 <= e)
        want = ["dispatch", "feed"] + (["log"] if step in (0, 2) else [])
        assert inside == sorted(want), (step, inside)
    assert not [name for s2, e2, name, _ in events if name != "round"
                and not any(s <= s2 and e2 <= e for s, e, _ in rounds)]


def test_span_emits_only_to_a_sink_and_always_annotates(tmp_path):
    from repro.obs import RingSink, span
    ring = RingSink()
    with jax.profiler.trace(str(tmp_path)):
        with span(ring, "cell", run_id="r0"):
            with span(None, "round", step_num=4):
                pass
    assert [(e["type"], e["name"], e["run_id"]) for e in ring.events] == [
        ("span", "cell", "r0")]
    got = {name: st for _, _, name, st in _host_events(
        str(tmp_path), ("cell", "round"))}
    assert got == {"cell": None, "round": 4}


def test_scoped_nested_calls_restore_the_name_stack():
    """``tree_utils.scoped`` opens a fresh scope per call: the same scope
    used as a ``jax.named_scope`` decorator shares one context object, and
    a nested call then leaves it on the name stack of what follows."""
    import jax.numpy as jnp

    from repro.core import tree_utils as tu

    @tu.scoped("aggregate")
    def agg(x, depth):
        return agg(x, depth - 1) if depth else jnp.sin(x)

    hlo = jax.jit(lambda x: jnp.cos(agg(x, 2))).lower(1.0).compile()
    names = set(_OP_NAME.findall(hlo.as_text()))
    assert {n for n in names if n.endswith("/cos")} == {"jit(<lambda>)/cos"}
    assert {n for n in names if n.endswith("/sin")} == {
        "jit(<lambda>)/aggregate/aggregate/aggregate/sin"}
