"""From the program's own names to per-layer device time and loop time.

The program names its parts (``repro.obs.profile``): the round step traces
its work under ``jax.named_scope`` layer scopes (``LAYERS``), MARINA's two
branches under round-kind scopes (``ROUND_KINDS``), and the training loop
runs each round under a ``round`` profiler step holding host spans
(``SPANS``). This module reads those names back from a traced window.

Device scopes: each op of the step program is an instruction of the
compiled HLO, which the trace records itself (the metadata plane's "Hlo
Proto" of each program). An instruction's ``op_name`` metadata is the name
stack it was traced under, ``jit(step)/cond/branch_0_fun/diff_round/
compress/...``; its layer is the innermost of the five layer scopes on that
path (a transform wraps a scope traced inside it, ``vmap(compress)``). An
instruction the compiler made
without metadata takes its nearest neighbour's ``op_name`` by the rule
``trace.hlo_stacks`` applies to stacks: the op names ride through that
function as source files. An op's device self time (``self_times``: each
instant counts once, to the latest-started op covering it) counts to its
layer, or to ``UNSCOPED`` (the key splits, MARINA's coin). An op inside a
conditional's branch counts to the branch's round kind, the one most of
the branch's own op_names carry, and a step execution that holds such an
op is a round of that kind.

Host spans: the events of those names on the trace's host plane, on the
profiler's clock; ``log_idle`` is the device's idle time inside the ``log``
spans (the loop's log-cadence syncs).

A program without the scopes or spans (one that predates them) gives
nothing to read, and the readers print nothing. Counts are checked where
they are read: the trace's round spans against the window's rounds here,
its rounds of each kind against the ``Schedule`` in the readers.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import heapq
import os
import re

from benchlib import harness, trace
from benchlib.harness import BenchError

LAYERS = ("grad", "compress", "attack", "aggregate", "update")
ROUND_KINDS = ("full_round", "diff_round")
SPANS = ("feed", "dispatch", "log", "checkpoint")
ROUND_SPAN = "round"
UNSCOPED = "unscoped"
# the HLO opcodes that move data between chips; the asynchronous ones also
# as their -start and -done halves
COLLECTIVES = ("all-to-all", "all-gather", "all-reduce", "reduce-scatter",
               "collective-permute")

_WRAP = re.compile(r"^(\w+)\((.*)\)$")


def scope_of(op_name: str):
    """-> (layer or None, round kind or None) of an HLO ``op_name``. A
    merged instruction's op_name lists its sources with ``;``: the first
    names it."""
    layer = kind = None
    for part in op_name.split(";", 1)[0].split("/"):
        m = _WRAP.match(part)
        while m and m.group(1) not in ("jit", "pjit"):
            part = m.group(2)
            m = _WRAP.match(part)
        if part in LAYERS:
            layer = part
        elif part in ROUND_KINDS:
            kind = part
    return layer, kind


def hlo_op_names(hlo_text: str) -> dict:
    """{instruction: op_name}, with the neighbour rule of
    ``trace.hlo_stacks`` for instructions without one: each instruction's
    metadata is rewritten to carry its op_name as its only source file, so
    that function's stacks are one-frame op names. A bare op_name with no
    name stack (``scatter``: an instruction a compiler pass made, such as
    the loop a scatter becomes) counts as none."""
    def as_source(m):
        name = trace._field(m.group(1), "op_name")
        meta = f'source_file="{name}"' if name and "/" in name else ""
        return m.group(0)[0] + "metadata={" + meta + "}"

    stacks = trace.hlo_stacks(trace._META.sub(as_source, hlo_text))
    return {name: st[0][0] for name, st in stacks.items() if st}


_BRANCHES = re.compile(r"(?:branch_computations|true_computation|"
                       r"false_computation)=(\{[^}]*\}|%[\w.\-]+)")
_OWN_OP_NAME = re.compile(r'[\s,]metadata=\{[^}]*op_name="((?:[^"\\]|\\.)*)"')


def branch_kinds(hlo_text: str) -> dict:
    """{instruction: round kind} for the instructions a conditional runs in
    its branches, at any depth (a loop or a fusion inside a branch). A
    branch's kind is the one most of its own op_names carry, so an
    instruction the neighbour rule named from elsewhere still counts to
    the branch it runs in."""
    comp, members, calls, own, branches = None, {}, {}, {}, []
    for line in hlo_text.splitlines():
        s = line.strip()
        m = trace._COMP.match(s)
        if m and " = " not in s:
            comp = m.group(1)
            continue
        m = trace._INSTR.match(line)
        if not m or comp is None:
            continue
        name, rest = m.group(1), m.group(2)
        members.setdefault(comp, []).append(name)
        mo = _OWN_OP_NAME.search(rest)
        if mo:
            own[name] = scope_of(mo.group(1))[1]
        for mc in trace._CALLED.finditer(rest):
            calls.setdefault(name, []).extend(
                trace._OPERANDS.findall(mc.group(1)))
        for mc in _BRANCHES.finditer(rest):
            branches += trace._OPERANDS.findall(mc.group(1))
    out = {}
    for branch in branches:
        tree, todo = [], [branch]
        while todo:
            c = todo.pop()
            for name in members.get(c, ()):
                tree.append(name)
                todo += calls.get(name, ())
        votes = {}
        for name in tree:
            if own.get(name):
                votes[own[name]] = votes.get(own[name], 0) + 1
        if votes:
            kind = max(votes, key=votes.get)
            out.update((name, kind) for name in tree)
    return out


def hlo_scopes(hlo_text: str) -> dict:
    """{instruction: (layer or None, round kind or None)}. The round kind
    is that of the conditional's branch the instruction runs in: one the
    compiler hoisted out of both branches keeps a branch's op_name but
    runs every round."""
    kinds = branch_kinds(hlo_text)
    return {name: (scope_of(op)[0], kinds.get(name))
            for name, op in hlo_op_names(hlo_text).items()}


_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")


def hlo_collectives(hlo_text: str) -> set:
    """The instructions of an HLO text whose opcode is a collective
    (``COLLECTIVES``). The opcode is the first word after the result's type
    that opens an operand list: a type holds no space before a parenthesis
    (``bf16[8]{0:T(8,128)}``, ``(f32[8]{0}, s32[])``)."""
    out = set()
    for line in hlo_text.splitlines():
        m = trace._INSTR.match(line)
        if not m:
            continue
        op = _OPCODE.search(" " + m.group(2))
        if op and op.group(1).removesuffix("-start").removesuffix(
                "-done") in COLLECTIVES:
            out.add(m.group(1))
    return out


def has_scopes(scopes: dict, names) -> bool:
    return any(layer in names or kind in names
               for layer, kind in scopes.values())


# --------------------------------------------------------------------------
# device time by scope
# --------------------------------------------------------------------------

def self_times(events) -> list:
    """Device self time (ns) of each of ``events`` [(start, end, ...)],
    sorted by start and, where starts tie, longest first: each instant of
    their union counts once, to the latest-started op that covers it (the
    shorter where two start together). So an enclosing loop or conditional
    keeps only the time no inner op covers, and two ops that overlap
    without nesting (an asynchronous copy) share their overlap rather than
    both count it. ``trace.self_times`` nests by a stack, which counts such
    an overlap twice, and ``trace.device_events`` orders ties shortest
    first, which nests a loop under the op it starts with."""
    out = [0.0] * len(events)
    bounds = sorted({t for s, e, *_ in events for t in (s, e)})
    active, k = [], 0                       # heap of (-index, end)
    for t0, t1 in zip(bounds, bounds[1:]):
        while k < len(events) and events[k][0] <= t0:
            heapq.heappush(active, (-k, events[k][1]))
            k += 1
        while active and active[0][1] <= t0:
            heapq.heappop(active)
        if active:
            out[-active[0][0]] += t1 - t0
    return out


@dataclasses.dataclass
class DeviceScopes:
    """Device time of the step program's executions over a window (ns,
    averaged over the device planes)."""
    layer_ns: dict          # layer or UNSCOPED -> self time
    step_ns: float          # self time of all step ops (= sum of layer_ns)
    exec_ns: float          # the step executions' own intervals
    kind_ns: dict           # round kind -> self time of ops under it
    kind_execs: dict        # round kind -> step executions holding its ops
    n_execs: int
    exchange_ns: float = 0.0  # self time of the collectives (hlo_collectives)


def device_scopes(dev: dict, execs: dict, module: str, scopes: dict,
                  collectives=frozenset()) -> DeviceScopes:
    """``dev``: {plane: [(start, end, module, op)]} (``trace.device_events``);
    ``execs``: {plane: [(start, end, execution name)]}; ``module``: the step
    program's name (``jit_step``); ``scopes``: ``hlo_scopes`` of its HLO;
    ``collectives``: ``hlo_collectives`` of its HLO."""
    per_plane = []
    for plane, evs in dev.items():
        mine = sorted(ex for ex in execs.get(plane, ())
                      if trace._module_name(ex[2]) == module)
        starts = [x[0] for x in mine]
        layer_ns, kind_ns, exchange = {}, {}, 0.0
        kinds = [set() for _ in mine]
        step = sorted((e for e in evs if trace._module_name(e[2]) == module),
                      key=lambda e: (e[0], -e[1]))
        for (s, _e, _m, op), ns in zip(step, self_times(step)):
            layer, kind = scopes.get(op, (None, None))
            key = layer or UNSCOPED
            layer_ns[key] = layer_ns.get(key, 0.0) + ns
            if op in collectives:
                exchange += ns
            if kind:
                kind_ns[kind] = kind_ns.get(kind, 0.0) + ns
                i = bisect.bisect_right(starts, s) - 1
                if i >= 0 and s < mine[i][1]:
                    kinds[i].add(kind)
        if any(len(k) > 1 for k in kinds):
            raise BenchError("a step execution holds ops of both round "
                             "kinds")
        per_plane.append((layer_ns, kind_ns,
                          {k: sum(k in ks for ks in kinds)
                           for k in ROUND_KINDS},
                          sum(e - s for s, e, _ in mine), len(mine),
                          exchange))
    n = max(len(per_plane), 1)
    counts = {(pp[2]["full_round"], pp[2]["diff_round"], pp[4])
              for pp in per_plane}
    if len(counts) > 1:
        raise BenchError(f"the device planes count different step "
                         f"executions: {sorted(counts)}")

    def mean(i):
        out = {}
        for pp in per_plane:
            for k, v in pp[i].items():
                out[k] = out.get(k, 0.0) + v / n
        return out

    layer_ns = mean(0)
    return DeviceScopes(
        layer_ns=layer_ns, step_ns=sum(layer_ns.values()),
        exec_ns=sum(pp[3] for pp in per_plane) / n, kind_ns=mean(1),
        kind_execs=dict(per_plane[0][2]) if per_plane else {},
        n_execs=per_plane[0][4] if per_plane else 0,
        exchange_ns=sum(pp[5] for pp in per_plane) / n)


# --------------------------------------------------------------------------
# host spans
# --------------------------------------------------------------------------

def host_spans(events):
    """[(start, end, name, step_num or None)] of the host's annotations ->
    {"round": [(start, end, step_num)], span: [(start, end)]}, keeping a
    span only inside a round."""
    rounds = sorted((s, e, st) for s, e, name, st in events
                    if name == ROUND_SPAN)
    starts = [r[0] for r in rounds]
    out = {ROUND_SPAN: rounds, **{k: [] for k in SPANS}}
    for s, e, name, _ in events:
        if name not in SPANS:
            continue
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and e <= rounds[i][1]:
            out[name].append((s, e))
    for k in SPANS:
        out[k].sort()
    return out


def annotation_events(pd):
    """[(start, end, name, step_num)] of every host-plane event named as a
    loop span, on any line."""
    names = (ROUND_SPAN,) + SPANS
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in names:
                    out.append((float(ev.start_ns),
                                float(ev.start_ns + ev.duration_ns), ev.name,
                                trace._stats(ev).get("step_num")))
    return out


def idle_within(dev_events, spans) -> float:
    """Device idle time (ns) inside the ``spans`` intervals: the gaps
    between the union of ``dev_events`` (sorted by start)."""
    total = 0.0
    for s, e in spans:
        total += sum(b - a for a, b in trace.idle_gaps(dev_events, s, e))
    return total


# --------------------------------------------------------------------------
# the step program's HLO, as the trace recorded it
# --------------------------------------------------------------------------

def _varint(buf, i):
    x = shift = 0
    while True:
        c = buf[i]
        i += 1
        x |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return x, i


def _fields(buf):
    """(field number, value) of a protobuf message: ints for varints,
    memoryview slices for the rest."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, v


def recorded_hlo(xplane_path: str) -> dict:
    """{program execution name, e.g. ``jit_step(12)``: serialized
    HloModuleProto} from the ``/host:metadata`` plane of an XSpace (XPlane:
    2 name, 4 event metadata, 5 stat metadata; XEventMetadata: 2 name, 5
    stats; XStat: 1 metadata id, 6 bytes; HloProto: 1 module)."""
    with open(xplane_path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for num, plane in _fields(space):
        if num != 1:
            continue
        fields = list(_fields(plane))
        name = next((bytes(v).decode() for k, v in fields if k == 2), "")
        if name != "/host:metadata":
            continue
        stat_names = {}
        for k, v in fields:
            if k == 5:
                meta = dict(_fields(dict(_fields(v))[2]))
                stat_names[meta.get(1, 0)] = bytes(meta.get(2, b"")).decode()
        for k, v in fields:
            if k != 4:
                continue
            ev = list(_fields(dict(_fields(v))[2]))
            ev_name = next((bytes(x).decode() for j, x in ev if j == 2), "")
            for j, stat in ev:
                st = dict(_fields(stat)) if j == 5 else {}
                if stat_names.get(st.get(1)) == "Hlo Proto" and 6 in st:
                    proto = dict(_fields(st[6]))
                    if 1 in proto:
                        out[ev_name] = bytes(proto[1])
    return out


def hlo_text(module_proto: bytes) -> str:
    """The HLO text of a serialized HloModuleProto, as
    ``Compiled.as_text()`` prints it (``%`` names, metadata)."""
    from jax._src.lib import xla_client
    module = xla_client.XlaComputation(module_proto).get_hlo_module()
    return module.to_string(xla_client._xla.HloPrintOptions())


# --------------------------------------------------------------------------
# a traced window -> what the readers read
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Spans:
    """What the scope and span readers read, over one traced window."""
    rounds: int
    device: DeviceScopes
    has_layers: bool
    has_kinds: bool
    host_ns: dict           # span -> host time inside rounds
    n_round_spans: int
    log_idle_ns: float

    def layer_ms_per_round(self, layer):
        if not self.has_layers or layer not in self.device.layer_ns:
            return None
        return self.device.layer_ns[layer] / 1e6 / self.rounds

    def round_kind_ms(self, kind: str, rounds_of_kind: int):
        """Device time under ``kind`` per round of that kind. The step
        executions holding its ops must number ``rounds_of_kind`` (the
        window's ``Schedule``), else the run fails."""
        if not self.has_kinds:
            return None
        n = self.device.kind_execs.get(kind, 0)
        if n != rounds_of_kind:
            raise BenchError(f"the trace holds {n} step executions under "
                             f"{kind}; the window drove {rounds_of_kind}")
        if not n:
            return None
        return self.device.kind_ns.get(kind, 0.0) / 1e6 / n

    def host_ms_per_round(self, ns: float):
        if not self.n_round_spans:
            return None
        return ns / 1e6 / self.rounds


def reduce(pd, xplane_path: str, rounds: int, log=print, planes=None):
    """-> ``Spans`` of a loaded trace, or None where the step program
    carries no scopes and the loop no spans. ``planes``: the cell's device
    planes (``trace.device_events``)."""
    dev = trace.device_events(pd, planes)
    if not dev:
        return None
    execs = {}
    for plane in pd.planes:
        for line in plane.lines:
            if plane.name in dev and line.name == trace.MODULE_LINE:
                execs[plane.name] = [
                    (float(ev.start_ns), float(ev.start_ns + ev.duration_ns),
                     ev.name) for ev in line.events]
    module = trace.step_module(dev)
    names = {ex[2] for exs in execs.values() for ex in exs
             if trace._module_name(ex[2]) == module}
    protos = {k: v for k, v in recorded_hlo(xplane_path).items()
              if trace._module_name(k) == module}
    proto = next((protos[k] for k in names if k in protos),
                 next(iter(protos.values())) if len(protos) == 1 else None)
    if proto is None:
        log(f"the trace records no HLO of {module} ({len(protos)} of that "
            "name): no scopes to read")
    text = hlo_text(proto) if proto is not None else ""
    scopes = hlo_scopes(text)
    has_layers = has_scopes(scopes, LAYERS)
    has_kinds = has_scopes(scopes, ROUND_KINDS)

    host = host_spans(annotation_events(pd))
    n_round_spans = len(host[ROUND_SPAN])
    if not (has_layers or has_kinds or n_round_spans):
        return None
    if n_round_spans and n_round_spans != rounds:
        raise BenchError(f"the trace holds {n_round_spans} round spans in "
                         f"a window of {rounds} rounds")
    device = device_scopes(dev, execs, module, scopes,
                           hlo_collectives(text))
    host_ns = {k: sum(e - s for s, e in host[k]) for k in SPANS}
    log_idle = sum(idle_within(evs, host["log"])
                   for evs in dev.values()) / len(dev)
    out = Spans(rounds=rounds, device=device, has_layers=has_layers,
                has_kinds=has_kinds, host_ns=host_ns,
                n_round_spans=n_round_spans, log_idle_ns=log_idle)
    _log(out, module, sorted(dev), log)
    return out


def _log(sp: Spans, module: str, planes: list, log) -> None:
    d, r = sp.device, sp.rounds
    total = d.step_ns or 1.0
    log(f"device planes read: {planes}; collectives "
        f"{d.exchange_ns / 1e6 / r:.3f} ms per round")
    log(f"device time by scope ({module}, per round): " + ", ".join(
        f"{k} {v / 1e6 / r:.3f} ms ({100 * v / total:.2f}%)"
        for k, v in sorted(d.layer_ns.items(), key=lambda kv: -kv[1])))
    log(f"scopes + unscoped {d.step_ns / 1e6 / r:.3f} ms per round; the "
        f"step executions {d.exec_ns / 1e6 / r:.3f} ms per round "
        f"({d.n_execs} executions, ratio "
        f"{d.step_ns / (d.exec_ns or 1.0):.4f})")
    log("round kinds: " + ", ".join(
        f"{k} {d.kind_execs.get(k, 0)} executions, "
        f"{d.kind_ns.get(k, 0.0) / 1e6:.3f} ms" for k in ROUND_KINDS))
    log(f"loop spans ({sp.n_round_spans} rounds, host ms per round): "
        + ", ".join(f"{k} {v / 1e6 / r:.3f}" for k, v in sp.host_ns.items())
        + f"; device idle inside log {sp.log_idle_ns / 1e6 / r:.3f}")


def read(ctx, tdir: str = None):
    """The ``Spans`` of the traced window a reader's ``ctx`` describes, or
    None. The trace is the harness's (``harness.OUT_DIR``/trace, read
    before the harness removes it) and must be the one ``ctx`` was made
    from: its device busy time is ``ctx.busy_s``. The result rides on
    ``ctx``, so one reduction serves every reader of a run."""
    if not hasattr(ctx, "spans"):
        ctx.spans = _read_trace(ctx, tdir)
    return ctx.spans


def _read_trace(ctx, tdir):
    tdir = tdir or os.path.join(harness.OUT_DIR, "trace")
    paths = sorted(glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        return None
    pd = trace.load(tdir)
    dev = trace.device_events(pd, ctx.planes)
    busy = (sum(trace.union_ns(evs) for evs in dev.values())
            / max(len(dev), 1) / 1e9)
    if not dev or abs(busy - ctx.busy_s) > 1e-9 * max(busy, 1.0):
        return None
    return reduce(pd, paths[-1], ctx.rounds, log=harness.log,
                  planes=ctx.planes)
