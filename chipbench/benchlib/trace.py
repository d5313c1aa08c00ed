"""From a profiler trace to device time per layer.

``capture`` wraps the measured window in ``jax.profiler.trace``. ``load``
reads the ``.xplane.pb`` it wrote with ``jax.profiler.ProfileData``.

Device time: the events of the device planes' op line ("XLA Ops"), each
an HLO instruction named by its text, inside an event of the module line
("XLA Modules") that names the program execution it belongs to. An event
that encloses others on its line (a loop or a conditional) counts only
its own time, so no interval is counted twice. Busy time is the union of
the intervals.

Layers: each instruction of the step's compiled HLO text carries the
Python stack it was traced from (``stack_frame_id`` into the module's
``StackFrames`` table, or ``source_file``/``source_line`` in older text).
The stack is walked from the innermost frame outwards, and the first
frame that a layer's table matches (``layers/*.json``: a path glob, or
``glob::function``) names the layer. An instruction the compiler made
without metadata takes its nearest traced neighbour's stack
(``hlo_stacks``). An instruction no table matches is unattributed.
"""
from __future__ import annotations

import contextlib
import fnmatch
import glob
import json
import os
import re
import shutil

OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"


@contextlib.contextmanager
def capture(out_dir):
    import jax
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir, exist_ok=True)
    with jax.profiler.trace(out_dir):
        yield


def load(out_dir):
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {out_dir}")
    return ProfileData.from_file(paths[-1])


def _stats(ev):
    try:
        return dict(ev.stats)
    except (TypeError, ValueError):
        return {}


def _op_name(name: str) -> str:
    """An op event is named by its HLO text (``%fusion.3 = f32[...] ...``)
    or by the instruction's name alone."""
    return name.split(" = ", 1)[0].strip().lstrip("%")


def _module_name(name: str) -> str:
    """``jit_step(1234)`` -> ``jit_step``."""
    return name.split("(", 1)[0]


def plane_name(device) -> str:
    """The trace's plane of a JAX device."""
    return f"/device:TPU:{device.id}"


def device_events(pd, planes=None, platform_prefix="/device:TPU:"):
    """-> {plane name: [(start_ns, end_ns, module, op)]} of op events.

    A device plane holds a module line ("XLA Modules": one event per
    program execution, ``jit_step(<id>)``) and an op line ("XLA Ops"); an
    op belongs to the module execution whose interval holds its start.

    ``planes``: the planes of the devices the cell runs on (``plane_name``).
    Only those are kept; another plane that holds an op of the step program
    (``step_module`` of the kept planes) fails the run, since the cell's
    work would then run on a device it does not count."""
    out = {}
    for plane in pd.planes:
        if not plane.name.startswith(platform_prefix):
            continue
        mods, ops = [], []
        for line in plane.lines:
            if line.name == MODULE_LINE:
                mods = sorted((float(ev.start_ns),
                               float(ev.start_ns + ev.duration_ns),
                               _module_name(ev.name)) for ev in line.events)
            elif line.name == OP_LINE:
                for ev in line.events:
                    st = _stats(ev)
                    ops.append((float(ev.start_ns),
                                float(ev.start_ns + ev.duration_ns),
                                str(st.get("hlo_module") or ""),
                                _op_name(str(st.get("hlo_op") or ev.name))))
        ops.sort()
        evs, i = [], 0
        for s, e, mod, op in ops:
            while i < len(mods) and mods[i][1] <= s:
                i += 1
            if not mod and i < len(mods) and mods[i][0] <= s:
                mod = mods[i][2]
            evs.append((s, e, mod, op))
        if evs:
            out[plane.name] = evs
    if planes is None:
        return out
    from benchlib.harness import BenchError
    kept = {p: evs for p, evs in out.items() if p in planes}
    if out and not kept:
        raise BenchError(f"the trace holds none of the cell's device planes "
                         f"{sorted(planes)}, only {sorted(out)}")
    step = step_module(kept)
    stray = sorted(p for p, evs in out.items() if p not in kept and any(
        _module_name(mod) == step for _, _, mod, _ in evs))
    if stray:
        raise BenchError(f"ops of {step} on device planes {stray}, outside "
                         f"the cell's {sorted(planes)}")
    return kept


def step_module(dev) -> str:
    """The step program of ``device_events``' planes: the module that holds
    the most device self time."""
    busy = {}
    for evs in dev.values():
        for mod, _, ns in self_times(evs):
            key = _module_name(mod)
            busy[key] = busy.get(key, 0.0) + ns
    return max(busy, key=busy.get) if busy else None


def host_events(pd):
    """-> [(start_ns, end_ns, name, depth)] of the host's Python thread."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if not line.name.startswith("python"):
                continue
            stack = []
            for ev in sorted(line.events, key=lambda e: e.start_ns):
                s, e = float(ev.start_ns), float(ev.start_ns + ev.duration_ns)
                while stack and stack[-1] <= s:
                    stack.pop()
                out.append((s, e, ev.name, len(stack)))
                stack.append(e)
    return out


def self_times(events):
    """[(start, end, module, op)] sorted by start -> [(module, op, self_ns)]
    where an enclosing event keeps only the time no inner event covers."""
    out = []
    stack = []          # [index into out, end]
    for s, e, mod, op in events:
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack:
            parent = stack[-1][0]
            inner = min(e, stack[-1][1]) - s
            out[parent][2] -= inner
        out.append([mod, op, e - s])
        stack.append((len(out) - 1, e))
    return [tuple(x) for x in out]


def union_ns(events):
    busy, cur_s, cur_e = 0.0, None, None
    for s, e, *_ in events:
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                busy += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        busy += cur_e - cur_s
    return busy


def idle_gaps(events, t0, t1):
    """Gaps between the union of ``events`` inside [t0, t1] -> [(s, e)]."""
    gaps, cur = [], t0
    for s, e, *_ in events:
        if s > cur:
            gaps.append((cur, min(s, t1)))
        cur = max(cur, e)
        if cur >= t1:
            break
    if cur < t1:
        gaps.append((cur, t1))
    return [(s, e) for s, e in gaps if e > s]


# --------------------------------------------------------------------------
# compiled HLO text -> stack of (file, function) per instruction
# --------------------------------------------------------------------------

_TABLE = re.compile(r"^(FileNames|FunctionNames|FileLocations|StackFrames)$")
_ROW = re.compile(r"^(\d+) (.*)$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$")
_META = re.compile(r"[\s,]metadata=\{([^}]*)\}")
_CALLED = re.compile(r"(?:calls|to_apply|body|condition|"
                     r"branch_computations)=(\{[^}]*\}|%[\w.\-]+)")
_OPERANDS = re.compile(r"%([\w.\-]+)")
_COMP = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s.*\{\s*$")


def _field(text, name):
    m = re.search(name + r'=("(?:[^"\\]|\\.)*"|[^\s},]+)', text)
    if not m:
        return None
    v = m.group(1)
    return v[1:-1] if v.startswith('"') else v


def hlo_stacks(hlo_text: str) -> dict:
    """{instruction name: [(file, function), ...] innermost first}.

    An instruction the compiler made without metadata (a fusion, a copy,
    a relayout loop) takes the stack of its nearest traced neighbour: the
    computation it calls (its root, or its first instruction with one),
    else its first operand with one, else the instruction that calls its
    computation (a loop), else its first user with one."""
    files, funcs, locs, frames = {}, {}, {}, {}
    table = None
    meta, body, calls = {}, {}, {}
    comp, roots, members = None, {}, {}
    for line in hlo_text.splitlines():
        s = line.strip()
        if _TABLE.match(s):
            table = s
            continue
        if table is not None:
            m = _ROW.match(s)
            if m:
                key, val = int(m.group(1)), m.group(2)
                if table == "FileNames":
                    files[key] = val.strip('"')
                elif table == "FunctionNames":
                    funcs[key] = val.strip('"')
                elif table == "FileLocations":
                    locs[key] = (int(_field(val, "file_name_id") or 0),
                                 int(_field(val, "function_name_id") or 0))
                else:
                    frames[key] = (int(_field(val, "file_location_id") or 0),
                                   int(_field(val, "parent_frame_id") or 0))
                continue
            if s:
                table = None
        m = _COMP.match(s)
        if m and " = " not in s:
            comp = m.group(1)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name, rest = m.group(1), m.group(2)
        mm = _META.search(rest)
        if mm:
            meta[name] = mm.group(1)
        called = []
        for mc in _CALLED.finditer(rest):
            called += _OPERANDS.findall(mc.group(1))
        if called:
            calls[name] = called
        args = rest.split("(", 1)[1] if "(" in rest else ""
        body[name] = _OPERANDS.findall(args.split("),", 1)[0])
        if comp is not None:
            members.setdefault(comp, []).append(name)
            if s.startswith("ROOT"):
                roots[comp] = name

    def chain(fid):
        out, seen = [], set()
        while fid and fid in frames and fid not in seen:
            seen.add(fid)
            loc, parent = frames[fid]
            f, fn = locs.get(loc, (0, 0))
            out.append((files.get(f, ""), funcs.get(fn, "")))
            fid = parent
        return out

    stacks = {}
    for name, m in meta.items():
        sf = _field(m, "stack_frame_id")
        if sf is not None:
            stacks[name] = chain(int(sf))
        else:
            src = _field(m, "source_file")
            if src:
                stacks[name] = [(src, "")]

    def of_computation(c):
        r = roots.get(c)
        if r in stacks:
            return stacks[r]
        return next((stacks[n] for n in members.get(c, ()) if n in stacks),
                    None)

    caller, users = {}, {}
    for name, cs in calls.items():
        for c in cs:
            caller.setdefault(c, name)
    home = {n: c for c, ns in members.items() for n in ns}
    for name, ops in body.items():
        for o in ops:
            users.setdefault(o, []).append(name)
    todo = [n for n in body if n not in stacks]
    for _ in range(8):
        left = []
        for name in todo:
            st = next((of_computation(c) for c in calls.get(name, ())
                       if of_computation(c) is not None), None)
            if st is None:
                st = next((stacks[o] for o in body[name] if o in stacks),
                          None)
            if st is None:
                st = stacks.get(caller.get(home.get(name)))
            if st is None:
                st = next((stacks[u] for u in users.get(name, ())
                           if u in stacks), None)
            if st is None:
                left.append(name)
            else:
                stacks[name] = st
        if len(left) == len(todo):
            break
        todo = left
    return stacks


def hlo_module_name(hlo_text: str) -> str:
    m = re.match(r"\s*HloModule\s+([\w.\-]+)", hlo_text)
    return m.group(1) if m else ""


# --------------------------------------------------------------------------
# layer tables
# --------------------------------------------------------------------------

def load_layers(layers_dir):
    """[(layer, [(path glob, function or None)])] from layers/*.json."""
    out = []
    for path in sorted(glob.glob(os.path.join(layers_dir, "*.json"))):
        with open(path) as f:
            spec = json.load(f)
        rules = []
        for src in spec["sources"]:
            pat, _, fn = src.partition("::")
            rules.append((pat, fn or None))
        out.append((spec["layer"], rules))
    return out


def classify(stack, layers):
    for file, fn in stack:
        for layer, rules in layers:
            for pat, want in rules:
                if (fnmatch.fnmatch(file, "*/" + pat)
                        or fnmatch.fnmatch(file, pat)) and (
                        want is None or want == fn):
                    return layer
    return None


def layer_times(events, stacks_by_module, layers):
    """Device self time per layer (ns), plus "unattributed" (step program
    ops no table matches) and "other programs" (ops of other modules)."""
    out = {}
    cache = {}
    for mod, op, ns in self_times(events):
        stacks = None
        for name, st in stacks_by_module.items():
            if _module_name(mod) == name:
                stacks = st
                break
        if stacks is None:
            key = "other programs"
        else:
            ck = (mod, op)
            if ck not in cache:
                cache[ck] = classify(stacks.get(op, []), layers)
            key = cache[ck] or "unattributed"
        out[key] = out.get(key, 0.0) + ns
    return out
