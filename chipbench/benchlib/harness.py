"""One run of one cell: set-up, the measured window, the reference check.

Set-up (``setup_s``, from process start to the window):
  * backend start and the device check (a TPU, as many chips as the cell
    asks for; anything else is an error and prints no result);
  * the weights, made from the seed on the device in one jitted call by
    the configuration's own ``init_params``, and the engine's initial
    state (g^0) through ``Experiment.start``;
  * ``CHECK_ROUNDS`` difference rounds (``Schedule``), through the
    window's own call (``Experiment.step``) and feed
    (``Experiment.step_args`` over the traffic's batches): they compile the
    step and give the program's side of the correctness check; the later
    two of them size the window.

Window: the same ``Experiment`` object continues from that state through
``Experiment.run`` (the user's loop, at its default log cadence) for
``rounds`` rounds, sized to fill ``--seconds``, with the same share of
full-gradient rounds for every seed (``Schedule``), and ends on
``block_until_ready``. Nothing compiles inside it (counted and printed).

After the window: the allocator's peak device memory, then (with
``--trace 1``) the compiled step's memory and the trace reduction, then
the program's state is dropped and the plain reference replays the first
rounds; ``correct`` is the comparison of the two.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import math
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
CACHE_DIR = os.path.join(BENCH_DIR, ".cache", "jax")
OUT_DIR = os.path.join(BENCH_DIR, ".out")
CHECK_ROUNDS = 3
DATA_SALT = 0xDA7A


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class BenchError(RuntimeError):
    """A run that cannot give a result (no chip, a missing file)."""


# --------------------------------------------------------------------------
# cells
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    conf: dict
    arch: dict
    model: object
    traffic: dict
    limits: dict
    per_layer: list


def _json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str, bench_dir: str = BENCH_DIR,
              bench_json: str = None) -> Cell:
    """The workload ``name`` of BENCHMARK.json, with its configuration
    (``configs/<config>.json`` and its reference ``configs/<config>.py``),
    traffic (``traffic/<traffic>.json``) and limits
    (``limits/<workload>.json``)."""
    bench = _json(bench_json or os.path.join(ROOT, "BENCHMARK.json"))
    wl = next((w for w in bench["workloads"] if w["name"] == name), None)
    if wl is None:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    conf = _json(os.path.join(bench_dir, "configs", wl["config"] + ".json"))
    model = load_module(os.path.join(bench_dir, "configs",
                                     wl["config"] + ".py"),
                        "bench_model_" + wl["config"].replace("-", "_")
                        .replace(".", "_"))
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", [name])]
    return Cell(name=name, chips=wl["chips"], conf=conf, arch=conf["arch"],
                model=model,
                traffic=_json(os.path.join(bench_dir, "traffic",
                                           wl["traffic"] + ".json")),
                limits=_json(os.path.join(bench_dir, "limits",
                                          name + ".json")),
                per_layer=per_layer)


# --------------------------------------------------------------------------
# compile cache and compile log
# --------------------------------------------------------------------------

class CompileLog:
    """Every backend compile request of the process: which program, how
    long, and whether the persistent cache served it."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.entries = []
        self._hit = False
        self._event = dispatch.BACKEND_COMPILE_EVENT
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self._hit = True

    def _on_duration(self, event, duration, **kw):
        if event == self._event:
            self.entries.append((kw.get("fun_name", "?"), float(duration),
                                 self._hit))
            self._hit = False

    def __len__(self):
        return len(self.entries)

    def since(self, n):
        return self.entries[n:]


def enable_cache() -> str:
    """JAX's persistent cache at one fixed path inside the checkout,
    whatever ``JAX_COMPILATION_CACHE_DIR`` says, and for every program."""
    import jax
    os.makedirs(CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # no size cap and so no eviction: a cap set for a shared cache would
    # refuse the largest programs, and its bookkeeping fails on entries
    # written without it
    jax.config.update("jax_compilation_cache_max_size", -1)
    return CACHE_DIR


# --------------------------------------------------------------------------
# device
# --------------------------------------------------------------------------

def check_devices(chips: int, require_tpu: bool = True):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise BenchError(f"JAX found no TPU (platform "
                         f"{devs[0].platform!r}); this benchmark runs only "
                         "on the chip")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chip(s), JAX sees "
                         f"{len(devs)}")
    return devs[:chips]


def peaks_for(kind: str) -> dict:
    table = _json(os.path.join(BENCH_DIR, "benchlib", "peaks.json"))
    if kind not in table["devices"]:
        raise BenchError(f"no peaks for device kind {kind!r} in "
                         "benchlib/peaks.json")
    return table["devices"][kind]


def peak_bytes(devs):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


# --------------------------------------------------------------------------
# the program under test
# --------------------------------------------------------------------------

def seed_keys(seed: int):
    """(spec seed, k_init, k_run, data key): the loop's canonical schedule
    (``Experiment.start``) from the run's seed, plus the traffic's key."""
    import jax
    spec_seed = int(seed) % (1 << 32)
    root = jax.random.PRNGKey(spec_seed)
    k_init, k_run = jax.random.split(root)
    return spec_seed, k_init, k_run, jax.random.fold_in(root, DATA_SALT)


# traffic keys the harness reads itself: the batch's shape (the program
# gets it through ``data_kwargs``), the agg_mode to resolve for the
# platform, and the anchor batch of the benchmark's own feed
DATA_KEYS = ("seq_len", "per_worker_batch", "remat")
HARNESS_KEYS = DATA_KEYS + ("agg_mode", "anchor_batches")
# RunSpec fields the harness sets: a traffic file may not name them
SPEC_OWNED = ("task", "arch", "steps", "seed", "data_kwargs")


def run_spec(cell: Cell, spec_seed: int):
    """The cell's ``RunSpec``: every traffic key other than the harness's
    own names a ``RunSpec`` field and is passed as it is; any other key is
    an error, so that a misspelt key cannot run as the default."""
    from repro.api import RunSpec, resolve_agg_mode
    from repro.configs.base import ArchConfig, register
    a = dict(cell.arch)
    a["block_pattern"] = tuple(a["block_pattern"])
    register(ArchConfig(**a))
    t = cell.traffic
    fields = {f.name for f in dataclasses.fields(RunSpec)} - set(SPEC_OWNED)
    unknown = sorted(set(t) - set(HARNESS_KEYS) - fields)
    if unknown:
        raise BenchError(f"traffic of {cell.name!r}: {unknown} name no "
                         "RunSpec field the traffic may set")
    passed = {k: v for k, v in t.items() if k not in HARNESS_KEYS}
    return RunSpec(
        task="lm", arch=a["name"], steps=CHECK_ROUNDS, seed=spec_seed,
        agg_mode=resolve_agg_mode(t["agg_mode"]),
        data_kwargs={k: t[k] for k in DATA_KEYS}, **passed)


def build_program(cell: Cell, spec_seed: int):
    """The program of ``run_spec``. The ``all_to_all`` backend builds its
    mesh over every device JAX sees, so it runs only where those are the
    cell's chips."""
    import jax
    from repro.api import build
    spec = run_spec(cell, spec_seed)
    seen = len(jax.devices())
    if spec.agg_mode == "all_to_all" and seen != cell.chips:
        raise BenchError(f"agg_mode 'all_to_all' meshes every device JAX "
                         f"sees: {seen}, for a cell of {cell.chips} chip(s)")
    return build(spec)


def same_layout(a, b) -> bool:
    import jax
    fa, ta = jax.tree.flatten(a)
    fb, tb = jax.tree.flatten(b)
    return ta == tb and all(x.shape == y.shape and x.dtype == y.dtype
                            for x, y in zip(fa, fb))


def wire_feed(exp, cell: Cell, k_data):
    """The benchmark's weights and batches in the program's place, and the
    initial-state program jitted (set-up, not the loop)."""
    import functools
    import jax
    from benchlib.data import Traffic
    init = jax.jit(functools.partial(cell.model.init_params, a=cell.arch))
    probe = jax.random.PRNGKey(0)
    if not same_layout(jax.eval_shape(exp.init_params, probe),
                       jax.eval_shape(init, probe)):
        raise BenchError("the configuration's weights do not have the "
                         "program's parameter layout")
    traffic = Traffic(cell.arch, cell.traffic, k_data)
    exp.init_params = init
    exp.minibatch = traffic.minibatch
    exp.anchor = traffic.anchor
    exp.method = dataclasses.replace(exp.method,
                                     init=jax.jit(exp.method.init))
    return init, traffic


def leaf_norms(tree) -> list:
    import jax
    import jax.numpy as jnp
    return [float(v) for v in jax.device_get(
        jax.jit(lambda t: [jnp.sqrt(jnp.sum(jnp.square(
            a.astype(jnp.float32)))) for a in jax.tree.leaves(t)])(tree))]


def accumulate(acc, g, lr):
    """acc + lr * g in float32, leaf by leaf (the optimizer's summed
    update before the parameters' dtype rounds it)."""
    import jax
    import jax.numpy as jnp
    if acc is None:
        return jax.tree.map(lambda a: lr * a.astype(jnp.float32), g)
    return jax.tree.map(lambda a, b: a + lr * b.astype(jnp.float32), acc, g)


def diff_norms(new, old) -> list:
    import jax
    import jax.numpy as jnp
    return [float(v) for v in jax.device_get(jax.jit(
        lambda a, b: [jnp.sqrt(jnp.sum(jnp.square(
            x.astype(jnp.float32) - y.astype(jnp.float32))))
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))])(
        new, old))]


class Schedule:
    """Which rounds of the loop's key schedule a run drives.

    The training loop draws round ``it``'s keys from ``fold_in(k_run,
    it + 1)``, and Byz-VR-MARINA draws c_k (a full-gradient round or a
    difference round) from them, so the mix of round kinds in a window
    would change with the seed. A run drives instead, in the schedule's
    order, its first ``CHECK_ROUNDS`` difference rounds (the correctness
    check), then a window with ``round(p * rounds)`` full-gradient rounds
    and the rest difference rounds: every seed does the same work, in its
    own order and on its own batches."""

    BLOCK = 4096

    def __init__(self, k_run, p: float):
        import jax
        self.p = p
        self._coins = []

        def one(k, it):
            k_step, _ = jax.random.split(jax.random.fold_in(k, it + 1))
            # the estimator's first named stream is its Bernoulli draw
            return jax.random.bernoulli(jax.random.split(k_step, 5)[0], p)

        # the key is an argument, so one program serves every seed
        self._draw = jax.jit(jax.vmap(one, in_axes=(None, 0)))
        self._k_run = k_run
        self.check = self._take(0, CHECK_ROUNDS, 0)

    def coin(self, it: int) -> int:
        import jax
        import jax.numpy as jnp
        while it >= len(self._coins):
            its = jnp.arange(len(self._coins), len(self._coins) + self.BLOCK,
                             dtype=jnp.uint32)
            self._coins += [int(c) for c in jax.device_get(
                self._draw(self._k_run, its))]
        return self._coins[it]

    def _take(self, start: int, n: int, n_full: int) -> list:
        out, full, it = [], 0, start
        while len(out) < n:
            c = self.coin(it)
            if (c and full < n_full) or (not c and len(out) - full
                                        < n - n_full):
                out.append(it)
                full += c
            it += 1
        return out

    def window(self, rounds: int) -> list:
        return self._take(self.check[-1] + 1, rounds,
                          int(round(self.p * rounds)))


def plant_fault(exp, traffic, fault: str):
    """Break the timed path underneath the harness (the benchmark's own
    tests): ``unchanged_state`` (the step returns its state as it got it),
    ``half_batch`` (half of every sequence's labels left out, the loss
    the mean over the rest) or ``no_exchange`` (the ``all_to_all``
    aggregation without its collectives: each chip keeps its own worker's
    message as the aggregate)."""
    import jax
    if fault == "unchanged_state":
        step = exp.method.step

        def stuck(state, batch, anchor, key):
            return state, step(state, batch, anchor, key)[1]

        exp.method = dataclasses.replace(exp.method, step=stuck)
        exp.__dict__.pop("step", None)
    elif fault == "half_batch":
        mb, an = traffic.minibatch, traffic.anchor
        half = jax.jit(halve_labels)
        exp.minibatch = lambda it, key=None: half(mb(it))
        exp.anchor = lambda it: half(an(it))
    elif fault == "no_exchange":
        from repro.core import sharded_agg
        exp.method = dataclasses.replace(
            exp.method, init=_swapped(exp.method.init, sharded_agg),
            step=_swapped(exp.method.step, sharded_agg))
        exp.__dict__.pop("step", None)
    else:
        raise ValueError(f"unknown fault {fault!r}")


def _swapped(fn, sharded_agg):
    """``fn`` traced with ``tree_aggregate_all_to_all`` replaced by the same
    ``shard_map`` without its all-to-all and all-gather."""
    import jax
    from jax.sharding import PartitionSpec as P

    def local(cfg, key, sent):
        w = tuple(cfg.worker_axes)
        w = w if len(w) > 1 else w[0]

        def one(leaf, spec):
            spec = tuple(spec) if spec is not None else ()
            return jax.shard_map(lambda x: x[0], mesh=cfg.mesh,
                                 in_specs=P(w, *spec), out_specs=P(*spec),
                                 check_vma=False)(leaf)

        return jax.tree.map(one, sent, cfg.grad_specs)

    def swapped(*args):
        real = sharded_agg.tree_aggregate_all_to_all
        sharded_agg.tree_aggregate_all_to_all = local
        try:
            return fn(*args)
        finally:
            sharded_agg.tree_aggregate_all_to_all = real

    return swapped


def halve_labels(batch, axis=2):
    """Labels of the second half of each sequence (along ``axis``) set to
    -1, so the loss is the mean over the first half."""
    lab = batch["labels"]
    idx = [slice(None)] * lab.ndim
    idx[axis] = slice(lab.shape[axis] // 2, None)
    return {**batch, "labels": lab.at[tuple(idx)].set(-1)}


def check_rounds(exp, schedule_of):
    """The initial state and the check rounds through the loop's own calls:
    the program's side of the correctness check. ``schedule_of(k_run)``
    gives the run's ``Schedule``.
    -> (numbers, state, k_run, schedule, seconds per round, abstract step
    args)."""
    import jax
    t = time.perf_counter()
    state, k_run = exp.start()
    sched = schedule_of(k_run)
    jax.block_until_ready(state)
    x0 = state["params"]
    prog = {"g0": leaf_norms(state["g"]), "losses": [], "c_k": []}
    log(f"weights and g^0 {time.perf_counter() - t:.3f} s")
    t_rounds, step_args, upd = [], None, None
    lr = exp.spec.lr
    for it in sched.check:
        upd = accumulate(upd, state["g"], lr)
        t = time.perf_counter()
        args = exp.step_args(state, it, k_run)
        if step_args is None:
            # an uncommitted argument's sharding is the call's choice, not
            # its own: given to ``lower`` it would make another program,
            # which a traced run then compiles again
            step_args = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(
                    a.shape, a.dtype,
                    sharding=a.sharding if a.committed else None), args)
        state, m = exp.step(*args)
        jax.block_until_ready(state)
        t_rounds.append(time.perf_counter() - t)
        prog["losses"].append(float(m["loss"]))
        prog["c_k"].append(int(m["c_k"]))
    del args, m
    prog["dx"] = diff_norms(state["params"], x0)
    prog["step"] = leaf_norms(upd)
    log("check rounds " + ", ".join(f"{s:.3f}" for s in t_rounds)
        + f" s, rounds {sched.check}, c_k {prog['c_k']}, losses "
        f"{prog['losses']}")
    return prog, state, k_run, sched, t_rounds, step_args


def reseed(exp, cell: Cell, seed: int):
    """Point an built experiment at another seed: the loop's key schedule,
    the weights and the traffic (its compiled step is kept)."""
    from benchlib.data import Traffic
    spec_seed, k_init, k_run, k_data = seed_keys(seed)
    exp.spec = exp.spec.replace(seed=spec_seed)
    traffic = Traffic(cell.arch, cell.traffic, k_data)
    exp.minibatch, exp.anchor = traffic.minibatch, traffic.anchor
    return traffic, k_init


# --------------------------------------------------------------------------
# one run
# --------------------------------------------------------------------------

def run(cell: Cell, seed: int, seconds: float, trace: bool, *,
        t_process: float, require_tpu: bool = True, fault: str = None,
        trace_dir: str = None) -> dict:
    import jax

    from benchlib import check
    from benchlib.refround import Reference

    t = time.perf_counter()
    devs = check_devices(cell.chips, require_tpu)
    dev = devs[0]
    log(f"device {dev.platform} {dev.device_kind!r} x{len(devs)} of the "
        f"{len(jax.devices())} JAX sees; backend start "
        f"{time.perf_counter() - t:.3f} s")
    peaks = peaks_for(dev.device_kind) if require_tpu else None
    clog = CompileLog()
    log(f"compile cache {enable_cache()}")

    t = time.perf_counter()
    spec_seed, k_init, k_run, k_data = seed_keys(seed)
    exp = build_program(cell, spec_seed)
    init, traffic = wire_feed(exp, cell, k_data)
    if fault:
        plant_fault(exp, traffic, fault)
    log(f"build {time.perf_counter() - t:.3f} s: agg_mode "
        f"{exp.spec.agg_mode!r}, {cell.traffic['n_workers']} workers")
    p = cell.traffic["p"]
    prog, state, k_run, sched, t_rounds, step_args = check_rounds(
        exp, lambda k: Schedule(k, p))
    t_round = sum(t_rounds[1:]) / max(len(t_rounds) - 1, 1)
    rounds = max(2, int(round(seconds / t_round)))
    order = sched.window(rounds)

    exp.spec = exp.spec.replace(steps=rounds)
    exp.start = lambda: (state, k_run)
    exp.step_args = lambda s, it, k: type(exp).step_args(exp, s, order[it],
                                                         k)
    setup_s = time.perf_counter() - t_process
    for fun, sec, hit in clog.entries:
        log(f"set-up compile {fun}: {sec:.3f} s "
            f"({'cache hit' if hit else 'compiled'})")
    log(f"setup_s {setup_s:.3f}; window of {rounds} rounds "
        f"(about {t_round:.3f} s each)")

    n_comp = len(clog)
    tdir = trace_dir or os.path.join(OUT_DIR, "trace")
    ctx = contextlib.nullcontext() if not trace else __import__(
        "benchlib.trace", fromlist=["capture"]).capture(tdir)
    with ctx:
        t0 = time.perf_counter()
        result = exp.run()
        jax.block_until_ready(result.state)
        window_s = time.perf_counter() - t0
    in_window = clog.since(n_comp)
    log(f"compiles inside the window: {len(in_window)} "
        + "".join(f"[{f} {s:.3f} s]" for f, s, _ in in_window))
    peak = peak_bytes(devs)
    ck = [sched.coin(it) for it in order]
    n_full = sum(ck)
    log(f"window {window_s:.6f} s: {rounds} rounds, {n_full} full-gradient "
        f"and {rounds - n_full} difference rounds")
    losses = [h["loss"] for h in result.history]
    failed = sum(not math.isfinite(x) for x in losses)
    final_ok = math.isfinite(float(result.history[-1]["g_norm"]))

    breakdown = None
    per_layer = {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    if trace:
        from benchlib import layers_report
        from benchlib.trace import plane_name
        compiled = exp.step.lower(*step_args).compile()
        mem = compiled.memory_analysis()
        if mem is not None:
            log("compiled step memory: arguments "
                f"{mem.argument_size_in_bytes}, outputs "
                f"{mem.output_size_in_bytes}, temporaries "
                f"{mem.temp_size_in_bytes}, aliased "
                f"{mem.alias_size_in_bytes} B")
        hlo = compiled.as_text()
        rep = layers_report.reduce(
            tdir, hlo, cell, rounds, ck, window_s, peaks,
            [plane_name(d) for d in devs])
        per_layer, breakdown = rep["metrics"], rep["breakdown"]
        device["busy_s"] = rep["busy_s"]
        device["window_s"] = rep["window_s"]
    del result, state, exp

    t = time.perf_counter()
    ref = Reference(cell.model, cell.arch, cell.traffic).run(
        init(k_init), traffic, k_run, sched.check)
    log(f"reference {time.perf_counter() - t:.3f} s, c_k {ref['c_k']}")
    log("leaves " + json.dumps({"prog": {k: prog[k] for k in (
        "losses", "g0", "dx", "step")}, "ref": {k: ref[k] for k in (
            "losses", "g0", "dx", "step")}}))
    numbers = check.gaps(prog, ref)
    if ref["c_k"] != prog["c_k"]:
        numbers = {k: math.inf for k in numbers}
        log("the program and the reference drew different rounds")
    correct, checks = check.verdict(numbers, cell.limits)
    correct = correct and final_ok and failed == 0

    tokens = traffic.tokens_per_round() * rounds
    if trace:
        metrics = per_layer
    else:
        metrics = {
            "tokens_per_s": {"value": tokens / window_s, "unit": "tokens/s"},
            "setup_s": {"value": setup_s, "unit": "s"}}
    out = {"correct": bool(correct), "attempted": rounds, "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    for name, c in checks.items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    return out
