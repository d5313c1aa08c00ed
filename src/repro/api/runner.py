"""Build and run experiments from a ``RunSpec`` — the ONE training loop.

Before this layer, ``launch/train.py``, ``benchmarks/bench_trainer.py``, and
every example carried its own copy of the jit'd round loop (key schedule,
communication accounting, logging, checkpointing) with slightly different
wiring. ``build(spec)`` assembles the experiment (method over the shared
round engine + task data + loss + corrupt_fn) and ``run(spec)`` drives it
with one canonical, fully seeded schedule:

    k_init, k_run = split(PRNGKey(spec.seed))
    params        = init_params(k_init)
    state         = method.init(params, anchor(0), k_run)
    per round it:   k_step, k_batch = split(fold_in(k_run, it + 1))
                    state, metrics = step(state, minibatch(it, k_batch),
                                          anchor(it), k_step)

so a trajectory is a pure function of the spec. ``tests/test_api_parity.py``
pins ``run(spec)`` bit-for-bit against the engine driven the PR-1 way
(hand-assembled config + ``make_method``) on fixed seeds for every method.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import time
from typing import Any, Callable, Optional

import jax

from repro.core import tree_utils as tu
from repro.core.engine import Method, make_method
from repro.obs.sink import span


# ---------------------------------------------------------------------------
# experiment assembly
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Experiment:
    """A fully-assembled experiment: the method plus its data plumbing.

    ``minibatch(it, key)`` / ``anchor(it)`` return stacked (n, ...) pytrees;
    tasks that sample deterministically (TokenStream) ignore the key.
    """
    spec: Any                            # RunSpec
    cfg: Any                             # ByzVRMarinaConfig
    method: Method
    loss_fn: Callable
    corrupt_fn: Optional[Callable]
    init_params: Callable                # key -> params
    minibatch: Callable                  # (it, key) -> stacked batch
    anchor: Callable                     # it -> stacked anchor batch
    data: Any = None                     # LogRegData (logreg task)
    arch_cfg: Any = None                 # ArchConfig (lm task)

    def run(self, **run_kw) -> "RunResult":
        return _run_experiment(self, **run_kw)

    @functools.cached_property
    def step(self):
        """The jit-compiled round step the run loop calls."""
        return jax.jit(self.method.step)

    def start(self):
        """``(state, k_run)``: the round-0 engine state and the run key of
        the canonical key schedule (module docstring)."""
        k_init, k_run = jax.random.split(jax.random.PRNGKey(self.spec.seed))
        params = self.init_params(k_init)
        return self.method.init(params, self.anchor(0), k_run), k_run

    def step_args(self, state, it: int, k_run):
        """The arguments of round ``it``'s step from ``state``."""
        k_step, k_batch = jax.random.split(jax.random.fold_in(k_run, it + 1))
        return state, self.minibatch(it, k_batch), self.anchor(it), k_step


def build(spec) -> Experiment:
    """Assemble (method, stream, loss_fn, corrupt_fn) for ``spec``."""
    cfg = spec.build_config()
    builder = _build_logreg if spec.task == "logreg" else _build_lm
    exp = builder(spec, cfg)
    if spec.agg_mode == "all_to_all":
        # the mesh/grad_specs extras are environment-derived (like "auto"),
        # so the spec stays serializable; rebuild the method over the
        # mesh-carrying config.
        exp.cfg = _attach_all_to_all_mesh(spec, exp)
        exp.method = make_method(spec.method, exp.cfg, exp.loss_fn,
                                 exp.corrupt_fn, **spec.method_kwargs)
    return exp


def _attach_all_to_all_mesh(spec, exp: Experiment):
    """agg_mode="all_to_all" shards the worker axis over real devices
    (shard_map; core/sharded_agg.py). Build a (n_workers, model) mesh from
    the visible devices and attach leaf-wise grad PartitionSpecs."""
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import sanitize_specs

    n_dev = len(jax.devices())
    if n_dev % spec.n_workers:
        raise ValueError(
            f"agg_mode='all_to_all' needs the {spec.n_workers}-worker axis "
            f"sharded over devices, but {n_dev} device(s) are visible — run "
            "with XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{spec.n_workers} (CPU) or on a pod, or use agg_mode='gspmd'")
    mesh = jax.make_mesh((spec.n_workers, n_dev // spec.n_workers),
                         ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    params_abs = jax.eval_shape(exp.init_params, jax.random.PRNGKey(0))
    if exp.arch_cfg is not None:
        from repro.models import param_specs
        pspecs = sanitize_specs(mesh, params_abs, param_specs(exp.arch_cfg))
    else:
        pspecs = jax.tree.map(lambda _: P(), params_abs)
    return dataclasses.replace(exp.cfg, worker_axes=("data",),
                               model_axis="model", mesh=mesh,
                               grad_specs=pspecs)


def _build_logreg(spec, cfg) -> Experiment:
    from repro.data import (corrupt_labels_logreg, init_logreg_params,
                            logreg_loss, make_logreg_data)

    dk = spec.data_kwargs
    dim = int(dk.get("dim", 30))
    lam = float(dk.get("lam", 0.01))
    batch_size = int(dk.get("batch_size", 32))
    data = make_logreg_data(
        jax.random.PRNGKey(int(dk.get("data_seed", 0))),
        n_samples=int(dk.get("n_samples", 400)), dim=dim,
        n_workers=spec.n_workers,
        homogeneous=bool(dk.get("homogeneous", True)),
        noise=float(dk.get("noise", 0.1)))
    loss = logreg_loss(lam, nonconvex=bool(dk.get("nonconvex", False)))
    anchor = data.stacked()

    if dk.get("sampling", "uniform") == "importance":
        from repro.core import theory
        probs, _ = theory.importance_weights(data.features, lam)

        def minibatch(it, key):
            return data.sample_batches_importance(key, batch_size, probs)
    else:
        def minibatch(it, key):
            return data.sample_batches(key, batch_size)

    return Experiment(
        spec=spec, cfg=cfg,
        method=make_method(spec.method, cfg, loss, corrupt_labels_logreg,
                           **spec.method_kwargs),
        loss_fn=loss, corrupt_fn=corrupt_labels_logreg,
        init_params=lambda key: init_logreg_params(dim),
        minibatch=minibatch, anchor=lambda it: anchor, data=data)


def _build_lm(spec, cfg) -> Experiment:
    from repro.configs import get_config
    from repro.data import TokenStream, corrupt_labels_lm
    from repro.models import init_params as model_init
    from repro.models import loss_fn as model_loss

    dk = spec.data_kwargs
    acfg = get_config(spec.arch)
    if dk.get("reduced", False):
        acfg = acfg.reduced()
    stream = TokenStream(
        vocab_size=acfg.vocab_size, seq_len=int(dk.get("seq_len", 128)),
        n_workers=spec.n_workers,
        per_worker_batch=int(dk.get("per_worker_batch", 4)),
        num_codebooks=acfg.num_codebooks,
        frontend_tokens=acfg.frontend_tokens, d_model=acfg.d_model,
        heterogeneous=bool(dk.get("heterogeneous", False)), seed=spec.seed)
    remat = bool(dk.get("remat", False))

    def loss(params, batch, key):
        return model_loss(params, acfg, batch, remat=remat)

    return Experiment(
        spec=spec, cfg=cfg,
        method=make_method(spec.method, cfg, loss, corrupt_labels_lm,
                           **spec.method_kwargs),
        loss_fn=loss, corrupt_fn=corrupt_labels_lm,
        init_params=lambda key: model_init(key, acfg),
        minibatch=lambda it, key: stream.minibatch(it),
        anchor=stream.anchor, arch_cfg=acfg)


# ---------------------------------------------------------------------------
# the shared training loop
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class RunResult:
    spec: Any
    history: list                        # logged metric dicts
    state: dict                          # final engine state
    n_params: int
    comm_bits: float                     # total uploaded bits per worker
    wall_s: float
    traces: list = dataclasses.field(default_factory=list)
    # host RoundTrace dicts, one per logged step (spec.trace runs only)

    @property
    def params(self):
        return self.state["params"]

    @property
    def final(self) -> dict:
        return self.history[-1] if self.history else {}

    def detection_summary(self, frac: float = 0.5) -> dict:
        """Mean filter precision/recall + byzantine influence leakage over
        the run's logged RoundTraces ({} without spec.trace)."""
        from repro.obs import detect
        return detect.summarize(self.traces, frac)

    def to_dict(self) -> dict:
        """Artifact payload: the resolved spec next to the trajectory, so a
        result file alone reproduces the run."""
        out = {"spec": self.spec.to_dict(), "n_params": self.n_params,
               "comm_bits": self.comm_bits, "wall_s": self.wall_s,
               "history": self.history}
        if self.traces:
            out["detection"] = self.detection_summary()
        return out


def run(spec, **run_kw) -> RunResult:
    """``build(spec)`` + the canonical loop. See module docstring for the
    key schedule; keyword options are the loop knobs that used to live in
    each driver separately:

      log_every    — record (and with verbose=True, print) every k-th step.
      verbose      — print per-log-step progress lines.
      warmup       — run one throwaway step first (compile) so wall_s is
                     steady-state, and a second from its output when the
                     step places the state otherwise (all_to_all), so the
                     rounds after the first do not compile either; the
                     trajectory is unchanged.
      checkpoint   — path prefix: save the FULL engine state (params +
                     estimator extras + step) via repro.checkpoint, at the
                     end of the run and every ``checkpoint_every`` steps.
      checkpoint_every — periodic checkpoint cadence in steps (needs
                     ``checkpoint``); the crash-restart point.
      resume       — checkpoint prefix to restart from: the engine state is
                     restored and the loop continues at the saved step with
                     the SAME key schedule, so an interrupted-and-resumed
                     run reproduces the uninterrupted trajectory exactly.
                     (history/comm_bits restart at the resume point — they
                     cover the resumed segment only.)
      metrics_out  — path: dump ``RunResult.to_dict()`` JSON (spec included).
      callback     — fn(it, state, logged_metrics) probe (e.g. a benchmark's
                     gap-vs-f*); a truthy return stops the run early
                     (rounds-to-target benchmarks).
      callback_every — callback cadence in steps (default: the log steps).
                     Metrics are float()-materialized (a device sync) only
                     on log/callback steps, so a frequent probe doesn't
                     force per-step syncs via log_every=1.
      sink         — repro.obs.sink.MetricSink: every logged round is also
                     emitted as a {"type": "round"} event, traced rounds as
                     {"type": "trace"} (spec.trace), and the run itself as a
                     {"type": "span", "name": "run"}.
      metrics_jsonl — path: shorthand for (and fan-out with) a JsonlSink.
    """
    return _run_experiment(build(spec), **run_kw)


def _placement(tree) -> list:
    return [getattr(a, "sharding", None) for a in jax.tree.leaves(tree)]


def _detection_metrics(trace_host: dict) -> dict:
    """A logged round's detection quality from its host RoundTrace, and
    on chaos rounds the guard's rejections against the injected faults."""
    from repro.obs import detect
    det = detect.detection_metrics(trace_host)
    out = {"detect_precision": det["precision"],
           "detect_recall": det["recall"],
           "byz_leakage": det["byz_leakage"],
           "n_filtered": det["n_filtered"]}
    fm = detect.fault_metrics(trace_host)
    if fm:
        out.update(fault_precision=fm["fault_precision"],
                   fault_recall=fm["fault_recall"],
                   n_fault_rejected=fm["n_rejected"])
    return out


def _run_experiment(exp: Experiment, *, log_every: int = 10,
                    verbose: bool = False, warmup: bool = False,
                    checkpoint: Optional[str] = None,
                    checkpoint_every: Optional[int] = None,
                    resume: Optional[str] = None,
                    metrics_out: Optional[str] = None,
                    callback: Optional[Callable] = None,
                    callback_every: Optional[int] = None,
                    sink=None,
                    metrics_jsonl: Optional[str] = None) -> RunResult:
    spec = exp.spec
    own_jsonl = None
    if metrics_jsonl:
        from repro.obs.sink import FanoutSink, JsonlSink
        own_jsonl = JsonlSink(metrics_jsonl)
        sink = FanoutSink(sink, own_jsonl) if sink is not None else own_jsonl
    state, k_run = exp.start()
    n_params = int(tu.tree_size(state["params"]))
    start = 0
    if resume:
        from repro.checkpoint import load_checkpoint
        state, ck_step = load_checkpoint(resume, like=state)
        start = int(ck_step or 0)
        if verbose:
            print(f"[run] resumed from {resume}.npz at step {start}")
    step = exp.step
    step_traced = None
    if spec.trace:
        from repro.obs import trace as obs_trace
        step_traced = jax.jit(exp.method.step_traced)

    if warmup and spec.steps > 0:
        wargs = exp.step_args(state, 0, k_run)
        thrown, _ = step(*wargs)
        if step_traced is not None:      # compile the telemetry twin too,
            thrown, _ = step_traced(*wargs)   # so log steps never compile
        if _placement(thrown) != _placement(state):
            # the step hands its state back placed otherwise (all_to_all's
            # mesh), and later rounds compile again for that placement
            wargs = exp.step_args(thrown, 0, k_run)
            thrown, _ = step(*wargs)
            if step_traced is not None:
                thrown, _ = step_traced(*wargs)
        jax.block_until_ready(thrown["g"])
        del thrown, wargs

    if checkpoint:
        from repro.checkpoint import save_checkpoint

    history = []
    traces: list = []
    comm_bits_total = 0.0
    # partial participation: only the sampled cohort uploads, so the
    # per-configured-worker average is scaled by n_active/n_workers — the
    # measured twin of theory.comm_bits_per_round(..., participation=...)
    # (pinned by the conformance harness)
    part_frac = spec.resolved_participation() / spec.n_workers
    pending_ck = []          # device arrays; synced only on log steps so the
    t0 = time.time()         # loop keeps JAX's async dispatch pipelined
    # each round is a profiler step ("round", step_num=it) holding its
    # feed / dispatch / log / checkpoint spans (DESIGN.md §5); of the spans
    # only "run" reaches the sink
    with span(sink, "run", steps=spec.steps - start):
        for it in range(start, spec.steps):
            with span(None, "round", step_num=it):
                last = it == spec.steps - 1
                do_log = it % max(log_every, 1) == 0 or last
                do_cb = callback is not None and (
                    (it + 1) % max(callback_every, 1) == 0 or last
                    if callback_every is not None else do_log)
                # the telemetry twin runs only at log cadence (bit-identical
                # trajectory, pinned by tests/test_obs.py), so the
                # off-cadence hot path stays the untraced jaxpr
                fn = step_traced if (step_traced is not None
                                     and (do_log or do_cb)) else step
                with span(None, "feed"):
                    args = exp.step_args(state, it, k_run)
                with span(None, "dispatch"):
                    state, metrics = fn(*args)
                del args
                rt = metrics.pop("trace", None) if spec.trace else None
                pending_ck.append(metrics.get("c_k"))
                if do_log or do_cb:
                    with span(None, "log"):
                        for ck in pending_ck:
                            comm_bits_total += (
                                part_frac * exp.method.round_bits(
                                    n_params,
                                    True if ck is None else bool(ck)))
                        pending_ck.clear()
                        m = {k: float(v) for k, v in metrics.items()}
                        m["step"] = it
                        m["wall_s"] = round(time.time() - t0, 2)
                        m["comm_bits"] = comm_bits_total
                        m["comm_gbits"] = round(comm_bits_total / 1e9, 4)
                        trace_host = None
                        if rt is not None:
                            # the only extra sync is here, at log cadence,
                            # where the float() materialization above
                            # already fenced the device
                            trace_host = obs_trace.to_host(rt)
                            m.update(_detection_metrics(trace_host))
                        if do_log:
                            history.append(m)
                            if trace_host is not None:
                                traces.append(trace_host)
                            if sink is not None:
                                sink.emit({"type": "round", **m})
                                if trace_host is not None:
                                    sink.emit({"type": "trace", "step": it,
                                               **trace_host})
                        if verbose and do_log:
                            ck = (f" c_k={int(m['c_k'])}" if "c_k" in m
                                  else "")
                            print(f"  step {it:5d} loss {m['loss']:.4f} "
                                  f"|g| {m['g_norm']:.3e}{ck} "
                                  f"comm {m['comm_gbits']:.3g}Gb "
                                  f"({m['wall_s']}s)")
                        if do_cb and callback(it, state, m):
                            if not do_log:       # record the stop point
                                history.append(m)
                            break        # callback asked for early stop
                if (checkpoint and checkpoint_every
                        and (it + 1) % checkpoint_every == 0 and not last):
                    with span(None, "checkpoint"):
                        save_checkpoint(checkpoint, state,
                                        step=int(state["step"]))
                    if verbose:
                        print(f"[run] checkpoint @ step {it + 1} -> "
                              f"{checkpoint}.npz")
        jax.block_until_ready(state["g"])
        result = RunResult(spec=spec, history=history, state=state,
                           n_params=n_params, comm_bits=comm_bits_total,
                           wall_s=time.time() - t0, traces=traces)
    if sink is not None:
        if traces:
            sink.emit({"type": "gauge", "name": "detection_summary",
                       "value": result.detection_summary()})
        if own_jsonl is not None:
            own_jsonl.close()

    if checkpoint:
        # the FULL engine state (params + estimator extras + step), so a
        # later run(..., resume=checkpoint) restarts the exact trajectory
        save_checkpoint(checkpoint, state, step=int(state["step"]))
        if verbose:
            print(f"[run] checkpoint -> {checkpoint}.npz")
    if metrics_out:
        with open(metrics_out, "w") as f:
            json.dump(result.to_dict(), f, indent=1)
    return result
