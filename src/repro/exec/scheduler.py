"""Sweep-execution orchestrator + multi-process worker pool (DESIGN.md §1.6).

``run_cells`` owns sweep execution end-to-end: it partitions cells into
jit-signature groups (exec/batching.py), runs batchable groups as single
vmapped trajectories in-process, shards the un-batchable remainder across
a bounded subprocess pool (per-worker ``CUDA_VISIBLE_DEVICES`` /
``JAX_PLATFORMS`` pinning, one process per TPU, per-cell timeout, failure
isolation — one
diverging attack cell records ``failed`` in the ledger and the grid keeps
going), journals every cell in the crash-safe ledger (exec/ledger.py), and
writes one artifact JSON per cell (``RunResult.to_dict()``, the same
payload ``api.sweep.run_sweep`` always wrote).

Resume semantics (``resume=True``): cells whose last ledger record is
``done`` AND whose artifact exists are loaded, not re-run; ``started`` /
``failed`` cells re-run. Granularity is chosen so a killed-and-resumed
sweep is bit-identical to an uninterrupted one:

  * serial cells commit independently — per-cell granularity;
  * a vmapped group commits atomically, and if ANY member is missing the
    WHOLE group re-runs at full width — so a cell never sees a different
    vmap width (and hence different float reassociation) than the
    uninterrupted sweep would have given it.

Keep the batch/pool configuration fixed across resume attempts; switching
e.g. ``batch=False`` mid-sweep re-runs cells on a different engine path,
which is numerically equivalent but not bit-identical.
"""
from __future__ import annotations

import concurrent.futures
import dataclasses
import hashlib
import json
import os
import queue
import shutil
import subprocess
import sys
import tempfile
import traceback
from typing import Callable, Mapping, Optional, Sequence, Tuple

from repro.api.runner import RunResult, build
from repro.api.runner import run as run_spec
from repro.api.spec import RunSpec
from repro.exec import batching
from repro.exec.ledger import Ledger, device_kind, git_sha
from repro.obs.sink import TagSink
from repro.obs.sink import span as obs_span


# ---------------------------------------------------------------------------
# results
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CompletedCell:
    """A cell loaded from a prior artifact (resume) or a worker subprocess —
    history and spec are available; live device state is not."""
    run_id: str
    payload: dict

    @property
    def history(self) -> list:
        return self.payload.get("history", [])

    @property
    def final(self) -> dict:
        return self.history[-1] if self.history else {}

    @property
    def spec(self) -> RunSpec:
        return RunSpec.from_dict(self.payload["spec"])

    def to_dict(self) -> dict:
        return self.payload


class SweepRun(Mapping):
    """The outcome of ``run_cells`` — a mapping ``run_id -> result``.

    Values are live ``RunResult``s for cells run in-process this call and
    ``CompletedCell``s for cells loaded from artifacts (resume / worker
    subprocesses); both expose ``history`` / ``final`` / ``to_dict()``.
    ``artifacts`` holds every completed cell's JSON payload (what
    ``exec.aggregate`` folds into summaries), ``failures`` the per-cell
    failure records, and ``stats`` the engine accounting (compile counts).
    """

    def __init__(self):
        self.results: dict = {}          # run_id -> RunResult (in-process)
        self.artifacts: dict = {}        # run_id -> payload dict
        self.failures: dict = {}         # run_id -> failure record
        self.skipped: set = set()        # resumed, loaded from artifacts
        self.stats: dict = {"n_cells": 0, "executed_cells": 0,
                            "vmapped_groups": 0, "serial_cells": 0,
                            "subprocess_cells": 0, "step_compiles": 0,
                            "max_group_cache": 0}

    def __getitem__(self, run_id):
        if run_id in self.results:
            return self.results[run_id]
        if run_id in self.artifacts:
            return CompletedCell(run_id, self.artifacts[run_id])
        raise KeyError(run_id)

    def __iter__(self):
        seen = set(self.results)
        yield from self.results
        for rid in self.artifacts:
            if rid not in seen:
                yield rid

    def __len__(self):
        return len(set(self.results) | set(self.artifacts))


# ---------------------------------------------------------------------------
# subprocess worker pool
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class WorkerPool:
    """Bounded local pool of subprocess workers for un-batchable cells.

    Each worker is a fresh ``python -m repro.exec.worker`` process so
    device pinning happens before jax initializes: ``gpu_ids`` round-robins
    ``CUDA_VISIBLE_DEVICES`` across workers, ``jax_platform`` sets
    ``JAX_PLATFORMS`` (e.g. "cpu" to keep sweep workers off the trainer's
    accelerator). ``timeout_s`` bounds each cell attempt; a crashed or
    timed-out attempt is retried up to ``max_retries`` times on a FRESH
    slot (the failed slot goes back to the queue — host re-queue), with
    ``backoff_s · 2^attempt`` sleep between attempts and the per-attempt
    timeout escalating by ``timeout_escalation``× each retry (a cell that
    legitimately needs more time eventually gets it; a hung worker is
    reaped each round). Only a cell that fails every attempt records
    ``failed`` — with the final returncode, the stderr tail and the full
    per-attempt history — and the rest of the grid proceeds.

    ``fault_plan`` (repro.faults, DESIGN.md §6) injects process-site
    chaos: cells selected by the plan's crash/hang specs get ``--fault``
    on their FIRST attempt only, so with retries enabled the sweep
    completes with artifacts byte-identical to a fault-free run.

    A TPU belongs to one process at a time, and the sweep's own process
    holds it once it has touched JAX there. ``check_chip_owner`` refuses a
    pool whose workers would need the chip then; ``jax_platform="cpu"``
    pins them off it.
    """
    max_workers: int = 2
    timeout_s: Optional[float] = None
    gpu_ids: Optional[Sequence[str]] = None
    jax_platform: Optional[str] = None
    extra_env: Mapping = dataclasses.field(default_factory=dict)
    max_retries: int = 2
    backoff_s: float = 0.25
    timeout_escalation: float = 2.0
    fault_plan: Optional[object] = None     # faults.FaultPlan or None
    hang_timeout_s: float = 60.0            # cap for injected hangs when
                                            # timeout_s is None

    def check_chip_owner(self) -> None:
        """Raise unless every worker can get the device it will ask for:
        workers not pinned to the CPU would need the TPU, which this
        process already holds (or, several at once, share among
        themselves)."""
        import jax
        child = self.cell_env(None).get("JAX_PLATFORMS", "").lower()
        if child == "cpu":
            return
        if jax.default_backend() == "tpu":
            raise RuntimeError(
                "WorkerPool: this process holds the TPU, so worker "
                "subprocesses that need it would fail or hang (one process "
                "per chip). Run the cells in-process (no pool / "
                "--workers 0) or pin the workers to the CPU "
                "(jax_platform='cpu' / --platform cpu)")
        if "tpu" in child and self.max_workers > 1:
            raise RuntimeError(
                f"WorkerPool: {self.max_workers} workers pinned to "
                f"JAX_PLATFORMS={child!r} would contend for one chip; use "
                "max_workers=1 or run the cells in-process")

    def cell_env(self, slot) -> dict:
        env = dict(os.environ)
        env.update(self.extra_env)
        if self.jax_platform:
            env["JAX_PLATFORMS"] = self.jax_platform
        if self.gpu_ids:
            env["CUDA_VISIBLE_DEVICES"] = str(slot)
        return env


def process_fault(plan, run_id: str, idx: int) -> Optional[str]:
    """Which process fault (if any) the plan injects into this cell's first
    attempt. Deterministic in (plan.seed, spec.kind, run_id) — a chaotic
    sweep replays the same kills. ``FaultSpec.workers`` for process-site
    specs are CELL indices in submission order (empty = every cell,
    thinned by ``prob``)."""
    if plan is None:
        return None
    import zlib

    from repro.faults.plan import PROCESS_FAULTS
    for spec in plan.faults:
        if spec.kind not in PROCESS_FAULTS:
            continue
        if spec.workers and idx not in spec.workers:
            continue
        if spec.prob >= 1.0:
            return spec.kind
        h = zlib.crc32(f"{plan.seed}:{spec.kind}:{run_id}".encode())
        if (h % (1 << 20)) / float(1 << 20) < spec.prob:
            return spec.kind
    return None


def _attempt_cell(pool: WorkerPool, slot, run_id: str, spec, out_path: str,
                  run_kw: Mapping, fault: Optional[str],
                  attempt: int) -> dict:
    """One subprocess attempt; returns {"ok": bool, ...} with returncode +
    stderr tail on failure."""
    with tempfile.NamedTemporaryFile(
            "w", suffix=".spec.json", delete=False) as f:
        f.write(spec.to_json())
        spec_path = f.name
    cmd = [sys.executable, "-m", "repro.exec.worker",
           "--spec", spec_path, "--out", out_path,
           "--run-kw", json.dumps(dict(run_kw))]
    if fault is not None:
        cmd += ["--fault", fault]
    timeout = pool.timeout_s
    if timeout is not None:
        timeout = timeout * (pool.timeout_escalation ** attempt)
    elif fault == "hang":
        timeout = pool.hang_timeout_s    # never let injected chaos wedge
    env = pool.cell_env(slot)
    env.setdefault("PYTHONPATH", os.pathsep.join(
        p for p in (os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))),
            os.environ.get("PYTHONPATH")) if p))
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              env=env, timeout=timeout)
    except subprocess.TimeoutExpired:
        return {"ok": False, "error": "timeout", "attempt": attempt,
                "slot": str(slot), "injected_fault": fault,
                "detail": f"attempt exceeded {timeout}s"}
    finally:
        os.unlink(spec_path)
    if proc.returncode != 0 or not os.path.exists(out_path):
        return {"ok": False, "error": "worker-failed", "attempt": attempt,
                "slot": str(slot), "injected_fault": fault,
                "returncode": proc.returncode,
                "stderr_tail": (proc.stderr or proc.stdout or "")[-2000:]}
    return {"ok": True, "attempt": attempt, "injected_fault": fault}


def _run_cell_subprocess(pool: WorkerPool, slots: queue.Queue, run_id: str,
                         spec, out_path: str, run_kw: Mapping,
                         fault: Optional[str] = None) -> dict:
    """Run one cell with bounded retry; returns a status dict carrying the
    per-attempt history (ledger failure forensics — satellite of the chaos
    layer). ``fault`` is injected on attempt 0 only."""
    import time

    history = []
    max_attempts = 1 + max(int(pool.max_retries), 0)
    for attempt in range(max_attempts):
        slot = slots.get()          # fresh slot per attempt: host re-queue
        try:
            status = _attempt_cell(pool, slot, run_id, spec, out_path,
                                   run_kw, fault if attempt == 0 else None,
                                   attempt)
        finally:
            slots.put(slot)
        if status.get("ok"):
            status["attempts"] = attempt + 1
            status["attempt_history"] = history
            status["injected_fault"] = fault
            return status
        history.append(status)
        if attempt + 1 < max_attempts and pool.backoff_s > 0:
            time.sleep(pool.backoff_s * (2 ** attempt))
    last = history[-1]
    return {"ok": False, "error": last.get("error", "unknown"),
            "detail": last.get("detail") or last.get("stderr_tail", ""),
            "returncode": last.get("returncode"),
            "stderr_tail": last.get("stderr_tail", ""),
            "attempts": max_attempts, "attempt_history": history,
            "injected_fault": fault}


# ---------------------------------------------------------------------------
# orchestration
# ---------------------------------------------------------------------------

def _atomic_write_json(path: str, payload: dict):
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(payload, f, indent=1)
    os.replace(tmp, path)


def _artifact_path(out_dir: str, run_id: str) -> str:
    # hand-made run ids may contain path separators (e.g. "fig1/cm/ALIE")
    return os.path.join(out_dir, run_id.replace(os.sep, "__") + ".json")


def _group_digest(key: str) -> str:
    return hashlib.sha1(key.encode()).hexdigest()[:10]


def run_cells(cells: Sequence[Tuple[str, object]], *,
              out_dir: Optional[str] = None,
              ledger_path: Optional[str] = None,
              resume: bool = False,
              batch="auto",
              pool: Optional[WorkerPool] = None,
              run_kw: Optional[Mapping] = None,
              cell_hook: Optional[Callable] = None,
              sink=None,
              verbose: bool = False) -> SweepRun:
    """Execute ``[(run_id, spec), ...]`` through the batched engine.

    ``batch``: "auto" vmaps every eligible multi-seed group (see
    ``batching.can_batch``); False forces per-cell serial execution.
    ``cell_hook(run_id, spec, exp) -> extra run_kw`` attaches per-cell loop
    knobs that need the built Experiment (benchmark probes / early-stop
    callbacks); hooked cells always run serially in-process.
    ``pool`` sends serial cells to pinned worker subprocesses instead
    (hooked cells and non-JSON loop knobs stay in-process — closures don't
    cross processes; without ``out_dir`` the workers hand results back
    through a scratch dir that is cleaned up afterwards).
    ``sink``: a ``repro.obs.sink.MetricSink``. In-process serial cells get
    a run_id-tagged view of it threaded into the runner (round/trace
    events), every cell and vmapped group is wrapped in a span event, and
    the final engine accounting lands as ``sweep_*`` gauges. Subprocess
    cells don't stream (sinks don't cross processes) — their artifacts
    carry the history instead.
    """
    run_kw = dict(run_kw or {})
    srun = SweepRun()
    srun.stats["n_cells"] = len(cells)
    ledger = None
    if ledger_path is None and out_dir:
        ledger_path = os.path.join(out_dir, "ledger.jsonl")
    if ledger_path:
        ledger = Ledger(ledger_path)

    if pool is not None:
        pool.check_chip_owner()
    # subprocess workers hand results back as artifact files; without an
    # out_dir they land in a scratch dir so a pool still works (pinning,
    # timeout, isolation) when the caller only wants in-memory results
    tmp_art_dir = None
    if pool is not None and out_dir is None:
        tmp_art_dir = tempfile.mkdtemp(prefix="repro-exec-")
    art_dir = out_dir or tmp_art_dir

    def _jsonable(kw) -> bool:
        try:
            json.dumps(kw)
            return True
        except (TypeError, ValueError):
            return False

    done = ledger.completed() if (resume and ledger) else set()

    def _load_completed(run_id):
        if out_dir is None:
            return False
        path = _artifact_path(out_dir, run_id)
        if not os.path.exists(path):
            return False
        try:
            with open(path) as f:
                srun.artifacts[run_id] = json.load(f)
        except (OSError, json.JSONDecodeError):
            return False
        srun.skipped.add(run_id)
        return True

    prov = {"git_sha": git_sha(), "device_kind": device_kind()}

    def _start(run_id, spec, engine, group):
        if ledger:
            ledger.append(run_id, "started", spec=spec.to_dict(),
                          engine=engine, group=group, **prov)

    def _commit(run_id, result: RunResult, engine, group):
        payload = result.to_dict()
        srun.results[run_id] = result
        srun.artifacts[run_id] = payload
        if out_dir:
            _atomic_write_json(_artifact_path(out_dir, run_id), payload)
        if ledger:
            ledger.append(run_id, "done", engine=engine, group=group,
                          wall_s=result.wall_s, **prov)

    def _fail(run_id, engine, group, err):
        rec = {"engine": engine, "group": group,
               "error": f"{type(err).__name__}: {err}",
               "traceback": traceback.format_exc(limit=20)}
        srun.failures[run_id] = rec
        if ledger:
            ledger.append(run_id, "failed", **{**prov, **rec})

    executor = slots = None
    futures = {}
    sub_idx = [0]          # subprocess submission order (fault selection)
    if pool is not None:
        executor = concurrent.futures.ThreadPoolExecutor(pool.max_workers)
        slots = queue.Queue()
        ids = list(pool.gpu_ids) if pool.gpu_ids else list(
            range(pool.max_workers))
        for s in ids:
            slots.put(s)

    def _run_serial(run_id, spec, group):
        if run_id in done and _load_completed(run_id):
            return
        kw = dict(run_kw)
        exp = None
        if cell_hook is not None:
            exp = build(spec)
            kw.update(cell_hook(run_id, spec, exp) or {})
        if pool is not None and exp is None and _jsonable(kw):
            _start(run_id, spec, "subprocess", group)
            out_path = _artifact_path(art_dir, run_id)
            fault = process_fault(pool.fault_plan, run_id, sub_idx[0])
            sub_idx[0] += 1
            fut = executor.submit(_run_cell_subprocess, pool, slots, run_id,
                                  spec, out_path, kw, fault)
            futures[fut] = (run_id, out_path, group)
            return
        engine = "serial"
        _start(run_id, spec, engine, group)
        if sink is not None and "sink" not in kw:
            kw["sink"] = TagSink(sink, run_id=run_id)
        try:
            with obs_span(sink, "cell", run_id=run_id, engine=engine):
                if exp is not None:
                    result = exp.run(**kw)
                else:
                    result = run_spec(spec, **kw)
        except Exception as e:                    # noqa: BLE001 — isolate
            _fail(run_id, engine, group, e)
            return
        srun.stats["executed_cells"] += 1
        srun.stats["serial_cells"] += 1
        srun.stats["step_compiles"] += 1
        _commit(run_id, result, engine, group)

    for key, members in batching.group_cells(cells):
        digest = _group_digest(key)
        batchable = (batch is not False    # "auto"/True both allow vmap
                     and cell_hook is None
                     and batching.can_batch(members, run_kw))
        if not batchable:
            for run_id, spec in members:
                _run_serial(run_id, spec, digest)
            continue
        # vmapped groups commit atomically: resume either skips the whole
        # group or re-runs it at full width (bit-identical either way).
        if done.issuperset(rid for rid, _ in members):
            if all(_load_completed(rid) for rid, _ in members):
                continue
            for rid, _ in members:       # torn artifacts: recompute
                srun.artifacts.pop(rid, None)
                srun.skipped.discard(rid)
        for run_id, spec in members:
            _start(run_id, spec, "vmapped", digest)
        try:
            with obs_span(sink, "vmapped_group", group=digest,
                          n_cells=len(members)):
                results, stats = batching.run_group(members, **run_kw)
        except Exception as e:                    # noqa: BLE001 — isolate
            for run_id, _ in members:
                _fail(run_id, "vmapped", digest, e)
            continue
        srun.stats["vmapped_groups"] += 1
        srun.stats["executed_cells"] += len(members)
        srun.stats["step_compiles"] += stats["step_compiles"]
        srun.stats["max_group_cache"] = max(srun.stats["max_group_cache"],
                                            stats["step_compiles"])
        for run_id, _ in members:
            _commit(run_id, results[run_id], "vmapped", digest)

    try:
        for fut in concurrent.futures.as_completed(futures):
            run_id, out_path, group = futures[fut]
            try:
                status = fut.result()
            except Exception as e:                # noqa: BLE001 — isolate
                status = {"ok": False,
                          "error": f"{type(e).__name__}: {e}",
                          "detail": traceback.format_exc(limit=20)}
            if status.get("ok"):
                with open(out_path) as f:
                    srun.artifacts[run_id] = json.load(f)
                srun.stats["executed_cells"] += 1
                srun.stats["subprocess_cells"] += 1
                if status.get("attempts", 1) > 1:
                    srun.stats["retried_cells"] = (
                        srun.stats.get("retried_cells", 0) + 1)
                if ledger:
                    ledger.append(run_id, "done", engine="subprocess",
                                  group=group,
                                  attempts=status.get("attempts", 1),
                                  injected_fault=status.get("injected_fault"),
                                  attempt_history=status.get(
                                      "attempt_history", []),
                                  **prov)
            else:
                rec = {"engine": "subprocess", "group": group,
                       "error": status.get("error", "unknown"),
                       "detail": status.get("detail", ""),
                       "returncode": status.get("returncode"),
                       "stderr_tail": status.get("stderr_tail", ""),
                       "attempts": status.get("attempts", 1),
                       "attempt_history": status.get("attempt_history", []),
                       "injected_fault": status.get("injected_fault")}
                srun.failures[run_id] = rec
                if ledger:
                    ledger.append(run_id, "failed", **{**prov, **rec})
    finally:
        if executor is not None:
            executor.shutdown()
        if tmp_art_dir is not None:
            shutil.rmtree(tmp_art_dir, ignore_errors=True)
    if verbose and srun.failures:
        for rid, rec in srun.failures.items():
            print(f"[exec] FAILED {rid}: {rec['error']}")
    if sink is not None:
        for k, v in srun.stats.items():
            sink.emit({"type": "gauge", "name": f"sweep_{k}", "value": v})
        sink.emit({"type": "counter", "name": "sweep_failures",
                   "value": len(srun.failures)})
    return srun
