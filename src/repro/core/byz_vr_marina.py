"""Byz-VR-MARINA (Algorithm 1) — the paper's contribution as a composable
JAX trainer.

One implementation serves both scales:

* laptop scale — ``n_workers`` simulated with ``vmap`` on one device (the
  paper's own logreg experiments, the convergence tests, the examples);
* pod scale — the same step ``jit``-ed onto the production mesh with the
  worker axis of every stacked input sharded over ``("pod", "data")`` and
  params/grads sharded over ``"model"`` (launch/train.py, launch/dryrun.py).

Per iteration (paper lines 4-10):

    c_k ~ Be(p)                                    (shared coin, broadcast)
    x^{k+1} = x^k - γ g^k                          (or any optim.Optimizer)
    good i: g_i = ∇f_i(x^{k+1})                    if c_k = 1   (anchor batch)
            g_i = g^k + Q(Δ̂_i(x^{k+1}, x^k))      otherwise    (minibatch)
    byz  i: g_i = attack(...)                      (omniscient; masked psums)
    g^{k+1} = ARAgg(g_1, ..., g_n)                 (bucketing + CM/RFA/Krum)

Since the unified-round-engine refactor (DESIGN.md §2) this module is a thin
facade: the round skeleton lives in ``core/engine.py``, the MARINA estimator
(dense + sparse-support) in ``core/estimators.py``, and this file keeps the
config, the legacy ``make_step`` / ``make_init`` entry points, and the
communication accounting. ``cfg.agg_mode`` selects the aggregation backend
(``engine.AGG_BACKENDS``): gspmd | all_to_all | sparse_support | pallas —
see core/sharded_agg.py and kernels/robust_agg.py for the beyond-paper
backends.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Callable, Optional

import jax.numpy as jnp

from repro.core import tree_utils as tu
from repro.core.aggregators import Aggregator
from repro.core.attacks import Attack, no_attack
from repro.core.compressors import Compressor, identity
from repro.core.engine import (AGG_BACKENDS, apply_attack,     # noqa: F401
                               make_method, stacked_grads, aggregate)


@dataclasses.dataclass(frozen=True)
class ByzVRMarinaConfig:
    n_workers: int
    n_byz: int = 0
    # partial participation: number of workers sampled each round (uniform
    # without replacement, seeded stream disjoint from attack/fault RNG).
    # None = all n_workers participate — compiles the identical program as
    # before the field existed.
    n_active: Optional[int] = None
    p: float = 0.1                       # full-gradient probability
    lr: float = 0.05
    aggregator: Aggregator = Aggregator("mean")
    compressor: Compressor = dataclasses.field(default_factory=identity)
    attack: Attack = dataclasses.field(default_factory=no_attack)
    agg_mode: str = "gspmd"   # gspmd | all_to_all | sparse_support | pallas
    optimizer: Optional[object] = None   # optim.Optimizer or None = plain SGD
    # distributed extras
    worker_axes: tuple = ()              # mesh axes carrying the worker dim
    model_axis: Optional[str] = None
    mesh: Optional[object] = None        # jax Mesh (all_to_all mode)
    grad_specs: Optional[object] = None  # PartitionSpec pytree (all_to_all)
    # system-fault chaos layer (repro.faults, DESIGN.md §6)
    fault_plan: Optional[object] = None  # faults.FaultPlan or None
    fault_guard: bool = False            # fail-closed non-finite masking

    def __post_init__(self):
        """Eager validation: a bad agg_mode / byzantine count used to
        surface as a bare ValueError at call time *inside jit* (or as a
        silently-poisoned aggregate); fail at construction instead."""
        if self.agg_mode not in AGG_BACKENDS:
            raise ValueError(
                f"agg_mode {self.agg_mode!r} not in {AGG_BACKENDS} "
                "(see engine.AGG_BACKENDS / DESIGN.md §3)")
        if not 0.0 <= self.p <= 1.0:
            raise ValueError(f"p={self.p} must be a probability in [0, 1]")
        if self.n_workers < 1:
            raise ValueError(f"n_workers={self.n_workers} must be >= 1")
        from repro.core.theory import delta_over_active_set
        if (not 0 <= self.n_byz
                or delta_over_active_set(self.n_workers, self.n_byz) >= 0.5):
            raise ValueError(
                f"n_byz={self.n_byz} must satisfy 0 <= n_byz < n_workers/2 "
                f"(= {self.n_workers / 2:g}): no (delta,c)-robust aggregator "
                "exists for a byzantine majority (Def. 2.1)")
        if self.n_active is not None:
            if not 1 <= self.n_active <= self.n_workers:
                raise ValueError(
                    f"n_active={self.n_active} must be in [1, n_workers="
                    f"{self.n_workers}]")
            if self.n_active < self.n_workers \
                    and self.agg_mode not in ("gspmd", "pallas"):
                raise ValueError(
                    f"partial participation (n_active={self.n_active}) is "
                    f"not supported under agg_mode={self.agg_mode!r}: the "
                    "masked aggregation prologue lives in the gspmd and "
                    "pallas backends (DESIGN.md §7)")
        n_act = self.active_count()
        s = max(self.aggregator.bucket_size, 1)
        if (self.aggregator.robust and s > 1
                and delta_over_active_set(
                    n_act, self.n_byz, bucket_size=s) >= 0.5):
            warnings.warn(
                f"after bucketing (s={s}) the byzantine fraction over the "
                f"active set is "
                f"{delta_over_active_set(n_act, self.n_byz, bucket_size=s):.2f}"
                " >= 1/2; Def. 2.1's robustness guarantee is void — reduce "
                "bucket_size or n_byz",
                stacklevel=2)
        if self.fault_plan is not None:
            f = self.fault_plan.worst_case_faulty(self.n_workers)
            if f and delta_over_active_set(n_act, self.n_byz + f) >= 0.5:
                warnings.warn(
                    f"fault plan can corrupt up to f={f} workers on top of "
                    f"n_byz={self.n_byz}: byz+faulty over the active set "
                    f"(n_active={n_act}) reaches >= 1/2, so the guarded δ "
                    "budget is exceeded in the worst round — the masked "
                    "aggregate may be unprotected (DESIGN.md §6)",
                    stacklevel=2)

    def active_count(self) -> int:
        """Workers sampled per round; n_workers when participation is off."""
        return self.n_workers if self.n_active is None else self.n_active

    @tu.scoped("attack")
    def byz_mask(self):
        return jnp.arange(self.n_workers) < self.n_byz


def train_state(params, g0, opt_state=None, step=0):
    return {"params": params, "g": g0, "opt_state": opt_state,
            "step": jnp.asarray(step, jnp.int32)}


# ---------------------------------------------------------------------------
# legacy entry points — thin wrappers over the shared round engine
# ---------------------------------------------------------------------------

def make_step(cfg: ByzVRMarinaConfig, loss_fn: Callable,
              corrupt_fn: Optional[Callable] = None):
    """loss_fn(params, batch, key) -> scalar loss.

    ``batch`` / ``anchor`` passed to the returned step are stacked pytrees
    with a leading worker axis (n, ...). ``corrupt_fn(batch, byz_mask)``
    implements data-level attacks (label flipping).
    """
    return make_method("marina", cfg, loss_fn, corrupt_fn).step


def make_init(cfg: ByzVRMarinaConfig, loss_fn: Callable,
              corrupt_fn: Optional[Callable] = None):
    """g^0 initialization (paper: g^0 = ARAgg(∇f_1(x^0), ..., ∇f_n(x^0)))."""
    return make_method("marina", cfg, loss_fn, corrupt_fn).init


# ---------------------------------------------------------------------------
# communication accounting (paper Fig. 8 / footnote 3)
# ---------------------------------------------------------------------------

def comm_bits(cfg: ByzVRMarinaConfig, d: int, c_k: bool) -> int:
    """Bits uploaded per worker this round (delegates to the estimator's
    own accounting so legacy and registry callers can never diverge)."""
    from repro.core.estimators import MarinaEstimator
    return MarinaEstimator().round_bits(cfg, d, bool(c_k))


def expected_comm_bits(cfg: ByzVRMarinaConfig, d: int) -> float:
    from repro.core.estimators import MarinaEstimator
    return MarinaEstimator().expected_bits(cfg, d)
