"""The Byzantine-robust round engine (DESIGN.md §2).

The paper's central observation is architectural: Byz-VR-MARINA and every
method it is compared against (SGD, BR-SGDm, CSGD, BR-DIANA, BR-MVR,
Byrd-SVRG) share one round skeleton and differ *only* in the gradient
estimator. This module owns that skeleton, once:

    1. parameter update            x^{k+1} = x^k - γ g^k  (or optim.Optimizer)
    2. data corruption             label-flipping byzantines (corrupt_fn)
    3. candidate computation       ← the pluggable ``GradientEstimator``
    4. omniscient attack           byzantines replace their message
    5. robust aggregation          backend dispatch (``AGG_BACKENDS``)
    6. server finalization         estimator post-processing (e.g. DIANA's
                                   shift mean) + state carry
    7. metrics + communication     loss, |g|, per-round uploaded bits

Estimators declare whether the parameter update happens *before* the
candidates are computed (MARINA-family: workers need x^{k+1} and x^k) or
*after* (SGD-family: the aggregate is the update direction), and which named
RNG streams they consume — the engine splits the per-round key exactly once,
so a method's trajectory is a pure function of (seed, estimator, config).

Aggregation-backend dispatch (``aggregate``):

  * ``gspmd``          — paper-faithful jnp over the stacked worker axis;
                         GSPMD inserts the all-gather on a mesh.
  * ``all_to_all``     — shard_map sharded aggregation (core/sharded_agg.py).
  * ``sparse_support`` — common-randomness RandK support-only aggregation
                         (handled inside the MARINA estimator; dense rounds
                         stay gspmd).
  * ``pallas``         — one-sweep-per-pass Pallas kernels for EVERY rule
                         (kernels/robust_agg + kernels/norm_agg), launched
                         leaf-wise with the bucketing permutation carried
                         on-chip; ``message_phase`` additionally fuses
                         kernel-fusable attacks into the aggregation load so
                         the attacked tensor never hits HBM.

Layer scopes (DESIGN.md §5): the round's work is traced under
``jax.named_scope`` names that a device trace reads back from each op's HLO
``op_name`` — ``grad`` (``stacked_grads``, MARINA's difference passes),
``compress``, ``attack`` (``apply_attack``, ``fusable_attack_ctx``),
``aggregate`` and ``update`` (``param_update`` and the new state); MARINA's
two branches add ``full_round`` / ``diff_round`` around theirs. Scopes are
metadata only: the step's jaxpr equations are unchanged.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.core import tree_utils as tu


AGG_BACKENDS = ("gspmd", "all_to_all", "sparse_support", "pallas")


# ---------------------------------------------------------------------------
# shared round primitives
# ---------------------------------------------------------------------------

@tu.scoped("attack")
def apply_attack(cfg, key, cand, mask=None, stats_valid=None):
    """cand: stacked pytree (n, ...). Returns the vectors actually 'sent'.

    Omniscient attacks see the good workers' per-coordinate mean/std; NA/LF
    leave the candidates untouched (LF acts at the data level). ``mask``
    overrides ``cfg.byz_mask()`` for callers whose byzantine set is decided
    per call rather than by worker index — the buffered-async service
    (repro.serve) passes the byzantine flags of whatever updates happen to
    sit in the fired buffer. ``stats_valid`` (fault guard, DESIGN.md §6)
    additionally restricts the attack's mean/std statistics to valid rows,
    so a NaN-faulted honest worker cannot poison the omniscient attack the
    way it cannot poison the masked aggregate.
    """
    if cfg.attack.name in ("NA", "LF") or (mask is None and cfg.n_byz == 0):
        return cand
    if mask is None:
        mask = cfg.byz_mask()
    good = ~mask
    if stats_valid is not None:
        good = good & stats_valid
    means, stds = tu.masked_mean_std(cand, good,
                                     sanitize=stats_valid is not None)

    def leaf(h, m, s):
        v = cfg.attack.apply(key, h, m, s).astype(h.dtype)
        bm = mask.reshape((-1,) + (1,) * (h.ndim - 1))
        return jnp.where(bm, v, h)

    return jax.tree.map(leaf, cand, means, stds)


@tu.scoped("grad")
def stacked_grads(loss_fn, params, batches, keys):
    """vmap(value_and_grad) over the leading worker axis of ``batches``."""
    def one(batch, key):
        return jax.value_and_grad(loss_fn)(params, batch, key)

    losses, grads = jax.vmap(one)(batches, keys)
    return jnp.mean(losses), grads


@tu.scoped("aggregate")
def aggregate(cfg, key, sent, valid=None):
    """Backend dispatch for line 10 (g = ARAgg(sent_1, ..., sent_n)).

    ``valid`` (fault guard) is the (n,) row-validity mask: invalid rows get
    zero aggregation weight via the masked rule twins. ``None`` (the
    default) is byte-for-byte the unguarded dispatch."""
    mode = cfg.agg_mode
    if mode in ("gspmd", "sparse_support"):
        # sparse_support only changes the MARINA VR branch (the estimator
        # aggregates on the shared support itself); dense aggregations
        # (init, full-grad rounds, other estimators) stay gspmd.
        if valid is not None:
            return cfg.aggregator.tree_masked(key, sent, valid)
        return cfg.aggregator.tree(key, sent)
    if mode == "all_to_all":
        if valid is not None:
            raise ValueError("fault_guard is not supported under "
                             "agg_mode='all_to_all' (guarded backends: "
                             "gspmd, pallas — DESIGN.md §6)")
        from repro.core.sharded_agg import tree_aggregate_all_to_all
        return tree_aggregate_all_to_all(cfg, key, sent)
    if mode == "pallas":
        from repro.core.sharded_agg import tree_aggregate_pallas
        return tree_aggregate_pallas(cfg, key, sent, valid=valid)
    # backstop only: ByzVRMarinaConfig/RunSpec validate agg_mode eagerly at
    # construction, so a hand-rolled cfg is the only way to get here.
    raise ValueError(f"agg_mode {mode!r} not in {AGG_BACKENDS}")


@tu.scoped("attack")
def fusable_attack_ctx(cfg, cand, mask, stats_valid=None):
    """Build the ``sharded_agg.AttackCtx`` for a kernel-fusable omniscient
    attack (BF/ALIE/IPM via ``Attack.coord_apply``): the byzantine mask plus
    the good workers' per-coordinate mean/std trees, computed only when the
    attack reads them. Shared by ``message_phase``/``ingest_message_phase``
    and the traced twins in ``repro.obs.trace``. ``stats_valid`` (fault
    guard) restricts the statistics to valid rows."""
    from repro.core.sharded_agg import AttackCtx
    means = stds = None
    if cfg.attack.needs_mean or cfg.attack.needs_std:
        good = ~mask if stats_valid is None else ~mask & stats_valid
        means, stds = tu.masked_mean_std(cand, good,
                                         sanitize=stats_valid is not None)
        if not cfg.attack.needs_std:
            stds = None
    return AttackCtx(fn=cfg.attack.coord_apply, mask=mask,
                     means=means, stds=stds)


# Round-scoped participation routing (DESIGN.md §7). Like ``_PHASE_TRACE``
# below, this is a module-level cell read at trace time only: the engine
# step sets it to the (n,) sampled-worker mask for the duration of the
# round when ``cfg.n_active`` requests partial participation, so message
# phases owned by estimators (MARINA's lax.cond branches) route through
# ``participating_message_phase`` without any signature change. With the
# cell at None (full participation) every phase traces the byte-identical
# jaxpr it did before the participation axis existed.
_PHASE_SAMPLED = [None]

# fold_in salt for the participation sampling stream — distinct from the
# fault layer's 0xFA17 so the three per-round streams (attack, fault,
# participation) are pairwise independent of each other's knobs (pinned in
# tests/test_participation.py).
_PART_SALT = 0x5A3B1E


def sampled_worker_mask(cfg, step_key):
    """(n,) bool — the uniformly-sampled participation cohort this round,
    or None under full participation.

    The draw folds ``_PART_SALT`` into the per-round step key (the key the
    engine splits into the estimator's named streams), so the sampling
    stream is disjoint from every named stream by construction and the
    sampled set is bit-replayable from (spec, seed) alone. A uniform
    m-subset without replacement: rank the n workers by a seeded
    permutation and take the first ``n_active``.
    """
    n_active = getattr(cfg, "n_active", None)
    if n_active is None or n_active >= cfg.n_workers:
        return None
    part_key = jax.random.fold_in(step_key, _PART_SALT)
    rank = jax.random.permutation(part_key, cfg.n_workers)
    return rank < n_active


def participating_message_phase(cfg, attack_key, agg_key, cand, sampled):
    """``message_phase`` over the sampled cohort: non-sampled rows get zero
    aggregation weight (select-zero via the masked rule twins — the same
    machinery the fault guard uses), the omniscient attack's mean/std
    statistics see only the sampled good workers (a non-participant is
    invisible to an in-round adversary), and under the guard the validity
    mask is ``sampled & finite`` so the two maskings compose.

    ``WireCandidates`` are densified first (``wire.reconstruct``): the
    fused wire kernels have no masked twin, and partial participation
    already pays the dense roster in simulation. Bucket renormalization
    over the survivors is ``faults.guard.masked_bucket_matrix`` — exactly
    the δ-over-active-set semantics the spec validates against.
    """
    from repro.core import wire
    plan = getattr(cfg, "fault_plan", None)
    if isinstance(cand, wire.WireCandidates):
        if plan is not None and plan.message_faults:
            from repro.faults import inject
            cand = inject.inject_wire(plan, attack_key, cand)
        cand = wire.reconstruct(cand)
    elif plan is not None and plan.tensor_faults:
        from repro.faults import inject
        cand = inject.inject_candidates(plan, attack_key, cand)
    if getattr(cfg, "fault_guard", False):
        from repro.faults import guard as fguard
        valid_pre = fguard.finite_row_mask(cand) & sampled
        sent = apply_attack(cfg, attack_key, cand, stats_valid=valid_pre)
        valid = fguard.finite_row_mask(sent) & sampled
        if cfg.agg_mode == "pallas":
            from repro.core.sharded_agg import tree_aggregate_pallas
            return tree_aggregate_pallas(cfg, agg_key, sent, valid=valid)
        return aggregate(cfg, agg_key, sent, valid=valid)
    if cfg.agg_mode == "pallas":
        from repro.core.sharded_agg import tree_aggregate_pallas
        clean = cfg.n_byz == 0 or cfg.attack.name in ("NA", "LF")
        if clean:
            return tree_aggregate_pallas(cfg, agg_key, cand, valid=sampled)
        if cfg.attack.coord_apply is not None:
            ctx = fusable_attack_ctx(cfg, cand, cfg.byz_mask(),
                                     stats_valid=sampled)
            return tree_aggregate_pallas(cfg, agg_key, cand, attack_ctx=ctx,
                                         valid=sampled)
        sent = apply_attack(cfg, attack_key, cand, stats_valid=sampled)
        return tree_aggregate_pallas(cfg, agg_key, sent, valid=sampled)
    sent = apply_attack(cfg, attack_key, cand, stats_valid=sampled)
    return aggregate(cfg, agg_key, sent, valid=sampled)


def message_phase(cfg, attack_key, agg_key, cand):
    """Lines 9-10 of the round: omniscient attack, then robust aggregation.

    For ``agg_mode="pallas"`` with a kernel-fusable attack (BF/ALIE/IPM via
    ``Attack.coord_apply``; NA/LF and n_byz=0 trivially) the injection
    happens inside the aggregation kernels' VMEM load — the attacked
    ``sent`` tensor is never written to HBM (DESIGN.md §3). RN (needs the
    exact jax.random stream) and the other backends materialize ``sent``
    via ``apply_attack`` as before.

    ``cand`` may also be a ``wire.WireCandidates`` payload (estimators whose
    compressor declares a kernel wire format, under pallas): then even the
    candidates themselves never materialize — the kernels reconstruct
    base + decode(payload) per VMEM block (DESIGN.md §Wire).

    The chaos layer (repro.faults, DESIGN.md §6) hooks in here: a
    ``cfg.fault_plan`` injects message-site faults into ``cand`` before the
    attack, and ``cfg.fault_guard`` reroutes to the fail-closed
    ``guarded_message_phase``. Both are static Python branches — with the
    plan unset and the guard off this function traces the identical jaxpr
    it did before the faults layer existed (pinned in tests/test_faults).

    Partial participation (DESIGN.md §7) routes here too: when the engine
    step has published a sampled-worker mask (``_PHASE_SAMPLED``), the
    round aggregates over the sampled cohort only. Full participation
    leaves the cell at None and this body is untouched.
    """
    if _PHASE_SAMPLED[0] is not None:
        return participating_message_phase(cfg, attack_key, agg_key, cand,
                                           _PHASE_SAMPLED[0])
    from repro.core import wire
    plan = getattr(cfg, "fault_plan", None)
    if isinstance(cand, wire.WireCandidates):
        if plan is not None and plan.message_faults:
            from repro.faults import inject
            cand = inject.inject_wire(plan, attack_key, cand)
        return wire.wire_message_phase(cfg, attack_key, agg_key, cand)
    if plan is not None and plan.tensor_faults:
        from repro.faults import inject
        cand = inject.inject_candidates(plan, attack_key, cand)
    if getattr(cfg, "fault_guard", False):
        return guarded_message_phase(cfg, attack_key, agg_key, cand)
    if cfg.agg_mode == "pallas":
        from repro.core.sharded_agg import tree_aggregate_pallas
        clean = cfg.n_byz == 0 or cfg.attack.name in ("NA", "LF")
        if clean:
            return tree_aggregate_pallas(cfg, agg_key, cand)
        if cfg.attack.coord_apply is not None:
            ctx = fusable_attack_ctx(cfg, cand, cfg.byz_mask())
            return tree_aggregate_pallas(cfg, agg_key, cand, attack_ctx=ctx)
    sent = apply_attack(cfg, attack_key, cand)
    return aggregate(cfg, agg_key, sent)


def guarded_message_phase(cfg, attack_key, agg_key, cand, return_valid=False):
    """Fail-closed twin of ``message_phase`` over dense candidates: rows
    that are non-finite in any coordinate get zero aggregation weight and
    count toward the δ budget (they are treated exactly as explicitly
    dropped workers — the equivalence the fault-matrix test pins).

    * attack statistics see only honest AND valid rows, matching the oracle
      that never saw the faulted workers;
    * a Byzantine row overwritten by the attack is valid again (the attack
      value is finite by construction — it is a *statistical* adversary,
      which is the aggregator's job, not the guard's);
    * materializing paths re-check finiteness on the attacked tensor, so
      even a non-finite attack output fails closed.

    ``return_valid`` additionally returns the final (n,) validity mask (the
    obs layer records ``~valid`` as the guard's detection next to the
    injected ground truth).
    """
    from repro.faults import guard as fguard
    valid_pre = fguard.finite_row_mask(cand)
    clean = cfg.n_byz == 0 or cfg.attack.name in ("NA", "LF")
    byz = None if clean else cfg.byz_mask()
    if cfg.agg_mode == "pallas":
        from repro.core.sharded_agg import tree_aggregate_pallas
        if clean:
            agg = tree_aggregate_pallas(cfg, agg_key, cand, valid=valid_pre)
            return (agg, valid_pre) if return_valid else agg
        if cfg.attack.coord_apply is not None:
            ctx = fusable_attack_ctx(cfg, cand, byz, stats_valid=valid_pre)
            # keep valid_pre: BF-style coord_apply transforms the candidate
            # value, so a byz∩faulty row's attacked value is still NaN —
            # crediting byz rows back as valid would let it through. The
            # prologue orders attack-select -> valid-select, zeroing it.
            agg = tree_aggregate_pallas(cfg, agg_key, cand, attack_ctx=ctx,
                                        valid=valid_pre)
            return (agg, valid_pre) if return_valid else agg
        sent = apply_attack(cfg, attack_key, cand, stats_valid=valid_pre)
        valid = fguard.finite_row_mask(sent)
        agg = tree_aggregate_pallas(cfg, agg_key, sent, valid=valid)
        return (agg, valid) if return_valid else agg
    sent = apply_attack(cfg, attack_key, cand, stats_valid=valid_pre)
    valid = fguard.finite_row_mask(sent)
    agg = aggregate(cfg, agg_key, sent, valid=valid)
    return (agg, valid) if return_valid else agg


# Trace-time routing for estimators that own their message phase (MARINA's
# lax.cond branches): the telemetry twin built by make_engine_step(trace=True)
# flips this flag while est.round traces, so ``phase_with_trace`` — called
# from INSIDE the branch — returns (agg, RoundTrace) and the trace escapes
# the cond through ``RoundOutput.trace`` (both branches build the same
# RoundTrace structure for a fixed rule, so lax.cond accepts it). The flag is
# read at trace time only; with it off the call is byte-for-byte
# ``message_phase`` and the extra None output adds no jaxpr equations.
_PHASE_TRACE = [False]


def phase_with_trace(cfg, attack_key, agg_key, cand):
    """``message_phase`` that also returns this round's RoundTrace when the
    enclosing engine step is the telemetry twin; ``(agg, None)`` otherwise."""
    if _PHASE_TRACE[0]:
        from repro.obs import trace as obs_trace
        return obs_trace.traced_message_phase(cfg, attack_key, agg_key, cand)
    return message_phase(cfg, attack_key, agg_key, cand), None


def ingest_message_phase(cfg, attack_key, agg_key, cand, *, byz_mask=None,
                         weights=None):
    """Partial/buffered-candidate entry to lines 9-10 of the round.

    Twin of ``message_phase`` for callers that aggregate a BUFFER of updates
    rather than the full worker roster (the streaming service, repro.serve):

    * ``byz_mask`` — (K,) bool over the buffered entries: which of them came
      from byzantine clients. The byzantine fraction is defined over the
      *buffered* set, so the mask is per-call data (traced), not the static
      ``cfg.byz_mask()`` worker-index prefix.
    * ``weights``  — optional (K,) per-entry multiplicative scale applied to
      the sent vectors before bucketing/rule (staleness weighting: the
      service passes ``K * s(tau_i) / sum_j s(tau_j)``, so ``rule="mean"``
      reproduces the FedBuff weighted mean exactly). Under pallas the scale
      is fused into the aggregation's on-chip ``w`` operator (a diagonal
      composed with the bucket matrix — zero extra HBM traffic); the jnp
      path materializes the scaled tree, which is also the test oracle.

    With both omitted this IS ``message_phase``. ``WireCandidates`` are not
    accepted — the service buffer holds dense (decoded) updates.
    """
    from repro.core import wire
    if isinstance(cand, wire.WireCandidates):
        raise TypeError(
            "ingest_message_phase aggregates dense buffered updates; decode "
            "wire payloads at ingest (serve/buffer.py) before firing")
    if byz_mask is None and weights is None:
        return message_phase(cfg, attack_key, agg_key, cand)
    clean = cfg.attack.name in ("NA", "LF") or (byz_mask is None
                                                and cfg.n_byz == 0)
    if getattr(cfg, "fault_guard", False):
        from repro.faults import guard as fguard
        valid_pre = fguard.finite_row_mask(cand)
        sent = apply_attack(cfg, attack_key, cand, mask=byz_mask,
                            stats_valid=valid_pre)
        valid = fguard.finite_row_mask(sent)
        if cfg.agg_mode == "pallas":
            from repro.core.sharded_agg import tree_aggregate_pallas
            return tree_aggregate_pallas(cfg, agg_key, sent, weights=weights,
                                         valid=valid)
        if weights is not None:
            w = weights.astype(jnp.float32)
            sent = jax.tree.map(
                lambda a: (a.astype(jnp.float32)
                           * w.reshape((-1,) + (1,) * (a.ndim - 1))
                           ).astype(a.dtype), sent)
        return aggregate(cfg, agg_key, sent, valid=valid)
    if cfg.agg_mode == "pallas":
        from repro.core.sharded_agg import tree_aggregate_pallas
        if clean:
            return tree_aggregate_pallas(cfg, agg_key, cand, weights=weights)
        if cfg.attack.coord_apply is not None:
            mask = byz_mask if byz_mask is not None else cfg.byz_mask()
            ctx = fusable_attack_ctx(cfg, cand, mask)
            return tree_aggregate_pallas(cfg, agg_key, cand, attack_ctx=ctx,
                                         weights=weights)
        # unfusable attack (RN): materialize, but keep the weights fused
        sent = apply_attack(cfg, attack_key, cand, mask=byz_mask)
        return tree_aggregate_pallas(cfg, agg_key, sent, weights=weights)
    sent = apply_attack(cfg, attack_key, cand, mask=byz_mask)
    if weights is not None:
        w = weights.astype(jnp.float32)
        sent = jax.tree.map(
            lambda a: (a.astype(jnp.float32)
                       * w.reshape((-1,) + (1,) * (a.ndim - 1))
                       ).astype(a.dtype), sent)
    return aggregate(cfg, agg_key, sent)


@tu.scoped("update")
def param_update(cfg, params, g, opt_state):
    """x <- x - γ g (dtype-preserving, fp32 math) or cfg.optimizer.update."""
    if cfg.optimizer is None:
        new = jax.tree.map(
            lambda x, gg: (x.astype(jnp.float32)
                           - cfg.lr * gg.astype(jnp.float32)).astype(x.dtype),
            params, g)
        return new, opt_state
    return cfg.optimizer.update(g, opt_state, params)


def maybe_corrupt(cfg, corrupt_fn, batch):
    """Data-level attacks (label flipping) on the byzantine workers."""
    if corrupt_fn is not None and cfg.attack.flips_labels and cfg.n_byz:
        return corrupt_fn(batch, cfg.byz_mask())
    return batch


# ---------------------------------------------------------------------------
# estimator protocol
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class RoundOutput:
    """What an estimator hands back to the engine each round.

    Either ``cand`` (stacked candidates the engine attacks + aggregates,
    with optional ``finalize(agg) -> (g, state_updates)`` server-side
    post-processing) or ``g_new`` (the estimator ran the message phase
    itself — the sparse-support path, where attack/aggregation happen on
    the shared RandK support only). ``trace`` carries the RoundTrace out of
    estimator-owned message phases (``phase_with_trace``) when the
    telemetry twin is running; None otherwise.
    """
    loss: Any
    cand: Any = None
    finalize: Optional[Callable] = None
    g_new: Any = None
    updates: Optional[dict] = None
    metrics: Optional[dict] = None
    trace: Any = None


class GradientEstimator:
    """Interface for pluggable per-worker gradient estimators.

    Subclasses set:
      * ``name``                — registry key.
      * ``rng``                 — ordered per-round RNG stream names; must
                                  end with ("attack", "agg"). The engine
                                  splits the round key into exactly these.
      * ``update_params_first`` — True for MARINA-family estimators whose
                                  candidates are computed at x^{k+1}.
      * ``seed_batchable``      — False when state must not be vmapped over
                                  seeds (per-worker gradient tables); the
                                  sweep engine then runs such cells on the
                                  serial / WorkerPool path (DESIGN.md §2).
      * ``streamable``          — True when the candidate computation is a
                                  pure per-client function of (params, batch,
                                  local state) so updates can be computed at
                                  dispatch time and aggregated later from a
                                  buffer (the buffered-async service,
                                  repro.serve / DESIGN.md §4). Estimators
                                  whose round couples clients through shared
                                  per-round draws or anchor full-gradient
                                  broadcasts (MARINA's c_k coin, SVRG
                                  snapshots) stay False.
    and implement ``init_extras`` and ``round``.
    """
    name: str = "?"
    rng: tuple = ("grad", "attack", "agg")
    update_params_first: bool = False
    seed_batchable: bool = True
    streamable: bool = False

    def init_extras(self, cfg, loss_fn, params, anchor, key):
        """-> (g0, extras): the initial server estimate and any extra state
        (stacked worker momenta / shifts / snapshots ...)."""
        raise NotImplementedError

    def round(self, cfg, loss_fn, state, params, old_params, batch, anchor,
              keys) -> RoundOutput:
        """Compute this round's candidate messages (or the full message
        phase, for estimators that own their aggregation)."""
        raise NotImplementedError

    # -- communication accounting (paper Fig. 8 / footnote 3) --------------
    def round_bits(self, cfg, d: int, full_round: bool = True) -> int:
        """Bits uploaded per worker this round."""
        return 32 * d

    def expected_bits(self, cfg, d: int) -> float:
        return float(self.round_bits(cfg, d))


def carry_unsampled_state(state, updates, sampled, n_workers):
    """Freeze the per-worker state of non-participants (DESIGN.md §7).

    A worker that was not sampled this round neither computed nor uploaded
    anything, so its estimator state — SAGA gradient tables, EF21
    ``worker_g``, cmfilter ``worker_m``/``worker_u``, SVRG snapshots — must
    carry forward bit-identically. Estimators mark per-worker stacked state
    with the ``worker_*`` key prefix (every leaf leading axis = n_workers);
    for those keys the round's update is select-merged row-wise against
    the previous state. Server-side updates (``snapshot``, ``prev_params``,
    DIANA's shift mean) pass through untouched: the server did run this
    round, over the sampled cohort.
    """
    out = {}
    for k, new in updates.items():
        old = state.get(k)
        if old is None or not k.startswith("worker_"):
            out[k] = new
            continue

        def merge(nl, ol):
            assert nl.shape[0] == n_workers, (k, nl.shape)
            keep = sampled.reshape((-1,) + (1,) * (nl.ndim - 1))
            return jnp.where(keep, nl, ol)

        out[k] = jax.tree.map(merge, new, old)
    return out


# ---------------------------------------------------------------------------
# engine step / init factories
# ---------------------------------------------------------------------------

def make_engine_init(cfg, loss_fn, estimator: GradientEstimator,
                     corrupt_fn: Optional[Callable] = None):
    def init(params, anchor, key):
        if anchor is not None:
            anchor = maybe_corrupt(cfg, corrupt_fn, anchor)
        g0, extras = estimator.init_extras(cfg, loss_fn, params, anchor, key)
        opt_state = (cfg.optimizer.init(params)
                     if cfg.optimizer is not None else None)
        return {"params": params, "g": g0, "opt_state": opt_state,
                "step": jnp.zeros((), jnp.int32), **extras}

    return init


def make_engine_step(cfg, loss_fn, estimator: GradientEstimator,
                     corrupt_fn: Optional[Callable] = None,
                     trace: bool = False):
    """``trace=True`` builds the telemetry twin: the message phase runs
    through ``repro.obs.trace.traced_message_phase`` — the identical
    aggregation calls plus the rule's own intermediates — and the returned
    metrics gain a ``"trace"`` RoundTrace entry. Estimators that own their
    message phase route through ``phase_with_trace`` and hand the trace back
    via ``RoundOutput.trace`` (None when they aggregate without the shared
    phase, e.g. sparse-support VR rounds). The default ``trace=False`` path
    is byte-for-byte today's step."""
    est = estimator
    assert est.rng[-2:] == ("attack", "agg"), est.rng

    def step(state, batch, anchor, key):
        keys = dict(zip(est.rng, jax.random.split(key, len(est.rng))))
        old_params = state["params"]
        sampled = sampled_worker_mask(cfg, key)

        if est.update_params_first:
            new_params, new_opt = param_update(cfg, old_params, state["g"],
                                               state["opt_state"])
        else:
            new_params, new_opt = old_params, state["opt_state"]

        batch = maybe_corrupt(cfg, corrupt_fn, batch)
        anchor = maybe_corrupt(cfg, corrupt_fn, anchor)

        prev_flag, prev_sampled = _PHASE_TRACE[0], _PHASE_SAMPLED[0]
        _PHASE_TRACE[0] = trace
        _PHASE_SAMPLED[0] = sampled
        try:
            ro = est.round(cfg, loss_fn, state, new_params, old_params,
                           batch, anchor, keys)
            updates = dict(ro.updates or {})

            rt = None
            if ro.g_new is not None:
                g = ro.g_new
                rt = ro.trace
            else:
                if trace:
                    from repro.obs import trace as obs_trace
                    agg, rt = obs_trace.traced_message_phase(
                        cfg, keys["attack"], keys["agg"], ro.cand)
                else:
                    agg = message_phase(cfg, keys["attack"], keys["agg"],
                                        ro.cand)
                if ro.finalize is not None:
                    g, fin_updates = ro.finalize(agg)
                    updates.update(fin_updates)
                else:
                    g = agg
        finally:
            _PHASE_TRACE[0] = prev_flag
            _PHASE_SAMPLED[0] = prev_sampled

        with jax.named_scope("update"):
            if sampled is not None:
                updates = carry_unsampled_state(state, updates, sampled,
                                                cfg.n_workers)

            if not est.update_params_first:
                new_params, new_opt = param_update(cfg, old_params, g,
                                                   state["opt_state"])

            new_state = {**state, **updates, "params": new_params, "g": g,
                         "opt_state": new_opt, "step": state["step"] + 1}
            metrics = {"loss": ro.loss,
                       **(ro.metrics or {}),
                       "g_norm": jnp.sqrt(tu.tree_norm_sq(g))}
        if trace:
            metrics["trace"] = rt
        return new_state, metrics

    return step


# ---------------------------------------------------------------------------
# method registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Method:
    """A fully-assembled Byzantine-robust training method.

    ``init(params, anchor, key) -> state`` and
    ``step(state, batch, anchor, key) -> (state, metrics)`` run through the
    shared engine; ``estimator`` is the plugged-in GradientEstimator.
    ``step_traced`` is the telemetry twin (metrics carry a ``"trace"``
    RoundTrace; the trajectory is bit-identical to ``step``) used by the
    runner on log-cadence steps when ``RunSpec.trace`` is on.
    """
    name: str
    estimator: GradientEstimator
    init: Callable
    step: Callable
    cfg: Any
    step_traced: Optional[Callable] = None

    def round_bits(self, d: int, full_round: bool = True) -> int:
        return self.estimator.round_bits(self.cfg, d, full_round)

    def expected_bits(self, d: int) -> float:
        return self.estimator.expected_bits(self.cfg, d)


def make_method(name: str, cfg, loss_fn,
                corrupt_fn: Optional[Callable] = None, **est_kw) -> Method:
    """Assemble a registered method over the shared round engine.

    name in ``list_methods()``: marina | sgd | sgdm | csgd | diana | mvr
    | svrg | byz_ef21 | cmfilter | saga. ``est_kw`` are estimator knobs
    (momentum, alpha, batch_size, ...).
    """
    from repro.core import estimators as E
    est = E.get_estimator(name, cfg, **est_kw)
    return Method(
        name=name, estimator=est, cfg=cfg,
        init=make_engine_init(cfg, loss_fn, est, corrupt_fn),
        step=make_engine_step(cfg, loss_fn, est, corrupt_fn),
        step_traced=make_engine_step(cfg, loss_fn, est, corrupt_fn,
                                     trace=True))


def list_methods():
    from repro.core import estimators as E
    return sorted(E.ESTIMATORS)
