"""Gradient estimators pluggable into the round engine (DESIGN.md §2).

Each estimator owns exactly what distinguishes its method from the others:
its per-worker candidate computation, any extra worker/server state, and its
communication cost. Everything else — parameter update, data corruption,
omniscient attacks, (δ,c)-robust aggregation, metrics — is the engine's.

  marina — Byz-VR-MARINA (Alg. 1): the paper's contribution. Geometric coin
           switches anchor full-gradients and compressed SARAH differences
           g^k + Q(∇f(x^{k+1}) - ∇f(x^k)). With agg_mode="sparse_support"
           and common-randomness RandK, the VR round attacks + aggregates
           only the shared K-coordinate support.
  sgd    — Parallel-SGD with (robust) averaging (Zinkevich et al. 2010).
  sgdm   — BR-SGDm: worker momenta are attacked & aggregated (Karimireddy
           et al. 2021/22).
  csgd   — compressed SGD; with a robust aggregator = BR-CSGD.
  diana  — BR-DIANA: worker shifts h_i, uploads Q(g_i - h_i) (Mishchenko et
           al. 2019 + robust aggregation).
  mvr    — BR-MVR / STORM momentum variance reduction (Karimireddy 2021).
  svrg   — Byrd-SVRG (loopless; App. B.4 proxy of Byrd-SAGA, Wu et al. 2020).

Successor methods over the same engine (ROADMAP "New estimators"):

  byz_ef21 — Byz-EF21 (Rammal et al. 2023): biased/contractive compressors
             + per-worker error feedback; every upload is one compressed
             difference, the EF state absorbs the compressor bias.
  cmfilter — compressed momentum filtering (Liu et al. 2024): worker
             momenta uploaded as compressed differences against a
             server-mirrored reconstruction; the robust aggregator is the
             filter, optionally blended by a server-side momentum.
  saga     — Byrd-SAGA (Wu et al. 2020) fitted to the stacked
             corrupt→attack→aggregate protocol: per-worker per-sample
             gradient table over the anchor partition. Tables are worker
             state, not wire traffic, and do NOT vmap over seeds
             (``seed_batchable = False`` routes sweeps down the serial /
             WorkerPool path — see exec/batching.can_batch).

Every entry must pass tests/test_estimator_contract.py (the conformance
harness): checkpoint round-trip, run(spec) ≡ hand-wired engine, comm
accounting ≡ theory.comm_bits_per_round, descent, pallas ≡ gspmd.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import tree_utils as tu
from repro.core.engine import (GradientEstimator, RoundOutput,
                               apply_attack, message_phase,
                               phase_with_trace, stacked_grads)


def _zeros_like_f32(params):
    return jax.tree.map(lambda x: jnp.zeros_like(x, jnp.float32), params)


class CompressedUploadBits:
    """Comm accounting for estimators whose every upload is Q(·)."""

    def round_bits(self, cfg, d, full_round=True):
        return int(cfg.compressor.bits_per_vector(d))

    def expected_bits(self, cfg, d):
        return float(cfg.compressor.bits_per_vector(d))


# ---------------------------------------------------------------------------
# Byz-VR-MARINA
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class MarinaEstimator(GradientEstimator):
    """Alg. 1 (lines 4-10): c_k ~ Be(p) picks anchor full-gradients or the
    compressed variance-reduced difference estimator."""
    name = "marina"
    rng = ("bern", "grad", "q", "attack", "agg")
    update_params_first = True

    def init_extras(self, cfg, loss_fn, params, anchor, key):
        # paper: g^0 = ARAgg(∇f_1(x^0), ..., ∇f_n(x^0))
        k_grad, k_attack, k_agg = jax.random.split(key, 3)
        wkeys = tu.per_worker_keys(k_grad, cfg.n_workers)
        _, grads = stacked_grads(loss_fn, params, anchor, wkeys)
        return message_phase(cfg, k_attack, k_agg, grads), {}

    def round(self, cfg, loss_fn, state, params, old_params, batch, anchor,
              keys):
        from repro.core import wire

        n = cfg.n_workers
        c_k = jax.random.bernoulli(keys["bern"], cfg.p)
        wkeys = tu.per_worker_keys(keys["grad"], n)

        # branch-local message phases (lax.cond branches must return one
        # pytree structure, and the VR branch's wire payload has none of the
        # full branch's dense shape): each branch attacks + aggregates with
        # the SAME keys the engine would have used, so trajectories are
        # unchanged vs. the engine-side phase. phase_with_trace lets the
        # telemetry twin's RoundTrace escape the cond (both branches build
        # the same trace structure); on the untraced step it IS
        # message_phase and the None slot adds nothing to the jaxpr. Each
        # branch runs under its round-kind scope, so a device trace shows c_k
        # per round, with the layer scopes inside.
        @tu.scoped("full_round")
        def full_branch(_):
            loss, grads = stacked_grads(loss_fn, params, anchor, wkeys)
            g, rt = phase_with_trace(cfg, keys["attack"], keys["agg"],
                                     grads)
            return loss, g, rt

        @tu.scoped("diff_round")
        def vr_branch(_):
            with jax.named_scope("compress"):
                qkeys = tu.per_worker_keys(
                    keys["q"], n, common=cfg.compressor.common_randomness)

            def one(b, kg):
                ln, gn = jax.value_and_grad(loss_fn)(params, b, kg)
                _, go = jax.value_and_grad(loss_fn)(old_params, b, kg)
                return ln, tu.tree_sub(gn, go)

            with jax.named_scope("grad"):
                losses, deltas = jax.vmap(one)(batch, wkeys)
                loss = jnp.mean(losses)
            if wire.wire_supported(cfg, deltas):
                # candidate = g^k + Q(delta): g^k rides as the SHARED (1, d)
                # reconstruction base, Q(delta) as the wire payload.
                wc = wire.pack_candidates(cfg.compressor, qkeys, deltas,
                                          base=state["g"], base_shared=True)
                g, rt = phase_with_trace(cfg, keys["attack"], keys["agg"],
                                         wc)
                return loss, g, rt
            with jax.named_scope("compress"):
                qs = jax.vmap(
                    lambda kq, t: tu.compress_tree(cfg.compressor, kq, t)
                )(qkeys, deltas)
                cand = jax.tree.map(lambda g0, q: g0[None] + q, state["g"],
                                    qs)
            g, rt = phase_with_trace(cfg, keys["attack"], keys["agg"],
                                     cand)
            return loss, g, rt

        loss, g_new, rt = lax.cond(c_k, full_branch, vr_branch, operand=None)
        dims = [int(p.size) for p in jax.tree.leaves(params)]
        vr_bits = wire.tree_wire_bits(
            cfg.compressor,
            jax.tree.map(lambda p: p[None], params))
        wire_bits = jnp.where(c_k, jnp.float32(32.0 * sum(dims)),
                              jnp.float32(vr_bits))
        return RoundOutput(loss=loss, g_new=g_new, trace=rt,
                           metrics={"c_k": c_k.astype(jnp.int32),
                                    "wire_bits": wire_bits})

    def round_bits(self, cfg, d, full_round=True):
        if full_round:
            return 32 * d
        return int(cfg.compressor.bits_per_vector(d))

    def expected_bits(self, cfg, d):
        return (cfg.p * 32 * d
                + (1 - cfg.p) * cfg.compressor.bits_per_vector(d))


@dataclasses.dataclass
class MarinaSparseEstimator(MarinaEstimator):
    """§Perf sparse-support variant: common-randomness RandK means every
    worker sends the SAME K coordinates, so only the K-sized support is
    attacked, gathered, and aggregated; off-support coordinates keep g^k
    exactly (the paper's own remark: the server bans senders outside the
    agreed support). Owns its whole message phase, so attack + aggregation
    live inside the c_k branches."""
    name = "marina_sparse"

    def round(self, cfg, loss_fn, state, params, old_params, batch, anchor,
              keys):
        from repro.core.compressors import unit_partition

        n = cfg.n_workers
        ratio = cfg.compressor.ratio   # validated by _marina_factory
        c_k = jax.random.bernoulli(keys["bern"], cfg.p)
        wkeys = tu.per_worker_keys(keys["grad"], n)

        def support_take(leaf_flat, idx, blk, d):
            pad = (-d) % blk
            xf = jnp.pad(leaf_flat, (0, pad)).reshape(-1, blk)
            return xf[idx]                               # (k_units, blk)

        def support_put(leaf, idx, blk, vals):
            d = leaf.size
            pad = (-d) % blk
            xf = jnp.pad(leaf.reshape(-1).astype(jnp.float32), (0, pad))
            xf = xf.reshape(-1, blk).at[idx].set(vals)
            return xf.reshape(-1)[:d].reshape(leaf.shape).astype(leaf.dtype)

        @tu.scoped("full_round")
        def full_branch(_):
            loss, grads = stacked_grads(loss_fn, params, anchor, wkeys)
            sent = apply_attack(cfg, keys["attack"], grads)
            with jax.named_scope("aggregate"):
                return loss, cfg.aggregator.tree(keys["agg"], sent)

        @tu.scoped("diff_round")
        def sparse_branch(_):
            # shared per-leaf supports (same key for every worker)
            g_leaves, treedef = jax.tree.flatten(state["g"])
            meta = []
            with jax.named_scope("compress"):
                for i, gl in enumerate(g_leaves):
                    d = gl.size
                    blk, n_units = unit_partition(d)
                    k_units = max(int(ratio * n_units), 1)
                    kk = jax.random.fold_in(keys["q"], i)
                    idx = jax.random.permutation(kk, n_units)[:k_units]
                    meta.append((blk, n_units, k_units, idx,
                                 n_units / k_units, d))

            def one(b, kg):
                with jax.named_scope("grad"):
                    ln, gn = jax.value_and_grad(loss_fn)(params, b, kg)
                    _, go = jax.value_and_grad(loss_fn)(old_params, b, kg)
                    delta = tu.tree_sub(gn, go)
                d_leaves = jax.tree.leaves(delta)
                vals = []
                with jax.named_scope("compress"):
                    for (blk, nu, ku, idx, scale, d), dl in zip(meta,
                                                                d_leaves):
                        v = support_take(dl.reshape(-1).astype(jnp.float32),
                                         idx, blk, d) * scale
                        vals.append(v)
                return ln, tuple(vals)

            losses, dvals = jax.vmap(one)(batch, wkeys)
            # candidates on the support: g^k|support + scaled delta
            cand = []
            with jax.named_scope("compress"):
                for (blk, nu, ku, idx, scale, d), gl, dv in zip(
                        meta, g_leaves, dvals):
                    base = support_take(gl.reshape(-1).astype(jnp.float32),
                                        idx, blk, d)
                    cand.append(base[None] + dv)
            sent = apply_attack(cfg, keys["attack"], tuple(cand))
            with jax.named_scope("aggregate"):
                agg_vals = cfg.aggregator.tree(keys["agg"], sent)
                new_leaves = [support_put(gl, m[3], m[0], av)
                              for m, gl, av in zip(meta, g_leaves,
                                                   agg_vals)]
            with jax.named_scope("grad"):
                loss = jnp.mean(losses)
            return loss, jax.tree.unflatten(treedef, new_leaves)

        loss, g_new = lax.cond(c_k, full_branch, sparse_branch, operand=None)
        return RoundOutput(loss=loss, g_new=g_new,
                           metrics={"c_k": c_k.astype(jnp.int32)})


# ---------------------------------------------------------------------------
# SGD / BR-SGDm
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class SGDEstimator(GradientEstimator):
    """momentum=0 -> Parallel-SGD; momentum>0 -> BR-SGDm (worker momenta are
    what gets attacked & aggregated, per Karimireddy et al. 2021)."""
    momentum: float = 0.0
    name = "sgd"
    rng = ("grad", "attack", "agg")
    streamable = True       # per-client grads/momenta: serve can buffer them

    def init_extras(self, cfg, loss_fn, params, anchor, key):
        g0 = (_zeros_like_f32(params) if self.momentum > 0.0
              else tu.tree_zeros_like(params))
        return g0, {"worker_m": tu.tree_broadcast_leading(
            _zeros_like_f32(params), cfg.n_workers)}

    def round(self, cfg, loss_fn, state, params, old_params, batch, anchor,
              keys):
        wkeys = tu.per_worker_keys(keys["grad"], cfg.n_workers)
        loss, grads = stacked_grads(loss_fn, params, batch, wkeys)
        if self.momentum > 0.0:
            m_new = jax.tree.map(
                lambda m, g: ((1 - self.momentum) * g.astype(jnp.float32)
                              + self.momentum * m.astype(jnp.float32)),
                state["worker_m"], grads)
            cand = m_new
        else:
            m_new = state["worker_m"]
            cand = grads
        return RoundOutput(loss=loss, cand=cand,
                           updates={"worker_m": m_new})


# ---------------------------------------------------------------------------
# CSGD / BR-CSGD
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class CSGDEstimator(CompressedUploadBits, GradientEstimator):
    name = "csgd"
    rng = ("grad", "q", "attack", "agg")
    streamable = True       # Q(grad_i) is still a pure per-client function

    def init_extras(self, cfg, loss_fn, params, anchor, key):
        return tu.tree_zeros_like(params), {}

    def round(self, cfg, loss_fn, state, params, old_params, batch, anchor,
              keys):
        from repro.core import wire

        n = cfg.n_workers
        wkeys = tu.per_worker_keys(keys["grad"], n)
        qkeys = tu.per_worker_keys(keys["q"], n,
                                   common=cfg.compressor.common_randomness)
        losses, grads = stacked_grads(loss_fn, params, batch, wkeys)
        metrics = {"wire_bits": jnp.float32(
            wire.tree_wire_bits(cfg.compressor, grads))}
        if wire.wire_supported(cfg, grads):
            cand = wire.pack_candidates(cfg.compressor, qkeys, grads)
        else:
            cand = jax.vmap(
                lambda kq, g: tu.compress_tree(cfg.compressor, kq, g)
            )(qkeys, grads)
        return RoundOutput(loss=losses, cand=cand, metrics=metrics)


# ---------------------------------------------------------------------------
# BR-DIANA
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class DianaEstimator(CompressedUploadBits, GradientEstimator):
    """DIANA: worker i keeps a shift h_i, uploads Q(g_i - h_i); the server
    adds the aggregated compressed difference to the shift mean. alpha
    defaults to 1/(1+omega) (Mishchenko et al. 2019)."""
    alpha: Optional[float] = None
    d_hint: Optional[int] = None
    name = "diana"
    rng = ("grad", "q", "attack", "agg")

    def init_extras(self, cfg, loss_fn, params, anchor, key):
        d = int(self.d_hint if self.d_hint is not None
                else tu.tree_size(params))
        omega = cfg.compressor.omega(d)
        a = self.alpha if self.alpha is not None else 1.0 / (1.0 + omega)
        extras = {
            "worker_h": tu.tree_broadcast_leading(_zeros_like_f32(params),
                                                  cfg.n_workers),
            "alpha": jnp.asarray(a, jnp.float32),
        }
        return _zeros_like_f32(params), extras

    def round(self, cfg, loss_fn, state, params, old_params, batch, anchor,
              keys):
        n = cfg.n_workers
        wkeys = tu.per_worker_keys(keys["grad"], n)
        qkeys = tu.per_worker_keys(keys["q"], n,
                                   common=cfg.compressor.common_randomness)
        h = state["worker_h"]                              # stacked (n, ...)
        a = state["alpha"]

        from repro.core import wire

        def one(b, kg, h_i):
            ln, g = jax.value_and_grad(loss_fn)(params, b, kg)
            return ln, tu.tree_sub(g, h_i)

        losses, diffs = jax.vmap(one)(batch, wkeys, h)
        metrics = {"wire_bits": jnp.float32(
            wire.tree_wire_bits(cfg.compressor, diffs))}
        if wire.wire_supported(cfg, diffs):
            cand = wire.pack_candidates(cfg.compressor, qkeys, diffs)
            qdiff = wire.decoded_payload(cand)   # ≡ vmap(compress_tree)
        else:
            cand = qdiff = jax.vmap(
                lambda kq, t: tu.compress_tree(cfg.compressor, kq, t)
            )(qkeys, diffs)
        h_mean = jax.tree.map(lambda x: jnp.mean(x, axis=0), h)
        h_new = jax.tree.map(lambda hh, q: hh + a * q, h, qdiff)

        def finalize(agg_diff):
            return tu.tree_add(h_mean, agg_diff), {"worker_h": h_new}

        return RoundOutput(loss=jnp.mean(losses), cand=cand,
                           finalize=finalize, metrics=metrics)


# ---------------------------------------------------------------------------
# BR-MVR (STORM)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class MVREstimator(GradientEstimator):
    """BR-MVR (Karimireddy et al. 2021): momentum variance reduction
    (STORM/MVR estimator) per worker + robust aggregation.

        v_i^k = g_i(x^k) + (1-α)(v_i^{k-1} - g_i(x^{k-1}))
    """
    alpha: float = 0.1
    name = "mvr"
    rng = ("grad", "attack", "agg")

    def init_extras(self, cfg, loss_fn, params, anchor, key):
        wkeys = tu.per_worker_keys(key, cfg.n_workers)
        _, grads = stacked_grads(loss_fn, params, anchor, wkeys)
        v0 = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        return _zeros_like_f32(params), {"prev_params": params,
                                         "worker_v": v0}

    def round(self, cfg, loss_fn, state, params, old_params, batch, anchor,
              keys):
        wkeys = tu.per_worker_keys(keys["grad"], cfg.n_workers)
        prev = state["prev_params"]
        alpha = self.alpha

        def one(b, kg, v_i):
            ln, gx = jax.value_and_grad(loss_fn)(params, b, kg)
            _, gp = jax.value_and_grad(loss_fn)(prev, b, kg)
            v_new = jax.tree.map(
                lambda g, vv, go: g.astype(jnp.float32)
                + (1 - alpha) * (vv - go.astype(jnp.float32)),
                gx, v_i, gp)
            return ln, v_new

        losses, v = jax.vmap(one)(batch, wkeys, state["worker_v"])
        return RoundOutput(loss=jnp.mean(losses), cand=v,
                           updates={"prev_params": params, "worker_v": v})


# ---------------------------------------------------------------------------
# Byrd-SVRG (App. B.4)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class SVRGEstimator(GradientEstimator):
    """Loopless SVRG: with prob p refresh the snapshot w <- x and the full
    worker gradients; each round worker i sends
    v_i = g_i(x, mb) - g_i(w, mb) + full_i, aggregated with RFA (geometric
    median) per Wu et al. (2020)."""
    name = "svrg"
    rng = ("bern", "grad", "attack", "agg")

    def init_extras(self, cfg, loss_fn, params, anchor, key):
        wkeys = tu.per_worker_keys(key, cfg.n_workers)
        _, fulls = stacked_grads(loss_fn, params, anchor, wkeys)
        return tu.tree_zeros_like(params), {"snapshot": params,
                                            "worker_full": fulls}

    def round(self, cfg, loss_fn, state, params, old_params, batch, anchor,
              keys):
        c_k = jax.random.bernoulli(keys["bern"], cfg.p)
        wkeys = tu.per_worker_keys(keys["grad"], cfg.n_workers)

        def refresh(_):
            _, fulls = stacked_grads(loss_fn, params, anchor, wkeys)
            return params, fulls

        def keep(_):
            return state["snapshot"], state["worker_full"]

        w, fulls = lax.cond(c_k, refresh, keep, operand=None)

        def one(b, kg, full_i):
            ln, gx = jax.value_and_grad(loss_fn)(params, b, kg)
            _, gw = jax.value_and_grad(loss_fn)(w, b, kg)
            return ln, tu.tree_add(tu.tree_sub(gx, gw), full_i)

        losses, cand = jax.vmap(one)(batch, wkeys, fulls)
        return RoundOutput(loss=jnp.mean(losses), cand=cand,
                           updates={"snapshot": w, "worker_full": fulls})


# ---------------------------------------------------------------------------
# Byz-EF21 (Rammal et al. 2023)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ByzEF21Estimator(CompressedUploadBits, GradientEstimator):
    """Byz-EF21: biased contractive compression + per-worker error feedback.

    Worker i maintains an estimate g_i of its local gradient; each round it
    uploads the compressed correction c_i = C(∇f_i(x^{k+1}) - g_i) and both
    sides update g_i <- g_i + c_i. The server robust-aggregates the
    reconstructed g_i — a Byzantine sender of arbitrary c_i is exactly an
    attack on its candidate g_i + c_i, so the engine's message phase models
    the adversary faithfully. Gradients are taken on the anchor set (the
    paper's deterministic Byz-EF21; the stochastic variant is cmfilter's
    momentum territory).

    EF21's contraction argument needs E||C(x)-x||² <= δ_C ||x||² with
    δ_C < 1 (``Compressor.contractive_delta``) — the factory rejects
    compressors without a contractive bound, since unbiasedness scaling
    (RandK's d/K) breaks the error-feedback recursion.
    """
    name = "byz_ef21"
    rng = ("grad", "q", "attack", "agg")
    update_params_first = True
    needs_contractive = True

    def init_extras(self, cfg, loss_fn, params, anchor, key):
        # g_i^0 = ∇f_i(x^0) (uncompressed init, as in EF21), then
        # g^0 = ARAgg(g_1^0, ..., g_n^0) like every other estimator here.
        k_grad, k_attack, k_agg = jax.random.split(key, 3)
        wkeys = tu.per_worker_keys(k_grad, cfg.n_workers)
        _, grads = stacked_grads(loss_fn, params, anchor, wkeys)
        g_i = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        return message_phase(cfg, k_attack, k_agg, g_i), {"worker_g": g_i}

    def round(self, cfg, loss_fn, state, params, old_params, batch, anchor,
              keys):
        n = cfg.n_workers
        wkeys = tu.per_worker_keys(keys["grad"], n)
        qkeys = tu.per_worker_keys(keys["q"], n,
                                   common=cfg.compressor.common_randomness)

        from repro.core import wire

        def one(b, kg, g_i):
            ln, g = jax.value_and_grad(loss_fn)(params, b, kg)
            return ln, jax.tree.map(lambda a, gi: a.astype(jnp.float32) - gi,
                                    g, g_i)

        losses, diffs = jax.vmap(one)(anchor, wkeys, state["worker_g"])
        metrics = {"wire_bits": jnp.float32(
            wire.tree_wire_bits(cfg.compressor, diffs))}
        if wire.wire_supported(cfg, diffs):
            cand = wire.pack_candidates(cfg.compressor, qkeys, diffs,
                                        base=state["worker_g"])
            c = wire.decoded_payload(cand)
            g_new = tu.tree_add(state["worker_g"], c)
        else:
            c = jax.vmap(
                lambda kq, t: tu.compress_tree(cfg.compressor, kq, t)
            )(qkeys, diffs)
            cand = g_new = tu.tree_add(state["worker_g"], c)
        return RoundOutput(loss=jnp.mean(losses), cand=cand,
                           updates={"worker_g": g_new}, metrics=metrics)


# ---------------------------------------------------------------------------
# compressed momentum filtering (Liu et al. 2024)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class CMFilterEstimator(CompressedUploadBits, GradientEstimator):
    """Compressed momentum filtering: worker i keeps a momentum
    m_i = (1-β) g_i(x^k) + β m_i and a server-mirrored reconstruction u_i,
    uploading only the compressed momentum difference Q(m_i - u_i); both
    sides update u_i <- u_i + Q(m_i - u_i). The robust aggregator IS the
    filter — it sees the reconstructed momenta u_i (what Byzantines can
    steer by sending arbitrary differences), and an optional server
    momentum η blends the filtered aggregate into the previous server
    direction g^k (the "server + worker momentum" of Liu et al. 2024)."""
    momentum: float = 0.9          # worker-side β
    server_momentum: float = 0.0   # server-side η (0 = plain filtering)
    name = "cmfilter"
    rng = ("grad", "q", "attack", "agg")

    def init_extras(self, cfg, loss_fn, params, anchor, key):
        z = _zeros_like_f32(params)
        zn = tu.tree_broadcast_leading(z, cfg.n_workers)
        return z, {"worker_m": zn, "worker_u": zn}

    def round(self, cfg, loss_fn, state, params, old_params, batch, anchor,
              keys):
        n = cfg.n_workers
        beta = self.momentum
        eta = self.server_momentum
        wkeys = tu.per_worker_keys(keys["grad"], n)
        qkeys = tu.per_worker_keys(keys["q"], n,
                                   common=cfg.compressor.common_randomness)

        from repro.core import wire

        def one(b, kg, m_i, u_i):
            ln, g = jax.value_and_grad(loss_fn)(params, b, kg)
            m_new = jax.tree.map(
                lambda gg, mm: (1 - beta) * gg.astype(jnp.float32)
                + beta * mm, g, m_i)
            return ln, m_new, tu.tree_sub(m_new, u_i)

        losses, m_new, diffs = jax.vmap(one)(batch, wkeys,
                                             state["worker_m"],
                                             state["worker_u"])
        metrics = {"wire_bits": jnp.float32(
            wire.tree_wire_bits(cfg.compressor, diffs))}
        if wire.wire_supported(cfg, diffs):
            cand = wire.pack_candidates(cfg.compressor, qkeys, diffs,
                                        base=state["worker_u"])
            q = wire.decoded_payload(cand)
            u_new = tu.tree_add(state["worker_u"], q)
        else:
            q = jax.vmap(
                lambda kq, t: tu.compress_tree(cfg.compressor, kq, t)
            )(qkeys, diffs)
            cand = u_new = tu.tree_add(state["worker_u"], q)
        g_prev = state["g"]

        def finalize(agg):
            g = jax.tree.map(
                lambda a, gp: (1 - eta) * a.astype(jnp.float32)
                + eta * gp.astype(jnp.float32), agg, g_prev)
            return g, {"worker_m": m_new, "worker_u": u_new}

        return RoundOutput(loss=jnp.mean(losses), cand=cand,
                           finalize=finalize, metrics=metrics)


# ---------------------------------------------------------------------------
# Byrd-SAGA over the stacked protocol (Wu et al. 2020)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class SAGAEstimator(GradientEstimator):
    """SAGA fitted to the stacked corrupt→attack→aggregate protocol: worker
    i keeps a per-sample gradient table over ITS slice of the anchor
    partition (the per-worker dataset) plus the table mean, and each round
    sends the SAGA estimate

        v_i = mean_j[ ∇f_{i,j}(x) - table_i[j] ] + mean(table_i)

    over freshly (without-replacement) sampled indices j; the candidates go
    through the engine's attack + robust aggregation unchanged. The table
    lives on the worker — it never hits the wire (``round_bits`` stays the
    dense 32d) — but it IS estimator state, so it rides the engine state
    dict through checkpoints and resume.

    REQUIRES a fixed anchor: table slot j corresponds to anchor sample j
    across rounds, so the driver must pass the same anchor every round
    (the logreg task's full per-worker dataset does; the lm TokenStream
    resamples per round, and ``RunSpec`` rejects that pairing eagerly).

    ``seed_batchable = False``: vmapping a sweep over seeds would stack the
    (n, m, d) tables into (seeds, n, m, d) — a silent memory blow-up on
    anything beyond toy problems — so exec/batching routes SAGA cells down
    the serial / WorkerPool path instead.
    """
    batch_size: int = 16
    name = "saga"
    rng = ("grad", "attack", "agg")
    seed_batchable = False

    def init_extras(self, cfg, loss_fn, params, anchor, key):
        n = cfg.n_workers
        m = jax.tree.leaves(anchor)[0].shape[1]   # per-worker sample count

        def table_leaf(p):
            return jnp.zeros((n, m) + p.shape, jnp.float32)

        return tu.tree_zeros_like(params), {
            "worker_table": jax.tree.map(table_leaf, params),
            "worker_table_mean": tu.tree_broadcast_leading(
                _zeros_like_f32(params), n),
        }

    def round(self, cfg, loss_fn, state, params, old_params, batch, anchor,
              keys):
        table = state["worker_table"]
        m = jax.tree.leaves(table)[0].shape[1]
        b = min(int(self.batch_size), m)
        wkeys = tu.per_worker_keys(keys["grad"], cfg.n_workers)

        def one(anchor_i, kg, table_i, mean_i):
            k_idx, k_loss = jax.random.split(kg)
            idx = jax.random.permutation(k_idx, m)[:b]   # w/o replacement

            def g_of(j):
                sample = jax.tree.map(lambda a: a[j][None], anchor_i)
                return jax.value_and_grad(loss_fn)(params, sample, k_loss)

            losses, g_new = jax.vmap(g_of)(idx)                  # (b, ...)
            g_new = jax.tree.map(lambda g: g.astype(jnp.float32), g_new)
            old = jax.tree.map(lambda t: t[idx], table_i)        # (b, ...)
            v = jax.tree.map(
                lambda gn, go, tm: jnp.mean(gn - go, axis=0) + tm,
                g_new, old, mean_i)
            new_table = jax.tree.map(lambda t, gn: t.at[idx].set(gn),
                                     table_i, g_new)
            new_mean = jax.tree.map(
                lambda tm, go, gn: tm + jnp.sum(gn - go, axis=0) / m,
                mean_i, old, g_new)
            return jnp.mean(losses), v, new_table, new_mean

        losses, v, tables, means = jax.vmap(one)(
            anchor, wkeys, table, state["worker_table_mean"])
        return RoundOutput(loss=jnp.mean(losses), cand=v,
                           updates={"worker_table": tables,
                                    "worker_table_mean": means})


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def _marina_factory(cfg, **kw):
    if cfg.agg_mode == "sparse_support":
        comp = cfg.compressor
        if not (comp.common_randomness and comp.ratio is not None):
            raise ValueError(
                "agg_mode='sparse_support' needs a common-randomness RandK "
                f"compressor, got {comp.name!r}")
        return MarinaSparseEstimator(**kw)
    return MarinaEstimator(**kw)


def _ef21_factory(cfg, **kw):
    if cfg.compressor.contractive_fn is None:
        raise ValueError(
            "byz_ef21 needs a contractive compressor (topk / sign / "
            "identity — Compressor.contractive_delta must be defined): the "
            "EF21 recursion contracts the error-feedback state, and "
            f"unbiasedness scaling breaks it; got {cfg.compressor.name!r}")
    return ByzEF21Estimator(**kw)


ESTIMATORS = {
    "marina": _marina_factory,
    "sgd": lambda cfg, **kw: SGDEstimator(momentum=kw.pop("momentum", 0.0),
                                          **kw),
    "sgdm": lambda cfg, **kw: SGDEstimator(momentum=kw.pop("momentum", 0.9),
                                           **kw),
    "csgd": lambda cfg, **kw: CSGDEstimator(**kw),
    "diana": lambda cfg, **kw: DianaEstimator(**kw),
    "mvr": lambda cfg, **kw: MVREstimator(**kw),
    "svrg": lambda cfg, **kw: SVRGEstimator(**kw),
    "byz_ef21": _ef21_factory,
    "cmfilter": lambda cfg, **kw: CMFilterEstimator(**kw),
    "saga": lambda cfg, **kw: SAGAEstimator(**kw),
}

# trait view for code that must answer questions about a method WITHOUT a
# cfg in hand (exec/batching.can_batch classifies cells before building
# anything); the sparse MARINA variant shares MarinaEstimator's traits.
ESTIMATOR_CLASSES = {
    "marina": MarinaEstimator,
    "sgd": SGDEstimator,
    "sgdm": SGDEstimator,
    "csgd": CSGDEstimator,
    "diana": DianaEstimator,
    "mvr": MVREstimator,
    "svrg": SVRGEstimator,
    "byz_ef21": ByzEF21Estimator,
    "cmfilter": CMFilterEstimator,
    "saga": SAGAEstimator,
}


def needs_contractive_compressor(name: str) -> bool:
    """Whether this method rejects unbiased-Q compressors (EF21 family) —
    the ONE place drivers consult to map a generic keep-ratio onto the
    right compressor kind (topk instead of randk). Pinned to the registry
    key set by the conformance harness alongside the other traits."""
    cls = ESTIMATOR_CLASSES.get(name)
    return bool(getattr(cls, "needs_contractive", False))


def streamable(name: str) -> bool:
    """Whether this method's candidates may be computed at dispatch time and
    buffered for asynchronous aggregation (repro.serve). Fails CLOSED like
    ``seed_batchable``: unknown names answer False, so a new estimator joins
    the streaming service only by declaring ``streamable = True``."""
    cls = ESTIMATOR_CLASSES.get(name)
    return False if cls is None else bool(getattr(cls, "streamable", False))


def seed_batchable(name: str) -> bool:
    """Whether same-signature cells of this method may run as one
    vmapped-over-seeds trajectory (exec/batching). Estimators with
    per-worker tables (SAGA) opt out via ``seed_batchable = False``.

    Unknown names answer False — batching is an optimization, so the
    classifier fails CLOSED: a method registered in ``ESTIMATORS`` but
    missing from ``ESTIMATOR_CLASSES`` runs serially (correct, slower)
    instead of vmapping state the author never vetted for a seed axis.
    The conformance harness pins the two registries to the same key set,
    so the miss also fails loudly in CI.
    """
    cls = ESTIMATOR_CLASSES.get(name)
    return False if cls is None else bool(getattr(cls, "seed_batchable",
                                                  True))


def get_estimator(name: str, cfg, **kw) -> GradientEstimator:
    if name not in ESTIMATORS:
        raise KeyError(f"unknown method {name!r}; known: {sorted(ESTIMATORS)}")
    return ESTIMATORS[name](cfg, **kw)
