"""The wire protocol layer: compressed payloads from worker to kernel.

The jnp ``Compressor`` path materializes every worker's DENSE compressed
candidate — compress writes (n, d), aggregation reads (n, d) again — so
compression saves wire bytes in the story but not a single HBM byte in the
simulation. This module closes that gap for ``agg_mode="pallas"``
(DESIGN.md §Wire): estimators hand the engine a ``WireCandidates`` payload
(the actual wire bytes: sparse (vals, idx) / int8 levels / signs / bf16)
instead of a dense stacked tree, and the aggregation kernels reconstruct
``cand = base + decode(payload)`` per (n, TILE_D) block in VMEM
(``kernels/quantize.recon_block``). The corrupt→compress→reconstruct→
attack→bucket→aggregate chain then touches HBM exactly once — for the wire
bytes, not for (n, d).

Layer contract:

* ``pack_candidates``   — per (worker, leaf) packing with compress_tree's
                          exact RNG schedule (fold_in(worker_key, leaf_i)),
                          so randk supports / int8 dither coincide
                          bit-for-bit with the jnp oracle.
* ``decoded_payload``   — jnp reconstruction ≡ vmap(compress_tree): the
                          worker-/server-side state updates (DIANA's h,
                          EF21's g_i, cmfilter's u) reuse the payload
                          instead of compressing twice.
* ``reconstruct``       — dense candidate tree (base + decoded, leaf-dtype
                          arithmetic): the fallback for attacks that need
                          materialized candidates (RN) or non-pallas modes.
* ``wire_stats``        — good-worker mean/std for omniscient attacks read
                          FROM the wire: elementwise decode for dense
                          formats, flat scatter-add + gathered cross-terms
                          for sparse — never an (n, d) scatter. (One
                          documented exception: sparse payloads with a
                          non-f32 candidate dtype reconstruct densely for
                          stats, because leaf-dtype rounding of the
                          candidates cannot be expressed termwise.)
* ``wire_message_phase``— the engine's lines 9–10 over a WireCandidates:
                          fused attack + one-sweep aggregation
                          (sharded_agg.tree_aggregate_pallas_wire), with
                          dense-reconstruct fallbacks that keep trajectories
                          method-identical.

``measured_bits`` reads the semantic wire size off the packed arrays (k,
block counts, value dtypes as actually packed); the conformance harness
pins it to ``theory.comm_bits_per_round(..., dims=...)`` so the payloads
the kernels consume are exactly what the theory bills for.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import tree_utils as tu
from repro.core.compressors import _MAX_UNITS
from repro.kernels import quantize


@dataclasses.dataclass(frozen=True)
class WireCandidates:
    """A stacked candidate pytree in wire form — what estimators hand the
    engine's message phase instead of the dense (n, ...) tree.

    ``payloads[j]`` is leaf j's packed dict (each array worker-stacked,
    (n, ...)); ``base`` is None or a tuple of (rows, d_j) reconstruction
    bases (rows = n for per-worker EF/mirror state, 1 for a shared server
    estimate); ``dtypes[j]`` is the dtype the ORACLE candidate leaf would
    have (decode + base arithmetic round-trips through it);
    ``src_dtypes[j]`` is the compressed leaf's own dtype (what
    ``compress`` would return — ``decoded_payload``'s output dtype).
    """
    fmt: str
    n: int
    payloads: tuple
    base: Optional[tuple]
    treedef: object
    shapes: tuple
    dtypes: tuple
    src_dtypes: tuple


def _wc_flatten(wc):
    return (wc.payloads, wc.base), (wc.fmt, wc.n, wc.treedef, wc.shapes,
                                    wc.dtypes, wc.src_dtypes)


def _wc_unflatten(aux, children):
    fmt, n, treedef, shapes, dtypes, src_dtypes = aux
    payloads, base = children
    return WireCandidates(fmt=fmt, n=n, payloads=tuple(payloads), base=base,
                          treedef=treedef, shapes=shapes, dtypes=dtypes,
                          src_dtypes=src_dtypes)


jax.tree_util.register_pytree_node(WireCandidates, _wc_flatten, _wc_unflatten)


def _leaf_d(shape) -> int:
    return int(math.prod(shape)) if shape else 1


# ---------------------------------------------------------------------------
# routing + packing
# ---------------------------------------------------------------------------

def wire_supported(cfg, stacked=None) -> bool:
    """Whether this (cfg, candidate tree) pair routes through the fused
    wire. Static — estimators branch on it at trace time. Requires the
    pallas backend, a declared kernel wire format, and (for sparse) leaves
    inside rand_k's per-coordinate selection regime (block selection on
    >2^22-unit leaves has no kernel wire; the jnp path handles it)."""
    comp = getattr(cfg, "compressor", None)
    if comp is None or getattr(cfg, "agg_mode", None) != "pallas":
        return False
    fmt = comp.wire_format
    if fmt is None or fmt == "dense32" or comp.fallback_only:
        return False
    if fmt == "sparse" and stacked is not None:
        dims = [_leaf_d(l.shape[1:]) for l in jax.tree.leaves(stacked)]
        if any(d > _MAX_UNITS for d in dims):
            return False
    return True


def _pack_fn(compressor):
    fmt = compressor.wire_format
    if fmt == "sparse":
        # TopK is the contractive sparse operator, RandK the unbiased one —
        # the same split Compressor encodes via contractive_fn.
        return functools.partial(quantize.pack_sparse,
                                 ratio=compressor.ratio,
                                 topk=compressor.contractive_fn is not None)
    return {"int8": quantize.pack_int8, "sign": quantize.pack_sign,
            "bf16": quantize.pack_bf16}[fmt]


@tu.scoped("compress")
def pack_candidates(compressor, qkeys, stacked, *, base=None,
                    base_shared: bool = False) -> WireCandidates:
    """Pack the to-be-compressed stacked tree into its wire payload.

    RNG contract: leaf i of worker w packs under fold_in(qkeys[w], i) —
    exactly ``jax.vmap(compress_tree)(qkeys, stacked)``'s key schedule, so
    the selected supports / dither draws coincide bit-for-bit with the jnp
    oracle. ``base`` is the reconstruction base tree (stacked (n, ...), or
    unstacked with ``base_shared=True`` for a server-shared estimate).
    """
    leaves, treedef = jax.tree.flatten(stacked)
    n = leaves[0].shape[0]
    fn = _pack_fn(compressor)
    base_leaves = (jax.tree.leaves(base) if base is not None
                   else [None] * len(leaves))
    payloads, bases, shapes, dtypes, src_dtypes = [], [], [], [], []
    for i, leaf in enumerate(leaves):
        lkeys = jax.vmap(lambda k, i=i: jax.random.fold_in(k, i))(qkeys)
        payloads.append(jax.vmap(fn)(lkeys, leaf.reshape(n, -1)))
        shapes.append(leaf.shape[1:])
        src_dtypes.append(leaf.dtype)
        b = base_leaves[i]
        if b is None:
            bases.append(None)
            dtypes.append(leaf.dtype)
        else:
            bases.append(b.reshape(1 if base_shared else n, -1))
            dtypes.append(jnp.result_type(b.dtype, leaf.dtype))
    return WireCandidates(
        fmt=compressor.wire_format, n=n, payloads=tuple(payloads),
        base=None if base is None else tuple(bases), treedef=treedef,
        shapes=tuple(shapes), dtypes=tuple(dtypes),
        src_dtypes=tuple(src_dtypes))


# ---------------------------------------------------------------------------
# jnp-side views of the wire
# ---------------------------------------------------------------------------

def decoded_payload(wc: WireCandidates):
    """Stacked dense tree EQUAL to ``vmap(compress_tree)`` on the packed
    input — the worker-side state updates reuse the payload instead of
    running the compressor a second time."""
    outs = []
    for payload, shape, dt in zip(wc.payloads, wc.shapes, wc.src_dtypes):
        d = _leaf_d(shape)
        dec = jax.vmap(lambda p: quantize.decode(wc.fmt, p, d))(payload)
        outs.append(dec.astype(dt).reshape((wc.n,) + shape))
    return jax.tree.unflatten(wc.treedef, outs)


@tu.scoped("aggregate")
def reconstruct(wc: WireCandidates):
    """The dense candidate tree the oracle path would materialize:
    decode → candidate dtype → + base → candidate dtype (leaf-dtype add,
    like the estimator's own arithmetic). The RN-attack / non-pallas
    fallback, and the stats fallback for sparse non-f32 leaves."""
    outs = []
    for j, (payload, shape, dt) in enumerate(zip(wc.payloads, wc.shapes,
                                                 wc.dtypes)):
        d = _leaf_d(shape)
        dec = jax.vmap(lambda p: quantize.decode(wc.fmt, p, d))(payload)
        x = dec.astype(dt)
        if wc.base is not None:
            x = (x.astype(jnp.float32)
                 + wc.base[j].astype(jnp.float32)).astype(dt)
        outs.append(jnp.broadcast_to(x, (wc.n, d)).reshape((wc.n,) + shape))
    return jax.tree.unflatten(wc.treedef, outs)


def wire_srcs(wc: WireCandidates):
    """Per-leaf ``quantize.WireSrc`` launch inputs for the kernels."""
    srcs = []
    for j, (payload, shape, dt) in enumerate(zip(wc.payloads, wc.shapes,
                                                 wc.dtypes)):
        d = _leaf_d(shape)
        arrays = tuple((nm, a.reshape(wc.n, -1)) for nm, a in payload.items())
        srcs.append(quantize.WireSrc(
            fmt=wc.fmt, n=wc.n, d=d, arrays=arrays,
            base=None if wc.base is None else wc.base[j], cand_dtype=dt))
    return srcs


# ---------------------------------------------------------------------------
# wire-size accounting
# ---------------------------------------------------------------------------

def _semantic_bits(fmt, d, *, k=None, vbits=32, nblocks=None) -> float:
    """Bits one worker's leaf payload carries: values at their packed
    precision + 32-bit indices/norms/scale. Signs are 1 bit each — the int8
    array is the TPU-side layout, not the wire entropy."""
    if fmt == "sparse":
        return k * (vbits + 32)
    if fmt == "int8":
        return 8 * d + 32 * nblocks
    if fmt == "sign":
        return d + 32
    if fmt == "bf16":
        return 16 * d
    raise ValueError(fmt)


def measured_bits(wc: WireCandidates) -> float:
    """Semantic wire bits per worker per round, read off the PACKED arrays
    (the k / block counts / value dtypes the kernels actually consumed)."""
    total = 0.0
    for payload, shape in zip(wc.payloads, wc.shapes):
        d = _leaf_d(shape)
        if wc.fmt == "sparse":
            total += _semantic_bits(
                "sparse", d, k=payload["vals"].shape[-1],
                vbits=payload["vals"].dtype.itemsize * 8)
        elif wc.fmt == "int8":
            total += _semantic_bits("int8", d,
                                    nblocks=payload["norms"].shape[-1])
        else:
            total += _semantic_bits(wc.fmt, d)
    return float(total)


def tree_wire_bits(compressor, stacked) -> float:
    """What ``measured_bits(pack_candidates(...))`` would return, from
    static shapes alone — the dense path's metric twin, so both backends
    report the identical per-round ``wire_bits``. Falls back to the theory
    accounting (``Compressor.tree_bits``) for compressors without a kernel
    wire format."""
    fmt = compressor.wire_format
    leaves = jax.tree.leaves(stacked)
    dims = [_leaf_d(l.shape[1:]) for l in leaves]
    if fmt in (None, "dense32") or compressor.fallback_only:
        return compressor.tree_bits(dims)
    total = 0.0
    for leaf, d in zip(leaves, dims):
        if fmt == "sparse":
            total += _semantic_bits(
                "sparse", d, k=max(int(compressor.ratio * d), 1),
                vbits=jnp.dtype(leaf.dtype).itemsize * 8)
        elif fmt == "int8":
            total += _semantic_bits("int8", d,
                                    nblocks=-(-d // quantize.INT8_BLOCK))
        else:
            total += _semantic_bits(fmt, d)
    return float(total)


# ---------------------------------------------------------------------------
# omniscient-attack stats from the wire
# ---------------------------------------------------------------------------

def wire_stats(wc: WireCandidates, good_mask, sanitize: bool = False):
    """Good-worker per-coordinate (mean, std) of the candidates, as per-leaf
    FLAT (d_j,) lists — ``tree_utils.masked_mean_std`` semantics, computed
    from the wire. Dense formats decode elementwise (no scatter); sparse
    payloads use a flat scatter-add for Σ w·q plus gathered cross-terms for
    Σ w·(x-m)², so no (n, d) gather/scatter ever appears. Sparse leaves
    with a non-f32 candidate dtype reconstruct densely instead (leaf-dtype
    rounding is not termwise-expressible) — the documented fallback.

    ``sanitize`` (fault guard): select-replace masked-out rows before the
    weighted sums — a zero weight does not neutralize a fault-poisoned
    payload (0·NaN = NaN, and garbled sparse indices would scatter out of
    range). Static, so the unguarded jaxpr is unchanged."""
    g = good_mask.astype(jnp.float32)
    cnt = jnp.maximum(jnp.sum(g), 1.0)
    w = g[:, None]
    means, stds = [], []
    for j, (payload, shape, dt) in enumerate(zip(wc.payloads, wc.shapes,
                                                 wc.dtypes)):
        d = _leaf_d(shape)
        base = None if wc.base is None else wc.base[j]
        if wc.fmt != "sparse" or jnp.dtype(dt) != jnp.float32:
            dec = jax.vmap(lambda p: quantize.decode(wc.fmt, p, d))(payload)
            x = dec.astype(dt).astype(jnp.float32)
            if base is not None:
                x = ((x + base.astype(jnp.float32))
                     .astype(dt).astype(jnp.float32))
            if sanitize:
                # select-zero, not multiply: masked rows are finite again,
                # so the weighted sums below cannot see 0·NaN
                x = jnp.where(w > 0.0, x, 0.0)
            m = jnp.sum(x * w, axis=0) / cnt
            var = jnp.sum(jnp.square(x - m[None]) * w, axis=0) / cnt
        else:
            vals = payload["vals"].astype(jnp.float32)        # (n, k)
            idx = payload["idx"]                              # (n, k) int32
            if sanitize:
                ok = good_mask[:, None]
                vals = jnp.where(ok, vals, 0.0)
                idx = jnp.where(ok, idx, 0)
            fi = idx.reshape(-1)
            qsum = jnp.zeros((d,), jnp.float32).at[fi].add(
                (w * vals).reshape(-1))
            if base is None:
                m = qsum / cnt
                s2 = jnp.zeros((d,), jnp.float32).at[fi].add(
                    (w * vals * vals).reshape(-1))
                var = s2 / cnt - jnp.square(m)
            else:
                bf = base.astype(jnp.float32)                 # (rows, d)
                rows = bf.shape[0]
                bmean = (jnp.sum(bf * w, axis=0) / cnt if rows == wc.n
                         else bf[0])
                m = bmean + qsum / cnt
                db = bf - m[None]
                t1 = (jnp.sum(jnp.square(db) * w, axis=0) if rows == wc.n
                      else cnt * jnp.square(db[0]))
                bg = (jnp.take_along_axis(bf, idx, axis=1) if rows == wc.n
                      else jnp.take(bf[0], idx))              # (n, k)
                mg = jnp.take(m, idx)                         # (n, k)
                cross = jnp.zeros((d,), jnp.float32).at[fi].add(
                    (w * vals * (2.0 * (bg - mg) + vals)).reshape(-1))
                var = (t1 + cross) / cnt
        means.append(m)
        stds.append(jnp.sqrt(jnp.maximum(var, 0.0)))
    return means, stds


# ---------------------------------------------------------------------------
# the wire message phase (engine lines 9-10 over a WireCandidates)
# ---------------------------------------------------------------------------

def wire_message_phase(cfg, attack_key, agg_key, wc: WireCandidates,
                       return_info=False, return_valid=False):
    """Omniscient attack + robust aggregation over a wire payload. The
    fused path (kernel-fusable attacks, pallas backend) never materializes
    the (n, d) candidates; RN-style attacks (exact jax.random stream on the
    materialized tensor) and non-pallas modes reconstruct densely, keeping
    the trajectory identical to the Compressor-oracle path.

    ``cfg.fault_guard`` (DESIGN.md §6) adds the fail-closed decode guard:
    rows whose payload does not decode safely (``faults.guard.payload_valid``
    — non-finite floats, sparse indices outside [0, d)) are *rejected*
    before they can touch the aggregate or the omniscient attack's
    statistics. Structurally valid garbage (garbled int8 levels under finite
    norms, a replayed zero payload) passes BY DESIGN — arbitrary finite
    deviation is the robust aggregator's job. The guard branch is static
    Python; guard-off traces the pre-faults jaxpr unchanged.

    ``return_info`` (repro.obs telemetry) returns ``(agg, info)`` with the
    rule drivers' scoring intermediates; ``return_valid`` appends the final
    (n,) validity mask (None when unguarded). The aggregate itself is
    produced by the identical calls either way."""
    from repro.core import engine

    def _ret(out, valid):
        return (out, valid) if return_valid else out

    guard = bool(getattr(cfg, "fault_guard", False))
    valid = None
    if guard:
        from repro.faults import guard as fguard
        valid = fguard.payload_valid(wc)
    if cfg.agg_mode != "pallas":   # defensive: estimators gate on pallas
        sent = engine.apply_attack(cfg, attack_key, reconstruct(wc),
                                   stats_valid=valid)
        if guard:
            from repro.faults import guard as fguard
            valid = valid & fguard.finite_row_mask(sent)
        if return_info:
            if guard:
                return _ret(cfg.aggregator.tree_masked(
                    agg_key, sent, valid, return_info=True), valid)
            return _ret(cfg.aggregator.tree_traced(agg_key, sent), valid)
        return _ret(engine.aggregate(cfg, agg_key, sent, valid=valid), valid)
    from repro.core.sharded_agg import (AttackCtx, tree_aggregate_pallas,
                                        tree_aggregate_pallas_wire)
    if cfg.n_byz == 0 or cfg.attack.name in ("NA", "LF"):
        return _ret(tree_aggregate_pallas_wire(cfg, agg_key, wc,
                                               return_info=return_info,
                                               valid=valid), valid)
    if cfg.attack.coord_apply is not None:
        with jax.named_scope("attack"):
            mask = cfg.byz_mask()
            means = stds = None
            if cfg.attack.needs_mean or cfg.attack.needs_std:
                good = ~mask if valid is None else ~mask & valid
                means, stds = wire_stats(wc, good, sanitize=guard)
                if not cfg.attack.needs_std:
                    stds = None
            ctx = AttackCtx(fn=cfg.attack.coord_apply, mask=mask,
                            means=means, stds=stds)
        return _ret(tree_aggregate_pallas_wire(cfg, agg_key, wc,
                                               attack_ctx=ctx,
                                               return_info=return_info,
                                               valid=valid), valid)
    sent = engine.apply_attack(cfg, attack_key, reconstruct(wc),
                               stats_valid=valid)
    if guard:
        from repro.faults import guard as fguard
        valid = valid & fguard.finite_row_mask(sent)
    return _ret(tree_aggregate_pallas(cfg, agg_key, sent,
                                      return_info=return_info, valid=valid),
                valid)
