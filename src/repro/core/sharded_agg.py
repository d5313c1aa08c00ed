"""Beyond-paper §Perf: the non-default aggregation backends.

Two backends live here, both reachable through the engine's ``agg_mode``
dispatch (core/engine.py):

* ``all_to_all``  — distributed robust aggregation via shard_map (below).
* ``pallas``      — single-host/default-trainer dense path: every rule
                    (mean/cm/tm via kernels/robust_agg, RFA/Krum via
                    kernels/norm_agg) runs as one-HBM-sweep-per-pass Pallas
                    kernels. Zero-copy: leaves launch kernels LEAF-WISE
                    sharing one on-chip bucketing operator (no concatenated
                    (n, D) flat matrix), many tiny leaves pack into a single
                    donated preallocated flat buffer, and a kernel-fusable
                    omniscient attack (engine.message_phase) is injected in
                    the kernels' VMEM load so the attacked ``sent`` tensor
                    never hits HBM. The jnp tree path (Aggregator.tree) is
                    kept as the parity oracle.

Paper-faithful aggregation gathers every worker's full vector to every
device (GSPMD all-gather: n x d_local bytes in, n x d_local held in memory)
and each device computes the identical aggregate for its model shard.

Coordinate-wise rules (mean / CM / trimmed-mean, incl. bucketing) commute
with coordinate partitioning, so instead each device can:

  1. all_to_all: send the j-th 1/n slice of its worker's local shard to
     device row j (wire: d_local bytes per device),
  2. aggregate its slice across all n workers locally,
  3. all_gather the n aggregated slices (wire: d_local bytes).

Peak memory drops from n x d_local to ~2 x d_local and the collective bytes
from n x d_local to ~2 x d_local — an O(n) reduction on both axes.

v2 NOTE (hillclimb lesson, see EXPERIMENTS.md §Perf): the first version
flattened the whole gradient pytree to one (n, D) matrix and re-sharded it
— the re-layout all-gathers cost MORE than the aggregation saved (llama:
collective 398s -> 705s). This version maps LEAF-WISE in each leaf's native
model sharding (``cfg.grad_specs``), so the shard_map body only ever
touches local contiguous shards and the re-layout disappears.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core import tree_utils as tu
from repro.core.aggregators import (COORD_KERNEL_RULE, _bucketize_perm,
                                    coord_median, coord_trimmed_mean)


# route the per-device coordinate rule through the Pallas kernel
# (kernels/robust_agg.py): fused bucket-mean + sort in VMEM, one HBM sweep.
# None = auto: default-ON where the kernel compiles (TPU), off on CPU/GPU
# hosts where interpret-mode would only slow the rule down. Explicit
# True/False (tests, launchers) or REPRO_PALLAS_AGG=0/1 override auto.
USE_PALLAS_AGG = [None]


def use_pallas_agg() -> bool:
    """Resolve the kernel toggle: explicit setting > env var > backend."""
    if USE_PALLAS_AGG[0] is not None:
        return bool(USE_PALLAS_AGG[0])
    import os
    env = os.environ.get("REPRO_PALLAS_AGG")
    if env is not None:
        return env.strip().lower() not in ("", "0", "false", "off", "no")
    return jax.default_backend() == "tpu"


def _coord_rule(agg, y, key):
    if use_pallas_agg() and agg.rule in ("cm", "tm", "mean"):
        from repro.kernels.ops import robust_agg as pallas_agg
        rule = COORD_KERNEL_RULE[agg.rule]
        k = key if agg.bucket_size > 1 else None
        return pallas_agg(y.astype(jnp.float32), k,
                          bucket_size=max(agg.bucket_size, 1), rule=rule,
                          trim=agg.trim)
    if agg.bucket_size > 1 and agg.rule != "mean":
        perm = jax.random.permutation(key, y.shape[0])
        y = _bucketize_perm(y, perm, agg.bucket_size)
    if agg.rule == "mean":
        return jnp.mean(y, axis=0)
    if agg.rule == "cm":
        return coord_median(y)
    return coord_trimmed_mean(y, agg.trim)


def flat_rule(agg, y, key):
    """One (n, d) stack -> (d,) through the kernel backend when enabled —
    ALL five rules, norm-based included — else the jnp Aggregator path."""
    if use_pallas_agg():
        if agg.coordinatewise:
            return _coord_rule(agg, y, key)
        from repro.kernels import ops
        k = key if agg.bucket_size > 1 else None
        if agg.rule == "rfa":
            return ops.rfa_agg(y, k, bucket_size=max(agg.bucket_size, 1),
                               iters=agg.iters, eps=agg.eps)
        return ops.krum_agg(y, k, bucket_size=max(agg.bucket_size, 1),
                            n_byz=agg.n_byz)
    return agg(key, y)


def tree_aggregate_all_to_all(cfg, key, sent):
    """cfg: ByzVRMarinaConfig with .mesh, .worker_axes, .model_axis and
    .grad_specs (pytree of PartitionSpec matching the param tree, model
    sharding only). sent: stacked pytree (n, ...)."""
    mesh = cfg.mesh
    assert mesh is not None, "all_to_all mode needs cfg.mesh"
    agg = cfg.aggregator
    assert agg.coordinatewise, (
        f"{agg.rule} is not coordinate-wise; all_to_all sharding only "
        "commutes with coordinate partitioning")
    specs = cfg.grad_specs
    assert specs is not None, "all_to_all mode needs cfg.grad_specs"
    w_axes = tuple(cfg.worker_axes)
    n = cfg.n_workers
    w_spec = w_axes if len(w_axes) > 1 else w_axes[0]

    def agg_leaf(leaf, spec):
        spec_t = tuple(spec) if spec is not None else ()
        in_spec = P(w_spec, *spec_t)
        out_spec = P(*spec_t)

        def body(x, k):
            # x: (n_local=1, *local_shape) — this worker's local model shard
            xf = x.reshape(-1).astype(jnp.float32)
            dl = xf.shape[0]
            pad = (-dl) % n
            if pad:
                xf = jnp.pad(xf, (0, pad))
            xc = xf.reshape(1, n, -1)
            y = lax.all_to_all(xc, w_axes, split_axis=1, concat_axis=0,
                               tiled=True).reshape(n, -1)
            a = _coord_rule(agg, y, k)
            g = lax.all_gather(a, w_axes, axis=0, tiled=True)
            return g[:dl].reshape(x.shape[1:]).astype(x.dtype)

        return jax.shard_map(body, mesh=mesh, in_specs=(in_spec, P()),
                             out_specs=out_spec,
                             check_vma=False)(leaf, key)

    return jax.tree.map(agg_leaf, sent, specs)


# ---------------------------------------------------------------------------
# pallas dense backend (agg_mode="pallas")
# ---------------------------------------------------------------------------

# leaves narrower than one lane-tile get packed into a single flat buffer so
# the transformer's many tiny bias/scale leaves don't each pay a kernel launch
SMALL_LEAF_D = 1024

# eager-mode reuse of the small-leaf packing buffer: one preallocated (n, D)
# fp32 buffer per shape, donated to the packing jit each round so XLA writes
# the new leaves in place instead of allocating a fresh flat intermediate.
# (Inside an enclosing jit the packer is traced inline and XLA does the same
# aliasing itself.)
_PACK_CACHE: dict = {}


@functools.partial(jax.jit, donate_argnums=(0,))
def _pack_into(buf, *flats):
    off = 0
    for f in flats:
        buf = jax.lax.dynamic_update_slice(
            buf, f.astype(jnp.float32), (0, off))
        off += f.shape[1]
    return buf


def _pack_rows(flats, tag):
    """Pack [(n, d_j)] into one (n, Dp) fp32 buffer, Dp lane-aligned with a
    zeroed tail (zero columns are neutral for every rule and fused attack).

    Eagerly, the buffer is preallocated per (tag, layout) and DONATED to the
    packing jit each round, so the leaf regions are overwritten in place
    (the zero tail survives — it is outside every leaf slice) and no fresh
    (n, D) intermediate is allocated per call. ``tag`` (x/mean/std) keeps
    same-shaped buffers that are alive simultaneously within one round from
    donating each other away. Under an enclosing jit the packer body is
    traced inline and XLA aliases the update chain itself.

    Packing is fp32: sub-tile bf16 leaves lose the oracle's bf16
    quantization of fused-attack values (bounded by bf16 eps; the large-leaf
    path round-trips through the leaf dtype in the kernel prologue).
    """
    n = flats[0].shape[0]
    widths = tuple(f.shape[1] for f in flats)
    dp = -(-sum(widths) // 128) * 128
    if any(isinstance(f, jax.core.Tracer) for f in flats):
        return _pack_into.__wrapped__(jnp.zeros((n, dp), jnp.float32), *flats)
    key = (tag, n, dp, widths)
    buf = _PACK_CACHE.pop(key, None)
    if buf is None:
        buf = jnp.zeros((n, dp), jnp.float32)
    packed = _pack_into(buf, *flats)
    _PACK_CACHE[key] = packed
    return packed


# ---------------------------------------------------------------------------
# giant-n tier (n > MAX_FUSED_WORKERS): hierarchical bucket-then-aggregate
# ---------------------------------------------------------------------------

def _materialize_attack_flat(flats, dtypes, attack_ctx):
    """jnp twin of the kernel prologue (norm_agg._prologue) for the blocked
    tier: attack → candidate-dtype round-trip → mask select, on flat
    (n, d_j) fp32 views. Bitwise the same malicious values the fused kernels
    would inject (coord_apply is coordinate-wise, so flat vs tiled blocks
    see identical inputs)."""
    if attack_ctx is None or attack_ctx.fn is None or attack_ctx.mask is None:
        return flats
    n = flats[0].shape[0]
    m_l = (jax.tree.leaves(attack_ctx.means)
           if attack_ctx.means is not None else [None] * len(flats))
    s_l = (jax.tree.leaves(attack_ctx.stds)
           if attack_ctx.stds is not None else [None] * len(flats))
    keep = attack_ctx.mask.reshape(n, 1)
    out = []
    for xf, mu, sd, dt in zip(flats, m_l, s_l, dtypes):
        muf = None if mu is None else mu.reshape(1, -1).astype(jnp.float32)
        sdf = None if sd is None else sd.reshape(1, -1).astype(jnp.float32)
        v = attack_ctx.fn(xf, muf, sdf).astype(dt).astype(jnp.float32)
        out.append(jnp.where(keep, v, xf))
    return out


def _tree_aggregate_large_n(cfg, key, sent, attack_ctx, weights,
                            return_info, valid):
    """Giant-n tier of ``tree_aggregate_pallas`` (DESIGN.md §7): above
    ``norm_agg.MAX_FUSED_WORKERS`` the fused kernels' n-in-sublanes layout
    no longer holds, so the hierarchy inverts — bucket FIRST (the Alg. 2
    reduction shrinks the stack leaf-wise before any rule kernel runs, so
    no kernel ever holds the full worker axis), then run the rule:

    * coordinate rules aggregate the bucketed stack in jnp (a sort over the
      worker axis is XLA's job at this scale; the ≤64-sublane coord kernel
      does not apply);
    * RFA / Krum route back to the FUSED norm_agg drivers when the bucketed
      row count fits under MAX_FUSED_WORKERS, else to the BLOCKED drivers
      (worker-tiled Gram / distance / weighted-sum kernels) — Krum at
      n = 4096 never materializes anything that scales like n²·d.

    The kernel prologue (attack injection, guard select-zero, staleness
    weighting) is materialized in jnp first: the zero-copy fusion is a
    ≤64-worker luxury, traded here for unbounded n. Semantics are unchanged
    — ``Aggregator.tree`` / ``tree_masked`` over ``apply_attack``-style
    materialized candidates remain the parity oracle."""
    agg = cfg.aggregator
    from repro.core import aggregators as A
    from repro.kernels import norm_agg

    leaves, treedef = jax.tree.flatten(sent)
    n = leaves[0].shape[0]
    flats = [a.reshape(n, -1).astype(jnp.float32) for a in leaves]
    flats = _materialize_attack_flat(flats, [a.dtype for a in leaves],
                                     attack_ctx)
    if valid is not None:
        keep = valid.reshape(n, 1)
        # select-zero, never multiply (0·NaN = NaN) — guard contract
        flats = [jnp.where(keep, xf, 0.0) for xf in flats]
    if weights is not None:
        flats = [xf * weights.reshape(n, 1).astype(jnp.float32)
                 for xf in flats]

    bvalid = valid
    if agg.bucket_size > 1 and agg.rule != "mean":
        perm = jax.random.permutation(key, n)
        if valid is not None:
            from repro.faults.guard import masked_bucket_matrix
            w_mat, bvalid = masked_bucket_matrix(perm, n, agg.bucket_size,
                                                 valid)
            flats = [w_mat @ xf for xf in flats]
        else:
            flats = [A._bucketize_perm(xf, perm, agg.bucket_size)
                     for xf in flats]
    m = flats[0].shape[0]

    info: dict = {}
    if agg.rule in COORD_KERNEL_RULE:
        if bvalid is not None:
            fns = {"mean": lambda y: A.masked_mean(y, bvalid),
                   "cm": lambda y: A.masked_coord_median(y, bvalid),
                   "tm": lambda y: A.masked_coord_trimmed_mean(
                       y, bvalid, agg.trim)}
            outs = [fns[agg.rule](xf) for xf in flats]
        elif agg.rule == "mean":
            outs = [jnp.mean(xf, axis=0) for xf in flats]
        elif agg.rule == "cm":
            outs = [coord_median(xf) for xf in flats]
        else:
            outs = [coord_trimmed_mean(xf, agg.trim) for xf in flats]
    elif agg.rule == "rfa":
        if m <= norm_agg.MAX_FUSED_WORKERS:
            res = norm_agg.rfa_segments(flats, iters=agg.iters, eps=agg.eps,
                                        return_info=return_info,
                                        bvalid=bvalid)
        else:
            res = norm_agg.rfa_segments_blocked(
                flats, iters=agg.iters, eps=agg.eps, bvalid=bvalid,
                return_info=return_info)
        outs = res[0] if return_info else res
        if return_info:
            info = res[1]
    elif agg.rule == "krum":
        if m <= norm_agg.MAX_FUSED_WORKERS:
            res = norm_agg.krum_segments(flats, n_byz=agg.n_byz,
                                         return_info=return_info,
                                         bvalid=bvalid)
        else:
            res = norm_agg.krum_segments_blocked(
                flats, n_byz=agg.n_byz, bvalid=bvalid,
                return_info=return_info)
        outs = res[0] if return_info else res
        if return_info:
            info = res[1]
    else:  # pragma: no cover — RULES is closed
        raise ValueError(agg.rule)

    tree_out = [o.reshape(a.shape[1:]).astype(a.dtype)
                for o, a in zip(outs, leaves)]
    tree = jax.tree.unflatten(treedef, tree_out)
    return (tree, info) if return_info else tree


@dataclasses.dataclass(frozen=True)
class AttackCtx:
    """Omniscient-attack context for in-kernel injection (engine.message_phase):
    the byzantine mask plus the good workers' per-coordinate mean/std trees
    (None when the attack doesn't read them), and the static coord_apply."""
    fn: object                   # attacks.Attack.coord_apply (static)
    mask: object                 # (n,) bool
    means: object = None         # pytree like cand minus the worker axis
    stds: object = None


def _segments(leaves, attack_ctx):
    """Partition the candidate leaves into kernel launch units.

    Returns (segs, means, stds, splits): segs[j] is a 2-D (n, d_j) view —
    either one large leaf (zero-copy reshape) or the packed small-leaf
    buffer — with per-segment flattened attack stats, and splits[j] the
    [(leaf_idx, offset, size)] map back into the tree.
    """
    n = leaves[0].shape[0]
    m_leaves = (jax.tree.leaves(attack_ctx.means)
                if attack_ctx is not None and attack_ctx.means is not None
                else [None] * len(leaves))
    s_leaves = (jax.tree.leaves(attack_ctx.stds)
                if attack_ctx is not None and attack_ctx.stds is not None
                else [None] * len(leaves))
    small = [i for i, x in enumerate(leaves) if x[0].size < SMALL_LEAF_D]
    segs, means, stds, splits = [], [], [], []
    if len(small) >= 2:
        flats = [leaves[i].reshape(n, -1) for i in small]
        segs.append(_pack_rows(flats, "x"))
        means.append(None if m_leaves[small[0]] is None else _pack_rows(
            [m_leaves[i].reshape(1, -1) for i in small], "mean"))
        stds.append(None if s_leaves[small[0]] is None else _pack_rows(
            [s_leaves[i].reshape(1, -1) for i in small], "std"))
        off, sp = 0, []
        for i in small:
            sp.append((i, off, leaves[i][0].size))
            off += leaves[i][0].size
        splits.append(sp)
        packed = set(small)
    else:
        packed = set()
    for i, x in enumerate(leaves):
        if i in packed:
            continue
        segs.append(x.reshape(n, -1))
        means.append(None if m_leaves[i] is None
                     else m_leaves[i].reshape(-1))
        stds.append(None if s_leaves[i] is None else s_leaves[i].reshape(-1))
        splits.append([(i, 0, x[0].size)])
    return segs, means, stds, splits


@tu.scoped("aggregate")
def tree_aggregate_pallas(cfg, key, sent, attack_ctx=None, weights=None,
                          return_info=False, valid=None):
    """Aggregate the stacked candidate pytree through the one-sweep Pallas
    kernels — every rule, no jnp fallback, zero per-round HBM copies:

    * leaf-wise kernel launches share ONE bucketing permutation, carried
      on-chip as ``norm_agg.bucket_matrix`` (no ``x[perm]`` gather copy, no
      concatenated (n, D) flat matrix);
    * many tiny leaves pack into a single donated preallocated flat buffer;
    * RFA/Krum sum tiny per-leaf distance accumulators so their distances
      stay GLOBAL across leaves, exactly like ``Aggregator.tree`` (the jnp
      parity oracle), at 2 sweeps/Weiszfeld-iteration and 2 sweeps/Krum;
    * ``attack_ctx`` (engine.message_phase) injects the omniscient attack
      inside the kernels' VMEM load — the attacked ``sent`` tensor is never
      written to HBM;
    * ``weights`` (engine.ingest_message_phase — staleness weighting) scales
      each sent row before bucketing/rule: the (n,) scale rides as a
      diagonal composed into the on-chip ``w_mat`` operator, so the scaled
      stack is never materialized either. Semantics (the jnp oracle):
      ``aggregator.tree(key, sent * weights[:, None])``.

    ``return_info`` (repro.obs telemetry) returns ``(tree, info)`` where
    ``info`` carries the norm-rule drivers' own scoring intermediates
    (final Weiszfeld weights / Krum scores+argmin — see kernels/norm_agg);
    coordinate rules return an empty info. The aggregate is produced by the
    identical kernel calls either way.

    ``valid`` ((n,) bool, fault guard — DESIGN.md §6) switches every rule
    to its masked twin: invalid rows are select-zeroed in the kernel
    prologue, bucketing renormalizes over valid members
    (``faults.guard.masked_bucket_matrix`` rides as the on-chip operator),
    and selection/weighting tracks the valid count. ``None`` is
    byte-for-byte the unguarded launch.

    fp32 accumulation, per-leaf output dtype preserved.
    """
    agg = cfg.aggregator
    from repro.kernels import norm_agg
    from repro.kernels.robust_agg import robust_agg as coord_kernel

    leaves, treedef = jax.tree.flatten(sent)
    n = leaves[0].shape[0]
    if n > norm_agg.MAX_FUSED_WORKERS:
        # giant n: the fused kernels keep the whole worker axis in sublanes
        # (n ≤ 64); route to the hierarchical bucket-then-aggregate tier.
        return _tree_aggregate_large_n(cfg, key, sent, attack_ctx, weights,
                                       return_info, valid)
    w_mat = bvalid = None
    if valid is not None:
        if agg.bucket_size > 1 and agg.rule != "mean":
            from repro.faults.guard import masked_bucket_matrix
            perm = jax.random.permutation(key, n)
            w_mat, bvalid = masked_bucket_matrix(perm, n, agg.bucket_size,
                                                 valid)
        else:
            bvalid = valid
    elif agg.bucket_size > 1 and agg.rule != "mean":
        perm = jax.random.permutation(key, n)
        w_mat = norm_agg.bucket_matrix(perm, n, agg.bucket_size)
    if weights is not None:
        # attack first, then scale, then bucket: W_eff = W_bucket @ diag(w)
        diag = jnp.diag(weights.astype(jnp.float32))
        w_mat = diag if w_mat is None else w_mat @ diag

    attack_fn, mask = None, None
    if attack_ctx is not None:
        attack_fn, mask = attack_ctx.fn, attack_ctx.mask
    segs, means, stds, splits = _segments(leaves, attack_ctx)

    info: dict = {}
    if agg.rule in COORD_KERNEL_RULE:
        rule = COORD_KERNEL_RULE[agg.rule]
        outs = [coord_kernel(xs, w_mat, mask, mu, sd, valid, bvalid,
                             rule=rule, trim=agg.trim, attack_fn=attack_fn)
                for xs, mu, sd in zip(segs, means, stds)]
    elif agg.rule == "rfa":
        outs = norm_agg.rfa_segments(
            segs, w_mat=w_mat, mask=mask, means=means, stds=stds,
            attack_fn=attack_fn, iters=agg.iters, eps=agg.eps,
            return_info=return_info, valid=valid, bvalid=bvalid)
        if return_info:
            outs, info = outs
    elif agg.rule == "krum":
        outs = norm_agg.krum_segments(
            segs, w_mat=w_mat, mask=mask, means=means, stds=stds,
            attack_fn=attack_fn, n_byz=agg.n_byz,
            return_info=return_info, valid=valid, bvalid=bvalid)
        if return_info:
            outs, info = outs
    else:  # pragma: no cover — RULES is closed
        raise ValueError(agg.rule)

    tree_out = [None] * len(leaves)
    for out, split in zip(outs, splits):
        for i, off, sz in split:
            tree_out[i] = (out[off:off + sz]
                           .reshape(leaves[i].shape[1:])
                           .astype(leaves[i].dtype))
    tree = jax.tree.unflatten(treedef, tree_out)
    return (tree, info) if return_info else tree


@tu.scoped("aggregate")
def tree_aggregate_pallas_wire(cfg, key, wc, attack_ctx=None,
                               return_info=False, valid=None):
    """Wire twin of ``tree_aggregate_pallas``: the candidates arrive as a
    ``wire.WireCandidates`` payload and each leaf launches its kernels on a
    ``quantize.WireSrc`` — reconstruction (decode + base add), attack,
    bucketing and the rule all happen per (n, TILE_D) block in VMEM, so the
    dense (n, d) candidate matrix never exists in HBM; the sweep reads the
    wire bytes instead.

    Differences from the dense path: no tiny-leaf packing (payload layouts
    don't concatenate; each leaf keeps its own launch) and ``attack_ctx``
    carries per-leaf FLAT (d_j,) stat lists (``wire.wire_stats``) rather
    than stat trees. RFA/Krum distances stay global across leaves exactly
    like the dense path. ``valid`` guards exactly as in the dense path —
    invalid rows (``wire.payload_valid`` rejections) are select-zeroed
    post-reconstruction in the kernel prologue.
    """
    agg = cfg.aggregator
    from repro.core import wire as W
    from repro.kernels import norm_agg
    from repro.kernels.robust_agg import robust_agg as coord_kernel

    n = wc.n
    if n > norm_agg.MAX_FUSED_WORKERS:
        # giant n: the wire kernels' n-in-sublanes layout no longer holds —
        # reconstruct (densify) once and take the dense giant-n tier. The
        # wire path's per-leaf FLAT stats reshape back to the aggregate
        # shapes so the dense tier's tree-shaped AttackCtx contract holds.
        cand = W.reconstruct(wc)
        ctx = attack_ctx
        if ctx is not None and (ctx.means is not None
                                or ctx.stds is not None):
            def unflat(stats):
                return jax.tree.unflatten(wc.treedef, [
                    s.reshape(sh) for s, sh in zip(stats, wc.shapes)])
            ctx = AttackCtx(
                fn=ctx.fn, mask=ctx.mask,
                means=None if ctx.means is None else unflat(ctx.means),
                stds=None if ctx.stds is None else unflat(ctx.stds))
        return tree_aggregate_pallas(cfg, key, cand, ctx,
                                     return_info=return_info, valid=valid)
    w_mat = bvalid = None
    if valid is not None:
        if agg.bucket_size > 1 and agg.rule != "mean":
            from repro.faults.guard import masked_bucket_matrix
            perm = jax.random.permutation(key, n)
            w_mat, bvalid = masked_bucket_matrix(perm, n, agg.bucket_size,
                                                 valid)
        else:
            bvalid = valid
    elif agg.bucket_size > 1 and agg.rule != "mean":
        perm = jax.random.permutation(key, n)
        w_mat = norm_agg.bucket_matrix(perm, n, agg.bucket_size)

    attack_fn = mask = None
    means = stds = [None] * len(wc.payloads)
    if attack_ctx is not None:
        attack_fn, mask = attack_ctx.fn, attack_ctx.mask
        if attack_ctx.means is not None:
            means = list(attack_ctx.means)
        if attack_ctx.stds is not None:
            stds = list(attack_ctx.stds)

    srcs = W.wire_srcs(wc)
    info: dict = {}
    if agg.rule in COORD_KERNEL_RULE:
        rule = COORD_KERNEL_RULE[agg.rule]
        outs = [coord_kernel(src, w_mat, mask, mu, sd, valid, bvalid,
                             rule=rule, trim=agg.trim, attack_fn=attack_fn)
                for src, mu, sd in zip(srcs, means, stds)]
    elif agg.rule == "rfa":
        outs = norm_agg.rfa_segments(
            srcs, w_mat=w_mat, mask=mask, means=means, stds=stds,
            attack_fn=attack_fn, iters=agg.iters, eps=agg.eps,
            return_info=return_info, valid=valid, bvalid=bvalid)
        if return_info:
            outs, info = outs
    elif agg.rule == "krum":
        outs = norm_agg.krum_segments(
            srcs, w_mat=w_mat, mask=mask, means=means, stds=stds,
            attack_fn=attack_fn, n_byz=agg.n_byz,
            return_info=return_info, valid=valid, bvalid=bvalid)
        if return_info:
            outs, info = outs
    else:  # pragma: no cover — RULES is closed
        raise ValueError(agg.rule)

    tree_out = [out.reshape(shape).astype(dt)
                for out, shape, dt in zip(outs, wc.shapes, wc.dtypes)]
    tree = jax.tree.unflatten(wc.treedef, tree_out)
    return (tree, info) if return_info else tree
