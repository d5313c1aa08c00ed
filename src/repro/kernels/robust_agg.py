"""Pallas TPU kernel: fused bucketing + coordinate-wise robust aggregation.

Server-side hot spot at pod scale: aggregating n worker vectors of
d_local ≈ 1.6e9 coordinates. The fusion argument (DESIGN.md §3): the naive
jnp path materializes the bucketed (n/s, d) intermediate and the sorted
(n/s, d) tensor in HBM — 3 full HBM sweeps of the worker-stacked matrix.
This kernel streams (n, TILE_D) blocks through VMEM once: bucket-mean and
the fixed-n sorting network happen in-register; HBM traffic is exactly
read(n·d) + write(d), the roofline floor for this op.

Zero-copy message phase (norm_agg.py holds the shared machinery): the
Alg. 2 permutation rides on-chip as the (nb, n) ``bucket_matrix`` applied
to the block in VMEM (so callers never materialize ``x[perm]``), and the
omniscient attack can be injected in the same load via
``attack.coord_apply`` + mask/mean/std inputs — the attacked ``sent``
tensor never hits HBM. The legacy contiguous path (pre-permuted rows +
``bucket_size``) is kept for callers that already hold a permuted stack.

TPU adaptation: the worker axis (n ≤ norm_agg.MAX_FUSED_WORKERS = 64) lives
in the sublane dimension; TILE_D is lane-aligned (multiple of 128).
Mosaic has no ``sort`` lowering, so the rows are ordered by a fixed Batcher
odd-even merge network of elementwise ``minimum``/``maximum`` over row
slices (``_sort_rows``) — exact selection, static in n. Giant-n stacks never
reach this kernel: callers
(kernels/ops.py, core/sharded_agg.py) bucket-reduce first and run the
coordinate rule in jnp — DESIGN.md §7.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.backend import resolve_interpret
from repro.kernels.norm_agg import _assemble, _prologue, src_dims


DEFAULT_TILE_D = 2048     # (64 workers x 2048 lanes x 4B = 512 KiB in VMEM)


def _merge_sort_pairs(m: int):
    """Comparators (lo, hi) of Batcher's odd-even merge sort for m rows.

    Built for the next power of two and pruned to hi < m: every comparator
    puts the min at the lower index, so virtual +inf rows past m would
    never move and each pruned comparator is a no-op."""
    p = 1
    while p < m:
        p *= 2
    pairs = []
    t = 1
    while t < p:
        k = t
        while k >= 1:
            for j in range(k % t, p - k, 2 * k):
                for i in range(min(k, p - j - k)):
                    if (i + j) // (2 * t) == (i + j + k) // (2 * t):
                        pairs.append((i + j, i + j + k))
            k //= 2
        t *= 2
    return [(a, b) for a, b in pairs if b < m]


def _sort_rows(x):
    """Rows of the (m, tile) block in ascending order per column, as a list
    of (1, tile) rows: ``jnp.sort(x, axis=0)``, NaN sorting last. min/max
    would propagate a NaN, so the network orders NaN as +inf and the last
    (NaN count) rows of each column are set back to NaN afterwards."""
    m = x.shape[0]
    rows = [x[i:i + 1, :] for i in range(m)]
    nans = [r != r for r in rows]
    n_nan = sum(v.astype(jnp.int32) for v in nans)
    rows = [jnp.where(v, jnp.inf, r) for v, r in zip(nans, rows)]
    for a, b in _merge_sort_pairs(m):
        rows[a], rows[b] = (jnp.minimum(rows[a], rows[b]),
                            jnp.maximum(rows[a], rows[b]))
    return [jnp.where(n_nan >= m - i, jnp.nan, r) for i, r in enumerate(rows)]


def _coord_rule_block(x, *, bucket_size, rule, trim, n):
    """The coordinate rule on one in-VMEM block; contiguous Alg. 2 bucketing
    (pre-permuted rows) when ``bucket_size`` > 1."""
    if bucket_size > 1:
        # matches aggregators._bucketize_perm (Alg. 2): when n is not a
        # bucket multiple the last bucket is padded with the stacked mean,
        # so no trailing worker is silently dropped.
        nb = -(-n // bucket_size)
        pad = nb * bucket_size - n
        if pad:
            fill = jnp.broadcast_to(jnp.mean(x, axis=0, keepdims=True),
                                    (pad, x.shape[1]))
            x = jnp.concatenate([x, fill], axis=0)
        x = x.reshape(nb, bucket_size, -1).mean(axis=1)
    m = x.shape[0]
    if rule == "mean":
        return jnp.mean(x, axis=0)
    xs = _sort_rows(x)
    if rule == "median":
        if m % 2:
            return xs[m // 2][0]
        return 0.5 * (xs[m // 2 - 1][0] + xs[m // 2][0])
    if rule == "trimmed":
        t = min(trim, (m - 1) // 2)
        return sum(xs[t + 1:m - t], xs[t])[0] / (m - 2 * t)
    raise ValueError(rule)


def _masked_coord_rule_block(x, bvalid, *, rule, trim):
    """Fault-guarded coordinate rule on one in-VMEM block (DESIGN.md §6).

    ``x`` (m, tile) is already sanitized (+ W-bucketed) by ``_prologue``;
    ``bvalid`` (m, 1) marks the rows (buckets) with at least one valid
    member. Invalid rows re-fill with +inf so the sorting network pushes
    them past every real entry, and the selection ranks track the TRACED
    valid count c — the in-kernel twin of ``aggregators.masked_coord_median``
    / ``masked_coord_trimmed_mean``. Rank gathers are scalar-predicated
    selects over the sorted rows (dynamic sublane indexing doesn't
    vectorize on the VPU)."""
    m = x.shape[0]
    c = jnp.sum(bvalid.astype(jnp.int32))
    if rule == "mean":
        return jnp.sum(x, axis=0) / jnp.maximum(c, 1).astype(jnp.float32)
    xs = _sort_rows(jnp.where(bvalid > 0.0, x, jnp.inf))
    if rule == "median":
        lo = sum(jnp.where(r == (c - 1) // 2, row, 0.0)
                 for r, row in enumerate(xs))
        hi = sum(jnp.where(r == c // 2, row, 0.0)
                 for r, row in enumerate(xs))
        return (0.5 * (lo + hi))[0]
    if rule == "trimmed":
        t = jnp.minimum(trim, (c - 1) // 2)
        kept = sum(jnp.where((r >= t) & (r < c - t), row, 0.0)
                   for r, row in enumerate(xs))
        return kept[0] / jnp.maximum(c - 2 * t, 1).astype(jnp.float32)
    raise ValueError(rule)


@functools.partial(jax.jit, static_argnames=("bucket_size", "rule", "trim",
                                             "tile_d", "interpret",
                                             "attack_fn"))
def robust_agg(x, bucket_matrix=None, mask=None, good_mean=None,
               good_std=None, valid=None, bvalid=None, *,
               bucket_size: int = 1, rule: str = "median",
               trim: int = 1, tile_d: int = DEFAULT_TILE_D, interpret=None,
               attack_fn=None):
    """x: (n, d) dense stack OR a ``quantize.WireSrc`` payload -> (d,)
    aggregate, one HBM sweep (of the wire bytes, when compressed).

    Either ``bucket_matrix`` ((nb, n), from ``norm_agg.bucket_matrix`` —
    carries the random permutation + Alg. 2 bucket means on-chip) or the
    legacy ``bucket_size`` over pre-permuted rows. ``attack_fn``/``mask``/
    ``good_mean``/``good_std`` inject the omniscient attack in-kernel.
    ``valid`` ((n,), fault guard) select-zeroes invalid worker rows in the
    prologue and ``bvalid`` ((m,) over the post-bucket rows) switches the
    rule to its masked twin (``_masked_coord_rule_block``); guarded callers
    pass ``faults.guard.masked_bucket_matrix`` as ``bucket_matrix``.
    ``interpret=None`` resolves per backend (kernels/backend.py).
    """
    n, d = src_dims(x)
    vals, specs, names, grid, dp, wire = _assemble(x, bucket_matrix, mask,
                                                   good_mean, good_std,
                                                   tile_d, valid=valid)
    tile = dp // grid[0]
    contiguous = bucket_size if bucket_matrix is None else 1
    if bvalid is not None:
        m = bucket_matrix.shape[0] if bucket_matrix is not None else n
        vals.append(bvalid.reshape(m, 1).astype(jnp.float32))
        specs.append(pl.BlockSpec((m, 1), lambda i: (0, 0)))
        names.append("bvalid")

    def kernel(*refs):
        env = dict(zip(names, refs[:-1]))
        o_ref = refs[-1]
        xb = _prologue(env, attack_fn, wire)    # attacked (+W-bucketed)
        if "bvalid" in env:
            o_ref[...] = _masked_coord_rule_block(xb, env["bvalid"][...],
                                                  rule=rule, trim=trim)
        else:
            o_ref[...] = _coord_rule_block(xb, bucket_size=contiguous,
                                           rule=rule, trim=trim, n=n)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=specs,
        out_specs=pl.BlockSpec((tile,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((dp,), jnp.float32),
        name="robust_agg",
        interpret=resolve_interpret(interpret),
    )(*vals)
    return out[:d]
