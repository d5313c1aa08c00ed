"""Pallas TPU kernels for the norm-based aggregation rules (RFA / Krum) and
the zero-copy bucket/attack machinery shared with the coordinate kernels.

The jnp tree path (core/aggregators.py, kept as the parity oracle) re-sweeps
the full (n, d) worker stack many times per call: RFA's smoothed Weiszfeld
materializes an (n, d) diff tensor per iteration (distance pass) plus a
weighted-sum pass, and Krum's pairwise Gram adds bucketize/gram/weighted-sum
passes. These kernels bring every rule to the roofline floor of
read(n·d) + write(d) HBM traffic *per pass*:

* ``pair_gram``     — one sweep: streams (n, TILE_D) blocks and accumulates
                      the (m, m) Gram matrix in the revisited output block
                      (VMEM); the (m, m) pairwise-distance matrix (Krum
                      scoring) is sq[i]+sq[j]-2G with sq = diag(G).
* ``rfa_iter``      — one fused Weiszfeld pass: z = wᵀ·xb and the squared
                      distances ||xb_i - z||² accumulate in the SAME sweep,
                      so T smoothed-Weiszfeld iterations + the final
                      weighted sum cost T+1 sweeps total (≤ 2 per iteration)
                      instead of the jnp path's ~4 per iteration.
* ``weighted_sum``  — one sweep: Σ_i w_i · sent_i (Krum winner extraction,
                      RFA finalization; bucketing rides in the weights).

Zero-copy message phase: the Alg. 2 bucketing permutation never touches HBM
— it is carried on-chip as the tiny (nb, n) linear operator
``bucket_matrix(perm)`` (W @ x ≡ ``aggregators._bucketize_perm(x, perm)``,
stacked-mean padding of a partial last bucket included) and applied to each
(n, TILE_D) block in VMEM on the MXU. A one-hot matmul is the TPU idiom for
a sublane gather: dynamically-indexed row gathers don't vectorize on the
VPU, W rides in VMEM like SMEM-prefetched indices would, and n ≤ 64 makes
the (nb, n)·(n, TILE_D) product negligible next to the HBM stream.
Omniscient-attack injection is fused the same way: the byzantine mask
((n, 1)) and the good workers' per-coordinate mean/std (tiled like x) enter
the kernel and ``attack.coord_apply`` runs on the block in VMEM, so the
attacked ``sent`` tensor is never written to HBM either.

Grid layout matches robust_agg.py: worker axis in sublanes (n ≤ 64), TILE_D
lane-aligned, sequential 1-D grid over d so revisited output blocks
(constant index map) accumulate in VMEM across grid steps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.backend import resolve_interpret
from repro.kernels import quantize


DEFAULT_TILE_D = 2048     # (64 workers x 2048 lanes x 4B = 512 KiB in VMEM)

# the fused kernels keep the WHOLE worker axis resident in sublanes, which
# caps them at MAX_FUSED_WORKERS; callers route larger stacks to the blocked
# kernels below (worker axis tiled too — DESIGN.md §7). One threshold shared
# with the jnp oracle's blocked-Gram dispatch (core/aggregators.py, which
# imports nothing from repro — no cycle).
from repro.core.aggregators import MAX_FUSED_WORKERS  # noqa: E402

# worker tile of the blocked kernels. It is also the LANE width of the
# blocked Gram's (tile_n, tile_n) output blocks, so a multi-block Gram needs
# a multiple of 128 on the chip; a stack of at most tile_n rows is one
# full-array block, legal at any height.
DEFAULT_TILE_N = 128


# ---------------------------------------------------------------------------
# bucketing as a linear operator (the in-kernel permutation)
# ---------------------------------------------------------------------------

def bucket_matrix(perm, n: int, s: int):
    """(nb, n) fp32 W with W @ x == ``aggregators._bucketize_perm(x, perm, s)``
    (Alg. 2): W[b, j] = (#{i in bucket b : perm[i] == j} + pad_b / n) / s,
    where the partial last bucket's ``pad_b`` rows are the stacked mean
    (= (1/n) Σ_j x_j, permutation-invariant)."""
    nb = -(-n // s)
    pad = nb * s - n
    onehot = jax.nn.one_hot(perm, n, dtype=jnp.float32)        # (n, n)
    member = jax.nn.one_hot(jnp.arange(n) // s, nb,
                            dtype=jnp.float32)                 # (n, nb)
    w = member.T @ onehot                                      # (nb, n)
    if pad:
        w = w.at[nb - 1, :].add(pad / n)
    return w / s


# ---------------------------------------------------------------------------
# shared block machinery: input assembly + in-VMEM attack/bucket prologue
# ---------------------------------------------------------------------------

def _tile_for(d: int, tile_d: int) -> int:
    """Lane-aligned tile; shrink for small d so tiny leaves stay one block."""
    return min(tile_d, max(128, -(-d // 128) * 128))


def _pad_cols(a, dp):
    """Zero-pad the trailing columns. Zero is attack/bucket-neutral: every
    coord_apply maps 0-stat/0-value pad columns to 0, W @ 0 = 0, and zero
    columns contribute nothing to Gram or squared-distance accumulators."""
    pad = dp - a.shape[-1]
    if pad:
        a = jnp.pad(a, ((0, 0),) * (a.ndim - 1) + ((0, pad),))
    return a


def src_dims(x):
    """(n, d) of a kernel input — dense (n, d) array or quantize.WireSrc."""
    if isinstance(x, quantize.WireSrc):
        return x.n, x.d
    return x.shape


def _assemble(x, w_mat, mask, good_mean, good_std, tile_d, valid=None):
    """Build (vals, in_specs, names, grid, dp, wire) for the optional-input
    kernels.

    x is either the dense (n, d) stack — riding as (n, tile) blocks over a
    1-D grid — or a ``quantize.WireSrc`` whose payload arrays ride instead
    (the dense candidate matrix then never exists in HBM; the kernels
    reconstruct per block via ``_prologue``). w_mat (nb, n), mask (n, 1) and
    the RFA weights are tiny constant blocks revisited every step; mean/std
    are (1, tile) blocks tiled like x. ``valid`` (fault guard, DESIGN.md §6)
    is the (n,) row-validity mask riding like ``mask``; ``_prologue``
    select-zeroes invalid rows in VMEM so a NaN/inf row never reaches the
    bucket matmul or the rule.
    """
    n, d = src_dims(x)
    wire = None
    if isinstance(x, quantize.WireSrc):
        tile = quantize.wire_tile(x, tile_d)
        dp = -(-d // tile) * tile
        vals, specs, names, wire = quantize.wire_inputs(x, tile, dp)
    else:
        tile = _tile_for(d, tile_d)
        dp = -(-d // tile) * tile
        vals = [_pad_cols(x, dp)]
        specs = [pl.BlockSpec((n, tile), lambda i: (0, i))]
        names = ["x"]
    if w_mat is not None:
        vals.append(w_mat)
        specs.append(pl.BlockSpec(w_mat.shape, lambda i: (0, 0)))
        names.append("w_mat")
    if mask is not None:
        vals.append(mask.reshape(n, 1).astype(jnp.float32))
        specs.append(pl.BlockSpec((n, 1), lambda i: (0, 0)))
        names.append("mask")
    if valid is not None:
        vals.append(valid.reshape(n, 1).astype(jnp.float32))
        specs.append(pl.BlockSpec((n, 1), lambda i: (0, 0)))
        names.append("valid")
    for nm, stat in (("mean", good_mean), ("std", good_std)):
        if stat is not None:
            vals.append(_pad_cols(stat.reshape(1, d).astype(jnp.float32), dp))
            specs.append(pl.BlockSpec((1, tile), lambda i: (0, i)))
            names.append(nm)
    return vals, specs, names, (dp // tile,), dp, wire


def _prologue(env, attack_fn, wire=None):
    """sent = attack(x) on the block in VMEM, then xb = W @ sent (MXU).

    With ``wire`` (a quantize.WireMeta), x is first RECONSTRUCTED on-chip
    from the payload blocks (``quantize.recon_block``: decode + base add,
    candidate-dtype faithful) — the corrupt→compress→reconstruct→attack→
    bucket→aggregate chain then runs in one VMEM residency.

    The attacked values round-trip through the candidate dtype before the
    fp32 select, matching ``apply_attack``'s ``.astype(h.dtype)`` exactly —
    a bf16 candidate tree sees the same bf16-quantized malicious vectors
    whether the attack is fused or materialized.
    """
    if wire is None:
        raw = env["x"][...]
        x = raw.astype(jnp.float32)
        cand_dtype = raw.dtype
    else:
        x = quantize.recon_block(env, wire)
        cand_dtype = wire.cand_dtype
    if attack_fn is not None and "mask" in env:
        mu = env["mean"][...] if "mean" in env else None
        sd = env["std"][...] if "std" in env else None
        v = attack_fn(x, mu, sd).astype(cand_dtype).astype(jnp.float32)
        x = jnp.where(env["mask"][...] > 0.0, v, x)
    if "valid" in env:
        # fault guard (DESIGN.md §6): select-zero invalid rows — NEVER
        # multiply (0·NaN = NaN) — before the bucket matmul, so a
        # non-finite worker row cannot reach any accumulator.
        x = jnp.where(env["valid"][...] > 0.0, x, 0.0)
    if "w_mat" in env:
        x = jnp.dot(env["w_mat"][...], x, precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32)
    return x


def _split_bf16(v):
    """f32 ``v`` as hi + mid + lo, three bf16 terms that hold all of its
    24-bit significand."""
    hi = v.astype(jnp.bfloat16)
    r = v - hi.astype(jnp.float32)
    mid = r.astype(jnp.bfloat16)
    return hi, mid, (r - mid.astype(jnp.float32)).astype(jnp.bfloat16)


def _gram_dot(a, b, *, mxu: bool):
    """``a @ b.T`` of f32 blocks, f32-accurate. ``mxu`` (compiled for the
    TPU, which multiplies bf16): the six bf16 products of the split operands
    above 2^-24, smallest first, each exact in its f32 accumulator;
    ``Precision.HIGHEST`` in Mosaic keeps fewer and is off by about 2.5e-5
    relative on a 2^18-wide Gram on a v5e. Otherwise (interpret mode) the
    host's f32 dot, which is f32-accurate itself."""
    if not mxu:
        return jnp.dot(a, b.T, precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)
    ah, am, al = _split_bf16(a)
    bh, bm, bl = _split_bf16(b)

    def nt(x, y):
        return jax.lax.dot_general(x, y, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    return (((nt(al, bh) + nt(ah, bl)) + nt(am, bm))
            + (nt(am, bh) + nt(ah, bm))) + nt(ah, bh)


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

@functools.partial(jax.jit,
                   static_argnames=("attack_fn", "tile_d", "interpret"))
def pair_gram(x, w_mat=None, mask=None, good_mean=None, good_std=None,
              valid=None, *, attack_fn=None, tile_d: int = DEFAULT_TILE_D,
              interpret=None):
    """One-HBM-sweep (m, m) Gram matrix of the (attacked, bucketed) worker
    stack; m = nb when ``w_mat`` is given else n. Krum's pairwise squared
    distances are d²[i,j] = G[i,i] + G[j,j] - 2 G[i,j]."""
    n, d = src_dims(x)
    interpret = resolve_interpret(interpret)
    m = w_mat.shape[0] if w_mat is not None else n
    vals, specs, names, grid, dp, wire = _assemble(x, w_mat, mask, good_mean,
                                                   good_std, tile_d,
                                                   valid=valid)

    def kernel(*refs):
        env = dict(zip(names, refs[:-1]))
        o_ref = refs[-1]
        xb = _prologue(env, attack_fn, wire)

        @pl.when(pl.program_id(0) == 0)
        def _():
            o_ref[...] = jnp.zeros_like(o_ref)

        o_ref[...] += _gram_dot(xb, xb, mxu=not interpret)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=specs,
        out_specs=pl.BlockSpec((m, m), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((m, m), jnp.float32),
        name="pair_gram",
        interpret=interpret,
    )(*vals)


@functools.partial(jax.jit,
                   static_argnames=("attack_fn", "tile_d", "interpret"))
def rfa_iter(x, w, w_mat=None, mask=None, good_mean=None, good_std=None,
             valid=None, *, attack_fn=None, tile_d: int = DEFAULT_TILE_D,
             interpret=None):
    """One fused smoothed-Weiszfeld pass in ONE sweep of x:
    z = Σ_b w_b · xb_b written tile-wise, and sq_b = ||xb_b - z||² accumulated
    in the revisited (m, 1) output block. Returns (z (d,), sq (m,)) fp32."""
    n, d = src_dims(x)
    m = w_mat.shape[0] if w_mat is not None else n
    vals, specs, names, grid, dp, wire = _assemble(x, w_mat, mask, good_mean,
                                                   good_std, tile_d,
                                                   valid=valid)
    tile = dp // grid[0]
    vals.append(w.reshape(m, 1).astype(jnp.float32))
    specs.append(pl.BlockSpec((m, 1), lambda i: (0, 0)))
    names.append("w")

    def kernel(*refs):
        env = dict(zip(names, refs[:-2]))
        z_ref, sq_ref = refs[-2], refs[-1]
        xb = _prologue(env, attack_fn, wire)
        z = jnp.sum(xb * env["w"][...], axis=0, keepdims=True)   # (1, tile)
        z_ref[...] = z
        diff = xb - z

        @pl.when(pl.program_id(0) == 0)
        def _():
            sq_ref[...] = jnp.zeros_like(sq_ref)

        sq_ref[...] += jnp.sum(diff * diff, axis=1, keepdims=True)

    z, sq = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=specs,
        out_specs=(pl.BlockSpec((1, tile), lambda i: (0, i)),
                   pl.BlockSpec((m, 1), lambda i: (0, 0))),
        out_shape=(jax.ShapeDtypeStruct((1, dp), jnp.float32),
                   jax.ShapeDtypeStruct((m, 1), jnp.float32)),
        name="rfa_iter",
        interpret=resolve_interpret(interpret),
    )(*vals)
    return z[0, :d], sq[:, 0]


@functools.partial(jax.jit,
                   static_argnames=("attack_fn", "tile_d", "interpret"))
def weighted_sum(x, w, mask=None, good_mean=None, good_std=None, valid=None,
                 *, attack_fn=None, tile_d: int = DEFAULT_TILE_D,
                 interpret=None):
    """z = Σ_i w_i · sent_i in one sweep. Bucketing rides in the weights
    (w_eff = Wᵀ · w_bucket), so no bucketed matrix is ever formed."""
    n, d = src_dims(x)
    vals, specs, names, grid, dp, wire = _assemble(x, None, mask, good_mean,
                                                   good_std, tile_d,
                                                   valid=valid)
    tile = dp // grid[0]
    vals.append(w.reshape(n, 1).astype(jnp.float32))
    specs.append(pl.BlockSpec((n, 1), lambda i: (0, 0)))
    names.append("w")

    def kernel(*refs):
        env = dict(zip(names, refs[:-1]))
        o_ref = refs[-1]
        sent = _prologue(env, attack_fn, wire)
        o_ref[...] = jnp.sum(sent * env["w"][...], axis=0, keepdims=True)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=specs,
        out_specs=pl.BlockSpec((1, tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, dp), jnp.float32),
        name="weighted_sum",
        interpret=resolve_interpret(interpret),
    )(*vals)
    return out[0, :d]


# ---------------------------------------------------------------------------
# rule drivers over segment lists (one logical (n, Σd_j) stack, leaf-wise)
# ---------------------------------------------------------------------------
#
# A "segment" is one (n, d_j) 2-D view of the stacked candidate pytree — a
# large leaf, or the packed buffer of many tiny leaves (core/sharded_agg.py).
# Global distances sum tiny per-segment accumulators; no concatenated
# (n, D) matrix is ever built.

def rfa_segments(segs, *, w_mat=None, mask=None, means=None, stds=None,
                 attack_fn=None, iters: int = 8, eps: float = 1e-8,
                 tile_d: int = DEFAULT_TILE_D, interpret=None,
                 return_info: bool = False, valid=None, bvalid=None):
    """Smoothed Weiszfeld (Pillutla et al. 2022) with global distances across
    segments; semantics of ``Aggregator._rfa_tree``. T+1 sweeps total: the
    t-th fused pass computes z_t = w_tᵀ·xb AND the distances to z_t; uniform
    w_0 makes z_0 the (bucketed) mean, and the final weighted sum realizes
    z_T. Returns the list of per-segment (d_j,) fp32 aggregates.

    ``valid`` / ``bvalid`` (fault guard, DESIGN.md §6): worker-level rows
    are select-zeroed in the kernel prologue, and the Weiszfeld weights of
    invalid (bucketed) rows are pinned to zero every iteration — the rule's
    twin of ``Aggregator._rfa_masked``.

    ``return_info`` (repro.obs telemetry) additionally returns the rule's own
    intermediates ``{"bucket_weights": w_T, "rfa_sq": ||xb - z_T||²}`` — the
    final Weiszfeld weights and, via ONE extra fused pass, the squared
    distances of the (bucketed) rows to the output. The aggregate itself is
    computed by the identical calls either way."""
    n = src_dims(segs[0])[0]
    m = w_mat.shape[0] if w_mat is not None else n
    means = means if means is not None else [None] * len(segs)
    stds = stds if stds is not None else [None] * len(segs)
    if bvalid is not None:
        bv = bvalid.astype(jnp.float32)
        w = bv / jnp.maximum(jnp.sum(bv), 1.0)
    else:
        w = jnp.full((m,), 1.0 / m, jnp.float32)
    for _ in range(iters):
        sq = sum(rfa_iter(xs, w, w_mat, mask, mu, sd, valid,
                          attack_fn=attack_fn, tile_d=tile_d,
                          interpret=interpret)[1]
                 for xs, mu, sd in zip(segs, means, stds))
        w = 1.0 / jnp.sqrt(sq + eps)
        if bvalid is not None:
            w = jnp.where(bvalid, w, 0.0)
        w = w / jnp.maximum(jnp.sum(w), 1e-30)
    w_eff = w if w_mat is None else w @ w_mat
    outs = [weighted_sum(xs, w_eff, mask, mu, sd, valid,
                         attack_fn=attack_fn, tile_d=tile_d,
                         interpret=interpret)
            for xs, mu, sd in zip(segs, means, stds)]
    if not return_info:
        return outs
    sq_t = sum(rfa_iter(xs, w, w_mat, mask, mu, sd, valid,
                        attack_fn=attack_fn, tile_d=tile_d,
                        interpret=interpret)[1]
               for xs, mu, sd in zip(segs, means, stds))
    return outs, {"bucket_weights": w, "rfa_sq": sq_t}


def krum_select(g, n_byz: int, bvalid=None):
    """Krum scoring (Eq. 15) from an (m, m) Gram matrix — the tiny O(m²)
    jnp step between the two kernel sweeps, shared by the fused and blocked
    drivers. Returns ``(onehot, scores, best)``: the winner's selection
    one-hot over the (bucketed) rows, the per-row scores, and the argmin.

    ``bvalid`` (fault guard): invalid rows/cols leave the distance pool, the
    neighbour count tracks the valid count, and an invalid row can never be
    selected — ``Aggregator._krum_masked``'s twin."""
    m = g.shape[0]
    sq = jnp.diag(g)
    d2 = jnp.maximum(sq[:, None] + sq[None, :] - 2.0 * g, 0.0)
    d2 = d2 + jnp.diag(jnp.full((m,), jnp.inf, d2.dtype))
    if bvalid is not None:
        pair_ok = bvalid[:, None] & bvalid[None, :]
        d2 = jnp.where(pair_ok, d2, jnp.inf)
        c = jnp.sum(bvalid.astype(jnp.int32))
        kv = jnp.maximum(c - n_byz - 2, 1)
        near = jnp.arange(m)[None, :] < kv
        srt = jnp.sort(d2, axis=1)
        scores = jnp.sum(jnp.where(near, srt, 0.0), axis=1)
        scores = jnp.where(bvalid, scores, jnp.inf)
    else:
        k = max(m - n_byz - 2, 1)
        scores = jnp.sum(jnp.sort(d2, axis=1)[:, :k], axis=1)
    best = jnp.argmin(scores)
    onehot = jax.nn.one_hot(best, m, dtype=jnp.float32)
    return onehot, scores, best


def krum_segments(segs, *, w_mat=None, mask=None, means=None, stds=None,
                  attack_fn=None, n_byz: int = 1,
                  tile_d: int = DEFAULT_TILE_D, interpret=None,
                  return_info: bool = False, valid=None, bvalid=None):
    """Krum (Eq. 15) in 2 sweeps: one Gram pass (global pairwise distances),
    tiny O(m²) scoring in jnp, one weighted-sum pass extracting the winner
    (through Wᵀ when bucketed). Semantics of ``Aggregator._krum_tree``.

    ``return_info`` (repro.obs telemetry) additionally returns
    ``{"bucket_weights": onehot, "krum_scores": scores, "krum_selected":
    argmin}`` — the scoring intermediates this driver computes anyway between
    the two sweeps; the aggregate is the identical calls either way."""
    means = means if means is not None else [None] * len(segs)
    stds = stds if stds is not None else [None] * len(segs)
    g = sum(pair_gram(xs, w_mat, mask, mu, sd, valid, attack_fn=attack_fn,
                      tile_d=tile_d, interpret=interpret)
            for xs, mu, sd in zip(segs, means, stds))
    onehot, scores, best = krum_select(g, n_byz, bvalid)
    w_eff = onehot if w_mat is None else onehot @ w_mat
    outs = [weighted_sum(xs, w_eff, mask, mu, sd, valid,
                         attack_fn=attack_fn, tile_d=tile_d,
                         interpret=interpret)
            for xs, mu, sd in zip(segs, means, stds)]
    if not return_info:
        return outs
    return outs, {"bucket_weights": onehot, "krum_scores": scores,
                  "krum_selected": best}


# ---------------------------------------------------------------------------
# blocked kernels (giant n — worker axis tiled too; DESIGN.md §7)
# ---------------------------------------------------------------------------
#
# Above MAX_FUSED_WORKERS the fused layout (whole worker axis in sublanes)
# no longer holds. The blocked twins tile the worker axis as well: no VMEM
# block ever holds more than (TILE_N, TILE_D) of the stack, and no kernel
# materializes anything that scales like n² · d — the Gram matrix
# accumulates (TILE_N, TILE_N) output blocks over a d-fastest grid.
#
# Inputs here are DENSE fp32 stacks with attack / guard select-zero /
# bucketing already materialized (core/sharded_agg.py runs the jnp prologue
# for this tier — the zero-copy fusion is a ≤64-worker luxury, traded for
# unbounded n). Zero-padded worker rows carry zero weight (weighted sums),
# are sliced away (Gram / distances), or both — always neutral.

def _pad_rows(a, mp):
    """Zero-pad the leading (worker) axis to ``mp`` rows."""
    pad = mp - a.shape[0]
    if pad:
        a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
    return a


def _tile_n_for(m: int, tile_n: int) -> int:
    """Sublane-aligned worker tile; shrink for small m (one block)."""
    return min(tile_n, max(8, -(-m // 8) * 8))


@functools.partial(jax.jit,
                   static_argnames=("tile_n", "tile_d", "interpret"))
def pair_gram_blocked(x, *, tile_n: int = DEFAULT_TILE_N,
                      tile_d: int = DEFAULT_TILE_D, interpret=None):
    """(m, m) Gram of a dense (m, d) stack with BOTH axes tiled: grid
    (mi, mj, dk), d fastest, so each (tile_n, tile_n) output block
    accumulates its d-sweep in VMEM. Peak VMEM is 2·(tile_n, tile_d) input
    blocks + one (tile_n, tile_n) accumulator, independent of m and d."""
    m, d = x.shape
    interpret = resolve_interpret(interpret)
    tile = _tile_for(d, tile_d)
    dp = -(-d // tile) * tile
    tn = _tile_n_for(m, tile_n)
    mp = -(-m // tn) * tn
    xp = _pad_rows(_pad_cols(x.astype(jnp.float32), dp), mp)

    def kernel(a_ref, b_ref, o_ref):
        @pl.when(pl.program_id(2) == 0)
        def _():
            o_ref[...] = jnp.zeros_like(o_ref)

        o_ref[...] += _gram_dot(a_ref[...], b_ref[...], mxu=not interpret)

    g = pl.pallas_call(
        kernel,
        grid=(mp // tn, mp // tn, dp // tile),
        in_specs=[pl.BlockSpec((tn, tile), lambda i, j, k: (i, k)),
                  pl.BlockSpec((tn, tile), lambda i, j, k: (j, k))],
        out_specs=pl.BlockSpec((tn, tn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, mp), jnp.float32),
        name="pair_gram_blocked",
        interpret=interpret,
    )(xp, xp)
    return g[:m, :m]


@functools.partial(jax.jit,
                   static_argnames=("tile_n", "tile_d", "interpret"))
def sqdist_to_blocked(x, z, *, tile_n: int = DEFAULT_TILE_N,
                      tile_d: int = DEFAULT_TILE_D, interpret=None):
    """(m,) squared distances ||x_i − z||² of a dense (m, d) stack to z
    (d,), worker axis tiled: grid (mi, dk), d fastest, each (tile_n, 1)
    output block accumulating its d-sweep in VMEM."""
    m, d = x.shape
    tile = _tile_for(d, tile_d)
    dp = -(-d // tile) * tile
    tn = _tile_n_for(m, tile_n)
    mp = -(-m // tn) * tn
    xp = _pad_rows(_pad_cols(x.astype(jnp.float32), dp), mp)
    zp = _pad_cols(z.reshape(1, d).astype(jnp.float32), dp)

    def kernel(x_ref, z_ref, o_ref):
        @pl.when(pl.program_id(1) == 0)
        def _():
            o_ref[...] = jnp.zeros_like(o_ref)

        diff = x_ref[...] - z_ref[...]
        o_ref[...] += jnp.sum(diff * diff, axis=1, keepdims=True)

    sq = pl.pallas_call(
        kernel,
        grid=(mp // tn, dp // tile),
        in_specs=[pl.BlockSpec((tn, tile), lambda i, k: (i, k)),
                  pl.BlockSpec((1, tile), lambda i, k: (0, k))],
        out_specs=pl.BlockSpec((tn, 1), lambda i, k: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((mp, 1), jnp.float32),
        name="sqdist_to_blocked",
        interpret=resolve_interpret(interpret),
    )(xp, zp)
    return sq[:m, 0]


@functools.partial(jax.jit,
                   static_argnames=("tile_n", "tile_d", "interpret"))
def weighted_sum_blocked(x, w, *, tile_n: int = DEFAULT_TILE_N,
                         tile_d: int = DEFAULT_TILE_D, interpret=None):
    """z = Σ_i w_i · x_i over a dense (m, d) stack, worker axis tiled:
    grid (dk, mi), WORKER tiles fastest, so each (1, tile_d) output block
    accumulates its worker sweep in VMEM. Padded rows get weight 0."""
    m, d = x.shape
    tile = _tile_for(d, tile_d)
    dp = -(-d // tile) * tile
    tn = _tile_n_for(m, tile_n)
    mp = -(-m // tn) * tn
    xp = _pad_rows(_pad_cols(x.astype(jnp.float32), dp), mp)
    wp = _pad_rows(w.reshape(m, 1).astype(jnp.float32), mp)

    def kernel(x_ref, w_ref, o_ref):
        @pl.when(pl.program_id(1) == 0)
        def _():
            o_ref[...] = jnp.zeros_like(o_ref)

        o_ref[...] += jnp.sum(x_ref[...] * w_ref[...], axis=0, keepdims=True)

    out = pl.pallas_call(
        kernel,
        grid=(dp // tile, mp // tn),
        in_specs=[pl.BlockSpec((tn, tile), lambda k, i: (i, k)),
                  pl.BlockSpec((tn, 1), lambda k, i: (i, 0))],
        out_specs=pl.BlockSpec((1, tile), lambda k, i: (0, k)),
        out_shape=jax.ShapeDtypeStruct((1, dp), jnp.float32),
        name="weighted_sum_blocked",
        interpret=resolve_interpret(interpret),
    )(xp, wp)
    return out[0, :d]


# ---------------------------------------------------------------------------
# blocked rule drivers (dense segments; prologue pre-materialized)
# ---------------------------------------------------------------------------

def rfa_segments_blocked(segs, *, iters: int = 8, eps: float = 1e-8,
                         bvalid=None, tile_n: int = DEFAULT_TILE_N,
                         tile_d: int = DEFAULT_TILE_D, interpret=None,
                         return_info: bool = False):
    """Giant-n smoothed Weiszfeld over dense (m, d_j) segments with global
    distances — semantics of ``Aggregator._rfa_tree`` / ``_rfa_masked``
    (via ``bvalid``). Costs 2 blocked sweeps per iteration (weighted sum +
    distances) + 1 final, vs the fused driver's 1 + 1 — the price of a
    worker axis of unbounded size. Returns per-segment (d_j,) aggregates;
    ``return_info`` mirrors ``rfa_segments``."""
    m = segs[0].shape[0]
    kw = dict(tile_n=tile_n, tile_d=tile_d, interpret=interpret)
    if bvalid is not None:
        bv = bvalid.astype(jnp.float32)
        w = bv / jnp.maximum(jnp.sum(bv), 1.0)
    else:
        w = jnp.full((m,), 1.0 / m, jnp.float32)
    for _ in range(iters):
        zs = [weighted_sum_blocked(xs, w, **kw) for xs in segs]
        sq = sum(sqdist_to_blocked(xs, z, **kw)
                 for xs, z in zip(segs, zs))
        w = 1.0 / jnp.sqrt(sq + eps)
        if bvalid is not None:
            w = jnp.where(bvalid, w, 0.0)
        w = w / jnp.maximum(jnp.sum(w), 1e-30)
    outs = [weighted_sum_blocked(xs, w, **kw) for xs in segs]
    if not return_info:
        return outs
    sq_t = sum(sqdist_to_blocked(xs, z, **kw)
               for xs, z in zip(segs, outs))
    return outs, {"bucket_weights": w, "rfa_sq": sq_t}


def krum_segments_blocked(segs, *, n_byz: int = 1, bvalid=None,
                          tile_n: int = DEFAULT_TILE_N,
                          tile_d: int = DEFAULT_TILE_D, interpret=None,
                          return_info: bool = False):
    """Giant-n Krum over dense (m, d_j) segments: blocked Gram (global
    pairwise distances, (tile_n, tile_n) accumulation — nothing n²·d-sized
    ever exists), tiny O(m²) scoring in jnp (``krum_select``), one blocked
    weighted-sum sweep extracting the winner. Semantics of
    ``Aggregator._krum_tree`` / ``_krum_masked`` (via ``bvalid``)."""
    kw = dict(tile_n=tile_n, tile_d=tile_d, interpret=interpret)
    g = sum(pair_gram_blocked(xs, **kw) for xs in segs)
    onehot, scores, best = krum_select(g, n_byz, bvalid)
    outs = [weighted_sum_blocked(xs, onehot, **kw) for xs in segs]
    if not return_info:
        return outs
    return outs, {"bucket_weights": onehot, "krum_scores": scores,
                  "krum_selected": best}
