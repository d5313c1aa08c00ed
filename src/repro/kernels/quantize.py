"""Pallas TPU kernels + wire formats for the one-sweep compressed pipeline.

Two layers live here:

1. ``block_quantize`` — the original fused block-wise l2-dithering quantizer
   (Def. 2.2): norm + stochastic-round + dequantize on a VMEM tile in one
   pass, with the dither noise supplied as an input so the kernel is
   deterministic and oracle-testable.

2. The WIRE layer (DESIGN.md §Wire): per-compressor payload layouts
   (``pack_*``), their jnp reconstructions (``reconstruct`` — the oracle and
   the worker-side state-update path), and the per-(n, TILE_D)-block
   in-kernel reconstruction (``recon_block``) that norm_agg/robust_agg fuse
   into their VMEM load. A reconstructed candidate is
   ``cand = base + decode(payload)`` computed per block on-chip: the dense
   (n, d) candidate matrix never exists in HBM between compress and
   aggregate. ``topk_select`` performs the TopK |x| pass on-chip (per-tile
   candidate pools in VMEM + a tiny O(T·c) final select) so even the
   SELECTION never materializes a dense sorted copy.

Formats (payloads are worker-stacked (n, ...) on the kernel side):

  sparse  — vals (n, k) leaf-dtype + idx (n, k) int32 ascending (randk keeps
            the d/k unbiasedness scaling in vals; topk values ride raw).
            In-kernel reconstruction is a windowed one-hot matmul: CSR-style
            row pointers (built once per launch by searchsorted, read from
            SMEM) bound each (worker, tile) segment, and lane-aligned value
            chunks DMA'd from HBM scatter into the tile on the MXU.
  int8    — levels (n, ceil(d/B)·B) int8 + per-block norms (n, ceil(d/B))
            f32, B = compressors.INT8_BLOCK; dequantized blockwise in VMEM.
  sign    — signs (n, d) int8 in {-1, 0, 1} + scale (n, 1) f32.
  bf16    — vals (n, d) bf16; decode is a cast.
  dense32 — no payload transform; the dense kernels already ARE the wire
            (identity compressor). Never routed through this module.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret
from repro.core.compressors import (INT8_BLOCK, INT8_LEVELS, _int8_decode,
                                    _int8_encode)


DEFAULT_TILE_D = 2048

WIRE_FORMATS = ("sparse", "int8", "sign", "bf16", "dense32")

# sparse reconstruction: value chunk width for the windowed one-hot matmul.
# Lane-aligned; (tile, CHUNK) one-hot = 2048·128·4B = 1 MiB VMEM at the
# default tile. The payload rides in HBM as (n, kp / CHUNK, CHUNK) rows and
# is DMA'd in SLAB_ROWS-row slabs: 16 rows align to the sublane tiling of
# 32- and 16-bit payloads alike.
SCATTER_CHUNK = 128
SLAB_ROWS = 16


def _quant_kernel(x_ref, u_ref, o_ref, *, levels):
    xb = x_ref[...].astype(jnp.float32)           # (TILE_D / block, block)
    ub = u_ref[...].astype(jnp.float32)
    norm = jnp.sqrt(jnp.sum(xb * xb, axis=1, keepdims=True))
    scaled = jnp.where(norm > 0, jnp.abs(xb) / jnp.maximum(norm, 1e-30), 0.0)
    level = jnp.floor(scaled * levels + ub)
    o_ref[...] = norm * jnp.sign(xb) * level / levels


@functools.partial(jax.jit, static_argnames=("levels", "block", "tile_d",
                                             "interpret"))
def block_quantize(x, u, *, levels: int = 4, block: int = 256,
                   tile_d: int = DEFAULT_TILE_D, interpret=None):
    """x, u: (d,). Returns dequantized (d,) float32. tile_d must be a
    multiple of ``block``. The vectors ride as (d / block, block) rows —
    one quantization block per row, a sublane-aligned number of rows per
    grid step — so the per-block norm is a lane reduction and no in-kernel
    reshape exists. Zero padding forms whole zero blocks (norm 0 -> 0).
    ``interpret=None`` resolves per backend (kernels/backend.py)."""
    assert tile_d % block == 0
    d = x.shape[0]
    rows = -(-(tile_d // block) // 8) * 8
    pad = (-d) % (rows * block)
    if pad:
        x = jnp.pad(x, (0, pad))
        u = jnp.pad(u, (0, pad))
    dp = d + pad
    spec = pl.BlockSpec((rows, block), lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(_quant_kernel, levels=levels),
        grid=(dp // (rows * block),),
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((dp // block, block), jnp.float32),
        name="block_quantize",
        interpret=resolve_interpret(interpret),
    )(x.reshape(-1, block), u.reshape(-1, block))
    return out.reshape(-1)[:d]


# ---------------------------------------------------------------------------
# wire descriptor
# ---------------------------------------------------------------------------

def _lane_tile(d: int, tile_d: int) -> int:
    """Lane-aligned tile, shrunk for small d (mirrors norm_agg._tile_for —
    duplicated locally so norm_agg can import this module cycle-free)."""
    return min(tile_d, max(128, -(-d // 128) * 128))


@dataclasses.dataclass(frozen=True)
class WireSrc:
    """One worker-stacked wire payload, standing in for the dense (n, d)
    candidate matrix at an aggregation-kernel call site.

    ``arrays`` is a tuple of (name, (n, ...) array) in a fixed per-format
    order; ``base`` is the reconstruction base added on-chip — (n, d) for
    per-worker EF/mirror state (byz_ef21, cmfilter), (1, d) for a shared
    server estimate (marina's g^k), or None (zero base: csgd, diana).
    ``cand_dtype`` is the candidate leaf dtype the oracle path would carry —
    decoded values and attacked values round-trip through it so fused ≡
    materialized exactly (norm_agg._prologue contract).
    """
    fmt: str
    n: int
    d: int
    arrays: tuple
    base: Optional[object] = None
    cand_dtype: object = jnp.float32


def _wiresrc_flatten(s):
    names = tuple(nm for nm, _ in s.arrays)
    return tuple(a for _, a in s.arrays) + (s.base,), (
        s.fmt, s.n, s.d, names, s.cand_dtype)


def _wiresrc_unflatten(aux, children):
    fmt, n, d, names, cd = aux
    *arrs, base = children
    return WireSrc(fmt=fmt, n=n, d=d, arrays=tuple(zip(names, arrs)),
                   base=base, cand_dtype=cd)


jax.tree_util.register_pytree_node(WireSrc, _wiresrc_flatten,
                                   _wiresrc_unflatten)


@dataclasses.dataclass(frozen=True)
class WireMeta:
    """Static per-launch reconstruction plan (hashable: rides in the traced
    kernel's closure). ``base_rows`` is 0 (no base) / 1 (shared) / n."""
    fmt: str
    n: int
    d: int
    tile: int
    base_rows: int = 0
    cand_dtype: object = jnp.float32


# ---------------------------------------------------------------------------
# worker-side packing (jnp; vmapped over workers by core/wire.py)
# ---------------------------------------------------------------------------

def topk_select(x, k: int, *, tile_d: int = DEFAULT_TILE_D, interpret=None):
    """Indices of the k largest |x| — ``lax.top_k(|x|, k)[1]`` semantics.

    Multi-tile inputs with k below a tile run the selection on-chip: a
    Pallas pass keeps each tile's top-cp candidates (cp = min(k, tile),
    lane-padded) in VMEM and writes only the (T, cp) pool; the final exact
    top-k runs on the tiny pool. Every global top-k element is inside its
    own tile's top-cp, so the pool provably contains the answer. Cross-tile
    ties of equal |x| may break differently from the dense sort (by pool
    rank, not global index). When the pool would be the whole tile the
    pass selects nothing, so the dense top-k runs directly.

    The in-kernel selection is cp rounds of max extraction over the
    (tile/128, 128) block — Mosaic lowers neither ``top_k`` nor gathers —
    each round taking the largest remaining |x| at its lowest index, which
    is ``lax.top_k``'s order.
    """
    xf = x.reshape(-1)
    d = xf.shape[0]
    tile = _lane_tile(d, tile_d)
    cp = min(tile, max(128, -(-min(k, tile) // 128) * 128))
    if d <= 2 * tile or cp == tile:
        return lax.top_k(jnp.abs(xf.astype(jnp.float32)), k)[1]
    pv, pi = topk_pool(xf, cp, tile=tile, interpret=interpret)
    _, sel = lax.top_k(pv.reshape(-1), k)
    return jnp.take(pi.reshape(-1), sel)


@functools.partial(jax.jit, static_argnames=("cp", "tile", "interpret"))
def topk_pool(xf, cp: int, *, tile: int, interpret=None):
    """The on-chip pass of ``topk_select``: each tile's cp largest |x| (in
    ``lax.top_k`` order) and their global indices, as (T, 1, cp) pools."""
    d = xf.shape[0]
    dp = -(-d // tile) * tile
    t_count = dp // tile
    rows = tile // 128
    xp = jnp.pad(xf.astype(jnp.float32), (0, dp - d)).reshape(-1, 128)

    def kern(x_ref, v_ref, i_ref):
        t = pl.program_id(0)
        gidx = (t * tile
                + 128 * lax.broadcasted_iota(jnp.int32, (rows, 128), 0)
                + lax.broadcasted_iota(jnp.int32, (rows, 128), 1))
        a = jnp.where(gidx < d, jnp.abs(x_ref[...]), -1.0)  # pad below |x|
        slot = lax.broadcasted_iota(jnp.int32, (1, cp), 1)

        def take(j, carry):
            a, vals, idxs = carry
            top = jnp.max(a)
            at = jnp.min(jnp.where(a == top, gidx, dp))
            vals = jnp.where(slot == j, top, vals)
            idxs = jnp.where(slot == j, at, idxs)
            return jnp.where(gidx == at, -jnp.inf, a), vals, idxs

        _, vals, idxs = lax.fori_loop(
            0, cp, take, (a, jnp.zeros((1, cp), jnp.float32),
                          jnp.zeros((1, cp), jnp.int32)))
        v_ref[0] = vals
        i_ref[0] = idxs

    return pl.pallas_call(
        kern,
        grid=(t_count,),
        in_specs=[pl.BlockSpec((rows, 128), lambda i: (i, 0))],
        out_specs=(pl.BlockSpec((1, 1, cp), lambda i: (i, 0, 0)),
                   pl.BlockSpec((1, 1, cp), lambda i: (i, 0, 0))),
        out_shape=(jax.ShapeDtypeStruct((t_count, 1, cp), jnp.float32),
                   jax.ShapeDtypeStruct((t_count, 1, cp), jnp.int32)),
        name="topk_pool",
        interpret=resolve_interpret(interpret),
    )(xp)


def pack_sparse(key, x, ratio: float, *, topk: bool):
    """(vals (k,) leaf-dtype, idx (k,) int32 ascending) for one leaf.

    Selection mirrors the jnp Compressor EXACTLY (same RNG call for randk,
    same |x| ordering for topk), so the fused path reproduces the oracle's
    coordinates bit-for-bit; only the layout differs.
    """
    d = x.size
    xf = x.reshape(-1)
    k = max(int(ratio * d), 1)
    if topk:
        sel = topk_select(xf, k)
        idx = jnp.sort(sel).astype(jnp.int32)
        vals = jnp.take(xf.astype(jnp.float32), idx).astype(x.dtype)
    else:
        # rand_k's block selection degenerates to per-coordinate for
        # d <= _MAX_UNITS; core/wire.py gates the sparse wire on that.
        sel = jax.random.permutation(key, d)[:k]
        idx = jnp.sort(sel).astype(jnp.int32)
        vals = (jnp.take(xf, idx) * (d / k)).astype(x.dtype)
    return {"vals": vals, "idx": idx}


def pack_int8(key, x):
    """(levels (ceil(d/B)·B,) int8, norms (ceil(d/B),) f32) for one leaf."""
    levels, norms = _int8_encode(key, x)
    return {"lev": levels.reshape(-1), "norms": norms}


def pack_sign(key, x):
    xf = x.reshape(-1).astype(jnp.float32)
    return {"signs": jnp.sign(xf).astype(jnp.int8),
            "scale": jnp.mean(jnp.abs(xf)).reshape(1)}


def pack_bf16(key, x):
    return {"vals": x.reshape(-1).astype(jnp.bfloat16)}


def decode(fmt: str, payload: dict, d: int):
    """Payload of ONE worker/leaf -> dense (d,) f32 — the jnp reconstruction
    shared by the oracle-parity tests and the worker-side state updates
    (DIANA's h, EF21's g_i, cmfilter's u). The in-kernel ``recon_block``
    must match this exactly, tile by tile."""
    if fmt == "sparse":
        out = jnp.zeros((d,), jnp.float32)
        return out.at[payload["idx"]].set(
            payload["vals"].astype(jnp.float32), mode="drop")
    if fmt == "int8":
        nb = payload["norms"].shape[0]
        return _int8_decode(payload["lev"].reshape(nb, INT8_BLOCK),
                            payload["norms"])[:d]
    if fmt == "sign":
        return payload["signs"].astype(jnp.float32) * payload["scale"][0]
    if fmt == "bf16":
        return payload["vals"].astype(jnp.float32)
    raise ValueError(fmt)


# ---------------------------------------------------------------------------
# kernel-side assembly + per-block reconstruction
# ---------------------------------------------------------------------------

def wire_tile(src: WireSrc, tile_d: int) -> int:
    """Tile for a wire launch; int8 tiles stay a multiple of the norm block
    so each tile sees whole quantization blocks."""
    t = _lane_tile(src.d, tile_d)
    if src.fmt == "int8":
        t = -(-t // INT8_BLOCK) * INT8_BLOCK
    return t


def _pad_to(a, width, fill=0):
    pad = width - a.shape[-1]
    if pad:
        a = jnp.pad(a, ((0, 0),) * (a.ndim - 1) + ((0, pad),),
                    constant_values=fill)
    return a


def wire_inputs(src: WireSrc, tile: int, dp: int):
    """Build (vals, specs, names, meta) for the aggregation kernels.

    Dense-ish payloads (int8 / sign / bf16 / base) ride as (n, tile) blocks
    like x would; the sparse wire's vals/idx stay in HBM for the kernel to
    window into, with CSR row pointers built here once by searchsorted.
    Column pads use value 0 (decode-neutral) and index sentinel dp
    (matches no tile).
    """
    n, d = src.n, src.d
    arr = dict(src.arrays)
    vals, specs, names = [], [], []

    def add(name, a, spec):
        vals.append(a)
        specs.append(spec)
        names.append(name)

    if src.fmt == "sparse":
        v, ix = arr["vals"], arr["idx"]
        slab = SLAB_ROWS * SCATTER_CHUNK
        kp = -(-v.shape[1] // slab) * slab
        v = _pad_to(v, kp).reshape(n, -1, SCATTER_CHUNK)
        ix = _pad_to(ix, kp, fill=dp)          # sentinel: outside every tile
        t_count = dp // tile
        bounds = jnp.arange(t_count + 1, dtype=jnp.int32) * tile
        starts = jax.vmap(
            lambda row: jnp.searchsorted(row, bounds).astype(jnp.int32))(ix)
        # vals/idx stay in HBM (a (n, k) stack outgrows VMEM at real d);
        # the kernel DMAs the windows it needs. Each tile's row pointers
        # ride as one whole (1, n) SMEM slab of the (T, 1, n) tables.
        hbm = pl.BlockSpec(memory_space=pl.ANY)
        ptr = pl.BlockSpec((1, 1, n), lambda i: (i, 0, 0),
                           memory_space=pltpu.SMEM)
        add("w_vals", v, hbm)
        add("w_idx", ix.reshape(n, -1, SCATTER_CHUNK), hbm)
        add("w_lo", starts[:, :-1].T.reshape(t_count, 1, n), ptr)
        add("w_hi", starts[:, 1:].T.reshape(t_count, 1, n), ptr)
    elif src.fmt == "int8":
        nb_t = tile // INT8_BLOCK
        lev = _pad_to(arr["lev"], dp)
        # (T, n, nb_t): each tile's norms are one whole trailing (n, nb_t)
        # slab, a legal block although nb_t is narrower than a lane tile
        norms = _pad_to(arr["norms"], dp // INT8_BLOCK)
        norms = norms.reshape(n, dp // tile, nb_t).transpose(1, 0, 2)
        add("w_lev", lev, pl.BlockSpec((n, tile), lambda i: (0, i)))
        add("w_norms", norms,
            pl.BlockSpec((1, n, nb_t), lambda i: (i, 0, 0)))
    elif src.fmt == "sign":
        add("w_signs", _pad_to(arr["signs"], dp),
            pl.BlockSpec((n, tile), lambda i: (0, i)))
        add("w_scale", arr["scale"].reshape(n, 1),
            pl.BlockSpec((n, 1), lambda i: (0, 0)))
    elif src.fmt == "bf16":
        add("w_bf", _pad_to(arr["vals"], dp),
            pl.BlockSpec((n, tile), lambda i: (0, i)))
    else:  # pragma: no cover — dense32 never builds a WireSrc
        raise ValueError(src.fmt)

    base_rows = 0
    if src.base is not None:
        base_rows = src.base.shape[0]
        add("w_base", _pad_to(src.base, dp),
            pl.BlockSpec((base_rows, tile), lambda i: (0, i)))

    meta = WireMeta(fmt=src.fmt, n=n, d=d, tile=tile,
                    base_rows=base_rows, cand_dtype=src.cand_dtype)
    return vals, specs, names, meta


def _recon_sparse_block(env, meta: WireMeta):
    """(n, tile) f32 payload values of the current tile, decoded from the
    CSR-windowed wire — a chunked one-hot matmul per worker over the
    lane-aligned chunks that overlap its [lo, hi) segment, so total work is
    O(n·k·tile/d + n·chunk·tile) per tile. Each chunk's slab is DMA'd from
    HBM and the chunk's row picked out by a select-reduce."""
    n, tile = meta.n, meta.tile
    ch = SCATTER_CHUNK
    lo = pl.program_id(0) * tile
    vref, iref = env["w_vals"], env["w_idx"]
    seg_lo, seg_hi = env["w_lo"], env["w_hi"]
    cols = lo + lax.broadcasted_iota(jnp.int32, (tile, ch), 0)
    lanes = lax.broadcasted_iota(jnp.int32, (1, ch), 1)
    slab_row = lax.broadcasted_iota(jnp.int32, (SLAB_ROWS, ch), 0)

    def scoped(vbuf, ibuf, out):
        for i in range(n):
            s, e = seg_lo[0, 0, i], seg_hi[0, 0, i]

            def body(c, acc, i=i, s=s, e=e):
                r0 = pl.multiple_of(c // SLAB_ROWS * SLAB_ROWS, SLAB_ROWS)
                pltpu.sync_copy(vref.at[i, pl.ds(r0, SLAB_ROWS), :], vbuf)
                pltpu.sync_copy(iref.at[i, pl.ds(r0, SLAB_ROWS), :], ibuf)
                row = slab_row == c - r0
                v = jnp.sum(jnp.where(row, vbuf[...].astype(jnp.float32),
                                      0.0), axis=0, keepdims=True)
                ix = jnp.sum(jnp.where(row, ibuf[...], 0), axis=0,
                             keepdims=True)
                pos = c * ch + lanes
                live = (pos >= s) & (pos < e)        # this row's segment
                vm = jnp.where(live, v, 0.0)
                oh = jnp.where(cols == ix, 1.0, 0.0)          # (tile, ch)
                return acc + lax.dot_general(
                    vm, oh, (((1,), (1,)), ((), ())),
                    precision=lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32)

            out[i:i + 1, :] = lax.fori_loop(
                s // ch, (e + ch - 1) // ch, body,
                jnp.zeros((1, tile), jnp.float32))
        return out[...]

    return pl.run_scoped(
        scoped, pltpu.VMEM((SLAB_ROWS, ch), vref.dtype),
        pltpu.VMEM((SLAB_ROWS, ch), jnp.int32),
        pltpu.VMEM((n, tile), jnp.float32))


def recon_block(env, meta: WireMeta):
    """The fused VMEM load: decode this tile's payload, round-trip through
    the candidate dtype (mirroring Compressor.compress's trailing astype),
    add the base, and round-trip the SUM like the oracle's leaf-dtype add.
    Returns the (n, tile) f32 candidate block."""
    if meta.fmt == "sparse":
        q = _recon_sparse_block(env, meta)
    elif meta.fmt == "int8":
        lev = env["w_lev"][...].astype(jnp.float32)       # (n, tile)
        norms = env["w_norms"][0]                          # (n, tile/B)
        # expand each block's norm over its B lanes with selects (exact;
        # a lane-splitting reshape does not lower)
        blk = lax.broadcasted_iota(jnp.int32, (1, meta.tile), 1) // INT8_BLOCK
        scale = sum(jnp.where(blk == b, norms[:, b:b + 1], 0.0)
                    for b in range(norms.shape[1]))
        q = scale * lev / INT8_LEVELS
    elif meta.fmt == "sign":
        q = env["w_signs"][...].astype(jnp.float32) * env["w_scale"][...]
    elif meta.fmt == "bf16":
        q = env["w_bf"][...].astype(jnp.float32)
    else:  # pragma: no cover
        raise ValueError(meta.fmt)
    q = q.astype(meta.cand_dtype).astype(jnp.float32)
    if meta.base_rows:
        x = q + env["w_base"][...].astype(jnp.float32)
        return x.astype(meta.cand_dtype).astype(jnp.float32)
    return q
