"""Compression operators: unbiased Q (Def. 2.2) and biased/contractive C.

Unbiased compressors map (key, x) -> x_hat with E[x_hat] = x and
E||x_hat - x||^2 <= omega ||x||^2. The ``omega`` attribute and the
``expected_density`` (zeta_Q, expected #nonzeros / floats sent) drive both the
theory-side step size and the communication accounting in the benchmarks.

Biased compressors (``top_k``, ``sign_compressor``) are *contractive*
instead: E||C(x) - x||^2 <= delta_C ||x||^2 with delta_C < 1, exposed via
``Compressor.contractive_delta(d)`` so ``core/theory.py`` can compute the
EF21-family step sizes. They are only sound inside error-feedback
estimators (Byz-EF21); plugging one into an unbiased-Q method silently
biases the estimator, which is why ``omega`` is NaN for them.

All compressors return a *dense* vector (the mathematical value the server
reconstructs). Wire-format size is reported by ``bits_per_vector`` so the
communication benchmarks (paper Fig. 8) are exact without simulating packets.

Wire formats (DESIGN.md §Wire): every registry entry either declares a
kernel-side ``wire_format`` — the payload layout ``core/wire.py`` packs and
``kernels/quantize.py`` reconstructs per (n, TILE_D) block inside the Pallas
aggregation kernels — or is explicitly ``fallback_only`` (the dense jnp
``compress`` stays the only implementation). The registry test fails CLOSED:
an entry declaring neither is a bug, like a method missing from
``seed_batchable``. ``compress`` remains the oracle in all cases; the fused
wire path must reproduce it exactly (tests/test_wire.py).

Tree boundary (pinned): compressors apply PER LEAF via
``tree_utils.compress_tree`` — TopK/RandK's k = max(int(ratio*d_leaf), 1) is
computed from each leaf's own size, never from the flat parameter count.
``bits_per_vector(d)``/``contractive_delta(d)`` therefore describe ONE
applied vector; tree-level accounting sums/maxes per-leaf values
(``theory.comm_bits_per_round(..., dims=...)``, ``theory.tree_contractive_delta``).

``common_randomness`` RandK is the beyond-paper variant (DESIGN.md §3): all
workers share the per-step key so the K coordinates coincide and the
all-gather can physically move only K values (see core/byz_vr_marina.py).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def _uniform_like(key, x):
    """U[0,1) of x's shape; chunked via scan for huge arrays so the threefry
    iota stays int32-safe (llama's stacked leaves exceed 2^31 coords)."""
    size = x.size
    chunk = 1 << 26
    if size <= chunk:
        return jax.random.uniform(key, x.shape)
    trips = -(-size // chunk)

    def body(c, i):
        return c, jax.random.uniform(jax.random.fold_in(key, i), (chunk,))

    _, us = lax.scan(body, 0, jnp.arange(trips))
    return us.reshape(-1)[:size].reshape(x.shape)


@dataclasses.dataclass(frozen=True)
class Compressor:
    name: str
    compress: Callable          # (key, x) -> dense x_hat
    omega_fn: Callable          # d -> omega
    bits_fn: Callable           # d -> bits on the wire per vector
    density_fn: Callable        # d -> expected nonzeros (zeta_Q)
    common_randomness: bool = False
    ratio: Optional[float] = None    # RandK/TopK keep-ratio
    contractive_fn: Optional[Callable] = None   # d -> delta_C in [0, 1)
    # kernel-side wire routing (core/wire.py): one of quantize.WIRE_FORMATS
    # ("sparse" | "int8" | "sign" | "bf16" | "dense32"), or None with
    # fallback_only=True for compressors that only exist as dense jnp.
    # Exactly one of (wire_format is not None, fallback_only) must hold —
    # enforced fail-closed by the conformance harness.
    wire_format: Optional[str] = None
    fallback_only: bool = False

    def omega(self, d):
        return self.omega_fn(d)

    def bits_per_vector(self, d):
        return self.bits_fn(d)

    def contractive_delta(self, d) -> Optional[float]:
        """delta_C with E||C(x) - x||^2 <= delta_C ||x||^2, or None when no
        contraction bound is known (unbiased compressors are contractive
        only after 1/(1+omega) scaling — see theory.contractive_delta).
        Per applied vector — i.e. per LEAF under ``compress_tree``; the
        tree-level bound is ``theory.tree_contractive_delta``."""
        return None if self.contractive_fn is None else self.contractive_fn(d)

    def tree_bits(self, dims) -> float:
        """Wire bits for one compressed pytree upload: Σ_leaf bits(d_leaf).
        The tree-boundary twin of ``bits_per_vector`` — matches what
        ``compress_tree``/``wire.pack_tree`` actually put on the wire."""
        return float(sum(self.bits_fn(int(d)) for d in dims))


# ---------------------------------------------------------------------------

def identity() -> Compressor:
    return Compressor(
        name="identity",
        compress=lambda key, x: x,
        omega_fn=lambda d: 0.0,
        bits_fn=lambda d: 32 * d,
        density_fn=lambda d: d,
        contractive_fn=lambda d: 0.0,    # C(x) = x: trivially contractive
        wire_format="dense32",   # no payload transform: the dense path IS
                                 # the wire, so wire routing is a no-op
    )


_MAX_UNITS = 1 << 22     # selection-unit cap: keeps RNG/sort sizes int32-safe
                         # even under a 32-way worker vmap on 1e11-param leaves


def shuffle_rounds(n: int) -> int:
    """Sort rounds ``jax.random.permutation`` makes over ``n`` items: JAX's
    ``_shuffle`` stop rule, ceil(3 ln n / ln(2^32 - 1)), computed as it does."""
    return int(np.ceil(3 * np.log(max(1, n)) / np.log(np.iinfo(np.uint32).max)))


def smallest_k(bits, k: int):
    """bool mask of the k smallest of uint32 ``bits``, ties broken by position
    (what a stable sort keeps), with no sort: a 32-step bitwise search finds
    the k-th smallest value t, one compare-and-count pass a step."""
    def step(i, t):
        c = t | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        return jnp.where(jnp.sum(bits < c, dtype=jnp.int32) < k, c, t)

    t = lax.fori_loop(0, 32, step, jnp.uint32(0))
    below = bits < t
    tie = bits == t
    room = k - jnp.sum(below, dtype=jnp.int32)
    return below | (tie & (jnp.cumsum(tie, dtype=jnp.int32) <= room))


@functools.partial(jax.jit, static_argnums=(1, 2))
def randk_mask(key, n_units: int, k_units: int):
    """bool[n_units] marking ``jax.random.permutation(key, n_units)[:k_units]``,
    bit for bit, without the last shuffle sort and without a scatter.

    Draws the bits ``_shuffle`` draws: rounds 1..R-1 sort the units by fresh
    keys as it does; round R's kept positions are the k smallest keys
    (``smallest_k``); one unstable sort of (unit << 1 | kept) by unit puts the
    kept bits back in unit order (each unit occurs once)."""
    assert 0 < k_units <= n_units < 1 << 31, (n_units, k_units)
    rounds = shuffle_rounds(n_units)
    if rounds == 0:
        return jnp.ones((n_units,), bool)
    units = jnp.arange(n_units)   # int32, as _shuffle's: XLA reuses it as the
                                  # stable sort's tie-break iota in round 1
    for _ in range(rounds - 1):
        key, sub = jax.random.split(key)
        _, units = lax.sort_key_val(
            jax.random.bits(sub, (n_units,), jnp.uint32), units)
    _, sub = jax.random.split(key)
    kept = smallest_k(jax.random.bits(sub, (n_units,), jnp.uint32), k_units)
    if rounds == 1:
        return kept
    packed = (units.astype(jnp.uint32) << 1) | kept.astype(jnp.uint32)
    return (lax.sort(packed, is_stable=False) & 1).astype(bool)


def rand_k(ratio: float = 0.1, *, common_randomness: bool = False) -> Compressor:
    """RandK sparsification: keep K = ratio*d coords, scale by d/K (unbiased).

    omega = d/K - 1 (Beznosikov et al. 2020). Wire: K values + K indices.

    For huge leaves (stacked 126-layer groups of llama3-405b: 1.1e11 coords)
    per-coordinate selection is replaced by contiguous-*block* selection
    (unit = ceil(d / 2^22) coords): still exactly unbiased with the same
    omega, int32-safe, and matches how production senders actually pack
    sparsified tensors (block-sparse wire format; cf. kernels/quantize.py).
    """
    if not (0 < ratio <= 1):
        raise ValueError(ratio)

    def compress(key, x):
        d = x.size
        shape = x.shape
        blk = max(-(-d // _MAX_UNITS), 1)
        n_units = -(-d // blk)
        k_units = max(int(ratio * n_units), 1)
        scale = n_units / k_units
        mask = randk_mask(key, n_units, k_units)
        if blk == 1:
            out = jnp.where(mask.reshape(shape), x * scale, 0)
            return out.astype(x.dtype)
        pad = n_units * blk - d
        xf = jnp.pad(x.reshape(-1), (0, pad)).reshape(n_units, blk)
        out = jnp.where(mask[:, None], xf * scale, 0)
        return out.reshape(-1)[:d].reshape(shape).astype(x.dtype)

    def _selection(d):
        """The (block, n_units, k_units) partition ``compress`` actually
        samples from — omega/bits/density are derived from the SAME
        partition so the theory-side constants and the wire accounting
        stay exact for huge (block-selected) leaves. For d <= 2^22 the
        block size is 1 and everything reduces to per-coordinate RandK."""
        blk, n_units = unit_partition(d)
        return blk, n_units, max(int(ratio * n_units), 1)

    def omega_fn(d):
        _, n_units, k_units = _selection(d)
        return n_units / k_units - 1.0

    def bits_fn(d):
        # wire: k_units dense blocks of blk fp32 values + one index per block
        blk, _, k_units = _selection(d)
        return k_units * (32 * blk + 32)

    def density_fn(d):
        blk, _, k_units = _selection(d)
        return min(k_units * blk, d)

    return Compressor(
        name=f"randk_{ratio}" + ("_cr" if common_randomness else ""),
        compress=compress,
        omega_fn=omega_fn,
        bits_fn=bits_fn,
        density_fn=density_fn,
        common_randomness=common_randomness,
        ratio=ratio,
        wire_format="sparse",
    )


def unit_partition(d: int):
    """(block_size, n_units) used by RandK's block selection — shared with
    the sparse-support aggregation path so supports line up exactly."""
    blk = max(-(-d // _MAX_UNITS), 1)
    return blk, -(-d // blk)


def top_k(ratio: float = 0.1) -> Compressor:
    """TopK magnitude sparsification — BIASED, contractive (Def. 3 of
    Beznosikov et al. 2020): keeping the K = ratio*d largest-magnitude
    coordinates unscaled gives ||C(x) - x||^2 <= (1 - K/d) ||x||^2.

    The compressor of choice for the EF21 family (Byz-EF21): the
    error-feedback state absorbs the bias, so the K kept coordinates go on
    the wire raw (K values + K indices) with NO unbiasedness scaling —
    unlike RandK there are no d/K-amplified values for Byzantines to hide
    noise in. ``omega`` is NaN: TopK must not be used where Def. 2.2
    unbiasedness is assumed.

    Tree boundary (PINNED): K is PER LEAF — ``compress_tree`` applies this
    operator to each leaf independently with k = max(int(ratio*d_leaf), 1),
    NOT one global top-k over the flattened parameter vector. Consequently
    ``contractive_delta(d)`` describes one leaf; the tree-level bound is
    the worst leaf, max_l (1 - k_l/d_l) = ``theory.tree_contractive_delta``
    (per-leaf top-k cannot beat its weakest leaf in the EF21 recursion).
    """
    if not (0 < ratio <= 1):
        raise ValueError(ratio)

    def _k(d):
        return max(int(ratio * d), 1)

    def compress(key, x):
        d = x.size
        k = _k(d)
        xf = x.reshape(-1).astype(jnp.float32)
        _, idx = lax.top_k(jnp.abs(xf), k)
        mask = jnp.zeros((d,), bool).at[idx].set(True)
        out = jnp.where(mask, xf, 0.0)
        return out.reshape(x.shape).astype(x.dtype)

    return Compressor(
        name=f"topk_{ratio}",
        compress=compress,
        omega_fn=lambda d: float("nan"),         # biased; no omega
        bits_fn=lambda d: _k(d) * (32 + 32),     # k values + k indices
        density_fn=lambda d: _k(d),
        ratio=ratio,
        contractive_fn=lambda d: 1.0 - _k(d) / d,
        wire_format="sparse",
    )


def l2_dithering(levels: int = 1) -> Compressor:
    """Random dithering / QSGD-style l2 quantization (Alistarh et al. 2017).

    q(x)_i = ||x||_2 * sign(x_i) * xi_i where xi_i is a random rounding of
    |x_i|/||x|| onto {0, 1/s, ..., 1}. Unbiased; omega <= min(d/s^2, sqrt(d)/s).
    """
    s = levels

    def compress(key, x):
        shape = x.shape
        xf = x.reshape(-1).astype(jnp.float32)
        norm = jnp.linalg.norm(xf)
        scaled = jnp.where(norm > 0, jnp.abs(xf) / jnp.maximum(norm, 1e-30), 0.0)
        u = _uniform_like(key, xf)
        level = jnp.floor(scaled * s + u)          # stochastic rounding
        out = norm * jnp.sign(xf) * level / s
        return out.reshape(shape).astype(x.dtype)

    def omega(d):
        return min(d / s**2, (d ** 0.5) / s)

    # wire: norm (32) + sign+level per coord (~(1 + log2(s+1)) bits), but a
    # coordinate is only sent when level>0: expected density s(s+sqrt(d)).
    def density(d):
        return min(s * (s + d ** 0.5), d)

    return Compressor(
        name=f"dither_s{s}",
        compress=compress,
        omega_fn=omega,
        bits_fn=lambda d: int(32 + density(d) * (2 + 32)),
        density_fn=density,
        # global-norm coupling: every tile needs ||x||_2 of the WHOLE vector
        # before any level can be decoded, which breaks one-sweep blockwise
        # reconstruction. The blockwise variant with a kernel wire is int8.
        fallback_only=True,
    )


def natural_compression() -> Compressor:
    """Natural compression (Horvath et al. 2019a): stochastic rounding of the
    magnitude to a power of two. omega = 1/8; wire = 9 bits/coord (sign+exp).
    """

    def compress(key, x):
        shape = x.shape
        xf = x.reshape(-1).astype(jnp.float32)
        mag = jnp.abs(xf)
        safe = jnp.maximum(mag, 1e-38)
        lo = jnp.floor(jnp.log2(safe))
        plo = 2.0 ** lo
        phi = plo * 2.0
        p_hi = (safe - plo) / plo                   # P(round up)
        u = _uniform_like(key, xf)
        rounded = jnp.where(u < p_hi, phi, plo)
        out = jnp.where(mag > 0, jnp.sign(xf) * rounded, 0.0)
        return out.reshape(shape).astype(x.dtype)

    return Compressor(
        name="natural",
        compress=compress,
        omega_fn=lambda d: 1.0 / 8.0,
        bits_fn=lambda d: 9 * d,
        density_fn=lambda d: d,
        # 9-bit sign+exponent words have no packed-array dtype on TPU; a
        # kernel wire would round-trip through int16 and save nothing over
        # bf16. Dense jnp stays the only implementation.
        fallback_only=True,
    )


def sign_compressor() -> Compressor:
    """sign(x)*||x||_1/d — BIASED, contractive: Cauchy–Schwarz gives
    ||C(x) - x||^2 = ||x||^2 - ||x||_1^2/d <= (1 - 1/d) ||x||^2. Serves the
    signSGD-style baselines and the EF21 family (1-bit-per-coord wire)."""

    def compress(key, x):
        xf = x.reshape(-1).astype(jnp.float32)
        scale = jnp.mean(jnp.abs(xf))
        return (jnp.sign(xf) * scale).reshape(x.shape).astype(x.dtype)

    return Compressor(
        name="sign",
        compress=compress,
        omega_fn=lambda d: float("nan"),     # not unbiased; no omega
        bits_fn=lambda d: d + 32,
        density_fn=lambda d: d,
        contractive_fn=lambda d: 1.0 - 1.0 / d,
        wire_format="sign",
    )


# ---------------------------------------------------------------------------
# kernel-native quantized wires (int8 / bf16)
# ---------------------------------------------------------------------------

INT8_BLOCK = 256       # per-block l2 norm granularity (one fp32 per block)
INT8_LEVELS = 127      # levels fit the signed-int8 payload exactly


def _int8_encode(key, x):
    """Blockwise l2-dithering onto signed int8 levels — the EXACT encoder
    shared by the jnp oracle ``compress`` and ``wire.pack_tree``, so the
    fused path reconstructs bit-identical values. Returns
    (levels (nb, B) int8, norms (nb,) f32) over zero-padded blocks."""
    xf = x.reshape(-1).astype(jnp.float32)
    d = xf.size
    pad = (-d) % INT8_BLOCK
    xb = jnp.pad(xf, (0, pad)).reshape(-1, INT8_BLOCK)
    norm = jnp.sqrt(jnp.sum(xb * xb, axis=1, keepdims=True))
    scaled = jnp.where(norm > 0, jnp.abs(xb) / jnp.maximum(norm, 1e-30), 0.0)
    u = jax.random.uniform(key, xb.shape)
    level = jnp.floor(scaled * INT8_LEVELS + u)      # <= 127: scaled <= 1
    return (jnp.sign(xb) * level).astype(jnp.int8), norm[:, 0]


def _int8_decode(levels, norms):
    """(nb, B) int8 + (nb,) f32 -> (nb*B,) f32 dequantized values."""
    out = norms[:, None] * levels.astype(jnp.float32) / INT8_LEVELS
    return out.reshape(-1)


def int8_quantization() -> Compressor:
    """Blockwise l2-dithering packed into a real int8 wire (QSGD with
    s = 127 levels per 256-coord block — Alistarh et al. 2017, blockwise).

    Unbiased with omega <= min(B/s², √B/s) = 256/127² ≈ 0.016 per block
    (blocks quantize independently, so the per-block bound is the vector
    bound). Wire: 8 bits/coord + one fp32 norm per block — the payload the
    Pallas kernels dequantize per (n, TILE_D) block (kernels/quantize.py).
    """
    s, b = INT8_LEVELS, INT8_BLOCK

    def compress(key, x):
        levels, norms = _int8_encode(key, x)
        out = _int8_decode(levels, norms)
        return out[:x.size].reshape(x.shape).astype(x.dtype)

    return Compressor(
        name="int8",
        compress=compress,
        omega_fn=lambda d: min(b / s**2, (b ** 0.5) / s),
        bits_fn=lambda d: 8 * d + 32 * (-(-d // b)),
        density_fn=lambda d: d,
        wire_format="int8",
    )


def bf16_cast() -> Compressor:
    """Deterministic bfloat16 rounding — BIASED (round-to-nearest, no
    dither), contractive with delta_C = 2^-16: the relative rounding error
    per coordinate is at most 2^-8 (8 mantissa bits incl. the hidden one),
    so ||C(x) - x||² <= 2^-16 ||x||². Wire: 16 bits/coord, the trivial
    kernel wire (the payload IS a TPU dtype). bf16 leaves pass through
    exactly."""

    def compress(key, x):
        return x.astype(jnp.bfloat16).astype(x.dtype)

    return Compressor(
        name="bf16",
        compress=compress,
        omega_fn=lambda d: float("nan"),     # deterministic rounding: biased
        bits_fn=lambda d: 16 * d,
        density_fn=lambda d: d,
        contractive_fn=lambda d: 2.0 ** -16,
        wire_format="bf16",
    )


REGISTRY = {
    "identity": identity,
    "randk": rand_k,
    "topk": top_k,
    "dither": l2_dithering,
    "natural": natural_compression,
    "sign": sign_compressor,
    "int8": int8_quantization,
    "bf16": bf16_cast,
}


def get_compressor(name: str, **kw) -> Compressor:
    return REGISTRY[name](**kw)
