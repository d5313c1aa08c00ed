"""Plain building blocks shared by the configurations' references, and the
precision policies they are computed in.

A policy has two functions: ``mm(subscripts, x, w)``, the product of an
activation with a weight matrix, and ``rd(x)``, the rounding of a tensor
the program holds in its compute dtype (a layer's input and output, a
residual stream, an embedding row). The references call ``rd`` where the
program casts to the configuration's dtype, so one model function serves:

* ``"f32"``: the reference. No rounding; products at ``HIGHEST`` (on a
  TPU a float32 product otherwise runs in one bf16 pass).
* ``"fp8"``: the control, the precision below the bfloat16 the
  configurations state: every such tensor, and its gradient, rounded to
  float8 e4m3 (4 exponent, 3 mantissa bits) under one scale per tensor
  (as float8 training scales), and products of rounded operands.
* ``"bf16"``: the configuration's own precision, the same rounding to
  bfloat16: a witness that tells the program's rounding from its
  mathematics.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
# (exponent bits, mantissa bits) for ``lax.reduce_precision``: a cast down
# and back up may be removed by the compiler (it may keep excess
# precision), a reduce_precision is not
BF16 = (8, 7)
FP8 = (4, 3)
FP8_MAX = 240.0      # the largest finite e4m3 value with IEEE semantics


def _round(x, bits):
    x = x.astype(jnp.float32)
    if bits == FP8:
        amax = jnp.max(jnp.abs(x))
        scale = jnp.where(amax > 0, FP8_MAX / amax, 1.0)
        return jax.lax.reduce_precision(x * scale, *bits) / scale
    return jax.lax.reduce_precision(x, *bits)


def round_to(x, dtype):
    """``x`` (float32) rounded to ``dtype``'s precision, in float32."""
    if jnp.dtype(dtype) == jnp.float32:
        return x
    if jnp.dtype(dtype) != jnp.bfloat16:
        raise ValueError(f"no rounding for stored dtype {dtype}")
    return _round(x, BF16)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def rounded(x, bits):
    """``x`` rounded to ``bits``; its gradient is rounded the same way."""
    return _round(x, bits)


def _rounded_fwd(x, bits):
    return _round(x, bits), None


def _rounded_bwd(bits, _, g):
    return (_round(g, bits),)


rounded.defvjp(_rounded_fwd, _rounded_bwd)


@dataclasses.dataclass(frozen=True)
class Policy:
    name: str
    rd: Callable
    mm: Callable


def make_policy(precision: str) -> Policy:
    if precision == "f32":
        def rd(x):
            return x
    elif precision in ("fp8", "bf16"):
        bits = FP8 if precision == "fp8" else BF16

        def rd(x):
            return rounded(x, bits)
    else:
        raise ValueError(f"unknown reference precision {precision!r}")

    def mm(sub, x, w):
        return rd(jnp.einsum(sub, rd(x), rd(w), precision=HIGHEST))

    return Policy(precision, rd, mm)


def rms_norm(x, w, eps):
    """RMSNorm with the scale ``1 + w``."""
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w)


def next_token_xent(logits, labels):
    """Mean cross entropy over the positions whose label is >= 0."""
    mask = (labels >= 0).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, jnp.maximum(labels, 0)[..., None],
                               axis=-1)[..., 0]
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)
